"""PR 26's additions to the benchmark: the ``deepseek`` family as files only
(a configuration, a mix, a cell, a reference, six readers, two kernels), the published sizes, and the latent readers' arithmetic."""

import gzip
import json
import os

import pytest

import cellspec
import run as runner
from conftest import TINY_GPT2, TINY_POOL

MOONLIGHT = "moonlight-16b-a3b-d8.reason-pool"
FIXTURES = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                        "fixtures", "spans")

TINY_DEEPSEEK = {
    "source": "test", "family": "deepseek", "config_class": "DeepseekConfig",
    "fields": {"vocab_size": "vocab_size", "hidden_size": "hidden_size",
               "intermediate_size": "intermediate_size",
               "moe_intermediate_size": "moe_intermediate_size",
               "num_layers": "num_hidden_layers",
               "num_heads": "num_attention_heads",
               "kv_lora_rank": "kv_lora_rank",
               "qk_nope_head_dim": "qk_nope_head_dim",
               "qk_rope_head_dim": "qk_rope_head_dim",
               "v_head_dim": "v_head_dim",
               "num_experts": "n_routed_experts",
               "num_shared_experts": "n_shared_experts",
               "top_k": "num_experts_per_tok",
               "first_k_dense": "first_k_dense_replace",
               "max_seq_len": "max_position_embeddings"},
    "vocab_size": 256, "hidden_size": 64, "intermediate_size": 96,
    "moe_intermediate_size": 48, "num_hidden_layers": 3,
    "num_attention_heads": 2, "kv_lora_rank": 32, "qk_nope_head_dim": 16,
    "qk_rope_head_dim": 16, "v_head_dim": 16, "n_routed_experts": 8,
    "n_shared_experts": 1, "num_experts_per_tok": 3,
    "first_k_dense_replace": 1, "max_position_embeddings": 2048,
    "reduced": [],
    "serve": {**TINY_GPT2["serve"],
              # 3 of 8 sigmoid scores flip under bf16 on a 64-wide model
              "check": {"match_rate_min": 0.5}},
}


def test_the_new_cell_resolves_with_its_traffic_as_the_issue_wrote_it():
    spec = cellspec.resolve(MOONLIGHT)
    assert spec["chips"] == 1 and spec["cell"] == {"clients": 128}
    mix = spec["mix"]
    assert mix["kind"] == "closed_loop" and mix["stream"] is False
    assert mix["prompt_tokens"] == {"dist": "lognormal", "median": 512,
                                    "sigma": 0.7, "min": 64, "max": 3072}
    assert mix["output_tokens"] == {"dist": "uniform", "min": 512, "max": 1024}
    assert (mix["total_tokens_max"], mix["lead_seconds"], mix["grace_seconds"],
            mix["warm_requests"], mix["warm_max_tokens"]) == (4096, 24, 35, 4, 8)
    engine = spec["config"]["serve"]["engine"]
    assert engine == {"block_size": 128, "num_blocks": 2049, "max_seqs": 128,
                      "max_tokens_per_step": 512, "max_blocks_per_seq": 32,
                      "prefill_tile": 128}
    assert mix["total_tokens_max"] == engine["block_size"] * engine["max_blocks_per_seq"]
    e2e = {m["name"] for m in spec["end_to_end"]}
    assert e2e == {"serve_tokens_per_s", "setup_s"}
    names = {m["name"] for m in spec["per_layer"]}
    assert {"kernel.mla_decode_roofline", "kernel.mla_prefill_roofline",
            "kernel.mla_decode_share", "kernel.mla_prefill_share",
            "model.mla_step_roofline_kv", "sched.pool_decode_step_ms_p50",
            "sched.mixed_step_ms_p50", "sched.cold_dispatches"} <= names
    # host_spans.attention_geometry reckons K and V heads: not for a latent row
    assert not names & {"model.step_roofline_kv", "kernel.paged_decode_roofline",
                        "kernel.tiled_prefill_roofline",
                        "kernel.paged_decode_share", "kernel.tiled_prefill_share"}


def test_the_configuration_is_the_catalog_entry_but_for_its_depth():
    conf = cellspec.resolve(MOONLIGHT)["config"]
    assert conf["reduced"] == ["num_hidden_layers"]
    published = {
        "attention_bias": False, "ep_size": 1, "first_k_dense_replace": 1,
        "hidden_act": "silu", "hidden_size": 2048, "intermediate_size": 11264,
        "kv_lora_rank": 512, "max_position_embeddings": 8192,
        "model_type": "deepseek_v3", "moe_intermediate_size": 1408,
        "moe_layer_freq": 1, "n_group": 1, "n_routed_experts": 64,
        "n_shared_experts": 2, "norm_topk_prob": True,
        "num_attention_heads": 16, "num_experts_per_tok": 6,
        "num_hidden_layers": 27, "num_key_value_heads": 16,
        "num_nextn_predict_layers": 0, "q_lora_rank": None,
        "qk_nope_head_dim": 128, "qk_rope_head_dim": 64, "rms_norm_eps": 1e-05,
        "rope_theta": 50000, "routed_scaling_factor": 2.446,
        "scoring_func": "sigmoid", "seq_aux": True,
        "tie_word_embeddings": False, "topk_group": 1,
        "topk_method": "noaux_tc", "v_head_dim": 128, "vocab_size": 163840}
    differs = {k for k, v in published.items() if conf.get(k, "absent") != v}
    assert differs == {"num_hidden_layers"} and conf["num_hidden_layers"] == 8


def test_the_published_sizes_of_moonlight():
    import dataclasses

    import jax
    import numpy as np

    family, cfg, reference = cellspec.model(cellspec.resolve(MOONLIGHT))
    assert (cfg.num_layers, cfg.first_k_dense, cfg.num_experts, cfg.top_k,
            cfg.num_shared_experts, cfg.kv_lora_rank, cfg.qk_rope_head_dim,
            cfg.row_lanes) == (8, 1, 64, 6, 2, 512, 64, 640)
    assert reference.num_params(cfg) == family.num_params(cfg) == 4_847_999_424
    full = dataclasses.replace(cfg, num_layers=27)
    assert reference.num_params(full) == family.num_params(full) == 15_960_110_208
    assert reference.weight_bytes(cfg) == 9_024_910_208      # a decode step's
    assert reference.kv_bytes_per_token(cfg) == 1152 * 8     # one row a layer
    assert reference.attn_flops_per_pair(cfg) == 34_816 * 8
    assert reference.attn_flops_per_pair(cfg, absorbed=False) == 10_240 * 8
    # the initialised tree has what the arithmetic says (shapes only)
    tree = jax.eval_shape(lambda: family.init_params(cfg, jax.random.PRNGKey(0)))
    assert sum(int(np.prod(a.shape))
               for a in jax.tree_util.tree_leaves(tree)) == 4_847_999_424


def test_the_tiny_family_runs_through_the_harness_end_to_end(copy, tmp_path):
    """The rehearsal of the chip run: a tiny ``deepseek`` added as files
    only, every step program warmed, a closed loop over HTTP, the served
    tokens against ``reference/deepseek.py``."""
    import jax
    import numpy as np

    root = copy({
        "benchmark/configs/tiny-deepseek.json": TINY_DEEPSEEK,
        "benchmark/traffic/tiny-pool.json": TINY_POOL,
        "benchmark/cells/tiny.cell.json": {"clients": 3},
    }, configs=[{"name": "tiny-deepseek", "source": "test", "reduced": [],
                 "file": "benchmark/configs/tiny-deepseek.json", "why": "tiny"}],
       workloads=[{"name": "tiny.cell", "config": "tiny-deepseek",
                   "traffic": "tiny-pool", "chips": 1, "why": "on the CPU"}])
    path = os.path.join(root, "BENCHMARK.json")
    with open(path) as f:
        bench = json.load(f)
    for m in bench["end_to_end"] + bench["per_layer"]:
        if MOONLIGHT in m.get("workloads", []):
            m["workloads"].append("tiny.cell")
    with open(path, "w") as f:
        json.dump(bench, f)
    spec = cellspec.resolve("tiny.cell", root=root)
    family, cfg, reference = cellspec.model(spec)
    tree = family.init_params(cfg, jax.random.PRNGKey(0))
    assert reference.num_params(cfg) == sum(
        int(np.prod(a.shape)) for a in jax.tree_util.tree_leaves(tree))
    reference.Q_BLOCK = 64  # serve_cell pads to multiples of 1024; any divisor
    raw = runner.run_cell(spec, seed=2**31 + 7, seconds=3.0, trace=False,
                          out_dir=str(tmp_path / "out"))
    assert raw["correct"] is True
    assert raw["attempted"] > 0 and raw["failed"] == 0
    assert raw["metrics"]["serve_tokens_per_s"] > 0
    counters = raw["window"]["counters"]
    assert counters["compiles"] == 0 and counters["program_cold_dispatches"] == 0
    # an untraced window: every latent reader says nothing and does not raise
    raw["window"]["trace"] = {"busy_s": 1.0, "window_s": 2.0, "top_ops": [],
                              "idle_gaps": [], "collective_exposed_s": 0.0,
                              "kernel_s": {}}
    raw["window"].setdefault("samples", [])
    line = runner.result_line(
        spec, raw, {"platform": "cpu", "kind": "cpu", "count": 1}, trace=True,
        peaks={"bf16_flops_per_s": 1.0, "hbm_bytes_per_s": 1.0})
    assert not [k for k in line["metrics"] if "mla_" in k]
    assert line["metrics"]["sched.pad_share"]["value"] >= 0


# ------------------------------------------------- the readers' arithmetic
def _ctx(cell: str, tl: dict) -> dict:
    spec = cellspec.resolve(cell)
    _, cfg, reference = cellspec.model(spec)
    busy = sum(b - a for a, b in tl["busy"]) * 1e-9
    window = {"host_spans": tl, "seconds": 51.0, "counters": {},
              "trace": {"busy_s": busy, "window_s": busy,
                        "kernel_s": {k: sum(d for _, d in v) * 1e-9
                                     for k, v in tl["kernels"].items()}}}
    return {"window": window, "spec": spec, "chips": 1,
            "peaks": cellspec.peaks_for(spec, "TPU v5 lite"),
            "end_to_end": {}, "cfg": cfg, "reference": reference}


def _synthetic() -> dict:
    """Two dispatches and their executions: a decode step of 128 rows over
    131,072 context tokens (10 ms, ``mla_decode`` 4 ms of it in 8 calls), a
    mixed step (40 ms, ``mla_prefill`` 2 ms)."""
    ms = 1e6
    steps = [("ragged_step_d128_t0", 0.0, 10 * ms,
              {"tokens": 128, "pad": 0, "kv_tokens": 131072,
               "attn_pairs": 131072, "dec_kv_tokens": 131072}),
             ("ragged_step_d128_t3", 20 * ms, 40 * ms,
              {"tokens": 512, "pad": 0, "kv_tokens": 131072 + 1024,
               "attn_pairs": 131072 + 300_000, "dec_kv_tokens": 131072})]
    host = [["engine/dispatch", start + 0.1 * ms, 0.2 * ms,
             {"program": name, **args}] for name, start, _, args in steps]
    return {
        "host": [{"thread": "engine", "events": host}],
        "modules": [[f"jit_{n}(1)", s + 0.5 * ms, d] for n, s, d, _ in steps],
        "busy": [[s + 0.5 * ms, s + 0.5 * ms + d] for _, s, d, _ in steps],
        "kernels": {"mla_decode": [[1 * ms + i * ms, 0.5 * ms] for i in range(8)]
                    + [[21 * ms + i * ms, 0.5 * ms] for i in range(8)],
                    "mla_prefill": [[30 * ms + i * ms, 0.25 * ms] for i in range(8)]}}


def test_the_latent_readers_count_one_row_a_token_and_layer():
    readers = cellspec.layer_readers(cellspec.resolve(MOONLIGHT))
    ctx = _ctx(MOONLIGHT, _synthetic())

    def read(name):
        return readers[name][1](ctx)

    # both steps' decode rows read 2 x 131,072 tokens x 1,152 B x 8 layers
    # once: 2.95 ms at 819 GB/s, against 8 ms in the kernel
    least = 2 * 131072 * 1152 * 8 / 819e9
    assert read("kernel.mla_decode_roofline") == pytest.approx(
        100 * least / 8e-3, rel=1e-9)
    # the chunk's 300,000 pairs at 34,816 FLOPs x 8 layers against 2 ms
    assert read("kernel.mla_prefill_roofline") == pytest.approx(
        100 * (300_000 * 34816 * 8 / 197e12) / 2e-3, rel=1e-9)
    assert read("kernel.mla_decode_share") == pytest.approx(100 * 8 / 50)
    assert read("kernel.mla_prefill_share") == pytest.approx(100 * 2 / 50)
    assert read("sched.pool_decode_step_ms_p50") == pytest.approx(10.0)
    assert read("sched.mixed_step_ms_p50") == pytest.approx(40.0)
    ref, cfg = ctx["reference"], ctx["cfg"]
    bytes_s = (2 * ref.weight_bytes(cfg) + 9216 * (2 * 131072 + 1024)) / 819e9
    flops_s = (2.0 * ref.active_params(cfg) * 640
               + 34816 * 8 * (2 * 131072 + 300_000)) / 197e12
    assert read("model.mla_step_roofline_kv") == pytest.approx(
        100 * max(bytes_s, flops_s) / 50e-3, rel=1e-9)
    for name in readers:
        if "roofline" in name or name.endswith("_share"):
            value = read(name)
            assert value is None or 0.0 <= value <= 100.0, name


def test_a_program_without_spans_or_kernels_reads_nothing():
    """The parent of PR 26 (or any family without the kernels): None, no
    error."""
    bare = dict(_synthetic(), host=[], kernels={"mla_decode": [], "mla_prefill": []})
    readers = cellspec.layer_readers(cellspec.resolve(MOONLIGHT))
    ctx = _ctx(MOONLIGHT, bare)
    for name in ("kernel.mla_decode_roofline", "kernel.mla_prefill_roofline",
                 "kernel.mla_decode_share", "kernel.mla_prefill_share",
                 "model.mla_step_roofline_kv", "sched.pool_decode_step_ms_p50"):
        assert readers[name][1](ctx) is None
    # a reference without the latent geometry (gpt2): the latent readers
    # have nothing to say there either
    import latent_spans

    assert latent_spans.geometry(_ctx("gpt2-xl.chat-open", _synthetic())) is None


@pytest.mark.parametrize("cell,fixture", [
    (MOONLIGHT, "v5e_moonlight_reason_spans")])
def test_the_new_readers_on_a_slice_recorded_on_the_chip(cell, fixture):
    with gzip.open(os.path.join(FIXTURES, fixture + ".json.gz"), "rt") as f:
        tl = json.load(f)
    with open(os.path.join(FIXTURES, fixture + ".expect.json")) as f:
        expect = json.load(f)
    ctx = _ctx(cell, tl)
    readers = cellspec.layer_readers(cellspec.resolve(cell))
    got = {name: readers[name][1](ctx) for name in expect if name != "_note"}
    for name, value in got.items():
        assert value == pytest.approx(expect[name], rel=1e-6), name
        if "roofline" in name or name.endswith("_share"):
            assert 0.0 <= value <= 100.0, name
