"""PR 40's additions to the benchmark: the ``kimi_linear`` family as files only
(a configuration, a cell on the mix the benchmark had, a reference, a kernel's
pattern, three readers), the published sizes and the cut's arithmetic, the
readers by hand and on a slice recorded on the chip, and the rehearsal of the
chip run at a tiny size."""

import gzip
import json
import os

import pytest

import cellspec
import run as runner
from conftest import TINY_GPT2, TINY_POOL

CELL = "kimi-linear-48b-a3b-d13-ep8.reason-pool"
NEW_READERS = ("kernel.kda_decode_share", "kernel.kda_decode_roofline",
               "sched.state_bytes_share")
FIXTURE = os.path.join(os.path.dirname(__file__), "fixtures", "spans",
                       "v5e_kimi_linear_reason_spans")

TINY_KIMI = {
    "source": "test", "family": "kimi_linear",
    "config_class": "KimiLinearConfig",
    "fields": {"vocab_size": "vocab_size", "hidden_size": "hidden_size",
               "intermediate_size": "intermediate_size",
               "moe_intermediate_size": "moe_intermediate_size",
               "num_layers": "num_hidden_layers",
               "linear_attn_config": "linear_attn_config",
               "num_heads": "num_attention_heads",
               "kv_lora_rank": "kv_lora_rank",
               "qk_nope_head_dim": "qk_nope_head_dim",
               "qk_rope_head_dim": "qk_rope_head_dim",
               "v_head_dim": "v_head_dim", "mla_use_nope": "mla_use_nope",
               "num_experts": "num_experts_published",
               "experts_held": "num_experts", "expert_rank": "expert_rank",
               "top_k": "num_experts_per_token",
               "first_k_dense": "first_k_dense_replace",
               "chunk_size": "chunk_size", "sub_chunk": "sub_chunk",
               "max_seq_len": "model_max_length"},
    "vocab_size": 256, "hidden_size": 64, "intermediate_size": 96,
    "moe_intermediate_size": 48, "num_hidden_layers": 5,
    "linear_attn_config": {"kda_layers": [1, 2, 4], "full_attn_layers": [3, 5],
                           "head_dim": 16, "num_heads": 2,
                           "short_conv_kernel_size": 4},
    "num_attention_heads": 2, "kv_lora_rank": 32, "qk_nope_head_dim": 16,
    "qk_rope_head_dim": 16, "v_head_dim": 16, "mla_use_nope": True,
    "num_experts_published": 8, "num_experts": 4, "expert_rank": 1,
    "num_experts_per_token": 3, "first_k_dense_replace": 1,
    "chunk_size": 16, "sub_chunk": 4, "model_max_length": 2048, "reduced": [],
    "serve": {**TINY_GPT2["serve"],
              # top-3 of 8 sigmoid scores flip under bf16 on a 64-wide model
              "check": {"match_rate_min": 0.5}},
}


def test_the_new_cell_resolves_with_the_traffic_as_the_issue_wrote_it():
    spec = cellspec.resolve(CELL)
    assert spec["chips"] == 1 and spec["cell"] == {"clients": 128}
    assert spec["traffic_name"] == "reason-pool"
    mix = spec["mix"]
    assert mix == cellspec.resolve("moonlight-16b-a3b-d8.reason-pool")["mix"]
    assert mix["kind"] == "closed_loop" and mix["stream"] is False
    assert mix["prompt_tokens"] == {"dist": "lognormal", "median": 512,
                                    "sigma": 0.7, "min": 64, "max": 3072}
    assert mix["output_tokens"] == {"dist": "uniform", "min": 512, "max": 1024}
    assert mix["total_tokens_max"] == 4096
    engine = spec["config"]["serve"]["engine"]
    assert engine == {"block_size": 128, "num_blocks": 4097, "max_seqs": 128,
                      "max_tokens_per_step": 512, "max_blocks_per_seq": 32,
                      "prefill_tile": 128}
    assert mix["total_tokens_max"] == engine["block_size"] * engine["max_blocks_per_seq"]
    assert {m["name"] for m in spec["end_to_end"]} == {"serve_tokens_per_s", "setup_s"}
    # <=, not ==: a later PR may append this cell to further metrics' lists
    assert set(NEW_READERS) | {
        "serve.request_p50_ms", "sched.pad_share", "sched.cold_dispatches",
        "model.step_roofline", "model.ssm_step_roofline_kv",
        "sched.mixed_step_ms_p50", "sched.pool_decode_step_ms_p50",
        "kernel.mla_decode_share", "kernel.mla_decode_roofline",
        "kernel.mla_prefill_share", "kernel.mla_prefill_roofline",
        "sched.moe_grouped_share", "kernel.moe_gmm_share",
        "setup.cache_hit_share", "setup.program_builds", "setup.trace_s",
        "setup.lower_s", "setup.compile_s", "setup.cache_retrieval_s",
        "setup.background_compile_s", "setup.engine_init_s",
        "setup.unattributed_s"} <= {m["name"] for m in spec["per_layer"]}
    # the step's roofline with the state in it, not the latent pool's alone
    assert "model.mla_step_roofline_kv" not in {m["name"] for m in spec["per_layer"]}
    with open(os.path.join(os.path.dirname(spec["base"]), "BENCHMARK.json")) as f:
        bench = json.load(f)
    assert len(bench["configs"]) >= 7 and len(bench["workloads"]) >= 9
    assert [w["chips"] for w in bench["workloads"]].count(4) == 1
    why = next(w["why"] for w in bench["workloads"] if w["name"] == CELL)
    assert len(why) <= 200 and "closed loop, 128 clients" in why
    assert "kda_decode" in why and "1/8 of 8 chips' rows" in why
    for m in bench["per_layer"]:
        if m["name"] in NEW_READERS:
            assert m["workloads"] == [CELL] and m["moves"] == "serve_tokens_per_s"
            assert m["unit"] == "%"
    with open(os.path.join(spec["base"], "kernels", "kda_decode.json")) as f:
        kernel = json.load(f)
    import re

    rx = re.compile(kernel["trace_pattern"])
    assert rx.search("%kda_decode.3 = (f32[1290,128,4096]) custom-call(%p)")
    assert not rx.search("%ssm_decode.3 = (f32[645,128,8192]) custom-call(%p)")
    assert not rx.search("%kda_state_write.1 = f32[1290,128,4096] custom-call(%p)")


def test_the_configuration_is_the_catalog_entry_but_for_its_cut():
    conf = cellspec.resolve(CELL)["config"]
    published = {
        "first_k_dense_replace": 1, "head_dim": 72, "hidden_act": "silu",
        "hidden_size": 2304, "intermediate_size": 9216, "kv_lora_rank": 512,
        "linear_attn_config": {
            "full_attn_layers": [4, 8, 12, 16, 20, 24, 27], "head_dim": 128,
            "kda_layers": [1, 2, 3, 5, 6, 7, 9, 10, 11, 13, 14, 15, 17, 18, 19,
                           21, 22, 23, 25, 26],
            "num_heads": 32, "short_conv_kernel_size": 4},
        "mla_use_nope": True, "model_max_length": 1048576,
        "model_type": "kimi_linear", "moe_intermediate_size": 1024,
        "moe_layer_freq": 1, "moe_renormalize": True,
        "moe_router_activation_func": "sigmoid", "num_attention_heads": 32,
        "num_expert_group": 1, "num_experts": 256, "num_experts_per_token": 8,
        "num_hidden_layers": 27, "num_key_value_heads": 32,
        "num_nextn_predict_layers": 0, "num_shared_experts": 1,
        "q_lora_rank": None, "qk_nope_head_dim": 128, "qk_rope_head_dim": 64,
        "rms_norm_eps": 1e-05, "rope_scaling": None, "rope_theta": 10000,
        "routed_scaling_factor": 2.446, "tie_word_embeddings": False,
        "topk_group": 1, "use_grouped_topk": True, "v_head_dim": 128,
        "vocab_size": 163840}
    differs = {k for k, v in published.items() if conf.get(k, "absent") != v}
    assert differs == set(conf["reduced"]) == {
        "num_hidden_layers", "linear_attn_config", "num_experts", "vocab_size"}
    lin = conf["linear_attn_config"]
    assert lin["kda_layers"] == [1, 2, 3, 5, 6, 7, 9, 10, 11, 13]
    assert lin["full_attn_layers"] == [4, 8, 12]
    # the group's widths are the source's
    assert {k: lin[k] for k in ("head_dim", "num_heads", "short_conv_kernel_size")} \
        == {"head_dim": 128, "num_heads": 32, "short_conv_kernel_size": 4}
    assert (conf["num_hidden_layers"], conf["num_experts"],
            conf["vocab_size"]) == (13, 32, 20480)
    assert (conf["num_experts_published"], conf["expert_rank"],
            conf["expert_ranks"], conf["vocab_size_published"]) == (
                256, 0, 8, 163840)
    assert conf["vocab_size"] * 8 == conf["vocab_size_published"]
    assert conf["num_experts"] * conf["expert_ranks"] == 256
    assert conf["source"].endswith(
        "Kimi-Linear-48B-A3B-Instruct/blob/main/config.json")
    assert {"low_rank_gates", "gate_forms", "qk_norm", "state_dtype",
            "positions", "router", "weights"} <= set(conf["assumed"])
    assert "eight" in conf["deployment"]
    assert "3,450,547,008" in conf["reduced_why"]
    assert 0.0 < conf["serve"]["check"]["match_rate_min"] < 1.0
    assert len(conf["serve"]["check"]["why"]) > 200


def test_the_sizes_of_the_cut():
    import jax
    import numpy as np

    family, cfg, reference = cellspec.model(cellspec.resolve(CELL))
    assert cfg.layer_pattern == "D" + "KKMK" * 3
    assert family._plan(cfg.layer_pattern) == ("D", "KKMK", 3, "")
    assert (cfg.num_layers, cfg.num_experts, cfg.held, cfg.top_k,
            cfg.held_share, cfg.routed_scaling_factor, cfg.mla_use_nope) == (
                13, 256, 32, 8, (0, 256), 2.446, True)
    assert (cfg.kda_heads, cfg.kda_head_dim, cfg.kda_width, cfg.conv_kernel) \
        == (32, 128, 4096, 4)
    assert cfg.softmax_scale == 192 ** -0.5 and cfg.row_lanes == 640
    # ISSUE 40's terms, one by one
    assert reference.kda_params(cfg) == 39_514_272
    assert reference.mla_params(cfg) == 29_114_880
    assert reference.expert_params(cfg) == 7_077_888
    assert reference._layer_params(cfg, "D", 0) == 103_219_872
    assert reference._layer_params(cfg, "K", 0) == 47_186_848
    assert reference._layer_params(cfg, "M", 0) == 36_787_456
    assert reference.num_params(cfg) == family.num_params(cfg) == 3_450_547_008
    assert reference.weight_bytes(cfg) == 2 * (3_450_547_008 - 20480 * 2304)
    # a token needs 8 x 32 / 256 = 1 held expert a layer
    assert reference.active_params(cfg) == pytest.approx(
        3_450_547_008 - 20480 * 2304 - 12 * 31 * 7_077_888)
    # one latent row a token in each of the 3 MLA layers, not 13
    assert reference.kv_bytes_per_token(cfg) == 3 * 1152
    assert reference.attn_flops_per_pair(cfg) == 3 * 2 * 32 * (2 * 512 + 64)
    # S and the three convolutions' rows over the 10 KDA layers
    assert reference.state_bytes_per_slot(cfg) == 10 * (2_097_152 + 3 * 12288 * 2)
    assert reference.ssm_flops_per_token(cfg) == 7 * 32 * 128 * 128 * 10
    tree = jax.eval_shape(lambda: family.init_params(cfg, jax.random.PRNGKey(0)))
    assert sum(int(np.prod(a.shape))
               for a in jax.tree_util.tree_leaves(tree)) == 3_450_547_008
    cache = jax.eval_shape(lambda: family.init_paged_cache(
        cfg, 4097, 128, jax.numpy.bfloat16, num_slots=129))
    assert cache["kv"].shape == (3, 4097, 128, 640)
    assert cache["slots"]["kda"].shape == (10, 129, 128, 4096)
    assert cache["slots"]["kda"].dtype == jax.numpy.float32
    assert cache["slots"]["conv"].shape == (10, 129, 3, 12288)
    pool = int(np.prod(cache["kv"].shape)) * 2
    state = sum(int(np.prod(a.shape)) * a.dtype.itemsize
                for a in cache["slots"].values())
    assert 2.01e9 < pool < 2.02e9 and 2.80e9 < state < 2.81e9
    assert state == 129 * reference.state_bytes_per_slot(cfg)
    spec = family.build(cfg)
    assert spec.state_kind == "kda" and spec.decode_bucket_min == 128
    assert [spec.moe_form(r) for r in (128, 255, 256, 512)] == [
        "dense", "dense", "grouped", "grouped"]


def test_the_tiny_family_runs_through_the_harness_end_to_end(copy, tmp_path):
    """The rehearsal of the chip run: a tiny ``kimi_linear`` (rank 1 of 2; KDA,
    MLA and both feed-forward parts) added as files only, every step program
    warmed, a closed loop over HTTP, the served tokens against
    ``reference/kimi_linear.py``."""
    import jax
    import numpy as np

    root = copy({
        "benchmark/configs/tiny-kimi.json": TINY_KIMI,
        "benchmark/traffic/tiny-pool.json": TINY_POOL,
        "benchmark/cells/tiny.cell.json": {"clients": 3},
    }, configs=[{"name": "tiny-kimi", "source": "test", "reduced": [],
                 "file": "benchmark/configs/tiny-kimi.json", "why": "tiny"}],
       workloads=[{"name": "tiny.cell", "config": "tiny-kimi",
                   "traffic": "tiny-pool", "chips": 1, "why": "on the CPU"}])
    path = os.path.join(root, "BENCHMARK.json")
    with open(path) as f:
        bench = json.load(f)
    for m in bench["end_to_end"] + bench["per_layer"]:
        if CELL in m.get("workloads", []):
            m["workloads"].append("tiny.cell")
    with open(path, "w") as f:
        json.dump(bench, f)
    spec = cellspec.resolve("tiny.cell", root=root)
    family, cfg, reference = cellspec.model(spec)
    assert cfg.layer_pattern == "DKMKM"
    tree = family.init_params(cfg, jax.random.PRNGKey(0))
    assert reference.num_params(cfg) == sum(
        int(np.prod(a.shape)) for a in jax.tree_util.tree_leaves(tree))
    reference.Q_BLOCK = 64
    raw = runner.run_cell(spec, seed=2**31 + 40, seconds=3.0, trace=False,
                          out_dir=str(tmp_path / "out"))
    assert raw["correct"] is True
    assert raw["attempted"] > 0 and raw["failed"] == 0
    assert raw["metrics"]["serve_tokens_per_s"] > 0
    counters = raw["window"]["counters"]
    assert counters["compiles"] == 0 and counters["program_cold_dispatches"] == 0
    # an untraced window: the new readers say nothing and do not raise
    raw["window"]["trace"] = {"busy_s": 1.0, "window_s": 2.0, "top_ops": [],
                              "idle_gaps": [], "collective_exposed_s": 0.0,
                              "kernel_s": {}}
    raw["window"].setdefault("samples", [])
    line = runner.result_line(
        spec, raw, {"platform": "cpu", "kind": "cpu", "count": 1}, trace=True,
        peaks={"bf16_flops_per_s": 1.0, "hbm_bytes_per_s": 1.0})
    assert not [k for k in line["metrics"] if k in NEW_READERS]
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


PER_SLOT = 2 * 10 * (2_097_152 + 73_728)   # a slot's state, once each way


def _synthetic(state: bool = True) -> dict:
    """Two dispatches and their executions: a mixed step of 128 decode rows
    and 3 tiles of 2 slots, and a decode step of 128 rows; ``kda_decode`` runs
    8 ms in each."""
    ms = 1e6
    steps = [("ragged_step_d128_t3", 0.0, 40 * ms,
              {"tokens": 512, "kv_tokens": 140_000, "attn_pairs": 300_000,
               "dec_kv_tokens": 130_000, "state_bytes": 130 * PER_SLOT,
               "dec_state_bytes": 128 * PER_SLOT, "ssm_prefill_tokens": 384,
               "state_kind": "kda"}),
             ("ragged_step_d128_t0", 41 * ms, 22 * ms,
              {"tokens": 128, "kv_tokens": 131_000, "attn_pairs": 131_000,
               "dec_kv_tokens": 131_000, "state_bytes": 128 * PER_SLOT,
               "dec_state_bytes": 128 * PER_SLOT, "ssm_prefill_tokens": 0,
               "state_kind": "kda"})]
    if not state:
        steps = [(n, s, d, {k: v for k, v in a.items()
                            if "state" not in k and k != "ssm_prefill_tokens"})
                 for n, s, d, a in steps]
    host = [["engine/dispatch", start + 0.1 * ms, 0.2 * ms,
             {"program": name, "pad": 0, "moe": "dense", **args}]
            for name, start, _, args in steps]
    return {"host": [{"thread": "engine", "events": host}],
            "modules": [[f"jit_{n}(1)", s + 0.5 * ms, d] for n, s, d, _ in steps],
            "busy": [[s + 0.5 * ms, s + 0.5 * ms + d] for _, s, d, _ in steps],
            "kernels": {"kda_decode": [[2 * ms, 8 * ms], [43 * ms, 8 * ms]],
                        "mla_decode": [[11 * ms, ms]]}}


def test_the_new_readers_by_hand():
    spec = cellspec.resolve(CELL)
    readers = cellspec.layer_readers(spec)
    _, cfg, reference = cellspec.model(spec)
    ctx = _ctx(CELL, _synthetic())
    assert readers["kernel.kda_decode_share"][1](ctx) == pytest.approx(
        100 * 16 / 62)
    # 256 decode rows' states once each way at the peak, over the kernel's 16 ms
    assert readers["kernel.kda_decode_roofline"][1](ctx) == pytest.approx(
        100 * (256 * PER_SLOT / 819e9) / 16e-3)
    state = 258 * PER_SLOT
    rest = 2 * reference.weight_bytes(cfg) + 3 * 1152 * 271_000
    assert readers["sched.state_bytes_share"][1](ctx) == pytest.approx(
        100 * state / (state + rest))
    assert 40.0 < readers["sched.state_bytes_share"][1](ctx) < 46.0
    # the accepted step roofline takes the state in through the same spans
    assert 0.0 < readers["model.ssm_step_roofline_kv"][1](ctx) < 100.0
    # a program without the state's arguments (every other family, the
    # parent), a program without spans: nothing, and nothing raised
    for bare in (_synthetic(state=False), dict(_synthetic(), host=[])):
        ctx = _ctx(CELL, bare)
        for name in ("kernel.kda_decode_roofline", "sched.state_bytes_share"):
            assert readers[name][1](ctx) is None, name
    # a trace without the kernel (the parent's): no share of nothing
    none = dict(_synthetic(), kernels={"mla_decode": [[11e6, 1e6]]})
    assert readers["kernel.kda_decode_roofline"][1](_ctx(CELL, none)) is None
    assert not readers["kernel.kda_decode_share"][1](_ctx(CELL, none))
    # the hybrid cell's spans feed the same reader of the state's share
    assert readers["sched.state_bytes_share"][1](
        _ctx("nemotron-3-super-120b-d11-ep4.reason-pool", _synthetic())) > 0


def test_the_readers_on_a_slice_recorded_on_the_chip():
    """One second cut from the traced chip run of the cell (PR 40): every
    reader the cell lists that reads spans gives what it gave there, no share
    over 100%, and the three new ones read the kernel and the spans'
    ``state_bytes``."""
    with gzip.open(FIXTURE + ".json.gz", "rt") as f:
        tl = json.load(f)
    with open(FIXTURE + ".expect.json") as f:
        expect = json.load(f)
    readers = cellspec.layer_readers(cellspec.resolve(CELL))
    assert set(NEW_READERS) <= set(expect)
    assert "model.ssm_step_roofline_kv" in expect
    for name, want in expect.items():
        if name.startswith("_"):
            continue
        value = readers[name][1](_ctx(CELL, json.loads(json.dumps(tl))))
        assert value == pytest.approx(want, rel=1e-6), name
        if "roofline" in name or name.endswith("_share"):
            assert 0.0 <= value <= 100.0, name
    dispatches = [e[3] for h in tl["host"] for e in h["events"]
                  if e[0] == "engine/dispatch"]
    assert dispatches and all(a["state_kind"] == "kda" for a in dispatches)
    assert all(a["dec_state_bytes"] <= a["state_bytes"] for a in dispatches)
    bare = dict(tl, host=[])
    bare["kernels"] = {k: [] for k in bare["kernels"]}
    for name in NEW_READERS:
        assert not readers[name][1](_ctx(CELL, bare))
