"""PR 31's additions to the benchmark: the ``nemotron_h`` family as files only (a
configuration, a cell on the existing ``reason-pool`` mix, a reference, five
readers, one kernel), the published sizes and the cut's arithmetic, and the
new readers' arithmetic."""

import json
import os

import pytest

import cellspec
import run as runner
from conftest import TINY_GPT2, TINY_POOL

NEMOTRON = "nemotron-3-super-120b-d11-ep4.reason-pool"
NEW_READERS = ("model.ssm_step_roofline_kv", "kernel.ssm_decode_share",
               "kernel.ssm_decode_roofline",
               "kernel.hybrid_paged_decode_roofline",
               "kernel.hybrid_tiled_prefill_roofline")

TINY_NEMOTRON = {
    "source": "test", "family": "nemotron_h", "config_class": "NemotronHConfig",
    "fields": {"vocab_size": "vocab_size", "hidden_size": "hidden_size",
               "num_layers": "num_hidden_layers",
               "hybrid_override_pattern": "hybrid_override_pattern",
               "num_heads": "num_attention_heads",
               "num_kv_heads": "num_key_value_heads", "head_dim": "head_dim",
               "mamba_num_heads": "mamba_num_heads",
               "mamba_head_dim": "mamba_head_dim", "n_groups": "n_groups",
               "ssm_state_size": "ssm_state_size", "chunk_size": "chunk_size",
               "moe_latent_size": "moe_latent_size",
               "moe_intermediate_size": "moe_intermediate_size",
               "moe_shared_expert_intermediate_size":
                   "moe_shared_expert_intermediate_size",
               "num_experts": "n_routed_experts_published",
               "experts_held": "n_routed_experts", "expert_rank": "expert_rank",
               "top_k": "num_experts_per_tok",
               "max_seq_len": "max_position_embeddings"},
    "vocab_size": 256, "hidden_size": 64, "num_hidden_layers": 5,
    "hybrid_override_pattern": "*EMEM", "num_attention_heads": 4,
    "num_key_value_heads": 2, "head_dim": 16, "mamba_num_heads": 8,
    "mamba_head_dim": 16, "n_groups": 2, "ssm_state_size": 16,
    "chunk_size": 16, "moe_latent_size": 32, "moe_intermediate_size": 48,
    "moe_shared_expert_intermediate_size": 96,
    "n_routed_experts_published": 16, "n_routed_experts": 4, "expert_rank": 1,
    "num_experts_per_tok": 6, "max_position_embeddings": 2048,
    "reduced": [],
    "serve": {**TINY_GPT2["serve"],
              # 6 of 16 sigmoid scores flip under bf16 on a 64-wide model
              "check": {"match_rate_min": 0.5}},
}


def test_the_new_cell_resolves_on_the_mix_as_it_is():
    spec = cellspec.resolve(NEMOTRON)
    assert spec["chips"] == 1 and spec["cell"] == {"clients": 128}
    assert spec["traffic_name"] == "reason-pool"
    assert spec["mix"] == cellspec.resolve("moonlight-16b-a3b-d8.reason-pool")["mix"]
    engine = spec["config"]["serve"]["engine"]
    assert engine == {"block_size": 128, "num_blocks": 4097, "max_seqs": 128,
                      "max_tokens_per_step": 512, "max_blocks_per_seq": 32,
                      "prefill_tile": 128}
    # every slot can hold the mix's longest request: no preemption
    assert (engine["num_blocks"] - 1 == engine["max_seqs"] * engine["max_blocks_per_seq"]
            and spec["mix"]["total_tokens_max"]
            == engine["block_size"] * engine["max_blocks_per_seq"])
    assert {m["name"] for m in spec["end_to_end"]} == {"serve_tokens_per_s", "setup_s"}
    names = {m["name"] for m in spec["per_layer"]}
    assert set(NEW_READERS) | {
        "serve.request_p50_ms", "sched.pad_share", "sched.cold_dispatches",
        "model.step_roofline", "kernel.attn_share", "sched.mixed_step_ms_p50",
        "sched.pool_decode_step_ms_p50", "sched.moe_grouped_share",
        "kernel.moe_gmm_share", "kernel.paged_decode_share",
        "kernel.tiled_prefill_share"} == names
    # host_spans.attention_geometry multiplies one layer's K/V by num_layers:
    # eleven times this model's one attention layer
    assert not names & {"model.step_roofline_kv", "kernel.paged_decode_roofline",
                        "kernel.tiled_prefill_roofline"}


def test_the_configuration_is_the_catalog_entry_but_for_its_cut():
    conf = cellspec.resolve(NEMOTRON)["config"]
    published = {
        "attention_bias": False, "chunk_size": 128, "conv_kernel": 4,
        "expand": 2, "head_dim": 128, "hidden_size": 4096,
        "hybrid_override_pattern": "MEMEMEM*EMEMEMEM*EMEMEMEM*EMEMEMEMEM*"
        "EMEMEMEMEM*EMEMEMEMEM*EMEMEMEMEM*EMEMEMEM*EMEMEMEME",
        "intermediate_size": 2688, "layer_norm_epsilon": 1e-05,
        "mamba_head_dim": 64, "mamba_hidden_act": "silu",
        "mamba_num_heads": 128, "mamba_proj_bias": False,
        "max_position_embeddings": 262144, "mlp_bias": False,
        "mlp_hidden_act": "relu2", "model_type": "nemotron_h",
        "moe_intermediate_size": 2688, "moe_latent_size": 1024,
        "moe_shared_expert_intermediate_size": 5376,
        "moe_shared_expert_overlap": False, "mtp_hybrid_override_pattern": "*E",
        "n_group": 1, "n_groups": 8, "n_routed_experts": 512,
        "n_shared_experts": 1, "norm_eps": 1e-05, "norm_topk_prob": True,
        "num_attention_heads": 32, "num_experts_per_tok": 22,
        "num_hidden_layers": 88, "num_key_value_heads": 2,
        "num_logits_to_keep": 1, "num_nextn_predict_layers": 1,
        "partial_rotary_factor": 1, "rescale_prenorm_residual": True,
        "residual_in_fp32": False, "rope_theta": 10000,
        "routed_scaling_factor": 5, "sliding_window": None,
        "ssm_state_size": 128, "tie_word_embeddings": False,
        "time_step_floor": 0.0001, "time_step_max": 0.1,
        "time_step_min": 0.001, "topk_group": 1, "use_bias": False,
        "use_conv_bias": True, "use_mamba_kernels": True, "vocab_size": 131072}
    differs = {k for k, v in published.items() if conf.get(k, "absent") != v}
    assert differs == set(conf["reduced"]) == {
        "num_hidden_layers", "hybrid_override_pattern", "n_routed_experts",
        "vocab_size", "num_nextn_predict_layers"}
    assert (conf["num_hidden_layers"], conf["hybrid_override_pattern"],
            conf["n_routed_experts"], conf["vocab_size"],
            conf["num_nextn_predict_layers"]) == (11, "*EMEMEMEMEM", 128, 32768, 0)
    # the cut period is the published layers 25-35, and the held share and
    # the published count are both in the file
    assert published["hybrid_override_pattern"][25:36] == "*EMEMEMEMEM"
    assert (conf["n_routed_experts_published"], conf["expert_rank"],
            conf["expert_ranks"], conf["vocab_size_published"]) == (512, 0, 4, 131072)
    # no width is cut: d_inner = expand x hidden = heads x head size
    assert conf["expand"] * conf["hidden_size"] == \
        conf["mamba_num_heads"] * conf["mamba_head_dim"]


def test_the_sizes_of_the_cut():
    import jax
    import numpy as np

    family, cfg, reference = cellspec.model(cellspec.resolve(NEMOTRON))
    assert (cfg.num_layers, cfg.num_experts, cfg.held, cfg.top_k, cfg.d_inner,
            cfg.conv_width, cfg.held_share) == (11, 512, 128, 22, 8192, 10240,
                                                (0, 512))
    assert reference.num_params(cfg) == family.num_params(cfg) == 4_648_163_712
    layer = reference._layer_params
    assert layer(cfg, "M", 0) == 109_640_064          # ISSUE: 109.6 M
    assert layer(cfg, "*", 0) == 35_655_680           # 35.7 M
    assert layer(cfg, "E", 0) == 54_530_560           # 54.5 M beside the experts
    assert layer(cfg, "E", 128) - layer(cfg, "E", 0) == 128 * 5_505_024
    assert reference.weight_bytes(cfg) == 2 * (4_648_163_712 - 32768 * 4096)
    # a token needs 22 x 128 / 512 = 5.5 of the held experts a layer
    assert reference.active_params(cfg) == pytest.approx(
        4_648_163_712 - 32768 * 4096 - 5 * (128 - 5.5) * 5_505_024)
    assert reference.kv_bytes_per_token(cfg) == 1024      # ONE attention layer
    assert reference.attn_flops_per_pair(cfg) == 4 * 32 * 128
    # a slot: 5 x (128 x 8192 float32 + 3 x 10240 bf16)
    assert reference.state_bytes_per_slot(cfg) == 5 * (4_194_304 + 61_440)
    assert reference.ssm_flops_per_token(cfg) == 5 * 5 * 128 * 8192
    tree = jax.eval_shape(lambda: family.init_params(cfg, jax.random.PRNGKey(0)))
    assert sum(int(np.prod(a.shape))
               for a in jax.tree_util.tree_leaves(tree)) == 4_648_163_712
    cache = jax.eval_shape(lambda: family.init_paged_cache(
        cfg, 4097, 128, jax.numpy.bfloat16, num_slots=129))
    assert cache["k"].shape == (1, 4097, 128, 256)
    assert cache["slots"]["ssm"].shape == (5, 129, 128, 8192)
    assert cache["slots"]["conv"].shape == (5, 129, 3, 10240)


def test_the_tiny_family_runs_through_the_harness_end_to_end(copy, tmp_path):
    """The rehearsal of the chip run: a tiny ``nemotron_h`` (rank 1 of 4) added
    as files only, every step program warmed, a closed loop over HTTP, the
    served tokens against ``reference/nemotron_h.py``."""
    import jax
    import numpy as np

    root = copy({
        "benchmark/configs/tiny-nemotron.json": TINY_NEMOTRON,
        "benchmark/traffic/tiny-pool.json": TINY_POOL,
        "benchmark/cells/tiny.cell.json": {"clients": 3},
    }, configs=[{"name": "tiny-nemotron", "source": "test", "reduced": [],
                 "file": "benchmark/configs/tiny-nemotron.json", "why": "tiny"}],
       workloads=[{"name": "tiny.cell", "config": "tiny-nemotron",
                   "traffic": "tiny-pool", "chips": 1, "why": "on the CPU"}])
    path = os.path.join(root, "BENCHMARK.json")
    with open(path) as f:
        bench = json.load(f)
    for m in bench["end_to_end"] + bench["per_layer"]:
        if NEMOTRON in m.get("workloads", []):
            m["workloads"].append("tiny.cell")
    with open(path, "w") as f:
        json.dump(bench, f)
    spec = cellspec.resolve("tiny.cell", root=root)
    family, cfg, reference = cellspec.model(spec)
    tree = family.init_params(cfg, jax.random.PRNGKey(0))
    assert reference.num_params(cfg) == sum(
        int(np.prod(a.shape)) for a in jax.tree_util.tree_leaves(tree))
    reference.Q_BLOCK = 64  # serve_cell pads to multiples of 1024; any divisor
    raw = runner.run_cell(spec, seed=2**31 + 11, seconds=3.0, trace=False,
                          out_dir=str(tmp_path / "out"))
    assert raw["correct"] is True
    assert raw["attempted"] > 0 and raw["failed"] == 0
    assert raw["metrics"]["serve_tokens_per_s"] > 0
    counters = raw["window"]["counters"]
    assert counters["compiles"] == 0 and counters["program_cold_dispatches"] == 0
    # an untraced window: every new reader says nothing and does not raise
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


SLOT = 2 * 5 * (4_194_304 + 61_440)    # a slot's state, read and written


def _synthetic(state: bool = True) -> dict:
    """Two dispatches and their executions: a decode step of 128 rows over
    131,072 context tokens (25 ms, ``ssm_decode`` 10 ms of it in 5 calls,
    ``paged_decode`` 0.5 ms), a mixed step of 128 rows and 3 tiles of two
    prompts (40 ms; ``ssm_decode`` 10 ms, ``tiled_prefill`` 1 ms)."""
    ms = 1e6
    steps = [("ragged_step_d128_t0", 0.0, 25 * ms,
              {"tokens": 128, "pad": 0, "kv_tokens": 131072,
               "attn_pairs": 131072, "dec_kv_tokens": 131072,
               "state_bytes": 128 * SLOT, "dec_state_bytes": 128 * SLOT,
               "ssm_prefill_tokens": 0}),
             ("ragged_step_d128_t3", 30 * ms, 40 * ms,
              {"tokens": 500, "pad": 12, "kv_tokens": 131072 + 1024,
               "attn_pairs": 131072 + 300_000, "dec_kv_tokens": 131072,
               "state_bytes": 130 * SLOT, "dec_state_bytes": 128 * SLOT,
               "ssm_prefill_tokens": 372})]
    if not state:
        steps = [(n, s, d, {k: v for k, v in a.items()
                            if "state" not in k and "ssm" not in k})
                 for n, s, d, a in steps]
    host = [["engine/dispatch", start + 0.1 * ms, 0.2 * ms,
             {"program": name, **args}] for name, start, _, args in steps]
    return {
        "host": [{"thread": "engine", "events": host}],
        "modules": [[f"jit_{n}(1)", s + 0.5 * ms, d] for n, s, d, _ in steps],
        "busy": [[s + 0.5 * ms, s + 0.5 * ms + d] for _, s, d, _ in steps],
        "kernels": {
            "ssm_decode": [[1 * ms + 2 * i * ms, 2 * ms] for i in range(5)]
            + [[31 * ms + 2 * i * ms, 2 * ms] for i in range(5)],
            "paged_decode": [[12 * ms, 0.5 * ms], [42 * ms, 0.5 * ms]],
            "tiled_prefill": [[43 * ms, 1 * ms]]}}


def test_the_new_readers_count_the_state_and_one_attention_layer():
    readers = cellspec.layer_readers(cellspec.resolve(NEMOTRON))
    ctx = _ctx(NEMOTRON, _synthetic())

    def read(name):
        return readers[name][1](ctx)

    # 256 decode rows' states once each way against 20 ms in the kernel
    assert read("kernel.ssm_decode_roofline") == pytest.approx(
        100 * (256 * SLOT / 819e9) / 20e-3, rel=1e-9)
    assert read("kernel.ssm_decode_share") == pytest.approx(100 * 20 / 65)
    # K and V of ONE layer: 1,024 B a context token, against 1 ms
    assert read("kernel.hybrid_paged_decode_roofline") == pytest.approx(
        100 * (2 * 131072 * 1024 / 819e9) / 1e-3, rel=1e-9)
    # the chunks' 300,000 pairs at 16,384 FLOPs against 1 ms
    assert read("kernel.hybrid_tiled_prefill_roofline") == pytest.approx(
        100 * (300_000 * 16384 / 197e12) / 1e-3, rel=1e-9)
    ref, cfg = ctx["reference"], ctx["cfg"]
    bytes_s = (2 * ref.weight_bytes(cfg) + 1024 * (2 * 131072 + 1024)
               + 258 * SLOT) / 819e9
    flops_s = ((2.0 * ref.active_params(cfg) + ref.ssm_flops_per_token(cfg)) * 628
               + 16384 * (2 * 131072 + 300_000)) / 197e12
    assert bytes_s > flops_s
    assert read("model.ssm_step_roofline_kv") == pytest.approx(
        100 * bytes_s / 65e-3, rel=1e-9)
    assert read("sched.pool_decode_step_ms_p50") == pytest.approx(25.0)
    assert read("sched.mixed_step_ms_p50") == pytest.approx(40.0)
    for name in readers:
        if "roofline" in name or name.endswith("_share"):
            value = read(name)
            assert value is None or 0.0 <= value <= 100.0, name


@pytest.mark.parametrize("bare", ["no_spans", "no_state_arguments"])
def test_a_program_without_spans_or_state_reads_nothing(bare):
    """The parent of PR 31, or any family without slot state: None, no
    error."""
    if bare == "no_spans":
        tl = dict(_synthetic(), host=[], kernels={
            "ssm_decode": [], "paged_decode": [], "tiled_prefill": []})
        silent = NEW_READERS
    else:   # spans of a family whose dispatches carry no state arguments
        tl = dict(_synthetic(state=False))
        tl["kernels"] = {**tl["kernels"], "ssm_decode": []}
        silent = ("model.ssm_step_roofline_kv", "kernel.ssm_decode_share",
                  "kernel.ssm_decode_roofline")
    readers = cellspec.layer_readers(cellspec.resolve(NEMOTRON))
    ctx = _ctx(NEMOTRON, tl)
    for name in silent:
        assert readers[name][1](ctx) is None, name
    # a reference without the recurrence's arithmetic: nothing to say either
    import ssm_spans

    assert ssm_spans.step_roofline_kv(
        _ctx("moonlight-16b-a3b-d8.reason-pool", _synthetic())) is None
