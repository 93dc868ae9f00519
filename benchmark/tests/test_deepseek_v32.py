"""PR 33's additions to the benchmark: the ``deepseek_v32`` family as files only
(a configuration, a new traffic mix and its cell, a reference, eight readers,
three kernels), the published sizes and the cut's arithmetic, and the new
readers' arithmetic."""

import json
import os

import pytest

import cellspec
import run as runner
from conftest import TINY_GPT2, TINY_POOL

CELL = "deepseek-v32-exp-d5-ep16.longctx-pool"
NEW_READERS = ("sched.dsa_selected_share", "kernel.dsa_index_share",
               "kernel.dsa_index_roofline", "kernel.dsa_attn_prefill_share",
               "kernel.dsa_attn_prefill_roofline",
               "kernel.dsa_attn_decode_share",
               "kernel.dsa_attn_decode_roofline", "model.dsa_step_roofline_kv")

TINY_V32 = {
    "source": "test", "family": "deepseek_v32",
    "config_class": "DeepseekV32Config",
    "fields": {"vocab_size": "vocab_size", "hidden_size": "hidden_size",
               "intermediate_size": "intermediate_size",
               "moe_intermediate_size": "moe_intermediate_size",
               "num_layers": "num_hidden_layers",
               "num_heads": "num_attention_heads",
               "kv_lora_rank": "kv_lora_rank", "q_lora_rank": "q_lora_rank",
               "qk_nope_head_dim": "qk_nope_head_dim",
               "qk_rope_head_dim": "qk_rope_head_dim",
               "v_head_dim": "v_head_dim",
               "num_experts": "n_routed_experts_published",
               "experts_held": "n_routed_experts", "expert_rank": "expert_rank",
               "num_shared_experts": "n_shared_experts",
               "top_k": "num_experts_per_tok",
               "first_k_dense": "first_k_dense_replace",
               "n_group": "n_group", "topk_group": "topk_group",
               "rope_theta": "rope_theta", "rope_scaling": "rope_scaling",
               "index_n_heads": "index_n_heads",
               "index_head_dim": "index_head_dim", "index_topk": "index_topk",
               "max_seq_len": "max_position_embeddings"},
    "vocab_size": 256, "hidden_size": 64, "intermediate_size": 96,
    "moe_intermediate_size": 48, "num_hidden_layers": 3,
    "num_attention_heads": 2, "kv_lora_rank": 32, "q_lora_rank": 24,
    "qk_nope_head_dim": 16, "qk_rope_head_dim": 16, "v_head_dim": 16,
    "n_routed_experts_published": 8, "n_routed_experts": 4, "expert_rank": 1,
    "n_shared_experts": 1, "num_experts_per_tok": 3,
    "first_k_dense_replace": 1, "n_group": 4, "topk_group": 2,
    "rope_theta": 10000,
    "rope_scaling": {"type": "yarn", "factor": 4, "beta_fast": 32,
                     "beta_slow": 1, "mscale": 1, "mscale_all_dim": 1,
                     "original_max_position_embeddings": 16},
    # the mix's prompts are 20-90 tokens: every query past the 16th selects
    "index_n_heads": 2, "index_head_dim": 24, "index_topk": 16,
    "max_position_embeddings": 2048,
    "reduced": [],
    "serve": {**TINY_GPT2["serve"],
              # 3 of 8 sigmoid scores and the 16th of <= 100 index scores
              # flip under bf16 on a 64-wide model
              "check": {"match_rate_min": 0.5}},
}


def test_the_new_cell_resolves_with_the_traffic_as_the_issue_wrote_it():
    spec = cellspec.resolve(CELL)
    assert spec["chips"] == 1 and spec["cell"] == {"clients": 16}
    assert spec["traffic_name"] == "longctx-pool"
    mix = spec["mix"]
    assert mix["kind"] == "closed_loop" and mix["stream"] is False
    assert mix["prompt_tokens"] == {"dist": "lognormal", "median": 6144,
                                    "sigma": 0.25, "min": 4096, "max": 7936}
    assert mix["output_tokens"] == {"dist": "uniform", "min": 64, "max": 256}
    assert (mix["total_tokens_max"], mix["lead_seconds"], mix["grace_seconds"],
            mix["warm_requests"], mix["warm_max_tokens"]) == (8192, 20, 25, 2, 8)
    assert mix["limits"] == cellspec.resolve(
        "moonlight-16b-a3b-d8.reason-pool")["mix"]["limits"]
    engine = spec["config"]["serve"]["engine"]
    assert engine == {"block_size": 128, "num_blocks": 1025, "max_seqs": 16,
                      "max_tokens_per_step": 512, "max_blocks_per_seq": 64,
                      "prefill_tile": 128}
    # every slot can hold the mix's longest request (no preemption), and the
    # table is the one width the warm-up enumerates
    assert (engine["num_blocks"] - 1 == engine["max_seqs"] * engine["max_blocks_per_seq"]
            and mix["total_tokens_max"]
            == engine["block_size"] * engine["max_blocks_per_seq"])
    # every request's context is 2-4x the selection's width
    assert mix["prompt_tokens"]["min"] == 2 * spec["config"]["index_topk"]
    assert {m["name"] for m in spec["end_to_end"]} == {"serve_tokens_per_s", "setup_s"}
    assert {m["name"] for m in spec["per_layer"]} == set(NEW_READERS) | {
        "serve.request_p50_ms", "sched.pad_share", "sched.cold_dispatches",
        "sched.mixed_step_ms_p50", "sched.moe_grouped_share",
        "kernel.moe_gmm_share", "model.step_roofline"}
    with open(os.path.join(os.path.dirname(spec["base"]), "BENCHMARK.json")) as f:
        bench = json.load(f)
    assert len(bench["workloads"]) == 7
    assert [w["chips"] for w in bench["workloads"]].count(4) == 1
    for m in bench["per_layer"]:
        if m["name"] in NEW_READERS:
            assert m["workloads"] == [CELL] and m["moves"] == "serve_tokens_per_s"


def test_the_configuration_is_the_catalog_entry_but_for_its_cut():
    conf = cellspec.resolve(CELL)["config"]
    published = {
        "attention_bias": False, "ep_size": 1, "first_k_dense_replace": 3,
        "hidden_act": "silu", "hidden_size": 7168, "index_head_dim": 128,
        "index_n_heads": 64, "index_topk": 2048, "intermediate_size": 18432,
        "kv_lora_rank": 512, "max_position_embeddings": 163840,
        "model_type": "deepseek_v32", "moe_intermediate_size": 2048,
        "moe_layer_freq": 1, "n_group": 8, "n_routed_experts": 256,
        "n_shared_experts": 1, "norm_topk_prob": True,
        "num_attention_heads": 128, "num_experts_per_tok": 8,
        "num_hidden_layers": 61, "num_key_value_heads": 128,
        "num_nextn_predict_layers": 1, "q_lora_rank": 1536,
        "qk_nope_head_dim": 128, "qk_rope_head_dim": 64, "rms_norm_eps": 1e-06,
        "rope_scaling": {"beta_fast": 32, "beta_slow": 1, "factor": 40,
                         "mscale": 1, "mscale_all_dim": 1,
                         "original_max_position_embeddings": 4096,
                         "type": "yarn"},
        "rope_theta": 10000, "routed_scaling_factor": 2.5,
        "scoring_func": "sigmoid", "tie_word_embeddings": False,
        "topk_group": 4, "topk_method": "noaux_tc", "v_head_dim": 128,
        "vocab_size": 129280}
    differs = {k for k, v in published.items() if conf.get(k, "absent") != v}
    assert differs == set(conf["reduced"]) == {
        "num_hidden_layers", "first_k_dense_replace", "n_routed_experts",
        "vocab_size", "num_nextn_predict_layers"}
    assert (conf["num_hidden_layers"], conf["first_k_dense_replace"],
            conf["n_routed_experts"], conf["vocab_size"],
            conf["num_nextn_predict_layers"]) == (5, 1, 16, 16160, 0)
    assert (conf["n_routed_experts_published"], conf["expert_rank"],
            conf["expert_ranks"], conf["vocab_size_published"]) == (256, 0, 16, 129280)
    assert conf["vocab_size"] * 8 == conf["vocab_size_published"]
    assert conf["n_routed_experts"] * conf["expert_ranks"] == 256
    assert conf["source"].endswith("DeepSeek-V3.2-Exp/blob/main/config.json")
    assert {"indexer_rotation", "index_key_dtype", "indexer_details",
            "weights"} <= set(conf["assumed"]) and "16" in conf["deployment"]


def test_the_sizes_of_the_cut():
    import jax
    import numpy as np

    family, cfg, reference = cellspec.model(cellspec.resolve(CELL))
    assert (cfg.num_layers, cfg.first_k_dense, cfg.num_experts, cfg.held,
            cfg.top_k, cfg.held_share, cfg.route_groups, cfg.index_topk) == (
                5, 1, 256, 16, 8, (0, 256), (8, 4), 2048)
    assert cfg.softmax_scale == pytest.approx(
        192 ** -0.5 * (0.1 * np.log(40) + 1) ** 2)
    assert reference.num_params(cfg) == family.num_params(cfg) == 4_635_518_208
    assert reference._attention_params(cfg) == 187_121_664 + 13_959_424
    assert reference._dense_layer_params(cfg) == 597_442_816
    assert reference._moe_layer_params(cfg, 16) == 951_599_616
    assert reference.weight_bytes(cfg) == 2 * (4_635_518_208 - 16160 * 7168)
    # a token needs 8 x 16 / 256 = 0.5 of the held experts a layer
    assert reference.active_params(cfg) == pytest.approx(
        4_635_518_208 - 16160 * 7168 - 7168 - 4 * (16 - 0.5) * 44_040_192)
    # a layer's numbers as ISSUE 33 gives them, times the five layers
    assert reference.kv_bytes_per_token(cfg) == 5 * 1152
    assert reference.attn_flops_per_pair(cfg) == 5 * 278_528
    assert reference.index_bytes_per_token(cfg) == 5 * 256
    assert reference.index_flops_per_pair(cfg) == 5 * 16_384
    assert reference.index_topk(cfg) == 2048
    tree = jax.eval_shape(lambda: family.init_params(cfg, jax.random.PRNGKey(0)))
    assert sum(int(np.prod(a.shape))
               for a in jax.tree_util.tree_leaves(tree)) == 4_635_518_208
    cache = jax.eval_shape(lambda: family.init_paged_cache(
        cfg, 1025, 128, jax.numpy.bfloat16))
    assert cache["kv"].shape == (5, 1025, 128, 640)
    assert cache["idx"].shape == (5, 1025, 128, 128)
    pool = sum(int(np.prod(a.shape)) * 2 for a in jax.tree_util.tree_leaves(cache))
    assert pool == 1025 * 128 * 5 * 1536 and 1.0e9 < pool < 1.02e9
    spec = family.build(cfg)
    assert spec.index_topk == 2048 and spec.decode_bucket_min == 16


def test_the_tiny_family_runs_through_the_harness_end_to_end(copy, tmp_path):
    """The rehearsal of the chip run: a tiny ``deepseek_v32`` (rank 1 of 2,
    16 rows kept a query) added as files only, every step program warmed, a
    closed loop over HTTP, the served tokens against
    ``reference/deepseek_v32.py``."""
    import jax
    import numpy as np

    root = copy({
        "benchmark/configs/tiny-v32.json": TINY_V32,
        "benchmark/traffic/tiny-pool.json": TINY_POOL,
        "benchmark/cells/tiny.cell.json": {"clients": 3},
    }, configs=[{"name": "tiny-v32", "source": "test", "reduced": [],
                 "file": "benchmark/configs/tiny-v32.json", "why": "tiny"}],
       workloads=[{"name": "tiny.cell", "config": "tiny-v32",
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
    tree = family.init_params(cfg, jax.random.PRNGKey(0))
    assert reference.num_params(cfg) == sum(
        int(np.prod(a.shape)) for a in jax.tree_util.tree_leaves(tree))
    reference.Q_BLOCK, reference.PAD_TO = 64, 1024
    raw = runner.run_cell(spec, seed=2**31 + 33, seconds=3.0, trace=False,
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


def _synthetic(selection: bool = True) -> dict:
    """Two dispatches and their executions. A decode step of 16 rows at 6,000
    tokens of context each (20 ms; ``dsa_index`` 2 ms, ``dsa_attn_decode``
    1 ms). A mixed step of the same rows and 3 tiles of ONE prompt from
    position 3,000 (50 ms; ``dsa_index`` 4 ms, ``dsa_attn_prefill`` 20 ms,
    ``dsa_attn_decode`` 1 ms): 384 queries at contexts 3,001-3,384, all past
    2,048, so each keeps 2,048 rows, as does each of the three tiles."""
    ms = 1e6
    dec = 16 * 6000
    causal = sum(range(3001, 3385))
    steps = [("ragged_step_d16_t0", 0.0, 20 * ms,
              {"tokens": 16, "pad": 0, "kv_tokens": dec, "attn_pairs": dec,
               "dec_kv_tokens": dec, "moe": "dense", "sel_pairs": 16 * 2048,
               "sel_kv_tokens": 16 * 2048, "dec_sel_kv_tokens": 16 * 2048}),
             ("ragged_step_d16_t3", 30 * ms, 50 * ms,
              {"tokens": 400, "pad": 0, "kv_tokens": dec + 3384,
               "attn_pairs": dec + causal, "dec_kv_tokens": dec,
               "moe": "grouped", "sel_pairs": (16 + 384) * 2048,
               "sel_kv_tokens": (16 + 3) * 2048,
               "dec_sel_kv_tokens": 16 * 2048})]
    if not selection:
        steps = [(n, s, d, {k: v for k, v in a.items() if "sel_" not in k})
                 for n, s, d, a in steps]
    host = [["engine/dispatch", start + 0.1 * ms, 0.2 * ms,
             {"program": name, **args}] for name, start, _, args in steps]
    return {
        "host": [{"thread": "engine", "events": host}],
        "modules": [[f"jit_{n}(1)", s + 0.5 * ms, d] for n, s, d, _ in steps],
        "busy": [[s + 0.5 * ms, s + 0.5 * ms + d] for _, s, d, _ in steps],
        "kernels": {
            "dsa_index": [[1 * ms, 2 * ms], [31 * ms, 4 * ms]],
            "dsa_attn_decode": [[4 * ms, 1 * ms], [36 * ms, 1 * ms]],
            "dsa_attn_prefill": [[40 * ms, 20 * ms]],
            "moe_gmm": [[65 * ms, 2 * ms]]}}


def test_the_new_readers_count_the_kept_rows_and_the_scored_context():
    readers = cellspec.layer_readers(cellspec.resolve(CELL))
    ctx = _ctx(CELL, _synthetic())

    def read(name):
        return readers[name][1](ctx)

    dec, causal = 16 * 6000, sum(range(3001, 3385))
    pairs = 2 * dec + causal
    assert read("sched.dsa_selected_share") == pytest.approx(
        100 * (16 + 16 + 384) * 2048 / pairs)
    # the indexer scores EVERY causal pair (81,920 FLOP a pair over 5 layers)
    # and reads every cached key once a sequence (1,280 B): FLOP-bound here
    flops_s, bytes_s = pairs * 81_920 / 197e12, (2 * dec + 3384) * 1280 / 819e9
    assert flops_s > bytes_s
    assert read("kernel.dsa_index_roofline") == pytest.approx(
        100 * flops_s / 6e-3, rel=1e-9)
    assert read("kernel.dsa_index_share") == pytest.approx(100 * 6 / 70)
    # the tiles' KEPT pairs (1,392,640 FLOP a pair over 5 layers)
    assert read("kernel.dsa_attn_prefill_roofline") == pytest.approx(
        100 * (384 * 2048 * 1_392_640 / 197e12) / 20e-3, rel=1e-9)
    assert read("kernel.dsa_attn_prefill_share") == pytest.approx(100 * 20 / 70)
    # a decode row's 2,048 kept rows: 5,760 B and 1,392,640 FLOP each, at
    # 242 FLOP a byte the FLOPs bound it (197e12 / 819e9 = 240.5)
    rows = 2 * 16 * 2048
    assert read("kernel.dsa_attn_decode_roofline") == pytest.approx(
        100 * max(rows * 1_392_640 / 197e12, rows * 5760 / 819e9) / 2e-3,
        rel=1e-9)
    ref, cfg = ctx["reference"], ctx["cfg"]
    compute_s = (2.0 * ref.active_params(cfg) * 416
                 + 1_392_640 * (16 + 16 + 384) * 2048
                 + 81_920 * pairs) / 197e12
    bytes_s = (2 * ref.weight_bytes(cfg) + 5760 * (16 + 16 + 3) * 2048
               + 1280 * (2 * dec + 3384)) / 819e9
    assert read("model.dsa_step_roofline_kv") == pytest.approx(
        100 * max(compute_s, bytes_s) / 70e-3, rel=1e-9)
    assert read("sched.mixed_step_ms_p50") == pytest.approx(50.0)
    assert read("sched.moe_grouped_share") == pytest.approx(50.0)
    for name in readers:
        if "roofline" in name or name.endswith("_share"):
            value = read(name)
            assert value is None or 0.0 <= value <= 100.0, name


@pytest.mark.parametrize("bare", ["no_spans", "no_selection_arguments"])
def test_a_program_without_spans_or_selection_reads_nothing(bare):
    """The parent of PR 33, or any family that attends over the whole
    context: None, no error."""
    if bare == "no_spans":
        tl = dict(_synthetic(), host=[], kernels={
            "dsa_index": [], "dsa_attn_decode": [], "dsa_attn_prefill": []})
    else:   # dispatch spans without sel_* arguments, no kernel of that name
        tl = dict(_synthetic(selection=False))
        tl["kernels"] = {"dsa_index": [], "dsa_attn_decode": [],
                         "dsa_attn_prefill": [], "moe_gmm": []}
    readers = cellspec.layer_readers(cellspec.resolve(CELL))
    ctx = _ctx(CELL, tl)
    for name in NEW_READERS:
        assert readers[name][1](ctx) is None, name
    # a reference without the indexer's arithmetic: nothing to say either
    import dsa_spans

    ctx = _ctx("moonlight-16b-a3b-d8.reason-pool", _synthetic())
    assert dsa_spans.geometry(ctx) is None
    assert dsa_spans.step_roofline_kv(ctx) is None
    assert dsa_spans.index_roofline(ctx) is None
