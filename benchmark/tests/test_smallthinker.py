"""PR 44's additions to the benchmark: the ``smallthinker`` family as files only
(a configuration, a traffic mix, a cell, a reference, two kernels' patterns,
six readers and their helper), the published sizes and the cut's arithmetic,
the readers by hand, and the rehearsal of the chip run at a tiny size."""

import json
import os
import re

import pytest

import cellspec
import run as runner
from conftest import TINY_GPT2, TINY_POOL

CELL = "smallthinker-21b-a3b-ep8.mixedlen-pool"
NEW_READERS = ("kernel.swa_decode_share", "kernel.swa_decode_roofline",
               "kernel.swa_prefill_share", "kernel.swa_prefill_roofline",
               "model.swa_step_roofline_kv", "sched.window_held_share")
LAYOUT = [0, 1, 1, 1] * 13
# the catalog row's ``config`` (guides/model-configs/architectures.jsonl)
PUBLISHED = {
    "head_dim": 128, "hidden_size": 2560, "max_position_embeddings": 16384,
    "model_name": "smallthinker_21b_instruct", "moe_ffn_hidden_size": 768,
    "moe_num_active_primary_experts": 6, "moe_num_primary_experts": 64,
    "moe_primary_router_apply_softmax": True, "norm_topk_prob": True,
    "num_attention_heads": 28, "num_hidden_layers": 52,
    "num_key_value_heads": 4, "rms_norm_eps": 1e-06, "rope_layout": LAYOUT,
    "rope_scaling": None, "rope_theta": 1500000,
    "sliding_window_layout": LAYOUT, "sliding_window_size": 4096,
    "tie_word_embeddings": False, "vocab_size": 151936}

TINY_ST = {
    "source": "test", "family": "smallthinker",
    "config_class": "SmallThinkerConfig",
    "fields": {"vocab_size": "vocab_size", "hidden_size": "hidden_size",
               "moe_intermediate_size": "moe_ffn_hidden_size",
               "num_layers": "num_hidden_layers",
               "num_heads": "num_attention_heads",
               "num_kv_heads": "num_key_value_heads", "head_dim": "head_dim",
               "num_experts": "moe_num_primary_experts_published",
               "experts_held": "moe_num_primary_experts",
               "expert_rank": "expert_rank",
               "top_k": "moe_num_active_primary_experts",
               "sliding_window": "sliding_window_size",
               "sliding_window_layout": "sliding_window_layout",
               "rope_layout": "rope_layout",
               "max_seq_len": "max_position_embeddings"},
    "vocab_size": 256, "hidden_size": 64, "moe_ffn_hidden_size": 32,
    "num_hidden_layers": 8, "num_attention_heads": 4,
    "num_key_value_heads": 2, "head_dim": 16,
    "moe_num_primary_experts_published": 8, "moe_num_primary_experts": 4,
    "expert_rank": 1, "moe_num_active_primary_experts": 3,
    "sliding_window_size": 32, "sliding_window_layout": [0, 1, 1, 1] * 2,
    "rope_layout": [0, 1, 1, 1] * 2, "max_position_embeddings": 2048,
    "reduced": [],
    "serve": {**TINY_GPT2["serve"],
              # top-3 of 8 logits flip under bf16 on a 64-wide model
              "check": {"match_rate_min": 0.5}},
}


def test_the_new_cell_resolves_with_the_traffic_as_the_issue_wrote_it():
    spec = cellspec.resolve(CELL)
    assert spec["chips"] == 1 and spec["cell"] == {"clients": 16}
    assert spec["traffic_name"] == "mixedlen-pool"
    mix = spec["mix"]
    assert mix["kind"] == "closed_loop" and mix["stream"] is False
    assert mix["prompt_tokens"] == {"dist": "lognormal", "median": 4096,
                                    "sigma": 0.5, "min": 512, "max": 7680}
    assert mix["output_tokens"] == {"dist": "uniform", "min": 128, "max": 384}
    assert (mix["total_tokens_max"], mix["lead_seconds"], mix["grace_seconds"],
            mix["warm_requests"], mix["warm_max_tokens"]) == (8192, 20, 25, 2, 8)
    assert mix["limits"] == {"ttft_ms": 2000, "gap_ms": 200}
    engine = spec["config"]["serve"]["engine"]
    assert {k: engine[k] for k in ("block_size", "max_seqs", "prefill_tile",
                                   "max_tokens_per_step", "max_blocks_per_seq")
            } == {"block_size": 128, "max_seqs": 16, "prefill_tile": 128,
                  "max_tokens_per_step": 512, "max_blocks_per_seq": 64}
    assert engine["num_blocks"] >= 577      # ISSUE 44: at least 36 a slot
    assert mix["total_tokens_max"] == engine["block_size"] * engine["max_blocks_per_seq"]
    assert {m["name"] for m in spec["end_to_end"]} == {"serve_tokens_per_s", "setup_s"}
    # <=, not ==: a later PR may append this cell to further metrics' lists
    assert set(NEW_READERS) | {
        "serve.request_p50_ms", "sched.pad_share", "sched.cold_dispatches",
        "model.step_roofline", "sched.mixed_step_ms_p50",
        "sched.moe_grouped_share", "kernel.moe_gmm_share",
        "kernel.paged_decode_share", "kernel.tiled_prefill_share",
        "kernel.hybrid_paged_decode_roofline",
        "kernel.hybrid_tiled_prefill_roofline",
        "setup.cache_hit_share", "setup.program_builds", "setup.trace_s",
        "setup.lower_s", "setup.compile_s", "setup.cache_retrieval_s",
        "setup.background_compile_s", "setup.engine_init_s",
        "setup.unattributed_s"} <= {m["name"] for m in spec["per_layer"]}
    with open(os.path.join(os.path.dirname(spec["base"]), "BENCHMARK.json")) as f:
        bench = json.load(f)
    # a 4 s slice of this cell can hold mixed steps alone (the driver's traced
    # run of PR 44, seed 1711943154, did), so the reader of decode-only steps
    # finds nothing there and the cell is not on its list
    assert "sched.pool_decode_step_ms_p50" not in {m["name"] for m in spec["per_layer"]}
    assert len(bench["configs"]) >= 8 and len(bench["workloads"]) >= 10
    assert [w["chips"] for w in bench["workloads"]].count(4) == 1
    why = next(w["why"] for w in bench["workloads"] if w["name"] == CELL)
    assert len(why) <= 200 and "closed loop, 16 clients" in why
    assert "1/8 of 8 chips' rows" in why and "window" in why
    for m in bench["per_layer"]:
        if m["name"] in NEW_READERS:
            assert m["workloads"] == [CELL] and m["moves"] == "serve_tokens_per_s"
            assert m["unit"] == "%"
    for kernel, other in (("swa_decode", "paged_decode"),
                          ("swa_prefill", "tiled_prefill")):
        with open(os.path.join(spec["base"], "kernels", kernel + ".json")) as f:
            mine = re.compile(json.load(f)["trace_pattern"])
        with open(os.path.join(spec["base"], "kernels", other + ".json")) as f:
            theirs = re.compile(json.load(f)["trace_pattern"])
        line = f"%{kernel}.3 = bf16[16,7,512] custom-call(%p)"
        assert mine.search(line) and not theirs.search(line)
        assert not mine.search(f"%{other}.3 = bf16[16,7,512] custom-call(%p)")


def test_the_configuration_is_the_catalog_entry_but_for_its_cut():
    conf = cellspec.resolve(CELL)["config"]
    differs = {k for k, v in PUBLISHED.items() if conf.get(k, "absent") != v}
    assert differs == set(conf["reduced"]) == {"moe_num_primary_experts",
                                               "vocab_size"}
    assert (conf["moe_num_primary_experts"], conf["vocab_size"]) == (8, 18992)
    assert (conf["moe_num_primary_experts_published"], conf["expert_rank"],
            conf["expert_ranks"], conf["vocab_size_published"]) == (
                64, 0, 8, 151936)
    assert conf["vocab_size"] * 8 == conf["vocab_size_published"]
    assert conf["moe_num_primary_experts"] * conf["expert_ranks"] == 64
    assert conf["source"].endswith(
        "SmallThinker-21BA3B-Instruct/blob/main/config.json")
    assert {"router_input", "attention", "window_edge", "rope",
            "secondary_experts", "weights"} <= set(conf["assumed"])
    assert "eight" in conf["deployment"]
    assert "3,650,214,400" in conf["reduced_why"]
    assert 0.0 < conf["serve"]["check"]["match_rate_min"] < 1.0
    assert len(conf["serve"]["check"]["why"]) > 200


def test_the_sizes_of_the_cut():
    import jax
    import numpy as np

    spec = cellspec.resolve(CELL)
    family, cfg, reference = cellspec.model(spec)
    assert cfg.layer_pattern == "FWWW" * 13
    assert family._plan(cfg) == ("", "FWWW", 13)
    assert reference._plan(cfg) == ([], [(0, 0), (1, 1), (1, 1), (1, 1)], 13)
    assert (cfg.num_layers, cfg.num_experts, cfg.held, cfg.top_k,
            cfg.held_range, cfg.sliding_window, cfg.rope_theta) == (
                52, 64, 8, 6, (0, 64), 4096, 1500000)
    # ISSUE 44's terms, one by one
    assert reference.attention_params(cfg) == 20_971_520
    assert reference.expert_params(cfg) == 5_898_240
    assert reference._layer_params(cfg, 8) == 68_326_400
    assert reference._layer_params(cfg, 64) == 398_627_840
    assert reference.num_params(cfg) == family.num_params(cfg) == 3_650_214_400
    assert reference.weight_bytes(cfg) == 2 * (3_650_214_400 - 18992 * 2560)
    # a token's 6 picks of 64 land on the 8 held experts 0.75 times a layer
    assert reference.active_params(cfg) == pytest.approx(
        3_650_214_400 - 18992 * 2560 - 2560 - 52 * 7.25 * 5_898_240)
    # K and V, 4 heads of 128, bf16: 2,048 B a token and layer, by kind
    assert (reference.full_layers(cfg), reference.window_layers(cfg)) == (13, 39)
    assert reference.kv_bytes_per_token(cfg) == 13 * 2048 == 26_624
    assert reference.window_kv_bytes_per_token(cfg) == 39 * 2048
    assert reference.attn_flops_per_pair(cfg) == 13 * 4 * 28 * 128
    assert reference.window_attn_flops_per_pair(cfg) == 39 * 4 * 28 * 128
    tree = jax.eval_shape(lambda: family.init_params(cfg, jax.random.PRNGKey(0)))
    assert sum(int(np.prod(a.shape))
               for a in jax.tree_util.tree_leaves(tree)) == 3_650_214_400
    engine = spec["config"]["serve"]["engine"]
    cache = jax.eval_shape(lambda: family.init_paged_cache(
        cfg, engine["num_blocks"], 128, jax.numpy.bfloat16,
        num_slots=engine["max_seqs"] + 1))
    assert cache["k"].shape == (13, engine["num_blocks"], 128, 512)
    # 16 slots x 33 blocks (a window of 4,096 over 128-token blocks) + scratch
    assert cache["swa"]["k"].shape == (39, 16 * 33 + 1, 128, 512)
    block = 128 * 512 * 2 * 2
    assert (13 * block, 39 * block) == (3_407_872, 10_223_616)
    sliding = 39 * 529 * block
    assert 5.40e9 < sliding < 5.42e9
    # at 8,192 tokens: 64 full blocks + 33 sliding ones where one table holds 64 of both
    assert 64 * 13 * block + 33 * 39 * block == pytest.approx(555e6, rel=0.01)
    assert 64 * 52 * block == pytest.approx(872e6, rel=0.01)
    built = family.build(cfg)
    assert built.sliding_window == 4096 and built.decode_bucket_min == 16
    assert [built.moe_form(r) for r in (16, 255, 256, 528)] == [
        "dense", "dense", "grouped", "grouped"]


def test_the_tiny_family_runs_through_the_harness_end_to_end(copy, tmp_path):
    """The rehearsal of the chip run: a tiny ``smallthinker`` (rank 1 of 2; a
    window of 32 over 16-token blocks, so requests of 40-100 tokens slide it)
    added as files only, every step program warmed, a closed loop over HTTP,
    both pools whole after the drain, the served tokens against
    ``reference/smallthinker.py``."""
    import jax
    import numpy as np

    root = copy({
        "benchmark/configs/tiny-st.json": TINY_ST,
        "benchmark/traffic/tiny-pool.json": TINY_POOL,
        "benchmark/cells/tiny.cell.json": {"clients": 3},
    }, configs=[{"name": "tiny-st", "source": "test", "reduced": [],
                 "file": "benchmark/configs/tiny-st.json", "why": "tiny"}],
       workloads=[{"name": "tiny.cell", "config": "tiny-st",
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
    assert cfg.layer_pattern == "FWWWFWWW" and cfg.held_range == (4, 8)
    tree = family.init_params(cfg, jax.random.PRNGKey(0))
    assert reference.num_params(cfg) == sum(
        int(np.prod(a.shape)) for a in jax.tree_util.tree_leaves(tree))
    reference.Q_BLOCK = 64
    raw = runner.run_cell(spec, seed=2**31 + 44, seconds=3.0, trace=False,
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


def test_the_planted_faults_come_out_as_not_correct(copy):
    """``check_controls.py`` at the tiny size: the served path correct, the
    reference in float8 and the window one block short not correct, each
    through ``serve_cell.ServeRig.check`` (the chip run reads the cell's)."""
    import check_controls

    slides = {**TINY_POOL,
              "prompt_tokens": {"dist": "uniform", "min": 48, "max": 72},
              "output_tokens": {"dist": "fixed", "value": 24}}
    root = copy({
        "benchmark/configs/tiny-st.json": TINY_ST,
        "benchmark/traffic/tiny-slides.json": slides,
    }, configs=[{"name": "tiny-st", "source": "test", "reduced": [],
                 "file": "benchmark/configs/tiny-st.json", "why": "tiny"}],
       workloads=[{"name": "tiny.cell", "config": "tiny-st",
                   "traffic": "tiny-slides", "chips": 1, "why": "on the CPU"}])
    spec = cellspec.resolve("tiny.cell", root=root)
    out = check_controls.controls(spec, seed=2**31 + 44)
    print(json.dumps(out))
    assert min(out["prompt_lens"]) >= 32
    assert out["served"]["ok"] and out["served"]["tokens"] == 72
    assert not out["lower"]["ok"] and not out["edge"]["ok"]


# ------------------------------------------------- the readers' arithmetic
def _ctx(tl: dict) -> dict:
    spec = cellspec.resolve(CELL)
    _, cfg, reference = cellspec.model(spec)
    busy = sum(b - a for a, b in tl["busy"]) * 1e-9
    window = {"host_spans": tl, "seconds": 51.0, "counters": {},
              "trace": {"busy_s": busy, "window_s": busy,
                        "kernel_s": {k: sum(d for _, d in v) * 1e-9
                                     for k, v in tl["kernels"].items()}}}
    return {"window": window, "spec": spec, "chips": 1,
            "peaks": cellspec.peaks_for(spec, "TPU v5 lite"),
            "end_to_end": {}, "cfg": cfg, "reference": reference}


def _synthetic(window: bool = True) -> dict:
    """Two dispatches and their executions: a mixed step of 16 decode rows at
    ~5K context beside 4 tiles of one 6K-token prompt, and a decode step."""
    ms = 1e6
    steps = [("ragged_step_d16_t4", 0.0, 30 * ms,
              {"tokens": 528, "kv_tokens": 86_000, "attn_pairs": 3_150_000,
               "dec_kv_tokens": 80_000, "win_kv_tokens": 64_000,
               "dec_win_kv_tokens": 60_000, "win_attn_pairs": 2_150_000,
               "full_blocks_busy": 600, "win_blocks_busy": 420}),
             ("ragged_step_d16_t0", 31 * ms, 18 * ms,
              {"tokens": 16, "kv_tokens": 81_000, "attn_pairs": 81_000,
               "dec_kv_tokens": 81_000, "win_kv_tokens": 61_000,
               "dec_win_kv_tokens": 61_000, "win_attn_pairs": 61_000,
               "full_blocks_busy": 640, "win_blocks_busy": 448})]
    if not window:
        steps = [(n, s, d, {k: v for k, v in a.items() if "win" not in k
                            and "blocks_busy" not in k})
                 for n, s, d, a in steps]
    host = [["engine/dispatch", start + 0.1 * ms, 0.2 * ms,
             {"program": name, "pad": 0, "moe": "grouped", **args}]
            for name, start, _, args in steps]
    return {"host": [{"thread": "engine", "events": host}],
            "modules": [[f"jit_{n}(1)", s + 0.5 * ms, d] for n, s, d, _ in steps],
            "busy": [[s + 0.5 * ms, s + 0.5 * ms + d] for _, s, d, _ in steps],
            "kernels": {"swa_decode": [[2 * ms, 7 * ms], [33 * ms, 7 * ms]],
                        "swa_prefill": [[10 * ms, 4 * ms]],
                        "paged_decode": [[15 * ms, 3 * ms], [41 * ms, 3 * ms]],
                        "tiled_prefill": [[19 * ms, 2 * ms]]}}


def test_the_new_readers_by_hand():
    spec = cellspec.resolve(CELL)
    readers = cellspec.layer_readers(spec)
    _, cfg, reference = cellspec.model(spec)
    ctx = _ctx(_synthetic())
    hbm, mxu = 819e9, 197e12
    assert readers["kernel.swa_decode_share"][1](ctx) == pytest.approx(
        100 * 14 / 48)
    assert readers["kernel.swa_prefill_share"][1](ctx) == pytest.approx(
        100 * 4 / 48)
    # 121,000 window rows of 79,872 B at the peak, over the kernel's 14 ms
    assert readers["kernel.swa_decode_roofline"][1](ctx) == pytest.approx(
        100 * (121_000 * 39 * 2048 / hbm) / 14e-3)
    # the tiles' 2,090,000 pairs inside the window x 39 layers x 14,336 FLOP
    assert readers["kernel.swa_prefill_roofline"][1](ctx) == pytest.approx(
        100 * (2_090_000 * 39 * 14_336 / mxu) / 4e-3)
    # the full layers' kernels through the hybrid cell's readers: 13 layers
    assert readers["kernel.hybrid_paged_decode_roofline"][1](ctx) == \
        pytest.approx(100 * (161_000 * 13 * 2048 / hbm) / 6e-3)
    assert readers["kernel.hybrid_tiled_prefill_roofline"][1](ctx) == \
        pytest.approx(100 * (3_070_000 * 13 * 14_336 / mxu) / 2e-3)
    assert readers["sched.window_held_share"][1](ctx) == pytest.approx(
        100 * 868 / 1240)
    bytes_s = (2 * reference.weight_bytes(cfg) + 26_624 * 167_000
               + 79_872 * 125_000) / hbm
    flops_s = (2 * reference.active_params(cfg) * 544
               + 13 * 14_336 * 3_231_000 + 39 * 14_336 * 2_211_000) / mxu
    assert readers["model.swa_step_roofline_kv"][1](ctx) == pytest.approx(
        100 * max(bytes_s, flops_s) / 48e-3)
    assert 0 < readers["model.swa_step_roofline_kv"][1](ctx) < 100
    # a program without the window's arguments (every other family, the
    # parent), a program without spans: nothing, and nothing raised
    for bare in (_synthetic(window=False), dict(_synthetic(), host=[])):
        ctx = _ctx(bare)
        for name in NEW_READERS:
            if not name.endswith("_share") or name == "sched.window_held_share":
                assert readers[name][1](ctx) is None, name
    # a trace without the kernels (the parent's): no share of nothing
    none = dict(_synthetic(), kernels={"paged_decode": [[15e6, 3e6]]})
    for name in NEW_READERS[:4]:
        assert readers[name][1](_ctx(none)) is None, name
