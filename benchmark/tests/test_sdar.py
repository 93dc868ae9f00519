"""PR 47's additions to the benchmark: the ``sdar`` family as files only (a
configuration, a traffic mix, a cell, a reference, two kernels' patterns,
seven readers and their helper, the planted faults), the published sizes and
the cut's arithmetic, the readers by hand, and the rehearsal of the chip run
at a tiny size."""

import json
import os
import re

import pytest

import cellspec
import run as runner
from conftest import TINY_GPT2, TINY_POOL

CELL = "sdar-30b-a3b-d7.blockgen-pool"
NEW_READERS = ("kernel.blk_decode_share", "kernel.blk_decode_roofline",
               "kernel.blk_prefill_share", "kernel.blk_prefill_roofline",
               "model.blk_step_roofline_kv", "sched.blk_passes_per_token",
               "sched.blk_commit_share")
# the catalog row's ``config`` (guides/model-configs/architectures.jsonl)
PUBLISHED = {
    "attention_bias": False, "decoder_sparse_step": 1, "head_dim": 128,
    "hidden_act": "silu", "hidden_size": 2048, "intermediate_size": 6144,
    "max_position_embeddings": 32768, "max_window_layers": 48,
    "mlp_only_layers": [], "model_type": "sdar_moe",
    "moe_intermediate_size": 768, "norm_topk_prob": True,
    "num_attention_heads": 32, "num_experts": 128, "num_experts_per_tok": 8,
    "num_hidden_layers": 48, "num_key_value_heads": 4, "rms_norm_eps": 1e-06,
    "rope_scaling": None, "rope_theta": 1000000, "sliding_window": None,
    "tie_word_embeddings": False, "use_sliding_window": False,
    "vocab_size": 151936}

TINY_SDAR = {
    "source": "test", "family": "sdar", "config_class": "SdarConfig",
    "fields": {"vocab_size": "vocab_size", "hidden_size": "hidden_size",
               "moe_intermediate_size": "moe_intermediate_size",
               "num_layers": "num_hidden_layers",
               "num_heads": "num_attention_heads",
               "num_kv_heads": "num_key_value_heads", "head_dim": "head_dim",
               "num_experts": "num_experts", "top_k": "num_experts_per_tok",
               "max_seq_len": "max_position_embeddings",
               "block_length": "block_length",
               "denoise_steps": "denoise_steps",
               "remask": "remasking_strategy",
               "mask_token_id": "mask_token_id"},
    "vocab_size": 256, "hidden_size": 64, "moe_intermediate_size": 32,
    "num_hidden_layers": 2, "num_attention_heads": 4,
    "num_key_value_heads": 2, "head_dim": 32, "num_experts": 8,
    "num_experts_per_tok": 2, "max_position_embeddings": 2048,
    "block_length": 4, "denoise_steps": 2,
    "remasking_strategy": "sequential", "mask_token_id": 255,
    "reduced": [],
    "serve": {**TINY_GPT2["serve"],
              # top-2 of 8 picks flip under bf16 on a 64-wide model
              "check": {"match_rate_min": 0.5}},
}
# the cell's shape at a tiny size: a fixed prompt of whole blocks
TINY_BLOCKGEN = {**TINY_POOL,
                 "prompt_tokens": {"dist": "fixed", "value": 32},
                 "output_tokens": {"dist": "uniform", "min": 9, "max": 30}}


def _copy(copy, mix, match_rate_min=0.5):
    conf = {**TINY_SDAR, "serve": {**TINY_SDAR["serve"], "check": {
        "match_rate_min": match_rate_min}}}
    root = copy({
        "benchmark/configs/tiny-sdar.json": conf,
        "benchmark/traffic/tiny-blockgen.json": mix,
        "benchmark/cells/tiny.cell.json": {"clients": 3},
    }, configs=[{"name": "tiny-sdar", "source": "test", "reduced": [],
                 "file": "benchmark/configs/tiny-sdar.json", "why": "tiny"}],
       workloads=[{"name": "tiny.cell", "config": "tiny-sdar",
                   "traffic": "tiny-blockgen", "chips": 1,
                   "why": "on the CPU"}])
    path = os.path.join(root, "BENCHMARK.json")
    with open(path) as f:
        bench = json.load(f)
    for m in bench["end_to_end"] + bench["per_layer"]:
        if CELL in m.get("workloads", []):
            m["workloads"].append("tiny.cell")
    with open(path, "w") as f:
        json.dump(bench, f)
    return root


def test_the_new_cell_resolves_with_the_traffic_as_the_issue_wrote_it():
    spec = cellspec.resolve(CELL)
    assert spec["chips"] == 1 and spec["cell"] == {"clients": 96}
    assert spec["traffic_name"] == "blockgen-pool"
    mix = spec["mix"]
    assert mix["kind"] == "closed_loop" and mix["stream"] is False
    assert mix["prompt_tokens"] == {"dist": "fixed", "value": 512}
    assert mix["output_tokens"] == {"dist": "uniform", "min": 512, "max": 1024}
    assert (mix["total_tokens_max"], mix["lead_seconds"], mix["grace_seconds"],
            mix["warm_requests"], mix["warm_max_tokens"]) == (2048, 24, 35, 4, 8)
    assert mix["limits"] == {"ttft_ms": 2000, "gap_ms": 200}
    engine = spec["config"]["serve"]["engine"]
    assert engine == {"block_size": 128, "num_blocks": 1537, "max_seqs": 96,
                      "max_tokens_per_step": 512, "max_blocks_per_seq": 16,
                      "prefill_tile": 128}
    assert mix["total_tokens_max"] == engine["block_size"] * engine["max_blocks_per_seq"]
    # the check can replay a prompt of whole blocks only (reference/sdar.py)
    assert mix["prompt_tokens"]["value"] % spec["config"]["block_length"] == 0
    assert {m["name"] for m in spec["end_to_end"]} == {"serve_tokens_per_s", "setup_s"}
    # <=, not ==: a later PR may append this cell to further metrics' lists
    assert set(NEW_READERS) | {
        "serve.request_p50_ms", "sched.pad_share", "sched.cold_dispatches",
        "model.step_roofline", "sched.mixed_step_ms_p50",
        "sched.pool_decode_step_ms_p50", "sched.moe_grouped_share",
        "kernel.moe_gmm_share", "setup.cache_hit_share",
        "setup.program_builds", "setup.trace_s", "setup.lower_s",
        "setup.compile_s", "setup.cache_retrieval_s",
        "setup.background_compile_s", "setup.engine_init_s",
        "setup.unattributed_s"} <= {m["name"] for m in spec["per_layer"]}
    with open(os.path.join(os.path.dirname(spec["base"]), "BENCHMARK.json")) as f:
        bench = json.load(f)
    assert len(bench["configs"]) >= 9 and len(bench["workloads"]) >= 11
    assert [w["chips"] for w in bench["workloads"]].count(4) == 1
    why = next(w["why"] for w in bench["workloads"] if w["name"] == CELL)
    assert len(why) <= 200 and "closed loop, 96 clients" in why
    for m in bench["per_layer"]:
        if m["name"] in NEW_READERS:
            assert m["workloads"] == [CELL] and m["moves"] == "serve_tokens_per_s"
    for kernel, other in (("blk_decode", "paged_decode"),
                          ("blk_prefill", "tiled_prefill")):
        with open(os.path.join(spec["base"], "kernels", kernel + ".json")) as f:
            mine = re.compile(json.load(f)["trace_pattern"])
        with open(os.path.join(spec["base"], "kernels", other + ".json")) as f:
            theirs = re.compile(json.load(f)["trace_pattern"])
        line = f"%{kernel}.3 = bf16[96,32,512] custom-call(%p)"
        assert mine.search(line) and not theirs.search(line)
        assert not mine.search(f"%{other}.3 = bf16[96,8,512] custom-call(%p)")


def test_the_configuration_is_the_catalog_entry_but_for_its_cut():
    conf = cellspec.resolve(CELL)["config"]
    differs = {k for k, v in PUBLISHED.items() if conf.get(k, "absent") != v}
    assert differs == set(conf["reduced"]) == {"num_hidden_layers"}
    assert conf["num_hidden_layers"] == 7
    assert conf["source"].endswith("SDAR-30B-A3B-Chat/blob/main/config.json")
    assert (conf["block_length"], conf["denoise_steps"],
            conf["remasking_strategy"], conf["mask_token_id"]) == (
                4, 2, "sequential", 151669)
    assert {"block_length", "denoise_steps", "remasking", "prefill",
            "logits", "mask_token", "masked_is_a_state", "attention", "rope",
            "weights", "noise_schedule"} <= set(conf["assumed"])
    assert "seven" in conf["deployment"]
    assert "4,984,174,336" in conf["reduced_why"]
    assert 0.0 < conf["serve"]["check"]["match_rate_min"] < 1.0
    assert len(conf["serve"]["check"]["why"]) > 200


def test_the_sizes_of_the_cut():
    import jax
    import numpy as np

    spec = cellspec.resolve(CELL)
    family, cfg, reference = cellspec.model(spec)
    assert (cfg.num_layers, cfg.num_experts, cfg.top_k, cfg.head_dim,
            cfg.num_heads, cfg.num_kv_heads, cfg.rope_theta) == (
                7, 128, 8, 128, 32, 4, 1000000)
    gen = family.build(cfg).block_gen
    assert (gen.length, gen.steps, gen.remask, gen.mask_token_id,
            gen.unmask) == (4, 2, "sequential", 151669, 2)
    # ISSUE 47's terms, one by one: attention 18,874,368 + q/k gains 256 +
    # norms 4,096 + router 262,144 + 128 x 4,718,592
    assert reference._layer_params(cfg, 128) == 623_120_640
    assert reference._layer_params(cfg, 0) == 18_874_368 + 256 + 4_096 + 262_144
    assert reference.num_params(cfg) == family.num_params(cfg) == \
        7 * 623_120_640 + 2 * 151_936 * 2_048 + 2_048 == 4_984_176_384
    assert reference.weight_bytes(cfg) == 2 * (4_984_176_384 - 151_936 * 2_048)
    assert reference.active_params(cfg) == \
        151_936 * 2_048 + 7 * (19_140_864 + 8 * 4_718_592)
    # K and V, 4 heads of 128, bf16: 2,048 B a token and layer
    assert reference.kv_bytes_per_token(cfg) == 7 * 2_048 == 14_336
    assert reference.attn_flops_per_pair(cfg) == 7 * 4 * 32 * 128
    tree = jax.eval_shape(lambda: family.init_params(cfg, jax.random.PRNGKey(0)))
    assert sum(int(np.prod(a.shape))
               for a in jax.tree_util.tree_leaves(tree)) == 4_984_176_384
    engine = spec["config"]["serve"]["engine"]
    cache = jax.eval_shape(lambda: family.init_paged_cache(
        cfg, engine["num_blocks"], 128, jax.numpy.bfloat16))
    assert cache["k"].shape == (7, 1537, 128, 512)
    assert 2 * 7 * 1537 * 128 * 512 * 2 == 2_820_407_296
    built = family.build(cfg)
    assert built.decode_bucket_min == 4 and built.sliding_window is None
    # 96 decoding sequences are 384 rows: the grouped form; 32 are 128: dense
    assert [built.moe_form(r) for r in (16, 128, 256, 384, 512)] == [
        "dense", "dense", "grouped", "grouped", "grouped"]


def test_the_tiny_family_runs_through_the_harness_end_to_end(copy, tmp_path):
    """The rehearsal of the chip run: a tiny ``sdar`` added as files only,
    every step program warmed by the harness's own enumeration (which counts
    a decode bucket in sequences, as this engine does), a closed loop over
    HTTP, the pool whole after the drain, the served tokens against
    ``reference/sdar.py`` through the harness's own check."""
    import jax
    import numpy as np

    root = _copy(copy, TINY_BLOCKGEN)
    spec = cellspec.resolve("tiny.cell", root=root)
    family, cfg, reference = cellspec.model(spec)
    tree = family.init_params(cfg, jax.random.PRNGKey(0))
    assert reference.num_params(cfg) == sum(
        int(np.prod(a.shape)) for a in jax.tree_util.tree_leaves(tree))
    reference.Q_BLOCK = 64
    raw = runner.run_cell(spec, seed=2**31 + 47, seconds=3.0, trace=False,
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
    """``blk_controls.py`` at the tiny size: the served path correct; the
    reference in float8, with a causal mask inside a block, without the
    commit, read shifted and replayed at another ``T`` not correct, each
    through ``serve_cell.ServeRig.check`` (the chip run reads the cell's)."""
    import blk_controls

    longer = {**TINY_BLOCKGEN,
              "output_tokens": {"dist": "fixed", "value": 48}}
    # a model 64 wide agrees with itself on every pick and, half blind, on
    # four in five (the chip's cell: PERF.md section 6, PR 47)
    spec = cellspec.resolve("tiny.cell", root=_copy(copy, longer, 0.98))
    _, _, reference = cellspec.model(spec)
    reference.Q_BLOCK = 16
    out = blk_controls.controls(spec, seed=2**31 + 47)
    print(json.dumps(out))
    assert out["served"]["ok"] and out["served"]["tokens"] == 144
    assert out["one_token_blocks_share"] < 0.5
    for name in blk_controls.PLANTED:
        assert not out[name]["ok"], name


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


def _synthetic(blocks: bool = True) -> dict:
    """Two dispatches and their executions: 96 blocks at ~1.3K context beside
    one 128-row tile at 384, and a step of blocks alone (32 of them commit)."""
    ms = 1e6
    steps = [("ragged_step_d96_t1", 0.0, 20 * ms,
              {"tokens": 512, "kv_tokens": 125_312, "attn_pairs": 556_800,
               "dec_kv_tokens": 124_800, "blk_seqs": 96,
               "blk_commit_seqs": 30, "blk_unmasked": 132, "blk_len": 4,
               "blk_steps": 2}),
             ("ragged_step_d96_t0", 21 * ms, 14 * ms,
              {"tokens": 384, "kv_tokens": 124_900, "attn_pairs": 499_600,
               "dec_kv_tokens": 124_900, "blk_seqs": 96,
               "blk_commit_seqs": 32, "blk_unmasked": 128, "blk_len": 4,
               "blk_steps": 2})]
    if not blocks:
        steps = [(n, s, d, {k: v for k, v in a.items() if "blk" not in k})
                 for n, s, d, a in steps]
    host = [["engine/dispatch", start + 0.1 * ms, 0.2 * ms,
             {"program": name, "pad": 0, "moe": "grouped", **args}]
            for name, start, _, args in steps]
    return {"host": [{"thread": "engine", "events": host}],
            "modules": [[f"jit_{n}(1)", s + 0.5 * ms, d] for n, s, d, _ in steps],
            "busy": [[s + 0.5 * ms, s + 0.5 * ms + d] for _, s, d, _ in steps],
            "kernels": {"blk_decode": [[2 * ms, 3 * ms], [23 * ms, 3 * ms]],
                        "blk_prefill": [[6 * ms, 1 * ms]],
                        "moe_gmm": [[8 * ms, 5 * ms], [27 * ms, 4 * ms]]}}


def test_the_new_readers_by_hand():
    spec = cellspec.resolve(CELL)
    readers = cellspec.layer_readers(spec)
    _, cfg, reference = cellspec.model(spec)
    ctx = _ctx(_synthetic())
    hbm, mxu = 819e9, 197e12
    assert readers["kernel.blk_decode_share"][1](ctx) == pytest.approx(
        100 * 6 / 34)
    assert readers["kernel.blk_prefill_share"][1](ctx) == pytest.approx(
        100 * 1 / 34)
    # 249,700 context rows of 14,336 B at the peak, once a sequence and pass,
    # over the kernel's 6 ms
    assert readers["kernel.blk_decode_roofline"][1](ctx) == pytest.approx(
        100 * (249_700 * 14_336 / hbm) / 6e-3)
    # the tile's 57,600 pairs (128 queries at 384, their blocks whole) x 7
    # layers x 16,384 FLOP over the kernel's 1 ms
    assert 556_800 - 4 * 124_800 == 57_600 == 128 * 384 + 128 * (128 + 4) // 2
    assert readers["kernel.blk_prefill_roofline"][1](ctx) == pytest.approx(
        100 * max(57_600 * 7 * 16_384 / mxu, 512 * 14_336 / hbm) / 1e-3)
    # the step: weights once a dispatch + K and V once a sequence and pass,
    # over the two executions' 34 ms
    weights = 2 * reference.weight_bytes(cfg)
    kv = (125_312 + 124_900) * 14_336
    flops = (2.0 * reference.active_params(cfg) * (512 + 384)
             + 7 * 16_384 * (556_800 + 499_600))
    assert readers["model.blk_step_roofline_kv"][1](ctx) == pytest.approx(
        100 * max((weights + kv) / hbm, flops / mxu) / 34e-3)
    assert readers["sched.blk_passes_per_token"][1](ctx) == pytest.approx(
        192 / 260)
    assert readers["sched.blk_commit_share"][1](ctx) == pytest.approx(
        100 * 62 / 192)
    for name in NEW_READERS:
        value = readers[name][1](ctx)
        if readers[name][0]["unit"] == "%":
            assert 0.0 <= value <= 100.0
    # a program that writes no such argument: no value, no error
    bare = _ctx(_synthetic(blocks=False))
    for name in NEW_READERS:
        if "share" in name and name.startswith("kernel."):
            continue   # a kernel's share reads the device trace alone
        assert readers[name][1](bare) is None, name
