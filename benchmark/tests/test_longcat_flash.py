"""PR 38's additions to the benchmark: the ``longcat_flash`` family as files
only (a configuration, a cell on the mix the benchmark had, a reference, two
readers), the published sizes and the cut's arithmetic, the readers on a slice
recorded on the chip, and the rehearsal of the chip run at a tiny size."""

import gzip
import json
import os

import pytest

import cellspec
import run as runner
from conftest import TINY_GPT2, TINY_POOL

CELL = "longcat-flash-omni-d4-ep32.reason-pool"
NEW_READERS = ("sched.moe_zero_pick_share", "sched.moe_held_rows_per_expert")
FIXTURE = os.path.join(os.path.dirname(__file__), "fixtures", "spans",
                       "v5e_longcat_flash_reason_spans")

TINY_LONGCAT = {
    "source": "test", "family": "longcat_flash",
    "config_class": "LongcatFlashConfig",
    "fields": {"vocab_size": "vocab_size", "hidden_size": "hidden_size",
               "ffn_hidden_size": "ffn_hidden_size",
               "expert_ffn_hidden_size": "expert_ffn_hidden_size",
               "num_layers": "num_layers", "num_heads": "num_attention_heads",
               "kv_lora_rank": "kv_lora_rank", "q_lora_rank": "q_lora_rank",
               "qk_nope_head_dim": "qk_nope_head_dim",
               "qk_rope_head_dim": "qk_rope_head_dim",
               "v_head_dim": "v_head_dim",
               "num_experts": "n_routed_experts_published",
               "experts_held": "n_routed_experts", "expert_rank": "expert_rank",
               "zero_expert_num": "zero_expert_num", "top_k": "moe_topk",
               "rope_theta": "rope_theta",
               "max_seq_len": "max_position_embeddings"},
    "vocab_size": 256, "hidden_size": 64, "ffn_hidden_size": 96,
    "expert_ffn_hidden_size": 48, "num_layers": 2, "num_attention_heads": 2,
    "kv_lora_rank": 32, "q_lora_rank": 24, "qk_nope_head_dim": 16,
    "qk_rope_head_dim": 16, "v_head_dim": 16,
    "n_routed_experts_published": 8, "n_routed_experts": 4, "expert_rank": 1,
    "zero_expert_num": 4, "moe_topk": 3, "rope_theta": 10000,
    "max_position_embeddings": 2048, "reduced": [],
    "serve": {**TINY_GPT2["serve"],
              # 3 of 12 softmax scores flip under bf16 on a 64-wide model
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
    assert (mix["total_tokens_max"], mix["lead_seconds"], mix["grace_seconds"],
            mix["warm_requests"]) == (4096, 24, 35, 4)
    engine = spec["config"]["serve"]["engine"]
    assert engine == {"block_size": 128, "num_blocks": 2049, "max_seqs": 128,
                      "max_tokens_per_step": 512, "max_blocks_per_seq": 32,
                      "prefill_tile": 128}
    assert mix["total_tokens_max"] == engine["block_size"] * engine["max_blocks_per_seq"]
    assert {m["name"] for m in spec["end_to_end"]} == {"serve_tokens_per_s", "setup_s"}
    # <=, not ==: a later PR may append this cell to further metrics' lists
    assert set(NEW_READERS) | {
        "serve.request_p50_ms", "sched.pad_share", "sched.cold_dispatches",
        "model.step_roofline", "sched.mixed_step_ms_p50",
        "sched.pool_decode_step_ms_p50", "kernel.mla_decode_share",
        "kernel.mla_decode_roofline", "kernel.mla_prefill_share",
        "kernel.mla_prefill_roofline", "model.mla_step_roofline_kv",
        "sched.moe_grouped_share", "kernel.moe_gmm_share",
        "setup.cache_hit_share", "setup.program_builds", "setup.trace_s",
        "setup.lower_s", "setup.compile_s", "setup.cache_retrieval_s",
        "setup.background_compile_s", "setup.engine_init_s",
        "setup.unattributed_s"} <= {m["name"] for m in spec["per_layer"]}
    assert "kernel.attn_share" not in {m["name"] for m in spec["per_layer"]}
    with open(os.path.join(os.path.dirname(spec["base"]), "BENCHMARK.json")) as f:
        bench = json.load(f)
    assert len(bench["configs"]) >= 6 and len(bench["workloads"]) >= 8
    assert [w["chips"] for w in bench["workloads"]].count(4) == 1
    why = next(w["why"] for w in bench["workloads"] if w["name"] == CELL)
    assert len(why) <= 200 and "closed loop, 128 clients" in why
    assert ("held experts a thirty-second of 32 chips' rows; attention and the "
            "dense FFNs their full share") in why
    for m in bench["per_layer"]:
        if m["name"] in NEW_READERS:
            assert m["workloads"] == [CELL] and m["moves"] == "serve_tokens_per_s"
            assert (m["layer"], m["source"]) == ("ragged scheduler", "program_span")


def test_the_configuration_is_the_catalog_entry_but_for_its_cut():
    conf = cellspec.resolve(CELL)["config"]
    published = {
        "attention_bias": False, "vocab_size": 131072, "hidden_size": 6144,
        "ffn_hidden_size": 12288, "expert_ffn_hidden_size": 2048,
        "num_layers": 28, "num_attention_heads": 64, "kv_lora_rank": 512,
        "q_lora_rank": 1536, "qk_rope_head_dim": 64, "v_head_dim": 128,
        "qk_nope_head_dim": 128, "mla_scale_q_lora": True,
        "mla_scale_kv_lora": True, "routed_scaling_factor": 6,
        "n_routed_experts": 512, "max_position_embeddings": 131072,
        "rms_norm_eps": 1e-05, "rope_theta": 10000000,
        "attention_method": "MLA", "zero_expert_num": 256,
        "zero_expert_type": "identity", "moe_topk": 12}
    differs = {k for k, v in published.items() if conf.get(k, "absent") != v}
    assert differs == set(conf["reduced"]) == {
        "num_layers", "n_routed_experts", "vocab_size"}
    assert (conf["num_layers"], conf["n_routed_experts"],
            conf["vocab_size"]) == (4, 16, 16384)
    assert (conf["n_routed_experts_published"], conf["expert_rank"],
            conf["expert_ranks"], conf["vocab_size_published"]) == (
                512, 0, 32, 131072)
    assert conf["vocab_size"] * 8 == conf["vocab_size_published"]
    assert conf["n_routed_experts"] * conf["expert_ranks"] == 512
    assert conf["source"].endswith("LongCat-Flash-Omni/blob/main/config.json")
    assert {"mla_scale_placement", "router", "hidden_act",
            "tie_word_embeddings", "rope", "omni", "weights"} <= set(conf["assumed"])
    assert "thirty-two" in conf["deployment"]
    assert "5,172,749,312" in conf["reduced_why"]
    assert 0.0 < conf["serve"]["check"]["match_rate_min"] < 1.0
    assert len(conf["serve"]["check"]["why"]) > 200


def test_the_sizes_of_the_cut():
    import jax
    import numpy as np

    family, cfg, reference = cellspec.model(cellspec.resolve(CELL))
    assert (cfg.num_layers, cfg.num_experts, cfg.held, cfg.zero_expert_num,
            cfg.top_k, cfg.held_share, cfg.routed_scaling_factor) == (
                4, 512, 16, 256, 12, (0, 512), 6)
    assert cfg.softmax_scale == 192 ** -0.5 and cfg.row_lanes == 640
    assert reference.num_params(cfg) == family.num_params(cfg) == 5_172_749_312
    assert reference._sublayer_params(cfg) == 90_572_800 + 12_288 + 226_492_416
    assert reference._layer_params(cfg, 0) == 638_874_368
    assert reference._layer_params(cfg, 16) == 1_242_854_144
    assert reference.weight_bytes(cfg) == 2 * (5_172_749_312 - 16384 * 6144)
    # a token needs 12 x 16 / 768 = 0.25 of a held expert a layer
    assert reference.active_params(cfg) == pytest.approx(
        5_172_749_312 - 16384 * 6144 - 6144 - 4 * (16 - 0.25) * 37_748_736)
    # two rows a token and layer, two attentions a layer
    assert reference.kv_bytes_per_token(cfg) == 8 * 1152
    assert reference.attn_flops_per_pair(cfg) == 8 * 2 * 64 * (2 * 512 + 64)
    assert reference.held_expert_slots(cfg) == 16 * 4
    tree = jax.eval_shape(lambda: family.init_params(cfg, jax.random.PRNGKey(0)))
    assert sum(int(np.prod(a.shape))
               for a in jax.tree_util.tree_leaves(tree)) == 5_172_749_312
    cache = jax.eval_shape(lambda: family.init_paged_cache(
        cfg, 2049, 128, jax.numpy.bfloat16))
    assert cache["kv"].shape == (8, 2049, 128, 640)
    pool = int(np.prod(cache["kv"].shape)) * 2
    assert pool == 2049 * 128 * 10_240 and 2.68e9 < pool < 2.69e9
    spec = family.build(cfg)
    assert spec.step_counters == ("moe_picks", "moe_zero_picks", "moe_held_picks")
    assert spec.decode_bucket_min == 128
    assert [spec.moe_form(r) for r in (128, 255, 256, 512)] == [
        "dense", "dense", "grouped", "grouped"]


def test_the_tiny_family_runs_through_the_harness_end_to_end(copy, tmp_path):
    """The rehearsal of the chip run: a tiny ``longcat_flash`` (rank 1 of 2,
    4 zero-compute outputs) added as files only, every step program warmed, a
    closed loop over HTTP, the served tokens against
    ``reference/longcat_flash.py``; the step programs' counts reach the engine."""
    import jax
    import numpy as np

    root = copy({
        "benchmark/configs/tiny-longcat.json": TINY_LONGCAT,
        "benchmark/traffic/tiny-pool.json": TINY_POOL,
        "benchmark/cells/tiny.cell.json": {"clients": 3},
    }, configs=[{"name": "tiny-longcat", "source": "test", "reduced": [],
                 "file": "benchmark/configs/tiny-longcat.json", "why": "tiny"}],
       workloads=[{"name": "tiny.cell", "config": "tiny-longcat",
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
    reference.Q_BLOCK = 64
    raw = runner.run_cell(spec, seed=2**31 + 38, seconds=3.0, trace=False,
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


def _synthetic(counts: bool = True) -> dict:
    """Three dispatches and their executions: a mixed step of 128 rows and 3
    tiles carrying nothing yet, a decode step carrying the mixed step's counts
    (512 tokens x 12 x 4 layers), and one carrying the decode step's."""
    ms = 1e6
    steps = [("ragged_step_d128_t3", 0.0, 30 * ms,
              {"tokens": 512, "moe": "grouped", "moe_picks": 0,
               "moe_zero_picks": 0, "moe_held_picks": 0}),
             ("ragged_step_d128_t0", 31 * ms, 20 * ms,
              {"tokens": 128, "moe": "dense", "moe_picks": 512 * 48,
               "moe_zero_picks": 8000, "moe_held_picks": 520}),
             ("ragged_step_d128_t0", 52 * ms, 20 * ms,
              {"tokens": 128, "moe": "dense", "moe_picks": 128 * 48,
               "moe_zero_picks": 2240, "moe_held_picks": 120})]
    if not counts:
        steps = [(n, s, d, {k: v for k, v in a.items() if not k.startswith("moe_")})
                 for n, s, d, a in steps]
    host = [["engine/dispatch", start + 0.1 * ms, 0.2 * ms,
             {"program": name, "pad": 0, "kv_tokens": 1000, "attn_pairs": 1000,
              "dec_kv_tokens": 1000, **args}] for name, start, _, args in steps]
    return {"host": [{"thread": "engine", "events": host}],
            "modules": [[f"jit_{n}(1)", s + 0.5 * ms, d] for n, s, d, _ in steps],
            "busy": [[s + 0.5 * ms, s + 0.5 * ms + d] for _, s, d, _ in steps],
            "kernels": {"mla_decode": [[1 * ms, 2 * ms]], "moe_gmm": [[5 * ms, ms]]}}


def test_the_new_readers_by_hand():
    readers = cellspec.layer_readers(cellspec.resolve(CELL))
    ctx = _ctx(CELL, _synthetic())
    assert readers["sched.moe_zero_pick_share"][1](ctx) == pytest.approx(
        100 * (8000 + 2240) / (640 * 48))
    assert readers["sched.moe_held_rows_per_expert"][1](ctx) == pytest.approx(
        (520 + 120) / (64 * 3))
    # a program without the counts (every other family, the parent), a
    # program without spans, a reference without the expert count: nothing
    for bare in (_synthetic(counts=False), dict(_synthetic(), host=[])):
        ctx = _ctx(CELL, bare)
        for name in NEW_READERS:
            assert readers[name][1](ctx) is None, name
    ctx = _ctx("moonlight-16b-a3b-d8.reason-pool", _synthetic())
    assert readers["sched.moe_held_rows_per_expert"][1](ctx) is None


def test_the_readers_on_a_slice_recorded_on_the_chip():
    """One second cut from the traced chip run of the cell (PR 38): every
    reader the cell lists that reads spans gives what it gave there, no share
    over 100%, and the two new ones read the step programs' own counts."""
    with gzip.open(FIXTURE + ".json.gz", "rt") as f:
        tl = json.load(f)
    with open(FIXTURE + ".expect.json") as f:
        expect = json.load(f)
    readers = cellspec.layer_readers(cellspec.resolve(CELL))
    assert set(NEW_READERS) <= set(expect)
    for name, want in expect.items():
        if name.startswith("_"):
            continue
        value = readers[name][1](_ctx(CELL, json.loads(json.dumps(tl))))
        assert value == pytest.approx(want, rel=1e-6), name
        if "roofline" in name or name.endswith("_share"):
            assert 0.0 <= value <= 100.0, name
    assert 25.0 <= expect["sched.moe_zero_pick_share"] <= 40.0
    assert 0.0 < expect["sched.moe_grouped_share"] < 100.0
    bare = dict(tl, host=[])
    bare["kernels"] = {k: [] for k in bare["kernels"]}
    for name in NEW_READERS:
        assert readers[name][1](_ctx(CELL, bare)) is None
