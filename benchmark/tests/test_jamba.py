"""PR 53's additions to the benchmark: the ``jamba`` family as files only (a
configuration, a cell on the existing ``reason-pool`` mix, a reference, two
kernels, four readers), the published sizes with nothing cut, the readers'
arithmetic, and the planted faults at a small size."""

import json
import os

import pytest

import cellspec
import run as runner
from conftest import TINY_GPT2, TINY_POOL

JAMBA = "ai21-jamba2-3b.reason-pool"
NEMOTRON = "nemotron-3-super-120b-d11-ep4.reason-pool"
NEW_READERS = ("kernel.selscan_decode_share", "kernel.selscan_decode_roofline",
               "kernel.selscan_tile_share", "kernel.selscan_tile_roofline")
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"

TINY_JAMBA = {
    "source": "test", "family": "jamba", "config_class": "JambaConfig",
    "fields": {"vocab_size": "vocab_size", "hidden_size": "hidden_size",
               "num_layers": "num_hidden_layers",
               "attn_layer_period": "attn_layer_period",
               "attn_layer_offset": "attn_layer_offset",
               "num_heads": "num_attention_heads",
               "num_kv_heads": "num_key_value_heads",
               "intermediate_size": "intermediate_size",
               "ssm_state_size": "mamba_d_state", "dt_rank": "mamba_dt_rank",
               "stream_growth": "stream_growth",
               "max_seq_len": "max_position_embeddings"},
    "vocab_size": 256, "hidden_size": 64, "num_hidden_layers": 4,
    "attn_layer_period": 4, "attn_layer_offset": 1,
    "num_attention_heads": 4, "num_key_value_heads": 1,
    "intermediate_size": 96, "mamba_d_state": 8, "mamba_dt_rank": 8,
    "stream_growth": 256.0, "max_position_embeddings": 2048, "reduced": [],
    "serve": {**TINY_GPT2["serve"],
              # 64 lanes and four layers: bf16 flips a third of the picks
              "check": {"match_rate_min": 0.4}},
}


def test_the_new_cell_resolves_on_the_mix_as_it_is():
    spec = cellspec.resolve(JAMBA)
    assert spec["chips"] == 1 and spec["traffic_name"] == "reason-pool"
    assert spec["mix"] == cellspec.resolve(NEMOTRON)["mix"]
    assert spec["cell"]["clients"] == 256
    engine = {**spec["config"]["serve"]["engine"],
              **spec["cell"].get("engine", {})}
    assert engine == {"block_size": 128, "num_blocks": 8193, "max_seqs": 256,
                      "max_tokens_per_step": 640, "max_blocks_per_seq": 32,
                      "prefill_tile": 128}
    # every client has a slot and every slot can hold the mix's longest
    # request: no queue for slots, no preemption
    assert (engine["num_blocks"] - 1
            == engine["max_seqs"] * engine["max_blocks_per_seq"]
            and spec["mix"]["total_tokens_max"]
            == engine["block_size"] * engine["max_blocks_per_seq"])
    # 256 decode rows leave three tiles: the mix needs ~220 prompt tokens a
    # step of 256 emitted ones (mean prompt 654 over mean output 768)
    assert engine["max_tokens_per_step"] - engine["max_seqs"] == 3 * 128
    assert {m["name"] for m in spec["end_to_end"]} == {"serve_tokens_per_s",
                                                       "setup_s"}
    names = {m["name"] for m in spec["per_layer"]}
    assert set(NEW_READERS) <= names
    # what Nemotron's reason-pool cell reports and this one can
    assert {"serve.request_p50_ms", "sched.pad_share", "sched.cold_dispatches",
            "model.step_roofline", "sched.mixed_step_ms_p50",
            "sched.pool_decode_step_ms_p50", "kernel.paged_decode_share",
            "kernel.tiled_prefill_share", "model.ssm_step_roofline_kv",
            "kernel.hybrid_paged_decode_roofline",
            "kernel.hybrid_tiled_prefill_roofline", "sched.state_bytes_share",
            "model.pool_slice_share", "setup.compile_s"} <= names
    # no routed experts; Mamba-2's kernel never runs; a reader that multiplies
    # one layer's K/V by num_layers would read 14 times too high; every
    # Pallas call is no attention kernel here
    assert not names & {"sched.moe_grouped_share", "kernel.moe_gmm_share",
                        "kernel.ssm_decode_share", "kernel.ssm_decode_roofline",
                        "kernel.paged_decode_roofline",
                        "kernel.tiled_prefill_roofline",
                        "model.step_roofline_kv", "kernel.attn_share"}


def test_the_benchmark_has_the_cell_its_configuration_and_its_readers_once_each():
    with open(os.path.join(os.path.dirname(cellspec.HERE), "BENCHMARK.json")) as f:
        bench = json.load(f)
    assert [w["name"] for w in bench["workloads"]].count(JAMBA) == 1
    (config,) = [c for c in bench["configs"] if c["name"] == "ai21-jamba2-3b"]
    assert config["reduced"] == []
    metrics = [m["name"] for m in bench["per_layer"]]
    for m in bench["per_layer"]:
        if m["name"] in NEW_READERS:
            assert metrics.count(m["name"]) == 1
            assert m["workloads"] == [JAMBA]
            assert m["moves"] == "serve_tokens_per_s" and m["layer"] == "kernels"
    assert set(NEW_READERS) <= set(metrics)
    four = sum(w["chips"] == 4 for w in bench["workloads"])
    assert four == 1 and len(bench["workloads"]) == 13


def test_the_configuration_is_the_catalog_entry_whole():
    conf = cellspec.resolve(JAMBA)["config"]
    if os.path.exists(CATALOG):
        with open(CATALOG) as f:
            (row,) = [r for r in map(json.loads, f)
                      if r["name"] == "AI21-Jamba2-3B"]
        assert conf["source"] == row["source_url"]
        assert {k: conf.get(k, "absent") for k in row["config"]} == row["config"]
    assert conf["reduced"] == []
    assert (conf["num_hidden_layers"], conf["hidden_size"], conf["vocab_size"],
            conf["attn_layer_period"], conf["attn_layer_offset"],
            conf["mamba_d_state"], conf["mamba_dt_rank"], conf["mamba_expand"],
            conf["mamba_d_conv"], conf["num_attention_heads"],
            conf["num_key_value_heads"], conf["num_experts"],
            conf["intermediate_size"], conf["tie_word_embeddings"]) == (
                28, 2560, 65536, 14, 7, 16, 160, 2, 4, 20, 1, 1, 8192, True)
    assert set(conf["assumed"]) >= {"layer_order", "dense_ffn", "state_dtype",
                                    "state_layout", "weights", "stream_growth"}
    check = conf["serve"]["check"]
    assert 0.0 < check["match_rate_min"] < 1.0 and "float8_e5m2" in check["why"]
    for fault in ("inner norms", "averaged over", "mod 14 == 6", "neighbour"):
        assert fault in check["why"], fault


def test_the_sizes_of_the_whole_model():
    import jax
    import numpy as np

    family, cfg, reference = cellspec.model(cellspec.resolve(JAMBA))
    assert (cfg.num_layers, cfg.d_inner, cfg.ssm_state_size, cfg.dt_rank,
            cfg.conv_kernel, cfg.head_dim, cfg.num_heads, cfg.num_kv_heads) == (
                28, 5120, 16, 160, 4, 128, 20, 1)
    assert cfg.runs == [("mamba", 7), ("attention", 1), ("mamba", 13),
                        ("attention", 1), ("mamba", 6)]
    assert reference.mixer_params(cfg, "mamba") == {
        "in_proj": 26_214_400, "conv": 25_600, "x_proj": 983_040,
        "dt_proj": 824_320, "a_log": 81_920, "d": 5_120, "inner_norms": 192,
        "out_proj": 13_107_200}
    assert reference.layer_params(cfg, "mamba") == 104_161_472
    assert reference.layer_params(cfg, "attention") == 76_682_240
    assert reference.num_params(cfg) == family.num_params(cfg) == 3_029_337_472
    assert reference.active_params(cfg) == 3_029_337_472
    assert reference.weight_bytes(cfg) == 2 * 3_029_337_472
    assert reference.kv_bytes_per_token(cfg) == 1024      # TWO layers, ONE head
    assert reference.attn_flops_per_pair(cfg) == 4 * 20 * 128 * 2
    assert reference.state_bytes_per_slot(cfg) == 26 * (327_680 + 30_720) \
        == 9_318_400
    assert reference.ssm_flops_per_token(cfg) == 26 * 7 * 16 * 5120
    assert reference.ssm_exps_per_token(cfg) == 26 * 16 * 5120
    assert reference.scan_io_bytes_per_token(cfg) == 26 * (3 * 5120 + 32) * 2
    tree = jax.eval_shape(lambda: family.init_params(cfg, jax.random.PRNGKey(0)))
    assert sum(int(np.prod(a.shape))
               for a in jax.tree_util.tree_leaves(tree)) == 3_029_337_472
    cache = jax.eval_shape(lambda: family.init_paged_cache(
        cfg, 8193, 128, jax.numpy.bfloat16, num_slots=257))
    assert cache["k"].shape == (2, 8193, 128, 128)
    assert cache["slots"]["ssm"].shape == (26, 257, 16, 5120)
    assert cache["slots"]["conv"].shape == (26, 257, 24, 640)
    # the issue's count of the cell: weights + 257 slots + the pool
    held = (2 * 3_029_337_472 + 257 * 9_318_400 + 8193 * 128 * 1024)
    assert 9.4e9 < held < 9.6e9


# ``TINY_POOL`` with answers long enough to count agreement on
TINY_REASON = {**TINY_POOL,
               "prompt_tokens": {"dist": "lognormal", "median": 30,
                                 "sigma": 0.4, "min": 16, "max": 60},
               "output_tokens": {"dist": "uniform", "min": 24, "max": 40}}


def _tiny(copy):
    root = copy({
        "benchmark/configs/tiny-jamba.json": TINY_JAMBA,
        "benchmark/traffic/tiny-pool.json": TINY_REASON,
        "benchmark/cells/tiny.cell.json": {"clients": 3},
    }, configs=[{"name": "tiny-jamba", "source": "test", "reduced": [],
                 "file": "benchmark/configs/tiny-jamba.json", "why": "tiny"}],
       workloads=[{"name": "tiny.cell", "config": "tiny-jamba",
                   "traffic": "tiny-pool", "chips": 1, "why": "on the CPU"}])
    path = os.path.join(root, "BENCHMARK.json")
    with open(path) as f:
        bench = json.load(f)
    for m in bench["end_to_end"] + bench["per_layer"]:
        if JAMBA in m.get("workloads", []):
            m["workloads"].append("tiny.cell")
    with open(path, "w") as f:
        json.dump(bench, f)
    return cellspec.resolve("tiny.cell", root=root)


def test_the_tiny_family_runs_through_the_harness_end_to_end(copy, tmp_path):
    """The rehearsal of the chip run: a tiny ``jamba`` added as files only,
    every step program warmed, a closed loop over HTTP, the served tokens
    against ``reference/jamba.py``."""
    import jax
    import numpy as np

    spec = _tiny(copy)
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


@pytest.mark.parametrize("fault", ["none", "lower", "no_inner_norms",
                                   "mamba2_decay", "decode_reads_neighbour"])
def test_planted_faults_at_a_small_size(copy, monkeypatch, fault):
    """The chip's controls (``.bench_tools``-style, PERF.md section 6, PR 53)
    rehearsed: three requests served by the engine alone and held to the
    reference by ``serve_cell.ServeRig.check`` itself. Served as it is:
    correct. The reference in float8, the inner norms dropped, ``A`` averaged
    over the state index, a decode row reading its neighbour's slot: not."""
    import types

    import jax
    import jax.numpy as jnp

    import check_controls
    import serve_cell
    import trafficgen
    from deepspeed_tpu.inference.ragged import (RaggedConfig,
                                                RaggedInferenceEngine)
    from deepspeed_tpu.models import mamba1

    spec = _tiny(copy)
    family, cfg, reference = cellspec.model(spec)
    reference.Q_BLOCK = 64
    seed = 2**31 + 29
    params = jax.tree_util.tree_map(
        lambda x: x.astype(jnp.bfloat16),
        family.init_params(cfg, jax.random.PRNGKey(seed)))
    real = mamba1.ragged
    if fault == "no_inner_norms":
        monkeypatch.setattr(mamba1, "rmsnorm", lambda x, w, eps: x)
    elif fault == "mamba2_decay":
        def ragged(c, h, lp, *rest):
            a = jnp.exp(lp["a_log"].astype(jnp.float32)).mean(0, keepdims=True)
            return real(c, h, {**lp, "a_log": jnp.broadcast_to(
                jnp.log(a), lp["a_log"].shape)}, *rest)
        monkeypatch.setattr(mamba1, "ragged", ragged)
    elif fault == "decode_reads_neighbour":
        def ragged(c, h, lp, state, slot0, scratch, slots, positions, tiles):
            n_dec = slots.shape[0] if tiles is None else tiles[0]
            dec = slots[:n_dec]
            slots = jnp.concatenate(
                [jnp.where(dec != scratch, dec ^ 1, dec), slots[n_dec:]])
            return real(c, h, lp, state, slot0, scratch, slots, positions, tiles)
        monkeypatch.setattr(mamba1, "ragged", ragged)
    engine = RaggedInferenceEngine(
        lambda ctx: family.build(cfg, ctx=ctx),
        RaggedConfig(**spec["config"]["serve"]["engine"]), dtype=jnp.bfloat16,
        params=params, seed=seed)
    records = check_controls.requests(spec, seed, 0)
    for uid, r in enumerate(records):
        engine.put(uid, trafficgen.prompt_tokens(
            seed, r["stream_id"], r["i"], r["prompt_len"], cfg.vocab_size),
            max_new_tokens=r["max_tokens"])
    served = engine.generate_all()
    records = [{**r, "status": 200, "tokens": list(served[uid])}
               for uid, r in enumerate(records)]
    ref = reference
    if fault == "lower":
        ref = types.SimpleNamespace(forward=lambda c, p, ids, dt: reference.forward(
            c, p, ids, jnp.float8_e5m2 if dt == jnp.float32 else dt))
    rig = types.SimpleNamespace(engine=engine, seed=seed, cfg=cfg, spec=spec,
                                reference=ref)
    verdict = serve_cell.ServeRig.check(rig, records)
    assert verdict["ok"] is (fault == "none"), verdict


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


SLOT = 2 * 9_318_400    # a slot's state, read and written


def _synthetic(scan: bool = True) -> dict:
    """Two dispatches and their executions: a decode step of 250 live rows in
    the bucket of 256 (20 ms, ``selscan_decode`` 6.5 ms of it in 26 calls), a
    mixed step of 240 rows and 3 tiles of two arrivals' 300 prompt tokens
    (36 ms; ``selscan_decode`` 6.5 ms, ``selscan_tile`` 2.6 ms)."""
    ms = 1e6
    steps = [("ragged_step_d256_t0", 0.0, 20 * ms,
              {"tokens": 250, "pad": 6, "kv_tokens": 250_000,
               "attn_pairs": 250_000, "dec_kv_tokens": 250_000,
               "state_bytes": 250 * SLOT, "dec_state_bytes": 250 * SLOT,
               "ssm_prefill_tokens": 0, "chunk_tiles": 0, "scan_tiles": 0,
               "state_pad_rows": 6, "slot_resets": 0}),
             ("ragged_step_d256_t3", 24 * ms, 36 * ms,
              {"tokens": 540, "pad": 100, "kv_tokens": 240_000 + 300,
               "attn_pairs": 240_000 + 30_000, "dec_kv_tokens": 240_000,
               "state_bytes": 242 * SLOT, "dec_state_bytes": 240 * SLOT,
               "ssm_prefill_tokens": 300, "chunk_tiles": 3, "scan_tiles": 3,
               "state_pad_rows": 16, "slot_resets": 2})]
    if not scan:
        steps = [(n, s, d, {k: v for k, v in a.items() if k != "scan_tiles"})
                 for n, s, d, a in steps]
    host = [["engine/dispatch", start + 0.1 * ms, 0.2 * ms,
             {"program": name, "state_kind": "mamba1", **args}]
            for name, start, _, args in steps]
    return {
        "host": [{"thread": "engine", "events": host}],
        "modules": [[f"jit_{n}(1)", s + 0.5 * ms, d] for n, s, d, _ in steps],
        "busy": [[s + 0.5 * ms, s + 0.5 * ms + d] for _, s, d, _ in steps],
        "kernels": {
            "selscan_decode": [[1 * ms + i * 0.5 * ms, 0.25 * ms]
                               for i in range(26)]
            + [[25 * ms + i * 0.5 * ms, 0.25 * ms] for i in range(26)],
            "selscan_tile": [[40 * ms + i * 0.5 * ms, 0.1 * ms]
                             for i in range(26)]}}


def test_the_new_readers_count_the_state_and_the_scan():
    readers = cellspec.layer_readers(cellspec.resolve(JAMBA))
    ctx = _ctx(JAMBA, _synthetic())

    def read(name):
        return readers[name][1](ctx)

    # 490 decode rows' states once each way against 13 ms in the kernel
    assert read("kernel.selscan_decode_roofline") == pytest.approx(
        100 * (490 * SLOT / 819e9) / 13e-3, rel=1e-9)
    assert read("kernel.selscan_decode_share") == pytest.approx(100 * 13 / 56)
    assert read("kernel.selscan_tile_share") == pytest.approx(100 * 2.6 / 56)
    # 300 prompt tokens' x, dt, B, C, y in bf16 and two slots' state once each
    # way against 2.6 ms: bytes bind before the MXU's FLOP/s would
    io = 300 * 26 * (3 * 5120 + 32) * 2 + 2 * SLOT
    flops = 300 * 26 * 7 * 16 * 5120
    assert io / 819e9 > flops / 197e12
    assert read("kernel.selscan_tile_roofline") == pytest.approx(
        100 * (io / 819e9) / 2.6e-3, rel=1e-9)
    for name in NEW_READERS:
        assert 0.0 <= read(name) <= 100.0, name
    # the shared readers' geometry holds here: TWO attention layers' K and V
    # (1,024 B a token), the state in the step's bytes
    ref, cfg = ctx["reference"], ctx["cfg"]
    bytes_s = (2 * ref.weight_bytes(cfg) + 1024 * (250_000 + 240_300)
               + 492 * SLOT) / 819e9
    assert readers["model.ssm_step_roofline_kv"][1](ctx) == pytest.approx(
        100 * bytes_s / 56e-3, rel=1e-9)
    assert readers["sched.state_bytes_share"][1](ctx) == pytest.approx(
        100 * 492 * SLOT / (bytes_s * 819e9), rel=1e-9)


@pytest.mark.parametrize("bare", ["no_spans", "no_scan_argument", "no_kernel"])
def test_a_program_without_spans_or_the_scan_reads_nothing(bare):
    """The parent of PR 53 (no such family: no span says ``scan_tiles``, no
    trace has the kernels), or any other family: None, no error."""
    tl = _synthetic(scan=bare != "no_scan_argument")
    if bare == "no_spans":
        tl = dict(tl, host=[])
    if bare != "no_scan_argument":
        tl["kernels"] = {}
    readers = cellspec.layer_readers(cellspec.resolve(JAMBA))
    ctx = _ctx(JAMBA, tl)
    ctx["window"]["trace"]["kernel_s"] = (
        {} if bare != "no_scan_argument" else ctx["window"]["trace"]["kernel_s"])
    silent = NEW_READERS if bare != "no_scan_argument" else NEW_READERS[3:]
    for name in silent:
        assert readers[name][1](ctx) is None, name
