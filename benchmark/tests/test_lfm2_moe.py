"""PR 62's additions to the benchmark: the ``lfm2_moe`` family as files only (a
configuration, a cell on the existing ``reason-pool`` mix, a reference; no new
kernel and so no new reader), the cut's sizes term by term, the shared
readers' arithmetic on this family's spans, and the planted faults at a small
size (``plant`` is what the chip's controls import too)."""

import json
import os

import pytest

import cellspec
import run as runner
from conftest import TINY_GPT2, TINY_POOL

CELL = "lfm2-8b-a1b-d12.reason-pool"
CONFIG = "lfm2-8b-a1b-d12"
JAMBA = "ai21-jamba2-3b.reason-pool"
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
STAGE = ["conv", "conv", "full_attention", "conv", "conv", "conv",
         "full_attention", "conv", "conv", "conv", "full_attention", "conv"]

TINY_LFM2 = {
    "source": "test", "family": "lfm2_moe", "config_class": "Lfm2MoeConfig",
    "fields": {"vocab_size": "vocab_size", "hidden_size": "hidden_size",
               "num_layers": "num_hidden_layers", "layer_types": "layer_types",
               "num_heads": "num_attention_heads",
               "num_kv_heads": "num_key_value_heads",
               "num_dense_layers": "num_dense_layers",
               "intermediate_size": "intermediate_size",
               "num_experts": "num_experts",
               "moe_intermediate_size": "moe_intermediate_size",
               "top_k": "num_experts_per_tok", "stream_gain": "stream_gain",
               "max_seq_len": "max_position_embeddings"},
    "vocab_size": 256, "hidden_size": 64, "num_hidden_layers": 5,
    "layer_types": ["conv", "conv", "full_attention", "conv", "conv"],
    "num_attention_heads": 4, "num_key_value_heads": 2, "num_dense_layers": 1,
    "intermediate_size": 96, "num_experts": 8, "moe_intermediate_size": 32,
    "num_experts_per_tok": 2, "stream_gain": 64.0,
    "max_position_embeddings": 2048, "reduced": [],
    "serve": {**TINY_GPT2["serve"],
              # 64 lanes, five layers, one gain: the served bf16 path agrees
              # on 0.93 of 83 tokens (a deviation of 0.03), the faults the
              # check can see on 0.34-0.70
              "check": {"match_rate_min": 0.8}},
}

FAULTS = ("decode_reads_neighbour", "window_not_zeroed", "taps_reversed",
          "bias_in_the_weights", "no_head_norm")
# what a check of served TOKENS cannot see: the selection bias is drawn at
# N(0, 0.01) under scores of ~0.5, so in the weights it moves a pick's weight
# by ~2% and a logit by less than the plain bf16 reference does (agreement
# 0.916 for 0.928 at the small size; ``tests/unit/test_lfm2_moe.py`` holds the
# LOGITS to it)
UNSEEN = ("bias_in_the_weights",)


def plant(fault: str, setattr_, cfg) -> None:
    """One of the mechanism's faults planted in the PROGRAM's serving path
    through ``setattr_(object, name, value)`` (``monkeypatch.setattr`` here;
    the chip's controls undo theirs by hand): a decode row reading the
    neighbour slot's window; the window not zeroed at position 0; the
    filter's taps reversed; the selection bias added to the weights; the head
    norm dropped."""
    import jax
    import jax.numpy as jnp

    from deepspeed_tpu.models import experts, lfm2_moe, paged, shortconv

    if fault == "decode_reads_neighbour":
        real = shortconv.ragged

        def ragged(c, h, lp, state, slot0, scratch, slots, positions, tiles):
            # the output from the neighbour's window, every slot's own window
            # kept as it should be: a row that read AND wrote the neighbour's
            # slot would find its own two rows there from its third token on
            # (two rows of memory forgive a swap of slots)
            n_dec = slots.shape[0] if tiles is None else tiles[0]
            dec = slots[:n_dec]
            other = jnp.concatenate(
                [jnp.where(dec != scratch, dec ^ 1, dec), slots[n_dec:]])
            out, _ = real(c, h, lp, state, slot0, scratch, other, positions,
                          tiles)
            _, state = real(c, h, lp, state, slot0, scratch, slots, positions,
                            tiles)
            return out, state
        setattr_(shortconv, "ragged", ragged)
    elif fault == "window_not_zeroed":
        decode, tile = paged.decode_windows, paged.tile_windows
        setattr_(paged, "decode_windows", lambda leaf, rows, new, fresh, real:
                 decode(leaf, rows, new, jnp.zeros_like(fresh), real))
        setattr_(paged, "tile_windows",
                 lambda leaf, rows, rows_w, tiles, cont, fresh, write, valid:
                 tile(leaf, rows, rows_w, tiles, cont, jnp.zeros_like(fresh),
                      write, valid))
    elif fault == "taps_reversed":
        conv = shortconv.causal_conv
        setattr_(shortconv, "causal_conv", lambda c, win, w, *a, **k:
                 conv(c, win, jnp.flip(w, axis=0), *a, **k))
    elif fault == "bias_in_the_weights":
        route = experts._route

        def biased(h, router_w, top_k, scoring, bias, renormalize, scale, eps,
                   groups=None):
            _, topi = route(h, router_w, top_k, scoring, bias, renormalize,
                            scale, eps, groups)
            s = jax.nn.sigmoid(h.astype(jnp.float32)
                               @ router_w.astype(jnp.float32)) + bias
            topv = jnp.take_along_axis(s, topi, axis=-1)
            return topv / (topv.sum(-1, keepdims=True) + eps), topi
        setattr_(experts, "_route", biased)
    elif fault == "no_head_norm":
        rmsnorm = lfm2_moe.rmsnorm
        setattr_(lfm2_moe, "rmsnorm", lambda x, w, eps: (
            x if x.shape[-1] == cfg.head_dim else rmsnorm(x, w, eps)))
    else:
        raise ValueError(fault)


def dirty_slots(engine, seed: int):
    """Every slot but the scratch slot holds a foreign window."""
    import jax
    import jax.numpy as jnp

    leaf = engine.cache["slots"]["conv"]
    engine.cache = {**engine.cache, "slots": {"conv": (jax.random.normal(
        jax.random.PRNGKey(seed + 1), leaf.shape, jnp.float32) * 3.0).astype(
            leaf.dtype).at[:, -1].set(0)}}


# ------------------------------------------------------------------ the cell
def test_the_new_cell_resolves_on_the_mix_as_it_is():
    spec = cellspec.resolve(CELL)
    assert spec["chips"] == 1 and spec["traffic_name"] == "reason-pool"
    assert spec["mix"] == cellspec.resolve(JAMBA)["mix"]
    assert spec["cell"]["clients"] == 512
    engine = {**spec["config"]["serve"]["engine"],
              **spec["cell"].get("engine", {})}
    assert (engine["block_size"], engine["max_seqs"],
            engine["max_tokens_per_step"], engine["max_blocks_per_seq"],
            engine["prefill_tile"]) == (128, 512, 1024, 32, 128)
    # every client has a slot, a slot can hold the mix's longest request, and
    # a step of 512 decode rows and four tiles is two calls of the grouped
    # kernel exactly
    assert spec["cell"]["clients"] == engine["max_seqs"]
    assert spec["mix"]["total_tokens_max"] \
        == engine["block_size"] * engine["max_blocks_per_seq"]
    assert engine["max_tokens_per_step"] - engine["max_seqs"] == 4 * 128
    # the pool holds 512 sequences at the mix's mean (~1,100 tokens) with room
    assert (engine["num_blocks"] - 1) * engine["block_size"] >= 512 * 1400
    assert {m["name"] for m in spec["end_to_end"]} == {"serve_tokens_per_s",
                                                       "setup_s"}
    names = {m["name"] for m in spec["per_layer"]}
    assert {"serve.request_p50_ms", "sched.pad_share", "sched.cold_dispatches",
            "model.step_roofline", "sched.mixed_step_ms_p50",
            "kernel.paged_decode_share",
            "kernel.tiled_prefill_share", "model.ssm_step_roofline_kv",
            "kernel.hybrid_paged_decode_roofline",
            "kernel.hybrid_tiled_prefill_roofline", "sched.state_bytes_share",
            "sched.moe_grouped_share", "kernel.moe_gmm_share",
            "model.pool_slice_share", "setup.compile_s"} <= names
    # no recurrence kernel runs here; a reader that multiplies one layer's
    # K/V by num_layers would read four times too high; 512 workers send ~440
    # prompt tokens for every 512 generated, so every step of a slice carries
    # tiles and none is decode-only (PERF.md section 6, PR 62: the reader
    # found nothing to read on the chip, as in the window cell)
    assert not names & {"sched.pool_decode_step_ms_p50",
                        "kernel.ssm_decode_share", "kernel.selscan_decode_share",
                        "kernel.kda_decode_share",
                        "kernel.paged_decode_roofline",
                        "kernel.tiled_prefill_roofline",
                        "model.step_roofline_kv", "kernel.attn_share"}
    for m in spec["per_layer"]:
        assert m["moves"] in ("serve_tokens_per_s", "setup_s"), m["name"]


def test_the_benchmark_has_the_cell_and_its_configuration_once_each():
    with open(os.path.join(os.path.dirname(cellspec.HERE), "BENCHMARK.json")) as f:
        bench = json.load(f)
    assert [w["name"] for w in bench["workloads"]].count(CELL) == 1
    (config,) = [c for c in bench["configs"] if c["name"] == CONFIG]
    assert config["reduced"] == ["num_hidden_layers", "layer_types"]
    assert bench["workloads"][-1]["name"] == CELL   # appended, not inserted
    assert bench["configs"][-1]["name"] == CONFIG
    for m in bench["end_to_end"] + bench["per_layer"]:
        if CELL in m.get("workloads", []):
            assert m["workloads"][-1] == CELL, m["name"]
    assert sum(w["chips"] == 4 for w in bench["workloads"]) == 1
    assert len(bench["configs"]) >= 14 and len(bench["workloads"]) >= 16
    assert sum(w["traffic"] == "reason-pool" for w in bench["workloads"]) >= 6


def test_the_configuration_is_the_catalog_entry_but_for_the_cut():
    conf = cellspec.resolve(CELL)["config"]
    if os.path.exists(CATALOG):
        with open(CATALOG) as f:
            (row,) = [r for r in map(json.loads, f)
                      if r["name"] == "LFM2-8B-A1B"]
        assert conf["source"] == row["source_url"]
        differs = {k for k, v in row["config"].items()
                   if conf.get(k, "absent") != v}
        assert differs == set(conf["reduced"])
        assert conf["layer_types"] == row["config"]["layer_types"][:12]
        assert conf["layer_types_published"] == row["config"]["layer_types"]
    assert conf["reduced"] == ["num_hidden_layers", "layer_types"]
    assert (conf["num_hidden_layers"], conf["num_hidden_layers_published"],
            conf["layer_types"]) == (12, 24, STAGE)
    assert set(conf["assumed"]) >= {"tied_table", "head_dim", "in_proj_order",
                                    "rotation", "router_eps", "state_dtype",
                                    "weights", "stream_gain"}
    for text in ("3,928,728,256", "8,339,930,560"):
        assert text in conf["reduced_why"], text
    assert "two pipeline stages" in conf["deployment"]
    check = conf["serve"]["check"]
    assert 0.0 < check["match_rate_min"] < 1.0 and "float8_e5m2" in check["why"]
    for fault in ("neighbour", "not zeroed", "reversed", "bias", "head norm"):
        assert fault in check["why"], fault
    assert conf["serve"]["memory_peak_bytes"] >= 0.25 * 16e9


def test_the_sizes_of_the_cut():
    import jax
    import numpy as np

    family, cfg, reference = cellspec.model(cellspec.resolve(CELL))
    assert (cfg.num_layers, cfg.hidden_size, cfg.head_dim, cfg.num_heads,
            cfg.num_kv_heads, cfg.conv_kernel, cfg.num_experts, cfg.top_k,
            cfg.num_dense_layers, cfg.rope_theta, cfg.rms_norm_eps) == (
                12, 2048, 64, 32, 8, 3, 32, 4, 2, 1000000, 1e-5)
    assert list(cfg.layer_types) == STAGE
    assert [n for _, n in cfg.runs] == [2, 1, 3, 1, 3, 1, 1]
    assert reference.kinds(cfg) == list(cfg.kinds)
    assert reference.mixer_params(cfg, "conv") == {
        "in_proj": 12_582_912, "conv": 6_144, "out_proj": 4_194_304}
    assert sum(reference.mixer_params(cfg, "full_attention").values()) \
        == 10_485_888
    assert reference.ffn_params(cfg, "dense", 0) == 44_040_192
    assert reference.ffn_params(cfg, "moe", cfg.num_experts) == 352_387_104
    held = (9 * 16_783_360 + 3 * 10_485_888 + 12 * 4_096 + 2 * 44_040_192
            + 10 * 352_387_104 + 134_217_728 + 2_048)
    assert reference.num_params(cfg) == family.num_params(cfg) == held \
        == 3_928_728_256
    assert reference.weight_bytes(cfg) == 2 * held
    assert reference.active_params(cfg) == held - 10 * 28 * 3 * 2048 * 1792
    assert reference.kv_bytes_per_token(cfg) == 6_144     # THREE layers, 8 x 64
    assert reference.attn_flops_per_pair(cfg) == 4 * 32 * 64 * 3
    assert reference.state_bytes_per_slot(cfg) == 9 * 2 * 2048 * 2 == 73_728
    assert reference.ssm_flops_per_token(cfg) == 9 * 7 * 2048
    assert reference.held_expert_slots(cfg) == 320
    tree = jax.eval_shape(lambda: family.init_params(cfg, jax.random.PRNGKey(0)))
    assert sum(int(np.prod(a.shape))
               for a in jax.tree_util.tree_leaves(tree)) == held
    sizes = cellspec.resolve(CELL)["config"]["serve"]["engine"]
    cache = jax.eval_shape(lambda: family.init_paged_cache(
        cfg, sizes["num_blocks"], 128, jax.numpy.bfloat16, num_slots=513))
    assert cache["k"].shape == (3, sizes["num_blocks"], 128, 512)
    assert cache["slots"]["conv"].shape == (9, 513, 32, 128)
    # the issue's count of the cell: weights + the pool + 513 slots
    total = 2 * held + sizes["num_blocks"] * 128 * 6_144 + 513 * 73_728
    assert 11.5e9 < total < 12.8e9


# ``TINY_POOL`` with answers long enough to count agreement on
TINY_REASON = {**TINY_POOL,
               "prompt_tokens": {"dist": "lognormal", "median": 30,
                                 "sigma": 0.4, "min": 16, "max": 60},
               "output_tokens": {"dist": "uniform", "min": 24, "max": 40}}


def _tiny(copy):
    root = copy({
        "benchmark/configs/tiny-lfm2.json": TINY_LFM2,
        "benchmark/traffic/tiny-pool.json": TINY_REASON,
        "benchmark/cells/tiny.cell.json": {"clients": 3},
    }, configs=[{"name": "tiny-lfm2", "source": "test", "reduced": [],
                 "file": "benchmark/configs/tiny-lfm2.json", "why": "tiny"}],
       workloads=[{"name": "tiny.cell", "config": "tiny-lfm2",
                   "traffic": "tiny-pool", "chips": 1, "why": "on the CPU"}])
    path = os.path.join(root, "BENCHMARK.json")
    with open(path) as f:
        bench = json.load(f)
    for m in bench["end_to_end"] + bench["per_layer"]:
        if CELL in m.get("workloads", []):
            m["workloads"].append("tiny.cell")
    with open(path, "w") as f:
        json.dump(bench, f)
    return cellspec.resolve("tiny.cell", root=root)


def test_the_tiny_family_runs_through_the_harness_end_to_end(copy, tmp_path):
    """The rehearsal of the chip run: a tiny ``lfm2_moe`` added as files only,
    every step program warmed, a closed loop over HTTP, the served tokens
    against ``reference/lfm2_moe.py``; a traced line's readers say what they
    can and raise nothing."""
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
    assert counters["preemptions"] == 0
    raw["window"]["trace"] = {"busy_s": 1.0, "window_s": 2.0, "top_ops": [],
                              "idle_gaps": [], "collective_exposed_s": 0.0,
                              "kernel_s": {}}
    raw["window"].setdefault("samples", [])
    line = runner.result_line(
        spec, raw, {"platform": "cpu", "kind": "cpu", "count": 1}, trace=True,
        peaks={"bf16_flops_per_s": 1.0, "hbm_bytes_per_s": 1.0})
    assert line["correct"] is True


@pytest.mark.parametrize("fault", ("none", "dirty_slots", "lower") + FAULTS)
def test_planted_faults_at_a_small_size(copy, monkeypatch, fault):
    """The chip's controls (``.bench_tools/lfm2_faults.py``, scratch; PERF.md
    section 6, PR 62) rehearsed: three requests served by the engine alone and
    held to the reference by ``serve_cell.ServeRig.check`` itself. Served as
    it is, on clean slots and on slots that all hold a foreign window:
    correct. The reference in float8 and each fault planted in the program
    that the check can see (all but ``UNSEEN``): not."""
    import types

    import jax
    import jax.numpy as jnp

    import check_controls
    import serve_cell
    import trafficgen
    from deepspeed_tpu.inference.ragged import (RaggedConfig,
                                                RaggedInferenceEngine)

    spec = _tiny(copy)
    family, cfg, reference = cellspec.model(spec)
    reference.Q_BLOCK = 64
    seed = 2**31 + 29
    params = jax.tree_util.tree_map(
        lambda x: x.astype(jnp.bfloat16),
        family.init_params(cfg, jax.random.PRNGKey(seed)))
    if fault in FAULTS:
        plant(fault, monkeypatch.setattr, cfg)
    engine = RaggedInferenceEngine(
        lambda ctx: family.build(cfg, ctx=ctx),
        RaggedConfig(**spec["config"]["serve"]["engine"]), dtype=jnp.bfloat16,
        params=params, seed=seed)
    if fault in ("dirty_slots", "window_not_zeroed"):
        dirty_slots(engine, seed)
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
    assert verdict["ok"] is (fault in ("none", "dirty_slots") + UNSEEN), verdict


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


SLOT = 2 * 73_728    # a slot's window rows, read and written


def _synthetic(state: bool = True) -> dict:
    """Two dispatches and their executions: a decode step of 500 live rows in
    the bucket of 512 (20 ms; ``paged_decode`` 6 ms of it in 3 calls,
    ``moe_gmm`` 9 ms in 10), a mixed step of 500 rows and 4 tiles of three
    arrivals' 450 prompt tokens (30 ms; ``paged_decode`` 6 ms,
    ``tiled_prefill`` 0.9 ms, ``moe_gmm`` 17 ms in 20 turns)."""
    ms = 1e6
    steps = [("ragged_step_d512_t0", 0.0, 20 * ms,
              {"tokens": 500, "pad": 12, "kv_tokens": 550_000,
               "attn_pairs": 550_000, "dec_kv_tokens": 550_000,
               "pool_slice_rows": 0, "moe": "grouped",
               "state_bytes": 500 * SLOT, "dec_state_bytes": 500 * SLOT,
               "ssm_prefill_tokens": 0, "state_pad_rows": 12,
               "slot_resets": 0}),
             ("ragged_step_d512_t4", 24 * ms, 30 * ms,
              {"tokens": 950, "pad": 74, "kv_tokens": 550_000 + 450,
               "attn_pairs": 550_000 + 40_000, "dec_kv_tokens": 550_000,
               "pool_slice_rows": 512, "moe": "grouped",
               "state_bytes": 503 * SLOT, "dec_state_bytes": 500 * SLOT,
               "ssm_prefill_tokens": 450, "state_pad_rows": 12,
               "slot_resets": 3})]
    if not state:
        steps = [(n, s, d, {k: v for k, v in a.items()
                            if "state" not in k and k != "moe"})
                 for n, s, d, a in steps]
    host = [["engine/dispatch", start + 0.1 * ms, 0.2 * ms,
             {"program": name,
              **({"state_kind": "shortconv"} if state else {}), **args}]
            for name, start, _, args in steps]
    return {
        "host": [{"thread": "engine", "events": host}],
        "modules": [[f"jit_{n}(1)", s + 0.5 * ms, d] for n, s, d, _ in steps],
        "busy": [[s + 0.5 * ms, s + 0.5 * ms + d] for _, s, d, _ in steps],
        "kernels": {
            "paged_decode": [[1 * ms + i * 2 * ms, 2 * ms] for i in range(3)]
            + [[25 * ms + i * 2 * ms, 2 * ms] for i in range(3)],
            "tiled_prefill": [[32 * ms + i * ms, 0.3 * ms] for i in range(3)],
            "moe_gmm": [[8 * ms + i * ms, 0.9 * ms] for i in range(10)]
            + [[35 * ms + i * 0.9 * ms, 0.85 * ms] for i in range(20)]}}


def test_the_shared_readers_count_this_familys_steps():
    readers = cellspec.layer_readers(cellspec.resolve(CELL))
    ctx = _ctx(_synthetic())

    def read(name):
        return readers[name][1](ctx)

    ref, cfg = ctx["reference"], ctx["cfg"]
    # THREE attention layers' K and V (6,144 B a token), the window rows and
    # every expert's weights in the step's bytes
    kv = 550_000 + 550_450
    bytes_s = (2 * ref.weight_bytes(cfg) + 6_144 * kv + 1003 * SLOT) / 819e9
    flops_s = ((2 * ref.active_params(cfg) + ref.ssm_flops_per_token(cfg))
               * 1450 + 24_576 * (550_000 + 590_000)) / 197e12
    assert bytes_s > flops_s
    assert read("model.ssm_step_roofline_kv") == pytest.approx(
        100 * bytes_s / 50e-3, rel=1e-9)
    assert read("sched.state_bytes_share") == pytest.approx(
        100 * 1003 * SLOT / (bytes_s * 819e9), rel=1e-9)
    assert read("sched.state_bytes_share") < 1.0    # 74 KB a sequence
    # 1,100,000 decode context tokens at 6,144 B against 12 ms in the kernel
    assert read("kernel.hybrid_paged_decode_roofline") == pytest.approx(
        100 * (6_144 * 1_100_000 / 819e9) / 12e-3, rel=1e-9)
    assert read("kernel.hybrid_tiled_prefill_roofline") == pytest.approx(
        100 * max(24_576 * 40_000 / 197e12, 6_144 * 450 / 819e9) / 0.9e-3,
        rel=1e-9)
    assert read("kernel.paged_decode_share") == pytest.approx(100 * 12 / 50)
    assert read("kernel.moe_gmm_share") == pytest.approx(100 * 26 / 50)
    assert read("sched.moe_grouped_share") == 100.0
    assert read("model.pool_slice_share") == pytest.approx(100 * 512 / 1450)
    assert read("sched.mixed_step_ms_p50") == pytest.approx(30.0)
    for name in ("model.ssm_step_roofline_kv", "sched.state_bytes_share",
                 "kernel.hybrid_paged_decode_roofline",
                 "kernel.hybrid_tiled_prefill_roofline",
                 "kernel.paged_decode_share", "kernel.moe_gmm_share"):
        assert 0.0 <= read(name) <= 100.0, name


@pytest.mark.parametrize("bare", ["no_spans", "no_state_arguments"])
def test_a_program_without_the_family_reads_nothing_and_raises_nothing(bare):
    """The parent of PR 62 cannot run the cell at all (no such family: it
    exits at the import). A program that writes no span, or none of the
    state's arguments, gives the state's readers nothing to read: None."""
    tl = _synthetic(state=bare != "no_state_arguments")
    if bare == "no_spans":
        tl = dict(tl, host=[])
    readers = cellspec.layer_readers(cellspec.resolve(CELL))
    ctx = _ctx(tl)
    for name in ("model.ssm_step_roofline_kv", "sched.state_bytes_share",
                 "sched.moe_grouped_share"):
        assert readers[name][1](ctx) is None, name
