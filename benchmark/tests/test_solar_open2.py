"""PR 57's additions to the benchmark: the ``solar_open2`` family as files
only (a configuration, a cell on the existing ``longctx-pool`` mix, a
reference, the ``kda_chunk`` kernel's file and two readers), the cut's sizes
term by term, the readers' arithmetic, and the planted faults at a small size
(``plant``: the scratch script that plants them at the timed sizes on the
chip imports it from here)."""

import json
import os
import re

import pytest

import cellspec
import run as runner
from conftest import TINY_GPT2, TINY_POOL

CELL = "solar-open2-250b-d4-ep8.longctx-pool"
SPARSE = "deepseek-v32-exp-d5-ep16.longctx-pool"
NEW_READERS = ("kernel.kda_chunk_share", "kernel.kda_chunk_roofline")
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
REDUCED = ["num_hidden_layers", "gqa_layers", "n_routed_experts", "vocab_size"]

TINY_SOLAR = {
    "source": "test", "family": "solar_open2",
    "config_class": "SolarOpen2Config",
    "fields": {"vocab_size": "vocab_size", "hidden_size": "hidden_size",
               "moe_intermediate_size": "moe_intermediate_size",
               "num_layers": "num_hidden_layers", "gqa_layers": "gqa_layers",
               "num_heads": "num_attention_heads",
               "num_kv_heads": "num_key_value_heads", "head_dim": "head_dim",
               "linear_attn_config": "linear_attn_config",
               "num_experts": "n_routed_experts_published",
               "experts_held": "n_routed_experts",
               "top_k": "num_experts_per_tok",
               "sub_chunk": "sub_chunk", "chunk_size": "chunk_size",
               "max_seq_len": "max_position_embeddings"},
    "vocab_size": 256, "hidden_size": 64, "moe_intermediate_size": 48,
    "num_hidden_layers": 4, "gqa_layers": [0], "num_attention_heads": 4,
    "num_key_value_heads": 2, "head_dim": 16,
    "linear_attn_config": {"num_heads": 2, "head_dim": 16,
                           "short_conv_kernel_size": 4, "num_kv_heads": None},
    "n_routed_experts_published": 8, "n_routed_experts": 4,
    "num_experts_per_tok": 3, "sub_chunk": 4, "chunk_size": 16,
    "max_position_embeddings": 2048, "reduced": [],
    "serve": {**TINY_GPT2["serve"],
              # 64 lanes, four layers and a top-3-of-8 router: bf16 flips a
              # third of the picks
              "check": {"match_rate_min": 0.4}},
}


# ------------------------------------------------------- the planted faults
FAULTS = ("beta_not_doubled", "gate_out", "lost_carry_first",
          "lost_carry_last", "stale_slot_start", "decode_reads_neighbour",
          "attention_reads_neighbours_table")


def plant(fault: str, setattr_, prompt_lens, tile: int):
    """Plant ``fault`` in the PROGRAM through ``setattr_(module, name,
    value)`` (``monkeypatch.setattr``, or anything that can undo it) and
    return what to ``dataclasses.replace`` in the program's config; the
    reference stays the configuration's. ``prompt_lens``: the served
    requests' prompt lengths (a prompt's tile boundaries are multiples of
    ``tile``).

    - ``beta_not_doubled``: ``beta = sigmoid`` in (0, 1), Kimi-Linear's.
    - ``gate_out``: the ``G`` layer without its output gate.
    - ``lost_carry_first`` / ``lost_carry_last``: the KDA state a prompt's
      SECOND tile / its LAST tile starts from is zeros, not what the tile
      before it left (the convolutions' window still carries).
    - ``stale_slot_start``: a prompt's first tile goes on from what its slot
      held (serve it on an engine whose slots hold foreign state).
    - ``decode_reads_neighbour``: a decode row's KDA state is slot xor 1's.
    - ``attention_reads_neighbours_table``: the ``G`` layer's decode rows
      follow (and write through) slot xor 1's row of the block table.
    """
    import jax.numpy as jnp

    from deepspeed_tpu.models import kda, paged
    from deepspeed_tpu.ops.pallas import kda as kda_ops

    if fault == "beta_not_doubled":
        return {"kda_allow_neg_eigval": False}
    if fault == "gate_out":
        return {"use_gqa_gate": False}
    real_ragged, real_chunk = kda.ragged, kda_ops.kda_chunk
    if fault in ("lost_carry_first", "lost_carry_last", "stale_slot_start"):
        starts = {"lost_carry_first": [tile for n in prompt_lens if n > tile],
                  "lost_carry_last": [(n - 1) // tile * tile
                                      for n in prompt_lens if n > tile],
                  "stale_slot_start": [0]}[fault]
        hit = []    # the step's tiles the fault falls on, mixer to kernel

        def ragged(cfg, h, lp, state, slot0, scratch, slots, positions, tiles):
            if tiles is not None:
                hit[:] = [jnp.isin(tiles[2], jnp.asarray(starts))]
            return real_ragged(cfg, h, lp, state, slot0, scratch, slots,
                               positions, tiles)

        def chunk(state, rows, rows_w, fresh, cont, write, *rest, **kw):
            lost = hit[0]
            if fault == "stale_slot_start":
                fresh = fresh & ~lost
            else:
                fresh, cont = fresh | lost, cont & ~lost
            return real_chunk(state, rows, rows_w, fresh, cont, write, *rest,
                              **kw)

        setattr_(kda, "ragged", ragged)
        setattr_(kda_ops, "kda_chunk", chunk)
        return {}

    def neighbours(slots, scratch, tiles):
        n_dec = slots.shape[0] if tiles is None else tiles[0]
        dec = slots[:n_dec]
        return jnp.concatenate(
            [jnp.where(dec != scratch, dec ^ 1, dec), slots[n_dec:]])

    if fault == "decode_reads_neighbour":
        def ragged(cfg, h, lp, state, slot0, scratch, slots, positions, tiles):
            return real_ragged(cfg, h, lp, state, slot0, scratch,
                               neighbours(slots, scratch, tiles), positions,
                               tiles)

        setattr_(kda, "ragged", ragged)
        return {}
    assert fault == "attention_reads_neighbours_table", fault
    real_attention = paged.nope_attention_ragged

    def attention(cfg, h, lp, pool, tables, slots, positions, tiles, **kw):
        scratch = tables.shape[0] - 1
        return real_attention(cfg, h, lp, pool, tables,
                              neighbours(slots, scratch, tiles), positions,
                              tiles, **kw)

    setattr_(paged, "nope_attention_ragged", attention)
    return {}


def dirty_slots(engine, seed: int):
    """Every slot but the scratch slot holds a foreign state."""
    import jax
    import jax.numpy as jnp

    slots = engine.cache["slots"]
    keys = jax.random.split(jax.random.PRNGKey(seed + 1), len(slots))
    engine.cache = {**engine.cache, "slots": {
        name: (jax.random.normal(key, a.shape, jnp.float32) * 3.0).astype(
            a.dtype).at[:, -1].set(0)
        for key, (name, a) in zip(keys, sorted(slots.items()))}}


# ------------------------------------------------------------------ the cell
def test_the_new_cell_resolves_on_the_mix_as_it_is():
    spec = cellspec.resolve(CELL)
    assert spec["chips"] == 1 and spec["traffic_name"] == "longctx-pool"
    mix = spec["mix"]
    assert mix == cellspec.resolve(SPARSE)["mix"]
    assert mix["kind"] == "closed_loop" and mix["stream"] is False
    assert mix["prompt_tokens"] == {"dist": "lognormal", "median": 6144,
                                    "sigma": 0.25, "min": 4096, "max": 7936}
    assert mix["output_tokens"] == {"dist": "uniform", "min": 64, "max": 256}
    assert spec["cell"] == {"clients": 16}
    engine = {**spec["config"]["serve"]["engine"],
              **spec["cell"].get("engine", {})}
    assert engine == {"block_size": 128, "num_blocks": 1025, "max_seqs": 16,
                      "max_tokens_per_step": 512, "max_blocks_per_seq": 64,
                      "prefill_tile": 128}
    # every client has a slot and every slot can hold the mix's longest
    # request: no queue for slots, no preemption
    assert (engine["num_blocks"] - 1
            == engine["max_seqs"] * engine["max_blocks_per_seq"]
            and mix["total_tokens_max"]
            == engine["block_size"] * engine["max_blocks_per_seq"])
    assert {m["name"] for m in spec["end_to_end"]} == {"serve_tokens_per_s",
                                                       "setup_s"}
    names = {m["name"] for m in spec["per_layer"]}
    # <=, not ==: a later PR may append this cell to further metrics' lists
    assert set(NEW_READERS) | {
        "serve.request_p50_ms", "sched.pad_share", "sched.cold_dispatches",
        "sched.mixed_step_ms_p50", "sched.moe_grouped_share",
        "sched.state_bytes_share", "model.step_roofline",
        "model.ssm_step_roofline_kv", "model.pool_slice_share",
        "kernel.moe_gmm_share", "kernel.kda_decode_share",
        "kernel.kda_decode_roofline", "kernel.paged_decode_share",
        "kernel.tiled_prefill_share", "kernel.hybrid_paged_decode_roofline",
        "kernel.hybrid_tiled_prefill_roofline", "setup.cache_hit_share",
        "setup.program_builds", "setup.trace_s", "setup.lower_s",
        "setup.compile_s", "setup.cache_retrieval_s",
        "setup.background_compile_s", "setup.engine_init_s",
        "setup.unattributed_s"} <= names
    # readers that multiply ONE layer's K/V by num_layers would read four
    # times too high; every Pallas call is no attention kernel here; no
    # latent row, no selection, no Mamba, no window, no blocks of rows
    assert not names & {"kernel.attn_share", "kernel.paged_decode_roofline",
                        "kernel.tiled_prefill_roofline",
                        "model.step_roofline_kv", "kernel.mla_decode_share",
                        "kernel.dsa_index_share", "kernel.ssm_decode_share",
                        "kernel.selscan_tile_share", "kernel.swa_decode_share",
                        "kernel.blk_decode_share"}


def test_the_benchmark_has_the_cell_its_configuration_and_its_readers_once_each():
    with open(os.path.join(os.path.dirname(cellspec.HERE), "BENCHMARK.json")) as f:
        bench = json.load(f)
    assert [w["name"] for w in bench["workloads"]].count(CELL) == 1
    assert bench["workloads"][-1]["name"] == CELL       # appended, not put in
    (config,) = [c for c in bench["configs"]
                 if c["name"] == "solar-open2-250b-d4-ep8"]
    assert config["reduced"] == REDUCED and len(config["source"]) <= 200
    assert config["source"] == cellspec.resolve(CELL)["config"]["source"]
    why = bench["workloads"][-1]["why"]
    assert len(why) <= 200 and "16 clients" in why and "kda_chunk" in why
    metrics = [m["name"] for m in bench["per_layer"]]
    assert metrics[-2:] == list(NEW_READERS)
    for m in bench["per_layer"][-2:]:
        assert m == {"name": m["name"], "unit": "%", "better": "higher",
                     "source": "device_trace", "layer": "kernels",
                     "moves": "serve_tokens_per_s", "workloads": [CELL]}
    assert len(bench["configs"]) >= 12 and len(bench["workloads"]) >= 14
    assert [w["chips"] for w in bench["workloads"]].count(4) == 1
    with open(os.path.join(cellspec.HERE, "kernels", "kda_chunk.json")) as f:
        rx = re.compile(json.load(f)["trace_pattern"])
    assert rx.search("%kda_chunk.3 = (f32[51,128,8192]) custom-call(%p)")
    assert not rx.search("%kda_decode.3 = (f32[51,128,8192]) custom-call(%p)")


def test_the_configuration_is_the_catalog_entry_but_for_its_cut():
    conf = cellspec.resolve(CELL)["config"]
    assert conf["reduced"] == REDUCED
    if os.path.exists(CATALOG):
        with open(CATALOG) as f:
            (row,) = [r for r in map(json.loads, f)
                      if r["name"] == "Solar-Open2-250B"]
        assert conf["source"] == row["source_url"]
        assert {k: conf.get(k, "absent") for k in row["config"]
                if k not in REDUCED} == {
            k: v for k, v in row["config"].items() if k not in REDUCED}
        # each reduced key's published value stands beside it
        assert [conf[k + "_published"] for k in REDUCED] == [
            row["config"][k] for k in REDUCED]
    assert [conf[k] for k in REDUCED] == [4, [0], 40, 24576]
    assert (conf["expert_rank"], conf["expert_ranks"]) == (0, 8)
    # no width differs
    assert (conf["hidden_size"], conf["head_dim"], conf["moe_intermediate_size"],
            conf["num_attention_heads"], conf["num_key_value_heads"],
            conf["num_experts_per_tok"], conf["linear_attn_config"]) == (
                4096, 128, 1280, 64, 8, 8,
                {"short_conv_kernel_size": 4, "head_dim": 128, "num_heads": 64,
                 "num_kv_heads": None})
    assert sorted(conf["assumed"]) == [
        "a_gqa_gate", "b_router", "c_shared_expert_and_inert_keys",
        "d_kda_forms", "e_state_dtype", "f_weights"]
    assert "EIGHT" in conf["deployment"] and "not run" in conf["deployment"]
    check = conf["serve"]["check"]
    assert 0.0 < check["match_rate_min"] < 1.0 and "float8_e5m2" in check["why"]


def test_the_sizes_of_the_cut_term_by_term():
    """ISSUE 57's count, with this repo's bytes."""
    import jax
    import numpy as np

    family, cfg, reference = cellspec.model(cellspec.resolve(CELL))
    assert (cfg.layer_pattern, cfg.kda_heads, cfg.kda_head_dim, cfg.conv_kernel,
            cfg.kda_beta_scale, cfg.num_heads, cfg.num_kv_heads, cfg.held,
            cfg.num_experts, cfg.held_share) == (
                "GKKK", 64, 128, 4, 2.0, 64, 8, 40, 320, (0, 320))
    assert reference.kda_params(cfg) == 137_732_288
    assert reference.gqa_params(cfg) == 109_051_904
    assert reference.expert_params(cfg) == 15_728_640
    beside = 15_728_640 + 1_310_720 + 320 + 8_192
    k_layer, g_layer = 137_732_288 + beside, 109_051_904 + beside
    assert (k_layer, g_layer) == (154_780_160, 126_099_776)
    assert (k_layer + 629_145_600, g_layer + 629_145_600) == (
        783_925_760, 755_245_376)
    assert 3 * 783_925_760 + 755_245_376 == 3_107_022_656
    assert reference.num_params(cfg) == family.num_params(cfg) \
        == 3_107_022_656 + 201_326_592 + 4_096 == 3_308_353_344
    tree = jax.eval_shape(lambda: family.init_params(cfg, jax.random.PRNGKey(0)))
    assert sum(int(np.prod(a.shape))
               for a in jax.tree_util.tree_leaves(tree)) == 3_308_353_344
    assert reference.weight_bytes(cfg) == 2 * (3_308_353_344 - 24_576 * 4_096)
    assert reference.state_bytes_per_slot(cfg) == 3 * 4_341_760
    assert reference.kda_state_bytes_per_slot(cfg) == 3 * 4_194_304
    assert reference.kv_bytes_per_token(cfg) == 4_096
    assert reference.attn_flops_per_pair(cfg) == 4 * 64 * 128
    assert reference.ssm_flops_per_token(cfg) == 3 * 7 * 64 * 128 * 128
    assert reference.kda_chunk_io_bytes_per_token(cfg) == 3 * 4 * (5 * 8192 + 64)
    assert reference.kda_chunk_flops_per_tile(cfg, 128) == 3 * 64 * 14 * 128 ** 3
    # a token makes one pick of a held expert a layer on average
    assert cfg.top_k * cfg.held / cfg.num_experts == 1.0
    cache = jax.eval_shape(lambda: family.init_paged_cache(
        cfg, 1025, 128, jax.numpy.bfloat16, num_slots=17))
    assert cache["k"].shape == cache["v"].shape == (1, 1025, 128, 1024)
    assert cache["slots"]["kda"].shape == (3, 17, 128, 8192)
    assert cache["slots"]["conv"].shape == (3, 17, 48, 1536)
    held = sum(int(np.prod(a.shape)) * a.dtype.itemsize
               for a in jax.tree_util.tree_leaves(cache)) + 2 * 3_308_353_344
    assert 7.3e9 < held < 7.5e9     # ~46% of the chip's 16 GB


# what ``serve_cell.check`` does not refuse at the tiny size (the case's text)
FORGOTTEN_AT_THIS_SIZE = ("lost_carry_first",)

# ------------------------------------------------------ the tiny rehearsal
TINY_DOCS = {**TINY_POOL,
             "prompt_tokens": {"dist": "lognormal", "median": 44,
                               "sigma": 0.3, "min": 34, "max": 60},
             "output_tokens": {"dist": "uniform", "min": 24, "max": 40}}


def _tiny(copy):
    root = copy({
        "benchmark/configs/tiny-solar.json": TINY_SOLAR,
        "benchmark/traffic/tiny-docs.json": TINY_DOCS,
        "benchmark/cells/tiny.cell.json": {"clients": 3},
    }, configs=[{"name": "tiny-solar", "source": "test", "reduced": [],
                 "file": "benchmark/configs/tiny-solar.json", "why": "tiny"}],
       workloads=[{"name": "tiny.cell", "config": "tiny-solar",
                   "traffic": "tiny-docs", "chips": 1, "why": "on the CPU"}])
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
    """The rehearsal of the chip run: a tiny ``solar_open2`` added as files
    only, every step program warmed, a closed loop over HTTP, the served
    tokens against ``reference/solar_open2.py``."""
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
    # an untraced window: the new readers say nothing and do not raise
    raw["window"]["trace"] = {"busy_s": 1.0, "window_s": 2.0, "top_ops": [],
                              "idle_gaps": [], "collective_exposed_s": 0.0,
                              "kernel_s": {}}
    raw["window"].setdefault("samples", [])
    line = runner.result_line(
        spec, raw, {"platform": "cpu", "kind": "cpu", "count": 1}, trace=True,
        peaks={"bf16_flops_per_s": 1.0, "hbm_bytes_per_s": 1.0})
    assert not [k for k in line["metrics"] if k in NEW_READERS]


@pytest.mark.parametrize("fault", ("none", "dirty_slots_no_fault", "lower")
                         + FAULTS)
def test_planted_faults_at_a_small_size(copy, monkeypatch, fault):
    """The chip's controls (scratch, PERF.md section 6, PR 57) rehearsed:
    three requests of 34-60 prompt tokens in tiles of 16 (two to three
    carries a prompt), served by the engine alone and held to the reference
    by ``serve_cell.ServeRig.check`` itself. Served as it is, on clean slots
    and on slots that all hold foreign state: correct. The reference in
    float8 and each of ``plant``'s faults: not, but for the carry lost at a
    prompt's FIRST tile boundary, which the recurrence forgets under the 20 to
    45 prompt tokens and the answer that follow it (agreement 0.77 for a
    clean 0.96, the served token within 0.05 of the reference's best where
    the limit reads 0.08; the carry lost at the LAST boundary reads 0.51 and
    0.15): PERF.md section 7 (h) found the same of Granite's, and the case
    holds the reading so that a check that comes to see it says so."""
    import dataclasses
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
    # at 64 lanes and std 0.02 the G layer's scores are flat (std 0.03) and
    # its output a hundredth of the stream's: sharpened here (scores ~4 wide,
    # the output projection x 8) so that the layer weighs at this size as it
    # does at the published one, where the same draw gives scores 1.6 wide
    mix = params["lead"][0]["mix"]
    params["lead"][0]["mix"] = {**mix, "wq": mix["wq"] * 12, "wk": mix["wk"] * 12,
                                "wo": mix["wo"] * 8}
    records = check_controls.requests(spec, seed, 0)
    sizes = spec["config"]["serve"]["engine"]
    cfg_prog = cfg
    if fault in FAULTS:
        cfg_prog = dataclasses.replace(cfg, **plant(
            fault, monkeypatch.setattr, [r["prompt_len"] for r in records],
            sizes["prefill_tile"]))
    engine = RaggedInferenceEngine(
        lambda ctx: family.build(cfg_prog, ctx=ctx), RaggedConfig(**sizes),
        dtype=jnp.bfloat16, params=params, seed=seed)
    if fault in ("dirty_slots_no_fault", "stale_slot_start"):
        dirty_slots(engine, seed)
    for uid, r in enumerate(records):
        engine.put(uid, trafficgen.prompt_tokens(
            seed, r["stream_id"], r["i"], r["prompt_len"], cfg.vocab_size),
            max_new_tokens=r["max_tokens"])
    served = engine.generate_all()
    assert not jnp.asarray(engine.cache["slots"]["kda"][:, -1]).any()
    records = [{**r, "status": 200, "tokens": list(served[uid])}
               for uid, r in enumerate(records)]
    ref = reference
    if fault == "lower":
        ref = types.SimpleNamespace(forward=lambda c, p, ids, dt: reference.forward(
            c, p, ids, jnp.float8_e5m2 if dt == jnp.float32 else dt))
    rig = types.SimpleNamespace(engine=engine, seed=seed, cfg=cfg, spec=spec,
                                reference=ref)
    verdict = serve_cell.ServeRig.check(rig, records)
    assert verdict["ok"] is (fault in ("none", "dirty_slots_no_fault")
                             + FORGOTTEN_AT_THIS_SIZE), verdict


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


SLOT = 2 * 3 * 4_341_760    # a slot's state, read and written


def _synthetic(chunk_slots: bool = True) -> dict:
    """Two dispatches and their executions: a mixed step of 16 decode rows
    and 3 tiles of ONE prompt (384 tokens; 18 ms, ``kda_chunk`` 2.4 ms of it
    in 3 calls, ``kda_decode`` 0.9 ms) and a prefill step of 4 tiles of two
    prompts (500 tokens, 12 padding rows; 20 ms, ``kda_chunk`` 3.0 ms)."""
    ms = 1e6
    steps = [("ragged_step_d16_t3", 0.0, 18 * ms,
              {"tokens": 400, "pad": 0, "kv_tokens": 16 * 6000 + 3000,
               "attn_pairs": 16 * 6000 + 384 * 2800,
               "dec_kv_tokens": 16 * 6000, "state_bytes": 17 * SLOT,
               "dec_state_bytes": 16 * SLOT, "ssm_prefill_tokens": 384,
               "chunk_tiles": 3, "chunk_slots": 1, "state_pad_rows": 0,
               "slot_resets": 0}),
             ("ragged_step_d0_t4", 22 * ms, 20 * ms,
              {"tokens": 500, "pad": 12, "kv_tokens": 9000,
               "attn_pairs": 500 * 4000, "dec_kv_tokens": 0,
               "state_bytes": 2 * SLOT, "dec_state_bytes": 0,
               "ssm_prefill_tokens": 500, "chunk_tiles": 4, "chunk_slots": 2,
               "state_pad_rows": 0, "slot_resets": 1})]
    if not chunk_slots:
        steps = [(n, s, d, {k: v for k, v in a.items() if k != "chunk_slots"})
                 for n, s, d, a in steps]
    host = [["engine/dispatch", start + 0.1 * ms, 0.2 * ms,
             {"program": name, "state_kind": "kda", **args}]
            for name, start, _, args in steps]
    return {
        "host": [{"thread": "engine", "events": host}],
        "modules": [[f"jit_{n}(1)", s + 0.5 * ms, d] for n, s, d, _ in steps],
        "busy": [[s + 0.5 * ms, s + 0.5 * ms + d] for _, s, d, _ in steps],
        "kernels": {
            "kda_chunk": [[2 * ms + i * ms, 0.8 * ms] for i in range(3)]
            + [[24 * ms + i * 2 * ms, 1.0 * ms] for i in range(3)],
            "kda_decode": [[6 * ms + i * ms, 0.3 * ms] for i in range(3)]}}


def test_the_new_readers_count_the_tokens_the_slots_and_the_products():
    readers = cellspec.layer_readers(cellspec.resolve(CELL))
    ctx = _ctx(_synthetic())

    def read(name):
        return readers[name][1](ctx)

    assert read("kernel.kda_chunk_share") == pytest.approx(100 * 5.4 / 38)
    # 884 prompt tokens' q, k, v, g, beta in and y out in float32 through
    # three layers and three slots' states once each way, against 5.4 ms in
    # the kernel: the bytes bind before the products at the MXU's peak would
    io = 884 * 3 * 4 * (5 * 8192 + 64) + 3 * 2 * 3 * 4_194_304
    flops = 3 * 64 * 14 * 128 ** 3 * 884 / 128
    assert io / 819e9 > flops / 197e12
    assert read("kernel.kda_chunk_roofline") == pytest.approx(
        100 * (io / 819e9) / 5.4e-3, rel=1e-9)
    for name in NEW_READERS:
        assert 0.0 < read(name) < 100.0, name
    # the shared readers' geometry holds here: ONE attention layer's K and V
    # (4,096 B a token), the state in the step's bytes, 16 rows' states
    # against the decode kernel's time
    ref, cfg = ctx["reference"], ctx["cfg"]
    bytes_s = (2 * ref.weight_bytes(cfg) + 4096 * (16 * 6000 + 3000 + 9000)
               + 19 * SLOT) / 819e9
    flops_s = ((2 * ref.active_params(cfg) + ref.ssm_flops_per_token(cfg)) * 900
               + 4 * 64 * 128 * (16 * 6000 + 384 * 2800 + 500 * 4000)) / 197e12
    assert readers["model.ssm_step_roofline_kv"][1](ctx) == pytest.approx(
        100 * max(bytes_s, flops_s) / 38e-3, rel=1e-9)
    assert readers["sched.state_bytes_share"][1](ctx) == pytest.approx(
        100 * 19 * SLOT / (bytes_s * 819e9), rel=1e-9)
    assert readers["kernel.kda_decode_roofline"][1](ctx) == pytest.approx(
        100 * (16 * SLOT / 819e9) / 0.9e-3, rel=1e-9)


@pytest.mark.parametrize("bare", ["no_spans", "no_chunk_slots", "no_kernel"])
def test_a_program_without_spans_or_the_argument_reads_nothing(bare):
    """The parent of PR 57 (no such family; its ``"kda"`` family's spans carry
    no ``chunk_slots``), or a trace without the kernel: None, no error."""
    tl = _synthetic(chunk_slots=bare != "no_chunk_slots")
    if bare == "no_spans":
        tl = dict(tl, host=[])
    if bare == "no_kernel":
        tl["kernels"] = {}
    readers = cellspec.layer_readers(cellspec.resolve(CELL))
    ctx = _ctx(tl)
    assert ctx["window"]["trace"]["kernel_s"].keys() == tl["kernels"].keys()
    # the share reads the reduced trace alone; the roofline needs all three
    silent = NEW_READERS if bare == "no_kernel" else NEW_READERS[1:]
    for name in silent:
        assert readers[name][1](ctx) is None, name
