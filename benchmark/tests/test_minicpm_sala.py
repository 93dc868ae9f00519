"""PR 60's additions to the benchmark: the ``minicpm_sala`` family as files
only (a configuration, a cell, the ``longctx32k-pool`` mix, a reference, two
kernels' files, ``bsa_spans.py`` and six readers), the cut's sizes term by
term, the readers' arithmetic, and the planted faults at a small size
(``plant``: the scratch script that plants them at the timed sizes on the
chip imports it from here)."""

import dataclasses
import json
import os
import re
import types

import pytest

import cellspec
import run as runner
from conftest import TINY_GPT2, TINY_POOL

CELL = "minicpm-sala-d8.longctx32k-pool"
NEW_READERS = ("kernel.bsa_decode_share", "kernel.bsa_decode_roofline",
               "kernel.bsa_prefill_share", "kernel.bsa_prefill_roofline",
               "model.bsa_step_roofline_kv", "sched.bsa_selected_share")
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
REDUCED = ["num_hidden_layers", "mixer_types", "vocab_size"]
FIELDS = {
    "vocab_size": "vocab_size", "hidden_size": "hidden_size",
    "num_layers": "num_hidden_layers", "mixer_types": "mixer_types",
    "depth_layers": "num_hidden_layers_published",
    "first_layer": "first_layer_published",
    "num_heads": "num_attention_heads", "num_kv_heads": "num_key_value_heads",
    "head_dim": "head_dim", "lightning_nh": "lightning_nh",
    "lightning_nkv": "lightning_nkv",
    "lightning_head_dim": "lightning_head_dim",
    "intermediate_size": "intermediate_size", "kernel_size": "kernel_size",
    "kernel_stride": "kernel_stride", "block_size": "block_size",
    "init_blocks": "init_blocks", "window_size": "window_size",
    "topk": "topk", "dense_len": "dense_len", "chunk_size": "chunk_size",
    "max_seq_len": "max_position_embeddings"}

TINY_SALA = {
    "source": "test", "family": "minicpm_sala",
    "config_class": "MiniCPMSalaConfig", "fields": FIELDS,
    "vocab_size": 256, "hidden_size": 64, "num_hidden_layers": 4,
    "mixer_types": ["minicpm4", "lightning-attn", "lightning-attn",
                    "minicpm4"],
    "num_hidden_layers_published": 8, "first_layer_published": 2,
    "num_attention_heads": 4, "num_key_value_heads": 2, "head_dim": 16,
    "lightning_nh": 4, "lightning_nkv": 4, "lightning_head_dim": 16,
    "intermediate_size": 96, "kernel_size": 4, "kernel_stride": 2,
    "block_size": 8, "init_blocks": 1, "window_size": 16, "topk": 4,
    "dense_len": 24, "chunk_size": 8, "max_position_embeddings": 2048,
    "reduced": [],
    "serve": {**TINY_GPT2["serve"], "check": {"match_rate_min": 0.6}},
}


# ------------------------------------------------------- the planted faults
FAULTS = ("dense_past", "no_local_blocks", "other_groups_selection",
          "stride_off", "other_layers_decays", "no_rotation",
          "state_lost_last_tile")


def plant(fault: str, reference, cfg, prompts, tile: int):
    """``reference`` with ``fault`` planted, as an object with ``forward``:
    the served path is right and the REFERENCE wrong, which reads the same
    disagreement as the fault in the program, for one serving of the
    requests. ``prompts``: the served requests' prompts (token lists), which
    ``state_lost_last_tile`` finds a sequence's last tile boundary by.

    - ``dense_past``: the selection left out (dense past ``dense_len``).
    - ``no_local_blocks``: the forced local blocks left out (``window_size``
      0: only the initial block is forced).
    - ``other_groups_selection``: a K/V head's group attends over the OTHER
      group's kept blocks.
    - ``stride_off``: compressed key ``j`` taken one stride on (the mean of
      rows ``S (j + 1) ..``).
    - ``other_layers_decays``: the Lightning layers' decay rows in reverse
      order (layer 10 with layer 15's, ...).
    - ``no_rotation``: Lightning's q and k not rotated.
    - ``state_lost_last_tile``: every Lightning layer's state zeroed at the
      prompt's last tile boundary (``(len - 1) // tile * tile``)."""
    import jax.numpy as jnp

    seen, patch = cfg, {}
    if fault == "dense_past":
        seen = dataclasses.replace(cfg, dense_len=2 ** 24)
    elif fault == "no_local_blocks":
        seen = dataclasses.replace(cfg, window_size=0)
    elif fault == "other_layers_decays":
        seen = dataclasses.replace(
            cfg, lightning_decay=tuple(reversed(cfg.lightning_decay)))
    elif fault == "other_groups_selection":
        kept = reference.kept_blocks
        patch["kept_blocks"] = lambda *a: kept(*a)[:, ::-1]
    elif fault == "stride_off":
        keys = reference.compressed_keys

        def shifted(c, k):
            ck = keys(c, k)
            return jnp.concatenate([ck[1:], jnp.zeros_like(ck[:1])])

        patch["compressed_keys"] = shifted
    elif fault == "no_rotation":
        patch["_rope"] = lambda x, theta: x
    elif fault != "state_lost_last_tile":
        raise ValueError(fault)

    def forward(_, params, ids, dtype):
        if fault == "state_lost_last_tile":
            head = min(8, min(len(p) for p in prompts))
            reset = sum(jnp.where(
                jnp.all(ids[:head] == jnp.asarray(p[:head])),
                (len(p) - 1) // tile * tile, 0) for p in prompts)
            recurrence = reference.recurrence

            def lost(q, k, v, lam):
                after = (jnp.arange(q.shape[0]) >= reset)[:, None, None]
                return jnp.where(
                    after, recurrence(q, jnp.where(after, k, 0),
                                      jnp.where(after, v, 0), lam),
                    recurrence(q, k, v, lam))

            patch["recurrence"] = lost
        was = {name: getattr(reference, name) for name in patch}
        for name, fn in patch.items():
            setattr(reference, name, fn)
        try:
            return reference.forward(seen, params, ids, dtype)
        finally:
            for name, fn in was.items():
                setattr(reference, name, fn)

    return types.SimpleNamespace(forward=forward)


def test_the_new_cell_resolves_on_its_own_mix():
    spec = cellspec.resolve(CELL)
    assert spec["chips"] == 1 and spec["traffic_name"] == "longctx32k-pool"
    mix = spec["mix"]
    assert mix["kind"] == "closed_loop" and mix["stream"] is False
    assert mix["prompt_tokens"] == {"dist": "lognormal", "median": 20480,
                                    "sigma": 0.25, "min": 12288, "max": 31744}
    assert mix["output_tokens"] == {"dist": "uniform", "min": 256, "max": 768}
    assert mix["total_tokens_max"] == 32768
    assert mix["limits"] == {"ttft_ms": 2000, "gap_ms": 200}
    assert spec["cell"] == {"clients": 16}
    engine = {**spec["config"]["serve"]["engine"],
              **spec["cell"].get("engine", {})}
    assert engine == {"block_size": 512, "num_blocks": 1089, "max_seqs": 16,
                      "max_tokens_per_step": 512, "max_blocks_per_seq": 64,
                      "prefill_tile": 128}
    # every client has a slot, every slot and the scratch slot's worth of
    # pages can hold the mix's longest request, and the harness's warm-up
    # enumerates one table width (serve_cell.py refuses a table past 64)
    assert engine["num_blocks"] - 1 == 17 * engine["max_blocks_per_seq"]
    assert mix["total_tokens_max"] == engine["block_size"] * 64
    # every prompt ends past the dense length
    assert mix["prompt_tokens"]["min"] > spec["config"]["dense_len"]
    assert {m["name"] for m in spec["end_to_end"]} == {"serve_tokens_per_s",
                                                       "setup_s"}
    names = {m["name"] for m in spec["per_layer"]}
    assert set(NEW_READERS) | {
        "serve.request_p50_ms", "sched.pad_share", "sched.cold_dispatches",
        "sched.mixed_step_ms_p50", "sched.state_bytes_share",
        "model.step_roofline", "model.pool_slice_share",
        "kernel.ssm_decode_share", "kernel.ssm_decode_roofline",
        "setup.cache_hit_share", "setup.program_builds", "setup.trace_s",
        "setup.lower_s", "setup.compile_s", "setup.cache_retrieval_s",
        "setup.background_compile_s", "setup.engine_init_s",
        "setup.unattributed_s"} <= names
    # no reader of another family's kernels or of a whole context's K and V
    assert not names & {"kernel.attn_share", "kernel.paged_decode_roofline",
                        "kernel.tiled_prefill_roofline",
                        "model.step_roofline_kv", "model.ssm_step_roofline_kv",
                        "kernel.dsa_index_share", "sched.dsa_selected_share",
                        "kernel.kda_decode_share", "kernel.moe_gmm_share"}


def test_the_benchmark_has_the_cell_its_configuration_and_its_readers_once_each():
    with open(os.path.join(os.path.dirname(cellspec.HERE), "BENCHMARK.json")) as f:
        bench = json.load(f)
    assert [w["name"] for w in bench["workloads"]].count(CELL) == 1
    (config,) = [c for c in bench["configs"] if c["name"] == "minicpm-sala-d8"]
    assert config["reduced"] == REDUCED and len(config["source"]) <= 200
    assert config["source"] == cellspec.resolve(CELL)["config"]["source"]
    (cell,) = [w for w in bench["workloads"] if w["name"] == CELL]
    assert len(cell["why"]) <= 200 and "16 workers" in cell["why"]
    metrics = [m["name"] for m in bench["per_layer"]]
    for name in NEW_READERS:
        assert metrics.count(name) == 1
        (m,) = [m for m in bench["per_layer"] if m["name"] == name]
        assert m["workloads"] == [CELL] and m["moves"] == "serve_tokens_per_s"
        assert m["unit"] == "%"
    assert len(bench["configs"]) >= 13 and len(bench["workloads"]) >= 15
    assert [w["chips"] for w in bench["workloads"]].count(4) == 1
    for kernel, other in (("bsa_decode", "bsa_prefill"),
                          ("bsa_prefill", "bsa_decode")):
        with open(os.path.join(cellspec.HERE, "kernels", kernel + ".json")) as f:
            rx = re.compile(json.load(f)["trace_pattern"])
        assert rx.search(f"%{kernel}.3 = bf16[32,16,128] custom-call(%p)")
        assert not rx.search(f"%{other}.3 = bf16[32,16,128] custom-call(%p)")
        assert not rx.search("%paged_decode.3 = bf16[32,16,128] custom-call(%p)")


def test_the_configuration_is_the_catalog_entry_but_for_its_cut():
    conf = cellspec.resolve(CELL)["config"]
    assert conf["reduced"] == REDUCED
    if os.path.exists(CATALOG):
        with open(CATALOG) as f:
            (row,) = [r for r in map(json.loads, f)
                      if r["name"] == "MiniCPM-SALA"]
        assert conf["source"] == row["source_url"]
        assert {k: conf.get(k, "absent") for k in row["config"]
                if k not in REDUCED} == {
            k: v for k, v in row["config"].items() if k not in REDUCED}
        assert [conf[k + "_published"] for k in REDUCED] == [
            row["config"][k] for k in REDUCED]
        assert conf["mixer_types"] == row["config"]["mixer_types"][9:17]
    assert [conf[k] for k in REDUCED] == [
        8, ["minicpm4"] + ["lightning-attn"] * 6 + ["minicpm4"], 18432]
    assert (conf["first_layer_published"], conf["vocab_rank"],
            conf["vocab_ranks"]) == (9, 0, 4)
    # no width differs
    assert (conf["hidden_size"], conf["head_dim"], conf["intermediate_size"],
            conf["num_attention_heads"], conf["num_key_value_heads"],
            conf["lightning_nh"], conf["lightning_nkv"],
            conf["lightning_head_dim"]) == (4096, 128, 16384, 32, 2, 32, 32,
                                            128)
    assert (conf["kernel_size"], conf["kernel_stride"], conf["block_size"],
            conf["init_blocks"], conf["window_size"], conf["topk"],
            conf["dense_len"]) == (32, 16, 64, 1, 2048, 64, 8192)
    # the decay table: Lightning Attention's slopes at published layers 9-16
    assert len(conf["lightning_decay"]) == 8
    for i, row_ in enumerate(conf["lightning_decay"]):
        assert row_ == pytest.approx([
            2.0 ** (-8.0 * (h + 1) / 32) * (1.0 - (9 + i) / 31 + 1e-5)
            for h in range(32)], rel=1e-12)
    assert sorted(conf["assumed"]) == [
        "a_sparse_config", "b_decay", "c_dense_switch", "d_forced_blocks",
        "e_stage_one", "f_inert_keys", "g_norms", "h_state_dtype",
        "i_weights"]
    assert "mup_denominator" in conf["assumed"]["f_inert_keys"]
    assert "FOUR" in conf["deployment"] and "not run" in conf["deployment"]
    check = conf["serve"]["check"]
    assert 0.0 < check["match_rate_min"] < 1.0 and "float8_e5m2" in check["why"]


def test_the_sizes_of_the_cut_term_by_term():
    """ISSUE 60's count, with this repo's bytes."""
    import jax
    import numpy as np

    family, cfg, reference = cellspec.model(cellspec.resolve(CELL))
    assert [n for _, n in cfg.runs] == [1, 6, 1]
    assert (cfg.first_layer, cfg.depth_layers, cfg.num_kv_heads, cfg.rep,
            cfg.kept_keys, cfg.local_blocks) == (9, 32, 2, 16, 4096, 32)
    assert cfg.lightning_decay[1] == pytest.approx(
        family.lightning_slopes(32, 10, 32))
    assert reference.layer_params(cfg, "minicpm4") == 253_763_840
    assert reference.layer_params(cfg, "lightning-attn") == 285_221_248
    assert reference.num_params(cfg) == family.num_params(cfg) \
        == 2 * 253_763_840 + 6 * 285_221_248 + 2 * 18_432 * 4_096 + 4_096 \
        == 2_369_854_208
    tree = jax.eval_shape(lambda: family.init_params(cfg, jax.random.PRNGKey(0)))
    assert sum(int(np.prod(a.shape))
               for a in jax.tree_util.tree_leaves(tree)) == 2_369_854_208
    assert reference.active_params(cfg) == 2_369_854_208 - 18_432 * 4_096
    assert reference.weight_bytes(cfg) == 2 * reference.active_params(cfg)
    assert reference.kv_bytes_per_token(cfg) == 2 * 1_024
    assert reference.attn_flops_per_pair(cfg) == 2 * 16_384
    assert reference.cmp_bytes_per_key(cfg) == 2 * 512
    assert reference.state_bytes_per_slot(cfg) == 6 * 2_097_152
    assert reference.ssm_flops_per_token(cfg) == 6 * 5 * 32 * 128 * 128
    cache = jax.eval_shape(lambda: family.init_paged_cache(
        cfg, 1089, 512, jax.numpy.bfloat16, num_slots=17))
    assert cache["k"].shape == cache["v"].shape == (2, 1089, 512, 256)
    assert cache["ck"].shape == (2, 1089, 32, 256)
    assert cache["slots"]["ssm"].shape == (6, 17, 128, 4096)
    held = sum(int(np.prod(a.shape)) * a.dtype.itemsize
               for a in jax.tree_util.tree_leaves(cache)) + 2 * 2_369_854_208
    assert 6.1e9 < held < 6.2e9     # 38% of the chip's 16 GB


# what ``serve_cell.check`` does not refuse at the tiny size: two Lightning
# layers of four heads whose decay rows (published layers 3 and 4 of 8) differ
# by a quarter; at the timed size six layers of 32 heads trade rows that
# differ up to 1.7-fold (PERF.md section 6, PR 60 has the chip's verdict)
FORGOTTEN_AT_THIS_SIZE = ("other_layers_decays",)

# ------------------------------------------------------ the tiny rehearsal
TINY_DOCS = {**TINY_POOL,
             "prompt_tokens": {"dist": "lognormal", "median": 52,
                               "sigma": 0.3, "min": 40, "max": 70},
             "output_tokens": {"dist": "uniform", "min": 24, "max": 40},
             "total_tokens_max": 110}


def _tiny(copy):
    root = copy({
        "benchmark/configs/tiny-sala.json": TINY_SALA,
        "benchmark/traffic/tiny-docs.json": TINY_DOCS,
        "benchmark/cells/tiny.cell.json": {"clients": 3},
    }, configs=[{"name": "tiny-sala", "source": "test", "reduced": [],
                 "file": "benchmark/configs/tiny-sala.json", "why": "tiny"}],
       workloads=[{"name": "tiny.cell", "config": "tiny-sala",
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
    """The rehearsal of the chip run: a tiny ``minicpm_sala`` added as files
    only, every step program warmed, a closed loop over HTTP, the served
    tokens against ``reference/minicpm_sala.py``."""
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
    raw["window"]["trace"] = {"busy_s": 1.0, "window_s": 2.0, "top_ops": [],
                              "idle_gaps": [], "collective_exposed_s": 0.0,
                              "kernel_s": {}}
    raw["window"].setdefault("samples", [])
    line = runner.result_line(
        spec, raw, {"platform": "cpu", "kind": "cpu", "count": 1}, trace=True,
        peaks={"bf16_flops_per_s": 1.0, "hbm_bytes_per_s": 1.0})
    assert not [k for k in line["metrics"] if k in NEW_READERS]


@pytest.mark.parametrize("fault", ("none", "lower") + FAULTS)
def test_planted_faults_at_a_small_size(copy, monkeypatch, fault):
    """The chip's controls (scratch, PERF.md section 6, PR 60) rehearsed:
    three requests of 40-70 prompt tokens in tiles of 16, all past the tiny
    dense length of 24, served by the engine alone and held to the reference
    by ``serve_cell.ServeRig.check`` itself. Served as it is: correct. The
    reference in float8 and each of ``plant``'s faults: not, but for
    ``FORGOTTEN_AT_THIS_SIZE``."""
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
    # at 64 lanes a draw of std 0.02 makes every projection an eighth of what
    # it is at 4,096 (and the MLP's way back a thirteenth): the stream would
    # be the embedding's and no layer weigh. Widened by the fan-in's root, so
    # that a layer weighs at this size as it does at the published one
    wider = {"wq": 8.0, "wk": 8.0, "wv": 8.0, "wo": 8.0, "w_gate": 8.0,
             "w_up": 8.0, "w_down": 13.0}
    # (the sparse branch needs no 8 x of its own at contexts of 40-110 keys:
    # the family's SPARSE_OUT_GAIN is there for means over thousands)
    monkeypatch.setattr(family, "SPARSE_OUT_GAIN", 1.0)
    params = jax.tree_util.tree_map(
        lambda x: x.astype(jnp.bfloat16),
        family.init_params(cfg, jax.random.PRNGKey(seed)))
    params = jax.tree_util.tree_map_with_path(
        lambda path, a: (a * wider.get(getattr(path[-1], "key", None), 1.0)
                         ).astype(a.dtype), params)
    records = check_controls.requests(spec, seed, 0)
    sizes = spec["config"]["serve"]["engine"]
    engine = RaggedInferenceEngine(
        lambda ctx: family.build(cfg, ctx=ctx), RaggedConfig(**sizes),
        dtype=jnp.bfloat16, params=params, seed=seed)
    prompts = [trafficgen.prompt_tokens(seed, r["stream_id"], r["i"],
                                        r["prompt_len"], cfg.vocab_size)
               for r in records]
    for uid, (r, prompt) in enumerate(zip(records, prompts)):
        engine.put(uid, prompt, max_new_tokens=r["max_tokens"])
    served = engine.generate_all()
    assert not jnp.asarray(engine.cache["slots"]["ssm"][:, -1]).any()
    records = [{**r, "status": 200, "tokens": list(served[uid])}
               for uid, r in enumerate(records)]
    ref = reference
    if fault == "lower":
        ref = types.SimpleNamespace(forward=lambda c, p, ids, dt: reference.forward(
            c, p, ids, jnp.float8_e5m2 if dt == jnp.float32 else dt))
    elif fault in FAULTS:
        ref = plant(fault, reference, cfg, prompts, sizes["prefill_tile"])
    rig = types.SimpleNamespace(engine=engine, seed=seed, cfg=cfg, spec=spec,
                                reference=ref)
    verdict = serve_cell.ServeRig.check(rig, records)
    assert verdict["ok"] is (fault in ("none",) + FORGOTTEN_AT_THIS_SIZE), \
        verdict


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


SLOT = 2 * 6 * 2_097_152    # a slot's state, read and written


def _synthetic(selecting: bool = True) -> dict:
    """Two dispatches and their executions: a mixed step of 12 decode rows at
    ~20K of context (4,064 kept keys each) and 3 tiles of ONE prompt at
    16,000 (384 queries keeping ~4,064 keys each; 25 ms, ``bsa_prefill`` 2.4
    ms of it, ``bsa_decode`` 0.3 ms, ``ssm_decode`` 0.6 ms) and a prefill
    step of 4 tiles from 4,096 (dense; 22 ms, ``bsa_prefill`` 1.0 ms)."""
    ms = 1e6
    kept = 4064
    steps = [("ragged_step_d16_t3", 0.0, 25 * ms,
              {"tokens": 396, "pad": 4, "kv_tokens": 12 * 20000 + 16384,
               "attn_pairs": 12 * 20000 + 384 * 16192,
               "dec_kv_tokens": 12 * 20000,
               "sel_pairs": 12 * kept + 384 * kept,
               "sel_kv_tokens": 12 * kept + 3 * kept,
               "dec_sel_kv_tokens": 12 * kept, "sel_queries": 396,
               "cmp_kv_tokens": 12 * 1249 + 3 * 1020,
               "state_bytes": 13 * SLOT, "dec_state_bytes": 12 * SLOT,
               "ssm_prefill_tokens": 384, "chunk_tiles": 3, "chunk_slots": 1,
               "state_pad_rows": 4, "slot_resets": 0}),
             ("ragged_step_d0_t4", 27 * ms, 22 * ms,
              {"tokens": 512, "pad": 0, "kv_tokens": 4608,
               "attn_pairs": 512 * 4352, "dec_kv_tokens": 0,
               "sel_pairs": 512 * 4352, "sel_kv_tokens": 4224 + 4352 + 4480
               + 4608, "dec_sel_kv_tokens": 0, "sel_queries": 0,
               "cmp_kv_tokens": 0, "state_bytes": SLOT, "dec_state_bytes": 0,
               "ssm_prefill_tokens": 512, "chunk_tiles": 4, "chunk_slots": 1,
               "state_pad_rows": 0, "slot_resets": 0})]
    if not selecting:
        steps = [(n, s, d, {k: v for k, v in a.items()
                            if k not in ("sel_queries", "cmp_kv_tokens")})
                 for n, s, d, a in steps]
    host = [["engine/dispatch", start + 0.1 * ms, 0.2 * ms,
             {"program": name, "state_kind": "lightning", **args}]
            for name, start, _, args in steps]
    return {
        "host": [{"thread": "engine", "events": host}],
        "modules": [[f"jit_{n}(1)", s + 0.5 * ms, d] for n, s, d, _ in steps],
        "busy": [[s + 0.5 * ms, s + 0.5 * ms + d] for _, s, d, _ in steps],
        "kernels": {
            "bsa_prefill": [[2 * ms, 1.2 * ms], [14 * ms, 1.2 * ms],
                            [29 * ms, 0.5 * ms], [40 * ms, 0.5 * ms]],
            "bsa_decode": [[4 * ms, 0.15 * ms], [16 * ms, 0.15 * ms]],
            "ssm_decode": [[6 * ms + i * ms, 0.1 * ms] for i in range(6)]}}


def test_the_new_readers_count_the_kept_pairs_the_kept_rows_and_the_state():
    readers = cellspec.layer_readers(cellspec.resolve(CELL))
    ctx = _ctx(_synthetic())

    def read(name):
        return readers[name][1](ctx)

    assert read("kernel.bsa_prefill_share") == pytest.approx(100 * 3.4 / 47)
    assert read("kernel.bsa_decode_share") == pytest.approx(100 * 0.3 / 47)
    kept = 4064
    # the tiles' kept pairs at 32,768 FLOP a pair (two layers) bind before
    # the rows their last queries keep at 2,048 B
    pairs = 384 * kept + 512 * 4352
    rows = 3 * kept + 4224 + 4352 + 4480 + 4608
    assert 32768 * pairs / 197e12 > 2048 * rows / 819e9
    assert read("kernel.bsa_prefill_roofline") == pytest.approx(
        100 * (32768 * pairs / 197e12) / 3.4e-3, rel=1e-9)
    # a decode row's kept keys are bytes-bound: 16 FLOP a byte
    assert read("kernel.bsa_decode_roofline") == pytest.approx(
        100 * (2048 * 12 * kept / 819e9) / 0.3e-3, rel=1e-9)
    assert read("sched.bsa_selected_share") == pytest.approx(
        100 * (12 * kept + pairs)
        / (12 * 20000 + 384 * 16192 + 512 * 4352), rel=1e-9)
    ref, cfg = ctx["reference"], ctx["cfg"]
    bytes_s = (2 * ref.weight_bytes(cfg) + 2048 * (12 * kept + rows)
               + 1024 * (12 * 1249 + 3 * 1020) + 14 * SLOT) / 819e9
    flops_s = ((2 * ref.active_params(cfg) + ref.ssm_flops_per_token(cfg)) * 908
               + 32768 * (12 * kept + pairs)) / 197e12
    assert read("model.bsa_step_roofline_kv") == pytest.approx(
        100 * max(bytes_s, flops_s) / 47e-3, rel=1e-9)
    for name in NEW_READERS:
        assert 0.0 < read(name) < 100.0, name
    # the shared readers hold here: the decode rows' states against
    # ``ssm_decode``'s time, the state's share of a step's least traffic
    assert readers["kernel.ssm_decode_roofline"][1](ctx) == pytest.approx(
        100 * (12 * SLOT / 819e9) / 0.6e-3, rel=1e-9)
    assert 0.0 < readers["sched.state_bytes_share"][1](ctx) < 100.0


@pytest.mark.parametrize("bare", ["no_spans", "no_selection", "no_kernel"])
def test_a_program_without_spans_or_the_argument_reads_nothing(bare):
    """The parent of PR 60 (no such family; DeepSeek-V3.2's spans carry
    ``sel_pairs`` but no ``sel_queries``), or a trace without the kernels:
    None, no error."""
    tl = _synthetic(selecting=bare != "no_selection")
    if bare == "no_spans":
        tl = dict(tl, host=[])
    if bare == "no_kernel":
        tl["kernels"] = {}
    readers = cellspec.layer_readers(cellspec.resolve(CELL))
    ctx = _ctx(tl)
    shares = ("kernel.bsa_decode_share", "kernel.bsa_prefill_share")
    silent = {"no_kernel": NEW_READERS[:4],
              "no_spans": [n for n in NEW_READERS if n not in shares],
              "no_selection": [n for n in NEW_READERS if n not in shares]}
    for name in silent[bare]:
        assert readers[name][1](ctx) is None, name
