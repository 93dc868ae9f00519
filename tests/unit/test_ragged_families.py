"""Ragged/continuous-batching serving for the non-Llama families (the
round-4 gap: only llama set ragged_forward_fn). Mixtral exercises MoE over a
paged cache — per-token top-k routing at decode (reference
``inference/v2/model_implementations/mixtral`` + ``ragged_ops`` MoE
gather/scatter); GPT-2 exercises learned positional embeddings riding the
ragged per-token positions."""

import jax.numpy as jnp
import numpy as np
import pytest
from shared import one_engine_each  # tests/unit is rootdir-inserted

from deepspeed_tpu.inference.engine import InferenceEngine
from deepspeed_tpu.inference.ragged import RaggedConfig, RaggedInferenceEngine
from deepspeed_tpu.models import (deepseek, deepseek_v32, gpt2, llama,
                                  longcat_flash, mixtral)

MIX = mixtral.MixtralConfig.tiny(89)
GPT = gpt2.GPT2Config.tiny(89)
LLA = llama.LlamaConfig.tiny(89)
FAMILIES = {"gpt2": (gpt2, GPT), "mixtral": (mixtral, MIX),
            "llama": (llama, LLA)}
# MLA over a ONE-leaf latent pool, a dense layer before the expert layers; no
# dense-cache engine and no quantized pool, so it joins the cases that need
# neither (the paged-addressing cases have their own in test_deepseek.py)
DSK = deepseek.DeepseekConfig.tiny(89)
# the latent families with more to rewrite when a step is run again: two block
# leaves behind one table (the selection's index keys), two block layers a
# model layer
LATENT = {"deepseek": (deepseek, DSK),
          "deepseek_v32": (deepseek_v32,
                           deepseek_v32.DeepseekV32Config.tiny(89)),
          "longcat_flash": (longcat_flash,
                            longcat_flash.LongcatFlashConfig.tiny(89))}


def _build(name):
    mod, cfg = LATENT.get(name) or FAMILIES[name]
    return lambda ctx: mod.build(cfg, ctx=ctx)


def _prompts(n=4, seed=3):
    rng = np.random.default_rng(seed)
    return {i: list(rng.integers(0, 89, (int(rng.integers(3, 12)),)))
            for i in range(n)}


def _dense_reference(name, prompts, max_new):
    if name == "deepseek":  # no dense cache: the whole sequence every token
        import jax

        spec = deepseek.build(DSK)
        params = spec.init_fn(jax.random.PRNGKey(0))
        fwd = jax.jit(spec.forward_fn)
        out = {}
        for uid, p in prompts.items():
            seq = list(p)
            for _ in range(max_new):
                ids = np.zeros(24, np.int32)   # causal: the padding is inert
                ids[:len(seq)] = seq
                seq.append(int(np.argmax(fwd(params, ids[None])[0, len(seq) - 1])))
            out[uid] = seq[len(p):]
        return out
    eng = InferenceEngine(_build(name), dtype=jnp.float32, seed=0)
    out = {}
    for uid, p in prompts.items():
        full = eng.generate(np.asarray(p)[None], max_new_tokens=max_new)
        out[uid] = list(np.asarray(full[0, len(p):]))
    return out


def _ragged(name, tile=0, quant="off", device_state=True):
    return RaggedInferenceEngine(
        model=_build(name), dtype=jnp.float32, seed=0,
        ragged_config=RaggedConfig(
            max_tokens_per_step=16, max_seqs=3, block_size=4,
            num_blocks=49, max_blocks_per_seq=16, retry_backoff_s=0.0,
            prefill_tile=tile, quant=quant, device_state=device_state))


@pytest.fixture(scope="module")
def ragged():
    """``ragged(name, ...)``: the module's ONE engine of that family and
    those options, as new each time it is asked for (``shared.py``);
    ``second=True`` is another of the same, for the case that moves blocks
    between two."""
    get = one_engine_each(_ragged)
    return lambda name, tile=0, quant="off", device_state=True, second=False: \
        get(name, tile, quant, device_state, second=second)


def _generate(eng, prompts, max_new):
    """The tokens of ``prompts`` alone: a shared engine's ``generate_all``
    also returns what earlier cases left."""
    for uid, p in prompts.items():
        eng.put(uid, p, max_new_tokens=max_new)
    out = eng.generate_all()
    return {uid: out[uid] for uid in prompts}


@pytest.mark.parametrize("name", ["mixtral", "gpt2", "deepseek"])
class TestRaggedFamilies:
    def test_greedy_parity_vs_dense(self, ragged, name):
        """Continuous batching at mixed lengths must reproduce the dense
        engine's greedy continuations exactly (same weights, fp32)."""
        prompts = _prompts()
        want = _dense_reference(name, prompts, max_new=8)
        assert _generate(ragged(name), prompts, 8) == want

    def test_tiled_prefill_parity(self, ragged, name):
        prompts = _prompts(4, seed=7)
        assert _generate(ragged(name), prompts, 5) == \
            _generate(ragged(name, tile=4), prompts, 5)


@pytest.mark.parametrize("name", ["gpt2", "llama", "mixtral", "deepseek"])
def test_host_staged_fallback_parity(ragged, name):
    """What a degraded engine serves on (the host-staged step, tiled) gives
    the family the device step's tokens: five prompts through three slots,
    so prefill tiles ride beside decode rows."""
    prompts = _prompts(5, seed=11)
    host = ragged(name, tile=4, device_state=False)
    assert _generate(host, prompts, 7) == \
        _generate(ragged(name, tile=4), prompts, 7)
    assert host._tiled_jits and not host._dev_step_jits


@pytest.mark.parametrize("name", ["gpt2", "mixtral", "deepseek",
                                  "deepseek_v32", "longcat_flash"])
def test_a_failed_step_is_invisible_in_the_tokens(ragged, name):
    """One dispatch and, later, one readback fail mid-run: the watchdog
    rewinds to what was delivered and runs those positions again, which
    rewrites the same rows of every block leaf and picks the same tokens
    (greedy and sampled). ``nemotron_h`` and ``kimi_linear``, whose slot
    state cannot be run twice, have ``[recovered_and_recomputed]``."""
    from deepspeed_tpu.utils.faults import (POINT_DISPATCH, POINT_READBACK,
                                              get_fault_injector)

    prompts = _prompts(5, seed=11)
    eng = ragged(name, tile=4)
    failures = eng.step_failures
    outs = {}
    for faulty in (False, True):   # one engine: the programs compile once
        if faulty:
            get_fault_injector().configure(
                [{"point": POINT_DISPATCH, "after": 3},
                 {"point": POINT_READBACK, "after": 7}])
        for uid, p in prompts.items():
            kw = dict(temperature=0.8, top_k=20, seed=31 + uid) if uid % 2 \
                else {}
            eng.put((faulty, uid), p, max_new_tokens=7, **kw)
        out = eng.generate_all()
        outs[faulty] = {uid: out[faulty, uid] for uid in prompts}
    assert outs[True] == outs[False]
    assert eng.step_failures - failures == 2 and eng.degraded_mode == 0
    assert eng.cfg.device_state and not eng._pending
    assert eng.allocator.free_blocks == eng.cfg.num_blocks - 1


def test_mixtral_decode_routing_is_per_token(ragged):
    """Decode tokens of DIFFERENT sequences in one mixed batch must route
    independently: serving two different prompts together equals serving
    them alone (no cross-request routing contamination)."""
    prompts = _prompts(3, seed=23)
    solo = {}
    for uid, p in prompts.items():
        solo.update(_generate(ragged("mixtral"), {uid: p}, 6))
    assert _generate(ragged("mixtral"), prompts, 6) == solo


# ------------------------------------------------------- the paged contract
# A layer addresses the pool (``[L, NB, BS, Hkv*D]``, L and NB merged inside
# the step) through its own block table and owns no slice of it
# (``models/paged.py``). The reference below knows nothing of pools, tables
# or tiles: the family's dense ``decode_forward``, one sequence at a time.
from deepspeed_tpu.ops import kvquant  # noqa: E402

NB, BS, TILE, PAD_ROW = 12, 4, 4, 3
TABLES = np.zeros((PAD_ROW + 1, 3), np.int32)   # the last row: all scratch
TABLES[0] = [3, 7, 1]
TABLES[1] = [5, 2, 9]
TABLES[2] = [11, 4, 6]


def _params(mod, cfg):
    import jax

    return mod.init_params(cfg, jax.random.PRNGKey(5))


def _dense(mod, cfg, params, seq):
    """Logits and per-layer K/V ``[L, len, Hkv, D]`` of one sequence from
    the dense path."""
    cache = mod.init_cache(cfg, 1, 16, jnp.float32)
    logits, cache = mod.decode_forward(
        cfg, params, jnp.asarray(seq, jnp.int32)[None], cache, 0)
    n = len(seq)
    return (np.asarray(logits[0]), np.asarray(cache["k"][:, 0, :n]),
            np.asarray(cache["v"][:, 0, :n]))


def _layout(chunks, n_dec, tiled):
    """The step's flat token rows for ``chunks`` = [(slot, pos0, tokens)]:
    the first ``n_dec`` chunks are single decode rows; with ``tiled`` every
    later chunk starts a new TILE-aligned run of tiles, rows past its end
    are padding (pad slot, position 0)."""
    toks, slots, pos, rows = [], [], [], []
    ts, tp, tv = [], [], []
    for i, (slot, p0, tokens) in enumerate(chunks):
        rows.append([len(toks) + j for j in range(len(tokens))])
        toks += tokens
        slots += [slot] * len(tokens)
        pos += list(range(p0, p0 + len(tokens)))
        if tiled and i >= n_dec:
            for off in range(0, len(tokens), TILE):
                ts.append(slot)
                tp.append(p0 + off)
                tv.append(min(TILE, len(tokens) - off))
            pad = -len(tokens) % TILE
            toks += [0] * pad
            slots += [PAD_ROW] * pad
            pos += [0] * pad
    tiles = None
    if tiled:
        tiles = (n_dec, jnp.asarray(ts, jnp.int32), jnp.asarray(tp, jnp.int32),
                 jnp.asarray(tv, jnp.int32), TILE)
    toks, slots, pos = (jnp.asarray(a, jnp.int32) for a in (toks, slots, pos))
    return toks, slots, pos, tiles, rows


def _pool_rows(pool, layer, slot, n, heads):
    """Positions ``0..n-1`` of ``slot`` read back from layer ``layer`` of the
    pool through the block table, as float ``[n, Hkv, D]``."""
    p = np.arange(n)
    blk, off = TABLES[slot, p // BS], p % BS
    if getattr(pool, "is_quantized_kv", False):
        q = np.asarray(pool.q)[layer, blk, off].astype(np.float32)
        s = np.asarray(pool.s)[layer, blk].reshape(n, BS, heads)[p, off]
        return q.reshape(n, heads, -1) * s.astype(np.float32)[..., None]
    return np.asarray(pool)[layer, blk, off].reshape(n, heads, -1)


@pytest.mark.parametrize("tiled", [False, True], ids=["flat", "tiles"])
@pytest.mark.parametrize("quant", [None, "int8"], ids=["fp", "int8"])
@pytest.mark.parametrize("name", sorted(FAMILIES))
def test_paged_addressing_matches_the_dense_reference(name, quant, tiled):
    """A prefill step of two sequences, then a mixed step (their next
    tokens beside a third sequence's prompt): logits and every pool row the
    tables name equal the dense path's, on an fp pool exactly (float32) and
    on an int8 pool within its rounding; no other block is touched."""
    import jax

    mod, cfg = FAMILIES[name]
    params = _params(mod, cfg)
    rng = np.random.default_rng(17)
    seqs = [list(rng.integers(1, 89, n)) for n in (8, 6, 3)]
    codec = kvquant.get_codec(quant) if quant else None
    cache = mod.init_paged_cache(cfg, NB, BS, jnp.float32, codec=codec)
    heads = getattr(cfg, "num_kv_heads", cfg.num_heads)
    steps = [
        ([(0, 0, seqs[0][:7]), (1, 0, seqs[1][:5])], 0),
        ([(0, 7, seqs[0][7:]), (1, 5, seqs[1][5:]), (2, 0, seqs[2])], 2),
    ]
    got = {}
    for chunks, n_dec in steps:
        toks, slots, pos, tiles, rows = _layout(chunks, n_dec, tiled)
        logits, cache = mod.ragged_forward(
            cfg, params, toks, slots, pos, jnp.asarray(TABLES), cache,
            prefill_tiles=tiles)
        for (slot, p0, tokens), r in zip(chunks, rows):
            for j, row in enumerate(r):
                got[slot, p0 + j] = np.asarray(logits[row])
    tol = dict(rtol=2e-4, atol=2e-4) if codec is None else dict(
        rtol=0.1, atol=0.1)
    for slot, seq in enumerate(seqs):
        want, k, v = _dense(mod, cfg, params, seq)
        for p in range(len(seq)):
            np.testing.assert_allclose(got[slot, p], want[p], **tol,
                                       err_msg=f"logits {slot}:{p}")
        for layer in range(cfg.num_layers):
            for pool, dense in ((cache["k"], k), (cache["v"], v)):
                rows = _pool_rows(pool, layer, slot, len(seq), heads)
                amax = np.abs(dense[layer]).max()
                np.testing.assert_allclose(
                    rows, dense[layer],
                    atol=1e-4 if codec is None else 0.02 * amax,
                    rtol=1e-4 if codec is None else 0.05,
                    err_msg=f"layer {layer} slot {slot}")
    # blocks no table names (8, 10) hold what they were built with
    for leaf in jax.tree_util.tree_leaves(cache):
        assert not np.asarray(leaf)[:, [8, 10]].any()


@pytest.mark.parametrize("name", sorted(FAMILIES))
def test_pallas_kernels_read_through_a_layers_own_table(name, monkeypatch):
    """The same mixed step with both Pallas kernels (interpret mode) where
    the chip would run them: they index the merged block axis through the
    layer's table like the XLA gather."""
    from deepspeed_tpu.ops import attention

    mod, cfg = FAMILIES[name]
    params = _params(mod, cfg)
    rng = np.random.default_rng(3)
    chunks = [(0, 0, list(rng.integers(1, 89, 7))),
              (1, 0, list(rng.integers(1, 89, 5)))]
    mixed = [(0, 7, [11]), (1, 5, [13]), (2, 0, list(rng.integers(1, 89, 6)))]

    def run():
        cache = mod.init_paged_cache(cfg, NB, BS, jnp.float32)
        out = []
        for step, n_dec in ((chunks, 0), (mixed, 2)):
            toks, slots, pos, tiles, rows = _layout(step, n_dec, True)
            logits, cache = mod.ragged_forward(
                cfg, params, toks, slots, pos, jnp.asarray(TABLES), cache,
                prefill_tiles=tiles)
            out.append(np.asarray(logits)[np.concatenate(rows)])
        return np.concatenate(out), cache

    want, want_cache = run()
    monkeypatch.setattr(attention, "_on_tpu", lambda: True)
    got, got_cache = run()
    np.testing.assert_allclose(got, want, rtol=2e-4, atol=2e-4)
    # but for block 0, every layer's scratch block: padding rows write there
    # what the two paths leave unspecified
    for a, b in zip(got_cache.values(), want_cache.values()):
        np.testing.assert_allclose(np.asarray(a)[:, 1:], np.asarray(b)[:, 1:],
                                   rtol=2e-4, atol=2e-4)


@pytest.mark.parametrize("name,quant", [
    (n, q) for n in sorted(FAMILIES) for q in ("off", "int8")]
    + [("deepseek", "off")])
class TestBlockPayloadsOnTheStorageForm:
    """Host-side code indexes blocks as ``a[:, ids]`` on ``[L, NB, ...]``;
    payloads have the pool's form on both ends."""

    def test_gather_scatter_round_trip(self, ragged, name, quant):
        import jax

        eng = ragged(name, quant=quant)
        eng.put("a", list(range(1, 12)), max_new_tokens=2)
        eng.generate_all()
        src, dst = [1, 2, 3], [9, 10, 11]
        payload = eng._gather_blocks(src)
        pool = jax.tree_util.tree_leaves(eng.cache)
        for got, leaf in zip(jax.tree_util.tree_leaves(payload), pool):
            assert got.shape == (leaf.shape[0], 3) + leaf.shape[2:]
            assert got.any()          # the prompt's KV, not zeros
        eng._scatter_blocks(dst, payload)
        for leaf in jax.tree_util.tree_leaves(eng.cache):
            a = np.asarray(leaf)
            np.testing.assert_array_equal(a[:, dst], a[:, src])

    def test_handoff_export_import(self, ragged, name, quant):
        prompt = list(range(3, 14))
        want = _generate(ragged(name, quant=quant), {"h": prompt}, 6)
        a, b = ragged(name, quant=quant), ragged(name, quant=quant, second=True)
        a.put("h", prompt, max_new_tokens=6, handoff=True)
        a.generate_all()
        record = a.export_handoff("h")
        assert record.n_blocks == 3 and record.codec == quant
        assert b.import_handoff(record)
        assert b.generate_all()["h"] == want["h"]


# ----------------------------------------------- rows to heads, and its pin
from deepspeed_tpu.models import (kimi_linear, lfm2_moe,  # noqa: E402
                                  nemotron_h, sdar, smallthinker)

# every family with a ragged step; SDAR's decoding sequence is a block of 4 rows
PINNED = {**FAMILIES, **LATENT,
          "nemotron_h": (nemotron_h, nemotron_h.NemotronHConfig.tiny()),
          "kimi_linear": (kimi_linear, kimi_linear.KimiLinearConfig.tiny()),
          "smallthinker": (smallthinker,
                           smallthinker.SmallThinkerConfig.tiny()),
          "sdar": (sdar, sdar.SdarConfig.tiny(89)),
          # a head norm between the product and the rotation, at 16 lanes
          "lfm2_moe": (lfm2_moe, lfm2_moe.Lfm2MoeConfig.tiny())}


@pytest.mark.parametrize("name", sorted(PINNED))
def test_the_pin_of_rows_to_heads_changes_no_value(name, monkeypatch):
    """``paged.rows_to_heads`` holds a projection's product to the layout of
    its stored weight with an optimization barrier, which is no arithmetic:
    one mixed step a family (two decoding sequences beside a three-token
    prompt in a tile, over a cache of random rows) gives the same logits and
    the same cache, to the last bit, with the pin taken out."""
    import jax

    from deepspeed_tpu.models import paged

    mod, cfg = PINNED[name]
    per = 4 if name == "sdar" else 1
    params = _params(mod, cfg)
    rng = np.random.default_rng(23)
    cache = jax.tree_util.tree_map(
        lambda a: jnp.asarray(rng.standard_normal(a.shape), a.dtype),
        mod.init_paged_cache(cfg, NB, BS, jnp.float32, num_slots=PAD_ROW + 1))
    # slot 0 decodes at position 8 (sdar: its block 8..11), slot 1 at 4
    slots = [0] * per + [1] * per + [2, 2, 2, PAD_ROW]
    pos = [8 + j for j in range(per)] + [4 + j for j in range(per)] + [0, 1, 2, 0]
    toks = list(rng.integers(1, 88, 2 * per + 3)) + [0]
    i32 = lambda a: jnp.asarray(a, jnp.int32)   # noqa: E731
    tiles = (2 * per, i32([2]), i32([0]), i32([3]), TILE)
    bt = jnp.asarray(TABLES)
    tables = (bt, bt) if paged.SWA in cache else bt   # (full, sliding)

    def step():
        logits, after, *_ = mod.ragged_forward(
            cfg, params, i32(toks), i32(slots), i32(pos), tables, cache,
            prefill_tiles=tiles)
        return [np.asarray(a) for a in
                jax.tree_util.tree_leaves((logits, after))]

    pinned = step()
    calls = []
    monkeypatch.setattr(paged, "_pin", lambda y: calls.append(y) or y)
    plain = step()
    assert calls, "the family's ragged step projects no rows through the helper"
    for a, b in zip(pinned, plain):
        np.testing.assert_array_equal(a, b)
    assert np.isfinite(pinned[0]).all() and pinned[0].any()
