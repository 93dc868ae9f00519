"""The pool's one write site (``models/paged.write_rows_paged``): a tile's
rows go into their pool blocks as slices, decode rows stay single rows (a
block model's runs of four among them), and the pool after the write is the
row form's, ``pool.at[blk, off].set(rows)``, bit for bit outside the scratch
block (block 0, where the row form drops its padding rows and nothing
reads)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deepspeed_tpu.models import mixtral
from deepspeed_tpu.models.paged import write_kv_paged, write_rows_paged

MAX_SEQS, NB, WIDTH = 4, 48, 11   # the table: [MAX_SEQS + 1, WIDTH]


def _tables(sliding):
    """Distinct blocks a sequence, the scratch slot's row all zeros; a
    ``sliding`` table's first entry a sequence has slid out (it points at the
    scratch block, as ``ragged._slide_windows`` leaves it)."""
    ids = np.random.default_rng(1).permutation(np.arange(1, NB))
    bt = np.zeros((MAX_SEQS + 1, WIDTH), np.int32)
    bt[:MAX_SEQS] = ids[:MAX_SEQS * WIDTH].reshape(MAX_SEQS, WIDTH)
    if sliding:
        bt[:MAX_SEQS, 0] = 0
    return bt


# name -> (decode rows as (slot, position) or None for a padding row,
#          tiles as (slot, pos0, valid) in units of (bs, tile))
def _cases(bs, tile):
    dec = [(0, bs + 3), (1, 2 * bs - 1), None, (3, bs)]
    return {
        "aligned_full": (dec, [(2, bs, tile)]),
        "last_tile_short": (dec, [(2, bs, tile), (2, bs + tile, tile - 5)]),
        "padded_tile": (dec, [(2, bs, tile), (MAX_SEQS, 0, 0)]),
        "offset_37": (dec, [(2, bs + 37 % bs, tile)]),
        "offset_37_short": (dec, [(2, bs + 37 % bs, tile - 9)]),
        "no_decode_rows": ([], [(2, bs, tile), (1, 2 * bs, 7)]),
        "no_tiles": (dec, []),
        "two_tiles_one_sequence": (dec, [(2, bs, tile), (2, bs + tile, tile)]),
        "two_straddling_tiles_one_sequence": (
            dec, [(2, bs + 5, tile), (2, bs + 5 + tile, tile - 1)]),
    }


# (block size, tile): the pool cells', GPT-2 XL's four runs a tile, two
# where the block is whole tiles (seen in blocks of a tile's rows,
# ``paged.sub_blocks``: the long-context cell's pages of four tiles), and one
# where neither divides the other, which keeps the row form
SHAPES = {"block_is_tile": (128, 128), "gpt2_xl": (32, 128),
          "tile_under_block": (64, 16), "page_of_four_tiles": (512, 128),
          "block_beside_tile": (48, 128)}


def _step(case, bs, tile, block):
    """``_pack_step``'s planes for a case: rows past a tile's ``valid`` carry
    the scratch slot at position 0. With ``block`` a decode entry is a run of
    ``block`` rows from the position rounded down to it."""
    dec, tiles = _cases(bs, tile)[case]
    per = block or 1
    slots, pos = [], []
    for d in dec:
        s, p = (MAX_SEQS, 0) if d is None else (d[0], d[1] // per * per)
        slots += [s] * per
        pos += list(range(p, p + per))
    n_dec = len(slots)
    for s, p0, valid in tiles:
        slots += [s] * valid + [MAX_SEQS] * (tile - valid)
        pos += list(range(p0, p0 + valid)) + [0] * (tile - valid)
    ts, tp, tv = (np.asarray([t[i] for t in tiles] or [MAX_SEQS * (i == 0)],
                             np.int32) for i in range(3))
    return (np.asarray(slots, np.int32), np.asarray(pos, np.int32),
            (n_dec, ts, tp, tv, tile))


def _row_form(pool, rows, slots, pos, bt):
    bs = pool.shape[1]
    return pool.at[bt[slots, pos // bs], pos % bs].set(
        rows.reshape(rows.shape[0], -1))


def _filled(shape, seed):
    return jnp.asarray(np.random.default_rng(seed).standard_normal(shape),
                       jnp.bfloat16)


@pytest.mark.parametrize("shape", list(SHAPES))
@pytest.mark.parametrize("case", list(_cases(128, 128)))
@pytest.mark.parametrize("leaf", ["kv", "latent", "sliding", "block4"])
def test_pool_after_write_is_the_row_forms(leaf, case, shape):
    bs, tile = SHAPES[shape]
    block = 4 if leaf == "block4" else None
    slots, pos, tiles = _step(case, bs, tile, block)
    bt = jnp.asarray(_tables(sliding=leaf == "sliding"))
    t = slots.shape[0]
    n_dec, ts, tp, tv, _ = tiles

    def jitted(write):   # the program sees n_dec and tile
        return jax.jit(lambda *a: write(*a[:-3], (n_dec, *a[-3:], tile)))

    if leaf == "latent":   # one leaf, a row of lanes
        pool, rows = _filled((NB, bs, 24), 0), _filled((t, 24), 1)
        got = [jitted(write_rows_paged)(pool, rows, slots, pos, bt, ts, tp,
                                        tv)]
        want = [_row_form(pool, rows, slots, pos, bt)]
    else:                  # K and V, a row [Hkv, D]
        kc, vc = _filled((NB, bs, 16), 0), _filled((NB, bs, 16), 2)
        kk, vv = _filled((t, 2, 8), 1), _filled((t, 2, 8), 3)
        got = jitted(write_kv_paged)(kc, vc, kk, vv, slots, pos, bt, ts, tp,
                                     tv)
        want = [_row_form(kc, kk, slots, pos, bt),
                _row_form(vc, vv, slots, pos, bt)]
    for g, w in zip(got, want):
        np.testing.assert_array_equal(np.asarray(g[1:]).view(np.uint16),
                                      np.asarray(w[1:]).view(np.uint16))


@pytest.mark.parametrize("tiles", [False, True],
                         ids=["no_tiles", "a_tiled_engines_decode_step"])
def test_a_step_without_tile_rows_traces_the_row_scatter(tiles):
    """A ``prefill_tile=0`` engine and ``ragged_forward`` without tiles, and
    a tiled engine's decode-only program (``prefill_tiles`` with no row past
    ``n_dec``): today's program text."""
    pool, rows = _filled((NB, 16, 8), 0), _filled((6, 8), 1)
    slots = jnp.asarray([0, 1, 2, 3, 4, 4], jnp.int32)
    pos = jnp.asarray([3, 17, 40, 5, 0, 0], jnp.int32)
    bt = jnp.asarray(_tables(sliding=False))
    pad = np.zeros(1, np.int32)      # the padded tile such a program is handed
    new = jax.make_jaxpr(lambda *a: write_rows_paged(
        *a, (6, pad + MAX_SEQS, pad, pad, 16) if tiles else None))(
            pool, rows, slots, pos, bt)
    old = jax.make_jaxpr(_row_form)(pool, rows, slots, pos, bt)
    assert str(new) == str(old)


def _scatters(jaxpr, found):
    for eqn in jaxpr.eqns:
        if eqn.primitive.name.startswith("scatter"):
            found.append(eqn)
        for sub in jax.core.jaxprs_in_params(eqn.params):
            _scatters(sub, found)
    return found


def test_mixed_step_scatters_no_more_than_its_decode_rows():
    """The program of a ``d16_t3`` step of the Mixtral family: no scatter
    into a pool leaf has more indices than the step has decode rows, whatever
    the tiles' offsets turn out to be (it reads them on the device)."""
    cfg = mixtral.MixtralConfig.tiny(89)
    spec = mixtral.build(cfg)
    params = jax.eval_shape(spec.init_fn, jax.random.PRNGKey(0))
    nd, nt, tile, bs = 16, 3, 128, 128
    cache = jax.eval_shape(
        lambda: spec.init_paged_cache_fn(NB, bs, jnp.float32))
    t = nd + nt * tile
    i32 = lambda *s: jax.ShapeDtypeStruct(s, jnp.int32)
    step = lambda p, tok, sl, po, bt, c, ts, tp, tv: spec.ragged_forward_fn(
        p, tok, sl, po, bt, c, prefill_tiles=(nd, ts, tp, tv, tile))
    jaxpr = jax.make_jaxpr(step)(
        params, i32(t), i32(t), i32(t), i32(MAX_SEQS + 1, WIDTH), cache,
        i32(nt), i32(nt), i32(nt))
    leaf = (NB * cfg.num_layers, bs, cfg.num_kv_heads * cfg.hd)
    into_pool = [e for e in _scatters(jaxpr.jaxpr, [])
                 if e.invars[0].aval.shape == leaf]
    assert len(into_pool) == 2           # K and V, the decode rows
    assert all(e.invars[1].aval.shape[0] == nd for e in into_pool)


# ------------------------------------------------- what the engine says of it
@pytest.mark.parametrize("block,tile", [(8, 8), (4, 8), (16, 8), (12, 8)],
                         ids=["block_is_tile", "two_runs_a_tile",
                              "two_tiles_a_block", "block_beside_tile"])
def test_dispatch_span_counts_the_rows_written_as_slices(block, tile,
                                                         monkeypatch):
    """``engine/dispatch`` carries ``pool_slice_rows``, the step's real rows
    in prefill tiles where the write site takes tiles as slices (a block's
    rows divide a tile's, or a tile's a block's), 0 on a decode-only step and where it keeps single
    rows; ``inference_pool_rows_written_total``'s two forms sum to the
    tokens scheduled."""
    from deepspeed_tpu import telemetry
    from deepspeed_tpu.inference import ragged
    from deepspeed_tpu.inference.ragged import (RaggedConfig,
                                                RaggedInferenceEngine)

    seen = []
    real = ragged.span
    monkeypatch.setattr(ragged, "span", lambda name, **a: (
        seen.append(a) if name == "engine/dispatch" else None, real(name, **a))[1])
    cfg = mixtral.MixtralConfig.tiny(89)
    telemetry.configure(enabled=True)
    try:
        eng = RaggedInferenceEngine(
            lambda ctx: mixtral.build(cfg, ctx=ctx), RaggedConfig(
                max_tokens_per_step=32, max_seqs=4, block_size=block,
                num_blocks=33, max_blocks_per_seq=8, prefill_tile=tile),
            dtype=jnp.float32)
        eng.put("a", list(range(1, 22)), max_new_tokens=3)   # tiles 8, 8, 5
        eng.step()
        eng.put("b", list(range(30, 41)), max_new_tokens=3)  # beside a's row
        eng.generate_all()
        series = telemetry.snapshot()["metrics"][
            "inference_pool_rows_written_total"]["series"]
    finally:
        telemetry.configure(enabled=False)
    sliced = tile % block == 0 or block % tile == 0
    assert [a["tokens"] for a in seen[:2]] == [21, 12]
    assert [a["pool_slice_rows"] for a in seen[:2]] == (
        [21, 11] if sliced else [0, 0])
    assert all(a["pool_slice_rows"] == 0 for a in seen[2:])   # decode rows
    by_form = {s["labels"]["form"]: s["value"] for s in series}
    assert by_form["slice"] == sum(a["pool_slice_rows"] for a in seen)
    assert by_form["slice"] + by_form["row"] == eng.tokens_scheduled == sum(
        a["tokens"] for a in seen)
