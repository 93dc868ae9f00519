"""``models/minicpm_sala.py`` at a small size on the CPU, seeded weights: the
plain forward pass and what is served (prefill in tiles, then decode, through
K/V pages, the compressed-key leaf AND the Lightning slot state) against the
plain reference ``benchmark/reference/minicpm_sala.py``; the selection alone;
Lightning through ``mamba2.ssd_tiles`` / ``ssm_decode`` at a group a head
against the plain recurrence; the two block-sparse kernels in interpret mode
against XLA's gather; each term of the parameter counts at the published
widths; what a dispatch span says of the selected work.

Logits are compared, not tokens. The tiny configuration is dense up to 24 keys
and keeps 5 blocks of 8 past it (one initial, two local), kernels of 4 every
2: prompts of 37-91 tokens run both regimes and cross from one to the other
inside a prompt. Tolerance 2e-4 (float32 everywhere here); observed
differences are under 1e-6 on logits of magnitude 0.6.
"""

import dataclasses
import functools
import importlib.util
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from shared import one_engine_each, over_one_length  # tests/unit on the path

from deepspeed_tpu.inference import ragged
from deepspeed_tpu.inference.ragged import (
    RaggedConfig,
    RaggedInferenceEngine,
    _kept_keys,
    _kept_pairs,
)
from deepspeed_tpu.models import mamba2, minicpm_sala
from deepspeed_tpu.models.api import BlockSelection
from deepspeed_tpu.models.paged import SLOTS, sub_blocks
from deepspeed_tpu.ops.pallas import bsa_attention as bsa
from deepspeed_tpu.ops.pallas.paged_attention import decode_steps
from deepspeed_tpu.ops.pallas.ssm import ssd_chunk, ssd_chunk_xla, ssm_decode_xla

REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
ATOL = 2e-4


def _reference():
    spec = importlib.util.spec_from_file_location(
        "reference_minicpm_sala",
        os.path.join(REPO, "benchmark", "reference", "minicpm_sala.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


REF = _reference()
CFG = minicpm_sala.MiniCPMSalaConfig.tiny()   # S L L S


@pytest.fixture(scope="module")
def params():
    return minicpm_sala.init_params(CFG, jax.random.PRNGKey(1))


def _engine(params, cfg=CFG, device_state=False, **sizes):
    rc = RaggedConfig(**{**dict(
        max_tokens_per_step=24, max_seqs=4, block_size=16, num_blocks=33,
        max_blocks_per_seq=8, prefill_tile=8, device_state=device_state),
        **sizes})
    return RaggedInferenceEngine(lambda ctx: minicpm_sala.build(cfg, ctx=ctx),
                                 rc, dtype=jnp.float32, params=params)


@pytest.fixture(scope="module")
def engine_of(params):
    return one_engine_each(functools.partial(_engine, params))


def _prompts(lengths, seed=0):
    rng = np.random.default_rng(seed)
    return {uid: rng.integers(0, CFG.vocab_size, n).tolist()
            for uid, n in enumerate(lengths)}


# the longest request served here is 91 + 6 tokens
_reference_rows = over_one_length(REF.forward, 128)


def test_forward_is_the_reference(params):
    """The family's plain forward pass (dense ``[S, S]`` scores under the
    selection's mask, Lightning in chunks of 8) against the reference (query
    blocks, the recurrence token by token): 96 tokens, four times the dense
    length."""
    ids = jax.random.randint(jax.random.PRNGKey(2), (2, 96), 0, CFG.vocab_size)
    got = np.asarray(minicpm_sala.forward(CFG, params, ids))
    for b in range(2):
        np.testing.assert_allclose(
            got[b], np.asarray(REF.forward(CFG, params, ids[b])), atol=ATOL)


# case -> (engine sizes, prompt lengths, new tokens, recover after step)
SERVED = {
    # pages of 16 = two tiles of 8 = two selection blocks: tiles go into the
    # pool as slices of a page (``paged.sub_blocks``); six requests over four
    # slots, decode rows beside tiles, slots reused; 91 and 60 tokens cross
    # the dense length inside the prompt and decode past it, 5 / 9 / 3 stay
    # dense, 37 crosses while decoding starts
    "pages_of_two_tiles": ({}, [5, 91, 37, 9, 60, 3], 6, None),
    # tiles of 12: their edges fall inside the 8-token selection blocks and
    # inside pages (the row form of the pool's write), a compressed key
    # completes at every other row of a tile
    "tile_edges_off_the_blocks": (
        {"prefill_tile": 12, "max_tokens_per_step": 32}, [91, 37, 60], 5, None),
    # one tile a step: the state, the K/V rows and the compressed keys'
    # windows all carried over steps
    "a_tile_a_step": ({"max_tokens_per_step": 8}, [61], 4, None),
    "slot_reused": ({"max_seqs": 1}, [43, 29], 5, None),
    "recovered_and_recomputed": ({}, [5, 91, 37, 9], 8, 4),
}


def _serve(eng, prompts, new_tokens, recover_after=None):
    rows = {}
    emit_tokens = eng._emit_tokens

    def recording(logits, emit):
        lg = np.asarray(logits)
        for row, seq in emit:
            rows[(seq.uid, len(seq.generated))] = lg[row]
        return emit_tokens(logits, emit)

    eng._emit_tokens = recording
    try:
        for uid, prompt in prompts.items():
            eng.put(uid, prompt, max_new_tokens=new_tokens)
        steps = 0
        while eng.has_work:
            eng.step()
            steps += 1
            if steps == recover_after:
                eng._recover_device_path()
            assert steps < 500
    finally:
        del eng._emit_tokens
    return rows


def _assert_served_is_the_reference(eng, cfg, params, prompts, rows,
                                    new_tokens, what):
    for uid, prompt in prompts.items():
        generated = eng.get_request(uid).generated
        assert len(generated) == new_tokens
        want = _reference_rows(cfg, params, prompt + generated)
        for g in range(new_tokens):
            np.testing.assert_allclose(
                rows[(uid, g)], want[len(prompt) + g - 1], atol=ATOL,
                err_msg=f"{what}: request {uid}, generated token {g}")


@pytest.mark.parametrize("case", SERVED)
def test_served_logits_match_the_reference(params, engine_of, case):
    sizes, lengths, new_tokens, recover_after = SERVED[case]
    eng = engine_of(**sizes)
    prompts = _prompts(lengths)
    rows = _serve(eng, prompts, new_tokens, recover_after)
    _assert_served_is_the_reference(eng, CFG, params, prompts, rows,
                                    new_tokens, case)
    assert not np.asarray(eng.cache[SLOTS]["ssm"][:, -1]).any()
    assert eng.allocator.free_blocks == eng.cfg.num_blocks - 1


def test_the_cache_has_the_three_block_leaves_and_the_slot_leaf(engine_of):
    eng = engine_of()
    shapes = jax.tree_util.tree_map(lambda a: a.shape, eng.cache)
    assert shapes == {"k": (2, 33, 16, 32), "v": (2, 33, 16, 32),
                      "ck": (2, 33, 8, 32),
                      SLOTS: {"ssm": (2, 5, 16, 64)}}
    assert eng.cache[SLOTS]["ssm"].dtype == jnp.float32
    assert eng.spec.state_kind == "lightning"
    assert eng.spec.index_topk == 5 * 8
    assert eng.spec.index_blocks == BlockSelection(24, 8, 4, 2)
    with pytest.raises(ValueError, match="no whole number"):
        minicpm_sala.init_paged_cache(CFG, 9, 12, num_slots=3)


@pytest.mark.parametrize("case", ["pages_of_two_tiles", "slot_reused"])
def test_device_resident_path_serves_the_reference_tokens(params, engine_of,
                                                          case):
    sizes, lengths, new_tokens, recover_after = SERVED[case]
    eng = engine_of(device_state=True, **sizes)
    prompts = _prompts(lengths)
    _serve(eng, prompts, new_tokens, recover_after)
    for uid, prompt in prompts.items():
        generated = eng.get_request(uid).generated[:new_tokens]
        want = _reference_rows(CFG, params, prompt + generated)
        greedy = want.argmax(-1)[len(prompt) - 1:len(prompt) + new_tokens - 1]
        assert generated == greedy.tolist(), (case, uid)


def test_the_refusals_name_the_missing_piece(params):
    for sizes in ({"enable_prefix_cache": True}, {"quant": "int8"},
                  {"prefill_tile": 0}):
        with pytest.raises((ValueError, NotImplementedError)):
            _engine(params, **sizes)


# ------------------------------------------------------------ the selection
def _p(rows, n_cmp, seed=3):
    return jnp.asarray(np.random.default_rng(seed).random(
        (rows, CFG.num_kv_heads, n_cmp)), jnp.float32)


def _visible(p, pos):
    vis = minicpm_sala.compressed_visible(CFG, pos, p.shape[-1])
    return jnp.where(vis[:, None], p, -jnp.inf)


def test_selection_keeps_the_forced_blocks_and_nothing_past_the_query():
    """Block 0, the two local blocks ending at the query's own, two more by
    score; no block past ``t // 8``; under the dense length every block up to
    the query's."""
    pos = jnp.asarray([95, 64, 40, 23, 24, 7], jnp.int32)
    keep = np.asarray(minicpm_sala.kept_blocks(
        CFG, _visible(_p(6, 48), pos), pos, 12))
    own = np.asarray(pos) // 8
    for r in range(6):
        for g in range(2):
            kept = np.flatnonzero(keep[r, g])
            assert kept.max() == own[r]
            if pos[r] + 1 <= CFG.dense_len:
                assert kept.tolist() == list(range(own[r] + 1))
            else:
                assert len(kept) == min(CFG.topk, own[r] + 1)
                assert {0, own[r], own[r] - 1} <= set(kept.tolist())


def test_a_block_scores_the_maximum_of_the_kernels_that_overlap_it():
    """Kernels of 4 every 2 over blocks of 8: block ``b`` is overlapped by
    kernels ``4b - 1 .. 4b + 3``. One large ``P`` on a single kernel keeps
    the one or two blocks it overlaps; an invisible one keeps none."""
    pos = jnp.asarray([95], jnp.int32)
    for j, blocks in ((11, {2, 3}), (13, {3}), (15, {3, 4}), (20, {5})):
        p = jnp.full((1, 2, 48), 1e-3).at[0, :, j].set(1.0)
        p = p.at[0, :, 8].set(0.5)          # block 2's own runner-up
        keep = np.asarray(minicpm_sala.kept_blocks(CFG, _visible(p, pos), pos,
                                                   12))
        scored = set(np.flatnonzero(keep[0, 0]).tolist()) - {0, 10, 11}
        assert scored == (blocks | {2}
                          if len(blocks | {2}) <= 2 else blocks), (j, scored)
    # kernel 46 covers tokens 92..95: visible at 95, not at 94
    p = jnp.full((1, 2, 48), 1e-3).at[0, :, 46].set(1.0)
    assert np.asarray(minicpm_sala.compressed_visible(
        CFG, jnp.asarray([95, 94]), 48))[:, 46].tolist() == [True, False]


def test_equal_scores_keep_the_lowest_block_and_a_group_shares_one_selection():
    """All scores equal: the two kept by score are blocks 1 and 2. The
    selection is a K/V head's: its query heads have none of their own, and
    the two groups keep different blocks under different ``P``."""
    pos = jnp.asarray([95], jnp.int32)
    keep = np.asarray(minicpm_sala.kept_blocks(
        CFG, _visible(jnp.ones((1, 2, 48)), pos), pos, 12))
    assert np.flatnonzero(keep[0, 0]).tolist() == [0, 1, 2, 10, 11]
    assert keep.shape == (1, CFG.num_kv_heads, 12)
    p = jnp.full((1, 2, 48), 1e-3).at[0, 0, 13].set(1.0).at[0, 1, 21].set(1.0)
    keep = np.asarray(minicpm_sala.kept_blocks(CFG, _visible(p, pos), pos, 12))
    assert 3 in np.flatnonzero(keep[0, 0]) and 5 in np.flatnonzero(keep[0, 1])
    assert 5 not in np.flatnonzero(keep[0, 0])


def test_selection_is_the_references(params):
    """Stage one and two on real q and k against the reference's, which
    ranks by a stable sort."""
    rng = np.random.default_rng(5)
    q = jnp.asarray(rng.standard_normal((96, 2, 2, 16)), jnp.float32)
    k = jnp.asarray(rng.standard_normal((96, 2, 16)), jnp.float32)
    pos = jnp.arange(96, dtype=jnp.int32)
    ck = minicpm_sala.compress_keys(CFG, k)
    np.testing.assert_allclose(ck, REF.compressed_keys(CFG, k), atol=1e-6)
    p = minicpm_sala.group_scores(CFG, q[None], ck[None], pos[None])[0]
    got = minicpm_sala.kept_blocks(CFG, p, pos, 12)
    want = REF.kept_blocks(CFG, q, ck, pos, 12)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


# ---------------------------------------------------------------- Lightning
def test_lightning_through_the_mamba2_path_is_the_plain_recurrence():
    """``ssd_tiles`` (tiles of 8, the state carried from tile to tile and
    from slot row to slot row) and ``ssm_decode`` at a group a head (``G = H``
    = 4) against ``S_t = lambda S_{t-1} + k_t^T v_t``, ``o_t = q_t S_t``
    token by token: 20 prompt tokens in three tiles (the last of 4 rows), then
    5 decode rows."""
    rng = np.random.default_rng(7)
    nh, d, r, n_pre, n_dec = 4, 16, 8, 20, 5
    n = n_pre + n_dec
    q, k, v = (jnp.asarray(rng.standard_normal((n, nh, d)), jnp.float32)
               for _ in range(3))
    s = jnp.asarray(minicpm_sala.lightning_slopes(nh, 3, 8), jnp.float32)
    want = np.asarray(REF.recurrence(q, k, v, jnp.exp(-s)))
    pad = 3 * r - n_pre
    tiles = [jnp.pad(a[:n_pre], ((0, pad), (0, 0), (0, 0))).reshape(
        3, r, nh, d) for a in (v, k, q)]
    live = (jnp.arange(3 * r) < n_pre).astype(jnp.float32).reshape(3, r, 1)
    y, states = mamba2.ssd_tiles(
        None, tiles[0], jnp.broadcast_to(live, (3, r, nh)), -s, tiles[1],
        tiles[2], jnp.zeros((3, d, nh * d)), jnp.asarray([False, True, True]))
    np.testing.assert_allclose(np.asarray(y).reshape(-1, nh, d)[:n_pre],
                               want[:n_pre], atol=1e-4)
    # the decode rows go on from the last tile's state, in a leaf of 3 rows
    leaf = jnp.zeros((3, d, nh * d)).at[1].set(states[-1])
    for t in range(n_pre, n):
        leaf, y = ssm_decode_xla(
            leaf, jnp.asarray([1]), jnp.repeat(jnp.exp(-s), d)[None],
            v[t].reshape(1, -1), k[t].T[None], q[t].T[None])
        np.testing.assert_allclose(np.asarray(y).reshape(nh, d), want[t],
                                   atol=1e-4)
    assert not np.asarray(leaf[0]).any() and not np.asarray(leaf[2]).any()


# a step's tiles as the engine hands them over (``mamba2.tile_rows``): (read
# row, write row, fresh, cont, write) a tile; row 5 of the 6-row leaf is the
# scratch slot
SSD_STEPS = {
    "one_whole_tile": [(1, 1, 0, 0, 1)],
    # a slot's prompt over three tiles: the state is carried in the kernel,
    # the tiles before the last park zeros in the scratch slot
    "three_tiles_of_one_slot": [(3, 5, 1, 0, 0), (3, 5, 0, 1, 0),
                                (3, 3, 0, 1, 1)],
    # the second slot must not start from the first's carry; both are written
    "two_slots": [(2, 5, 0, 0, 0), (2, 2, 0, 1, 1), (0, 0, 0, 0, 1)],
    # position 0 of a slot whose row holds what the last request left (NaN)
    "fresh_over_garbage": [(4, 4, 1, 0, 1)],
    # ``dt`` 0 past the valid rows of a slot's last tile: they neither decay
    # nor feed the state that is written
    "short_last_tile": [(1, 5, 0, 0, 0), (1, 1, 0, 1, 1)],
    # a padding tile names the scratch slot both ways and writes it zeros
    "padding_tile": [(0, 0, 0, 0, 1), (5, 5, 1, 0, 0)],
    # B and C of two groups for four heads: ``mamba2.ssd_tiles``' shapes
    "shared_groups_are_refused": [(1, 1, 0, 0, 1)],
}


@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32],
                         ids=["bfloat16", "float32"])
@pytest.mark.parametrize("case", SSD_STEPS)
def test_ssd_chunk_kernel_is_the_xla_form(case, dtype):
    """``ssd_chunk`` in interpret mode (heads of 128 x 128, a whole lane tile
    each, cut out by the kernel's blocks) against ``ssd_chunk_xla`` on a leaf
    whose scratch row holds garbage before the step, ``dt`` any positive
    number: readings and written rows to 2e-4 of their largest magnitude
    under bfloat16 operands (an operand rounded at another point than
    ``ssd_tiles`` rounds it is 4e-3 off) and 1e-5 in float32, and no row but
    the written ones changes by a bit."""
    tiles = SSD_STEPS[case]
    rng = np.random.default_rng(len(case))
    n_i, r, h, n = len(tiles), 32, 4, 128

    def draw(*shape, scale=1.0):
        return jnp.asarray(scale * rng.standard_normal(shape), jnp.float32)

    x = draw(n_i, r, h * n).astype(dtype)
    b, c = (draw(n_i, r, h * n, scale=0.1).astype(dtype) for _ in range(2))
    dt = jnp.abs(draw(n_i, r, h)) * 0.5
    a = -jnp.arange(1, h + 1, dtype=jnp.float32) * 0.05
    if case == "short_last_tile":
        dt = dt.at[1, 11:].set(0.0)
    if case == "padding_tile":
        dt = dt.at[1].set(0.0)
    leaf = draw(6, n, h * n)
    if case == "fresh_over_garbage":
        leaf = leaf.at[4].set(jnp.nan)
    rows, rows_w, fresh, cont, write = (
        jnp.asarray(col, jnp.int32) for col in zip(*tiles))
    args = [leaf, rows, rows_w, fresh > 0, cont > 0, write > 0, x, dt, a, b, c]
    if case == "shared_groups_are_refused":
        args[-2:] = b[..., :2 * n], c[..., :2 * n]
        for impl in ("pallas", "xla"):
            with pytest.raises(ValueError, match="a group a head"):
                ssd_chunk(*args, impl=impl, interpret=True)
        return
    got_s, got_y = ssd_chunk(*args, impl="pallas", interpret=True)
    want_s, want_y = ssd_chunk_xla(*args)
    assert got_y.dtype == jnp.float32 and got_y.shape == (n_i, r, h * n)
    assert np.isfinite(np.asarray(got_y)).all()

    def rel(got, want):
        return float(jnp.max(jnp.abs(got - want)) / jnp.max(jnp.abs(want)))

    tol = 2e-4 if dtype == jnp.bfloat16 else 1e-5
    assert rel(got_y, want_y) < tol and rel(got_s, want_s) < tol
    written = sorted(set(np.asarray(rows_w).tolist()))
    untouched = [i for i in range(6) if i not in written]
    np.testing.assert_array_equal(np.asarray(got_s)[untouched],
                                  np.asarray(leaf)[untouched])
    if 5 in written:
        assert not np.asarray(got_s[5]).any()
    assert all((np.asarray(got_s[i]) != np.asarray(leaf[i])).any()
               for i in written)


@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32],
                         ids=["bfloat16", "float32"])
def test_ssd_chunk_xla_is_ssd_tiles_at_a_group_a_head(dtype):
    """``ssd_chunk_xla`` (one product over a head axis where
    ``mamba2.ssd_tiles`` loops over its groups; ``ops`` may not import
    ``models``) against the path ``lightning_ragged`` ran before the kernel:
    a dynamic slice a tile's state, ``mamba2.ssd_tiles`` at ``G = H``, a
    dynamic-update-slice a tile. The same roundings: to the bit in float32
    on this backend, a rounding flip of a bfloat16 operand apart otherwise.
    Head sizes that are no lane tiles (the tiny configurations'), which the
    kernel refuses by name."""
    rng = np.random.default_rng(5)
    n_i, r, h, n, p = 3, 8, 4, 16, 8

    def draw(*shape, scale=1.0):
        return jnp.asarray(scale * rng.standard_normal(shape), jnp.float32)

    x = draw(n_i, r, h * p).astype(dtype)
    b, c = (draw(n_i, r, h * n, scale=0.3).astype(dtype) for _ in range(2))
    dt = (jnp.abs(draw(n_i, r, h)) * 0.5).at[2, 5:].set(0.0)
    a = -jnp.arange(1, h + 1, dtype=jnp.float32) * 0.05
    leaf = draw(4, n, h * p)
    rows, rows_w, fresh, cont, write = mamba2.tile_rows(
        jnp.asarray([1, 1, 2]), jnp.asarray([8, 16, 0]), 0, 3)
    args = (leaf, rows, rows_w, fresh, cont, write, x, dt, a, b, c)
    with pytest.raises(ValueError, match="whole lane tiles"):
        ssd_chunk(*args, impl="pallas", interpret=True)
    got_s, got_y = ssd_chunk_xla(*args)
    s_old = jnp.stack([leaf[int(i)] for i in rows])
    want_y, s_new = mamba2.ssd_tiles(
        None, x.reshape(n_i, r, h, p), dt, a, b.reshape(n_i, r, h, n),
        c.reshape(n_i, r, h, n), jnp.where(fresh[:, None, None], 0.0, s_old),
        cont)
    want_s = leaf
    for i in range(n_i):
        want_s = want_s.at[rows_w[i]].set(jnp.where(write[i], s_new[i], 0.0))
    tol = 2e-4 if dtype == jnp.bfloat16 else 1e-6
    np.testing.assert_allclose(np.asarray(got_y), np.asarray(want_y),
                               atol=tol * float(jnp.abs(want_y).max()))
    np.testing.assert_allclose(np.asarray(got_s), np.asarray(want_s),
                               atol=tol * float(jnp.abs(want_s).max()))


def test_the_decay_table_keeps_the_published_indices():
    """The tiny stack starts at published layer 2 of 8; the cell's at 9 of
    32. A table handed in is the table."""
    assert CFG.lightning_decay[1] == minicpm_sala.lightning_slopes(4, 3, 8)
    row = minicpm_sala.lightning_slopes(32, 16, 32)
    assert abs(row[31] - 2.0 ** -8 * (1 - 16 / 31 + 1e-5)) < 1e-12
    other = dataclasses.replace(CFG, lightning_decay=tuple(
        (0.5,) * 4 for _ in range(4)))
    assert other.lightning_decay[2] == (0.5,) * 4


# ------------------------------------------------------------- the kernels
def _pool(rng, nb=20, bs=16, mb=8, lanes=32):
    kc, vc = (jnp.asarray(rng.standard_normal((nb, bs, lanes)), jnp.float32)
              for _ in range(2))
    tables = np.zeros((5, mb), np.int32)
    perm = rng.permutation(np.arange(1, nb))
    tables[0], tables[1] = perm[:8], perm[8:16]
    return kc, vc, jnp.asarray(tables)


def test_decode_kernel_reads_the_kept_blocks_alone():
    """``bsa_decode`` in interpret mode against XLA's gather: a row deep in
    the sparse regime, one under the dense length, a padding row, one at a
    block's last key."""
    rng = np.random.default_rng(0)
    kc, vc, tables = _pool(rng)
    slots = jnp.asarray([0, 1, 4, 0], jnp.int32)
    pos = jnp.asarray([100, 17, 0, 63], jnp.int32)
    q = jnp.asarray(rng.standard_normal((4, 2, 2, 16)), jnp.float32)
    keep = minicpm_sala.kept_blocks(CFG, _visible(_p(4, 64), pos), pos, 16)
    want = minicpm_sala.attend_xla(q, kc, vc, keep, slots, pos, tables, 8)
    k_sel, sel_tables = sub_blocks(kc, tables, 8)
    v_sel, _ = sub_blocks(vc, tables, 8)
    ids, n_keys = minicpm_sala.kept_lists(CFG, keep, slots, pos, sel_tables)
    assert np.asarray(n_keys).tolist() == [[37] * 2, [18] * 2, [1] * 2, [40] * 2]
    got = bsa.bsa_decode_attention(q, k_sel, v_sel, ids, n_keys, 0.25,
                                   interpret=True)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=1e-5)


def test_tile_kernel_attends_under_the_selections_bias():
    rng = np.random.default_rng(1)
    kc, vc, tables = _pool(rng)
    r = 8
    ts, tp, tv = (jnp.asarray(a, jnp.int32) for a in (
        [0, 0, 1, 4], [88, 96, 16, 0], [8, 5, 8, 0]))
    q = jnp.asarray(rng.standard_normal((4 * r, 2, 2, 16)), jnp.float32)
    pos = tp[:, None] + jnp.arange(r)
    p = jnp.asarray(rng.random((4, r, 2, 64)), jnp.float32)
    vis = minicpm_sala.compressed_visible(CFG, pos, 64)
    keep = minicpm_sala.kept_blocks(
        CFG, jnp.where(vis[:, :, None], p, -jnp.inf), pos, 16
    ).reshape(4 * r, 2, -1)
    want = minicpm_sala.attend_xla(q, kc, vc, keep, jnp.repeat(ts, r),
                                   pos.reshape(-1), tables, 8)
    got = bsa.bsa_prefill_attention(
        q.reshape(4 * r, 4, 16), kc, vc, keep, ts, tp, tv, tables, r, 0.25,
        interpret=True).reshape(4 * r, 2, 2, 16)
    valid = np.asarray((jnp.arange(r)[None] < tv[:, None]).reshape(-1))
    np.testing.assert_allclose(np.asarray(got)[valid],
                               np.asarray(want)[valid], atol=1e-5)


def test_a_selecting_decode_rows_grid_is_its_kept_blocks_whatever_the_table():
    """At the published sizes a decode row past the dense length walks at
    most 64 blocks a K/V head, eight a grid step: 8 steps at 12K of context
    and at 32K, behind a table of 32,768 tokens; under the dense length
    every block up to its own (16 steps at 8,191)."""
    cfg = minicpm_sala.MiniCPMSalaConfig()
    pos = jnp.asarray([12_300, 32_767, 8_191, 8_192, 0], jnp.int32)
    p = _p(5, 2048)
    vis = minicpm_sala.compressed_visible(cfg, pos, 2048)
    keep = minicpm_sala.kept_blocks(cfg, jnp.where(vis[:, None], p, -jnp.inf),
                                    pos, 512)
    assert np.asarray(keep).sum(-1).tolist() == [[64] * 2, [64] * 2,
                                                 [128] * 2, [64] * 2, [1] * 2]
    tables = jnp.arange(5 * 512, dtype=jnp.int32).reshape(5, 512)
    ids, n_keys = minicpm_sala.kept_lists(cfg, keep, jnp.arange(5), pos, tables)
    assert ids.shape == (5, 2, 128)          # 128 = dense_len / 64, not 512
    assert int(n_keys.max()) == 8192 and np.asarray(n_keys)[0].tolist() == [
        63 * 64 + 12_300 % 64 + 1] * 2
    nb, n_steps = bsa.decode_grid_steps(10, 64, 128, 2, 128)
    assert (nb, n_steps) == (8, 160)
    ends, _, _ = decode_steps(n_keys.reshape(-1) - 1, nb * 64, n_steps + 1)
    steps = np.diff(np.asarray(ends), prepend=0).reshape(5, 2)
    assert steps.tolist() == [[8, 8], [8, 8], [16, 16], [8, 8], [1, 1]]


# ------------------------------------------------------------ the arithmetic
def test_every_term_of_the_parameter_counts():
    """9,477,110,784 published, 2,369,854,208 in the benchmark's cut."""
    full = minicpm_sala.MiniCPMSalaConfig()
    assert full.mixer_types.count("minicpm4") == 8
    assert [i for i, k in enumerate(full.mixer_types) if k == "minicpm4"] == [
        0, 9, 16, 17, 22, 29, 30, 31]
    mlp = 3 * 4096 * 16384
    sparse = 3 * 4096 * 4096 + 2 * 4096 * 256 + 2 * 128 + mlp + 2 * 4096
    lightning = 5 * 4096 * 4096 + 3 * 128 + mlp + 2 * 4096
    assert (mlp, sparse, lightning) == (201_326_592, 253_763_840, 285_221_248)
    assert REF.layer_params(full, "minicpm4") == sparse
    assert REF.layer_params(full, "lightning-attn") == lightning
    assert 8 * sparse + 24 * lightning + 2 * 73_448 * 4096 + 4096 \
        == minicpm_sala.num_params(full) == REF.num_params(full) \
        == 9_477_110_784
    cut = dataclasses.replace(
        full, num_layers=8, mixer_types=full.mixer_types[9:17], first_layer=9,
        vocab_size=18_432, lightning_decay=None)
    assert [n for _, n in cut.runs] == [1, 6, 1]
    assert 2 * sparse + 6 * lightning + 2 * 18_432 * 4096 + 4096 \
        == minicpm_sala.num_params(cut) == REF.num_params(cut) \
        == 2_369_854_208
    tree = jax.eval_shape(lambda: minicpm_sala.init_params(
        cut, jax.random.PRNGKey(0)))
    assert sum(int(np.prod(a.shape))
               for a in jax.tree_util.tree_leaves(tree)) == 2_369_854_208
    assert cut.lightning_decay[1] == minicpm_sala.lightning_slopes(32, 10, 32)
    assert abs(cut.residual_scale - 1.4 / 32 ** 0.5) < 1e-12
    assert (cut.logits_divisor, cut.kept_keys, cut.list_blocks) == (16, 4096,
                                                                    128)


# ------------------------------------------------- what the engine says of it
def test_kept_pairs_by_the_families_rule():
    """Every key up to the dense length, the kept blocks past it (the own
    block cut at the query); without a rule what it always was."""
    rule = BlockSelection(dense_len=8192, block=64, kernel=32, stride=16)
    assert _kept_keys(8191, 4096, rule) == 8192
    assert _kept_keys(8192, 4096, rule) == 63 * 64 + 1
    assert _kept_keys(32_767, 4096, rule) == 4096
    for pos0, take in ((0, 128), (8128, 128), (8190, 5), (20_000, 128)):
        assert _kept_pairs(pos0, take, 4096, rule) == sum(
            p + 1 if p + 1 <= 8192 else 63 * 64 + p % 64 + 1
            for p in range(pos0, pos0 + take))
    assert _kept_pairs(5, 9, 8) == sum(min(p + 1, 8) for p in range(5, 14))
    assert rule.compressed(np.asarray([30, 31, 47, 32_767])).tolist() == [
        0, 1, 2, 2047]


def test_dispatch_spans_carry_the_selected_work_and_the_state(params,
                                                              monkeypatch):
    seen = []
    real = ragged.span
    monkeypatch.setattr(ragged, "span", lambda name, **a: (
        seen.append(a) if name == "engine/dispatch" else None,
        real(name, **a))[1])
    eng = _engine(params, device_state=True)
    eng.put("a", list(range(1, 44)), max_new_tokens=3)    # 24 + 19, then decode
    eng.generate_all()
    first, second, third = seen[:3]
    rule = eng.spec.index_blocks

    def kept(lo, hi):
        return sum(p + 1 if p < 24 else min(p + 1, 40 - 8 + p % 8 + 1)
                   for p in range(lo, hi))

    assert first["sel_pairs"] == kept(0, 24) == 300 and first["sel_queries"] == 0
    assert first["cmp_kv_tokens"] == 0
    assert second["sel_pairs"] == kept(24, 43) and second["sel_queries"] == 19
    # tiles 24..31, 32..39, 40..42 all select: what their last queries see
    assert second["cmp_kv_tokens"] == sum(
        int(rule.compressed(p)) for p in (31, 39, 42)) == 15 + 19 + 20
    assert second["sel_kv_tokens"] == sum(
        int(rule.kept(p, 40)) for p in (31, 39, 42))
    assert third["dec_sel_kv_tokens"] == third["sel_pairs"] \
        == int(rule.kept(43, 40)) == 36
    assert third["sel_queries"] == 1 and third["cmp_kv_tokens"] == 21
    for a in seen:
        assert a["state_kind"] == "lightning" and "chunk_slots" in a
        assert a["state_bytes"] % (2 * 2 * 4 * 16 * 64) == 0
        assert "sel_decode" not in a
