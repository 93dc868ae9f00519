"""The ``deepseek_v32`` family (DeepSeek-V3.2's architecture: ``deepseek``'s
layers with a low-rank query, YaRN, group-limited routing and a held share of
the experts, under a lightning indexer that keeps ``index_topk`` rows a query)
at a tiny size, float32, seeded weights, with ``index_topk`` (8) well under the
sequence lengths so that the selection bites: each piece against
``benchmark/reference/deepseek_v32.py`` (straight ``jax.numpy``, nothing
imported from the program), against its XLA form, or against a hand-worked
case."""

import functools
import importlib.util
import math
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from shared import one_engine_each  # tests/unit is rootdir-inserted

from deepspeed_tpu.inference.ragged import (RaggedConfig,
                                            RaggedInferenceEngine, _kept_pairs)
from deepspeed_tpu.models import deepseek, deepseek_v32 as v32
from deepspeed_tpu.models.experts import routed_experts_einsum
from deepspeed_tpu.ops.attention import rope_frequencies
from deepspeed_tpu.ops.pallas import dsa_attention as dsa

VOCAB = 89
CFG = v32.DeepseekV32Config.tiny(VOCAB)   # keeps 8 rows a query; 8 experts in
#                                   4 groups (2 stay) top-3; YaRN x4 over 16


@pytest.fixture(scope="module")
def reference():
    path = os.path.join(os.path.dirname(__file__), os.pardir, os.pardir,
                        "benchmark", "reference", "deepseek_v32.py")
    spec = importlib.util.spec_from_file_location("reference_deepseek_v32", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    mod.Q_BLOCK = mod.PAD_TO = 4   # the sequences here are multiples of 4
    return mod


@pytest.fixture(scope="module")
def params():
    return v32.init_params(CFG, jax.random.PRNGKey(1))


def test_forward_matches_the_reference(reference, params):
    """``forward_fn`` (the dense ``[S, S]`` index scores, ``select_mask`` as a
    bias on ``xla_attention``) against the reference's ``lax.top_k`` mask, at
    32 tokens of which a query keeps 8: float32 both."""
    ids = jnp.asarray(np.random.default_rng(0).integers(0, VOCAB, (2, 32)),
                      jnp.int32)
    got = np.asarray(v32.build(CFG).forward_fn(params, ids))
    for b in range(2):
        want = np.asarray(reference.forward(CFG, params, ids[b], jnp.float32))
        np.testing.assert_allclose(got[b], want, rtol=0, atol=1e-5)
    n = sum(int(np.prod(a.shape)) for a in jax.tree_util.tree_leaves(params))
    assert n == v32.num_params(CFG) == reference.num_params(CFG)
    axes = v32.build(CFG).param_logical_axes
    assert (jax.tree_util.tree_structure(params)
            == jax.tree_util.tree_structure(
                axes, is_leaf=lambda a: isinstance(a, tuple)))


# ------------------------------------------------ the engine's two block leaves
# Float32 end to end on the CPU: the served logits differ from the reference's
# full forward pass by summation order only (7e-8 here on logits of magnitude
# ~0.5). LOGIT_ATOL leaves a decade and is 10x under each negative control
# below: attention over the whole context in place of the selection, an index
# cache rounded to bf16 or zeroed, a latent cache rounded to bf16.
LOGIT_ATOL = 2e-6
PROMPT_LEN, NEW_TOKENS = 22, 6


def _engine(params, **over):
    sizes = dict(max_tokens_per_step=8, max_seqs=2, block_size=4,
                 num_blocks=33, max_blocks_per_seq=8, prefill_tile=4,
                 device_state=False)
    return RaggedInferenceEngine(
        lambda ctx: v32.build(CFG, ctx=ctx), dtype=jnp.float32, params=params,
        seed=0, ragged_config=RaggedConfig(**{**sizes, **over}))


@pytest.fixture(scope="module")
def engine_of(params):
    """``engine_of(**over)``: the module's ONE engine of those options, as new
    each time it is asked for (``shared.py``): the cases that send
    other requests, or spoil the pool, share its programs."""
    return one_engine_each(functools.partial(_engine, params))


def _serve_logits(eng, spoil=None):
    """Prefill a 22-token prompt in chunks of <= 8 tokens (tiles of 4) and
    decode 6 tokens; returns the served sequence and the logits row behind
    every emitted token. ``spoil(cache)`` rewrites the pool after every step."""
    rows = []
    emit = eng._emit_tokens
    dispatched = eng.dispatch_count

    def record(logits, pairs):
        rows.extend(np.asarray(logits[i]) for i, _ in pairs)
        if spoil is not None:
            eng.cache = spoil(eng.cache)
        return emit(logits, pairs)

    eng._emit_tokens = record
    try:
        prompt = list(np.random.default_rng(5).integers(1, VOCAB, PROMPT_LEN))
        eng.put("s", prompt, max_new_tokens=NEW_TOKENS)
        out = eng.generate_all()["s"]
    finally:
        del eng._emit_tokens        # the engine is shared: the method again
    # 3 prefill chunks
    assert eng.dispatch_count - dispatched >= 3 + NEW_TOKENS - 1
    return prompt + out, np.stack(rows)


def _reference_rows(reference, params, seq):
    ids = np.zeros(-(-len(seq) // 4) * 4, np.int32)  # causal: padding inert
    ids[:len(seq)] = seq
    want = np.asarray(reference.forward(CFG, params, jnp.asarray(ids),
                                        jnp.float32))
    return want[PROMPT_LEN - 1:len(seq) - 1]         # row i predicts i + 1


def test_engine_logits_match_the_reference(reference, params, engine_of):
    seq, got = _serve_logits(engine_of())
    want = _reference_rows(reference, params, seq)
    assert got.shape == want.shape == (NEW_TOKENS, VOCAB)
    np.testing.assert_allclose(got, want, rtol=0, atol=LOGIT_ATOL)


def _bf16_index(cache):
    return {**cache, "idx": cache["idx"].astype(jnp.bfloat16
                                                ).astype(cache["idx"].dtype)}


def _no_index(cache):
    return {**cache, "idx": jnp.zeros_like(cache["idx"])}


def _bf16_latent(cache):
    return {**cache, "kv": cache["kv"].astype(jnp.bfloat16
                                              ).astype(cache["kv"].dtype)}


@pytest.mark.parametrize("spoil", [_bf16_index, _no_index, _bf16_latent],
                         ids=["bf16_index_cache", "no_index_cache",
                              "bf16_latent_cache"])
def test_the_tolerance_catches_a_spoiled_cache(reference, params, engine_of,
                                               spoil):
    seq, got = _serve_logits(engine_of(), spoil)
    want = _reference_rows(reference, params, seq)
    assert np.abs(got - want).max() > 10 * LOGIT_ATOL


def test_the_tolerance_catches_dense_attention(reference, params, monkeypatch):
    """The same engine with every causal row kept (what ``deepseek`` does):
    the reference's selection is then not what was served."""
    monkeypatch.setattr(
        v32, "select_mask",
        lambda scores, positions, k: (jnp.arange(scores.shape[1])[None, :]
                                      <= positions[:, None]))
    gather = v32._gather_kept
    monkeypatch.setattr(v32, "_gather_kept", lambda mask, k, *rest: gather(
        mask, mask.shape[1], *rest))
    seq, got = _serve_logits(_engine(params))   # its own: another program
    want = _reference_rows(reference, params, seq)
    assert np.abs(got - want).max() > 10 * LOGIT_ATOL


# ------------------------------------------------------ each kernel = its XLA form
def _ragged_case(rng, dtype):
    """3 decode rows (one padding) and 3 tiles of 8 (a second chunk of a
    prompt, a first chunk of 5 tokens, a padding tile) over pools of 8-token
    blocks behind one table of 6."""
    bs, mb, nb, n_slots = 8, 6, 40, 5
    hi, di, h, lat, w = 4, 16, 2, 32, 128
    n_dec, tile = 3, 8
    bt = jnp.asarray(rng.permutation(np.arange(1, nb))[:(n_slots + 1) * mb]
                     .reshape(n_slots + 1, mb), jnp.int32).at[n_slots].set(0)
    ts = np.array([2, 3, n_slots], np.int32)
    tp = np.array([16, 0, 0], np.int32)
    tv = np.array([8, 5, 0], np.int32)
    slots = np.full(n_dec + 3 * tile, n_slots, np.int32)
    positions = np.zeros_like(slots)
    slots[:2], positions[:2] = (0, 1), (37, 5)
    for i in range(3):
        for r in range(tv[i]):
            slots[n_dec + i * tile + r] = ts[i]
            positions[n_dec + i * tile + r] = tp[i] + r
    t = len(slots)

    def normal(*shape):
        return jnp.asarray(rng.standard_normal(shape), dtype)

    pool_kv = normal(nb, bs, w).at[..., lat + 16:].set(0)
    q = normal(t, h, w).at[..., lat + 16:].set(0)
    return dict(
        q=q, q_idx=normal(t, hi, di),
        w_idx=jnp.asarray(rng.standard_normal((t, hi)), jnp.float32),
        pool_kv=pool_kv, pool_idx=normal(nb, bs, di),
        slots=jnp.asarray(slots), positions=jnp.asarray(positions), bt=bt,
        tiles=(n_dec, jnp.asarray(ts), jnp.asarray(tp), jnp.asarray(tv), tile),
        real=slots != n_slots, lat=lat)


@pytest.mark.parametrize("dtype,tol", [(jnp.float32, 2e-5), (jnp.bfloat16, 3e-2)],
                         ids=["float32", "bfloat16"])
def test_dsa_kernels_equal_their_xla_forms(dtype, tol):
    c = _ragged_case(np.random.default_rng(0), dtype)
    got = np.asarray(dsa.dsa_index_scores(
        c["q_idx"], c["w_idx"], c["pool_idx"], c["slots"], c["positions"],
        c["bt"], c["tiles"], interpret=True))
    want = np.asarray(v32.index_scores_xla(
        c["q_idx"], c["w_idx"], c["pool_idx"], c["slots"], c["positions"],
        c["bt"]))
    causal = (np.arange(got.shape[1])[None, :]
              <= np.asarray(c["positions"])[:, None]) & c["real"][:, None]
    # an indexer's score sums 4 heads of 16-lane products: bf16 keys round it
    np.testing.assert_allclose(np.where(causal, got, 0), np.where(causal, want, 0),
                               rtol=0, atol=tol * 10)

    class Geometry:
        kv_lora_rank, softmax_scale, index_topk = c["lat"], 0.3, 16

    n_dec = c["tiles"][0]       # decode rows token-major, tiles head-major
    q_tiles = jnp.swapaxes(c["q"][n_dec:], 0, 1)
    out = {impl: np.asarray(v32.sparse_pool_attention(
        Geometry, c["q"][:n_dec],
        (q_tiles[..., :c["lat"]], q_tiles[..., c["lat"]:]),
        c["q_idx"].astype(jnp.float32), c["w_idx"],
        c["pool_kv"], c["pool_idx"].astype(jnp.float32), c["slots"],
        c["positions"], c["bt"], c["tiles"], impl=impl), np.float32)
        for impl in ("pallas", "xla")}
    assert out["pallas"].shape == (2, len(c["real"]), c["lat"])
    np.testing.assert_allclose(out["pallas"][:, c["real"]],
                               out["xla"][:, c["real"]], rtol=0, atol=tol)


@pytest.mark.parametrize("heads,dtype,tol", [
    (16, jnp.float32, 2e-5), (32, jnp.float32, 2e-5), (64, jnp.float32, 2e-5),
    (128, jnp.bfloat16, 3e-2)], ids=["16", "32", "64", "128-bf16"])
def test_dsa_prefill_takes_and_gives_head_major_rows(heads, dtype, tol):
    """``dsa_attn_prefill`` (interpret mode) on head-major rows against its
    XLA form at the four head counts of the cells that share the contract
    (``test_deepseek.head_major_case``: the cells' widths, so each at its own
    sub-tile, 8 queries at this family's 128 heads), under a selection that
    keeps a third of the causal pairs and every query's own position: one
    bias row a query, broadcast over its heads."""
    from test_deepseek import head_major_case

    rng = np.random.default_rng(heads)
    c = head_major_case(rng, heads, dtype)
    t = 2 * c["tile"]
    slots = np.repeat(np.asarray(c["ts"]), c["tile"])
    positions = (np.repeat(np.asarray(c["tp"]), c["tile"])
                 + np.tile(np.arange(c["tile"]), 2))
    keys = np.arange(2 * c["bs"])[None, :]
    keep = (rng.random((t, 2 * c["bs"])) < 1 / 3) | (keys == positions[:, None])
    keep &= (keys <= positions[:, None]) & c["valid"][:, None]
    bias = jnp.where(jnp.asarray(keep), 0.0, -1e30)
    want = v32.prefill_attention_xla(
        *c["q"], c["pool"], bias, jnp.asarray(np.where(c["valid"], slots, 2)),
        c["tables"], 0.03)
    got = dsa.dsa_prefill_attention(
        *c["q"], c["pool"], bias, c["ts"], c["tp"], c["tv"], c["tables"],
        c["tile"], 0.03, interpret=True)
    assert got.shape == (heads, t, c["lat"])
    np.testing.assert_allclose(np.asarray(got, np.float32)[:, c["valid"]],
                               np.asarray(want, np.float32)[:, c["valid"]],
                               rtol=tol, atol=tol)


WALK_ROWS = ["everything_kept", "scattered_over_three_steps",
             "first_chunk_keeps_nothing", "middle_chunk_keeps_nothing",
             "padding_row_at_position_0"]


@functools.lru_cache(maxsize=None)
def _walk_case(dtype_name):
    """Five decode rows over pools of 8-token blocks behind a table of 24 (a
    step of the walk takes 8 blocks, so 3 steps of 64 tokens), 16 kept at
    most: the masked walk, ``decode_attention_xla`` over ``_gather_kept``'s
    rows, and the softmax over the kept positions in float64."""
    from deepspeed_tpu.ops.pallas.mla_attention import mla_decode_attention

    dtype = jnp.dtype(dtype_name)
    rng = np.random.default_rng(5)
    bs, mb, nb, n_slots, h, lat, w, k, scale = 8, 24, 200, 4, 2, 32, 128, 16, 0.3
    bt = jnp.asarray(rng.permutation(np.arange(1, nb))[:(n_slots + 1) * mb]
                     .reshape(n_slots + 1, mb), jnp.int32).at[n_slots].set(0)
    pool = jnp.asarray(rng.standard_normal((nb, bs, w)), dtype
                       ).at[..., lat + 16:].set(0)
    q = jnp.asarray(rng.standard_normal((5, h, w)), dtype
                    ).at[..., lat + 16:].set(0)
    positions = np.array([5, 190, 150, 140, 0], np.int32)
    slots = np.array([0, 1, 2, 3, n_slots], np.int32)
    mask = np.zeros((5, mb * bs), bool)
    mask[0, :6] = True
    mask[1, rng.choice(191, k, replace=False)] = True
    mask[2, 64 + rng.choice(151 - 64, k, replace=False)] = True
    mask[3, rng.choice(64, 8, replace=False)] = True
    mask[3, 128:131] = True
    mask[4, 0] = True
    got = np.asarray(mla_decode_attention(
        q, pool, jnp.asarray(slots), jnp.asarray(positions), bt, lat, scale,
        keep=jnp.asarray(mask), interpret=True), np.float32)
    rows, n = v32._gather_kept(jnp.asarray(mask), k, pool, jnp.asarray(slots), bt)
    gathered = np.asarray(v32.decode_attention_xla(q, rows, n, lat, scale),
                          np.float32)
    ctx = np.asarray(pool, np.float64)[np.asarray(bt)[slots]].reshape(5, -1, w)
    s = np.einsum("thw,tcw->thc", np.asarray(q, np.float64) * scale, ctx)
    s = np.where(mask[:, None, :], s, -np.inf)
    p = np.exp(s - s.max(-1, keepdims=True))
    exact = np.einsum("thc,tcl->thl", p / p.sum(-1, keepdims=True),
                      ctx[..., :lat])
    return got, gathered, exact


@pytest.mark.parametrize("row", range(len(WALK_ROWS)), ids=WALK_ROWS)
@pytest.mark.parametrize("dtype,tol", [("float32", 2e-5), ("bfloat16", 3e-2)])
def test_the_masked_walk_is_the_softmax_over_the_kept_rows(dtype, tol, row):
    """``mla_decode_attention(keep=...)``: a chunk that keeps nothing, the
    first included, leaves nothing behind; a row keeps what it would gather."""
    got, gathered, exact = _walk_case(dtype)
    assert np.isfinite(got).all()
    np.testing.assert_allclose(got[row], gathered[row], rtol=0, atol=tol)
    np.testing.assert_allclose(got[row], exact[row], rtol=0, atol=tol)


@pytest.mark.parametrize("impl", ["pallas", "xla"])
@pytest.mark.parametrize("table,form", [(3, "walk"), (6, "gather")],
                         ids=["table_24_walks", "table_48_gathers"])
def test_both_decode_forms_attend_alike(monkeypatch, table, form, impl):
    """``sparse_pool_attention`` on either side of the table-width constant
    (set between the two tables here): the decode rows take the form
    ``decode_form`` names, and either way the output is the softmax over the
    selection."""
    monkeypatch.setattr(v32, "WALK_MAX_TABLE_TOKENS", 32)
    c = _ragged_case(np.random.default_rng(1), jnp.float32)
    bt, n_dec = c["bt"][:, :table], c["tiles"][0]
    positions = jnp.minimum(c["positions"], table * 8 - 1)
    gathers = []
    real = v32._gather_kept
    monkeypatch.setattr(v32, "_gather_kept",
                        lambda *a: gathers.append(1) or real(*a))

    class Geometry:
        kv_lora_rank, softmax_scale, index_topk = c["lat"], 0.3, 6

    assert v32.decode_form(table * 8) == form
    got = np.asarray(v32.sparse_pool_attention(
        Geometry, c["q"][:n_dec], None, c["q_idx"][:n_dec], c["w_idx"][:n_dec],
        c["pool_kv"], c["pool_idx"], c["slots"][:n_dec], positions[:n_dec],
        bt, impl=impl))
    assert bool(gathers) == (form == "gather")
    scores = v32.index_scores_xla(c["q_idx"][:n_dec], c["w_idx"][:n_dec],
                                  c["pool_idx"], c["slots"][:n_dec],
                                  positions[:n_dec], bt)
    mask = v32.select_mask(scores, positions[:n_dec], 6)
    assert int(mask[0].sum()) == 6          # the selection bites
    q = jnp.swapaxes(c["q"][:n_dec], 0, 1)            # head-major, as got
    want = np.asarray(v32.prefill_attention_xla(
        q[..., :c["lat"]], q[..., c["lat"]:], c["pool_kv"],
        jnp.where(mask, 0.0, -1e30), c["slots"][:n_dec], bt, 0.3))
    np.testing.assert_allclose(got, want, rtol=0, atol=2e-5)


def test_selection_is_the_exact_top_k_lowest_position_first():
    """``select_mask`` against a stable sort, on scores with many equal
    values (negative zeros among them), rows shorter and longer than ``k``."""
    rng = np.random.default_rng(3)
    scores = np.round(rng.standard_normal((24, 64)) * 3).astype(np.float32)
    scores[scores == 0] *= rng.choice([-1.0, 1.0], (scores == 0).sum())
    positions = rng.integers(0, 64, 24).astype(np.int32)
    positions[:3] = (0, 15, 63)
    got = np.asarray(v32.select_mask(jnp.asarray(scores),
                                     jnp.asarray(positions), 16))
    for r, p in enumerate(positions):
        keep = np.argsort(-scores[r, :p + 1], kind="stable")[:16]
        want = np.zeros(64, bool)
        want[keep] = True
        np.testing.assert_array_equal(got[r], want)
    fine = rng.standard_normal((8, 64)).astype(np.float32)
    got = np.asarray(v32.select_mask(jnp.asarray(fine),
                                     jnp.full((8,), 63, jnp.int32), 16))
    _, top = jax.lax.top_k(jnp.asarray(fine), 16)
    assert all(set(np.flatnonzero(got[r])) == set(np.asarray(top[r]))
               for r in range(8))


def test_a_decode_row_gathers_its_kept_rows_and_no_others():
    """``_gather_kept``: ``min(context, k)`` rows of the pool in position
    order; the rest repeat the sequence's first row and are not counted."""
    pool = jnp.arange(6 * 4 * 2, dtype=jnp.float32).reshape(6, 4, 2)
    bt = jnp.asarray([[3, 1, 5], [2, 4, 0]], jnp.int32)
    mask = np.zeros((2, 12), bool)
    mask[0, [1, 6, 7, 11]] = True
    mask[1, [0, 2]] = True
    rows, n = v32._gather_kept(jnp.asarray(mask), 4, pool,
                               jnp.asarray([0, 1]), bt)
    np.testing.assert_array_equal(np.asarray(n), [4, 2])
    flat = np.asarray(pool)
    np.testing.assert_array_equal(
        np.asarray(rows[0]), [flat[3, 1], flat[1, 2], flat[1, 3], flat[5, 3]])
    np.testing.assert_array_equal(np.asarray(rows[1, :2]),
                                  [flat[2, 0], flat[2, 2]])


# ------------------------------------------------------------------- routing
def test_group_limited_routing_picks_inside_the_best_groups():
    """8 experts in 4 groups of 2, the 2 best groups stay (by the sum of a
    group's two largest biased scores), 3 picks: a hand-made router whose
    third-largest expert lies in the third group, which the limit excludes."""
    eye = jnp.eye(8, dtype=jnp.float32)
    logits = jnp.asarray([[3.0, 2.0, 2.5, -9.0, 2.9, -9.0, 0.0, 0.1]])
    ones = jnp.ones((8, 8, 4), jnp.float32)
    out = {}
    for groups in (None, (4, 2)):
        picked = []

        def spy(h, topv, topi, *a, **k):
            picked.append(np.asarray(topi))
            return jnp.zeros_like(h)

        from deepspeed_tpu.models import experts
        orig, experts._einsum_experts = experts._einsum_experts, spy
        try:
            routed_experts_einsum(logits, eye, ones, ones, ones.swapaxes(1, 2),
                                  3, scoring="sigmoid",
                                  bias=jnp.zeros((8,)), groups=groups)
        finally:
            experts._einsum_experts = orig
        out[groups] = sorted(picked[0][0])
    assert out[None] == [0, 2, 4]          # the three largest anywhere
    # groups (0,1) = 5.0 and (2,3) = 2.5 + sigmoid(-9) beat (4,5) = 2.9 + ...:
    # in sigmoid terms 0.953+0.881, 0.924+0, 0.948+0, 0.5+0.525: groups 0 and 3
    assert out[(4, 2)] == [0, 1, 7]


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["float32", "bfloat16"])
def test_one_group_is_bit_equal_to_no_groups(dtype):
    rng = np.random.default_rng(2)
    h = jnp.asarray(rng.standard_normal((12, 16)), dtype)
    args = [jnp.asarray(rng.standard_normal(s) * 0.3, dtype)
            for s in ((16, 8), (8, 16, 24), (8, 16, 24), (8, 24, 16))]
    kw = dict(scoring="sigmoid", bias=jnp.asarray(rng.standard_normal(8) * .01),
              scale=2.5, eps=1e-20)
    want = routed_experts_einsum(h, *args, 3, **kw)
    got = routed_experts_einsum(h, *args, 3, **kw, groups=(1, 1))
    np.testing.assert_array_equal(np.asarray(got, np.float32),
                                  np.asarray(want, np.float32))
    cfg = deepseek.DeepseekConfig.tiny()
    # Moonlight's router call and rotation are today's
    assert cfg.route_groups is None and cfg.held_share is None
    assert cfg.yarn is None


def test_the_ranks_parts_add_up_to_the_uncut_layer(params):
    """One expert layer's FFN on the same tokens: the routed parts of the two
    ranks (4 of 8 experts each) plus the shared expert, counted once, against
    the layer that holds all 8."""
    whole = v32.DeepseekV32Config.tiny(VOCAB)
    lp = jax.tree_util.tree_map(lambda a: a[0], params["layers"])
    h = jnp.asarray(np.random.default_rng(4).standard_normal((10, 64)),
                    jnp.float32)
    want = deepseek._ffn(whole, h, lp, routed_experts_einsum)
    shared = deepseek.swiglu(h, lp["ws_gate"], lp["ws_up"], lp["ws_down"])
    parts = 0
    for rank in range(2):
        cut = v32.DeepseekV32Config(**{**whole.__dict__,
                                       "rope_scaling": dict(whole.rope_scaling),
                                       "experts_held": 4, "expert_rank": rank})
        assert cut.held_share == (4 * rank, 8)
        mine = {**lp, **{k: lp[k][4 * rank:4 * rank + 4]
                         for k in ("w_gate", "w_up", "w_down")}}
        parts = parts + deepseek._ffn(cut, h, mine, routed_experts_einsum) - shared
    np.testing.assert_allclose(np.asarray(parts + shared), np.asarray(want),
                               rtol=0, atol=1e-6)
    assert v32.num_params(cut) < v32.num_params(whole)


# ---------------------------------------------------------------------- YaRN
def test_yarn_frequencies_and_mscale_by_hand():
    """32 rotated lanes a head (16 frequencies), theta 10,000, factor 4 over
    16 original positions, beta 32 / 1: every lane turns fewer than 32 times
    over 16 positions (lane 0 turns 16 / 2 pi = 2.5 times), so the ramp starts
    at lane 0; a lane that turns once is lane 16 ln(16 / 2 pi) / ln 10,000 =
    1.62, so from lane 2 on the frequencies are the plain ones / 4."""
    half, theta = 16, 10000.0
    plain = theta ** (-np.arange(half) / half)
    got = np.asarray(rope_frequencies(half, theta, (4.0, 32.0, 1.0, 16)))
    low = max(math.floor(half * math.log(16 / (32 * 2 * math.pi)) / math.log(theta)), 0)
    high = math.ceil(half * math.log(16 / (1 * 2 * math.pi)) / math.log(theta))
    assert (low, high) == (0, 2)
    ramp = np.clip(np.arange(half) / 2.0, 0, 1)
    np.testing.assert_allclose(got, plain / 4 * ramp + plain * (1 - ramp),
                               rtol=1e-6)
    np.testing.assert_allclose(got[0], 1.0, rtol=1e-6)          # untouched
    np.testing.assert_allclose(got[1], plain[1] * (0.5 / 4 + 0.5), rtol=1e-6)
    np.testing.assert_allclose(got[2:], plain[2:] / 4, rtol=1e-6)
    np.testing.assert_allclose(np.asarray(rope_frequencies(half, theta)),
                               plain, rtol=1e-6)
    mscale = 0.1 * math.log(4.0) + 1.0
    assert CFG.softmax_scale == pytest.approx(32 ** -0.5 * mscale ** 2)
    # DeepSeek-V3.2's own: factor 40 -> mscale 1.3689, squared on 192^-0.5
    big = deepseek.DeepseekConfig(rope_scaling={
        "type": "yarn", "factor": 40, "beta_fast": 32, "beta_slow": 1,
        "mscale": 1, "mscale_all_dim": 1,
        "original_max_position_embeddings": 4096})
    assert big.softmax_scale == pytest.approx(
        192 ** -0.5 * (0.1 * math.log(40) + 1) ** 2)
    assert big.yarn == (40.0, 32.0, 1.0, 4096) and hash(big) == hash(big)
    assert deepseek.DeepseekConfig().softmax_scale == 192 ** -0.5


# --------------------------------------- both leaves through the cache manager
def test_a_prefix_hit_restores_both_pool_leaves(params, engine_of):
    """Two prompts that share 16 tokens (4 whole blocks): the second splices
    the first's blocks in, latent rows and index keys alike, and serves the
    tokens an engine without the prefix cache serves."""
    rng = np.random.default_rng(7)
    shared = list(rng.integers(1, VOCAB, 16))
    prompts = {"a": shared + list(rng.integers(1, VOCAB, 5)),
               "b": shared + list(rng.integers(1, VOCAB, 7))}
    want = {}
    for uid, prompt in prompts.items():
        eng = engine_of()
        eng.put(uid, prompt, max_new_tokens=4)
        want[uid] = eng.generate_all()[uid]
    eng = _engine(params, enable_prefix_cache=True)
    eng.put("a", prompts["a"], max_new_tokens=4)
    assert eng.generate_all()["a"] == want["a"]
    eng.put("b", prompts["b"], max_new_tokens=4)
    assert eng.generate_all()["b"] == want["b"]
    assert eng.prefix_hits == 1 and eng.prefix_tokens_reused == 16
    assert set(eng.cache) == {"kv", "idx"}


def test_pool_pressure_and_containment_recompute_both_pool_leaves(params,
                                                                  engine_of):
    """A pool too small for two sequences at once: the second waits for the
    first's blocks and is computed over them (both leaves hold the first's
    stale rows) to the tokens an engine with room serves. Then crash
    containment (``reset_state``: a fresh cache, both leaves) and the same
    requests again, recomputed from their prompts to the same tokens."""
    rng = np.random.default_rng(8)
    prompts = {u: list(rng.integers(1, VOCAB, 14)) for u in ("a", "b")}
    roomy = engine_of()
    for uid, prompt in prompts.items():
        roomy.put(uid, prompt, max_new_tokens=10)
    want = roomy.generate_all()
    want = {uid: want[uid] for uid in prompts}
    tight = _engine(params, num_blocks=10)       # 9 usable blocks of 4 tokens
    for uid, prompt in prompts.items():
        tight.put(uid, prompt, max_new_tokens=10)
    tight.step()
    assert len(tight._running) == 1              # 6 blocks a sequence, 9 free
    assert tight.generate_all() == want
    assert tight.allocator.free_blocks == 9
    assert float(jnp.abs(tight.cache["idx"][:, 1:]).max()) > 0
    for uid, prompt in prompts.items():
        tight.put(uid + "2", prompt, max_new_tokens=10)
    tight.step()
    assert tight.reset_state() == 2
    assert set(tight.cache) == {"kv", "idx"}
    assert float(jnp.abs(tight.cache["idx"]).max()) == 0
    for uid, prompt in prompts.items():
        tight.put(uid, prompt, max_new_tokens=10)
    again = tight.generate_all()
    assert {uid: again[uid] for uid in prompts} == want


# ------------------------------------------------------ spans and what is refused
def test_kept_pairs_is_the_sum_it_stands_for():
    """Chunks before, across and past the ``index_topk``-th position."""
    for pos0, take, k in ((0, 4, 8), (6, 4, 8), (8, 4, 8), (20, 2, 8)):
        assert _kept_pairs(pos0, take, k) == sum(
            min(p + 1, k) for p in range(pos0, pos0 + take))


def test_the_engine_reports_what_a_step_selected(params, monkeypatch):
    from deepspeed_tpu.inference import ragged

    seen = []
    real = ragged.span

    def spy(name, **args):
        if name == "engine/dispatch":
            seen.append(args)
        return real(name, **args)

    monkeypatch.setattr(ragged, "span", spy)
    eng = _engine(params, device_state=True)
    assert eng.spec.index_topk == 8
    eng.put("s", list(range(1, 23)), max_new_tokens=3)
    eng.generate_all()
    first = seen[0]          # two tiles of 4 from position 0
    assert (first["tokens"], first["attn_pairs"], first["kv_tokens"]) == (8, 36, 8)
    assert (first["sel_pairs"], first["sel_kv_tokens"],
            first["dec_sel_kv_tokens"]) == (36, 4 + 8, 0)
    third = seen[2]          # positions 16..21: every query keeps 8 of its rows
    assert third["tokens"] == 6 and third["attn_pairs"] == sum(range(17, 23))
    assert (third["sel_pairs"], third["sel_kv_tokens"]) == (6 * 8, 8 + 8)
    decode = seen[-1]        # one decode row past 22 tokens of context
    assert decode["tokens"] == 1 and decode["dec_kv_tokens"] >= 23
    assert (decode["sel_pairs"], decode["sel_kv_tokens"],
            decode["dec_sel_kv_tokens"]) == (8, 8, 8)
    # the form the program's decode rows read the pool in: the rule the model
    # calls, on the table's 8 blocks x 4 tokens
    assert {a["sel_decode"] for a in seen} == {"walk"}
    monkeypatch.setattr(v32, "WALK_MAX_TABLE_TOKENS", 16)
    seen.clear()
    eng.put("t", list(range(1, 9)), max_new_tokens=2)
    eng.generate_all()
    assert seen and {a["sel_decode"] for a in seen} == {"gather"}
    # a family that attends over everything writes none of the three
    seen.clear()
    plain = RaggedInferenceEngine(
        lambda ctx: deepseek.build(deepseek.DeepseekConfig.tiny(VOCAB), ctx=ctx),
        dtype=jnp.float32, seed=0, ragged_config=RaggedConfig(
            max_tokens_per_step=8, max_seqs=2, block_size=4, num_blocks=33,
            max_blocks_per_seq=8, prefill_tile=4))
    plain.put("s", list(range(1, 9)), max_new_tokens=2)
    plain.generate_all()
    assert plain.spec.sparse_decode_form is None
    assert seen and not any(k.startswith("sel_") or "dec_sel" in k
                            for a in seen for k in a)


@pytest.mark.parametrize("over,match", [
    (dict(quant="int8"), "quantized latent pool"),
], ids=["quantized_pool"])
def test_what_cannot_carry_the_selection_raises(params, over, match):
    with pytest.raises(NotImplementedError, match=match):
        _engine(params, device_state=True, **over)


def test_a_v32_config_needs_the_low_rank_query():
    with pytest.raises(ValueError, match="q_lora_rank"):
        v32.DeepseekV32Config(q_lora_rank=None)
    with pytest.raises(NotImplementedError, match="rope_scaling of type"):
        deepseek.DeepseekConfig(rope_scaling={"type": "linear", "factor": 2})
    with pytest.raises(ValueError, match="topk_group"):
        deepseek.DeepseekConfig(n_group=8, topk_group=9)
    with pytest.raises(ValueError, match="experts_held"):
        deepseek.DeepseekConfig(experts_held=5)
