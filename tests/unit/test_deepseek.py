"""The ``deepseek`` family (Moonlight-16B-A3B's architecture) at a tiny size,
float32, seeded weights: the plain forward pass, the latent paged pool behind
``RaggedInferenceEngine``, absorbed attention and the router, each against
``benchmark/reference/deepseek.py`` (straight ``jax.numpy``, nothing imported
from the program) or against a hand-worked case."""

import importlib.util
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deepspeed_tpu.inference.ragged import RaggedConfig, RaggedInferenceEngine
from deepspeed_tpu.models import deepseek
from deepspeed_tpu.models.experts import routed_experts
from deepspeed_tpu.ops import attention

VOCAB = 89
CFG = deepseek.DeepseekConfig.tiny(VOCAB)   # 2 heads, latent 32 + rope 16,
#                                  8 experts top-3 + 1 shared, 1 dense + 2 MoE


@pytest.fixture(scope="module")
def reference():
    path = os.path.join(os.path.dirname(__file__), os.pardir, os.pardir,
                        "benchmark", "reference", "deepseek.py")
    spec = importlib.util.spec_from_file_location("reference_deepseek", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    mod.Q_BLOCK = 4   # the sequences here are multiples of 4, not of 512
    return mod


@pytest.fixture(scope="module")
def params():
    return deepseek.init_params(CFG, jax.random.PRNGKey(1))


def test_forward_matches_the_reference(reference, params):
    """(a) ``forward_fn`` (plain MLA on ``xla_attention``, the shared
    all-experts einsum) against the reference's one-expert-at-a-time pass:
    float32 both, so 1e-4 on logits of magnitude ~0.6 is rounding only."""
    ids = jnp.asarray(np.random.default_rng(0).integers(0, VOCAB, (2, 16)),
                      jnp.int32)
    got = np.asarray(deepseek.build(CFG).forward_fn(params, ids))
    for b in range(2):
        want = np.asarray(reference.forward(CFG, params, ids[b], jnp.float32))
        np.testing.assert_allclose(got[b], want, rtol=0, atol=1e-4)


def test_param_count_matches_the_tree(reference, params):
    n = sum(int(np.prod(a.shape)) for a in jax.tree_util.tree_leaves(params))
    assert n == deepseek.num_params(CFG) == reference.num_params(CFG)


# ----------------------------------------------- (b) the engine's latent pool
# Float32 end to end on the CPU: the served logits differ from the
# reference's full forward pass by summation order only (the absorbed form
# contracts q_nope through W_kvb before the context instead of after), which
# measures 1.2e-7 here on logits of magnitude ~0.56. LOGIT_ATOL leaves a
# decade for other BLAS builds and is still 40x under what a cache rounded to
# bf16 does (8.1e-5) and 500x under a dropped k_rope term (1.1e-3): both are
# run below and must miss it by 10x.
LOGIT_ATOL = 2e-6
PROMPT_LEN, NEW_TOKENS = 22, 6


def _serve_logits(params, spoil=None):
    """Prefill a 22-token prompt in chunks of <= 8 tokens (tiles of 4) and
    decode 6 tokens through ``RaggedInferenceEngine``; returns the served
    sequence and the logits row behind every emitted token. ``spoil(cache)``
    rewrites the pool after every step (the negative controls)."""
    eng = RaggedInferenceEngine(
        lambda ctx: deepseek.build(CFG, ctx=ctx), dtype=jnp.float32,
        params=params, seed=0,
        ragged_config=RaggedConfig(
            max_tokens_per_step=8, max_seqs=2, block_size=4, num_blocks=33,
            max_blocks_per_seq=8, prefill_tile=4, device_state=False))
    rows = []
    emit = eng._emit_tokens

    def record(logits, pairs):
        rows.extend(np.asarray(logits[i]) for i, _ in pairs)
        if spoil is not None:
            eng.cache = spoil(eng.cache)
        return emit(logits, pairs)

    eng._emit_tokens = record
    prompt = list(np.random.default_rng(5).integers(1, VOCAB, PROMPT_LEN))
    eng.put("s", prompt, max_new_tokens=NEW_TOKENS)
    out = eng.generate_all()["s"]
    assert eng.dispatch_count >= 3 + NEW_TOKENS - 1   # 3 prefill chunks
    return prompt + out, np.stack(rows)


def _reference_rows(reference, params, seq):
    ids = np.zeros(-(-len(seq) // 4) * 4, np.int32)  # causal: padding inert
    ids[:len(seq)] = seq
    want = np.asarray(reference.forward(CFG, params, jnp.asarray(ids),
                                        jnp.float32))
    return want[PROMPT_LEN - 1:len(seq) - 1]         # row i predicts i + 1


def test_engine_logits_match_the_reference(reference, params):
    seq, got = _serve_logits(params)
    want = _reference_rows(reference, params, seq)
    assert got.shape == want.shape == (NEW_TOKENS, VOCAB)
    np.testing.assert_allclose(got, want, rtol=0, atol=LOGIT_ATOL)


def _bf16_cache(cache):
    return jax.tree_util.tree_map(
        lambda a: a.astype(jnp.bfloat16).astype(a.dtype), cache)


def _no_k_rope(cache):
    return {"kv": cache["kv"].at[..., CFG.kv_lora_rank:].set(0.0)}


@pytest.mark.parametrize("spoil", [_bf16_cache, _no_k_rope],
                         ids=["bf16_cache", "no_k_rope"])
def test_the_tolerance_catches_a_lesser_cache(reference, params, spoil):
    seq, got = _serve_logits(params, spoil)
    want = _reference_rows(reference, params, seq)
    assert np.abs(got - want).max() > 10 * LOGIT_ATOL


# --------------------------------------------- (c) absorbed vs plain attention
def _random_pool(rng, blocks, bs, lanes):
    return jnp.asarray(rng.standard_normal((blocks, bs, lanes)), jnp.float32)


def test_absorbed_attention_equals_plain_attention():
    """On random inputs: scores against the cached rows through ``W_kvb``'s
    key half and values through its value half equal attention over
    per-head keys and values made from the latent."""
    rng = np.random.default_rng(2)
    heads, lat, nope, rope, vd, n = 3, 32, 16, 8, 12, 11
    c = rng.standard_normal((n, lat))
    k_rope = rng.standard_normal((n, rope))
    wk = rng.standard_normal((lat, heads, nope))
    wv = rng.standard_normal((lat, heads, vd))
    q_nope = rng.standard_normal((heads, nope))
    q_rope = rng.standard_normal((heads, rope))
    scale = (nope + rope) ** -0.5

    k = np.concatenate([np.einsum("tl,lhn->thn", c, wk),
                        np.broadcast_to(k_rope[:, None], (n, heads, rope))], -1)
    v = np.einsum("tl,lhv->thv", c, wv)
    s = np.einsum("hd,thd->ht", np.concatenate([q_nope, q_rope], -1), k) * scale
    p = np.exp(s - s.max(-1, keepdims=True))
    want = np.einsum("ht,thv->hv", p / p.sum(-1, keepdims=True), v)

    bs = 4
    pool = np.zeros((5, bs, lat + rope), np.float32)
    table = np.array([[3, 1, 4], [0, 0, 0]], np.int32)
    rows = np.concatenate([c, k_rope], -1)
    for t in range(n):
        pool[table[0, t // bs], t % bs] = rows[t]
    q = np.concatenate([np.einsum("hn,lhn->hl", q_nope, wk), q_rope], -1)
    o_lat = attention.latent_paged_attention(
        jnp.asarray(q[None], jnp.float32), jnp.asarray(pool),
        jnp.zeros(1, jnp.int32), jnp.full(1, n - 1, jnp.int32),
        jnp.asarray(table), lat, scale)
    got = np.einsum("hl,lhv->hv", np.asarray(o_lat[0]), wv)
    np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-4)


@pytest.mark.parametrize("dtype,tol", [(jnp.float32, 2e-5), (jnp.bfloat16, 3e-2)],
                         ids=["f32", "bf16"])
def test_mla_kernels_equal_the_xla_gather(dtype, tol):
    """Both Pallas kernels (interpret mode) against the XLA gather form, on a
    pool whose blocks the tables scatter: decode rows of different context
    lengths (one exactly a block, one padding row), and prefill tiles with a
    partly valid and an all-padding tile."""
    from deepspeed_tpu.ops.pallas import mla_attention

    rng = np.random.default_rng(3)
    heads, lat, rope, width, bs, tile = 4, 128, 64, 256, 8, 8
    lanes = jnp.arange(width) < lat + rope      # a row: c, k_rope, zeros
    pool = (_random_pool(rng, 16, bs, width) * lanes).astype(dtype)
    tables = jnp.asarray([[3, 7, 1, 12], [5, 2, 9, 0], [11, 4, 6, 10],
                          [0, 0, 0, 0]], jnp.int32)
    scale = 0.07

    def q(n):
        return (jnp.asarray(rng.standard_normal((n, heads, width)),
                            jnp.float32) * lanes).astype(dtype)

    slots = jnp.asarray([0, 2, 1, 3, 2], jnp.int32)
    pos = jnp.asarray([7, 30, 16, 0, 8], jnp.int32)
    qd = q(5)
    want = attention.latent_paged_attention(qd, pool, slots, pos, tables, lat,
                                            scale, impl="xla")
    got = mla_attention.mla_decode_attention(qd, pool, slots, pos, tables, lat,
                                             scale, interpret=True)
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32), rtol=tol, atol=tol)

    ts = jnp.asarray([0, 0, 2, 3], jnp.int32)
    tp = jnp.asarray([8, 16, 0, 0], jnp.int32)
    tv = jnp.asarray([8, 5, 8, 0], jnp.int32)
    qp = jnp.swapaxes(q(4 * tile), 0, 1)       # a tile's rows lie head-major
    qp = (qp[..., :lat], qp[..., lat:])         # and come in their two parts
    want = attention.latent_prefill_attention(*qp, pool, ts, tp, tv, tables,
                                              tile, scale, impl="xla")
    got = mla_attention.mla_prefill_attention(*qp, pool, ts, tp, tv, tables,
                                              tile, scale, interpret=True)
    assert got.shape == (heads, 4 * tile, lat)
    valid = np.concatenate([np.arange(tile) < n for n in np.asarray(tv)])
    np.testing.assert_allclose(np.asarray(got, np.float32)[:, valid],
                               np.asarray(want, np.float32)[:, valid],
                               rtol=tol, atol=tol)


# heads -> the kernel's sub-tile of a 128-row tile at the cells' widths (512
# latent + 64 rope lanes in 640, 128-token blocks): Moonlight, Kimi-Linear,
# LongCat-Flash, DeepSeek-V3.2
HEAD_MAJOR_SUBTILES = {16: 64, 32: 32, 64: 16, 128: 8}


def head_major_case(rng, heads, dtype):
    """Two 128-row tiles at the cells' widths over a table of two 128-token
    blocks: the second chunk of a prompt (positions 128 .. 255) and a first
    chunk of 70 tokens, their queries HEAD-MAJOR in two parts, ``[H, 256,
    512]`` and ``[H, 256, 128]``; the kernels' sub-tile is the cell's."""
    from deepspeed_tpu.ops.pallas.mla_attention import mla_prefill_kernel_tile

    lat, rope, width, bs, tile = 512, 64, 640, 128, 128
    assert mla_prefill_kernel_tile(tile, heads, lat, width,
                                   bs) == HEAD_MAJOR_SUBTILES[heads]
    lanes = jnp.arange(width) < lat + rope
    pool = (_random_pool(rng, 6, bs, width) * lanes).astype(dtype)
    q = (jnp.asarray(rng.standard_normal((heads, 2 * tile, width)),
                     jnp.float32) * lanes).astype(dtype)
    tables = jnp.asarray([[3, 1], [4, 2], [0, 0]], jnp.int32)
    ts, tp, tv = (jnp.asarray(a, jnp.int32) for a in ([0, 1], [128, 0],
                                                      [128, 70]))
    valid = np.concatenate([np.arange(tile) < n for n in np.asarray(tv)])
    return dict(q=(q[..., :lat], q[..., lat:]), pool=pool, tables=tables,
                ts=ts, tp=tp, tv=tv, tile=tile, lat=lat, bs=bs, valid=valid)


@pytest.mark.parametrize("heads,dtype,tol", [
    (16, jnp.float32, 2e-5), (32, jnp.float32, 2e-5), (64, jnp.float32, 2e-5),
    (128, jnp.bfloat16, 3e-2)], ids=["16", "32", "64", "128-bf16"])
def test_mla_prefill_takes_and_gives_head_major_rows(heads, dtype, tol):
    """``mla_prefill`` (interpret mode) on head-major rows against the XLA
    gather at the four cells' head counts, each at the sub-tile the chooser
    gives it there: 8 queries at 128 heads, half a bfloat16 tile's rows,
    which the kernel lays out as the rows of its products once a tile."""
    from deepspeed_tpu.ops.pallas import mla_attention

    c = head_major_case(np.random.default_rng(heads), heads, dtype)
    args = (*c["q"], c["pool"], c["ts"], c["tp"], c["tv"], c["tables"],
            c["tile"], 0.03)
    want = attention.latent_prefill_attention(*args, impl="xla")
    got = mla_attention.mla_prefill_attention(*args, interpret=True)
    assert got.shape == (heads, 2 * c["tile"], c["lat"])
    np.testing.assert_allclose(np.asarray(got, np.float32)[:, c["valid"]],
                               np.asarray(want, np.float32)[:, c["valid"]],
                               rtol=tol, atol=tol)


# blocks a step -> block size: a float32 row of 256 lanes is 1 KiB, and a
# step takes the power of two of blocks that 1 MiB holds
_WALK_BLOCK = {1: 1024, 2: 512, 4: 256}
_WALK_EDGES = ("0", "BS-1", "BS", "kBS-1", "kBS", "last")


@pytest.mark.parametrize("case", [*_WALK_EDGES, "one_block_then_full", "nan"])
@pytest.mark.parametrize("k", sorted(_WALK_BLOCK))
def test_mla_decode_walks_its_steps(k, case):
    """``mla_decode`` (interpret mode) against the XLA gather at 1, 2 and 4
    blocks a step, a row at every edge of the walk among rows whose slots
    repeat: the first token, a block's last and the next block's first, a
    step's last and the next step's first, the table's last; a row of one
    block before a row of the table's width (its first step is in flight
    while the short row is computed). ``nan``: every pool block no table
    names and every token past a row's position hold NaN, and nothing of
    them reaches the output."""
    from deepspeed_tpu.ops.pallas import mla_attention
    from deepspeed_tpu.ops.pallas.paged_attention import decode_step_blocks

    heads, lat, rope, width = 2, 128, 64, 256
    bs = _WALK_BLOCK[k]
    assert decode_step_blocks(bs, width, 4, arrays=1) == k
    mb = 2 * k + 1
    last = mb * bs - 1
    rng = np.random.default_rng(34 + k)
    lanes = np.arange(width) < lat + rope
    n_blocks = 3 * mb + 3
    pool = (rng.standard_normal((n_blocks, bs, width)) * lanes).astype(
        np.float32)
    tables = np.zeros((4, mb), np.int32)
    tables[:3] = rng.permutation(np.arange(1, n_blocks))[:3 * mb].reshape(3, mb)
    if case == "one_block_then_full":
        slots, pos = [2, 0, 1, 0], [last, 5, last, bs + 1]
    elif case == "nan":
        slots = [0, 1, 0, 3, 2, 1]
        pos = [k * bs - 1, last, 0, 0, bs, k * bs]
    else:
        edge = {"0": 0, "BS-1": bs - 1, "BS": bs, "kBS-1": k * bs - 1,
                "kBS": k * bs, "last": last}[case]
        slots, pos = [0, 1, 0, 3, 1], [bs // 2, edge, last, 0, edge]
    q = (rng.standard_normal((len(slots), heads, width)) * lanes).astype(
        np.float32)
    args = (jnp.asarray(slots, jnp.int32), jnp.asarray(pos, jnp.int32),
            jnp.asarray(tables))
    want = attention.latent_paged_attention(
        jnp.asarray(q), jnp.asarray(pool), *args, lat, 0.07, impl="xla")
    if case == "nan":
        read = np.zeros((n_blocks, bs), bool)
        for slot, p in zip(slots, pos):
            tok = np.arange(p + 1)
            read[tables[slot, tok // bs], tok % bs] = True
        pool = np.where(read[:, :, None], pool, np.nan)
    got = mla_attention.mla_decode_attention(
        jnp.asarray(q), jnp.asarray(pool), *args, lat, 0.07, interpret=True)
    assert np.isfinite(np.asarray(got)).all()
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-5, atol=2e-5)


def test_ragged_step_with_the_kernels_equals_the_gather(params, monkeypatch):
    """A prefill step of two sequences and a mixed step through
    ``ragged_forward`` with both kernels where the chip runs them
    (interpret mode): the same logits and the same pool as the XLA form."""
    tables = np.zeros((4, 3), np.int32)
    tables[0], tables[1], tables[2] = [3, 7, 1], [5, 2, 9], [11, 4, 6]
    rng = np.random.default_rng(3)
    pad = 3

    def step(cache, chunks, n_dec):
        toks, slots, pos, ts, tp, tv = [], [], [], [], [], []
        for i, (slot, p0, tokens) in enumerate(chunks):
            toks += tokens
            slots += [slot] * len(tokens)
            pos += list(range(p0, p0 + len(tokens)))
            if i >= n_dec:
                for off in range(0, len(tokens), 4):
                    ts.append(slot), tp.append(p0 + off)
                    tv.append(min(4, len(tokens) - off))
                fill = -len(tokens) % 4
                toks += [0] * fill
                slots += [pad] * fill
                pos += [0] * fill
        i32 = lambda a: jnp.asarray(a, jnp.int32)  # noqa: E731
        return deepseek.ragged_forward(
            CFG, params, i32(toks), i32(slots), i32(pos), jnp.asarray(tables),
            cache, prefill_tiles=(n_dec, i32(ts), i32(tp), i32(tv), 4))

    first = [(0, 0, list(rng.integers(1, VOCAB, 7))),
             (1, 0, list(rng.integers(1, VOCAB, 5)))]
    mixed = [(0, 7, [11]), (1, 5, [13]), (2, 0, list(rng.integers(1, VOCAB, 6)))]

    def run():
        cache = deepseek.init_paged_cache(CFG, 12, 4, jnp.float32)
        l1, cache = step(cache, first, 0)
        l2, cache = step(cache, mixed, 2)
        return np.asarray(l1), np.asarray(l2), np.asarray(cache["kv"])

    want = run()
    monkeypatch.setattr(attention, "_on_tpu", lambda: True)
    got = run()
    keep1 = [0, 1, 2, 3, 4, 5, 6, 8, 9, 10, 11, 12]       # rows 7, 13..15 pad
    np.testing.assert_allclose(got[0][keep1], want[0][keep1], atol=2e-5)
    keep2 = [0, 1, 2, 3, 4, 5, 6, 7]                      # 2 decode + 6 prompt
    np.testing.assert_allclose(got[1][keep2], want[1][keep2], atol=2e-5)
    # block 0 is every layer's scratch block: padding rows write there
    np.testing.assert_allclose(got[2][:, 1:], want[2][:, 1:], atol=2e-5)
    assert want[2].shape == (CFG.num_layers, 12, 4, CFG.row_lanes)
    assert want[2][0, [3, 7, 5, 2]].any() and want[2][2, [11, 4]].any()
    assert not want[2][:, [8, 10]].any()                   # no table names them


# ------------------------------------------------------------- (d) the router
def test_router_bias_changes_the_picks_not_the_weights():
    """Hand-worked: 4 experts, 2 a token. Expert ``e`` multiplies the token
    by ``e + 1`` (gate and up are chosen so that ``silu(g) * u`` is the
    token's first lane, w_down spreads it). Sigmoid scores of logits
    (2, 1, 0, -1) are (.8808, .7311, .5, .2689). Without a bias experts 0 and
    1 are picked. With bias (0, 0, .5, 0) expert 2 ranks (1.0) above expert
    1 (.7311): picks 0 and 2, weights from the scores WITHOUT the bias,
    .8808 and .5, renormalised to .6379 and .3621, times 2.446."""
    d, e, f = 4, 4, 2
    h = jnp.asarray([[1.0, 0.0, 0.0, 0.0]], jnp.float32)
    router = jnp.zeros((d, e)).at[0].set(jnp.asarray([2.0, 1.0, 0.0, -1.0]))
    # silu(g) * u with g = 20 * x0 (silu(20) = 20 to 1e-7), u = x0 / 20
    w_gate = jnp.zeros((e, d, f)).at[:, 0, 0].set(20.0)
    w_up = jnp.zeros((e, d, f)).at[:, 0, 0].set(0.05)
    w_down = jnp.zeros((e, f, d)).at[:, 0, 0].set(jnp.arange(1.0, e + 1))
    s = 1.0 / (1.0 + np.exp(-np.array([2.0, 1.0, 0.0, -1.0])))

    def out(bias, **kw):
        return float(routed_experts(h, router, w_gate, w_up, w_down, 2,
                                    scoring="sigmoid", bias=bias, **kw)[0, 0])

    no_bias = jnp.zeros(e)
    bias = jnp.asarray([0.0, 0.0, 0.5, 0.0])
    plain = (s[0] * 1 + s[1] * 2) / (s[0] + s[1])
    moved = (s[0] * 1 + s[2] * 3) / (s[0] + s[2])
    assert out(no_bias, eps=1e-20) == pytest.approx(plain, rel=1e-5)
    assert out(bias, eps=1e-20) == pytest.approx(moved, rel=1e-5)
    assert out(bias, scale=2.446, eps=1e-20) == pytest.approx(2.446 * moved,
                                                              rel=1e-5)
    # not renormalised: the raw scores weigh
    assert out(bias, renormalize=False) == pytest.approx(s[0] * 1 + s[2] * 3,
                                                         rel=1e-5)


def _moe_infer_at_the_parent(h, router_w, w_gate, w_up, w_down, top_k):
    """``models/mixtral._moe_infer`` as it stood before it moved to
    ``models/experts.routed_experts`` (kept verbatim)."""
    from jax import lax

    t, d = h.shape
    probs = jax.nn.softmax(
        h.astype(jnp.float32) @ router_w.astype(jnp.float32), axis=-1)
    topv, topi = lax.top_k(probs, top_k)
    topv = topv / (jnp.sum(topv, axis=-1, keepdims=True) + 1e-9)
    e = probs.shape[-1]
    w = jnp.zeros((t, e), jnp.float32).at[
        jnp.arange(t)[:, None], topi].set(topv)
    dtype = h.dtype
    g = jnp.einsum("td,edf->tef", h, w_gate.astype(dtype))
    u = jnp.einsum("td,edf->tef", h, w_up.astype(dtype))
    y = jnp.einsum("tef,efd->ted", jax.nn.silu(g) * u, w_down.astype(dtype))
    return jnp.einsum("ted,te->td", y, w.astype(dtype))


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["f32", "bf16"])
def test_mixtral_routing_is_bit_equal_after_the_move(dtype):
    """Mixtral's settings are the shared function's defaults: the same bits
    as the function Mixtral had, eager and jitted."""
    from deepspeed_tpu.models import mixtral

    cfg = mixtral.MixtralConfig.tiny(VOCAB)
    lp = jax.tree_util.tree_map(
        lambda a: a[0].astype(dtype),
        mixtral.init_params(cfg, jax.random.PRNGKey(3))["layers"])
    h = jnp.asarray(np.random.default_rng(4).standard_normal(
        (37, cfg.hidden_size)), dtype)
    args = (h, lp["router"], lp["w_gate"], lp["w_up"], lp["w_down"])
    want = _moe_infer_at_the_parent(*args, cfg.top_k)
    np.testing.assert_array_equal(
        np.asarray(routed_experts(*args, cfg.top_k), np.float32),
        np.asarray(want, np.float32))
    jitted = jax.jit(routed_experts, static_argnums=5)(*args, cfg.top_k)
    want_jit = jax.jit(_moe_infer_at_the_parent, static_argnums=5)(
        *args, cfg.top_k)
    np.testing.assert_array_equal(np.asarray(jitted, np.float32),
                                  np.asarray(want_jit, np.float32))


# ------------------------------------------------------- what is not there
def test_unimplemented_variants_raise():
    from deepspeed_tpu.ops import kvquant

    with pytest.raises(NotImplementedError, match="quantized latent pool"):
        deepseek.init_paged_cache(CFG, 8, 4, jnp.float32,
                                  codec=kvquant.get_codec("int8"))
    with pytest.raises(NotImplementedError, match="quantized latent pool"):
        RaggedInferenceEngine(
            lambda ctx: deepseek.build(CFG, ctx=ctx), dtype=jnp.float32,
            ragged_config=RaggedConfig(quant="int8"))
