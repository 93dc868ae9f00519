"""``models/sdar.py`` at a small size on the CPU, seeded weights: generation by
diffusion over blocks through the ragged engine (prefill in tiles under the
block-causal mask, then blocks of four rows, ``T`` denoise passes and a commit
each, unmasked on the device) against the plain reference
``benchmark/reference/sdar.py``; the two attention kernels with a ``block``
against a dense masked softmax; what the scheduler promises; the refusals; and
``smallthinker``'s step program against the parent's (the eight families
before it are held by ``test_smallthinker.py``).

Logits are compared, and tokens. Tolerance 5e-4 (float32 everywhere here):
the program runs a block against cached rows and the reference as whole
streams, so the same sums are taken in another order; observed differences
are under 3e-5 on logits of magnitude ~3.

``python tests/unit/test_sdar.py <out.json>`` writes the fixture
``fixtures/step_jaxprs_pr48_smallthinker.json`` from whatever tree
``PYTHONPATH`` names.
"""

import dataclasses
import hashlib
import importlib.util
import json
import os
import re
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
FIXTURE = os.path.join(os.path.dirname(os.path.abspath(__file__)), "fixtures",
                       "step_jaxprs_pr48_smallthinker.json")
ATOL = 5e-4


# ----------------------------------------- (e) the family the fixtures lack
def smallthinker_step_digests() -> dict:
    """``{form: sha256 of smallthinker's device step program's jaxpr}``
    (``test_smallthinker.step_jaxpr_digests``' program and text, its pin
    taken out likewise, for the one family its fixtures do not hold)."""
    from deepspeed_tpu.inference.ragged import (RaggedConfig,
                                                RaggedInferenceEngine)
    from deepspeed_tpu.models import paged, smallthinker
    from deepspeed_tpu.ops import attention

    def abstract(tree):
        return jax.tree_util.tree_map(
            lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype), tree)

    cfg = smallthinker.SmallThinkerConfig.tiny()
    rc = RaggedConfig(max_tokens_per_step=32, max_seqs=8, block_size=8,
                      num_blocks=33, max_blocks_per_seq=8, prefill_tile=8)
    was, pin, out = attention._on_tpu, paged._pin, {}
    paged._pin = lambda y: y
    try:
        for form in ("xla", "pallas"):
            attention._on_tpu = lambda form=form: form == "pallas"
            eng = RaggedInferenceEngine(
                lambda ctx: smallthinker.build(cfg, ctx=ctx), rc,
                dtype=jnp.float32, params=jax.tree_util.tree_map(
                    lambda a: np.zeros(a.shape, a.dtype), jax.eval_shape(
                        lambda: smallthinker.init_params(
                            cfg, jax.random.PRNGKey(0)))))
            t, nd, nt = 8 + 2 * 8, 8, 2
            fn = eng._build_dev_step(t, nd, nt, rc.max_blocks_per_seq, False,
                                     False, False)
            text = str(jax.make_jaxpr(fn)(
                abstract(eng.params), abstract(eng.cache),
                abstract(eng._dev_state), abstract(eng._tables_dev()),
                jax.ShapeDtypeStruct((4 * t + 3 * nt,), jnp.int32),
                abstract(eng._sample_root)))
            out[form] = {"smallthinker": hashlib.sha256(
                re.sub(r"0x[0-9a-f]+", "0x", text).encode()).hexdigest()}
    finally:
        attention._on_tpu, paged._pin = was, pin
    return out


if __name__ == "__main__":
    with open(sys.argv[1], "w") as f:
        json.dump(smallthinker_step_digests(), f, indent=1)
    sys.exit(0)


from deepspeed_tpu.inference import ragged  # noqa: E402
from deepspeed_tpu.inference.ragged import (  # noqa: E402
    RaggedConfig,
    RaggedInferenceEngine,
)
from deepspeed_tpu.models import sdar  # noqa: E402
from deepspeed_tpu.models.api import BlockGen  # noqa: E402
from deepspeed_tpu.ops.pallas.paged_attention import (  # noqa: E402
    decode_step_blocks,
    decode_steps,
    ragged_prefill_attention,
)


def test_smallthinkers_step_program_is_the_parents():
    """``block=None`` / ``block_gen=None`` trace what the parent traces (the
    other eight families: ``test_smallthinker.py``); since PR 48 the parent
    is that PR's tree, whose programs with tiles write them as slices."""
    with open(FIXTURE) as f:
        assert smallthinker_step_digests() == json.load(f)


# -------------------------------------------------------------- the model
def _reference():
    spec = importlib.util.spec_from_file_location(
        "reference_sdar", os.path.join(REPO, "benchmark", "reference",
                                       "sdar.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    mod.Q_BLOCK = 8
    return mod


REF = _reference()
B = 4
SIZES = dict(max_tokens_per_step=32, max_seqs=4, block_size=8, num_blocks=33,
             max_blocks_per_seq=8, prefill_tile=8)


def _cfg(steps=2, remask="sequential"):
    return sdar.SdarConfig.tiny(89, denoise_steps=steps, remask=remask)


@pytest.fixture(scope="module")
def params():
    return sdar.init_params(_cfg(), jax.random.PRNGKey(3))


def _engine(params, cfg, **sizes):
    return RaggedInferenceEngine(
        lambda ctx: sdar.build(cfg, ctx=ctx),
        RaggedConfig(**{**SIZES, **sizes}), dtype=jnp.float32, params=params)


def _prompts(lengths, seed=0):
    rng = np.random.default_rng(seed)
    return {uid: rng.integers(0, 88, n).tolist()   # 88 is the mask token
            for uid, n in enumerate(lengths)}


def _serve(eng, prompts, new_tokens, eos=None, recover_after=None):
    """Run the requests to their end, a state snapshot around every dispatch.
    Returns ``(chosen, tokens, dispatches)``: ``chosen[(uid, position)] =
    (the logits row that chose the token there, the pass of its block it was
    unmasked in)``, ``tokens[(uid, position)]`` every position a block
    unmasked (a cut tail's too), ``dispatches`` one record a dispatch
    (``{"blocks": {uid: "denoise" | "commit"}, "rows", "tiles",
    "in_flight"}``)."""
    calls, chosen, tokens, dispatches = [], {}, {}, []
    passes: dict = {}
    real_fwd = eng.spec.ragged_forward_fn

    def recording(p, tok, slots, positions, bt, cache, **kw):
        logits, cache = real_fwd(p, tok, slots, positions, bt, cache, **kw)
        jax.debug.callback(
            lambda *a: calls.append([np.asarray(x) for x in a]),
            slots, positions, logits, ordered=True)
        return logits, cache

    eng.spec.ragged_forward_fn = recording
    real_dispatch = eng._dispatch_step_device

    def state():   # (next_position, the blocks' tokens, what is masked)
        return [np.asarray(eng._dev_state[i]) for i in (1, -2, -1)]

    real_pack, packed = eng._pack_step, []

    def pack(host_feed):
        packed.append(real_pack(host_feed))
        return packed[-1]

    def dispatch():
        p0s, _, before = state()
        in_flight = len(eng._pending)
        done = real_dispatch()
        _, tok, after = state()
        jax.effects_barrier()
        if not done:
            return done
        plan = packed[-1]
        _, _, logits = calls[-1]
        rec = {"blocks": {}, "rows": plan[4], "in_flight": in_flight,
               "tiles": plan[4] - B * len(plan[-1]["seqs"])}
        for i, seq in enumerate(plan[-1]["seqs"]):
            slot, uid = seq.slot, seq.uid
            p0 = int(p0s[slot])
            if not before[slot].any():
                rec["blocks"][uid] = "commit"
                continue
            rec["blocks"][uid] = "denoise"
            k = passes.get((uid, p0), 0)
            passes[(uid, p0)] = k + 1
            for b in np.flatnonzero(before[slot] & ~after[slot]):
                chosen[(uid, p0 + b)] = (logits[i * B + b], k)
                tokens[(uid, p0 + b)] = int(tok[slot][b])
        dispatches.append(rec)
        return done

    eng._pack_step = pack
    eng._dispatch_step_device = dispatch
    for uid, prompt in prompts.items():
        eng.put(uid, prompt, max_new_tokens=new_tokens[uid], eos_token_id=eos)
    steps = 0
    while eng.has_work:
        eng.step()
        steps += 1
        if steps == recover_after:
            eng._recover_device_path()
        assert steps < 600
    return chosen, tokens, dispatches


_REF_JITS: dict = {}
PAD = 32   # every request's ids are padded to it: one reference program a T


def _ref_logits(cfg, params, ids, order, steps):
    """``REF.denoise_logits`` of ``ids`` under the trajectory ``order``
    (padded to ``PAD``: positions never reached stay masked), jitted once a
    configuration and pass count."""
    key = (cfg, steps)
    if key not in _REF_JITS:
        _REF_JITS[key] = jax.jit(lambda p, i, o: REF.denoise_logits(
            cfg, p, i, order=o, steps=steps))
    ids = np.pad(np.asarray(ids, np.int32), (0, PAD - len(ids)))
    order = np.pad(np.asarray(order, np.int32), (0, PAD - len(order)),
                   constant_values=steps)
    with jax.default_matmul_precision("highest"):
        return np.asarray(_REF_JITS[key](params, ids, order))


def _check_against_reference(cfg, params, eng, prompts, chosen, tokens):
    """Every generated token is the ``argmax`` of the logits that chose it,
    and those logits are the reference's for the served trajectory."""
    for uid, prompt in prompts.items():
        generated = eng.get_request(uid).generated
        end = len(prompt) + len(generated)
        last = -(-end // B) * B
        ids = prompt + [tokens[(uid, p)] for p in range(len(prompt), last)]
        assert ids[len(prompt):end] == generated
        order = [-1] * len(prompt) + [chosen[(uid, p)][1]
                                      for p in range(len(prompt), last)]
        # a first block that opens with r prompt tokens takes fewer passes
        want = _ref_logits(cfg, params, ids, order, cfg.denoise_steps)
        for p in range(len(prompt), end):
            row = chosen[(uid, p)][0]
            assert int(row.argmax()) == ids[p]
            np.testing.assert_allclose(
                row, want[p], atol=ATOL,
                err_msg=f"request {uid}, position {p}")


# prompt lengths 8, 13, 18, 7, 3: every remainder of four, one prompt shorter
# than a block (it opens the first block and nothing is prefilled); 8-token
# pool blocks, so every request crosses several; answers of 11, 8, 6, 9, 5
# tokens cut their last block's tail; five requests over four slots
LENGTHS = [8, 13, 18, 7, 3]
NEW = [11, 8, 6, 9, 5]
SERVED = {
    "T1": (1, "sequential"),
    "T2": (2, "sequential"),
    "T4": (4, "sequential"),
    "T2_by_confidence": (2, "low_confidence_static"),
    "T4_by_confidence": (4, "low_confidence_static"),
}
_RUNS: dict = {}


def _served(params, case):
    if case not in _RUNS:
        cfg = _cfg(*SERVED[case])
        eng = _engine(params, cfg)
        prompts = _prompts(LENGTHS)
        _RUNS[case] = (cfg, eng, prompts, *_serve(eng, prompts, NEW))
    return _RUNS[case]


@pytest.mark.parametrize("case", SERVED)
def test_served_blocks_match_the_reference(params, case):
    """(a) engine = reference, logits and tokens, through prefill tiles,
    several blocks, pool-block boundaries, every prompt remainder, a cut
    last block; where the order is by confidence the reference is handed the
    served trajectory (the pass each position was unmasked in)."""
    cfg, eng, prompts, chosen, tokens, dispatches = _served(params, case)
    for uid in prompts:
        assert len(eng.get_request(uid).generated) == NEW[uid]
    _check_against_reference(cfg, params, eng, prompts, chosen, tokens)
    if "confidence" in case:
        # the rule ranks: somewhere a position was unmasked before one to its
        # left in the same block
        assert any(chosen[(u, p)][1] < chosen[(u, p - 1)][1]
                   for (u, p) in chosen if p % B and (u, p - 1) in chosen)
    # the pool comes back whole and nothing is left promised
    assert eng.allocator.free_blocks == eng.cfg.num_blocks - 1
    assert eng._reserved == 0 and not eng.block_tables.any()


def test_forward_is_what_the_benchmarks_check_reads(params):
    """(b) ``serve_cell.ServeRig.check`` takes row ``i - 1`` of
    ``reference.forward`` as the logits that chose token ``i`` and pads the
    ids with zeros: under the ``sequential`` rule, a prompt of whole blocks
    and the configuration's ``T`` the served tokens are those rows'
    ``argmax``, a cut last block included."""
    cfg, eng, prompts, *_ = _served(params, "T2")
    uid = 0
    assert len(prompts[uid]) % B == 0
    served = eng.get_request(uid).generated
    assert len(served) % B
    seq = prompts[uid] + served
    ids = np.zeros(32, np.int32)
    ids[:len(seq)] = seq
    with jax.default_matmul_precision("highest"):
        logits = np.asarray(jax.jit(lambda p, i: REF.forward(cfg, p, i))(
            params, ids))
    rows = np.arange(len(prompts[uid]) - 1, len(seq) - 1)
    assert logits[rows].argmax(-1).tolist() == served


def test_the_familys_forward_is_the_references_two_streams(params):
    """``sdar.forward(masked=)`` (XLA, both streams in one pass) against the
    reference's stream after stream, at the first pass of ``T = 2``."""
    cfg = _cfg()
    ids = jnp.asarray(_prompts([24], seed=5)[0], jnp.int32)
    off = jnp.arange(24) % B
    fwd = jax.jit(lambda p, i, m: sdar.forward(cfg, p, i, masked=m)[0])
    with jax.default_matmul_precision("highest"):
        got = fwd(params, ids[None], (off >= 2)[None])
        plain = jax.jit(lambda p, i: sdar.forward(cfg, p, i)[0])(
            params, ids[None])
        unmasked = fwd(params, ids[None], jnp.zeros((1, 24), bool))
    want = _ref_logits(cfg, params, ids, np.where(np.arange(24) % B >= 2, 1, 0),
                       2)
    rows = np.flatnonzero(np.arange(24) % B >= 2)
    np.testing.assert_allclose(np.asarray(got)[rows], want[rows], atol=ATOL)
    # with nothing masked the noisy stream IS the clean one
    np.testing.assert_allclose(np.asarray(unmasked), np.asarray(plain),
                               atol=ATOL)


def test_eos_inside_a_block_ends_the_request_there(params):
    cfg, eng, prompts, *_ = _served(params, "T2")
    uid, at = next(
        (u, i) for u in prompts
        for i, t in enumerate(eng.get_request(u).generated)
        if (len(prompts[u]) + i) % B in (1, 2)
        and t not in eng.get_request(u).generated[:i])
    whole = eng.get_request(uid).generated
    again = _engine(params, cfg)
    again.put(uid, prompts[uid], max_new_tokens=NEW[uid],
              eos_token_id=whole[at])
    assert again.generate_all()[uid] == whole[:at + 1]
    assert again.allocator.free_blocks == again.cfg.num_blocks - 1


# ----------------------------------------------------------- (c) kernels
def _pool(rng, n_seqs, blocks_per_seq, bs, hkv, d):
    """A pool whose block 0 is scratch and a table that names blocks in a
    shuffled order."""
    nb = n_seqs * blocks_per_seq + 1
    k = rng.standard_normal((nb, bs, hkv * d)).astype(np.float32)
    v = rng.standard_normal((nb, bs, hkv * d)).astype(np.float32)
    table = np.zeros((n_seqs + 1, blocks_per_seq), np.int32)
    table[:n_seqs] = rng.permutation(np.arange(1, nb)).reshape(n_seqs, -1)
    return jnp.asarray(k), jnp.asarray(v), jnp.asarray(table)


def _dense(q, k, v, table, slot, qpos, seen, hkv, d):
    """Softmax attention of query rows ``q`` [R, Hq, D] at positions
    ``qpos`` over slot ``slot``'s context under ``seen(qpos, kpos)``."""
    ctx_k = np.asarray(k)[np.asarray(table)[slot]].reshape(-1, hkv, d)
    ctx_v = np.asarray(v)[np.asarray(table)[slot]].reshape(-1, hkv, d)
    rep = q.shape[1] // hkv
    ctx_k, ctx_v = np.repeat(ctx_k, rep, 1), np.repeat(ctx_v, rep, 1)
    s = np.einsum("qhd,khd->hqk", q, ctx_k) / np.sqrt(d)
    ok = seen(np.asarray(qpos)[:, None], np.arange(ctx_k.shape[0])[None, :])
    s = np.where(ok[None], s, -np.inf)
    p = np.exp(s - s.max(-1, keepdims=True))
    p /= p.sum(-1, keepdims=True)
    return np.einsum("hqk,khd->qhd", p, ctx_v)


@pytest.mark.parametrize("impl", ["pallas", "xla"])
def test_block_decode_rows_match_a_dense_masked_softmax(impl):
    """``paged_decode(block=4)`` / the XLA form: every row of a block sees
    keys ``0 .. p0 + 3``. Contexts that end at a kernel chunk's edge (a step
    takes ``nb`` blocks of 8 = ``ch`` keys), one block short of it, at a pool
    block's edge and inside one; a padding block."""
    from deepspeed_tpu.ops.attention import paged_attention

    rng = np.random.default_rng(0)
    hq, hkv, d, bs = 4, 2, 16, 8
    k, v, table = _pool(rng, 5, 16, bs, hkv, d)
    ch = bs * decode_step_blocks(bs, hkv * d, 4)
    p0s = [ch - B, ch, 2 * bs - B, 2 * bs + B, 0]          # p0 + B = context
    slots = np.array([0, 1, 2, 3, 4, 5], np.int32)         # 5: padding row
    p0 = np.array(p0s + [0], np.int32)
    q = rng.standard_normal((6 * B, hq, d)).astype(np.float32)
    positions = (p0[:, None] + np.arange(B)).reshape(-1)
    got = np.asarray(paged_attention(
        jnp.asarray(q), k, v, jnp.asarray(np.repeat(slots, B)),
        jnp.asarray(positions), table, impl=impl, block=B))
    for r in range(5):
        want = _dense(q[r * B:(r + 1) * B], k, v, table, r,
                      positions[r * B:(r + 1) * B],
                      lambda i, j: j <= (i | (B - 1)), hkv, d)
        np.testing.assert_allclose(got[r * B:(r + 1) * B], want, atol=2e-5,
                                   err_msg=f"block at {p0s[r]}")
    assert np.isfinite(got).all()


def test_a_block_takes_the_grid_steps_of_one_row_at_its_context():
    """A sequence's K and V are fetched once a pass for its four queries:
    the block rides through the decode kernel as ONE row at ``p0 + 3``, so
    its grid steps are one row's at that context, a quarter of what four
    single-query rows walk."""
    ch, p0 = 256, np.array([1300, 512 - B, 40], np.int32)
    as_blocks, _, _ = decode_steps(jnp.asarray(p0 + B - 1), ch, 64)
    as_rows, _, _ = decode_steps(
        jnp.asarray((p0[:, None] + np.arange(B)).reshape(-1)), ch, 64)
    one_row = [int(p + B - 1) // ch + 1 for p in p0]
    assert np.diff(np.asarray(as_blocks), prepend=0).tolist() == one_row
    assert int(as_rows[-1]) == B * int(as_blocks[-1])


# (q heads, kv heads): the SDAR cell's, one KV head, a KV head a query head
SPLIT_GEOMETRIES = {"cell_32_over_4": (32, 4), "one_kv_head": (8, 1),
                    "a_kv_head_a_query_head": (4, 4)}


@pytest.mark.parametrize("dtype", ["bfloat16", "float32"])
@pytest.mark.parametrize("geometry", sorted(SPLIT_GEOMETRIES))
def test_block_kernel_splits_by_kv_head_and_serves_the_wide_forms_rows(
        geometry, dtype):
    """The block kernel's own body (heads of 128 lanes: a KV head's ``B x
    rep`` queries against that head's lanes of the chunk only) against the
    XLA form under the block-causal mask, and against the form it replaced
    (the block as ONE row of ``B x Hq`` heads through ``_paged_decode``'s
    ``[Hq, Hkv*D]`` body), whose rows it gives bit for bit in the pool's
    bfloat16 (the products dropped are products with exact zeros). 128-token
    pool blocks; contexts that end inside a chunk's first block, on a chunk's
    edge (its last position and the next chunk's first), in a chunk's last
    block and at position 0; two padding rows on the scratch slot; a table
    wider than any row's context."""
    from deepspeed_tpu.ops.attention import paged_attention
    from deepspeed_tpu.ops.pallas import paged_attention as kernels

    hq, hkv = SPLIT_GEOMETRIES[geometry]
    d, bs, dt = 128, 128, jnp.dtype(dtype)
    rep = hq // hkv
    nb = decode_step_blocks(bs, hkv * d, dt.itemsize)
    ch = nb * bs
    rng = np.random.default_rng(2)
    k, v, table = _pool(rng, 5, 2 * nb + 3, bs, hkv, d)
    k, v = k.astype(dt), v.astype(dt)
    # p0 + B - 1 is the row's position in the walk
    p0s = [bs // 2, ch - B, ch, 2 * ch - bs // 2, 0]
    assert [(p + B - 1) // ch for p in p0s] == [0, 0, 1, 1, 0]
    assert max(p0s) + B <= (table.shape[1] - 3) * bs       # a wider table
    slots = jnp.asarray([0, 1, 2, 3, 4, 5, 5], jnp.int32)  # 5: the scratch
    p0 = jnp.asarray(p0s + [0, 0], jnp.int32)
    rows = len(p0s) + 2
    q = jnp.asarray(rng.standard_normal((rows, B, hq, d)), dt)
    got = kernels.paged_decode_attention(q, k, v, slots, p0, table,
                                         interpret=True, block=B)
    assert got.shape == q.shape and got.dtype == dt

    # the XLA form, a block a call (its context is gathered head by head)
    tol = 2e-5 if dtype == "float32" else 3e-2
    for r, at in enumerate(p0s):
        want = paged_attention(
            q[r].astype(jnp.float32), k.astype(jnp.float32),
            v.astype(jnp.float32), jnp.full((B,), r, jnp.int32),
            at + jnp.arange(B, dtype=jnp.int32), table, impl="xla", block=B)
        np.testing.assert_allclose(
            np.asarray(got[r], np.float32), np.asarray(want), atol=tol,
            err_msg=f"block at {at}")
    assert np.isfinite(np.asarray(got, np.float32)).all()  # the padding's too

    # the parent's block form: head (g, r) of query b is head g * B * rep +
    # b * rep + r of ONE row at the block's last position
    as_heads = q.reshape(rows, B, hkv, rep, d).transpose(
        0, 2, 1, 3, 4).reshape(rows, B * hq, d)
    wide = kernels._paged_decode(
        as_heads, k, v, slots, p0 + (B - 1), table, scale=1.0 / d ** 0.5,
        interpret=True, name="blk_decode")
    wide = np.asarray(wide.reshape(rows, hkv, B, rep, d).transpose(
        0, 2, 1, 3, 4).reshape(q.shape), np.float32)
    got = np.asarray(got, np.float32)
    if dtype == "float32":
        np.testing.assert_allclose(got, wide, atol=1e-6)
    elif rep > 1:
        assert np.array_equal(got, wide)
    else:
        # four rows a product: the CPU's routine for them adds the same terms
        # in another order, an ulp of bfloat16 in a value of two thousand
        np.testing.assert_allclose(got, wide, rtol=2.0 ** -7, atol=2.0 ** -10)
        assert (got != wide).mean() < 1e-3


@pytest.mark.parametrize("pos0,valid", [(0, 16), (16, 16), (8, 12), (24, 4)])
def test_block_prefill_tiles_match_a_dense_masked_softmax(pos0, valid):
    """``tiled_prefill(block=4)``: a tile's rows under ``kpos <= (qpos |
    3)``; tiles that start at 0, at a step's edge, inside a pool block, and
    one with a padded tail."""
    rng = np.random.default_rng(1)
    hq, hkv, d, bs, ct = 4, 2, 16, 8, 16
    k, v, table = _pool(rng, 2, 8, bs, hkv, d)
    q = rng.standard_normal((ct, hq, d)).astype(np.float32)
    got = np.asarray(ragged_prefill_attention(
        jnp.asarray(q), k, v, jnp.asarray([1], jnp.int32),
        jnp.asarray([pos0], jnp.int32), jnp.asarray([valid], jnp.int32),
        table, ct, interpret=True, block=B))
    qpos = pos0 + np.arange(valid)
    want = _dense(q[:valid], k, v, table, 1, qpos,
                  lambda i, j: j <= (i | (B - 1)), hkv, d)
    np.testing.assert_allclose(got[:valid], want, atol=2e-5)
    # and the causal mask would have been another answer
    causal = _dense(q[:valid], k, v, table, 1, qpos, lambda i, j: j <= i,
                    hkv, d)
    assert np.abs(causal - want).max() > 1e-2


def test_the_engine_serves_the_same_through_the_kernels(params, monkeypatch):
    """The step programs with both Pallas kernels in them (interpret mode)
    serve the XLA forms' tokens."""
    from deepspeed_tpu.ops import attention

    cfg, eng, prompts, *_ = _served(params, "T2")
    monkeypatch.setattr(attention, "_on_tpu", lambda: True)
    kern = _engine(params, cfg)
    for uid in (1, 2):
        kern.put(uid, prompts[uid], max_new_tokens=NEW[uid])
    got = kern.generate_all()
    assert {u: got[u] for u in (1, 2)} == {
        u: eng.get_request(u).generated for u in (1, 2)}


# --------------------------------------------------------- (d) scheduler
def test_a_sequence_runs_t_plus_one_passes_a_block(params):
    """Exactly ``T`` denoise passes and one commit a block (a first block
    that opens with ``r`` prompt tokens: ``ceil((B - r) / n)``; the last
    block is never committed), two dispatches in flight, and a tile beside
    the decode blocks whenever a prompt waits."""
    cfg, eng, prompts, chosen, tokens, dispatches = _served(params, "T2")
    n = B // 2
    for uid, prompt in prompts.items():
        r = len(prompt) % B
        blocks = -(-(r + NEW[uid]) // B)
        seq = eng.get_request(uid)
        assert seq.blk_passes == [-(-(B - r) // n) + 2 * (blocks - 1),
                                  blocks - 1]
        ran = [d["blocks"][uid] for d in dispatches if uid in d["blocks"]]
        assert ran.count("commit") == blocks - 1
        # between two commits: the block's T denoise passes, nothing else
        assert "".join(x[0] for x in ran).split("c")[1:-1] == \
            ["dd"] * (blocks - 2)
    # the dispatch window stays two deep: a step is dispatched while the one
    # before it is unread
    assert max(d["in_flight"] for d in dispatches) == 1
    assert sum(d["in_flight"] for d in dispatches) >= len(dispatches) - 2
    # rows: B a decoding sequence, beside whole tiles, inside the budget
    mixed = [d for d in dispatches if d["blocks"] and d["tiles"]]
    assert mixed and all(d["rows"] <= eng.cfg.max_tokens_per_step
                         for d in dispatches)


def test_a_tile_fits_beside_a_full_decode_bucket(params):
    """``4 nd + tile x nt <= max_tokens_per_step``: with every slot but one
    decoding (12 rows of the 16-row bucket's worth) a new prompt's tile is
    scheduled in the same step, and the step zoo names that program."""
    cfg = _cfg()
    eng = _engine(params, cfg, max_tokens_per_step=24, num_blocks=65)
    prompts = _prompts([4, 4, 4, 16], seed=2)
    for uid in range(3):
        eng.put(uid, prompts[uid], max_new_tokens=24)
    for _ in range(4):
        eng.step()
    assert sum(s.in_decode for s in eng._running.values()) == 3
    eng.put(3, prompts[3], max_new_tokens=4)
    seen = []
    real = eng._get_dev_step
    eng._get_dev_step = lambda *key: seen.append(key[:3]) or real(*key)
    eng.step()
    assert seen == [(4 * B + 8, 4, 1)]
    zoo = {key[:3] for key in eng._step_zoo()}
    assert (4 * B + 8, 4, 1) in zoo and all(t <= 24 for t, _, _ in zoo)
    eng.generate_all()
    assert eng.allocator.free_blocks == 64


def test_a_recomputed_request_reproduces_its_tokens(params):
    """The watchdog's recovery mid-flight (what a failed step gets): blocks
    in flight are dropped, what the host holds is run again as prefill under
    the block's mask, and the tokens are the undisturbed run's."""
    cfg, eng, prompts, *_ = _served(params, "T2")
    again = _engine(params, cfg)
    _serve(again, prompts, NEW, recover_after=9)
    for uid in prompts:
        assert again.get_request(uid).generated == \
            eng.get_request(uid).generated
    assert again.allocator.free_blocks == again.cfg.num_blocks - 1


def test_a_pick_of_the_mask_tokens_id_is_a_token(params):
    """"Masked" is a state the engine keeps, not a token id: a head that
    picks the mask token's id everywhere still unmasks ``n`` positions a
    pass and finishes."""
    cfg = _cfg()
    head = np.zeros((cfg.hidden_size, cfg.vocab_size), np.float32)
    rigged = {**params, "final_norm": jnp.zeros_like(params["final_norm"]),
              "lm_head": jnp.asarray(head)}
    # rmsnorm(x) * 0 = 0 -> logits all equal -> argmax 0; move the mask there
    cfg0 = dataclasses.replace(cfg, mask_token_id=0)
    eng = _engine(rigged, cfg0)
    eng.put(0, [5, 6, 7, 8], max_new_tokens=8)
    assert eng.generate_all()[0] == [0] * 8
    assert eng.get_request(0).blk_passes == [4, 1]


def test_the_dispatch_span_says_what_the_blocks_did(params, monkeypatch):
    spans = []
    real_span = ragged.span

    def recording(name, **attrs):
        if name == "engine/dispatch":
            spans.append(attrs)
        return real_span(name, **attrs)

    monkeypatch.setattr(ragged, "span", recording)
    cfg = _cfg()
    eng = _engine(params, cfg)
    prompts = _prompts([8, 16], seed=4)
    for uid, p in prompts.items():
        eng.put(uid, p, max_new_tokens=8)
    eng.generate_all()
    keys = {"blk_seqs", "blk_commit_seqs", "blk_unmasked", "blk_len",
            "blk_steps"}
    assert spans and all(keys <= set(a) for a in spans)
    total = {k: sum(a[k] for a in spans) for k in keys}
    # two requests x two blocks: 2 denoise passes each, one commit a request
    assert total["blk_unmasked"] == 16 and total["blk_commit_seqs"] == 2
    assert total["blk_seqs"] == 8 + 2
    for a in spans:
        assert a["tokens"] >= B * a["blk_seqs"]
        if a["blk_seqs"]:
            # a block reads its context once and spends B queries on it
            assert a["dec_kv_tokens"] >= (8 + B) * a["blk_seqs"] - 8
            assert a["attn_pairs"] >= B * a["dec_kv_tokens"]


# ------------------------------------------------------------ (f) refusals
@pytest.mark.parametrize("what,sizes", [
    ("enable_prefix_cache", {"enable_prefix_cache": True}),
    ("kv_tier", {"enable_prefix_cache": True, "kv_tier": True}),
    ("quant='int8'", {"quant": "int8"}),
    ("device_state=False", {"device_state": False}),
    ("prefill_tile=0", {"prefill_tile": 0}),
    ("prefill_tile=6", {"prefill_tile": 6}),
    ("block_size=6", {"block_size": 6}),
])
def test_what_blocks_cannot_carry_refuses_at_construction(params, what, sizes):
    with pytest.raises(ValueError, match="generates by blocks of 4; "
                       + re.escape(what.split("=")[0])):
        _engine(params, _cfg(), **sizes)


def test_what_blocks_cannot_carry_refuses_at_put(params):
    eng = _engine(params, _cfg())
    with pytest.raises(ValueError, match="temperature > 0 is refused"):
        eng.put(0, [1, 2, 3, 4], temperature=0.7)
    with pytest.raises(ValueError, match="KVHandoff is refused"):
        eng.put(0, [1, 2, 3, 4], handoff=True)
    assert not eng.has_work


def test_a_threshold_rule_refuses_in_words():
    with pytest.raises(NotImplementedError, match="low_confidence_dynamic"):
        _cfg(remask="low_confidence_dynamic")
    with pytest.raises(ValueError, match="do not divide"):
        BlockGen(4, 3, "sequential", 0)
    with pytest.raises(ValueError, match="outside the vocabulary"):
        sdar.SdarConfig.tiny(89, mask_token_id=89)


def test_a_block_model_does_not_degrade_to_the_host_step(params):
    eng = _engine(params, _cfg(), degrade_after=1)
    eng._consec_failures = 1
    assert not eng._maybe_degrade(RuntimeError("x"))
    assert eng.cfg.device_state and eng.degraded_mode == 0
