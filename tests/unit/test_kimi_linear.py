"""``models/kimi_linear.py`` at a small size on the CPU, seeded weights: what is
served (prefill in tiles, then decode, through the latent pool AND the slot
state) against the plain reference ``benchmark/reference/kimi_linear.py``; the
chunk form of the delta rule against the token-by-token recurrence at the
strongest decay the configuration can draw, as XLA's ~60 operations and as
the kernel; the decode and the chunk kernel against XLA's forms; one rank's
share of the experts; MLA with and without rotation; what a dispatch span says
of the state.

Logits are compared, not tokens. Tolerance 2e-4 (float32 everywhere here): the
program runs a prompt as chunks (a triangular solve and matmuls inside a chunk,
the state carried between them) and the reference as a scan over tokens, so the
same sums are taken in another order; observed differences are under 2e-6 on
logits of magnitude 0.6.
"""

import functools
import importlib.util
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from shared import one_engine_each, over_one_length  # tests/unit on the path

from deepspeed_tpu.inference.ragged import RaggedConfig, RaggedInferenceEngine
from deepspeed_tpu.models import deepseek, experts, kimi_linear, nemotron_h
from deepspeed_tpu.models import paged
from deepspeed_tpu.models.paged import SLOTS
from deepspeed_tpu.ops.pallas.kda import (
    kda_chunk,
    kda_chunk_xla,
    kda_decode,
    kda_decode_xla,
    kda_tiles,
)

REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
ATOL = 2e-4


def _reference():
    spec = importlib.util.spec_from_file_location(
        "reference_kimi_linear",
        os.path.join(REPO, "benchmark", "reference", "kimi_linear.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


REF = _reference()
CFG = kimi_linear.KimiLinearConfig.tiny()   # "DKMKM", 4 of 8 experts held


@pytest.fixture(scope="module")
def params():
    return kimi_linear.init_params(CFG, jax.random.PRNGKey(1))


def _engine(params, cfg=CFG, device_state=False, **sizes):
    rc = RaggedConfig(**{**dict(
        max_tokens_per_step=32, max_seqs=4, block_size=8, num_blocks=33,
        max_blocks_per_seq=8, prefill_tile=8, device_state=device_state),
        **sizes})
    return RaggedInferenceEngine(lambda ctx: kimi_linear.build(cfg, ctx=ctx),
                                 rc, dtype=jnp.float32, params=params)


@pytest.fixture(scope="module")
def engine_of(params):
    """``engine_of(**sizes)``: the module's ONE engine of those sizes, as new
    each time it is asked for (``shared.py``)."""
    return one_engine_each(functools.partial(_engine, params))


def _prompts(lengths, seed=0):
    rng = np.random.default_rng(seed)
    return {uid: rng.integers(0, CFG.vocab_size, n).tolist()
            for uid, n in enumerate(lengths)}


# the longest request served here is 37 + 8 tokens
_reference_rows = over_one_length(REF.forward, 48)


# case -> (engine sizes, prompt lengths, new tokens, step after which the
# watchdog's recovery runs, or None)
SERVED = {
    # 13 tokens, tile 8: two tiles of ONE slot in one step, the second partial
    "prompt_in_one_step": ({}, [13], 4, None),
    # 16 a step: 16 + 16 + 5, a partial last tile, the state carried over steps
    "prompt_chunked_over_steps": ({"max_tokens_per_step": 16}, [37], 4, None),
    # six requests over four slots: decode rows beside tiles, slots reused
    "mixed_steps": ({}, [5, 19, 37, 9, 26, 3], 6, None),
    # one slot: the second request starts from zeros where the first ended
    "slot_reused": ({"max_seqs": 1}, [11, 7], 5, None),
    # positions rewound mid-flight (what a preempted or contained request
    # gets): the state restarts from zeros with a re-prefill from position 0
    "recovered_and_recomputed": ({}, [5, 19, 37, 9], 8, 4),
    # 8 blocks of 8 for three requests that grow to 27 + 34 + 29 tokens: the
    # third waits for the blocks of the first and takes over its slot's rows
    "tight_pool": ({"num_blocks": 9}, [19, 26, 21], 8, None),
    # three decoders in a bucket of four: a padding row on the scratch slot
    "bucket_padding_rows": ({}, [6, 9, 4], 5, None),
}


def _serve(eng, prompts, new_tokens, recover_after=None):
    """Run the requests to their end; ``{(uid, g): logits row}`` of every
    emission of the host-staged path (generated token ``g`` of ``uid``; a
    recomputed request's later emission replaces its earlier one)."""
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
        del eng._emit_tokens        # the engine is shared: the method again
    return rows


@pytest.mark.parametrize("case", SERVED)
def test_served_logits_match_the_reference(params, engine_of, case):
    sizes, lengths, new_tokens, recover_after = SERVED[case]
    eng = engine_of(**sizes)
    prompts = _prompts(lengths)
    rows = _serve(eng, prompts, new_tokens, recover_after)
    for uid, prompt in prompts.items():
        generated = eng.get_request(uid).generated
        assert len(generated) == new_tokens
        want = _reference_rows(CFG, params, prompt + generated)
        for g in range(new_tokens):
            np.testing.assert_allclose(
                rows[(uid, g)], want[len(prompt) + g - 1], atol=ATOL,
                err_msg=f"{case}: request {uid}, generated token {g}")
    # the scratch slot is what padding rows and tiles read and write: zero
    # before, zero after
    slots = eng.cache[SLOTS]
    assert not np.asarray(slots["kda"][:, -1]).any()
    assert not np.asarray(slots["conv"][:, -1]).any()
    assert eng.allocator.free_blocks == eng.cfg.num_blocks - 1


@pytest.mark.parametrize("case", ["mixed_steps", "recovered_and_recomputed",
                                  "slot_reused"])
def test_device_resident_path_serves_the_reference_tokens(params, engine_of,
                                                          case):
    """The device-resident step (slot rows, picks on the device) against the
    reference's greedy tokens, teacher-forced on what was served."""
    sizes, lengths, new_tokens, recover_after = SERVED[case]
    eng = engine_of(device_state=True, **sizes)
    prompts = _prompts(lengths)
    _serve(eng, prompts, new_tokens, recover_after)
    for uid, prompt in prompts.items():
        generated = eng.get_request(uid).generated[:new_tokens]
        want = _reference_rows(CFG, params, prompt + generated)
        greedy = want.argmax(-1)[len(prompt) - 1:len(prompt) + new_tokens - 1]
        assert generated == greedy.tolist(), (case, uid)


@pytest.mark.parametrize("pattern", ["DKMKM", "DKKMKKKMKM", "AKMKM"])
def test_plain_forward_is_the_reference(pattern):
    """The family's ``forward`` (the chunk form, chunks of 8 in sub-chunks of
    4) against the reference's token-by-token recurrence: the published order's
    three kinds of layer, a tail after the last whole period (``D`` + 2 x
    ``KKMK`` + ``M``), and a leading layer that is MLA + dense."""
    cfg = kimi_linear.KimiLinearConfig.tiny(pattern=pattern)
    p = kimi_linear.init_params(cfg, jax.random.PRNGKey(3))
    lead, period, repeats, tail = paged.stack_plan_tail(cfg.layer_pattern)
    assert lead + period * repeats + tail == pattern
    assert (len(p["lead"]), len(p["period"]), len(p["tail"])) == (
        len(lead), len(period), len(tail))
    ids = jnp.asarray(_prompts([41], seed=3)[0])
    np.testing.assert_allclose(
        np.asarray(kimi_linear.forward(cfg, p, ids[None])[0]),
        np.asarray(REF.forward(cfg, p, ids)), atol=ATOL)
    assert kimi_linear.num_params(cfg) == REF.num_params(cfg) == sum(
        a.size for a in jax.tree_util.tree_leaves(p))
    axes = kimi_linear.param_logical_axes(cfg)
    is_axes = lambda a: isinstance(a, tuple)  # noqa: E731
    assert jax.tree_util.tree_structure(p) == jax.tree_util.tree_structure(
        axes, is_leaf=is_axes)
    for leaf, ax in zip(jax.tree_util.tree_leaves(p),
                        jax.tree_util.tree_leaves(axes, is_leaf=is_axes)):
        assert leaf.ndim == len(ax)


def test_a_tail_after_the_scan_is_served(params):
    """``D`` + 2 x ``KKMK`` + ``M``: the last MLA layer runs after the scan, at
    block layer 2 of 3 (``paged._scan_periods``' ``tail``)."""
    cfg = kimi_linear.KimiLinearConfig.tiny(pattern="DKKMKKKMKM")
    assert paged.stack_plan_tail(cfg.layer_pattern) == ("D", "KKMK", 2, "M")
    p = kimi_linear.init_params(cfg, jax.random.PRNGKey(4))
    eng = _engine(p, cfg=cfg, max_tokens_per_step=16, max_seqs=2,
                  num_blocks=17, max_blocks_per_seq=4)
    assert eng.cache["kv"].shape[0] == 3 and eng.cache[SLOTS]["kda"].shape[0] == 7
    prompt = _prompts([21], seed=9)[0]
    eng.put(0, prompt, max_new_tokens=3)
    got = list(eng.generate_all()[0])
    want = np.asarray(REF.forward(cfg, p, jnp.asarray(prompt + got)))
    assert got == want.argmax(-1)[len(prompt) - 1:len(prompt) + 2].tolist()


def test_the_published_order_has_a_plan():
    full = kimi_linear.KimiLinearConfig()
    assert full.layer_pattern == "D" + "KKMK" * 5 + "KKMKKM"
    lead, period, repeats, tail = paged.stack_plan_tail(full.layer_pattern)
    assert len(lead + period + tail) == 7 and repeats == 6
    # 49.12 B whole (published: 48B); the benchmark's cut is in its own test
    assert REF.num_params(full) == kimi_linear.num_params(full) == 49_122_681_728
    with pytest.raises(NotImplementedError, match="repeated period"):
        kimi_linear.KimiLinearConfig.tiny(pattern="DM")


# ------------------------------------------------------- the chunk form
def _token_by_token(q, k, v, g, beta, s0):
    """The recurrence as written, one tile after another of ONE sequence:
    ``q`` .. [I, R, H, *], ``s0`` [K, H x V] -> ``(y [I, R, H x V], s)``."""
    n_i, r, h, kd = q.shape
    vd = v.shape[-1]

    def token(state, xs):                                         # [H, K, V]
        q_t, k_t, v_t, g_t, b_t = xs
        state = jnp.exp(g_t)[:, :, None] * state
        u = b_t[:, None] * (v_t - jnp.einsum("hkv,hk->hv", state, k_t))
        state = state + k_t[:, :, None] * u[:, None, :]
        return state, jnp.einsum("hkv,hk->hv", state, q_t)

    flat = [t.reshape((n_i * r,) + t.shape[2:]) for t in (q, k, v, g, beta)]
    state, y = jax.lax.scan(
        token, s0.reshape(kd, h, vd).transpose(1, 0, 2), tuple(flat))
    return (y.reshape(n_i, r, h * vd),
            state.transpose(1, 0, 2).reshape(kd, h * vd))


def _rel(got, want):
    """The largest difference as a share of the largest wanted magnitude."""
    got, want = np.asarray(got), np.asarray(want)
    return float(np.abs(got - want).max() / np.abs(want).max())


def _one_sequence(form, q, k, v, g, beta, s0, sub):
    """The tiles ``q`` .. [I, R, H, *] of ONE sequence from the state ``s0``
    [K, H x V] -> ``(y [I, R, H x V], the last tile's state)``, as XLA's
    form (``kda_tiles``) or as the kernel reads and writes a slot leaf: the
    sequence's state in row 2 of 4 (the others garbage), row 3 the scratch
    slot, which every tile but the last writes."""
    n_i, r = q.shape[:2]
    cont = jnp.arange(n_i) > 0
    if form == "xla":
        y, s = kda_tiles(
            q, k, v, g, beta, jnp.broadcast_to(s0, (n_i,) + s0.shape), cont, sub)
        return y, s[-1]
    leaf = jnp.full((4,) + s0.shape, jnp.nan, jnp.float32).at[2].set(s0)
    last = jnp.arange(n_i) == n_i - 1
    leaf, y = kda_chunk(
        leaf, jnp.full((n_i,), 2), jnp.where(last, 2, 3),
        jnp.zeros((n_i,), bool), cont, last,
        *(t.reshape(n_i, r, -1) for t in (q, k, g, v)), beta, sub,
        impl="pallas", interpret=True)
    if n_i > 1:
        assert not np.asarray(leaf[3]).any()
    assert np.isnan(np.asarray(leaf[:2])).all()
    return y, leaf[2]


@pytest.mark.parametrize("tiles", [1, 4])
@pytest.mark.parametrize("form", ["xla", "kernel"])
def test_chunk_form_is_the_recurrence_at_the_strongest_decay(form, tiles):
    """A 128-row tile (and four with the carry) in sub-chunks of 16 at the
    strongest decay the configuration can draw, ``A = 16`` and ``dt = 0.1``
    on every channel of half the heads (``g = -1.6`` a token, ``G = -205``
    over the tile: ``exp(-G)`` alone is float32's ``inf``), the seeded range
    on the others, from a state that is not zero: finite, and the
    token-by-token recurrence to float32 rounding (2e-5 on readings of
    magnitude ~1: a 16 x 16 block's substitution, 8 steps over the
    sub-chunks and the pairwise sums in another order). ``kernel``:
    ``kda_chunk`` in interpret mode, which is also pinned to XLA's form at
    1e-5 of the largest reading and state: float32 and six-pass products
    give that, bfloat16 operands or a single pass 1e-3."""
    rng = np.random.default_rng(tiles)
    r, h, kd = 128, 4, 32
    shape = (tiles, r, h, kd)

    def unit(x):
        return x / np.linalg.norm(x, axis=-1, keepdims=True)

    q = unit(rng.standard_normal(shape)) * kd ** -0.5
    k = unit(rng.standard_normal(shape))
    v = rng.standard_normal(shape)
    dt = np.exp(rng.uniform(np.log(0.001), np.log(0.1), shape))
    g = -rng.uniform(1.0, 16.0, (1, 1, h, 1)) * dt
    g[:, :, :h // 2] = -16.0 * 0.1
    beta = rng.uniform(0.0, 1.0, shape[:3])
    s0 = rng.standard_normal((kd, h * kd))
    q, k, v, g, beta, s0 = (jnp.asarray(a, jnp.float32)
                            for a in (q, k, v, g, beta, s0))
    assert float(jnp.cumsum(g, axis=1).min()) < -200.0
    got_y, got_s = _one_sequence(form, q, k, v, g, beta, s0, 16)
    want_y, want_s = _token_by_token(q, k, v, g, beta, s0)
    assert np.isfinite(np.asarray(got_y)).all()
    assert np.isfinite(np.asarray(got_s)).all()
    np.testing.assert_allclose(np.asarray(got_y), np.asarray(want_y), atol=2e-5)
    np.testing.assert_allclose(np.asarray(got_s), np.asarray(want_s), atol=2e-5)
    if form == "kernel":
        xla_y, xla_s = _one_sequence("xla", q, k, v, g, beta, s0, 16)
        assert _rel(got_y, xla_y) < 1e-5 and _rel(got_s, xla_s) < 1e-5


@pytest.mark.parametrize("form", ["xla", "kernel"])
def test_rows_past_a_tiles_valid_ones_neither_decay_nor_feed(form):
    rng = np.random.default_rng(5)
    r, h, kd, valid = 16, 2, 8, 11
    q, k, v = (jnp.asarray(rng.standard_normal((1, r, h, kd)), jnp.float32)
               for _ in range(3))
    g = -jnp.asarray(rng.uniform(0.01, 1.0, (1, r, h, kd)), jnp.float32)
    beta = jnp.asarray(rng.uniform(0, 1, (1, r, h)), jnp.float32)
    s0 = jnp.asarray(rng.standard_normal((kd, h * kd)), jnp.float32)
    live = (jnp.arange(r) < valid)[None, :, None]
    _, s_pad = _one_sequence(
        form, q, k, v, jnp.where(live[..., None], g, 0.0),
        jnp.where(live, beta, 0.0), s0, 4)
    _, s_cut = _token_by_token(q[:, :valid], k[:, :valid], v[:, :valid],
                               g[:, :valid], beta[:, :valid], s0)
    np.testing.assert_allclose(np.asarray(s_pad), np.asarray(s_cut), atol=1e-5)


# a step's tiles as the engine hands them over: (read row, write row, fresh,
# cont, write) a tile; row 5 of the 6-row leaf is the scratch slot
CHUNK_STEPS = {
    # a slot's prompt over two and over three tiles: the state is carried in
    # the kernel, the tiles before the last park zeros in the scratch slot
    "continued_over_two_tiles": [(1, 5, 0, 0, 0), (1, 1, 0, 1, 1)],
    "continued_over_three_tiles": [(3, 5, 1, 0, 0), (3, 5, 0, 1, 0),
                                   (3, 3, 0, 1, 1)],
    # position 0 of a slot whose row holds what the last request left (NaN)
    "fresh_slot_over_garbage": [(4, 4, 1, 0, 1)],
    # a padding tile names the scratch slot both ways and writes it zeros
    "padding_tile": [(0, 0, 0, 0, 1), (5, 5, 1, 0, 0), (5, 5, 1, 0, 0)],
    # two slots, the first continued: the second must not start from the
    # first's carry, and both rows are written
    "two_slots": [(2, 5, 0, 0, 0), (2, 2, 0, 1, 1), (0, 0, 0, 0, 1)],
    # two slots, both continued, a padding tile between them: the scratch
    # slot is written zeros three times, and the state the kernel carries
    # from turn to turn of a tile leaks into neither the padding tile nor
    # the second slot
    "two_slots_around_a_padding_tile": [
        (2, 5, 0, 0, 0), (2, 2, 0, 1, 1), (5, 5, 1, 0, 0),
        (0, 5, 1, 0, 0), (0, 0, 0, 1, 1)],
    # a tile whose valid rows end INSIDE a sub-chunk (6 of 16 rows in
    # sub-chunks of 4), then the slot's next tile: the rows past them
    # neither decay nor feed the state that is carried on
    "valid_rows_end_inside_a_sub_chunk": [(1, 5, 0, 0, 0), (1, 1, 0, 1, 1)],
}


@pytest.mark.parametrize("case", CHUNK_STEPS)
def test_kda_chunk_kernel_is_the_xla_form(case):
    """``kda_chunk`` in interpret mode against a dynamic slice a tile,
    ``kda_tiles`` and a dynamic-update-slice a tile, on a leaf whose scratch
    row holds garbage before the step: readings and written rows to 1e-5 of
    their largest magnitude, and no row but the written ones changes by a
    bit. The output aliases the leaf, so a tile's row is fetched while the
    tile before it still computes: a continued tile ignores what it fetched,
    and the only row written twice is the scratch slot's (zeros each
    time)."""
    tiles = CHUNK_STEPS[case]
    rng = np.random.default_rng(len(case))
    n_i, r, h, kd, sub = len(tiles), 16, 2, 8, 4

    def draw(*shape):
        return jnp.asarray(rng.standard_normal(shape), jnp.float32)

    q, k, v = (draw(n_i, r, h * kd) for _ in range(3))
    g = -jnp.abs(draw(n_i, r, h * kd)) * 0.3
    beta = jax.nn.sigmoid(draw(n_i, r, h))
    if case == "padding_tile":
        g, beta = g.at[1:].set(0.0), beta.at[1:].set(0.0)
    if case == "two_slots_around_a_padding_tile":
        g, beta = g.at[2].set(0.0), beta.at[2].set(0.0)
    if case == "valid_rows_end_inside_a_sub_chunk":
        g, beta = g.at[0, 6:].set(0.0), beta.at[0, 6:].set(0.0)
    leaf = draw(6, kd, h * kd)
    if case == "fresh_slot_over_garbage":
        leaf = leaf.at[4].set(jnp.nan)
    rows, rows_w, fresh, cont, write = (
        jnp.asarray(col, jnp.int32) for col in zip(*tiles))
    args = (leaf, rows, rows_w, fresh > 0, cont > 0, write > 0, q, k, g, v,
            beta, sub)
    got_s, got_y = kda_chunk(*args, impl="pallas", interpret=True)
    want_s, want_y = kda_chunk_xla(*args)
    assert np.isfinite(np.asarray(got_y)).all()
    assert _rel(got_y, want_y) < 1e-5 and _rel(got_s, want_s) < 1e-5
    written = sorted(set(np.asarray(rows_w).tolist()))
    untouched = [i for i in range(6) if i not in written]
    np.testing.assert_array_equal(np.asarray(got_s)[untouched],
                                  np.asarray(leaf)[untouched])
    if 5 in written:
        assert not np.asarray(got_s[5]).any()
    assert all((np.asarray(got_s[i]) != np.asarray(leaf[i])).any()
               for i in written)


@pytest.mark.parametrize("heads", [1, 2, 3, 4, 6, 8])
def test_kda_chunk_kernel_takes_the_heads_a_grid_step_that_divide(heads):
    """The kernel takes four heads a grid step where four divide the head
    count, two where two do, else one (the heads of a step share ONE
    substitution chain, side by side on the lanes): every count gives XLA's
    form, a slot continued over two tiles beside a fresh one in sub-chunks
    of 16 (two bands of 8 rows), each head its own ``beta`` column."""
    rng = np.random.default_rng(heads)
    n_i, r, kd, sub = 3, 32, 16, 16

    def draw(*shape):
        return jnp.asarray(rng.standard_normal(shape), jnp.float32)

    def unit(x):  # a head's K normalised, as the model's keys are
        x = x.reshape(n_i, r, heads, kd)
        return (x / jnp.linalg.norm(x, axis=-1, keepdims=True)).reshape(
            n_i, r, heads * kd)

    q, k, v = (unit(draw(n_i, r, heads * kd)), unit(draw(n_i, r, heads * kd)),
               draw(n_i, r, heads * kd))
    g = -jnp.abs(draw(n_i, r, heads * kd)) * 0.3
    beta = jax.nn.sigmoid(draw(n_i, r, heads))
    leaf = draw(4, kd, heads * kd)
    tiles = [(1, 3, 0, 0, 0), (1, 1, 0, 1, 1), (0, 0, 1, 0, 1)]  # 3: scratch
    rows, rows_w, fresh, cont, write = (
        jnp.asarray(col, jnp.int32) for col in zip(*tiles))
    args = (leaf, rows, rows_w, fresh > 0, cont > 0, write > 0, q, k, g, v,
            beta, sub)
    got_s, got_y = kda_chunk(*args, impl="pallas", interpret=True)
    want_s, want_y = kda_chunk_xla(*args)
    assert _rel(got_y, want_y) < 1e-5 and _rel(got_s, want_s) < 1e-5
    np.testing.assert_array_equal(np.asarray(got_s[2]), np.asarray(leaf[2]))
    assert not np.asarray(got_s[3]).any()


# ------------------------------------------------------------ the kernel
def test_kda_decode_kernel_is_the_xla_form():
    """``kda_decode`` in interpret mode against gather -> update -> scatter,
    and both against the recurrence a row; two padding rows (``a = 1``,
    ``beta = 0``) on the scratch row leave it as it was, and no row outside
    the step's moves."""
    rng = np.random.default_rng(0)
    rows_n, kd, h, vd, t = 10, 16, 2, 128, 5
    state = jnp.asarray(rng.standard_normal((rows_n, kd, h * vd)), jnp.float32)
    rows = jnp.asarray([3, 7, 9, 1, 9], jnp.int32)              # 9: scratch
    a = jnp.asarray(rng.uniform(0.2, 1.0, (t, kd, h)), jnp.float32)
    k = rng.standard_normal((t, kd, h))
    k = jnp.asarray(k / np.linalg.norm(k, axis=1, keepdims=True), jnp.float32)
    q = jnp.asarray(rng.standard_normal((t, kd, h)), jnp.float32)
    v = jnp.asarray(rng.standard_normal((t, h * vd)), jnp.float32)
    beta = jnp.repeat(jnp.asarray(rng.uniform(0, 1, (t, h)), jnp.float32), vd, 1)
    pad = jnp.asarray([False, False, True, False, True])
    a = jnp.where(pad[:, None, None], 1.0, a)
    beta = jnp.where(pad[:, None], 0.0, beta)
    got_s, got_y = kda_decode(state, rows, a, k, q, v, beta, impl="pallas",
                              interpret=True)
    want_s, want_y = kda_decode_xla(state, rows, a, k, q, v, beta)
    np.testing.assert_allclose(np.asarray(got_s), np.asarray(want_s), atol=1e-5)
    np.testing.assert_allclose(np.asarray(got_y), np.asarray(want_y), atol=1e-4)
    untouched = [0, 2, 4, 5, 6, 8, 9]
    np.testing.assert_array_equal(np.asarray(got_s)[untouched],
                                  np.asarray(state)[untouched])
    # row 0 of the step, head 1, as the recurrence writes it
    s = np.asarray(state[3]).reshape(kd, h, vd)[:, 1]
    d = np.asarray(a[0, :, 1])[:, None] * s
    u = float(beta[0, vd]) * (np.asarray(v[0, vd:]) - d.T @ np.asarray(k[0, :, 1]))
    new = d + np.outer(np.asarray(k[0, :, 1]), u)
    np.testing.assert_allclose(
        np.asarray(got_s[3]).reshape(kd, h, vd)[:, 1], new, atol=1e-5)
    np.testing.assert_allclose(np.asarray(got_y[0, vd:]),
                               new.T @ np.asarray(q[0, :, 1]), atol=1e-4)


def test_padding_rows_leave_other_slots_alone(params):
    """A step of one real decode row and three padding rows: the slots that
    are not in the step keep their state bit for bit."""
    cache = kimi_linear.init_paged_cache(CFG, 9, 8, jnp.float32, num_slots=5)
    key = jax.random.PRNGKey(5)
    cache[SLOTS]["kda"] = jax.random.normal(key, cache[SLOTS]["kda"].shape
                                            ).at[:, -1].set(0.0)
    before = np.asarray(cache[SLOTS]["kda"])
    tables = np.zeros((5, 2), np.int32)
    tables[2] = [3, 4]
    i32 = lambda a: jnp.asarray(a, jnp.int32)  # noqa: E731
    _, cache = kimi_linear.ragged_forward(
        CFG, params, i32([7, 0, 0, 0]), i32([2, 4, 4, 4]), i32([5, 0, 0, 0]),
        jnp.asarray(tables), cache,
        prefill_tiles=(4, i32([4]), i32([0]), i32([0]), 8))
    after = np.asarray(cache[SLOTS]["kda"])
    np.testing.assert_array_equal(after[:, [0, 1, 3, 4]], before[:, [0, 1, 3, 4]])
    assert (after[:, 2] != before[:, 2]).any()


# --------------------------------------------------- one rank's share
def test_eight_ranks_parts_add_up_to_the_uncut_layer():
    """The share test of the model-configs guide, section 4: an expert layer
    with all 16 experts against the eight ranks' layers of 2 experts each,
    the same router over all 16. The ranks' routed parts add up and the
    shared expert counts once: the uncut layer of the reference."""
    whole = kimi_linear.KimiLinearConfig.tiny(num_experts=16, experts_held=None)
    full = jax.tree_util.tree_map(
        lambda a: a[0],
        kimi_linear.init_params(whole, jax.random.PRNGKey(2))["period"][0]["ffn"])
    h = jnp.asarray(np.random.default_rng(1).standard_normal(
        (23, whole.hidden_size)), jnp.float32)
    want = np.asarray(REF._moe(whole, h, full, jnp.float32))
    shared = experts.swiglu(h, full["ws_gate"], full["ws_up"], full["ws_down"])
    routed = 0.0
    for rank in range(8):
        cfg = kimi_linear.KimiLinearConfig.tiny(
            num_experts=16, experts_held=2, expert_rank=rank)
        lp = {**full, **{w: full[w][2 * rank:2 * rank + 2]
                         for w in ("w_gate", "w_up", "w_down")}}
        layer = deepseek._ffn(cfg, h, lp, experts.routed_experts)
        # a rank's own layer is what the reference computes for that rank
        np.testing.assert_allclose(
            np.asarray(layer), np.asarray(REF._moe(cfg, h, lp, jnp.float32)),
            atol=ATOL)
        routed = routed + (layer - shared)
    np.testing.assert_allclose(np.asarray(routed + shared), want, atol=ATOL)


# ------------------------------------------------------ MLA, no positions
def test_mla_without_rotation_is_the_reference_and_differs_from_rotated():
    """``mla_use_nope``: the shared MLA helpers leave the 64 "rope" lanes of
    the query and of the key as projected; with it off they rotate them, as
    ``deepseek``'s own configs do (its tests run unchanged). Both against the
    reference's layer, and against each other."""
    lp = jax.tree_util.tree_map(
        lambda a: a[0],
        kimi_linear.init_params(CFG, jax.random.PRNGKey(6))["period"][1]["mix"])
    # seeded at std 0.02 the scores are too flat for a rotation to show
    lp = {**lp, "wq": lp["wq"] * 8, "wkv_a": lp["wkv_a"] * 8}
    h = jnp.asarray(np.random.default_rng(2).standard_normal(
        (1, 19, CFG.hidden_size)), jnp.float32)
    pos = jnp.arange(19)[None]
    out = {}
    for nope in (True, False):
        cfg = kimi_linear.KimiLinearConfig.tiny(mla_use_nope=nope)
        out[nope] = np.asarray(deepseek._plain_attention(cfg, h, lp, pos)[0])
        np.testing.assert_allclose(
            out[nope], np.asarray(REF._mla(cfg, h[0], lp, jnp.float32)),
            atol=1e-5)
    assert np.abs(out[True] - out[False]).max() > 1e-3  # 4e-3 on outputs of ~6e-3
    assert not deepseek.DeepseekConfig.tiny().mla_use_nope


# ------------------------------------------------------------ the engine
def test_engine_accounts_blocks_and_slots_apart(engine_of):
    """The latent leaf counts the 2 MLA layers, the slot leaves the 3 KDA
    layers (``S`` and the three convolutions' rows): the reference's
    geometry, which the benchmark's readers multiply the spans by."""
    eng = engine_of()
    assert eng.cache["kv"].shape[0] == 2 and eng.cache[SLOTS]["kda"].shape[0] == 3
    assert eng.kv_bytes_per_token() == CFG.row_lanes * 4 * 2
    assert REF.kv_bytes_per_token(CFG, 4) == (32 + 16) * 4 * 2
    assert eng.state_bytes_per_slot() == REF.state_bytes_per_slot(CFG, 4) \
        == 3 * (4 * 2 * 16 * 16 + 3 * 3 * 32 * 4)


@pytest.mark.parametrize("family", ["kimi_linear", "nemotron_h"])
def test_dispatch_span_says_what_state_the_step_moved(engine_of, family,
                                                      monkeypatch):
    """``engine/dispatch`` of a model with slot state: ``state_bytes`` (decode
    rows + distinct prefilling slots, a slot's bytes once each way),
    ``dec_state_bytes``, ``ssm_prefill_tokens``, ``chunk_tiles`` (the tiles
    the step program runs the chunk form over) and ``state_kind`` (``"kda"``
    here, ``"mamba2"`` for ``nemotron_h``); the same bytes on
    ``inference_slot_state_bytes_total``, decode and prefill parts labelled."""
    from deepspeed_tpu import telemetry
    from deepspeed_tpu.inference import ragged
    from deepspeed_tpu.models import nemotron_h

    seen = []
    real = ragged.span
    monkeypatch.setattr(ragged, "span", lambda name, **a: (
        seen.append(a) if name == "engine/dispatch" else None, real(name, **a))[1])
    telemetry.configure(enabled=True)
    try:
        if family == "kimi_linear":
            eng, kind = engine_of(device_state=True), "kda"
        else:
            cfg = nemotron_h.NemotronHConfig.tiny()
            eng = RaggedInferenceEngine(
                lambda ctx: nemotron_h.build(cfg, ctx=ctx), RaggedConfig(
                    max_tokens_per_step=32, max_seqs=4, block_size=8,
                    num_blocks=33, max_blocks_per_seq=8, prefill_tile=8),
                dtype=jnp.float32)
            kind = "mamba2"
        for uid, prompt in _prompts([11, 5]).items():
            eng.put(uid, prompt, max_new_tokens=4)
        eng.generate_all()
        series = telemetry.snapshot()["metrics"][
            "inference_slot_state_bytes_total"]["series"]
    finally:
        telemetry.configure(enabled=False)
    per_slot = 2 * eng.state_bytes_per_slot()
    assert seen and all(a["state_kind"] == kind for a in seen)
    # the first step prefills both prompts (two slots), the others decode
    assert seen[0]["state_bytes"] == 2 * per_slot
    assert seen[0]["dec_state_bytes"] == 0
    assert seen[0]["ssm_prefill_tokens"] == 16
    # the program's four: 11 tokens are two tiles of 8, 5 are one, one pads
    assert seen[0]["chunk_tiles"] == 4
    assert all(a["state_bytes"] == a["dec_state_bytes"] == 2 * per_slot
               and a["ssm_prefill_tokens"] == a["chunk_tiles"] == 0
               for a in seen[1:-1])
    by_part = {s["labels"]["part"]: s["value"] for s in series
               if s["labels"].get("state_kind") == kind}
    assert by_part["decode"] == sum(a["dec_state_bytes"] for a in seen)
    assert by_part["prefill"] == sum(
        a["state_bytes"] - a["dec_state_bytes"] for a in seen)


@pytest.mark.parametrize("family", ["kimi_linear", "nemotron_h"])
def test_the_watchdogs_ladder_ends_at_the_host_staged_step(params, family):
    """Failures that go on take an engine with slot state to rung 1 (the
    host-staged step) and no further: rung 2 turns prefill tiles off, and the
    recurrence runs a prompt's rows as tiles. Each failed step starts its
    sequences again from an empty state, and the tokens are the clean run's."""
    from deepspeed_tpu.utils.faults import POINT_DISPATCH, get_fault_injector

    mod, cfg, own = ((kimi_linear, CFG, params) if family == "kimi_linear"
                     else (nemotron_h, nemotron_h.NemotronHConfig.tiny(), None))
    eng = RaggedInferenceEngine(
        lambda ctx: mod.build(cfg, ctx=ctx), RaggedConfig(
            max_tokens_per_step=32, max_seqs=4, block_size=8,
            num_blocks=33, max_blocks_per_seq=8, prefill_tile=8,
            dispatch_retries=2, retry_backoff_s=0.0, degrade_after=2),
        dtype=jnp.float32, params=own, seed=0)
    outs = {}
    for faulty in (False, True):   # one engine: the clean run first
        if faulty:
            get_fault_injector().configure(
                [{"point": POINT_DISPATCH, "after": 2, "times": 4}])
        for uid, prompt in _prompts([11, 5, 19]).items():
            eng.put((faulty, uid), prompt, max_new_tokens=5)
        outs[faulty] = {uid: toks for (run, uid), toks
                        in eng.generate_all().items() if run is faulty}
    assert outs[True] == outs[False]
    assert eng.step_failures == 4
    assert eng.degraded_mode == 1 and not eng.cfg.device_state
    assert eng.cfg.prefill_tile == 8 and eng._use_tiles
    assert eng._tiled_jits and not eng._pending  # the host-staged step served
    assert eng.allocator.free_blocks == eng.cfg.num_blocks - 1


def test_decode_ladder_and_refusals(params):
    eng = _engine(params, max_tokens_per_step=512, max_seqs=128,
                  num_blocks=257, max_blocks_per_seq=2, prefill_tile=128)
    assert eng._dec_buckets == [128] and len(eng._step_zoo()) == 7
    assert eng.spec.state_kind == "kda"
    for sizes, match in ((dict(enable_prefix_cache=True), "snapshot"),
                         (dict(quant="int8"), "quantized pool"),
                         (dict(prefill_tile=0), "tile")):
        with pytest.raises((ValueError, NotImplementedError), match=match):
            _engine(params, **sizes)
    odd = _engine(params, prefill_tile=6, block_size=6, max_tokens_per_step=12)
    odd.put(0, [1, 2, 3, 4, 5, 6, 7], max_new_tokens=1)
    with pytest.raises(ValueError, match="sub-chunk"):
        odd.generate_all()


# ------------------------------------------------ the convolutions' window leaf
# 16 KDA heads of 64: the three convolutions' 3,072 channels are whole float32
# tiles (8 x 128 divides them), so the window leaf is folded; the tiny
# configuration's 96 are not, and the leaf keeps its rows
FOLDED = kimi_linear.KimiLinearConfig.tiny(linear_attn_config={
    "kda_layers": [1, 2, 4], "full_attn_layers": [3, 5], "head_dim": 64,
    "num_heads": 16, "short_conv_kernel_size": 4})     # "DKMKM"
WINDOW_FAMILIES = {
    # family -> (module, its tiny configuration, one with a width that folds)
    "kimi_linear": (kimi_linear, CFG, FOLDED, lambda c: 3 * c.kda_width),
    # 8 heads of 96 + 2 x 2 groups x 64 states = 1,024 channels
    "nemotron_h": (nemotron_h, nemotron_h.NemotronHConfig.tiny(),
                   nemotron_h.NemotronHConfig.tiny(mamba_head_dim=96,
                                                   ssm_state_size=64),
                   lambda c: c.conv_width),
}


@pytest.mark.parametrize("form", ["folded", "rows"])
@pytest.mark.parametrize("family", WINDOW_FAMILIES)
def test_window_leaf_round_trips(family, form):
    """The window leaf as each family's ``init_paged_cache`` builds it, in
    the folded form (a slot whole tiles) and in the form a width the tile
    does not divide keeps, through ``models/paged``'s accessors: a slot reads
    its last three rows, oldest first; a row at position 0 reads zeros
    whatever the slot held; padding rows leave the scratch slot zero; a tile
    goes on from the tile before it, from zeros or from its slot, and leaves
    the three rows before its first invalid one; no other layer's row
    moves."""
    mod, tiny, folds, width = WINDOW_FAMILIES[family]
    cfg = folds if form == "folded" else tiny
    w, k1, s = width(cfg), cfg.conv_kernel - 1, 5
    leaf = mod.init_paged_cache(cfg, 9, 8, jnp.float32, num_slots=s)[SLOTS]["conv"]
    n_l = leaf.shape[0]
    r = 8 if form == "folded" else 1
    assert w % (8 * 128) == (0 if form == "folded" else w)
    assert leaf.shape == (n_l, s, k1 * r, w // r) and not leaf.any()
    # the same bytes a slot whatever the form (``state_bytes_per_slot``)
    assert int(np.prod(leaf.shape[2:])) == k1 * w
    # bfloat16 tiles are 16 rows: the cells' leaves
    assert paged.init_window_leaf(10, 129, 3, 12288, jnp.bfloat16).shape \
        == (10, 129, 48, 768)
    assert paged.init_window_leaf(5, 129, 3, 10240, jnp.bfloat16).shape \
        == (5, 129, 48, 640)
    assert paged.init_window_leaf(2, 5, 3, 96, jnp.bfloat16).shape == (2, 5, 3, 96)

    rng = np.random.default_rng(0)
    merged = leaf.reshape((n_l * s,) + leaf.shape[2:])
    layer, scratch = n_l - 1, s - 1
    slots = np.array([0, 2, scratch, scratch])         # two padding rows
    rows = jnp.asarray(slots + layer * s)
    real = jnp.asarray(slots != scratch)
    held = {0: np.zeros((k1, w), np.float32), 2: np.zeros((k1, w), np.float32)}
    for step in range(5):
        new = rng.standard_normal((4, w)).astype(np.float32)
        # slot 2 is taken over by a new request at step 3: position 0 again
        fresh = np.array([step == 0, step in (0, 3), False, False])
        win, merged = paged.decode_windows(merged, rows, jnp.asarray(new),
                                           jnp.asarray(fresh) & real, real)
        assert win.shape == (4, k1 + 1, r, w // r)
        win = np.asarray(win).reshape(4, k1 + 1, w)
        now = np.asarray(paged.read_windows(merged, rows, w)).reshape(4, k1, w)
        for i, slot in enumerate(slots[:2]):
            before = np.zeros((k1, w)) if fresh[i] else held[slot]
            np.testing.assert_array_equal(win[i], np.vstack([before, new[i:i + 1]]))
            held[slot] = win[i, 1:]
            np.testing.assert_array_equal(now[i], held[slot])
        assert not now[2:].any()                       # the scratch slot
    assert held[0].any() and held[2].any()

    # a 13-token prompt of slot 1 as two tiles of 8 (the second has 5 valid
    # rows) beside a padding tile: only the slot's last tile writes the slot
    tiles = rng.standard_normal((3, 8, w)).astype(np.float32)
    t_rows = jnp.asarray(np.array([1, 1, scratch]) + layer * s)
    write = jnp.asarray([False, True, False])
    win, merged = paged.tile_windows(
        merged, t_rows, jnp.where(write, t_rows, scratch + layer * s),
        jnp.asarray(tiles), jnp.asarray([False, True, False]),
        jnp.asarray([True, False, True]), write, jnp.asarray([8, 5, 0]))
    win = np.asarray(win)
    assert win.shape == (3, k1 + 8, w) and not win[0, :k1].any()
    np.testing.assert_array_equal(win[1, :k1], tiles[0, 8 - k1:])
    np.testing.assert_array_equal(win[:, k1:], tiles)
    # its next tile, in a later step, goes on from what the slot holds
    win, _ = paged.tile_windows(
        merged, t_rows[:1], t_rows[:1], jnp.asarray(tiles[2:]),
        jnp.asarray([False]), jnp.asarray([False]), jnp.asarray([True]),
        jnp.asarray([8]))
    np.testing.assert_array_equal(np.asarray(win)[0, :k1], tiles[1, 5 - k1:5])
    out = np.asarray(merged).reshape(n_l, s, k1, w)
    np.testing.assert_array_equal(out[layer, 0], held[0])
    np.testing.assert_array_equal(out[layer, 1], tiles[1, 5 - k1:5])
    np.testing.assert_array_equal(out[layer, 2], held[2])
    assert not out[layer, 3:].any() and not out[:layer].any()


def test_both_window_forms_serve_the_same_logits():
    """A width that folds, served with the leaf as ``init_paged_cache``
    builds it and with the leaf in rows (the accessors read the form off the
    array): the same logits to the last bit, the same windows left behind,
    and the reference's logits."""
    params = kimi_linear.init_params(FOLDED, jax.random.PRNGKey(2))
    rng = np.random.default_rng(3)
    prompts = {uid: rng.integers(0, FOLDED.vocab_size, n).tolist()
               for uid, n in enumerate([5, 19, 13, 3])}
    engines = [_engine(params, cfg=FOLDED) for _ in range(2)]
    conv = engines[1].cache[SLOTS]["conv"]
    k1, w = FOLDED.conv_kernel - 1, 3 * FOLDED.kda_width
    assert conv.shape[2:] == (k1 * 8, w // 8)
    engines[1].cache = {**engines[1].cache, SLOTS: {
        **engines[1].cache[SLOTS],
        "conv": jnp.zeros(conv.shape[:2] + (k1, w), conv.dtype)}}
    folded, in_rows = (_serve(eng, prompts, 4) for eng in engines)
    assert folded.keys() == in_rows.keys() and len(folded) == 4 * 4
    for key, row in folded.items():
        np.testing.assert_array_equal(row, in_rows[key])
    a, b = (np.asarray(eng.cache[SLOTS]["conv"]) for eng in engines)
    assert a.shape[2:] == (k1 * 8, w // 8) and b.shape[2:] == (k1, w)
    np.testing.assert_array_equal(a.reshape(b.shape), b)
    assert b[:, :-1].any() and not b[:, -1].any()
    for uid, prompt in prompts.items():
        generated = engines[0].get_request(uid).generated
        want = _reference_rows(FOLDED, params, prompt + generated)
        for g in range(4):
            np.testing.assert_allclose(folded[(uid, g)],
                                       want[len(prompt) + g - 1], atol=ATOL)
