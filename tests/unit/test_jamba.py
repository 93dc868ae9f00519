"""``models/jamba.py`` at a small size on the CPU, seeded weights: what is
served (prefill, then decode, through the paged pool AND the slot state)
against the plain reference ``benchmark/reference/jamba.py``; the two kernels
of ``ops/pallas/selscan.py`` against their XLA forms; 20 query heads on one
K/V head through the paged kernels; the stack as runs; the parameter count
term by term; what the span arguments and counters say.

Logits are compared, not tokens. Tolerance 2e-5 (float32 everywhere here): the
program and the reference run the same recurrence token by token, but the
program carries the state between a prompt's tiles and steps and folds the
convolution's channels, so sums are taken in another order; observed
differences are under 1e-6 on logits of magnitude 1 (deviation 0.3). A Mamba-2
decay (``A`` averaged over the state index), a dropped inner norm and
attention one layer early each move a logit by 100 tolerances and more
(``test_the_model_one_line_away_is_not_the_reference``).
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

from deepspeed_tpu import telemetry
from deepspeed_tpu.inference.ragged import RaggedConfig, RaggedInferenceEngine
from deepspeed_tpu.models import granite_hybrid, jamba, mamba1, mamba2
from deepspeed_tpu.models.paged import SLOTS
from deepspeed_tpu.ops.pallas import selscan

REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
ATOL = 2e-5


def _reference():
    spec = importlib.util.spec_from_file_location(
        "reference_jamba",
        os.path.join(REPO, "benchmark", "reference", "jamba.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


REF = _reference()
# m m a m m m: one K/V head under four query heads, a state of 8 x 128
CFG = jamba.JambaConfig.tiny()


@pytest.fixture(scope="module")
def params():
    return jamba.init_params(CFG, jax.random.PRNGKey(1))


def _engine(params, device_state=False, cfg=CFG, **sizes):
    rc = RaggedConfig(**{**dict(
        max_tokens_per_step=32, max_seqs=4, block_size=8, num_blocks=33,
        max_blocks_per_seq=8, prefill_tile=8, device_state=device_state),
        **sizes})
    return RaggedInferenceEngine(
        lambda ctx: jamba.build(cfg, ctx=ctx), rc, dtype=jnp.float32,
        params=params)


@pytest.fixture(scope="module")
def engine_of(params):
    """``engine_of(**sizes)``: the module's ONE engine of those sizes, as new
    each time it is asked for (``shared.py``)."""
    return one_engine_each(functools.partial(_engine, params))


def _prompts(lengths, seed=0):
    rng = np.random.default_rng(seed)
    return {uid: rng.integers(0, CFG.vocab_size, n).tolist()
            for uid, n in enumerate(lengths)}


# the longest request served here is 37 + 6 tokens
_reference_rows = over_one_length(REF.forward, 48)

# case -> (engine sizes, prompt lengths, new tokens)
SERVED = {
    # 16 a step: 16 + 16 + 5, a partial last tile, the state carried over steps
    "prompt_chunked_over_steps": ({"max_tokens_per_step": 16}, [37], 4),
    # six requests over four slots: decode rows beside tiles, a prompt's tiles
    # carried from tile to tile, slots reused, padding rows on the scratch slot
    "mixed_steps": ({}, [5, 19, 37, 9, 26, 3], 6),
    # one slot: the second request starts from zeros where the first ended
    "slot_reused": ({"max_seqs": 1}, [11, 7], 5),
}


def _serve(eng, prompts, new_tokens):
    """Run the requests to their end; ``{(uid, g): logits row}`` of every
    emission of the host-staged path (generated token ``g`` of ``uid``)."""
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
            assert steps < 500
    finally:
        del eng._emit_tokens        # the engine is shared: the method again
    return rows


@pytest.mark.parametrize("case", SERVED)
def test_served_logits_match_the_reference(params, engine_of, case):
    sizes, lengths, new_tokens = SERVED[case]
    eng = engine_of(**sizes)
    prompts = _prompts(lengths)
    rows = _serve(eng, prompts, new_tokens)
    for uid, prompt in prompts.items():
        generated = eng.get_request(uid).generated
        assert len(generated) == new_tokens
        # the seeded draw (``init_params``): the tied head does not hand a
        # request its own last token back over and over
        assert len(set(generated)) > 1
        want = _reference_rows(CFG, params, prompt + generated)
        for g in range(new_tokens):
            np.testing.assert_allclose(
                rows[(uid, g)], want[len(prompt) + g - 1], atol=ATOL,
                err_msg=f"{case}: request {uid}, generated token {g}")
    slots = eng.cache[SLOTS]
    assert not np.asarray(slots["ssm"][:, -1]).any()
    assert not np.asarray(slots["conv"][:, -1]).any()
    assert eng.allocator.free_blocks == eng.cfg.num_blocks - 1


def test_a_reused_slot_serves_a_fresh_ones_logits(params, engine_of):
    """One slot, the same prompt twice, another request between: the second
    time the slot held what the other request left, and the logits are those
    of the first time to the last bit."""
    eng = engine_of(max_seqs=1)
    prompt = _prompts([13], seed=4)[0]
    rows = _serve(eng, {0: prompt, 1: _prompts([21], seed=5)[0], 2: prompt}, 4)
    assert eng.get_request(0).generated == eng.get_request(2).generated
    for g in range(4):
        np.testing.assert_array_equal(rows[(0, g)], rows[(2, g)])


@pytest.mark.parametrize("case", ["mixed_steps", "slot_reused"])
def test_device_resident_path_serves_the_reference_tokens(params, engine_of,
                                                          case):
    """The device-resident step (slot rows, picks on the device) against the
    reference's greedy tokens, teacher-forced on what was served."""
    sizes, lengths, new_tokens = SERVED[case]
    eng = engine_of(device_state=True, **sizes)
    prompts = _prompts(lengths)
    _serve(eng, prompts, new_tokens)
    for uid, prompt in prompts.items():
        generated = eng.get_request(uid).generated[:new_tokens]
        want = _reference_rows(CFG, params, prompt + generated)
        greedy = want.argmax(-1)[len(prompt) - 1:len(prompt) + new_tokens - 1]
        assert generated == greedy.tolist(), (case, uid)


def test_plain_forward_is_the_reference(params):
    ids = jnp.asarray(_prompts([41], seed=3)[0])
    np.testing.assert_allclose(
        np.asarray(jamba.forward(CFG, params, ids[None])[0]),
        np.asarray(REF.forward(CFG, params, ids)), atol=ATOL)


def _wrong_a(params):
    """``A`` averaged over the state index: a Mamba-2 decay, one a channel."""
    def fix(path, leaf):
        if getattr(path[-1], "key", None) != "a_log":
            return leaf
        a = jnp.exp(leaf).mean(axis=1, keepdims=True)
        return jnp.broadcast_to(jnp.log(a), leaf.shape)
    return jax.tree_util.tree_map_with_path(fix, params)


@pytest.mark.parametrize("fault", ["mamba2_decay", "no_inner_norms",
                                   "attention_a_layer_early"])
def test_the_model_one_line_away_is_not_the_reference(params, monkeypatch,
                                                      fault):
    """The program with one of the family's distinctive lines changed is not
    the reference by 100 tolerances: the decay one value a channel (what the
    chunk form could run), the bottleneck's three RMSNorms dropped, the
    attention layer at ``i mod period == offset - 1``."""
    ids = jnp.asarray(_prompts([29], seed=6)[0])
    want = np.asarray(REF.forward(CFG, params, ids))
    cfg, served = CFG, params
    if fault == "mamba2_decay":
        served = _wrong_a(params)
    elif fault == "no_inner_norms":
        monkeypatch.setattr(mamba1, "rmsnorm", lambda x, w, eps: x)
    else:
        # the stacks of the shifted config hold other layer counts, so they
        # are drawn anew from the same key: the model a builder who read the
        # rule one off would have served
        cfg = dataclasses.replace(CFG, attn_layer_offset=CFG.attn_layer_offset - 1)
        served = jamba.init_params(cfg, jax.random.PRNGKey(1))
    got = np.asarray(jamba.forward(cfg, served, ids[None])[0])
    assert np.abs(got - want).max() > 100 * ATOL


# ------------------------------------------------------------ the kernels
def _decode_args(t=6, rows_n=11, n=16, ch=256):
    k = jax.random.split(jax.random.PRNGKey(0), 6)
    state = jax.random.normal(k[0], (rows_n, n, ch)).at[10].set(0.0)
    rows = jnp.asarray([3, 7, 1, 10, 10, 5], jnp.int32)
    fresh = jnp.asarray([0, 1, 0, 0, 0, 0], bool)
    # rows 3 and 4 are padding rows: the scratch slot (10), dt = 0
    dt = jax.nn.softplus(jax.random.normal(k[1], (t, ch)) - 3).at[3:5].set(0.0)
    x = jax.random.normal(k[2], (t, ch))
    a = -jnp.exp(jax.random.normal(k[3], (n, ch)))
    return (state, rows, fresh, dt, x, a, jax.random.normal(k[4], (t, n)),
            jax.random.normal(k[5], (t, n)))


def test_selscan_decode_kernel_is_the_xla_form():
    """Interpret mode here; ``test_compile_tpu.py`` compiles the cell's shape
    for the chip. 1e-5 of values of magnitude 4: the kernel and XLA order the
    same float32 operations alike, but for the sum over the state index."""
    args = _decode_args()
    got_s, got_y = selscan.selscan_decode(*args, impl="pallas", interpret=True)
    want_s, want_y = selscan.selscan_decode_xla(*args)
    np.testing.assert_allclose(np.asarray(got_s), np.asarray(want_s), atol=1e-5)
    np.testing.assert_allclose(np.asarray(got_y), np.asarray(want_y), atol=1e-5)
    state = np.asarray(args[0])
    untouched = [0, 2, 4, 6, 8, 9, 10]    # the scratch slot among them
    np.testing.assert_array_equal(np.asarray(got_s)[untouched], state[untouched])
    # the fresh row started from zeros: what it holds is its own feed alone
    _, _, _, dt, x, _, b, _ = args
    np.testing.assert_allclose(
        np.asarray(got_s)[7], np.asarray(b[1][:, None] * (dt[1] * x[1])),
        atol=1e-6)


@pytest.mark.parametrize("rows", [8, 24, 32])
def test_selscan_tile_kernel_is_the_xla_form(rows):
    """Four tiles: a slot's two (the second goes on where the first ended and
    writes the slot), a slot's only tile with rows past its valid ones, a
    padding tile. ``rows`` under, over and at a multiple of the kernel's
    unrolled group of 16."""
    k = jax.random.split(jax.random.PRNGKey(1), 6)
    n, ch, n_i = 16, 256, 4
    state = jax.random.normal(k[0], (11, n, ch)).at[10].set(0.0)
    a = -jnp.exp(jax.random.normal(k[1], (n, ch)))
    tiles = dict(
        rows=jnp.asarray([2, 2, 4, 10], jnp.int32),
        rows_w=jnp.asarray([10, 2, 4, 10], jnp.int32),
        fresh=jnp.asarray([1, 0, 0, 1], bool),
        cont=jnp.asarray([0, 1, 0, 0], bool),
        write=jnp.asarray([0, 1, 1, 0], bool))
    dt = jax.nn.softplus(jax.random.normal(k[2], (n_i, rows, ch)) - 3)
    dt = dt.at[2, rows // 2 + 1:].set(0.0).at[3].set(0.0)
    x = jax.random.normal(k[3], (n_i, rows, ch))
    b = jax.random.normal(k[4], (n_i, rows, n))
    c = jax.random.normal(k[5], (n_i, rows, n))
    got_s, got_y = selscan.selscan_tile(state, *tiles.values(), dt, x, a, b, c,
                                        impl="pallas", interpret=True)
    want_s, want_y = selscan.selscan_tile_xla(state, *tiles.values(), dt, x, a,
                                              b, c)
    np.testing.assert_allclose(np.asarray(got_s), np.asarray(want_s), atol=1e-5)
    np.testing.assert_allclose(np.asarray(got_y), np.asarray(want_y), atol=1e-5)
    assert not np.asarray(got_s)[10].any()
    # and the tiles ARE the recurrence token by token: slot 2's two tiles
    # against the decode form a row at a time
    s = jnp.zeros((1, n, ch))
    for i in (0, 1):
        for t in range(rows):
            s, y = selscan.selscan_decode_xla(
                s, jnp.zeros((1,), jnp.int32), jnp.zeros((1,), bool),
                dt[i, t][None], x[i, t][None], a, b[i, t][None], c[i, t][None])
            np.testing.assert_allclose(np.asarray(y[0]),
                                       np.asarray(got_y[i, t]), atol=2e-5)
    np.testing.assert_allclose(np.asarray(s[0]), np.asarray(got_s)[2], atol=2e-5)


def test_the_served_kernels_give_the_xla_forms_logits(params, monkeypatch):
    """The engine with both kernels in interpret mode (``impl`` has no option
    on the serving path: ``_on_chip`` is what a chip changes) serves the
    logits the XLA forms serve."""
    prompts = _prompts([19, 5, 11], seed=7)
    want = _serve(_engine(params), prompts, 3)
    monkeypatch.setattr(selscan, "_on_chip", lambda impl: True)
    got = _serve(_engine(params), prompts, 3)
    assert got.keys() == want.keys()
    for key in want:
        np.testing.assert_allclose(got[key], want[key], atol=ATOL)


@pytest.mark.parametrize("kernel", ["paged_decode", "tiled_prefill"])
def test_twenty_query_heads_on_one_kv_head_through_the_paged_kernels(kernel):
    """The published attention geometry (20 query heads of 128 on ONE K/V
    head: a pool row is one lane tile) through the two paged kernels in
    interpret mode against the XLA gather."""
    from deepspeed_tpu.ops import attention

    hq, d, block, table = 20, 128, 8, 4
    k = jax.random.split(jax.random.PRNGKey(2), 3)
    kc = jax.random.normal(k[0], (9, block, d))
    vc = jax.random.normal(k[1], (9, block, d))
    bt = jnp.asarray([[1, 2, 3, 4], [5, 6, 7, 8], [0, 0, 0, 0]], jnp.int32)
    if kernel == "paged_decode":
        q = jax.random.normal(k[2], (2, hq, d))
        slots, pos = jnp.asarray([0, 1], jnp.int32), jnp.asarray([21, 9], jnp.int32)
        got = attention.paged_attention(q, kc, vc, slots, pos, bt, impl="pallas")
        want = attention.paged_attention(q, kc, vc, slots, pos, bt, impl="xla")
    else:
        tile = 8
        q = jax.random.normal(k[2], (2 * tile, hq, d))
        ts, tp = jnp.asarray([0, 1], jnp.int32), jnp.asarray([16, 0], jnp.int32)
        tv = jnp.asarray([8, 5], jnp.int32)
        got = attention.ragged_prefill_attention(q, kc, vc, ts, tp, tv, bt, tile,
                                                 impl="pallas")
        want = attention.ragged_prefill_attention(q, kc, vc, ts, tp, tv, bt,
                                                  tile, impl="xla")
        got, want = got[:tile + 5], want[:tile + 5]   # rows past valid: any
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-5)


# -------------------------------------------------------------- the stack
def test_the_stack_is_runs_and_the_layer_rule_is_the_published_one():
    assert CFG.runs == [("mamba", 2), ("attention", 1), ("mamba", 3)]
    published = jamba.JambaConfig()
    assert [i for i, k in enumerate(published.layer_types)
            if k == "attention"] == [7, 21]
    assert published.runs == [("mamba", 7), ("attention", 1), ("mamba", 13),
                              ("attention", 1), ("mamba", 6)]
    assert list(published.layer_types) == REF.layer_types(published)
    assert published.head_dim == 128 and published.d_inner == 5120
    # the attention path is Granite's, the convolution Mamba-2's
    assert jamba.mamba1.causal_conv is mamba2.causal_conv
    assert jamba.JambaConfig().q_scale == 1.0  # paged.nope_attention_ragged
    with pytest.raises(NotImplementedError, match="routed FFNs"):
        jamba.JambaConfig.tiny(num_experts=16)
    with pytest.raises(ValueError, match="attn_layer_offset"):
        jamba.JambaConfig.tiny(attn_layer_offset=4)


def test_the_published_parameter_count_term_by_term():
    cfg = jamba.JambaConfig()
    mamba = REF.mixer_params(cfg, "mamba")
    assert mamba == {"in_proj": 26_214_400, "conv": 25_600,
                     "x_proj": 983_040, "dt_proj": 824_320, "a_log": 81_920,
                     "d": 5_120, "inner_norms": 192, "out_proj": 13_107_200}
    assert sum(mamba.values()) == mamba1.mixer_param_count(cfg) == 41_241_792
    assert sum(REF.mixer_params(cfg, "attention").values()) == 13_762_560
    assert REF.layer_params(cfg, "mamba") == 104_161_472
    assert REF.layer_params(cfg, "attention") == 76_682_240
    assert jamba.num_params(cfg) == REF.num_params(cfg) == 3_029_337_472 == (
        26 * 104_161_472 + 2 * 76_682_240 + 65_536 * 2_560 + 2_560)
    assert REF.state_bytes_per_slot(cfg) == 9_318_400
    assert REF.kv_bytes_per_token(cfg) == 1_024
    # and the tree the program draws has exactly those leaves
    shapes = jax.eval_shape(lambda: jamba.init_params(cfg, jax.random.PRNGKey(0)))
    assert sum(int(np.prod(a.shape)) for a in
               jax.tree_util.tree_leaves(shapes)) == 3_029_337_472


# ------------------------------------------------------------ the engine
def test_engine_accounts_blocks_and_slots_apart(params, engine_of):
    eng = engine_of()
    assert eng.kv_bytes_per_token() == REF.kv_bytes_per_token(CFG, 4)
    assert eng.state_bytes_per_slot() == REF.state_bytes_per_slot(CFG, 4)
    assert jamba.num_params(CFG) == REF.num_params(CFG) == sum(
        a.size for a in jax.tree_util.tree_leaves(params))
    assert eng._dec_buckets == [4]      # one decode bucket: max_seqs
    assert eng.spec.state_kind == "mamba1"
    # the published width's window leaf is whole half-tiles (24 x 640 a slot)
    leaf = jax.eval_shape(lambda: mamba1.init_slot_leaves(
        jamba.JambaConfig(), 2, 3, jnp.bfloat16))["conv"]
    assert leaf.shape == (2, 3, 24, 640)


@pytest.mark.parametrize("option,match", [
    ({"enable_prefix_cache": True}, "prefix"),
    ({"kv_tier": True, "enable_prefix_cache": True}, "prefix|tier"),
    ({"quant": "int8"}, "quant"),
    ({"prefill_tile": 0}, "tile")])
def test_what_a_prefix_of_blocks_cannot_restore_refuses(params, option, match):
    with pytest.raises((ValueError, NotImplementedError), match=match):
        _engine(params, **option)


def test_dispatch_span_says_the_third_state_kind(params, monkeypatch):
    """``engine/dispatch`` of this family: ``state_kind`` ``"mamba1"``,
    ``state_bytes`` / ``dec_state_bytes`` / ``ssm_prefill_tokens`` and
    ``scan_tiles``, the tiles the step's scan kernel runs over; ``/metrics``
    counts the state's bytes under the same label."""
    from deepspeed_tpu.inference import ragged

    seen = []
    real = ragged.span
    monkeypatch.setattr(ragged, "span", lambda name, **a: (
        seen.append(a) if name == "engine/dispatch" else None, real(name, **a))[1])
    telemetry.configure(enabled=True)
    try:
        eng = _engine(params, device_state=True)
        prompts = _prompts([19, 5, 9], seed=8)
        for uid, prompt in prompts.items():
            eng.put(uid, prompt, max_new_tokens=4)
        eng.generate_all()
        metrics = telemetry.snapshot()["metrics"]
    finally:
        telemetry.configure(enabled=False)
    assert seen and all(a["state_kind"] == "mamba1" for a in seen)
    per_slot = 2 * eng.state_bytes_per_slot()
    for a in seen:
        tiles = int(a["program"].rsplit("_t", 1)[1])
        assert a["scan_tiles"] == a["chunk_tiles"] == tiles
        assert a["state_bytes"] % per_slot == 0 <= a["dec_state_bytes"]
    assert sum(a["ssm_prefill_tokens"] for a in seen) == sum(
        map(len, prompts.values()))
    moved = sum(s["value"] for s in
                metrics["inference_slot_state_bytes_total"]["series"]
                if s["labels"].get("state_kind") == "mamba1")
    assert moved >= sum(a["state_bytes"] for a in seen) > 0
