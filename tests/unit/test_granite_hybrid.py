"""``models/granite_hybrid.py`` at a small size on the CPU, seeded weights: what
is served (prefill, then decode, through the paged pool AND the slot state)
against the plain reference ``benchmark/reference/granite_hybrid.py``; one
rank's share of the experts; ``n_groups`` 1 through the decode kernel; the
stack as runs; what the new span arguments and counters say.

Logits are compared, not tokens. Tolerance 2e-5 (float32 everywhere here): the
program runs a prompt as chunks (matmuls inside a chunk, the state carried
between them) and the reference as a scan over tokens, so the same sums are
taken in another order; observed differences are under 1e-7 on logits of
magnitude 0.04 (deviation 0.01). The tiny preset keeps ``n_groups`` 1, the four
multipliers at their published values and the tied head: a missing multiplier
moves a logit by 10 tolerances and more
(``test_each_multiplier_is_in_the_logits``).
"""

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
from deepspeed_tpu.models import experts, granite_hybrid, mamba2, nemotron_h
from deepspeed_tpu.models.paged import SLOTS, scan_runs_paged

REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
ATOL = 2e-5


def _reference():
    spec = importlib.util.spec_from_file_location(
        "reference_granite_hybrid",
        os.path.join(REPO, "benchmark", "reference", "granite_hybrid.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


REF = _reference()
# m m a m m m: 6 of 12 experts held, top-4, one group
CFG = granite_hybrid.GraniteHybridConfig.tiny()


@pytest.fixture(scope="module")
def params():
    return granite_hybrid.init_params(CFG, jax.random.PRNGKey(1))


def _engine(params, device_state=False, cfg=CFG, **sizes):
    rc = RaggedConfig(**{**dict(
        max_tokens_per_step=32, max_seqs=4, block_size=8, num_blocks=33,
        max_blocks_per_seq=8, prefill_tile=8, device_state=device_state),
        **sizes})
    return RaggedInferenceEngine(
        lambda ctx: granite_hybrid.build(cfg, ctx=ctx), rc, dtype=jnp.float32,
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
    # six requests over four slots: decode rows beside tiles, slots reused,
    # padding rows on the scratch slot (three decoders in a bucket of four)
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
        np.asarray(granite_hybrid.forward(CFG, params, ids[None])[0]),
        np.asarray(REF.forward(CFG, params, ids)), atol=ATOL)


@pytest.mark.parametrize("field,other", [
    ("embedding_multiplier", 1.0), ("attention_multiplier", 0.25),
    ("residual_multiplier", 1.0), ("logits_scaling", 1.0)])
def test_each_multiplier_is_in_the_logits(field, other):
    """The program with one of the four multipliers at another value (0.25 is
    ``head_dim ** -0.5`` here) is not the reference: the tolerance above would
    not pass a forward that left one out. On a stack that ends in its
    attention layer, where the seeded draw gives that layer the largest
    projections."""
    late = dict(layer_types=("mamba", "mamba", "attention"))
    cfg = granite_hybrid.GraniteHybridConfig.tiny(**late)
    params = granite_hybrid.init_params(cfg, jax.random.PRNGKey(1))
    ids = jnp.asarray(_prompts([29], seed=6)[0])
    wrong = granite_hybrid.GraniteHybridConfig.tiny(**late, **{field: other})
    got = np.asarray(granite_hybrid.forward(wrong, params, ids[None])[0])
    want = np.asarray(REF.forward(cfg, params, ids))
    assert np.abs(got - want).max() > 10 * ATOL


# --------------------------------------------------- one rank's share
def test_two_ranks_parts_add_up_to_the_uncut_layer():
    """The share test of the model-configs guide, section 4: an expert block
    with all 12 experts against the two ranks' blocks of 6 experts each
    (experts 0-5, 6-11), the same router. The ranks' routed parts add up and
    the shared MLP counts once: the uncut block of the reference; and with
    the mixer (whole on every rank, counted once) the uncut LAYER."""
    whole = granite_hybrid.GraniteHybridConfig.tiny(experts_held=None)
    # the last layer: the seeded draw gives it the largest projections
    run = granite_hybrid.init_params(whole, jax.random.PRNGKey(2))["runs"][-1]
    full = jax.tree_util.tree_map(lambda a: a[-1], run)
    x = jnp.asarray(np.random.default_rng(1).standard_normal(
        (23, whole.hidden_size)), jnp.float32)
    r = whole.residual_multiplier
    # the mixer sublayer is the same on either rank and in the reference
    after_mixer = x + r * REF._mamba(
        whole, REF._rms(x, full["norm"], whole.rms_norm_eps), full["mix"],
        jnp.float32)
    h = REF._rms(after_mixer, full["ffn_norm"], whole.rms_norm_eps)
    ffn = full["ffn"]
    routed = shared = 0.0
    for rank in range(2):
        cfg = granite_hybrid.GraniteHybridConfig.tiny(expert_rank=rank)
        lp = {**ffn, **{w: ffn[w][6 * rank:6 * rank + 6]
                        for w in ("w_gate", "w_up", "w_down")}}
        part, shared = granite_hybrid.ffn_parts(cfg, h, lp,
                                                experts.routed_experts)
        # a rank's own block is what the reference computes for that rank
        np.testing.assert_allclose(np.asarray(part + shared),
                                   np.asarray(REF._ffn(cfg, h, lp, jnp.float32)),
                                   atol=ATOL)
        assert np.abs(np.asarray(part)).max() > 100 * ATOL
        routed = routed + part
    np.testing.assert_allclose(np.asarray(routed + shared),
                               np.asarray(REF._ffn(whole, h, ffn, jnp.float32)),
                               atol=ATOL)
    np.testing.assert_allclose(
        np.asarray(after_mixer + r * (routed + shared)),
        np.asarray(REF._layer(whole, "mamba", x, full, jnp.float32)), atol=ATOL)


# ------------------------------------------------------------ the kernel
def test_ssm_decode_kernel_at_one_group_is_the_xla_form():
    """``n_groups`` 1: ``bt`` / ``ct`` are ``[T, N, 1]`` and the kernel's one
    group is all the lanes (interpret mode here; ``test_compile_tpu.py``
    compiles the shape for the chip)."""
    from deepspeed_tpu.ops.pallas.ssm import ssm_decode, ssm_decode_xla

    k = jax.random.split(jax.random.PRNGKey(0), 5)
    rows_n, n, hp, t = 10, 16, 256, 4
    state = jax.random.normal(k[0], (rows_n, n, hp))
    rows = jnp.asarray([3, 7, 1, 9], jnp.int32)
    da = jax.random.uniform(k[1], (t, hp))
    dtx = jax.random.normal(k[2], (t, hp))
    bt = jax.random.normal(k[3], (t, n, 1))
    ct = jax.random.normal(k[4], (t, n, 1))
    got_s, got_y = ssm_decode(state, rows, da, dtx, bt, ct)
    want_s, want_y = ssm_decode_xla(state, rows, da, dtx, bt, ct)
    np.testing.assert_allclose(np.asarray(got_s), np.asarray(want_s), atol=1e-5)
    np.testing.assert_allclose(np.asarray(got_y), np.asarray(want_y), atol=1e-4)
    untouched = [0, 2, 4, 5, 6, 8]
    np.testing.assert_array_equal(np.asarray(got_s)[untouched],
                                  np.asarray(state)[untouched])


# ------------------------------------------------- the in-projection's parts
def _plain_split(cfg, h, lp):
    """What ``mamba2.split`` computes, written as the columns of the one
    product with nothing between them and their readers."""
    di, cw = cfg.d_inner, cfg.conv_width
    zxbcdt = h @ lp["w_in"].astype(h.dtype)
    dt = jax.nn.softplus(zxbcdt[..., di + cw:].astype(jnp.float32)
                         + lp["dt_bias"].astype(jnp.float32))
    return zxbcdt[..., :di], zxbcdt[..., di:di + cw], dt


@pytest.mark.parametrize("how", ["jit", "grad"])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["f32", "bf16"])
@pytest.mark.parametrize("family", ["granite", "nemotron"])
def test_split_hands_out_the_columns_of_the_one_product(family, dtype, how):
    """``mamba2.split`` ties its three parts so that the compiler keeps
    ``z`` and not the whole product across the layer (PR 54;
    ``test_compile_tpu.test_mamba2_step_makes_the_in_projection_once``). The
    tie is no arithmetic: the parts are the columns of ``h @ W_in`` (``dt``
    their softplus) bit for bit, at one group and at two, in the dtype
    served and the dtype trained, and so are the gradients through them
    (``mamba2.sequence`` trains through ``split``)."""
    mod, cfg = {"granite": (granite_hybrid, CFG),
                "nemotron": (nemotron_h, nemotron_h.NemotronHConfig.tiny())
                }[family]
    tree = mod.init_params(cfg, jax.random.PRNGKey(3))
    stack = tree["runs"][0]["mix"] if family == "granite" else tree["mamba"]
    lp = jax.tree_util.tree_map(lambda a: a[1].astype(dtype), stack)
    keys = jax.random.split(jax.random.PRNGKey(4), 4)
    h = jax.random.normal(keys[0], (20, cfg.hidden_size), jnp.float32).astype(
        dtype)
    widths = (cfg.d_inner, cfg.conv_width, cfg.mamba_num_heads)
    assert lp["w_in"].shape == (cfg.hidden_size, sum(widths))
    if how == "jit":
        got = jax.jit(lambda h, lp: mamba2.split(cfg, h, lp))(h, lp)
        want = jax.jit(lambda h, lp: _plain_split(cfg, h, lp))(h, lp)
        assert [a.shape[-1] for a in got] == list(widths)
        assert [a.dtype for a in got] == [dtype, dtype, jnp.float32]
    else:
        weights = [jax.random.normal(k, (20, w), jnp.float32)
                   for k, w in zip(keys[1:], widths)]

        def loss(split, h, lp):
            return sum(jnp.sum(part.astype(jnp.float32) * w)
                       for part, w in zip(split(cfg, h, lp), weights))

        got, want = (jax.jit(jax.grad(functools.partial(loss, split),
                                      argnums=(0, 1)))(h, lp)
                     for split in (mamba2.split, _plain_split))
        assert float(jnp.abs(got[1]["w_in"].astype(jnp.float32)).sum()) > 0
        assert float(jnp.abs(got[1]["dt_bias"].astype(jnp.float32)).sum()) > 0
    for a, b in zip(jax.tree_util.tree_leaves(got),
                    jax.tree_util.tree_leaves(want)):
        np.testing.assert_array_equal(np.asarray(a.astype(jnp.float32)),
                                      np.asarray(b.astype(jnp.float32)))


# -------------------------------------------------------------- the stack
def test_the_stack_is_runs_and_the_mixer_is_shared():
    assert CFG.runs == [("mamba", 2), ("attention", 1), ("mamba", 3)]
    published = granite_hybrid.GraniteHybridConfig()
    assert published.runs == [("mamba", 5), ("attention", 1)] + \
        [("mamba", 9), ("attention", 1)] * 3 + [("mamba", 4)]
    assert published.head_dim == 128 and published.q_scale == pytest.approx(
        128 ** -0.5)
    # ONE Mamba-2: nemotron_h's layer calls the module this family calls
    assert nemotron_h.mamba2 is granite_hybrid.mamba2 is mamba2
    with pytest.raises(ValueError, match="layer_types"):
        granite_hybrid.GraniteHybridConfig.tiny(num_layers=5)
    with pytest.raises(ValueError, match="experts_held"):
        granite_hybrid.GraniteHybridConfig.tiny(experts_held=5)


def test_scan_runs_counts_the_layers_of_each_kind(params):
    cache = granite_hybrid.init_paged_cache(CFG, 9, 8, jnp.float32, num_slots=5)
    fn = lambda x, lp, pool, address: (x, pool)  # noqa: E731
    with pytest.raises(ValueError, match="slot leaves hold 5 layers"):
        scan_runs_paged([("slot", fn, {"w": jnp.zeros((4, 1))}),
                         ("block", fn, {"w": jnp.zeros((1, 1))})],
                        jnp.zeros((2, 4)), cache, jnp.zeros((5, 2), jnp.int32))


# ------------------------------------------------------------ the engine
def test_engine_accounts_blocks_and_slots_apart(params, engine_of):
    eng = engine_of()
    assert eng.kv_bytes_per_token() == REF.kv_bytes_per_token(CFG, 4)
    assert eng.state_bytes_per_slot() == REF.state_bytes_per_slot(CFG, 4)
    assert granite_hybrid.num_params(CFG) == REF.num_params(CFG) == sum(
        a.size for a in jax.tree_util.tree_leaves(params))
    assert eng._dec_buckets == [4]      # one decode bucket: max_seqs


def test_dispatch_span_says_padding_rows_and_slot_resets(params, monkeypatch):
    """``engine/dispatch`` of a family with slot state: ``state_pad_rows``,
    the rows of the decode bucket past the real ones, and ``slot_resets``, the
    sequences whose first tile the step carries; the same on the two
    ``/metrics`` counters. Three requests into a bucket of four: every decode
    step pads a row at least, and each request resets its slot once."""
    from deepspeed_tpu.inference import ragged

    seen = []
    real = ragged.span
    monkeypatch.setattr(ragged, "span", lambda name, **a: (
        seen.append(a) if name == "engine/dispatch" else None, real(name, **a))[1])
    telemetry.configure(enabled=True)
    try:
        eng = _engine(params, device_state=True)
        for uid, prompt in _prompts([19, 5, 9], seed=8).items():
            eng.put(uid, prompt, max_new_tokens=4)
        eng.generate_all()
        metrics = telemetry.snapshot()["metrics"]
    finally:
        telemetry.configure(enabled=False)
    assert seen and all(a["state_kind"] == "mamba2" for a in seen)
    assert sum(a["slot_resets"] for a in seen) == 3
    per_slot = 2 * eng.state_bytes_per_slot()
    decode_only = [a for a in seen if a["program"].endswith("_t0")]
    assert decode_only and all(
        a["state_pad_rows"] == 4 - a["dec_state_bytes"] // per_slot >= 1
        for a in decode_only)
    # a step with no decode row has no bucket and pads none
    assert all(a["state_pad_rows"] == 0 for a in seen
               if a["program"].startswith("ragged_step_d0_"))

    def total(name):
        return sum(s["value"] for s in metrics[name]["series"]
                   if s["labels"].get("state_kind") == "mamba2")

    assert total("inference_slot_resets_total") == 3
    assert total("inference_slot_state_pad_rows_total") == sum(
        a["state_pad_rows"] for a in seen)
