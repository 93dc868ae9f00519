"""``models/solar_open2.py`` at a small size on the CPU, seeded weights: what is
served (prefill in tiles, then decode, through a K/V pool AND the KDA slot
state) against the plain reference ``benchmark/reference/solar_open2.py``;
``beta`` doubled and not, the ``G`` layer's gate in and out; one rank's share
of the experts; each term of the parameter count at the published widths; the
two KDA kernels in interpret mode at 64 heads against XLA's forms; what a
dispatch span says of the chunk form's work; that the KDA mixer is ONE module
with two users.

Logits are compared, not tokens. Tolerance 2e-4 (float32 everywhere here): the
program runs a prompt as chunks (a triangular solve and matmuls inside a chunk,
the state carried between them) and the reference as a scan over tokens, so the
same sums are taken in another order; observed differences are under 2e-6 on
logits of magnitude 0.6.
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

from deepspeed_tpu.inference.ragged import RaggedConfig, RaggedInferenceEngine
from deepspeed_tpu.models import experts, kda, kimi_linear, paged, solar_open2
from deepspeed_tpu.models.paged import SLOTS
from deepspeed_tpu.ops.pallas.kda import (
    kda_chunk,
    kda_chunk_xla,
    kda_decode,
    kda_decode_xla,
)

REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
ATOL = 2e-4


def _reference():
    spec = importlib.util.spec_from_file_location(
        "reference_solar_open2",
        os.path.join(REPO, "benchmark", "reference", "solar_open2.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


REF = _reference()
CFG = solar_open2.SolarOpen2Config.tiny()   # "GKKK", 4 of 8 experts held


@pytest.fixture(scope="module")
def params():
    return solar_open2.init_params(CFG, jax.random.PRNGKey(1))


def _engine(params, cfg=CFG, device_state=False, **sizes):
    rc = RaggedConfig(**{**dict(
        max_tokens_per_step=32, max_seqs=4, block_size=8, num_blocks=33,
        max_blocks_per_seq=8, prefill_tile=8, device_state=device_state),
        **sizes})
    return RaggedInferenceEngine(lambda ctx: solar_open2.build(cfg, ctx=ctx),
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
    # 16 a step: 16 + 16 + 5, the state carried over a tile AND over steps
    "prompt_chunked_over_steps": ({"max_tokens_per_step": 16}, [37], 4, None),
    # six requests over four slots: decode rows beside tiles, slots reused,
    # and the FIRST tokens after a short prompt (3 and 5 tokens: the
    # convolution's window is not yet full when decoding starts)
    "mixed_steps": ({}, [5, 19, 37, 9, 26, 3], 6, None),
    # one slot: the second request starts from zeros where the first ended
    "slot_reused": ({"max_seqs": 1}, [11, 7], 5, None),
    # positions rewound mid-flight: the state restarts from zeros with a
    # re-prefill from position 0
    "recovered_and_recomputed": ({}, [5, 19, 37, 9], 8, 4),
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
    # the scratch slot is what padding rows and tiles read and write: zero
    # before, zero after
    slots = eng.cache[SLOTS]
    assert not np.asarray(slots["kda"][:, -1]).any()
    assert not np.asarray(slots["conv"][:, -1]).any()
    assert eng.allocator.free_blocks == eng.cfg.num_blocks - 1


@pytest.mark.parametrize("case", ["mixed_steps", "slot_reused"])
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


# ----------------------------------------------- the two switches of the row
VARIANTS = {
    "beta_not_doubled": dict(kda_allow_neg_eigval=False),
    "gate_out": dict(use_gqa_gate=False),
}


@pytest.mark.parametrize("variant", VARIANTS)
def test_each_switch_is_served_as_the_reference_has_it(params, variant):
    """``kda_allow_neg_eigval`` (``beta`` doubled or not) and ``use_gqa_gate``
    (the gate in or out): with the switch the OTHER way the system still
    serves the reference's logits of that configuration, prefill then decode
    through the cache, and those differ from the published configuration's by
    far more than the tolerance: neither switch is inert."""
    cfg = dataclasses.replace(CFG, **VARIANTS[variant])
    own = params
    if variant == "gate_out":   # the tree has no ``w_g`` then
        own = solar_open2.init_params(cfg, jax.random.PRNGKey(1))
        assert "w_g" not in own["lead"][0]["mix"]
        assert "w_g" in params["lead"][0]["mix"]
    prompts = _prompts([19])
    eng = _engine(own, cfg=cfg)
    rows = _serve(eng, prompts, 3)
    _assert_served_is_the_reference(eng, cfg, own, prompts, rows, 3, variant)
    if variant == "beta_not_doubled":
        ids = jnp.asarray(prompts[0])
        as_published = np.asarray(REF.forward(CFG, params, ids))
        other = np.asarray(REF.forward(cfg, params, ids))
        assert np.abs(as_published - other).max() > 50 * ATOL
        assert CFG.kda_beta_scale == 2.0 and cfg.kda_beta_scale == 1.0


def test_the_gate_moves_the_layers_output(params):
    """The ``G`` layer with its gate against the same weights without: the
    gate's pre-activation is about one wide (``GATE_PREACT_STD``), so the
    gate is no constant 0.5 and the output is not half the ungated one."""
    lp = params["lead"][0]["mix"]
    h = jnp.asarray(np.random.default_rng(2).standard_normal(
        (1, 19, CFG.hidden_size)), jnp.float32)
    gated = np.asarray(solar_open2._attention_sequence(CFG, h, lp)[0])
    plain = np.asarray(solar_open2._attention_sequence(
        dataclasses.replace(CFG, use_gqa_gate=False), h, lp)[0])
    np.testing.assert_allclose(
        gated, np.asarray(REF._gqa(CFG, h[0], lp, jnp.float32)), atol=1e-5)
    gate = np.asarray(solar_open2._gate(CFG, h, lp))
    assert 0.5 < gate.std() * 4 and gate.min() < 0.2 and gate.max() > 0.8
    assert np.abs(gated - 0.5 * plain).max() > 0.1 * np.abs(plain).max()


@pytest.mark.parametrize("pattern", ["GKKK", "GKKKGKKK", "GKGKK"])
def test_plain_forward_is_the_reference(pattern):
    """The family's ``forward`` (the chunk form, chunks of 8 in sub-chunks of
    4) against the reference's token-by-token recurrence: the benchmark's cut
    (a lead ``G`` and a scan over three ``K``), two whole periods (a scan over
    ``GKKK``) and an order that ends off its period (``GK`` x 2 + ``K``)."""
    cfg = solar_open2.SolarOpen2Config.tiny(pattern=pattern)
    p = solar_open2.init_params(cfg, jax.random.PRNGKey(3))
    lead, period, repeats, tail = paged.stack_plan_tail(cfg.layer_pattern)
    assert lead + period * repeats + tail == pattern
    assert (len(p["lead"]), len(p["period"]), len(p["tail"])) == (
        len(lead), len(period), len(tail))
    ids = jnp.asarray(_prompts([41], seed=3)[0])
    np.testing.assert_allclose(
        np.asarray(solar_open2.forward(cfg, p, ids[None])[0]),
        np.asarray(REF.forward(cfg, p, ids)), atol=ATOL)
    assert solar_open2.num_params(cfg) == REF.num_params(cfg) == sum(
        a.size for a in jax.tree_util.tree_leaves(p))
    axes = solar_open2.param_logical_axes(cfg)
    is_axes = lambda a: isinstance(a, tuple)  # noqa: E731
    assert jax.tree_util.tree_structure(p) == jax.tree_util.tree_structure(
        axes, is_leaf=is_axes)
    for leaf, ax in zip(jax.tree_util.tree_leaves(p),
                        jax.tree_util.tree_leaves(axes, is_leaf=is_axes)):
        assert leaf.ndim == len(ax)


# ----------------------------------------------------- the published count
def test_each_term_of_the_published_count():
    """The whole model as the catalog's row configures it: 48 layers, 12 x
    ``GKKK`` (four bodies a step program); every term of ISSUE 57's count, and
    their sum, the published "250B-A15B"."""
    full = solar_open2.SolarOpen2Config()
    assert full.layer_pattern == "GKKK" * 12
    assert paged.stack_plan_tail(full.layer_pattern) == ("", "GKKK", 12, "")
    assert full.kda_width == 8192 and full.kda_beta_scale == 2.0
    d = 4096
    kda_mixer = (3 * d * 8192 + 3 * 8192 * 4 + 2 * d * 128 + 2 * 128 * 8192
                 + d * 64 + 8192 + 64 + 128 + 8192 * d)
    assert kda_mixer == REF.kda_params(full) == 137_732_288
    assert solar_open2._count(kda.mixer_shapes(full)) == kda_mixer
    gqa_mixer = 3 * d * 8192 + 2 * d * 1024
    assert gqa_mixer == REF.gqa_params(full) == 109_051_904
    assert solar_open2._count(
        solar_open2._mixer_shapes(full, "G")) == gqa_mixer
    expert = REF.expert_params(full)
    assert expert == 3 * d * 1280 == 15_728_640
    beside = expert + d * 320 + 320 + 2 * d      # shared, router, bias, norms
    assert beside == 17_047_872
    k_layer = kda_mixer + beside + 320 * expert
    g_layer = gqa_mixer + beside + 320 * expert
    assert (k_layer, g_layer) == (5_187_944_960, 5_159_264_576)
    whole = 36 * k_layer + 12 * g_layer + 2 * 196608 * d + d
    assert whole == REF.num_params(full) == solar_open2.num_params(full) \
        == 250_287_810_304
    # 14.7 B a token with the embedding's row counted as the head's is
    assert round((REF.active_params(full) + 196608 * d) / 1e8) == 147
    # the same formulas give Kimi-Linear's mixer (one mixer, two families)
    kimi = kimi_linear.KimiLinearConfig()
    assert solar_open2._count(kda.mixer_shapes(kimi)) == 39_514_272


def test_one_kda_mixer_for_two_families():
    """``models/kda.py`` is what both families run: neither has a mixer of
    its own, and ``kda_beta_scale`` is what they hand it, 1.0 and 2.0."""
    assert kimi_linear.kda is solar_open2.kda is kda
    for mod in (kimi_linear, solar_open2):
        assert not [n for n in vars(mod) if n.startswith("_kda")
                    or n in ("_conv", "_qkv_split")]
    assert kimi_linear.KimiLinearConfig.tiny().kda_beta_scale == 1.0
    assert CFG.kda_beta_scale == 2.0
    assert kda.STATE_KIND == "kda"
    h = jnp.asarray(np.random.default_rng(0).standard_normal(
        (5, CFG.hidden_size)), jnp.float32)
    lp = jax.tree_util.tree_map(
        lambda a: a[0], solar_open2.init_params(
            CFG, jax.random.PRNGKey(0))["period"][0]["mix"])
    once = kda.inputs(dataclasses.replace(CFG, kda_allow_neg_eigval=False),
                      h, lp)[2]
    np.testing.assert_array_equal(np.asarray(kda.inputs(CFG, h, lp)[2]),
                                  2.0 * np.asarray(once))


# --------------------------------------------------- one rank's share
def test_eight_ranks_parts_add_up_to_the_uncut_layer():
    """The share test of the model-configs guide, section 4: an expert layer
    with all 16 experts against the eight ranks' layers of 2 experts each,
    the same router over all 16. The ranks' routed parts add up and the
    shared expert counts once: the uncut layer of the reference."""
    whole = solar_open2.SolarOpen2Config.tiny(num_experts=16, experts_held=None)
    full = solar_open2.init_params(whole, jax.random.PRNGKey(2))["lead"][0]["ffn"]
    h = jnp.asarray(np.random.default_rng(1).standard_normal(
        (23, whole.hidden_size)), jnp.float32)
    want = np.asarray(REF._moe(whole, h, full, jnp.float32))
    total, shared_seen = 0.0, []
    for rank in range(8):
        cfg = solar_open2.SolarOpen2Config.tiny(
            num_experts=16, experts_held=2, expert_rank=rank)
        lp = {**full, **{w: full[w][2 * rank:2 * rank + 2]
                         for w in ("w_gate", "w_up", "w_down")}}
        routed, shared = solar_open2.ffn_parts(cfg, h, lp,
                                               experts.routed_experts)
        # a rank's own layer is what the reference computes for that rank
        np.testing.assert_allclose(
            np.asarray(routed + shared),
            np.asarray(REF._moe(cfg, h, lp, jnp.float32)), atol=ATOL)
        total = total + routed
        shared_seen.append(np.asarray(shared))
    for other in shared_seen[1:]:   # what every rank computes alike
        np.testing.assert_array_equal(other, shared_seen[0])
    np.testing.assert_allclose(np.asarray(total) + shared_seen[0], want,
                               atol=ATOL)


# ------------------------------------------- the kernels at 64 heads
def test_kda_decode_kernel_at_64_heads_is_the_xla_form():
    """``kda_decode`` in interpret mode at the published 64 heads of 128 (a
    row's state ``[128, 8192]`` float32, 4 MB; ``[128, 64]`` operands)
    against gather -> update -> scatter, ``beta`` up to 2; a padding row on
    the scratch row leaves it as it was."""
    rng = np.random.default_rng(0)
    rows_n, kd, h, t = 4, 128, 64, 3
    state = jnp.asarray(rng.standard_normal((rows_n, kd, h * kd)), jnp.float32)
    rows = jnp.asarray([2, 0, 3], jnp.int32)                    # 3: scratch
    a = jnp.asarray(rng.uniform(0.2, 1.0, (t, kd, h)), jnp.float32)
    k = rng.standard_normal((t, kd, h))
    k = jnp.asarray(k / np.linalg.norm(k, axis=1, keepdims=True), jnp.float32)
    q = jnp.asarray(rng.standard_normal((t, kd, h)), jnp.float32)
    v = jnp.asarray(rng.standard_normal((t, h * kd)), jnp.float32)
    beta = jnp.repeat(jnp.asarray(rng.uniform(0, 2, (t, h)), jnp.float32), kd, 1)
    pad = jnp.asarray([False, False, True])
    a = jnp.where(pad[:, None, None], 1.0, a)
    beta = jnp.where(pad[:, None], 0.0, beta)
    got_s, got_y = kda_decode(state, rows, a, k, q, v, beta, impl="pallas",
                              interpret=True)
    want_s, want_y = kda_decode_xla(state, rows, a, k, q, v, beta)
    np.testing.assert_allclose(np.asarray(got_s), np.asarray(want_s), atol=1e-5)
    np.testing.assert_allclose(np.asarray(got_y), np.asarray(want_y), atol=1e-4)
    np.testing.assert_array_equal(np.asarray(got_s)[[1, 3]],
                                  np.asarray(state)[[1, 3]])
    assert float(beta.max()) > 1.5


def test_kda_chunk_kernel_at_64_heads_is_the_xla_form():
    """``kda_chunk`` in interpret mode at 64 heads of 128 (a grid of 16
    blocks of four heads x tiles) against ``kda_chunk_xla``: a slot's prompt continued over two
    16-row tiles beside another slot's fresh one, ``beta`` up to 2 (the
    delta rule with negative eigenvalues), readings and states to 1e-5 of
    their largest magnitude."""
    rng = np.random.default_rng(1)
    n_i, r, h, kd, sub = 3, 16, 64, 128, 8

    def draw(*shape):
        return jnp.asarray(rng.standard_normal(shape), jnp.float32)

    def unit(x):  # [I, R, H x K], a head's K normalised
        x = x.reshape(n_i, r, h, kd)
        return (x / jnp.linalg.norm(x, axis=-1, keepdims=True)).reshape(
            n_i, r, h * kd)

    q, k, v = unit(draw(n_i, r, h * kd)) * kd ** -0.5, unit(
        draw(n_i, r, h * kd)), draw(n_i, r, h * kd)
    g = -jnp.abs(draw(n_i, r, h * kd)) * 0.3
    beta = 2.0 * jax.nn.sigmoid(draw(n_i, r, h))
    leaf = draw(4, kd, h * kd)
    tiles = [(1, 3, 0, 0, 0), (1, 1, 0, 1, 1), (0, 0, 1, 0, 1)]  # 3: scratch
    rows, rows_w, fresh, cont, write = (
        jnp.asarray(col, jnp.int32) for col in zip(*tiles))
    args = (leaf, rows, rows_w, fresh > 0, cont > 0, write > 0, q, k, g, v,
            beta, sub)
    got_s, got_y = kda_chunk(*args, impl="pallas", interpret=True)
    want_s, want_y = kda_chunk_xla(*args)

    def rel(got, want):
        got, want = np.asarray(got), np.asarray(want)
        return float(np.abs(got - want).max() / np.abs(want).max())

    assert np.isfinite(np.asarray(got_y)).all()
    assert rel(got_y, want_y) < 1e-5 and rel(got_s, want_s) < 1e-5
    np.testing.assert_array_equal(np.asarray(got_s[2]), np.asarray(leaf[2]))
    assert not np.asarray(got_s[3]).any()
    assert float(beta.max()) > 1.5


@pytest.mark.parametrize("tiles", [1, 4])
def test_kda_chunk_kernel_carries_the_strongest_decay_over_the_widest_step(
        tiles):
    """``g`` = -1.6 a token on EVERY channel (``G`` -205 over a 128-row tile:
    ``exp(-G)`` is float32's ``inf``) through the kernel at 128 channels a
    head, four heads a grid step, a slot continued over four tiles (the
    cell's widest program, ``d0_t4``) from a state that is not zero: the
    state the loop carries from sub-chunk to sub-chunk decays by ``exp`` of
    a sub-chunk's sum, never grows by ``exp`` of a difference the other way,
    so readings and state are finite and XLA's form to 1e-5 of its largest
    value."""
    rng = np.random.default_rng(tiles)
    r, h, kd, sub = 128, 4, 128, 16

    def draw(*shape):
        return jnp.asarray(rng.standard_normal(shape), jnp.float32)

    def unit(x):
        x = x.reshape(tiles, r, h, kd)
        return (x / jnp.linalg.norm(x, axis=-1, keepdims=True)).reshape(
            tiles, r, h * kd)

    q, k, v = unit(draw(tiles, r, h * kd)) * kd ** -0.5, unit(
        draw(tiles, r, h * kd)), draw(tiles, r, h * kd)
    g = jnp.full((tiles, r, h * kd), -1.6, jnp.float32)
    beta = 2.0 * jax.nn.sigmoid(draw(tiles, r, h))
    leaf = draw(3, kd, h * kd)
    last = jnp.arange(tiles) == tiles - 1
    args = (leaf, jnp.full((tiles,), 1), jnp.where(last, 1, 2),
            jnp.zeros((tiles,), bool), jnp.arange(tiles) > 0, last, q, k, g, v,
            beta, sub)
    assert float(g[0, :, 0].sum()) < -200.0
    got_s, got_y = kda_chunk(*args, impl="pallas", interpret=True)
    want_s, want_y = kda_chunk_xla(*args)

    def rel(got, want):
        got, want = np.asarray(got), np.asarray(want)
        return float(np.abs(got - want).max() / np.abs(want).max())

    assert np.isfinite(np.asarray(got_y)).all()
    assert np.isfinite(np.asarray(got_s)).all()
    assert rel(got_y, want_y) < 1e-5 and rel(got_s[1], want_s[1]) < 1e-5
    np.testing.assert_array_equal(np.asarray(got_s[0]), np.asarray(leaf[0]))
    if tiles > 1:
        assert not np.asarray(got_s[2]).any()


# ------------------------------------------------------------ the engine
def test_engine_accounts_blocks_and_slots_apart(engine_of):
    """The K/V leaves count the ONE ``G`` layer, the slot leaves the three
    ``K`` layers: the reference's geometry, which the benchmark's readers
    multiply the spans by."""
    eng = engine_of()
    assert eng.cache["k"].shape[0] == eng.cache["v"].shape[0] == 1
    assert eng.cache[SLOTS]["kda"].shape[0] == 3
    assert eng.kv_bytes_per_token() == REF.kv_bytes_per_token(CFG, 4) \
        == 2 * 2 * 16 * 4
    assert eng.state_bytes_per_slot() == REF.state_bytes_per_slot(CFG, 4) \
        == 3 * (4 * 2 * 16 * 16 + 3 * 3 * 32 * 4)
    assert REF.attn_flops_per_pair(CFG) == 4 * 4 * 16
    # at the published widths: 4 MB of state and the window leaf's 144 KB a
    # slot and layer, 4,096 B of K and V a token in the cut's one G layer
    cut = solar_open2.SolarOpen2Config(num_layers=4, gqa_layers=(0,))
    assert REF.state_bytes_per_slot(cut) == 3 * 4_341_760
    assert REF.kv_bytes_per_token(cut) == 4096
    assert REF.kda_state_bytes_per_slot(cut) == 3 * 4_194_304
    assert REF.kda_chunk_io_bytes_per_token(cut) == 3 * 4 * (5 * 8192 + 64)
    assert REF.kda_chunk_flops_per_tile(cut, 128) == 3 * 64 * (
        6 * 128 ** 3 + 8 * 128 ** 3)
    cache = jax.eval_shape(lambda: solar_open2.init_paged_cache(
        cut, 1025, 128, jnp.bfloat16, num_slots=17))
    assert cache["k"].shape == (1, 1025, 128, 1024)
    assert cache[SLOTS]["kda"].shape == (3, 17, 128, 8192)
    assert cache[SLOTS]["conv"].shape == (3, 17, 48, 1536)


def test_dispatch_span_says_what_the_chunk_form_moved(engine_of, monkeypatch):
    """``engine/dispatch`` of a family with a chunk form over slot state:
    ``chunk_slots``, the distinct prefilling slots of the step (the states the
    chunk form reads and writes once each: the prefill part of
    ``state_bytes`` over a slot's bytes), beside ``chunk_tiles``; the tiles
    summed on ``inference_chunk_tiles_total{state_kind=}``."""
    from deepspeed_tpu import telemetry
    from deepspeed_tpu.inference import ragged

    seen = []
    real = ragged.span
    monkeypatch.setattr(ragged, "span", lambda name, **a: (
        seen.append(a) if name == "engine/dispatch" else None, real(name, **a))[1])
    telemetry.configure(enabled=True)
    try:
        eng = engine_of(device_state=True)
        for uid, prompt in _prompts([11, 5]).items():
            eng.put(uid, prompt, max_new_tokens=4)
        eng.generate_all()
        series = telemetry.snapshot()["metrics"][
            "inference_chunk_tiles_total"]["series"]
    finally:
        telemetry.configure(enabled=False)
    per_slot = 2 * eng.state_bytes_per_slot()
    assert seen and all(a["state_kind"] == "kda" for a in seen)
    # the first step prefills both prompts: two slots, 2 + 1 tiles and one pad
    assert (seen[0]["chunk_slots"], seen[0]["chunk_tiles"]) == (2, 4)
    assert all(a["chunk_slots"] * per_slot
               == a["state_bytes"] - a["dec_state_bytes"] for a in seen)
    assert all(a["chunk_slots"] == a["chunk_tiles"] == 0 for a in seen[1:-1])
    assert [s["value"] for s in series
            if s["labels"].get("state_kind") == "kda"] == [
                sum(a["chunk_tiles"] for a in seen)]


def test_decode_ladder_and_refusals(params):
    """ONE decode bucket at the cell's 16 slots, so seven step programs (no
    decode row and 1, 2 or 4 tiles; 16 rows and 0 to 3 tiles), as
    Kimi-Linear's one bucket gives; what a prefix of blocks cannot restore
    refuses by name, as for every slot family."""
    eng = _engine(params, max_tokens_per_step=512, max_seqs=16,
                  num_blocks=129, max_blocks_per_seq=8, prefill_tile=128,
                  block_size=16)
    assert eng._dec_buckets == [16] and len(eng._step_zoo()) == 7
    assert eng.spec.state_kind == "kda"
    for sizes, match in ((dict(enable_prefix_cache=True), "snapshot"),
                         (dict(quant="int8"), "quantized pool"),
                         (dict(kv_tier=True), "snapshot"),
                         (dict(prefill_tile=0), "tile")):
        with pytest.raises((ValueError, NotImplementedError), match=match):
            _engine(params, **sizes)
    with pytest.raises(NotImplementedError, match="as published"):
        solar_open2.SolarOpen2Config.tiny(use_rope=True)
    with pytest.raises(NotImplementedError, match="repeated period"):
        solar_open2.SolarOpen2Config.tiny(pattern="KGK")
