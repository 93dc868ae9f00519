"""``models/lfm2_moe.py`` and ``models/shortconv.py`` at a small size on the
CPU, seeded weights: what is served (prefill by tiles, then decode, through
the paged pool AND the window leaf) against the plain reference
``benchmark/reference/lfm2_moe.py``; the router; 32 query heads of 64 lanes on
8 K/V heads through the paged kernels; the stack as runs; the parameter count
term by term; what the span arguments and counters say.

Logits are compared, not tokens. Tolerance 2e-5 (float32 everywhere here): the
program and the reference compute the same taps, but the program carries a
slot's two rows between a prompt's tiles and steps and folds the channels over
a tile's rows, attention runs by blocks of the pool and the experts by picks,
so sums are taken in another order; observed differences are under 1e-6 on
logits of magnitude 1 (deviation 0.16). A dropped output gate, a filter fed
``u`` alone and a tap shifted by one each move a logit by 100 tolerances and
more
(``test_the_model_one_line_away_is_not_the_reference``).
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
from deepspeed_tpu.models import experts, lfm2_moe, mamba2, shortconv
from deepspeed_tpu.models.paged import SLOTS

REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
ATOL = 2e-5


def _reference():
    spec = importlib.util.spec_from_file_location(
        "reference_lfm2_moe",
        os.path.join(REPO, "benchmark", "reference", "lfm2_moe.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


REF = _reference()
# c c a c c a c: two dense layers, then experts; 4 query heads on 2 K/V heads
CFG = lfm2_moe.Lfm2MoeConfig.tiny()


@pytest.fixture(scope="module")
def params():
    return lfm2_moe.init_params(CFG, jax.random.PRNGKey(1))


def _engine(params, device_state=False, cfg=CFG, **sizes):
    rc = RaggedConfig(**{**dict(
        max_tokens_per_step=32, max_seqs=4, block_size=8, num_blocks=33,
        max_blocks_per_seq=8, prefill_tile=8, device_state=device_state),
        **sizes})
    return RaggedInferenceEngine(
        lambda ctx: lfm2_moe.build(cfg, ctx=ctx), rc, dtype=jnp.float32,
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
    # 16 a step: 16 + 16 + 5, a partial last tile, the window carried over
    # steps; 37 + 4 tokens cross five blocks of 8
    "prompt_chunked_over_steps": ({"max_tokens_per_step": 16}, [37], 4),
    # six requests over four slots: decode rows beside tiles (the window
    # carried across ``cont`` tiles of one slot), slots reused, padding rows
    # on the scratch slot
    "mixed_steps": ({}, [5, 19, 37, 9, 26, 3], 6),
    # one slot: the second request starts from zeros where the first ended
    "slot_reused": ({"max_seqs": 1}, [11, 7], 5),
    # prompts shorter than the filter: position 0 and 1 see zeros before them
    "prompts_under_the_taps": ({}, [1, 2, 3], 4),
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
        want = _reference_rows(CFG, params, prompt + generated)
        for g in range(new_tokens):
            np.testing.assert_allclose(
                rows[(uid, g)], want[len(prompt) + g - 1], atol=ATOL,
                err_msg=f"{case}: request {uid}, generated token {g}")
    # the scratch slot's window stays zero, the pool comes back whole
    assert not np.asarray(eng.cache[SLOTS]["conv"][:, -1]).any()
    assert eng.allocator.free_blocks == eng.cfg.num_blocks - 1


def test_a_reused_slot_serves_a_fresh_ones_logits(params, engine_of):
    """One slot, the same prompt twice, a longer request between: the second
    time the slot held what the other request left, and the logits are those
    of the first time to the last bit (position 0 starts from zeros)."""
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
        np.asarray(lfm2_moe.forward(CFG, params, ids[None])[0]),
        np.asarray(REF.forward(CFG, params, ids)), atol=ATOL)


FAULTS = ("no_output_gate", "filter_fed_u_alone", "tap_shifted")


def plant(monkeypatch, fault):
    """One of the mixer's distinctive lines changed in the PROGRAM."""
    if fault == "no_output_gate":         # Op = c W_out: the C gate dropped
        split = shortconv.split
        monkeypatch.setattr(shortconv, "split", lambda cfg, h, lp: (
            split(cfg, h, lp)[0], jnp.ones_like(h)))
    elif fault == "filter_fed_u_alone":   # z = u, not B * u
        def split_u(cfg, h, lp):
            d = cfg.hidden_size
            bcu = h @ lp["w_in"].astype(h.dtype)
            return bcu[..., 2 * d:], bcu[..., d:2 * d]
        monkeypatch.setattr(shortconv, "split", split_u)
    elif fault == "tap_shifted":          # w_k meets z_{t-k}: the taps reversed
        conv = mamba2.causal_conv
        monkeypatch.setattr(shortconv, "causal_conv", lambda cfg, win, w, *a, **k:
                            conv(cfg, win, jnp.flip(w, axis=0), *a, **k))
    else:
        raise ValueError(fault)


@pytest.mark.parametrize("fault", FAULTS)
def test_the_model_one_line_away_is_not_the_reference(params, monkeypatch,
                                                      fault):
    """The program with one of the mixer's distinctive lines changed is not
    the reference by 100 tolerances (the router's and the head norm's faults,
    planted in the serving path, are ``benchmark/tests/test_lfm2_moe.py``'s)."""
    ids = jnp.asarray(_prompts([29], seed=6)[0])
    want = np.asarray(REF.forward(CFG, params, ids))
    plant(monkeypatch, fault)
    got = np.asarray(lfm2_moe.forward(CFG, params, ids[None])[0])
    assert np.abs(got - want).max() > 100 * ATOL


# -------------------------------------------------------------- the router
def test_the_selection_bias_picks_and_never_weighs(params):
    """The reference's picks and weights against ``experts._route``'s; a
    bias large enough to decide every pick changes WHICH experts and leaves a
    pick's weight its own score over the picked scores' sum."""
    ffn = jax.tree_util.tree_map(lambda a: a[0], params["runs"][1]["ffn"])
    h = jax.random.normal(jax.random.PRNGKey(5), (64, CFG.hidden_size))
    topv, topi = experts._route(h, ffn["router"], CFG.top_k, "sigmoid",
                                ffn["router_bias"], True, 1.0,
                                lfm2_moe.ROUTER_EPS)
    want_w, want_i = REF.router_picks(CFG, h, ffn)
    np.testing.assert_array_equal(np.asarray(topi), np.asarray(want_i))
    np.testing.assert_allclose(np.asarray(topv), np.asarray(want_w), atol=1e-7)
    # the seeded bias changes picks somewhere (a zero bias would leave the
    # mechanism untested) ...
    wide = jax.random.normal(jax.random.PRNGKey(6), (4096, CFG.hidden_size))
    no_bias = {**ffn, "router_bias": 0 * ffn["router_bias"]}
    assert (np.asarray(REF.router_picks(CFG, wide, ffn)[1])
            != np.asarray(REF.router_picks(CFG, wide, no_bias)[1])).any()
    # ... and a bias large enough to decide every pick changes WHICH experts
    # and leaves a pick's weight its own score over the picked scores' sum
    big = {**ffn, "router_bias": jnp.arange(CFG.num_experts, dtype=jnp.float32)}
    w, i = REF.router_picks(CFG, h, big)
    assert (np.sort(np.asarray(i), -1) == [CFG.num_experts - 2,
                                           CFG.num_experts - 1]).all()
    picked = np.take_along_axis(
        np.asarray(jax.nn.sigmoid(h @ ffn["router"])), np.asarray(i), -1)
    np.testing.assert_allclose(np.asarray(w), picked / (
        picked.sum(-1, keepdims=True) + 1e-6), atol=1e-7)
    np.testing.assert_allclose(np.asarray(w).sum(-1), 1.0, atol=1e-5)


# ------------------------------------------------------------ the kernels
@pytest.mark.parametrize("kernel", ["paged_decode", "tiled_prefill"])
def test_sixty_four_lane_grouped_queries_through_the_paged_kernels(kernel):
    """The published attention geometry (32 query heads of 64 lanes, four a
    K/V head: a pool row is 512 lanes, the decode kernel's WIDE form at
    ``rep`` 4, the tile kernel's head padded to 128 lanes) through the two
    paged kernels in interpret mode against the XLA gather."""
    from deepspeed_tpu.ops import attention

    hq, hkv, d, block = 32, 8, 64, 8
    k = jax.random.split(jax.random.PRNGKey(2), 3)
    kc = jax.random.normal(k[0], (9, block, hkv * d))
    vc = jax.random.normal(k[1], (9, block, hkv * d))
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


def test_the_served_kernels_give_the_xla_forms_logits(params, monkeypatch):
    """The engine with the paged kernels in interpret mode (``_on_tpu`` is
    what a chip changes) serves the logits the XLA gather serves."""
    from deepspeed_tpu.ops import attention

    prompts = _prompts([19, 5, 11], seed=7)
    want = _serve(_engine(params), prompts, 3)
    monkeypatch.setattr(attention, "_on_tpu", lambda: True)
    got = _serve(_engine(params), prompts, 3)
    assert got.keys() == want.keys()
    for key in want:
        np.testing.assert_allclose(got[key], want[key], atol=ATOL)


# -------------------------------------------------------------- the stack
def test_the_stack_is_runs_and_the_layer_order_is_the_published_one():
    assert CFG.runs == [(("conv", "dense"), 2), (("full_attention", "moe"), 1),
                        (("conv", "moe"), 2), (("full_attention", "moe"), 1),
                        (("conv", "moe"), 1)]
    published = lfm2_moe.Lfm2MoeConfig()
    assert [i for i, k in enumerate(published.layer_types)
            if k == "full_attention"] == [2, 6, 10, 14, 18, 21]
    assert len(published.runs) == 13
    assert list(published.kinds) == REF.kinds(published)
    assert published.head_dim == 64
    stage = lfm2_moe.Lfm2MoeConfig(num_layers=12,
                                   layer_types=lfm2_moe.PUBLISHED[:12])
    assert [n for _, n in stage.runs] == [2, 1, 3, 1, 3, 1, 1]
    assert (stage.layers_of("conv"), stage.layers_of("full_attention")) == (9, 3)
    # the taps are Mamba-2's, with no bias and no activation
    assert shortconv.causal_conv is mamba2.causal_conv
    win = jax.random.normal(jax.random.PRNGKey(0), (7, 16))
    w = jax.random.normal(jax.random.PRNGKey(1), (3, 16))
    np.testing.assert_allclose(
        np.asarray(mamba2.causal_conv(CFG, win, w, None, 5, act=None)),
        np.asarray(sum(win[k:k + 5] * w[k] for k in range(3))), atol=1e-6)
    with pytest.raises(NotImplementedError, match="bias"):
        lfm2_moe.Lfm2MoeConfig.tiny(conv_bias=True)
    with pytest.raises(ValueError, match="layer_types"):
        lfm2_moe.Lfm2MoeConfig.tiny(num_layers=3)


def test_the_published_parameter_count_term_by_term():
    cfg = lfm2_moe.Lfm2MoeConfig()
    conv = REF.mixer_params(cfg, "conv")
    assert conv == {"in_proj": 12_582_912, "conv": 6_144, "out_proj": 4_194_304}
    assert sum(conv.values()) == shortconv.mixer_param_count(cfg) == 16_783_360
    assert sum(REF.mixer_params(cfg, "full_attention").values()) == 10_485_888
    assert REF.ffn_params(cfg, "dense", 0) == 44_040_192
    assert REF.ffn_params(cfg, "moe", 32) == 352_387_104
    whole = (18 * 16_783_360 + 6 * 10_485_888 + 24 * 4_096 + 2 * 44_040_192
             + 22 * 352_387_104 + 65_536 * 2_048 + 2_048)
    assert lfm2_moe.num_params(cfg) == REF.num_params(cfg) == whole \
        == 8_339_930_560
    # 1.56 B a token: the published "A1.5B"
    assert 1.5e9 < REF.active_params(cfg) < 1.6e9
    # the benchmark's stage: layers 0-11
    stage = lfm2_moe.Lfm2MoeConfig(num_layers=12,
                                   layer_types=lfm2_moe.PUBLISHED[:12])
    held = (9 * 16_783_360 + 3 * 10_485_888 + 12 * 4_096 + 2 * 44_040_192
            + 10 * 352_387_104 + 134_217_728 + 2_048)
    assert lfm2_moe.num_params(stage) == REF.num_params(stage) == held \
        == 3_928_728_256
    assert REF.kv_bytes_per_token(stage) == 6_144
    assert REF.state_bytes_per_slot(stage) == 73_728
    assert REF.held_expert_slots(stage) == 320
    assert REF.attn_flops_per_pair(stage) == 4 * 32 * 64 * 3
    # and the tree the program draws has exactly those leaves
    shapes = jax.eval_shape(lambda: lfm2_moe.init_params(
        stage, jax.random.PRNGKey(0)))
    assert sum(int(np.prod(a.shape)) for a in
               jax.tree_util.tree_leaves(shapes)) == held


# ------------------------------------------------------------ the engine
def test_engine_accounts_blocks_and_slots_apart(params, engine_of):
    eng = engine_of()
    assert eng.kv_bytes_per_token() == REF.kv_bytes_per_token(CFG, 4)
    assert eng.state_bytes_per_slot() == REF.state_bytes_per_slot(CFG, 4)
    assert lfm2_moe.num_params(CFG) == REF.num_params(CFG) == sum(
        a.size for a in jax.tree_util.tree_leaves(params))
    assert eng._dec_buckets == [4]      # one decode bucket: max_seqs
    assert eng.spec.state_kind == "shortconv"
    assert set(eng.cache[SLOTS]) == {"conv"}    # a window leaf alone
    # the published width's window leaf is whole bfloat16 tiles (32 x 128 a
    # slot), and the cell's cache is what the configuration's file reckons
    cache = jax.eval_shape(lambda: lfm2_moe.init_paged_cache(
        lfm2_moe.Lfm2MoeConfig(num_layers=12,
                               layer_types=lfm2_moe.PUBLISHED[:12]),
        6145, 128, jnp.bfloat16, num_slots=513))
    assert cache[SLOTS]["conv"].shape == (9, 513, 32, 128)
    assert cache["k"].shape == cache["v"].shape == (3, 6145, 128, 512)


@pytest.mark.parametrize("option,match", [
    ({"enable_prefix_cache": True}, "prefix"),
    ({"kv_tier": True, "enable_prefix_cache": True}, "prefix|tier"),
    ({"quant": "int8"}, "quant"),
    ({"prefill_tile": 0}, "tile")])
def test_what_a_prefix_of_blocks_cannot_restore_refuses(params, option, match):
    with pytest.raises((ValueError, NotImplementedError), match=match):
        _engine(params, **option)


def test_dispatch_span_says_the_fourth_state_kind(params, monkeypatch):
    """``engine/dispatch`` of this family: ``state_kind`` ``"shortconv"``,
    ``state_bytes`` / ``dec_state_bytes`` (the window rows, once each way) /
    ``ssm_prefill_tokens``, ``moe``, and NO ``chunk_tiles`` / ``chunk_slots``
    / ``scan_tiles`` (no matrix state, no chunk form, no scan); ``/metrics``
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
    assert seen and all(a["state_kind"] == "shortconv" for a in seen)
    per_slot = 2 * eng.state_bytes_per_slot()
    for a in seen:
        assert not {"chunk_tiles", "chunk_slots", "scan_tiles"} & set(a)
        assert a["moe"] == "dense"      # 32 rows a step: under the crossover
        assert a["state_bytes"] % per_slot == 0 <= a["dec_state_bytes"]
    assert sum(a["ssm_prefill_tokens"] for a in seen) == sum(
        map(len, prompts.values()))
    moved = sum(s["value"] for s in
                metrics["inference_slot_state_bytes_total"]["series"]
                if s["labels"].get("state_kind") == "shortconv")
    assert moved >= sum(a["state_bytes"] for a in seen) > 0
    assert "inference_chunk_tiles_total" not in metrics or not [
        s for s in metrics["inference_chunk_tiles_total"]["series"]
        if s["labels"].get("state_kind") == "shortconv"]
