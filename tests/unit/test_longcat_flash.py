"""The ``longcat_flash`` family (LongCat-Flash's double layer: two MLA
sublayers and two dense FFNs around a shortcut-connected expert branch with
zero-compute experts) at a tiny size, float32, seeded weights: the plain
forward pass, the two-rows-a-layer latent pool behind ``RaggedInferenceEngine``,
the router, ``routed_experts``' ``zero_experts`` in both forms, the ranks'
parts, the cache manager over both sublayers' rows and the counts a step hands
back, each against ``benchmark/reference/longcat_flash.py`` (straight
``jax.numpy``, nothing imported from the program) or a hand-worked case."""

import dataclasses
import functools
import importlib.util
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from shared import one_engine_each  # tests/unit is rootdir-inserted

from deepspeed_tpu.inference.ragged import RaggedConfig, RaggedInferenceEngine
from deepspeed_tpu.models import experts, paged
from deepspeed_tpu.models import longcat_flash as lc
from deepspeed_tpu.models.experts import swiglu
from deepspeed_tpu.models.llama import rmsnorm

VOCAB = 89
CFG = lc.LongcatFlashConfig.tiny(VOCAB)   # 2 double layers, 2 heads, latent
#                32 + rope 16, q rank 24, 8 routed + 4 zero experts, top-3


@pytest.fixture(scope="module")
def reference():
    path = os.path.join(os.path.dirname(__file__), os.pardir, os.pardir,
                        "benchmark", "reference", "longcat_flash.py")
    spec = importlib.util.spec_from_file_location("reference_longcat", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    mod.Q_BLOCK = mod.PAD_TO = 4   # the sequences here are padded to 4s
    return mod


@pytest.fixture(scope="module")
def params():
    return lc.init_params(CFG, jax.random.PRNGKey(1))


def test_forward_matches_the_reference(reference, params):
    """``forward_fn`` (plain MLA on ``xla_attention``, the expert branch's
    einsum form) against the reference's one-expert-at-a-time pass: float32
    both, so 1e-5 on logits of magnitude ~0.7 is rounding only."""
    ids = jnp.asarray(np.random.default_rng(0).integers(0, VOCAB, (2, 16)),
                      jnp.int32)
    got = np.asarray(lc.build(CFG).forward_fn(params, ids))
    for b in range(2):
        want = np.asarray(reference.forward(CFG, params, ids[b], jnp.float32))
        np.testing.assert_allclose(got[b], want, rtol=0, atol=1e-5)


def test_param_tree_is_what_the_axes_and_the_counts_say(reference, params):
    axes = lc.param_logical_axes(CFG)
    assert jax.tree_util.tree_structure(params) == jax.tree_util.tree_structure(
        axes, is_leaf=lambda a: isinstance(a, tuple))
    for leaf, ax in zip(jax.tree_util.tree_leaves(params),
                        jax.tree_util.tree_leaves(
                            axes, is_leaf=lambda a: isinstance(a, tuple))):
        assert leaf.ndim == len(ax)
    n = sum(int(np.prod(a.shape)) for a in jax.tree_util.tree_leaves(params))
    assert n == lc.num_params(CFG) == reference.num_params(CFG)
    # the published layer (ISSUE 38 section 1): the non-expert part and one expert
    full = lc.LongcatFlashConfig()
    assert lc._layer_params(full, 0) == 638_874_368
    assert lc._layer_params(full, 1) - lc._layer_params(full, 0) == 37_748_736
    # two attentions a layer in the training FLOPs
    one = lc.flops_per_token(CFG, 64) - lc.flops_per_token(CFG, 0)
    assert one == 6.0 * 2 * CFG.num_layers * CFG.num_heads * (32 + 16) * 32


# ------------------------------------------------ the engine's two-row pool
# Float32 end to end on the CPU: the served logits differ from the reference's
# full forward pass by summation order only (absorbed attention, the einsum
# over the experts). LOGIT_ATOL is ``test_deepseek.py``'s; every control below
# is a wrong model one line away from the right one and must miss it by 10x.
LOGIT_ATOL = 2e-6
PROMPT_LEN, NEW_TOKENS = 22, 6


def _engine(params, cfg=CFG, device_state=False, **over):
    sizes = dict(max_tokens_per_step=8, max_seqs=2, block_size=4,
                 num_blocks=33, max_blocks_per_seq=8, prefill_tile=4,
                 device_state=device_state)
    return RaggedInferenceEngine(
        lambda ctx: lc.build(cfg, ctx=ctx), dtype=jnp.float32, params=params,
        seed=0, ragged_config=RaggedConfig(**{**sizes, **over}))


@pytest.fixture(scope="module")
def shared_engine(params):
    """``shared_engine()``: the module's ONE engine of ``_engine``'s own
    sizes, as new each time it is asked for (``shared.py``)."""
    return one_engine_each(functools.partial(_engine, params))


def _serve_logits(eng, cfg=CFG):
    """Prefill a 22-token prompt in chunks of <= 8 tokens (tiles of 4) and
    decode 6 tokens; the served sequence and the logits row behind every
    emitted token."""
    rows = []
    emit = eng._emit_tokens
    dispatched = eng.dispatch_count

    def record(logits, pairs):
        rows.extend(np.asarray(logits[i]) for i, _ in pairs)
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
    assert eng.cache["kv"].shape[0] == 2 * cfg.num_layers
    return prompt + out, np.stack(rows)


def _reference_rows(reference, params, seq):
    want = np.asarray(reference.forward(CFG, params, jnp.asarray(seq, jnp.int32),
                                        jnp.float32))
    return want[PROMPT_LEN - 1:len(seq) - 1]         # row i predicts i + 1


def test_engine_logits_match_the_reference(reference, params, shared_engine):
    seq, got = _serve_logits(shared_engine())
    want = _reference_rows(reference, params, seq)
    assert got.shape == want.shape == (NEW_TOKENS, VOCAB)
    np.testing.assert_allclose(got, want, rtol=0, atol=LOGIT_ATOL)


def _wiring(fault):
    """``lc._double_layer`` with one line wrong."""
    def layer(cfg, x, lp, attend, branch):
        eps = cfg.rms_norm_eps
        s0, s1 = lp["sub"]
        x_in = x
        x = x + attend(0, rmsnorm(x, s0["attn_norm"], eps), s0)
        h = rmsnorm(x, s0["mlp_norm"], eps)
        m, picks = branch(rmsnorm(x_in, s0["mlp_norm"], eps)
                          if fault == "fed_the_layers_input" else h)
        x = x + swiglu(h, s0["wd_gate"], s0["wd_up"], s0["wd_down"])
        if fault == "added_after_the_first_ffn":
            x, m = x + m, 0.0
        x = x + attend(1, rmsnorm(x, s1["attn_norm"], eps), s1)
        h = rmsnorm(x, s1["mlp_norm"], eps)
        return x + swiglu(h, s1["wd_gate"], s1["wd_up"], s1["wd_down"]) + m, picks
    return layer


def _one_cache_layer(scan):
    """``scan_layers_paged`` handing sublayer 1 sublayer 0's block table."""
    def wrong(layer_fn, x, layers, pool, block_tables, **kw):
        return scan(lambda x, lp, pool, tabs: layer_fn(x, lp, pool,
                                                       (tabs[0], tabs[0])),
                    x, layers, pool, block_tables, **kw)
    return wrong


CONTROLS = ["fed_the_layers_input", "added_after_the_first_ffn",
            "zero_picks_dropped", "one_cache_layer", "no_q_lora_scale",
            "no_kv_lora_scale", "picks_renormalised"]


@pytest.mark.parametrize("fault", CONTROLS)
def test_the_tolerance_catches_a_model_one_line_away(reference, params,
                                                     monkeypatch, fault):
    cfg = CFG
    if fault in ("fed_the_layers_input", "added_after_the_first_ffn"):
        monkeypatch.setattr(lc, "_double_layer", _wiring(fault))
    elif fault == "zero_picks_dropped":
        monkeypatch.setattr(experts, "_identity_part",
                            lambda h, *_: jnp.zeros_like(h))
    elif fault == "one_cache_layer":
        monkeypatch.setattr(paged, "scan_layers_paged",
                            _one_cache_layer(paged.scan_layers_paged))
    elif fault == "picks_renormalised":
        real = lc.routed_experts
        monkeypatch.setattr(lc, "routed_experts", lambda *a, **k: real(
            *a, **{**k, "renormalize": True}))
    else:
        cfg = dataclasses.replace(CFG, **{
            "mla_scale_q_lora" if fault == "no_q_lora_scale"
            else "mla_scale_kv_lora": False})
    seq, got = _serve_logits(_engine(params, cfg), cfg)   # another program
    want = _reference_rows(reference, params, seq)
    assert np.abs(got - want).max() > 10 * LOGIT_ATOL


# ------------------------------------------------------------------ the router
def test_the_router_by_hand():
    """One token over 3 routed + 2 zero-compute outputs, top-2: softmax over
    all five, the bias selects (output 3 over output 1) and never weighs, the
    weights are the softmax scores x 6 and are NOT renormalised."""
    logits = np.log(np.array([[4.0, 3.0, 1.0, 2.0, 1.0]], np.float32))
    bias = jnp.asarray([0.0, 0.0, 0.0, 0.15, 0.0], jnp.float32)
    topv, topi = experts._route(jnp.ones((1, 1), jnp.float32),
                                jnp.asarray(logits), 2, "softmax", bias, False,
                                6.0, 1e-9)
    s = np.array([4, 3, 1, 2, 1], np.float32) / 11
    assert list(np.asarray(topi[0])) == [0, 3]     # 3/11 < 2/11 + 0.15
    np.testing.assert_allclose(np.asarray(topv[0]), 6 * s[[0, 3]], rtol=1e-6)
    assert float(topv.sum()) == pytest.approx(6 * 6 / 11)   # not 6: no renorm
    # without the bias output 1 is picked; the weights stay the scores
    topv, topi = experts._route(jnp.ones((1, 1), jnp.float32),
                                jnp.asarray(logits), 2, "softmax", None, False,
                                6.0, 1e-9)
    assert list(np.asarray(topi[0])) == [0, 1]


# -------------------------------------------- zero_experts in both forms
def _case(rng, dtype, t, d, f, routed, zero, k, held):
    e = routed if held is None else held[2]
    cast = lambda a: jnp.asarray(a, jnp.float32).astype(dtype)  # noqa: E731
    return dict(
        h=cast(rng.standard_normal((t, d))),
        router=jnp.asarray(rng.standard_normal((d, routed + zero)), jnp.float32),
        bias=jnp.asarray(rng.standard_normal(routed + zero) * 0.01, jnp.float32),
        w_gate=cast(rng.standard_normal((e, d, f)) * 0.2),
        w_up=cast(rng.standard_normal((e, d, f)) * 0.2),
        w_down=cast(rng.standard_normal((e, f, d)) * 0.2),
        k=k, routed=routed, zero=zero,
        held=None if held is None else held[:2])


def _by_token(c):
    """A loop over tokens and picks, float32: held experts computed, the
    others' picks skipped, zero-compute picks as ``w * h``."""
    h = np.asarray(c["h"], np.float32)
    topv, topi = experts._route(c["h"], c["router"], c["k"], "softmax",
                                c["bias"], False, 6.0, 1e-9)
    first = 0 if c["held"] is None else c["held"][0]
    out = np.zeros_like(h)
    for t in range(h.shape[0]):
        for w, i in zip(np.asarray(topv[t]), np.asarray(topi[t])):
            j = int(i) - first
            if i >= c["routed"]:
                out[t] += w * h[t]
            elif 0 <= j < c["w_up"].shape[0]:
                g, u, dn = (np.asarray(c[n][j], np.float32)
                            for n in ("w_gate", "w_up", "w_down"))
                a = h[t] @ g
                out[t] += w * ((a / (1 + np.exp(-a)) * (h[t] @ u)) @ dn)
    return out, np.asarray(topi)


@pytest.mark.parametrize("held", [None, (8, 32, 8), (24, 32, 8)],
                         ids=["all", "rank1", "rank3"])
@pytest.mark.parametrize("dtype,tol", [(jnp.float32, 1e-4), (jnp.bfloat16, 0.25)],
                         ids=["float32", "bfloat16"])
def test_zero_experts_in_both_forms_are_the_loop(monkeypatch, dtype, tol, held):
    c = _case(np.random.default_rng(3), dtype, 40, 32, 48, 32, 16, 6, held)
    want, topi = _by_token(c)
    assert (topi >= 32).any() and (topi < 32).any()
    call = lambda: experts.routed_experts(  # noqa: E731
        c["h"], c["router"], c["w_gate"], c["w_up"], c["w_down"], c["k"],
        bias=c["bias"], renormalize=False, scale=6.0, held=c["held"],
        zero_experts=c["zero"], count_picks=True)
    assert experts.expert_form(40, 32, 6) == "dense"
    dense, counts = call()
    monkeypatch.setattr(experts, "GROUPED_MIN_ROWS", 32)
    assert experts.expert_form(40, 32, 6) == "grouped"
    grouped, counts_g = call()
    for got in (dense, grouped):
        assert got.dtype == dtype
        np.testing.assert_allclose(np.asarray(got, np.float32), want,
                                   atol=tol, rtol=tol)
    first, e = (0, 32) if held is None else (held[0], 8)
    np.testing.assert_array_equal(np.asarray(counts), np.asarray(counts_g))
    np.testing.assert_array_equal(np.asarray(counts[:, 0]), (topi >= 32).sum(-1))
    np.testing.assert_array_equal(
        np.asarray(counts[:, 1]), ((topi >= first) & (topi < first + e)).sum(-1))
    np.testing.assert_allclose(
        np.asarray(experts.routed_experts_einsum(
            c["h"], c["router"], c["w_gate"], c["w_up"], c["w_down"], c["k"],
            bias=c["bias"], renormalize=False, scale=6.0, held=c["held"],
            zero_experts=c["zero"]), np.float32),
        np.asarray(dense, np.float32), atol=0, rtol=0)


# the router arguments of the four families that serve through
# ``routed_experts`` today: (kwargs, gated, top_k, routed, held, router_h)
TODAY = {
    "mixtral": (dict(), True, 2, 8, None, False),
    "moonlight": (dict(scoring="sigmoid", renormalize=True, scale=2.446,
                       eps=1e-20), True, 6, 64, None, False),
    "nemotron": (dict(scoring="sigmoid", renormalize=True, scale=5.0,
                      eps=1e-20), False, 22, 512, (0, 512, 128), True),
    "deepseek_v32": (dict(scoring="sigmoid", renormalize=True, scale=2.5,
                          eps=1e-20, groups=(8, 4)), True, 8, 256,
                     (0, 256, 16), False),
}


@pytest.mark.parametrize("form", ["dense", "grouped"])
@pytest.mark.parametrize("family", sorted(TODAY))
def test_without_zero_experts_the_layer_is_todays(monkeypatch, family, form):
    """``zero_experts=0`` (the default) is the composition the function was
    before it knew of them, ``_route`` then the form's own function on the
    same arguments: bit-equal at each family's router arguments, in both
    forms, and one output only."""
    kw, gated, k, routed, held, latent = TODAY[family]
    rng = np.random.default_rng(11)
    t, d, f = 24, 32, 16
    e = routed if held is None else held[2]
    h = jnp.asarray(rng.standard_normal((t, d)), jnp.bfloat16)
    router_h = (jnp.asarray(rng.standard_normal((t, 48)), jnp.bfloat16)
                if latent else None)
    router = jnp.asarray(rng.standard_normal(((48 if latent else d), routed)),
                         jnp.float32)
    bias = (jnp.asarray(rng.standard_normal(routed) * 0.01, jnp.float32)
            if family != "mixtral" else None)
    w_gate = (jnp.asarray(rng.standard_normal((e, d, f)) * 0.2, jnp.bfloat16)
              if gated else None)
    w_up = jnp.asarray(rng.standard_normal((e, d, f)) * 0.2, jnp.bfloat16)
    w_down = jnp.asarray(rng.standard_normal((e, f, d)) * 0.2, jnp.bfloat16)
    share = None if held is None else held[:2]
    if form == "grouped":
        monkeypatch.setattr(experts, "GROUPED_MIN_ROWS", 16)
    assert experts.expert_form(t, routed, k) == form
    got = experts.routed_experts(h, router, w_gate, w_up, w_down, k, bias=bias,
                                 held=share, router_h=router_h, **kw)
    groups = kw.get("groups")
    topv, topi = experts._route(
        h if router_h is None else router_h, router, k,
        kw.get("scoring", "softmax"), bias, kw.get("renormalize", True),
        kw.get("scale", 1.0), kw.get("eps", 1e-9), groups)
    if form == "dense":
        want = experts._einsum_experts(h, topv, topi, w_gate, w_up, w_down,
                                       share)
    else:
        want = experts._grouped_experts(h, topv, topi, w_gate, w_up, w_down, 0,
                                        e, share)
    assert isinstance(got, jax.Array) and got.dtype == jnp.bfloat16
    np.testing.assert_array_equal(np.asarray(got, np.float32),
                                  np.asarray(want, np.float32))


# ----------------------------------------------- the share ties to the model
def test_four_ranks_parts_and_the_identity_part_once_add_up(reference):
    """32 routed + 16 zero-compute experts, top-6, over 4 ranks of 8: each
    rank's held part (its layer's output less the identity part every rank
    computes alike) summed, plus the identity part ONCE, is the uncut
    reference's expert branch."""
    whole = lc.LongcatFlashConfig(
        vocab_size=VOCAB, hidden_size=32, ffn_hidden_size=48,
        expert_ffn_hidden_size=24, num_layers=1, num_heads=2, kv_lora_rank=32,
        q_lora_rank=24, qk_nope_head_dim=16, qk_rope_head_dim=16, v_head_dim=16,
        num_experts=32, zero_expert_num=16, top_k=6)
    lp = jax.tree_util.tree_map(
        lambda a: a[0], lc.init_params(whole, jax.random.PRNGKey(3))["layers"])
    del lp["sub"]   # the expert branch alone
    # a router sharp enough that picks differ a token (std 0.02 would not be)
    lp["router"] = lp["router"] * 50
    h = jnp.asarray(np.random.default_rng(4).standard_normal((12, 32)),
                    jnp.float32)
    want = np.asarray(reference._scmoe(whole, h, lp, jnp.float32))
    _, picks = reference.route(whole, h, lp)
    assert (np.asarray(picks) >= 32).any() and (np.asarray(picks) < 32).any()
    topv, topi = experts._route(h, lp["router"], 6, "softmax",
                                lp["router_bias"], False, 6.0, 1e-9)
    identity = np.asarray(experts._identity_part(h, topv, topi, 32))
    assert np.abs(identity).max() > 0
    parts = 0
    for rank in range(4):
        cut = dataclasses.replace(whole, experts_held=8, expert_rank=rank)
        assert cut.held_share == (8 * rank, 32)
        mine = {**lp, **{k: lp[k][8 * rank:8 * rank + 8]
                         for k in ("w_gate", "w_up", "w_down")}}
        got = np.asarray(lc._experts(cut, h, mine,
                                     experts.routed_experts_einsum))
        np.testing.assert_allclose(
            got, np.asarray(reference._scmoe(cut, h, mine, jnp.float32)),
            atol=1e-6)
        parts = parts + got - identity
    np.testing.assert_allclose(parts + identity, want, rtol=0, atol=2e-6)
    assert lc.num_params(cut) < lc.num_params(whole)
    assert reference.active_params(cut) < reference.active_params(whole)


# ------------------------------- both sublayers' rows through the cache manager
def test_a_prefix_hit_restores_both_sublayers_rows(params, shared_engine):
    """Two prompts that share 16 tokens (4 whole blocks): the second splices
    the first's blocks in, all 2 x num_layers block layers of them, and
    serves the tokens an engine without the prefix cache serves."""
    rng = np.random.default_rng(7)
    shared = list(rng.integers(1, VOCAB, 16))
    prompts = {"a": shared + list(rng.integers(1, VOCAB, 5)),
               "b": shared + list(rng.integers(1, VOCAB, 7))}
    want = {}
    for uid, prompt in prompts.items():
        eng = shared_engine()
        eng.put(uid, prompt, max_new_tokens=4)
        want[uid] = eng.generate_all()[uid]
    eng = _engine(params, enable_prefix_cache=True)
    eng.put("a", prompts["a"], max_new_tokens=4)
    assert eng.generate_all()["a"] == want["a"]
    eng.put("b", prompts["b"], max_new_tokens=4)
    assert eng.generate_all()["b"] == want["b"]
    assert eng.prefix_hits == 1 and eng.prefix_tokens_reused == 16
    # bytes a cached token come from the leaf: 4 block layers x 128 lanes x 4 B
    assert eng.kv_bytes_per_token() == 2 * CFG.num_layers * CFG.row_lanes * 4


def test_pool_pressure_and_containment_recompute_both_sublayers_rows(
        params, shared_engine):
    """A pool too small for two sequences at once: the second waits for the
    first's blocks and is computed over them (every block layer holds the
    first's stale rows) to the tokens an engine with room serves; then crash
    containment (a fresh cache) and the same requests again."""
    rng = np.random.default_rng(8)
    prompts = {u: list(rng.integers(1, VOCAB, 14)) for u in ("a", "b")}
    roomy = shared_engine()
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
    used = np.asarray(jnp.abs(tight.cache["kv"][:, 1:]).max(axis=(1, 2, 3)))
    assert used.shape == (2 * CFG.num_layers,) and (used > 0).all()
    for uid, prompt in prompts.items():
        tight.put(uid + "2", prompt, max_new_tokens=10)
    tight.step()
    assert tight.reset_state() == 2
    assert float(jnp.abs(tight.cache["kv"]).max()) == 0
    for uid, prompt in prompts.items():
        tight.put(uid, prompt, max_new_tokens=10)
    again = tight.generate_all()
    assert {uid: again[uid] for uid in prompts} == want


# ------------------------------------------------- what a step hands back
def test_the_engine_reports_what_the_router_picked(reference, params,
                                                   monkeypatch):
    """The device-resident step sums the model's counts over its real rows
    and hands them back behind the picked tokens; the engine puts every
    step's counts on ONE later ``engine/dispatch`` span and on ``/metrics``.
    They equal the counts recomputed from the reference's router over the
    positions the engine scheduled."""
    from deepspeed_tpu import telemetry
    from deepspeed_tpu.inference import ragged

    seen = []
    real = ragged.span

    def spy(name, **args):
        if name == "engine/dispatch":
            seen.append(args)
        return real(name, **args)

    monkeypatch.setattr(ragged, "span", spy)
    telemetry.configure(enabled=True)
    try:
        eng = _engine(params, device_state=True)
        assert eng.spec.step_counters == lc.STEP_COUNTERS
        prompt = list(np.random.default_rng(5).integers(1, VOCAB, PROMPT_LEN))
        eng.put("s", prompt, max_new_tokens=NEW_TOKENS)
        out = eng.generate_all()["s"]
        metrics = telemetry.snapshot()["metrics"]
    finally:
        telemetry.configure(enabled=False)
    n = eng.tokens_scheduled            # positions 0 .. n - 1, once each
    assert PROMPT_LEN + NEW_TOKENS - 1 <= n <= PROMPT_LEN + NEW_TOKENS
    picks = np.asarray(reference.router_picks(
        CFG, params, jnp.asarray((prompt + out)[:n], jnp.int32)))
    assert picks.shape == (CFG.num_layers, n, CFG.top_k)
    want = {"moe_picks": picks.size,
            "moe_zero_picks": int((picks >= CFG.num_experts).sum()),
            "moe_held_picks": int((picks < CFG.num_experts).sum())}
    assert 0 < want["moe_zero_picks"] < want["moe_picks"]
    assert eng.step_counts == want
    # the first span can carry nothing yet; what the spans carried plus what
    # no span has carried is everything, each step's counts once
    assert all(set(lc.STEP_COUNTERS) <= set(a) for a in seen)
    assert seen[0]["moe_picks"] == 0
    for name in lc.STEP_COUNTERS:
        assert sum(a[name] for a in seen) + eng._counts_unspanned[name] \
            == want[name]
        series = metrics[f"inference_{name}_total"]["series"]
        assert sum(s["value"] for s in series) == want[name]
    # a span's counts are whole steps': tokens x top_k x layers each
    per_token = CFG.top_k * CFG.num_layers
    carried = [a["moe_picks"] for a in seen if a["moe_picks"]]
    assert carried and all(c % per_token == 0 for c in carried)


def test_a_family_without_step_counters_hands_back_nothing(monkeypatch):
    from deepspeed_tpu.inference import ragged
    from deepspeed_tpu.models import deepseek

    seen = []
    real = ragged.span
    monkeypatch.setattr(ragged, "span", lambda name, **a: (
        seen.append(a) if name == "engine/dispatch" else None, real(name, **a))[1])
    plain = RaggedInferenceEngine(
        lambda ctx: deepseek.build(deepseek.DeepseekConfig.tiny(VOCAB), ctx=ctx),
        dtype=jnp.float32, seed=0, ragged_config=RaggedConfig(
            max_tokens_per_step=8, max_seqs=2, block_size=4, num_blocks=33,
            max_blocks_per_seq=8, prefill_tile=4))
    plain.put("s", list(range(1, 9)), max_new_tokens=2)
    plain.generate_all()
    assert plain.spec.step_counters == () and plain.step_counts == {}
    assert seen and not any(k.startswith("moe_") for a in seen for k in a)
    fn = next(iter(plain._dev_step_jits.values()))
    t = next(iter(plain._dev_step_jits))[0]
    picked = jax.eval_shape(fn, plain.params, plain.cache, plain._dev_state,
                            plain._bt_dev, jnp.zeros(7 * t, jnp.int32),
                            plain._sample_root)[0]
    assert picked.shape == (t,)


# --------------------------------------------------------------- what raises
def test_what_raises_raises_by_name(params):
    with pytest.raises(NotImplementedError, match="quantized latent pool"):
        _engine(params, quant="int8")
    with pytest.raises(NotImplementedError, match="zero experts of type"):
        lc.LongcatFlashConfig(zero_expert_type="copy")
    with pytest.raises(ValueError, match="experts_held"):
        lc.LongcatFlashConfig(experts_held=5)
    with pytest.raises(ValueError, match="top_k"):
        lc.LongcatFlashConfig(num_experts=4, zero_expert_num=2, top_k=7)
    with pytest.raises(ValueError, match="no multiple of a layer's 2"):
        paged.scan_layers_paged(
            lambda x, lp, pool, tabs: (x, pool), jnp.zeros(3), {"w": jnp.zeros(3)},
            {"kv": jnp.zeros((3, 2, 4, 8))}, jnp.zeros((2, 2), jnp.int32),
            block_layers=2)
    h = jnp.zeros((4, 8))
    with pytest.raises(ValueError, match="zero-compute experts"):
        experts.routed_experts(h, jnp.zeros((8, 6)), jnp.zeros((4, 8, 8)),
                               jnp.zeros((4, 8, 8)), jnp.zeros((4, 8, 8)), 2,
                               zero_experts=4)
    from deepspeed_tpu.models import deepseek
    with pytest.raises(ValueError, match="mla_scale_q_lora"):
        deepseek.DeepseekConfig(mla_scale_q_lora=True)
