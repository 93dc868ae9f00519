"""The two forms of the serving paths' expert FFN (``models/experts.py``):
the grouped form (sort the picks by expert, ``ops/pallas/moe_gmm.py``,
un-sort) against the all-experts einsum, the rule that picks between them as
data, and a ragged engine on either side of it."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deepspeed_tpu.inference import ragged
from deepspeed_tpu.inference.ragged import RaggedConfig, RaggedInferenceEngine
from deepspeed_tpu.models import deepseek, experts, mixtral
from deepspeed_tpu.ops.pallas.moe_gmm import ROW_ALIGN, ffn_tile, grouped_swiglu

CROSS = experts.GROUPED_MIN_ROWS

# the two families' router settings (``routed_experts``' keyword arguments)
ROUTERS = {
    "softmax_top2_of_8": dict(e=8, k=2, kw={}),
    "sigmoid_bias_scale_top6_of_64": dict(
        e=64, k=6, kw=dict(scoring="sigmoid", renormalize=True, scale=2.446,
                           eps=1e-20)),
}
# rows: just under, at and just over the crossover, not a multiple of the
# kernel's row pass, and more than one call of the kernel takes
ROWS = [CROSS - 1, CROSS, CROSS + 1, 300, 600]
EDGES = ["plain", "unpicked_expert", "one_expert_for_all", "padding_rows"]
D, F = 32, 64


def _case(router: str, t: int, edge: str, dtype):
    r = ROUTERS[router]
    e, k = r["e"], r["k"]
    ks = jax.random.split(jax.random.PRNGKey(t), 6)
    # non-negative activations, so a router column of one sign decides
    h = jnp.abs(jax.random.normal(ks[0], (t, D), jnp.float32))
    router_w = jax.random.normal(ks[1], (D, e), jnp.float32) * 0.1
    if edge == "unpicked_expert":
        router_w = router_w.at[:, 3].set(-10.0)
    elif edge == "one_expert_for_all":
        router_w = router_w.at[:, 5].set(10.0)
    elif edge == "padding_rows":     # what rides along behind a step's tokens
        h = h.at[t - 17:].set(0.0)
    kw = dict(r["kw"])
    if "scoring" in kw:
        kw["bias"] = jax.random.normal(ks[5], (e,), jnp.float32) * 0.1
    w = [(jax.random.normal(ks[2 + i], shape, jnp.float32)
          * shape[1] ** -0.5).astype(dtype)
         for i, shape in enumerate([(e, D, F), (e, D, F), (e, F, D)])]
    return h.astype(dtype), router_w, w, k, kw


@pytest.mark.parametrize("edge", EDGES)
@pytest.mark.parametrize("t", ROWS)
@pytest.mark.parametrize("router", sorted(ROUTERS))
def test_grouped_form_equals_the_einsum_form(router, t, edge):
    """Same routing, same operands, every pick computed: the grouped form
    differs from the einsum by rounding only (float32: the order of a sum;
    bfloat16: the einsum rounds each expert's result before combining)."""
    for dtype, tol in ((jnp.float32, 2e-5), (jnp.bfloat16, 4e-2)):
        h, router_w, w, k, kw = _case(router, t, edge, dtype)
        topv, topi = experts._route(
            h, router_w, k, kw.get("scoring", "softmax"), kw.get("bias"),
            kw.get("renormalize", True), kw.get("scale", 1.0),
            kw.get("eps", 1e-9))
        if edge == "unpicked_expert":
            assert not bool(jnp.any(topi == 3))
        if edge == "one_expert_for_all":
            assert bool(jnp.all(jnp.any(topi == 5, axis=1)))
        e = ROUTERS[router]["e"]
        want = np.asarray(experts._einsum_experts(h, topv, topi, *w),
                          np.float32)
        got = np.asarray(jax.jit(experts._grouped_experts, static_argnums=7)(
            h, topv, topi, *w, 0, e), np.float32)
        assert np.isfinite(got).all()
        np.testing.assert_allclose(got, want, atol=tol * np.abs(want).max())
        # and through the rule, whichever side of it ``t`` is on
        ruled = np.asarray(experts.routed_experts(h, router_w, *w, k, **kw),
                           np.float32)
        dense = experts.expert_form(t, e, k) == "dense"
        np.testing.assert_array_equal(ruled, want if dense else got)
        # the same layer addressed inside every layer's weights (a scan's
        # ``expert_stacks``): layer 1 of 2
        stacks = [jnp.concatenate([jnp.flip(a, 0), a]) for a in w]
        stacked = experts.routed_experts(
            h, router_w, *w, k, stacked=(*stacks, jnp.int32(e)), **kw)
        np.testing.assert_array_equal(np.asarray(stacked, np.float32), ruled)


# ------------------------------------------ one rank's share (``held``)
# the layer holds experts 4 .. 4 + e - 1 of the 16 the router scores. How the
# picks fall: as the seed has it; every pick on a held expert (the row buffer
# full: the worst case stays exact); none; one held expert picked by every row
ROUTINGS = ["near_uniform", "every_pick_held", "no_pick_held",
            "one_expert_for_all"]
# rows of the step (over 512: two calls under ``lax.map``), held experts,
# picks a token, gated, gate, zero-compute outputs. ``two_calls`` is also the
# geometry whose last block of sorted rows reaches past the buffer's end when
# every pick is held (a pass of 64 rows: 1,072 + 64 rows, 1,056 of them real)
HELD = {
    "silu": dict(t=400, e=4, k=3, gated=True, act="silu", zero=0),
    "relu": dict(t=400, e=4, k=3, gated=True, act="relu", zero=0),
    "ungated": dict(t=400, e=4, k=3, gated=False, act="silu", zero=0),
    "zero_experts": dict(t=400, e=4, k=3, gated=True, act="silu", zero=8),
    "two_calls": dict(t=1024, e=3, k=2, gated=True, act="silu", zero=0),
}
ROUTED, FIRST = 16, 4


def _held_case(routing: str, geometry: str):
    g = HELD[geometry]
    t, e, k = g["t"], g["e"], g["k"]
    ks = jax.random.split(jax.random.PRNGKey(t + e), 5)
    h = jnp.abs(jax.random.normal(ks[0], (t, D), jnp.float32))
    router_w = jax.random.normal(ks[1], (D, ROUTED + g["zero"]),
                                 jnp.float32) * 0.1
    mine = slice(FIRST, FIRST + e)
    if routing == "every_pick_held":
        # unevenly, so that the experts' ends are not multiples of ROW_ALIGN
        router_w = router_w.at[:, mine].add(10.0)
    elif routing == "no_pick_held":
        router_w = router_w.at[:, mine].set(-10.0)
    elif routing == "one_expert_for_all":
        router_w = router_w.at[:, FIRST + 1].set(10.0)
    w = [jax.random.normal(ks[2 + i], shape, jnp.float32) * shape[1] ** -0.5
         for i, shape in enumerate([(e, D, F), (e, D, F), (e, F, D)])]
    if not g["gated"]:
        w[0] = None
    kw = dict(held=(FIRST, ROUTED), zero_experts=g["zero"],
              gate_act=g["act"])
    return h, router_w, w, k, kw


@pytest.mark.parametrize("geometry", sorted(HELD))
@pytest.mark.parametrize("routing", ROUTINGS)
def test_held_grouped_form_equals_the_einsum_form(routing, geometry,
                                                  monkeypatch):
    """With ``held`` the grouped form gathers and combines the rows that
    exist, a block of sorted rows at a time: every pick of a held expert is
    computed at any routing, and what the never-initialised part of the row
    buffer holds (NaN here) reaches nothing."""
    monkeypatch.setattr(jax.lax, "empty",
                        lambda shape, dtype: jnp.full(shape, jnp.nan, dtype))
    h, router_w, w, k, kw = _held_case(routing, geometry)
    g = HELD[geometry]
    _, topi = experts._route(h, router_w, k, "softmax", None, True, 1.0, 1e-9)
    held_picks = int(jnp.sum((topi >= FIRST) & (topi < FIRST + g["e"])))
    assert {"every_pick_held": held_picks == topi.size,
            "no_pick_held": held_picks == 0,
            "one_expert_for_all": bool(jnp.all(jnp.any(topi == FIRST + 1, 1))),
            "near_uniform": 0 < held_picks < topi.size // 2}[routing]
    assert experts.expert_form(g["t"], ROUTED, k) == "grouped"
    want = np.asarray(experts.routed_experts_einsum(h, router_w, *w, k, **kw))
    got = np.asarray(jax.jit(
        lambda h, r, *w: experts.routed_experts(h, r, *w, k, **kw))(
            h, router_w, *w))
    assert np.isfinite(got).all()
    np.testing.assert_allclose(got, want,
                               atol=2e-5 * max(np.abs(want).max(), 1e-3))


@pytest.mark.parametrize("held", [(0, ROUTED), None], ids=["held", "every_expert"])
def test_rows_that_pad_a_second_call_get_no_row(monkeypatch, held):
    """A 528-row step (the window cell's four tiles beside 16 decode rows) is
    two calls of 512 rows, the second 16 rows and 496 of padding. The rank
    that holds expert 0 must not take the padding's picks for its own: they
    were 496 x top_k rows of one expert, more than a call's 512 rows, which
    is all the kernel's VMEM holds of an expert. A layer that holds EVERY
    routed expert gives a padding row one row of each of experts 0 .. top_k -
    1, as a real row picks (ROADMAP D13: the LFM2 cell's steps are 513-1,024
    rows, and on the chip the kernel's DMA ran out of bounds)."""
    seen = []

    def counting(x, w_gate, w_up, w_down, row0, counts, tm, **kw):
        jax.debug.callback(lambda c: seen.append(np.asarray(c)), counts)
        return jnp.zeros(x.shape, jnp.float32)

    monkeypatch.setattr(experts, "grouped_swiglu", counting)
    t, e, k = 528, 4 if held else ROUTED, 3
    ks = jax.random.split(jax.random.PRNGKey(0), 3)
    h = jax.random.normal(ks[0], (t, D), jnp.float32)
    topi = jnp.argsort(jax.random.normal(ks[1], (t, ROUTED)), axis=1)[:, :k]
    topv = jnp.full((t, k), 1.0 / k)
    w = [jnp.zeros(s_) for s_ in [(e, D, F), (e, D, F), (e, F, D)]]
    jax.block_until_ready(experts._grouped_experts(
        h, topv, topi.astype(jnp.int32), *w, 0, e, held))
    jax.effects_barrier()
    want = [np.bincount(np.asarray(topi[a:b]).ravel(), minlength=ROUTED)[:e]
            for a, b in ((0, 512), (512, t))]
    if held is None:
        want[1][:k] += 2 * 512 - t
    assert len(seen) == 2
    for got in seen:
        assert got.max() <= 512
        assert any((got == w_).all() for w_ in want), (got, want)


def _eqns(jaxpr):
    """Every equation of a jaxpr and of what it calls, a kernel's body left
    out (``moe_gmm`` loops over an expert's passes)."""
    for eqn in jaxpr.eqns:
        yield eqn
        if eqn.primitive.name == "pallas_call":
            continue
        for sub in jax.core.jaxprs_in_params(eqn.params):
            yield from _eqns(sub)


@pytest.mark.parametrize("held", [None, (FIRST, ROUTED)],
                         ids=["every_expert", "held"])
def test_what_a_grouped_layer_moves_around_the_kernel(held):
    """A 400-row step. The layer holds every routed expert: the picks' rows
    are gathered whole and come back whole, ``[T x top_k, D]``, and nothing
    loops. It holds a share: no array of ``T x top_k`` float32 rows exists and
    no gather makes the row buffer; two loops move blocks of sorted rows."""
    t, k, e = 400, 3, ROUTED if held is None else 4
    sh = jax.ShapeDtypeStruct
    args = (sh((t, D), jnp.float32), sh((D, ROUTED), jnp.float32),
            *[sh(s, jnp.float32) for s in [(e, D, F), (e, D, F), (e, F, D)]])
    jaxpr = jax.make_jaxpr(lambda h, r, *w: experts.routed_experts(
        h, r, *w, k, held=held))(*args).jaxpr
    eqns = list(_eqns(jaxpr))
    kernel, = [q for q in eqns if q.primitive.name == "pallas_call"]
    rows = kernel.outvars[0].aval.shape[0]
    assert rows > t * k
    made = [(q.primitive.name, v.aval.shape, v.aval.dtype)
            for q in eqns for v in q.outvars if hasattr(v.aval, "shape")]
    unsorted = [m for m in made if m[1] == (t * k, D) and m[2] == jnp.float32]
    buffers = [m for m in made if m[0] == "gather" and m[1] == (rows, D)]
    loops = [q for q in eqns if q.primitive.name == "while"]
    if held is None:
        assert [m[0] for m in unsorted] == ["gather"]
        assert len(buffers) == 1 and not loops
    else:
        assert not unsorted and not buffers and len(loops) == 2
        assert ("gather", (experts._SORTED_BLOCK, D), jnp.float32) in made


def test_the_kernel_takes_rows_past_an_experts_end_in_its_stride():
    """A pass always moves ``tm`` rows: what an expert's last pass writes
    into the next experts' rows is overwritten by them, in order."""
    e, tm = 4, 32
    counts = jnp.asarray([40, 0, 7, 33], jnp.int32)
    aligned = -(-counts // ROW_ALIGN) * ROW_ALIGN
    row0 = jnp.cumsum(aligned) - aligned
    rows = int(aligned.sum()) + tm
    ks = jax.random.split(jax.random.PRNGKey(0), 4)
    x = jax.random.normal(ks[0], (rows, D), jnp.float32)
    wg, wu = (jax.random.normal(k, (e, D, F), jnp.float32) for k in ks[1:3])
    wd = jax.random.normal(ks[3], (e, F, D), jnp.float32)
    y = grouped_swiglu(x, wg, wu, wd, row0, counts, tm, max_rows=40)
    for i in range(e):
        r0, n = int(row0[i]), int(counts[i])
        xs = x[r0:r0 + n]
        want = (jax.nn.silu(xs @ wg[i]) * (xs @ wu[i])) @ wd[i]
        np.testing.assert_allclose(np.asarray(y[r0:r0 + n]), np.asarray(want),
                                   rtol=1e-4, atol=1e-4)


def test_ffn_tile_reads_a_weight_once_at_both_geometries():
    assert ffn_tile(4096, 14336, 2) == 512      # Mixtral: 28 tiles of 25 MB
    assert ffn_tile(2048, 1408, 2) == 1408      # Moonlight: an expert whole
    assert ffn_tile(D, F, 4) == F
    with pytest.raises(ValueError):
        ffn_tile(2 ** 20, 1000, 2)


# ------------------------------------------------------- the rule, as data
# every step program the benchmark's cells dispatch (PERF.md section 5):
# ``ragged_step_d<rows>_t<tiles>`` runs ``rows + 128 * tiles`` tokens
MIXTRAL, MOONLIGHT = (8, 2), (64, 6)
PROGRAMS = [
    # the long-document cell: every dispatch a t3 program
    ("d4_t3", MIXTRAL, "grouped"), ("d8_t3", MIXTRAL, "grouped"),
    ("d16_t3", MIXTRAL, "grouped"),
    # the Moonlight cell's mixed steps and its decode-only program
    ("d128_t3", MOONLIGHT, "grouped"), ("d128_t2", MOONLIGHT, "grouped"),
    ("d128_t0", MOONLIGHT, "dense"),
    # the decode-only programs of the two chat cells with routed experts
    ("d4_t0", MIXTRAL, "dense"), ("d8_t0", MIXTRAL, "dense"),
    ("d16_t0", MIXTRAL, "dense"), ("d32_t0", MIXTRAL, "dense"),
    ("d64_t0", MIXTRAL, "dense"), ("d128_t0", MIXTRAL, "dense"),
]


@pytest.mark.parametrize("program,geometry,form", PROGRAMS,
                         ids=[f"{p}-{g[0]}x{g[1]}" for p, g, _ in PROGRAMS])
def test_the_rule_on_the_programs_the_cells_dispatch(program, geometry, form):
    rows, tiles = (int(s[1:]) for s in program.split("_"))
    assert experts.expert_form(rows + 128 * tiles, *geometry) == form


# --------------------------------------------- an engine on either side
MIX = dataclasses.replace(mixtral.MixtralConfig.tiny(89), max_seq_len=512)
DSK = dataclasses.replace(deepseek.DeepseekConfig.tiny(89), max_seq_len=512)
BUILD = {"mixtral": lambda ctx: mixtral.build(MIX, ctx=ctx),
         "deepseek": lambda ctx: deepseek.build(DSK, ctx=ctx)}


def _engine(family):
    return RaggedInferenceEngine(
        model=BUILD[family], dtype=jnp.float32, seed=0,
        ragged_config=RaggedConfig(
            max_tokens_per_step=320, max_seqs=2, block_size=16,
            num_blocks=65, max_blocks_per_seq=32, prefill_tile=64))


def _served(family, monkeypatch, prompt_len):
    """Greedy tokens of one prompt and the ``engine/dispatch`` arguments."""
    seen = []
    real = ragged.span

    def recording(name, **args):
        if name == "engine/dispatch":
            seen.append(args)
        return real(name, **args)

    monkeypatch.setattr(ragged, "span", recording)
    eng = _engine(family)
    rng = np.random.default_rng(prompt_len)
    eng.put("a", list(rng.integers(1, 89, (prompt_len,))), max_new_tokens=4)
    return eng.generate_all()["a"], seen


@pytest.mark.parametrize("prompt_len,form", [(300, "grouped"), (40, "dense")])
@pytest.mark.parametrize("family", sorted(BUILD))
def test_an_engine_step_on_either_side_of_the_crossover(
        family, prompt_len, form, monkeypatch):
    """The served greedy tokens are the einsum path's, and the dispatch
    span's ``moe`` is what the model's own trace took for that step."""
    taken = []
    real = experts.expert_form

    def noting(rows, e, k):
        taken.append((rows, real(rows, e, k)))
        return taken[-1][1]

    monkeypatch.setattr(experts, "expert_form", noting)
    tokens, spans = _served(family, monkeypatch, prompt_len)
    prefill = spans[0]
    assert prefill["moe"] == form
    assert [s["moe"] for s in spans[1:]] == ["dense"] * (len(spans) - 1)
    # the rows the model's trace asked the rule about are the spans' programs'
    by_rows = dict(taken)
    for s in spans:
        assert by_rows[s["tokens"] + s["pad"]] == s["moe"]
    def einsum_path(*args, stacked=None, **kw):   # the parent's
        return experts.routed_experts_einsum(*args, **kw)

    with monkeypatch.context() as m:
        m.setattr(mixtral, "routed_experts", einsum_path)
        m.setattr(deepseek, "routed_experts", einsum_path)
        want, _ = _served(family, m, prompt_len)
    assert len(tokens) == 4 and tokens == want


def test_a_family_without_routed_experts_writes_no_moe(monkeypatch):
    from deepspeed_tpu.models import llama

    cfg = llama.LlamaConfig(vocab_size=97, hidden_size=32,
                            intermediate_size=64, num_layers=2, num_heads=4,
                            num_kv_heads=2, max_seq_len=128)
    seen = []
    real = ragged.span
    monkeypatch.setattr(
        ragged, "span",
        lambda name, **a: (seen.append((name, a)), real(name, **a))[1])
    eng = RaggedInferenceEngine(
        lambda ctx: llama.build(cfg, ctx=ctx), dtype=jnp.float32, seed=0,
        ragged_config=RaggedConfig(max_tokens_per_step=32, max_seqs=4,
                                   block_size=4, num_blocks=65,
                                   max_blocks_per_seq=16, prefill_tile=8))
    eng.put("a", [1, 2, 3], max_new_tokens=2)
    eng.generate_all()
    dispatches = [a for n, a in seen if n == "engine/dispatch"]
    assert dispatches and all("moe" not in a for a in dispatches)
