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
