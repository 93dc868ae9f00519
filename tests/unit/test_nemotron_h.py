"""``models/nemotron_h.py`` at a small size on the CPU, seeded weights: what is
served (prefill, then decode, through the paged pool AND the slot state)
against the plain reference ``benchmark/reference/nemotron_h.py``; one rank's
share of the experts; the two-matrix ``relu**2`` expert in both forms; what an
engine refuses for a model with slot state.

Logits are compared, not tokens. Tolerance 2e-4 (float32 everywhere here): the
program runs a prompt as chunks (matmuls inside a chunk, the state carried
between them) and the reference as a scan over tokens, so the same sums are
taken in another order; observed differences are under 1e-6 on logits of
magnitude 0.6.
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
from deepspeed_tpu.models import experts, nemotron_h
from deepspeed_tpu.models.paged import SLOTS, stack_plan

REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
ATOL = 2e-4


def _reference():
    spec = importlib.util.spec_from_file_location(
        "reference_nemotron_h",
        os.path.join(REPO, "benchmark", "reference", "nemotron_h.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


REF = _reference()
CFG = nemotron_h.NemotronHConfig.tiny()        # "*EMEM", 4 of 16 experts held


@pytest.fixture(scope="module")
def params():
    return nemotron_h.init_params(CFG, jax.random.PRNGKey(1))


def _engine(params, device_state=False, cfg=CFG, **sizes):
    rc = RaggedConfig(**{**dict(
        max_tokens_per_step=32, max_seqs=4, block_size=8, num_blocks=33,
        max_blocks_per_seq=8, prefill_tile=8, device_state=device_state),
        **sizes})
    return RaggedInferenceEngine(lambda ctx: nemotron_h.build(cfg, ctx=ctx), rc,
                                 dtype=jnp.float32, params=params)


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
    # two full tiles of one slot in one step
    "two_tiles_of_one_slot": ({}, [16], 3, None),
    # six requests over four slots: decode rows beside tiles, slots reused
    "mixed_steps": ({}, [5, 19, 37, 9, 26, 3], 6, None),
    # one slot: the second request starts from zeros where the first ended
    "slot_reused": ({"max_seqs": 1}, [11, 7], 5, None),
    # positions rewound mid-flight: the state restarts with a re-prefill
    "recovered_and_recomputed": ({}, [5, 19, 37, 9], 8, 4),
    # three decoders in a bucket of four: a padding row on the scratch slot
    "bucket_padding_rows": ({}, [6, 9, 4], 5, None),
}


def _serve(eng, prompts, new_tokens, recover_after=None):
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
    assert not np.asarray(slots["ssm"][:, -1]).any()
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


def test_padding_rows_leave_other_slots_alone(params):
    """A step of one real decode row and three padding rows: the slots that
    are not in the step keep their state bit for bit."""
    cache = nemotron_h.init_paged_cache(CFG, 9, 8, jnp.float32, num_slots=5)
    key = jax.random.PRNGKey(5)
    cache[SLOTS]["ssm"] = jax.random.normal(key, cache[SLOTS]["ssm"].shape
                                            ).at[:, -1].set(0.0)
    before = np.asarray(cache[SLOTS]["ssm"])
    tables = np.zeros((5, 2), np.int32)
    tables[2] = [3, 4]
    i32 = lambda a: jnp.asarray(a, jnp.int32)  # noqa: E731
    _, cache = nemotron_h.ragged_forward(
        CFG, params, i32([7, 0, 0, 0]), i32([2, 4, 4, 4]), i32([5, 0, 0, 0]),
        jnp.asarray(tables), cache,
        prefill_tiles=(4, i32([4]), i32([0]), i32([0]), 8))
    after = np.asarray(cache[SLOTS]["ssm"])
    np.testing.assert_array_equal(after[:, [0, 1, 3, 4]], before[:, [0, 1, 3, 4]])
    assert (after[:, 2] != before[:, 2]).any()


def test_plain_forward_is_the_reference(params):
    ids = jnp.asarray(_prompts([41], seed=3)[0])
    np.testing.assert_allclose(
        np.asarray(nemotron_h.forward(CFG, params, ids[None])[0]),
        np.asarray(REF.forward(CFG, params, ids)), atol=ATOL)


# --------------------------------------------------- one rank's share (E)
def test_four_ranks_parts_add_up_to_the_uncut_layer():
    """The share test of the model-configs guide, section 4: an ``E`` layer
    with all 16 experts against the four ranks' layers of 4 experts each,
    the same router. The ranks' routed parts add up (``W_lat_out`` is
    linear, so after it as well as before) and the shared expert counts
    once: the uncut layer of the reference."""
    whole = nemotron_h.NemotronHConfig.tiny(experts_held=None)
    full = jax.tree_util.tree_map(
        lambda a: a[0],
        nemotron_h.init_params(whole, jax.random.PRNGKey(2))["moe"])
    h = jnp.asarray(np.random.default_rng(1).standard_normal(
        (23, whole.hidden_size)), jnp.float32)
    want = np.asarray(REF._moe(whole, h, full, jnp.float32))
    routed = shared = 0.0
    for rank in range(4):
        cfg = nemotron_h.NemotronHConfig.tiny(expert_rank=rank)
        lp = {**full, "w_up": full["w_up"][4 * rank:4 * rank + 4],
              "w_down": full["w_down"][4 * rank:4 * rank + 4]}
        part, shared = nemotron_h.moe_parts(cfg, h, lp, experts.routed_experts)
        # a rank's own layer is what the reference computes for that rank
        np.testing.assert_allclose(np.asarray(part + shared),
                                   np.asarray(REF._moe(cfg, h, lp, jnp.float32)),
                                   atol=ATOL)
        routed = routed + part
    np.testing.assert_allclose(np.asarray(routed + shared), want, atol=ATOL)


@pytest.mark.parametrize("held", [None, (4, 16), (12, 16)],
                         ids=["all", "rank1", "rank3"])
def test_relu2_grouped_form_is_the_einsum_form(held, monkeypatch):
    """The two-matrix ``relu**2`` expert: the grouped kernel's walk over the
    sorted picks against the all-experts einsum, with a held share (the
    picks of absent experts get no row) and without."""
    rng = np.random.default_rng(7)
    t, d, f, routed, k = 40, 32, 48, 16, 6
    e = routed if held is None else 4
    h = jnp.asarray(rng.standard_normal((t, d)), jnp.float32)
    router = jnp.asarray(rng.standard_normal((d, routed)), jnp.float32)
    w_up = jnp.asarray(rng.standard_normal((e, d, f)) * 0.2, jnp.float32)
    w_down = jnp.asarray(rng.standard_normal((e, f, d)) * 0.2, jnp.float32)
    topv, topi = experts._route(h, router, k, "sigmoid", None, True, 5.0, 1e-20)
    dense = experts._einsum_experts(h, topv, topi, None, w_up, w_down, held)
    grouped = experts._grouped_experts(h, topv, topi, None, w_up, w_down, 0, e,
                                       held)
    np.testing.assert_allclose(np.asarray(grouped), np.asarray(dense),
                               atol=1e-4, rtol=1e-4)
    if held is not None:   # some picks are of absent experts, some are not
        present = (np.asarray(topi) >= held[0]) & (np.asarray(topi) < held[0] + e)
        assert present.any() and not present.all()
    # and through the rule: a step of 256 rows and more takes the kernel
    monkeypatch.setattr(experts, "GROUPED_MIN_ROWS", 32)
    served = experts.routed_experts(h, router, None, w_up, w_down, k,
                                    scoring="sigmoid", scale=5.0, eps=1e-20,
                                    held=held)
    np.testing.assert_allclose(np.asarray(served), np.asarray(dense),
                               atol=1e-4, rtol=1e-4)


# ------------------------------------------------------------ the kernel
def test_ssm_decode_kernel_is_the_xla_form():
    from deepspeed_tpu.ops.pallas.ssm import ssm_decode, ssm_decode_xla

    k = jax.random.split(jax.random.PRNGKey(0), 5)
    rows_n, n, hp, g, t = 10, 16, 256, 2, 4
    state = jax.random.normal(k[0], (rows_n, n, hp))
    rows = jnp.asarray([3, 7, 1, 9], jnp.int32)
    da = jax.random.uniform(k[1], (t, hp))
    dtx = jax.random.normal(k[2], (t, hp))
    bt = jax.random.normal(k[3], (t, n, g))
    ct = jax.random.normal(k[4], (t, n, g))
    got_s, got_y = ssm_decode(state, rows, da, dtx, bt, ct)
    want_s, want_y = ssm_decode_xla(state, rows, da, dtx, bt, ct)
    np.testing.assert_allclose(np.asarray(got_s), np.asarray(want_s), atol=1e-5)
    np.testing.assert_allclose(np.asarray(got_y), np.asarray(want_y), atol=1e-4)
    untouched = [0, 2, 4, 5, 6, 8]
    np.testing.assert_array_equal(np.asarray(got_s)[untouched],
                                  np.asarray(state)[untouched])


# -------------------------------------------------------------- the stack
def test_stack_plan():
    assert stack_plan("*EMEMEMEMEM") == ("*", "EM", 5)
    assert stack_plan("MEME") == ("", "ME", 2)
    assert stack_plan("M*EMEME") == ("M*E", "ME", 2)
    with pytest.raises(NotImplementedError, match="repeated period"):
        stack_plan("*EM")
    with pytest.raises(NotImplementedError, match="repeated period"):
        nemotron_h.build(nemotron_h.NemotronHConfig.tiny(pattern="M*E"))


def test_a_period_with_attention_inside(params):
    """``E*ME*M``: no leading layer, an attention layer and a Mamba layer in
    every repeat, each addressed in its own leaves."""
    cfg = nemotron_h.NemotronHConfig.tiny(pattern="E*ME*M")
    p = nemotron_h.init_params(cfg, jax.random.PRNGKey(4))
    eng = RaggedInferenceEngine(
        lambda ctx: nemotron_h.build(cfg, ctx=ctx),
        RaggedConfig(max_tokens_per_step=16, max_seqs=2, block_size=8,
                     num_blocks=17, max_blocks_per_seq=4, prefill_tile=8,
                     device_state=False),
        dtype=jnp.float32, params=p)
    prompt = _prompts([21], seed=9)[0]
    eng.put(0, prompt, max_new_tokens=3)
    got = list(eng.generate_all()[0])
    want = np.asarray(REF.forward(cfg, p, jnp.asarray(prompt + got)))
    assert got == want.argmax(-1)[len(prompt) - 1:len(prompt) + 2].tolist()


# ------------------------------------------------------------ the engine
def test_engine_accounts_blocks_and_slots_apart(params, engine_of):
    eng = engine_of()
    assert eng.kv_bytes_per_token() == REF.kv_bytes_per_token(CFG, 4)
    assert eng.state_bytes_per_slot() == REF.state_bytes_per_slot(CFG, 4)
    assert eng._block_bytes() == 8 * eng.kv_bytes_per_token()
    assert nemotron_h.num_params(CFG) == REF.num_params(CFG) == sum(
        a.size for a in jax.tree_util.tree_leaves(params))
    # a family with no slot state reports none
    from deepspeed_tpu.models import llama

    plain = RaggedInferenceEngine(
        lambda ctx: llama.build(llama.LlamaConfig.tiny(64), ctx=ctx),
        RaggedConfig(), dtype=jnp.float32)
    assert plain.state_bytes_per_slot() == 0 and not plain._slot_state


@pytest.mark.parametrize("family,max_seqs,ladder,programs", [
    ("nemotron_h", 128, [128], 7), ("nemotron_h", 256, [128, 256], 10),
    ("llama", 128, [4, 8, 16, 32, 64, 128], 27)])
def test_decode_ladder_starts_where_the_model_says(params, family, max_seqs,
                                                   ladder, programs):
    """``ModelSpec.decode_bucket_min``: the tiled step programs' decode
    buckets double from the model's smallest to ``max_seqs``. This family
    asks for 128 (a padding row costs its decode step 23 us on a v5e, a
    bucket four step programs: PERF.md section 6, PR 31); a family that says
    nothing keeps the ladder from 4. The zoo at the benchmark cell's sizes
    (512 tokens a step, tiles of 128) is what its set-up compiles."""
    sizes = dict(max_tokens_per_step=max(512, 2 * max_seqs), max_seqs=max_seqs,
                 block_size=8, num_blocks=max_seqs * 2 + 1,
                 max_blocks_per_seq=2, prefill_tile=128)
    if family == "nemotron_h":
        eng = _engine(params, **sizes)
    else:
        from deepspeed_tpu.models import llama

        eng = RaggedInferenceEngine(
            lambda ctx: llama.build(llama.LlamaConfig.tiny(64), ctx=ctx),
            RaggedConfig(**sizes), dtype=jnp.float32)
    assert eng._dec_buckets == ladder
    assert len(eng._step_zoo()) == programs


REFUSED = {
    "enable_prefix_cache": (dict(enable_prefix_cache=True), "snapshot"),
    "kv_tier": (dict(enable_prefix_cache=True, kv_tier=True), "snapshot"),
    "quantized_pool": (dict(quant="int8"), "quantized pool"),
    "untiled_prefill": (dict(prefill_tile=0), "tile"),
}


@pytest.mark.parametrize("what", REFUSED)
def test_engine_refuses_what_slot_state_cannot_restore(params, what):
    sizes, match = REFUSED[what]
    with pytest.raises((ValueError, NotImplementedError), match=match):
        _engine(params, **sizes)


def test_handoff_is_refused(engine_of):
    from deepspeed_tpu.inference.ragged import KVHandoff

    eng = engine_of()
    with pytest.raises(ValueError, match="KVHandoff"):
        eng.put(0, [1, 2, 3], handoff=True)
    record = KVHandoff.__new__(KVHandoff)
    with pytest.raises(ValueError, match="KVHandoff"):
        eng.import_handoff(record)
    assert eng.export_prefix([1, 2, 3]) is None and eng.import_prefix(None) == 0


def test_both_window_forms_serve_the_same_logits():
    """A convolution width that is whole float32 tiles (8 heads of 96 + 2 x
    2 groups x 64 states = 1,024 channels), served with the window leaf as
    ``init_paged_cache`` builds it (folded: a slot whole tiles) and with the
    leaf in rows (``models/paged``'s accessors read the form off the array):
    the same logits to the last bit, the same windows left behind, and the
    reference's logits. The accessors' own test is
    ``test_kimi_linear.py::test_window_leaf_round_trips``."""
    cfg = nemotron_h.NemotronHConfig.tiny(mamba_head_dim=96, ssm_state_size=64)
    params = nemotron_h.init_params(cfg, jax.random.PRNGKey(2))
    prompts = _prompts([5, 19, 13, 3], seed=3)
    engines = [_engine(params, cfg=cfg) for _ in range(2)]
    conv = engines[1].cache[SLOTS]["conv"]
    k1, w = cfg.conv_kernel - 1, cfg.conv_width
    assert w == 1024 and conv.shape[2:] == (k1 * 8, w // 8)
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
        want = _reference_rows(cfg, params, prompt + generated)
        for g in range(4):
            np.testing.assert_allclose(folded[(uid, g)],
                                       want[len(prompt) + g - 1], atol=ATOL)


# ------------------------------------------------- the shared Mamba-2 (PR 51)
# sha256 of the tiny preset's mixed step (4 decode rows + 2 tiles of 8) as
# lowered BEFORE the Mamba-2 code moved to ``models/mamba2.py`` (commit
# 9c8cdec, jax 0.9.0, CPU lowering). A change that is meant to alter this
# family's step program re-pins it and says so; one that is not does not get
# to. Re-pinned by PR 54 (it was ``eddfb049...5cf5c9``): ``mamba2.split``
# ties its three parts, which is ONE more ``stablehlo.optimization_barrier``
# (the scanned period's Mamba body) with the three slices in the parts'
# order before it; every other line is the parent's but for value numbers.
_MIXED_STEP_SHA256 = \
    "a7ad283c4c0031d0eebc4703edfc5a0688d24f65a2ca7264b725b304989b12fc"


@pytest.mark.skipif(jax.__version__ != "0.9.0",
                    reason="the digest is of jax 0.9.0's lowering")
def test_step_program_lowers_to_the_text_it_had_before_mamba2_was_shared():
    """``nemotron_h`` imports its Mamba-2 from ``models/mamba2.py`` (with
    ``granite_hybrid``) and its step program is the one it was, to the
    character."""
    import hashlib

    from deepspeed_tpu.models import granite_hybrid, mamba2

    assert nemotron_h.mamba2 is mamba2 is granite_hybrid.mamba2
    assert not hasattr(nemotron_h, "ssd_tiles")     # no copy left behind
    rows, tiles, tile = 4, 2, 8
    abstract = jax.eval_shape(lambda: jax.tree_util.tree_map(
        lambda a: a.astype(jnp.bfloat16),
        nemotron_h.init_params(CFG, jax.random.PRNGKey(0))))
    cache = jax.eval_shape(lambda: nemotron_h.init_paged_cache(
        CFG, 9, 8, jnp.bfloat16, num_slots=5))

    def i32(*shape):
        return jax.ShapeDtypeStruct(shape, jnp.int32)

    def step(params, cache, tokens, slots, positions, bt, ts, tp, tv):
        return nemotron_h.ragged_forward(
            CFG, params, tokens, slots, positions, bt, cache,
            prefill_tiles=(rows, ts, tp, tv, tile))

    t = rows + tiles * tile
    text = jax.jit(step).lower(abstract, cache, i32(t), i32(t), i32(t),
                               i32(5, 4), i32(tiles), i32(tiles),
                               i32(tiles)).as_text()
    assert hashlib.sha256(text.encode()).hexdigest() == _MIXED_STEP_SHA256
