"""``models/smallthinker.py`` at a small size on the CPU, seeded weights: what is
served (prefill in tiles, then decode, through the full pool AND the sliding
pool, whose blocks go back as the window slides) against the plain reference
``benchmark/reference/smallthinker.py``; the two attention kernels with a
``window`` against a dense masked softmax at the window's edges; the ranks'
parts of an expert layer against the uncut layer; what the second allocator
promises; the four refusals; and, for every family that was there before, the
device step program's jaxpr against the one PR 48's tree traces, both forms
(``fixtures/step_jaxprs_pr48.json``; ``python tests/unit/test_smallthinker.py
<out.json> [family ...]`` writes it from whatever tree ``PYTHONPATH`` names.
PR 43's and PR 45's fixtures held until the pool's write site took a step's
tiles as slices, which every family's program with tiles shows).

Logits are compared, not tokens. Tolerance 2e-4 (float32 everywhere here): the
program runs a prompt as tiles against cached rows and the reference as one
masked pass, so the same sums are taken in another order; observed
differences are under 1e-6 on logits of magnitude 0.7.
"""

import hashlib
import functools
import importlib.util
import json
import os
import re
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from shared import one_engine_each  # tests/unit is rootdir-inserted

REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
FIXTURES = os.path.join(os.path.dirname(os.path.abspath(__file__)), "fixtures")
FIXTURE = os.path.join(FIXTURES, "step_jaxprs_pr48.json")
ATOL = 2e-4


# ------------------------------------------------ (e) the families before it
def _tiny_families():
    """``{name: (module, tiny config)}`` of the families PR 43's tree serves,
    and ``granite_hybrid`` (PR 61: with ``nemotron_h`` the other caller of
    ``mamba2.ssd_tiles``, its digests written from PR 60's tree)."""
    from deepspeed_tpu.models import (
        deepseek,
        deepseek_v32,
        gpt2,
        granite_hybrid,
        kimi_linear,
        llama,
        longcat_flash,
        mixtral,
        nemotron_h,
    )

    return {
        "gpt2": (gpt2, gpt2.GPT2Config.tiny(89)),
        "llama": (llama, llama.LlamaConfig.tiny(89)),
        "mixtral": (mixtral, mixtral.MixtralConfig.tiny(89)),
        "deepseek": (deepseek, deepseek.DeepseekConfig.tiny(89)),
        "deepseek_v32": (deepseek_v32, deepseek_v32.DeepseekV32Config.tiny(89)),
        "longcat_flash": (longcat_flash,
                          longcat_flash.LongcatFlashConfig.tiny(89)),
        "nemotron_h": (nemotron_h, nemotron_h.NemotronHConfig.tiny()),
        "kimi_linear": (kimi_linear, kimi_linear.KimiLinearConfig.tiny()),
        "granite_hybrid": (granite_hybrid,
                           granite_hybrid.GraniteHybridConfig.tiny()),
    }


def step_jaxpr_digests(on_tpu: bool) -> dict:
    """sha256 of the text of each family's device step program's jaxpr (8
    decode rows beside two 8-row tiles, a tiny config, abstract arguments):
    with ``on_tpu`` the attention dispatchers believe they are on the chip,
    so the Pallas kernels are in it (their bodies too); without, XLA's
    forms. ``paged.rows_to_heads``' pin (PR 50) is taken out: it is one
    ``optimization_barrier`` a projection and no arithmetic
    (``test_the_pin_of_rows_to_heads_changes_no_value``), and without it
    PR 50's tree still prints PR 48's programs to the letter. ``nemotron_h``'s
    two digests are PR 54's: ``mamba2.split`` ties its three parts, one more
    ``optimization_barrier`` a Mamba body and the slices in the parts' order
    (no arithmetic: ``test_granite_hybrid.
    test_split_hands_out_the_columns_of_the_one_product``)."""
    from deepspeed_tpu.inference.ragged import (RaggedConfig,
                                                RaggedInferenceEngine)
    from deepspeed_tpu.models import paged
    from deepspeed_tpu.ops import attention

    def abstract(tree):
        return jax.tree_util.tree_map(
            lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype), tree)

    was, pin = attention._on_tpu, paged._pin
    attention._on_tpu, paged._pin = (lambda: on_tpu), (lambda y: y)
    out = {}
    try:
        for name, (mod, cfg) in _tiny_families().items():
            rc = RaggedConfig(max_tokens_per_step=32, max_seqs=8, block_size=8,
                              num_blocks=33, max_blocks_per_seq=8,
                              prefill_tile=8)
            eng = RaggedInferenceEngine(
                lambda ctx, mod=mod, cfg=cfg: mod.build(cfg, ctx=ctx), rc,
                dtype=jnp.float32,
                params=jax.tree_util.tree_map(
                    lambda a: np.zeros(a.shape, a.dtype), jax.eval_shape(
                        lambda mod=mod, cfg=cfg: mod.init_params(
                            cfg, jax.random.PRNGKey(0)))))
            t, nd, nt = 8 + 2 * 8, 8, 2
            fn = eng._build_dev_step(t, nd, nt, rc.max_blocks_per_seq, False,
                                     False, False)
            text = str(jax.make_jaxpr(fn)(
                abstract(eng.params), abstract(eng.cache),
                abstract(eng._dev_state), abstract(eng._bt_dev),
                jax.ShapeDtypeStruct((4 * t + 3 * nt,), jnp.int32),
                abstract(eng._sample_root)))
            text = re.sub(r"0x[0-9a-f]+", "0x", text)
            out[name] = hashlib.sha256(text.encode()).hexdigest()
    finally:
        attention._on_tpu, paged._pin = was, pin
    return out


if __name__ == "__main__":
    forms = {"xla": step_jaxpr_digests(False),
             "pallas": step_jaxpr_digests(True)}
    if sys.argv[2:]:
        forms = {form: {k: v for k, v in digests.items() if k in sys.argv[2:]}
                 for form, digests in forms.items()}
    with open(sys.argv[1], "w") as f:
        json.dump(forms, f, indent=1)
    sys.exit(0)


from deepspeed_tpu.inference.ragged import (  # noqa: E402
    RaggedConfig,
    RaggedInferenceEngine,
)
from deepspeed_tpu.models import experts, smallthinker  # noqa: E402
from deepspeed_tpu.models.paged import SWA  # noqa: E402
from deepspeed_tpu.ops.pallas.paged_attention import (  # noqa: E402
    decode_step_blocks,
    decode_steps,
    paged_decode_attention,
    ragged_prefill_attention,
)


@pytest.mark.parametrize("form", ["xla", "pallas"])
def test_the_other_families_step_programs_are_the_parents(form):
    """``window=None`` traces what it traced, the period scan and the expert
    forms too: every family's step program prints the jaxpr PR 48's tree
    printed (PR 43's, but for the tile kernel's body PR 45 rebuilt and the
    tiles' rows written as slices)."""
    with open(FIXTURE) as f:
        want = json.load(f)[form]
    got = step_jaxpr_digests(form == "pallas")
    assert got == want, sorted(k for k in want if got.get(k) != want[k])


# -------------------------------------------------------------- the model
def _reference():
    spec = importlib.util.spec_from_file_location(
        "reference_smallthinker",
        os.path.join(REPO, "benchmark", "reference", "smallthinker.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    mod.Q_BLOCK = 16
    return mod


REF = _reference()
CFG = smallthinker.SmallThinkerConfig.tiny()   # F W W W twice, window 12
BS = 4                                         # so a window is 3-4 blocks


@pytest.fixture(scope="module")
def params():
    return smallthinker.init_params(CFG, jax.random.PRNGKey(1))


def _engine(params, cfg=CFG, device_state=False, **sizes):
    rc = RaggedConfig(**{**dict(
        max_tokens_per_step=8, max_seqs=3, block_size=BS, num_blocks=65,
        max_blocks_per_seq=24, prefill_tile=4, device_state=device_state),
        **sizes})
    return RaggedInferenceEngine(lambda ctx: smallthinker.build(cfg, ctx=ctx),
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


def _poison_free_sliding_blocks(eng):
    """Every block on the sliding pool's free list gets rows no softmax
    would survive reading inside a window: what the slide returned is not
    read again."""
    def poisoned(leaf):
        leaf = np.array(leaf)
        leaf[:, eng.window_allocator._free] = 1e4
        return jnp.asarray(leaf)

    eng.cache = {**eng.cache, SWA: {k: poisoned(v)
                                    for k, v in eng.cache[SWA].items()}}


def _serve(eng, prompts, new_tokens, recover_after=None, poison=False):
    """Run the requests to their end; ``{(uid, g): logits row}`` of every
    emission of the host-staged path (a recomputed request's later emission
    replaces its earlier one) and the most sliding blocks any sequence held
    between two steps."""
    rows, held = {}, 0
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
            held = max([held]
                       + [len(s.win_blocks) for s in eng._running.values()])
            if poison:
                _poison_free_sliding_blocks(eng)
            if steps == recover_after:
                eng._recover_device_path()
            assert steps < 500
    finally:
        del eng._emit_tokens        # the engine is shared: the method again
    return rows, held


# case -> (engine sizes, prompt lengths, new tokens, recover after, poison)
SERVED = {
    # 50 + 10 tokens over a window of 12 in blocks of 4: the window slides
    # ~12 blocks past; three slots, four requests, decode rows beside tiles;
    # whatever the slide returned is poisoned before the next step
    "window_slides_and_blocks_go_back": ({}, [50, 9, 33, 21], 10, None, True),
    # positions rewound mid-flight (what a preempted or contained request
    # gets): its sliding blocks go and are written again from position 0
    "recovered_and_recomputed": ({}, [41, 9, 27], 8, 5, False),
    # 12 full blocks of 4 for requests that grow to 37 + 30 + 25 tokens and
    # two slots: the third waits for blocks; the sliding pool is 2 x 4 + 1
    "tight_pools": ({"num_blocks": 13, "max_seqs": 2}, [29, 22, 17], 8, None,
                    True),
}


@pytest.mark.parametrize("case", SERVED)
def test_served_logits_match_the_reference(params, engine_of, case):
    sizes, lengths, new_tokens, recover_after, poison = SERVED[case]
    eng = engine_of(**sizes)
    slid = eng.window_blocks_slid
    prompts = _prompts(lengths)
    rows, held = _serve(eng, prompts, new_tokens, recover_after, poison)
    for uid, prompt in prompts.items():
        generated = eng.get_request(uid).generated
        assert len(generated) == new_tokens
        want = np.asarray(REF.forward(CFG, params,
                                      jnp.asarray(prompt + generated)))
        for g in range(new_tokens):
            np.testing.assert_allclose(
                rows[(uid, g)], want[len(prompt) + g - 1], atol=ATOL,
                err_msg=f"{case}: request {uid}, generated token {g}")
    # a window of 12 over blocks of 4 spans 4 blocks wherever it starts: no
    # sequence held more between steps, however long it grew
    cap = -(-CFG.sliding_window // BS) + 1
    assert 0 < held <= cap
    assert eng.window_blocks_slid > slid
    # both pools come back whole, and nothing is left promised
    assert eng.allocator.free_blocks == eng.cfg.num_blocks - 1
    assert eng.window_allocator.free_blocks == eng.cfg.max_seqs * cap
    assert eng._reserved == eng._win_reserved == 0
    assert not eng.window_tables.any() and not eng.block_tables.any()


def test_the_device_step_serves_what_the_host_staged_step_serves(params,
                                                                 engine_of):
    """The path every cell runs (device-resident rows, two tables on the
    device, the readback one step behind) gives the host-staged path's
    tokens, and its dispatch spans say what the window layers read."""
    from deepspeed_tpu.inference import ragged

    prompts = _prompts([37, 9, 22])
    want = engine_of()
    for uid, p in prompts.items():
        want.put(uid, p, max_new_tokens=8)
    want = {uid: toks for uid, toks in want.generate_all().items()
            if uid in prompts}
    spans = []

    def recording(name, **attrs):
        if name == "engine/dispatch":
            spans.append(attrs)
        return real_span(name, **attrs)

    real_span = ragged.span
    ragged.span = recording
    try:
        eng = _engine(params, device_state=True)
        for uid, p in prompts.items():
            eng.put(uid, p, max_new_tokens=8)
        got = eng.generate_all()
    finally:
        ragged.span = real_span
    assert got == want
    assert spans and all(
        {"win_kv_tokens", "dec_win_kv_tokens", "win_attn_pairs",
         "full_blocks_busy", "win_blocks_busy"} <= set(a) for a in spans)
    # the window cuts what the full layers read, never adds to it
    assert all(a["win_kv_tokens"] <= a["kv_tokens"]
               and a["dec_win_kv_tokens"] <= a["dec_kv_tokens"]
               and a["win_attn_pairs"] <= a["attn_pairs"] for a in spans)
    assert any(a["win_kv_tokens"] < a["kv_tokens"] for a in spans)
    assert any(a["win_blocks_busy"] < a["full_blocks_busy"] for a in spans)
    assert max(a["dec_win_kv_tokens"] for a in spans) <= 3 * CFG.sliding_window
    assert eng.window_allocator.free_blocks == eng.window_allocator.num_blocks - 1


# ------------------------------------------------ (d) the second allocator
def test_admission_waits_for_whichever_pool_is_short(params):
    eng = _engine(params, max_seqs=3, num_blocks=33)
    cap = -(-CFG.sliding_window // BS) + 1
    prompts = _prompts([30, 30, 30])
    eng.put(0, prompts[0], max_new_tokens=12)
    eng.step()
    first = eng.get_request(0)
    assert first.slot >= 0 and first.win_cap == cap
    assert eng._win_reserved + len(first.win_blocks) == cap
    # the sliding pool short: beside what the first request has reserved,
    # someone else holds all but three blocks, and a request needs four
    alloc = eng.window_allocator
    held = alloc.allocate(alloc.free_blocks - eng._win_reserved - (cap - 1))
    eng.put(1, prompts[1], max_new_tokens=4)
    eng.step()
    assert len(eng._running) == 1 and len(eng._queued) == 1
    alloc.free(held)
    eng.step()
    assert len(eng._running) == 2 and not eng._queued
    # the full pool short: the third request's worst case does not fit
    held = eng.allocator.allocate(eng.allocator.free_blocks - eng._reserved - 3)
    eng.put(2, prompts[2], max_new_tokens=4)
    eng.step()
    assert len(eng._running) == 2 and len(eng._queued) == 1
    eng.allocator.free(held)
    eng.generate_all()
    assert eng.allocator.free_blocks == 32
    assert alloc.free_blocks == 3 * cap and eng._win_reserved == 0


def test_a_window_rows_walk_is_bounded_whatever_its_context():
    """``decode_steps`` with a window: a row takes the chunks from the one
    that holds ``pos - W + 1`` to its last, at most ``W / CH + 1``; the
    cell's shape: four 128-token blocks of 512 lanes a step, a window of
    4,096."""
    ch = 128 * decode_step_blocks(128, 512, 2)
    assert ch == 512
    pos = jnp.asarray([0, 255, 256, 4095, 4096, 4351, 8191, 16383], jnp.int32)
    ends, rows, chunks = decode_steps(pos, ch, 8 * 64 + 1, 4096)
    n = np.diff(np.concatenate([[0], np.asarray(ends)]))
    assert n.tolist() == [1, 1, 1, 8, 9, 9, 8, 8]
    assert n.max() <= 4096 // ch + 1
    first = np.asarray(chunks)[np.concatenate([[0], np.asarray(ends)[:-1]])]
    assert first.tolist() == [0, 0, 0, 0, 0, 0, 8, 24]
    full = decode_steps(pos, ch, 8 * 64 + 1)[0]
    assert int(full[-1]) == sum(int(p) // ch + 1 for p in pos)


# ---------------------------------------------------- (c) the two kernels
W, KBS, MB = 12, 4, 10     # a window of three blocks over a table of ten


def _dense(q, k, v, pos, window):
    """softmax over keys ``pos - window < j <= pos`` of one sequence: ``q``
    [T, Hq, D] at positions ``pos`` against ``k`` / ``v`` [S, Hkv, D]."""
    rep = q.shape[1] // k.shape[1]
    k, v = np.repeat(k, rep, 1), np.repeat(v, rep, 1)
    j = np.arange(k.shape[0])[None, :]
    seen = (j <= pos[:, None]) & (j > pos[:, None] - window)
    s = np.einsum("thd,shd->ths", q, k) / np.sqrt(q.shape[-1])
    s = np.where(seen[:, None, :], s, -np.inf)
    p = np.exp(s - s.max(-1, keepdims=True))
    return np.einsum("ths,shd->thd", p / p.sum(-1, keepdims=True), v)


def _pool(rng, hkv=2, d=16):
    """One sequence's K and V as pool blocks in a shuffled table; block 0 is
    the scratch block, and one block no table entry names holds rows no
    softmax survives."""
    s = MB * KBS
    k = rng.normal(size=(s, hkv, d)).astype(np.float32)
    v = rng.normal(size=(s, hkv, d)).astype(np.float32)
    order = rng.permutation(np.arange(1, MB + 1))
    kp = np.zeros((MB + 2, KBS, hkv * d), np.float32)
    vp = np.zeros_like(kp)
    kp[order] = k.reshape(MB, KBS, -1)
    vp[order] = v.reshape(MB, KBS, -1)
    kp[MB + 1] = vp[MB + 1] = 1e4
    return k, v, kp, vp, order


@pytest.mark.parametrize("impl", ["xla", "pallas"])
def test_window_decode_rows_match_a_dense_masked_softmax(impl):
    """Contexts of ``window - 1``, ``window`` and ``window + 1`` rows, the
    first and the last row of a block, the table's end. What the slide has
    returned (every block before a row's window) is the scratch block in
    its table, as the engine leaves it, and the poisoned block before the
    row's first chunk, which the kernel never fetches."""
    from deepspeed_tpu.ops.attention import paged_attention

    rng = np.random.default_rng(3)
    k, v, kp, vp, order = _pool(rng)
    pos = np.asarray([0, W - 2, W - 1, W, W + 3, 4 * KBS - 1, 4 * KBS, 27,
                      MB * KBS - 1], np.int32)
    q = rng.normal(size=(len(pos), 4, 16)).astype(np.float32)
    ch = KBS * decode_step_blocks(KBS, 32, 4)
    bt = np.zeros((len(pos) + 1, MB), np.int32)
    for t, p in enumerate(pos):
        bt[t] = order
        first = max(0, p - W + 1) // KBS
        bt[t, :first] = 0
        if impl == "pallas":
            bt[t, :max(0, p - W + 1) // ch * (ch // KBS)] = MB + 1
    out = paged_attention(
        jnp.asarray(q), jnp.asarray(kp), jnp.asarray(vp),
        jnp.arange(len(pos), dtype=jnp.int32), jnp.asarray(pos),
        jnp.asarray(bt), impl=impl, window=W)
    np.testing.assert_allclose(np.asarray(out), _dense(q, k, v, pos, W),
                               rtol=2e-5, atol=2e-5)
    # and with no window the same call is the whole context's
    whole = paged_attention(
        jnp.asarray(q), jnp.asarray(kp), jnp.asarray(vp),
        jnp.zeros(len(pos), jnp.int32), jnp.asarray(pos),
        jnp.asarray(np.tile(order, (2, 1))), impl=impl)
    np.testing.assert_allclose(np.asarray(whole),
                               _dense(q, k, v, pos, 10**6), rtol=2e-5,
                               atol=2e-5)


def _window_tiles(pos0, window, step_keys, monkeypatch):
    """Two 8-row tiles of one sequence from ``pos0`` (the second partly
    padding) and a padding tile through the tile kernel, ``step_keys`` keys
    a grid step; every table entry before the first tile's first needed
    block names a block of NaNs, which one read of a slid-out block would
    carry into the output. Returns the real rows' outputs and the dense
    softmax over ``W`` keys."""
    from deepspeed_tpu.ops.pallas import paged_attention as kernels

    ct = 8
    if step_keys:
        monkeypatch.setattr(kernels, "PREFILL_STEP_KEYS", step_keys)
    rng = np.random.default_rng(5)
    k, v, kp, vp, order = _pool(rng)
    kp[MB + 1] = vp[MB + 1] = np.nan
    tp = np.asarray([pos0, pos0 + ct, 0], np.int32)
    tv = np.asarray([ct, 5, 0], np.int32)
    q = rng.normal(size=(3 * ct, 4, 16)).astype(np.float32)
    bt = np.zeros((3, MB), np.int32)
    bt[0] = order
    bt[0, :max(0, pos0 - window + 1) // KBS] = MB + 1
    out = np.asarray(ragged_prefill_attention(
        jnp.asarray(q), jnp.asarray(kp), jnp.asarray(vp),
        jnp.asarray([0, 0, 2], jnp.int32), jnp.asarray(tp), jnp.asarray(tv),
        jnp.asarray(bt), ct, interpret=True, window=window))
    assert np.isfinite(out).all()
    rows = np.concatenate([np.arange(ct), ct + np.arange(5)])
    pos = np.concatenate([pos0 + np.arange(ct), pos0 + ct + np.arange(5)])
    return out[rows], _dense(q[rows], k, v, pos, W)


# (pos0, keys a grid step): 0 keeps the rule's 8 blocks of 4 tokens, one step
# a tile; 8 is two blocks a step, three steps a tile, and at 15 and 25 the
# tile's first needed block (1, 3) is no multiple of them
WINDOW_TILES = [(0, 0), (3, 0), (8, 0), (9, 0), (21, 0), (15, 8), (25, 8),
                (21, 8)]


@pytest.mark.parametrize(
    "pos0,step_keys", WINDOW_TILES,
    ids=[f"tile_at_{p}" + (f"_{k}_keys_a_step" if k else "")
         for p, k in WINDOW_TILES])
def test_window_prefill_tiles_match_a_dense_masked_softmax(pos0, step_keys,
                                                           monkeypatch):
    """Tiles from ``pos0`` (block-aligned and not): the grid starts at each
    tile's first needed block, NOT at a multiple of the blocks a step, so
    every entry before it may name the poisoned block."""
    got, want = _window_tiles(pos0, W, step_keys, monkeypatch)
    np.testing.assert_allclose(got, want, rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("kernel", ["decode", "prefill"])
def test_a_window_edge_off_by_one_block_is_seen(kernel, monkeypatch):
    """The control: a window one block short is another model, by far more
    than the tolerance the served logits are held to; through the tile
    kernel at a first needed block that is no multiple of the blocks a
    step."""
    if kernel == "prefill":
        got, want = _window_tiles(25, W - KBS, 8, monkeypatch)
        assert np.abs(got - want).max() > 0.05
        return
    rng = np.random.default_rng(3)
    k, v, kp, vp, order = _pool(rng)
    pos = np.asarray([W + 3, 27], np.int32)
    q = rng.normal(size=(2, 4, 16)).astype(np.float32)
    out = paged_decode_attention(
        jnp.asarray(q), jnp.asarray(kp), jnp.asarray(vp),
        jnp.zeros(2, jnp.int32), jnp.asarray(pos),
        jnp.asarray(order[None]), interpret=True, window=W - KBS)
    assert np.abs(np.asarray(out) - _dense(q, k, v, pos, W)).max() > 0.05


# -------------------------------------------- (b) one rank's share, all eight
@pytest.mark.parametrize("rows,ranks", [(24, 8), (256, 2)],
                         ids=["einsum_8_ranks", "grouped_2_ranks"])
def test_the_ranks_parts_add_up_to_the_uncut_layer(params, rows, ranks):
    """The expert sublayer of one layer (routed on the layer's input, computed
    on the post-attention norm, ReLU-gated), cut over ``ranks`` ranks: every
    rank's part through ``routed_experts(held=...)``, in the form its row
    count takes, summed, against the reference's uncut layer."""
    _, lp = smallthinker.layers_in_order(CFG, params)[1]
    rng = np.random.default_rng(rows)
    x = jnp.asarray(rng.normal(size=(rows, CFG.hidden_size)), jnp.float32)
    h2 = jnp.asarray(rng.normal(size=(rows, CFG.hidden_size)), jnp.float32)
    want = REF._moe(CFG, x, h2, lp, jnp.float32)
    assert experts.expert_form(rows, CFG.num_experts, CFG.top_k) == (
        "grouped" if rows >= 256 else "dense")
    held = CFG.num_experts // ranks
    total = 0.0
    for rank in range(ranks):
        mine = slice(rank * held, (rank + 1) * held)
        part = experts.routed_experts(
            h2, lp["router"], lp["w_gate"][mine], lp["w_up"][mine],
            lp["w_down"][mine], CFG.top_k, held=(rank * held, CFG.num_experts),
            router_h=x, gate_act="relu")
        # a rank's part is what the reference gives that rank's config
        cut = smallthinker.SmallThinkerConfig(
            **{**CFG.__dict__, "experts_held": held, "expert_rank": rank})
        np.testing.assert_allclose(
            part, REF._moe(cut, x, h2, {**lp, **{
                w: lp[w][mine] for w in ("w_gate", "w_up", "w_down")}},
                jnp.float32), atol=1e-6)
        total = total + part
    np.testing.assert_allclose(total, want, atol=1e-6)
    # silu in relu's place, or a router on the normed rows, is another model
    for wrong in ({"gate_act": "silu"}, {"router_h": h2}):
        other = experts.routed_experts(
            h2, lp["router"], lp["w_gate"], lp["w_up"], lp["w_down"],
            CFG.top_k, **{"router_h": x, "gate_act": "relu", **wrong})
        assert np.abs(np.asarray(other - want)).max() > 0.1 * float(
            np.abs(want).max())


# ------------------------------------------------------- (f) what refuses
REFUSED = {
    "enable_prefix_cache": (dict(enable_prefix_cache=True),
                            "sliding leaves; enable_prefix_cache"),
    "kv_tier": (dict(kv_tier=True), "sliding leaves; kv_tier"),
    "quantized_pool": (dict(quant="int8"), "quantized pool"),
}


@pytest.mark.parametrize("what", [*REFUSED, "KVHandoff"])
def test_what_a_prefix_of_blocks_cannot_restore_refuses(params, engine_of,
                                                        what):
    if what == "KVHandoff":
        from deepspeed_tpu.inference.ragged import KVHandoff

        eng = engine_of()
        with pytest.raises(ValueError, match="sliding leaves; KVHandoff"):
            eng.put(0, [1, 2, 3], max_new_tokens=2, handoff=True)
        with pytest.raises(ValueError, match="sliding leaves; KVHandoff"):
            eng.import_handoff(KVHandoff.__new__(KVHandoff))
        return
    sizes, match = REFUSED[what]
    with pytest.raises((ValueError, NotImplementedError), match=match):
        _engine(params, **sizes)


def test_the_spec_says_its_window_and_the_cache_has_two_pools(params):
    spec = smallthinker.build(CFG)
    assert spec.sliding_window == 12 and spec.decode_bucket_min == 16
    cache = spec.init_paged_cache_fn(65, BS, jnp.float32, codec=None,
                                     num_slots=4)
    assert cache["k"].shape == (2, 65, BS, 32)
    assert cache[SWA]["k"].shape == (6, 3 * 4 + 1, BS, 32)
    assert CFG.layer_pattern == "FWWW" * 2
    assert smallthinker._plan(CFG) == ("", "FWWW", 2)
    # the lists are read, not a rule: another order is another plan
    odd = smallthinker.SmallThinkerConfig(
        **{**CFG.__dict__, "num_layers": 5,
           "sliding_window_layout": (1, 0, 1, 0, 1),
           "rope_layout": (0, 0, 1, 0, 1)})
    assert odd.layer_pattern == "wFWFW"
    assert smallthinker._plan(odd) == ("w", "FW", 2)
