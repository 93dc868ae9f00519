"""Pallas paged flash-decode kernel vs the XLA padded-gather path
(reference ``inference/v2/kernels/ragged_ops`` blocked flash attention)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deepspeed_tpu.ops.attention import paged_attention


def _setup(seed=0, T=6, Hq=4, Hkv=2, D=16, NB=16, BS=8, MB=4):
    rng = np.random.default_rng(seed)
    q = jnp.asarray(rng.normal(size=(T, Hq, D)).astype(np.float32))
    kp = jnp.asarray(rng.normal(size=(NB, BS, Hkv * D)).astype(np.float32))
    vp = jnp.asarray(rng.normal(size=(NB, BS, Hkv * D)).astype(np.float32))
    bt = np.zeros((3, MB), np.int32)
    bt[0] = [3, 5, 7, 11]
    bt[1] = [2, 9, 1, 0]
    slots = jnp.asarray(np.array([0, 0, 1, 1, 0, 1], np.int32))
    pos = jnp.asarray(np.array([0, 13, 5, 8, 31, 17], np.int32))
    return q, kp, vp, slots, pos, jnp.asarray(bt)


def test_pallas_matches_xla_gather():
    args = _setup()
    out_x = paged_attention(*args, impl="xla")
    out_p = paged_attention(*args, impl="pallas")  # interpret mode on CPU
    np.testing.assert_allclose(np.asarray(out_p), np.asarray(out_x),
                               rtol=2e-5, atol=2e-5)


def test_pallas_mixed_prefill_decode_positions():
    # positions within the same block and across block boundaries
    q, kp, vp, _, _, bt = _setup(T=4)
    slots = jnp.asarray(np.array([0, 0, 0, 0], np.int32))
    pos = jnp.asarray(np.array([7, 8, 15, 16], np.int32))  # block edges
    a = paged_attention(q[:4], kp, vp, slots, pos, bt, impl="xla")
    b = paged_attention(q[:4], kp, vp, slots, pos, bt, impl="pallas")
    np.testing.assert_allclose(np.asarray(b), np.asarray(a),
                               rtol=2e-5, atol=2e-5)


# (q heads, kv heads, head size, block size, table width): the two chat
# cells' decode shapes (benchmark/configs/gpt2-xl.json, mixtral-8x7b-d3.json)
CHAT_GEOMETRIES = {"gpt2-xl": (25, 25, 64, 32, 32),
                   "mixtral": (32, 8, 128, 128, 8)}


def _chat_case(geometry, rows, dtype, seed=0):
    """``rows`` decode rows of unequal context over a pool of 64 blocks + a
    poisoned one: positions 0, a block's last token, the next block's first,
    a partly filled last block, a step's last token and the next step's
    first, the whole table, then random ones; the last of several rows is
    the padding row (all-scratch table row, position 0). Returns the
    kernel's arguments, whose table entries past each row's context are
    out-of-range ids or the NaN block, and the gather's, where they are 0."""
    from deepspeed_tpu.ops.pallas.paged_attention import decode_step_blocks

    hq, hkv, d, bs, mb = CHAT_GEOMETRIES[geometry]
    rng = np.random.default_rng(seed)
    nb, poisoned = 66, 65
    q = rng.normal(size=(rows, hq, d)).astype(np.float32)
    kp = rng.normal(size=(nb, bs, hkv * d)).astype(np.float32)
    vp = rng.normal(size=(nb, bs, hkv * d)).astype(np.float32)
    kp[poisoned] = vp[poisoned] = np.nan
    ch = bs * decode_step_blocks(bs, hkv * d, jnp.dtype(dtype).itemsize)
    edges = [0, bs - 1, bs, bs + 5, ch - 1, min(ch, mb * bs - 1), mb * bs - 1]
    pos = np.array([edges[r] if r < len(edges) else rng.integers(0, mb * bs)
                    for r in range(rows)], np.int32)
    slots = np.arange(rows, dtype=np.int32)
    clean = np.zeros((rows + 1, mb), np.int32)
    dirty = np.zeros((rows + 1, mb), np.int32)
    for r in range(rows):
        need = pos[r] // bs + 1
        clean[r, :need] = rng.integers(1, poisoned, need)
        dirty[r] = rng.choice([2**30, -1, poisoned, nb], mb)
        dirty[r, :need] = clean[r, :need]
    if rows > 1:                       # the padding row
        slots[-1], pos[-1] = rows, 0
    def args(table):
        return (jnp.asarray(q, dtype), jnp.asarray(kp, dtype),
                jnp.asarray(vp, dtype), jnp.asarray(slots), jnp.asarray(pos),
                jnp.asarray(table))
    return args(dirty), args(clean)


@pytest.mark.parametrize("rows", [1, 4, 8, 32])
@pytest.mark.parametrize("geometry", sorted(CHAT_GEOMETRIES))
def test_decode_kernel_walks_each_rows_own_context(geometry, rows):
    """The decode kernel (interpret mode) against the gather at the chat
    cells' shapes: the walk ends at each row's position and never reads
    through a table entry past it (those hold out-of-range ids and a block
    of NaNs, which one masked read would carry into the output)."""
    dirty, clean = _chat_case(geometry, rows, jnp.float32)
    want = paged_attention(*clean, impl="xla")
    got = paged_attention(*dirty, impl="pallas")
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("geometry", sorted(CHAT_GEOMETRIES))
def test_decode_kernel_in_the_pools_own_precision(geometry):
    """bfloat16, as the cells serve: more blocks a step than in float32
    (``decode_step_blocks`` goes by bytes), products of bf16 operands
    accumulated in float32; ``chip_smoke.py``'s measure and limit."""
    dirty, clean = _chat_case(geometry, 8, jnp.bfloat16, seed=1)
    want = np.asarray(paged_attention(*clean, impl="xla"), np.float32)
    got = np.asarray(paged_attention(*dirty, impl="pallas"), np.float32)
    assert np.isfinite(got).all()
    assert np.abs(got - want).max() / np.abs(want).max() <= 3e-2


def test_decode_step_blocks_follow_the_blocks_bytes():
    from deepspeed_tpu.ops.pallas.paged_attention import decode_step_blocks

    assert decode_step_blocks(32, 1600, 2) == 4     # GPT-2 XL: 128 tokens
    assert decode_step_blocks(128, 1024, 2) == 2    # Mixtral, Llama-3-8B: 256
    assert decode_step_blocks(128, 1024, 4) == 1
    assert decode_step_blocks(8, 32, 4) == 8        # never more than eight


# (tile rows, table width, q heads, kv heads, dtype, tiles as (pos0, valid)):
# D = 16 and 8-token blocks, so a grid step takes 8 blocks = 64 keys
# (``prefill_step_blocks``) and a table of 12 two steps
TILE_CASES = {
    # seq0 chunk of 2*CT-2 tokens from position 5, seq1 chunk of 6, a pad tile
    "whole_tile": (8, 4, 4, 2, "float32", [(5, 8), (13, 6), (0, 6), (0, 0)]),
    # a budget this geometry exceeds: 32-row tiles run as 4 x 8
    "split_tile": (32, 10, 4, 2, "float32",
                   [(5, 32), (37, 30), (0, 6), (0, 0)]),
    # one step whose operands 1..7 (2..7) repeat the tile's last block, and a
    # second step that begins with the last block and repeats it seven times
    "clamped_repeats": (8, 12, 4, 2, "float32",
                        [(0, 8), (3, 8), (64, 8), (60, 3)]),
    # the diagonal inside a step's first block, inside its last block, across
    # two steps, and a ragged tile under the second step's first block
    "diagonal_in_first_or_last_block": (8, 12, 4, 2, "float32",
                                        [(64, 8), (56, 8), (58, 8), (66, 5)]),
    "rep_1": (8, 12, 2, 2, "float32", [(5, 8), (60, 6), (70, 8), (0, 0)]),
    "rep_7": (8, 12, 14, 2, "float32", [(5, 8), (60, 6), (70, 8), (0, 0)]),
    # as the cells serve: products of bf16 operands accumulated in float32
    "bf16_pool": (16, 12, 4, 2, "bfloat16",
                  [(5, 16), (50, 14), (72, 16), (0, 0)]),
}


@pytest.mark.parametrize("case", sorted(TILE_CASES))
def test_tiled_prefill_kernel_matches_xla(case, monkeypatch):
    """The tiled prefill kernel (interpret mode on CPU) against the XLA
    path: tile padding, block-edge positions, a pad tile, a tile over the
    scoped-VMEM budget run as sub-tiles, steps of several blocks whose last
    operands are clamped repeats, the diagonal in a step's first and last
    block, one and seven query heads a KV head, the pool's own precision.
    Every tile is a sequence of its own whose table entries past its last
    block name a block of NaNs, which one read would carry into the output
    (a masked key's ``p = 0`` times its value)."""
    from deepspeed_tpu.ops.attention import ragged_prefill_attention
    from deepspeed_tpu.ops.pallas import paged_attention as kernels

    CT, MB, Hq, Hkv, dtype, tiles = TILE_CASES[case]
    D, BS = 16, 8
    if case == "split_tile":
        monkeypatch.setattr(kernels, "_PREFILL_VMEM_BYTES", 2**17)
    assert kernels.prefill_step_blocks(BS, Hkv * D, 4) == 8
    assert kernels.prefill_kernel_tile(CT, Hq, Hkv, D, 4, 8 * BS) == (
        8 if case == "split_tile" else CT)
    rng = np.random.default_rng(4)
    NB = len(tiles) * MB + 2
    poisoned = NB - 1
    q = rng.normal(size=(len(tiles) * CT, Hq, D)).astype(np.float32)
    kp = rng.normal(size=(NB, BS, Hkv * D)).astype(np.float32)
    vp = rng.normal(size=(NB, BS, Hkv * D)).astype(np.float32)
    kp[poisoned] = vp[poisoned] = np.nan
    clean = np.zeros((len(tiles) + 1, MB), np.int32)
    dirty = np.full((len(tiles) + 1, MB), poisoned, np.int32)
    dirty[-1] = 0                       # the padding row: all scratch
    for c, (pos0, valid) in enumerate(tiles):
        need = (pos0 + valid - 1) // BS + 1 if valid else 0
        clean[c, :need] = 1 + c * MB + rng.permutation(MB)[:need]
        dirty[c, :need] = clean[c, :need]
    ts = np.array([c if v else len(tiles) for c, (_, v) in enumerate(tiles)],
                  np.int32)
    tp = jnp.asarray(np.array([p for p, _ in tiles], np.int32))
    tv = jnp.asarray(np.array([v for _, v in tiles], np.int32))

    def run(table, impl):
        return np.asarray(ragged_prefill_attention(
            jnp.asarray(q, dtype), jnp.asarray(kp, dtype),
            jnp.asarray(vp, dtype), jnp.asarray(ts), tp, tv,
            jnp.asarray(table), CT, impl=impl), np.float32)

    out_x, out_p = run(clean, "xla"), run(dirty, "pallas")
    # compare valid rows only (pad rows are unspecified, but finite)
    assert np.isfinite(out_p).all()
    for c, (_, v) in enumerate(tiles):
        if not v:
            continue
        a = out_x[c * CT:c * CT + v]
        b = out_p[c * CT:c * CT + v]
        if dtype == "bfloat16":    # chip_smoke.py's measure and limit
            assert np.abs(b - a).max() / np.abs(a).max() <= 3e-2, f"tile {c}"
        else:
            np.testing.assert_allclose(b, a, rtol=2e-5, atol=2e-5,
                                       err_msg=f"tile {c}")


def test_prefill_step_blocks_follow_the_blocks_shape():
    from deepspeed_tpu.ops.pallas.paged_attention import prefill_step_blocks

    assert prefill_step_blocks(128, 512, 2) == 4     # SmallThinker: 512 keys
    assert prefill_step_blocks(128, 1024, 2) == 4    # Mixtral: 512 keys, 1 MiB
    assert prefill_step_blocks(128, 256, 2) == 4     # Nemotron-3
    assert prefill_step_blocks(32, 1600, 2) == 8     # GPT-2 XL: 256 keys
    assert prefill_step_blocks(128, 1024, 4) == 2    # by the bytes
    assert prefill_step_blocks(8, 32, 4) == 8        # never more than eight


def _tiny_engine(**cfg):
    from deepspeed_tpu.comm.topology import reset_topology
    from deepspeed_tpu.inference.ragged import RaggedConfig, RaggedInferenceEngine
    from deepspeed_tpu.models import llama

    reset_topology()
    model = llama.LlamaConfig.tiny(256)
    return RaggedInferenceEngine(
        lambda ctx: llama.build(model, ctx=ctx),
        RaggedConfig(max_seqs=4, num_blocks=64, block_size=16,
                     max_tokens_per_step=32, **cfg),
        dtype=jnp.float32, seed=3)


def test_ragged_engine_uses_dispatcher():
    """End-to-end ragged generation still exact after the dispatcher swap."""
    eng = _tiny_engine()
    eng.put("a", list(range(9)), max_new_tokens=5)
    out = eng.generate_all()
    assert len(out["a"]) == 5


def test_dispatch_span_says_the_keys_a_grid_step_takes(monkeypatch):
    """``engine/dispatch`` of a program with tiles carries
    ``prefill_step_keys``, a static of its tile kernel (eight 16-token
    blocks here) by the rule the dispatcher goes by; a program without tiles
    does not, nor any on the XLA path, and the tile kernel serves the XLA
    path's tokens."""
    import contextlib

    from deepspeed_tpu.inference import ragged
    from deepspeed_tpu.ops import attention

    prompt = list(range(3, 43))     # two steps with tiles
    spans = []

    @contextlib.contextmanager
    def recording(name, **args):
        if name == "engine/dispatch":
            spans.append(args)
        yield

    monkeypatch.setattr(ragged, "span", recording)
    want = _tiny_engine(prefill_tile=8)
    want.put("a", prompt, max_new_tokens=4)
    want = want.generate_all()
    assert spans and all("prefill_step_keys" not in a for a in spans)
    del spans[:]
    monkeypatch.setattr(attention, "_on_tpu", lambda: True)   # interpreted
    eng = _tiny_engine(prefill_tile=8)
    eng.put("a", prompt, max_new_tokens=4)
    assert eng.generate_all() == want
    tiled = [a for a in spans if not a["program"].endswith("_t0")]
    assert len(tiled) == 2 and len(spans) > 2
    assert all(a.get("prefill_step_keys") == (128 if a in tiled else None)
               for a in spans)
