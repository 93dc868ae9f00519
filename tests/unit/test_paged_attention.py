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


@pytest.mark.parametrize("CT,MB,split", [(8, 4, False), (32, 10, True)],
                         ids=["whole_tile", "split_tile"])
def test_tiled_prefill_kernel_matches_xla(CT, MB, split, monkeypatch):
    """The tiled prefill kernel (interpret mode on CPU) is exact vs the XLA
    path, including tile padding, block-edge positions and a pad tile —
    also when a tile over the scoped-VMEM budget runs as sub-tiles."""
    from deepspeed_tpu.ops.attention import ragged_prefill_attention
    from deepspeed_tpu.ops.pallas import paged_attention as kernels

    Hq, Hkv, D, BS = 4, 2, 16, 8
    if split:  # a budget this geometry exceeds: 32-row tiles run as 4 x 8
        monkeypatch.setattr(kernels, "_VMEM_SCOPED_BYTES", 2**17)
    assert kernels.prefill_kernel_tile(CT, Hq, D) == 8
    rng = np.random.default_rng(4)
    NB = 2 * MB + 1
    # 4 tiles: seq0 chunk of 2*CT-2 tokens from position 5 (tiles 0-1), seq1
    # chunk of 6 tokens (tile 2, pos 0..5), tile 3 all-pad
    q = jnp.asarray(rng.normal(size=(4 * CT, Hq, D)).astype(np.float32))
    kp = jnp.asarray(rng.normal(size=(NB, BS, Hkv * D)).astype(np.float32))
    vp = jnp.asarray(rng.normal(size=(NB, BS, Hkv * D)).astype(np.float32))
    bt = np.zeros((3, MB), np.int32)
    bt[0] = rng.permutation(np.arange(1, MB + 1))
    bt[1] = rng.permutation(np.arange(MB + 1, 2 * MB + 1))
    ts = jnp.asarray(np.array([0, 0, 1, 2], np.int32))
    tp = jnp.asarray(np.array([5, 5 + CT, 0, 0], np.int32))
    tv = jnp.asarray(np.array([CT, CT - 2, 6, 0], np.int32))
    out_x = ragged_prefill_attention(q, kp, vp, ts, tp, tv, jnp.asarray(bt),
                                     CT, impl="xla")
    out_p = ragged_prefill_attention(q, kp, vp, ts, tp, tv, jnp.asarray(bt),
                                     CT, impl="pallas")
    # compare valid rows only (pad rows are unspecified garbage/zeros)
    for c in range(4):
        v = int(tv[c])
        a = np.asarray(out_x)[c * CT:c * CT + v]
        b = np.asarray(out_p)[c * CT:c * CT + v]
        np.testing.assert_allclose(b, a, rtol=2e-5, atol=2e-5, err_msg=f"tile {c}")


def test_ragged_engine_uses_dispatcher():
    """End-to-end ragged generation still exact after the dispatcher swap."""
    from deepspeed_tpu.comm.topology import reset_topology
    from deepspeed_tpu.inference.ragged import RaggedConfig, RaggedInferenceEngine
    from deepspeed_tpu.models import llama

    reset_topology()
    cfg = llama.LlamaConfig.tiny(256)
    eng = RaggedInferenceEngine(
        lambda ctx: llama.build(cfg, ctx=ctx),
        RaggedConfig(max_seqs=4, num_blocks=64, block_size=16,
                     max_tokens_per_step=32),
        dtype=jnp.float32, seed=3)
    eng.put("a", list(range(9)), max_new_tokens=5)
    out = eng.generate_all()
    assert len(out["a"]) == 5
