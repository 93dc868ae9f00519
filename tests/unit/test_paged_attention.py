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
