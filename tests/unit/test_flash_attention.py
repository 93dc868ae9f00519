"""Pallas flash attention vs the XLA reference (interpret mode on CPU;
the same kernel compiles for real on TPU). Reference test style:
``tests/unit/ops`` kernel-vs-eager numerics."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deepspeed_tpu.ops.attention import xla_attention
from deepspeed_tpu.ops.pallas.flash_attention import flash_attention


def _qkv(b=2, sq=128, skv=128, hq=4, hkv=4, d=32, seed=0, dtype=jnp.float32):
    ks = jax.random.split(jax.random.PRNGKey(seed), 3)
    q = jax.random.normal(ks[0], (b, sq, hq, d), dtype)
    k = jax.random.normal(ks[1], (b, skv, hkv, d), dtype)
    v = jax.random.normal(ks[2], (b, skv, hkv, d), dtype)
    return q, k, v


@pytest.mark.parametrize("causal", [True, False])
def test_matches_xla(causal):
    q, k, v = _qkv()
    ref = xla_attention(q, k, v, causal=causal)
    out = flash_attention(q, k, v, causal, None, 64, 64)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), rtol=2e-5, atol=2e-5)


def test_gqa_head_mapping():
    q, k, v = _qkv(hq=8, hkv=2)
    ref = xla_attention(q, k, v, causal=True)
    out = flash_attention(q, k, v, True, None, 64, 64)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), rtol=2e-5, atol=2e-5)


def test_multiple_kv_blocks_online_softmax():
    q, k, v = _qkv(sq=64, skv=256)
    ref = xla_attention(q, k, v, causal=False)
    out = flash_attention(q, k, v, False, None, 64, 64)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), rtol=2e-5, atol=2e-5)


def test_grads_match_xla():
    q, k, v = _qkv(sq=64, skv=64, hq=4, hkv=2)

    def loss_flash(q, k, v):
        return jnp.sum(flash_attention(q, k, v, True, None, 32, 32) ** 2)

    def loss_ref(q, k, v):
        return jnp.sum(xla_attention(q, k, v, causal=True) ** 2)

    g_f = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
    g_r = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(g_f, g_r):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=5e-5, atol=5e-5)


def test_unsupported_shape_raises():
    q, k, v = _qkv(hq=3, hkv=2)  # 3 % 2 != 0
    with pytest.raises(NotImplementedError):
        flash_attention(q, k, v, True, None, 64, 64)


def test_bf16_inputs():
    q, k, v = _qkv(dtype=jnp.bfloat16)
    ref = xla_attention(q, k, v, causal=True)
    out = flash_attention(q, k, v, True, None, 64, 64)
    np.testing.assert_allclose(np.asarray(out, np.float32), np.asarray(ref, np.float32),
                               rtol=2e-2, atol=2e-2)


def test_kernel_runs_manual_on_a_mesh():
    """GSPMD cannot partition a Mosaic kernel ("wrap the call in a
    shard_map"): on a mesh of several devices ``ShardCtx.attention`` runs the
    kernel manual over the mesh, batch and heads split by the activation
    rules — forward and backward equal to the XLA path GSPMD partitions."""
    from deepspeed_tpu.comm.comm import init_distributed
    from deepspeed_tpu.config.config import MeshConfig
    from deepspeed_tpu.models.api import ShardCtx

    mesh = init_distributed(MeshConfig(data=2, fsdp=2, tensor=2)).mesh
    ctx = ShardCtx(mesh=mesh)
    q, k, v = _qkv(b=4, sq=64, skv=64, hq=4, hkv=2)

    def loss(impl, q, k, v):
        return jnp.sum(ctx.attention(q, k, v, causal=True, impl=impl) ** 2)

    for impl in ("pallas", "xla"):
        val, grads = jax.jit(jax.value_and_grad(
            lambda *a, i=impl: loss(i, *a), argnums=(0, 1, 2)))(q, k, v)
        if impl == "pallas":
            got = (val, *grads)
            text = jax.jit(lambda *a: loss("pallas", *a)).lower(q, k, v).as_text()
            assert "shard_map" in text or "manual" in text
        else:
            for a, b in zip(got, (val, *grads)):
                np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                           rtol=5e-5, atol=5e-5)
