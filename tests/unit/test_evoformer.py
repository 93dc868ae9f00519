"""Evoformer attention (reference ``DS4Sci_EvoformerAttention`` numerics, at
the shapes of the reference's own benchmark of it)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deepspeed_tpu.ops.evoformer import evoformer_attention


def _inputs(b=1, n=3, r=16, h=2, d=8, seed=0):
    rng = np.random.default_rng(seed)
    q, k, v = (jnp.asarray(rng.normal(size=(b, n, r, h, d)).astype(np.float32))
               for _ in range(3))
    bias1 = jnp.asarray(rng.normal(size=(b, n, 1, 1, r)).astype(np.float32))
    bias2 = jnp.asarray(rng.normal(size=(b, 1, h, r, r)).astype(np.float32))
    return q, k, v, bias1, bias2


def _ref(q, k, v, bias1, bias2):
    d = q.shape[-1]
    s = jnp.einsum("bnrhd,bnshd->bnhrs", q / jnp.sqrt(jnp.float32(d)), k)
    if bias1 is not None:
        s = s + bias1
    if bias2 is not None:
        s = s + bias2
    return jnp.einsum("bnhrs,bnshd->bnrhd", jax.nn.softmax(s, -1), v)


@pytest.mark.parametrize("use_b1,use_b2", [(True, True), (True, False),
                                           (False, False)])
def test_matches_dense_reference(use_b1, use_b2):
    q, k, v, b1, b2 = _inputs()
    biases = ([b1] if use_b1 else []) + ([b2] if use_b1 and use_b2 else [])
    out = evoformer_attention(q, k, v, biases)
    ref = _ref(q, k, v, b1 if use_b1 else None,
               b2 if (use_b1 and use_b2) else None)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)


def test_chunked_matches_dense_and_grads():
    q, k, v, b1, b2 = _inputs(r=32)
    dense = evoformer_attention(q, k, v, [b1, b2])
    chunked = evoformer_attention(q, k, v, [b1, b2], chunk_size=8)
    np.testing.assert_allclose(np.asarray(chunked), np.asarray(dense),
                               rtol=2e-5, atol=2e-5)

    g1 = jax.grad(lambda q: jnp.sum(
        jnp.square(evoformer_attention(q, k, v, [b1, b2]))))(q)
    g2 = jax.grad(lambda q: jnp.sum(
        jnp.square(evoformer_attention(q, k, v, [b1, b2], chunk_size=8))))(q)
    np.testing.assert_allclose(np.asarray(g2), np.asarray(g1),
                               rtol=2e-4, atol=2e-4)


def test_bias_shape_validation():
    q, k, v, b1, b2 = _inputs()
    with pytest.raises(ValueError, match="bias1"):
        evoformer_attention(q, k, v, [b2])
    with pytest.raises(ValueError, match="bias2"):
        evoformer_attention(q, k, v, [b1, b1])
