"""Expert FFNs of the inference paths, shared by the MoE families.

``routed_experts`` is the dropless all-experts einsum both ``mixtral`` and
``deepseek`` serve through; the families differ only in how a token's
combine weights come out of the router's logits (its keyword arguments).
``swiglu`` is the plain gated FFN beside it: a dense layer, shared experts.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax


def swiglu(h: jnp.ndarray, w_gate, w_up, w_down) -> jnp.ndarray:
    """``w_down(silu(h w_gate) * h w_up)``, weights cast to ``h``'s dtype."""
    dtype = h.dtype
    return (jax.nn.silu(h @ w_gate.astype(dtype)) * (h @ w_up.astype(dtype))
            ) @ w_down.astype(dtype)


def routed_experts(h: jnp.ndarray, router_w, w_gate, w_up, w_down,
                   top_k: int, *, scoring: str = "softmax", bias=None,
                   renormalize: bool = True, scale: float = 1.0,
                   eps: float = 1e-9) -> jnp.ndarray:
    """Dropless per-token top-k MoE for the inference paths (``h`` [T, D]
    flat tokens).

    Role parity with the reference's ragged MoE serving stack
    (``inference/v2/model_implementations/mixtral/model.py`` +
    ``inference/v2/kernels/ragged_ops`` top-k gating, MoE gather/scatter):
    the CUDA version compacts tokens per expert with gather/scatter kernels;
    the TPU-native shape is a batched [E] einsum — every expert processes
    every token on the MXU and the router's top-k weights combine the
    results. Exact (no capacity, no drops), at E/top_k x the ideal expert
    FLOPs — the right trade at serving token counts, where the expert GEMMs
    are small and a compaction pass would serialize; a sort-based exact
    dispatch is the optimization point if prefill chunks ever dominate.

    Routing, in float32: scores are ``softmax`` (Mixtral) or ``sigmoid``
    (DeepSeek-V3) of the router's logits; the ``top_k`` experts are picked
    by ``scores + bias`` (``bias`` [E]: the auxiliary-loss-free selection
    bias, which never enters a weight); the picked scores are divided by
    their sum (``renormalize``) and multiplied by ``scale``. The defaults
    are Mixtral's.
    """
    t, d = h.shape
    logits = h.astype(jnp.float32) @ router_w.astype(jnp.float32)
    if scoring == "softmax":
        scores = jax.nn.softmax(logits, axis=-1)
    elif scoring == "sigmoid":
        scores = jax.nn.sigmoid(logits)
    else:
        raise ValueError(f"unknown router scoring {scoring!r}")
    if bias is None:
        topv, topi = lax.top_k(scores, top_k)
    else:
        _, topi = lax.top_k(scores + bias.astype(jnp.float32), top_k)
        topv = jnp.take_along_axis(scores, topi, axis=-1)
    if renormalize:
        topv = topv / (jnp.sum(topv, axis=-1, keepdims=True) + eps)
    if scale != 1.0:
        topv = topv * scale
    e = scores.shape[-1]
    w = jnp.zeros((t, e), jnp.float32).at[
        jnp.arange(t)[:, None], topi].set(topv)
    dtype = h.dtype
    g = jnp.einsum("td,edf->tef", h, w_gate.astype(dtype))
    u = jnp.einsum("td,edf->tef", h, w_up.astype(dtype))
    y = jnp.einsum("tef,efd->ted", jax.nn.silu(g) * u, w_down.astype(dtype))
    return jnp.einsum("ted,te->td", y, w.astype(dtype))
