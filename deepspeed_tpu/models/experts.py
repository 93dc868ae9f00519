"""Expert FFNs of the inference paths, shared by the MoE families.

``routed_experts`` is the dropless per-token top-k MoE both ``mixtral`` and
``deepseek`` serve through; the families differ only in how a token's combine
weights come out of the router's logits (its keyword arguments). It has two
exact forms of the same computation, and ``expert_form`` picks between them
from the step's shapes. ``routed_experts_einsum`` is the one form that
differentiates and partitions over a mesh, for the callers that need either.
``swiglu`` is the plain gated FFN beside them: a dense layer, shared experts.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax

from deepspeed_tpu.ops.pallas.moe_gmm import ROW_ALIGN, grouped_swiglu

# ``expert_form``'s crossover, in rows of a step. From a sweep of both forms,
# one layer's expert FFN alone on a TPU v5e, at 8 x [4096, 14336] top-2 and
# 64 x [2048, 1408] top-6 (PERF.md section 6, PR 27): at 240 rows the forms
# tie (4.08 / 4.08 ms) or the einsum leads by 2% (1.66 / 1.69 ms), at 256 the
# grouped form leads by 49% and 5%, and the einsum grows by the row from there
GROUPED_MIN_ROWS = 256
# the rows one call of the grouped kernel takes: an expert may be picked by
# all of them, and the kernel holds an expert's rows in VMEM
_GROUPED_MAX_ROWS = 512


def swiglu(h: jnp.ndarray, w_gate, w_up, w_down) -> jnp.ndarray:
    """``w_down(silu(h w_gate) * h w_up)``, weights cast to ``h``'s dtype."""
    dtype = h.dtype
    return (jax.nn.silu(h @ w_gate.astype(dtype)) * (h @ w_up.astype(dtype))
            ) @ w_down.astype(dtype)


def expert_form(rows: int, num_experts: int, top_k: int) -> str:
    """``"grouped"`` or ``"dense"``: the form ``routed_experts`` takes for a
    step of ``rows`` tokens (static under ``jit``: a step program has one).

    The all-experts einsum multiplies every token by every expert and reads
    every expert's weights once. That is ``num_experts / top_k`` times the
    needed FLOPs, and free while the layer is bound by the weight bytes: the
    einsum turns FLOP-bound at the chip's ridge point,
    ``rows > peak FLOP/s / peak bytes/s`` (~240 on a TPU v5e, whatever the
    model; measured there too). Above it the grouped form does the needed
    products only and still reads the weights once; below it the grouped
    form saves nothing (at decode row counts the picks touch every expert)
    and pays for the sort. A router that picks every expert leaves nothing
    to skip at any size.
    """
    grouped = rows >= GROUPED_MIN_ROWS and top_k < num_experts
    return "grouped" if grouped else "dense"


def _route(h, router_w, top_k: int, scoring: str, bias, renormalize: bool,
           scale: float, eps: float):
    """The router, in float32 -> (combine weights ``[T, top_k]``, picked
    experts ``[T, top_k]``)."""
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
    return topv, topi


def _einsum_experts(h, topv, topi, w_gate, w_up, w_down):
    """Every expert over every token on the MXU, the router's weights (zero
    for the experts a token did not pick) combining the results."""
    t, _ = h.shape
    e = w_gate.shape[0]
    w = jnp.zeros((t, e), jnp.float32).at[
        jnp.arange(t)[:, None], topi].set(topv)
    dtype = h.dtype
    g = jnp.einsum("td,edf->tef", h, w_gate.astype(dtype))
    u = jnp.einsum("td,edf->tef", h, w_up.astype(dtype))
    y = jnp.einsum("tef,efd->ted", jax.nn.silu(g) * u, w_down.astype(dtype))
    return jnp.einsum("ted,te->td", y, w.astype(dtype))


def _grouped_experts(h, topv, topi, w_gate, w_up, w_down, first_expert,
                     num_experts):
    """Each pick through its own expert only: the ``T x top_k`` picks are
    sorted by expert (a counting sort: a pick's place is its expert's first
    row plus the picks of that expert before it), every expert's rows go
    through that expert's weights (``ops/pallas/moe_gmm.py``), and the
    results come back to token order for the router's weights. The layer's
    ``num_experts`` experts start at ``first_expert`` of the weights."""
    t, d = h.shape
    e, k = num_experts, topi.shape[1]
    if t > _GROUPED_MAX_ROWS:
        pad = -t % _GROUPED_MAX_ROWS
        parts = [jnp.pad(a, ((0, pad), (0, 0))).reshape(
            -1, _GROUPED_MAX_ROWS, a.shape[1]) for a in (h, topv, topi)]
        out = lax.map(lambda p: _grouped_experts(
            *p, w_gate, w_up, w_down, first_expert, e), tuple(parts))
        return out.reshape(-1, d)[:t]
    # The kernel's shapes are those of a full call whatever ``t`` is, so
    # that every step program of an engine shares ONE traced kernel
    # (``grouped_swiglu``); the rows past the step's own are never read.
    # A pass of the kernel is 128 rows where an expert's share of a full call
    # is more than 64 (128 at Mixtral's 2 of 8), else 64 (48 at Moonlight's
    # 6 of 64): one pass an expert either way.
    tm = 128 if _GROUPED_MAX_ROWS * k > 64 * e else 64
    flat = topi.reshape(-1)
    before = jnp.cumsum(flat[:, None] == jnp.arange(e)[None, :], axis=0,
                        dtype=jnp.int32)
    counts = before[-1]
    aligned = -(-counts // ROW_ALIGN) * ROW_ALIGN
    row0 = jnp.cumsum(aligned) - aligned
    place = row0[flat] + jnp.take_along_axis(before, flat[:, None], 1)[:, 0] - 1
    rows = (-(-(_GROUPED_MAX_ROWS * k + e * (ROW_ALIGN - 1)) // ROW_ALIGN)
            * ROW_ALIGN + tm)
    token = jnp.zeros((rows,), jnp.int32).at[place].set(
        jnp.arange(t * k, dtype=jnp.int32) // k)
    dtype = h.dtype
    y = grouped_swiglu(h[token], w_gate.astype(dtype), w_up.astype(dtype),
                       w_down.astype(dtype), row0, counts, tm,
                       max_rows=_GROUPED_MAX_ROWS, first_expert=first_expert)
    return jnp.einsum("tkd,tk->td", y[place].reshape(t, k, d),
                      topv).astype(dtype)


def routed_experts(h: jnp.ndarray, router_w, w_gate, w_up, w_down,
                   top_k: int, *, stacked=None, scoring: str = "softmax",
                   bias=None, renormalize: bool = True, scale: float = 1.0,
                   eps: float = 1e-9) -> jnp.ndarray:
    """Dropless per-token top-k MoE for the serving paths (``h`` [T, D]
    flat tokens): exact (no capacity, no drops, every pick computed), bf16
    operands with float32 accumulation in either form.

    Role parity with the reference's ragged MoE serving stack
    (``inference/v2/model_implementations/mixtral/model.py`` +
    ``inference/v2/kernels/ragged_ops`` top-k gating, MoE gather/scatter):
    the CUDA version compacts tokens per expert with gather/scatter kernels.
    Here a step above the chip's ridge point does the same (sort the picks
    by expert, one grouped matmul kernel that reads an expert's weights
    once, un-sort), and a step below it runs the batched [E] einsum, every
    expert over every token: ``expert_form`` has the rule and its reason.
    The grouped kernel is a Mosaic kernel: it has no gradient and GSPMD
    cannot partition it, so this function is for one device's forward pass
    (the ragged engine's step programs).

    The weights are the layer's, ``[E, ...]``. A layer of a scan also gives
    ``stacked``: every layer's weights whole and this layer's place in them,
    ``(w_gate, w_up, w_down, first_expert)`` of ``expert_stacks``. The
    grouped form reads those, because a Mosaic kernel's operand is an array
    in HBM and a scan's slice of the weights would be copied to become one;
    the einsum form reads the slice, which XLA fuses into the einsum (given
    the whole stack it re-lays all of it out, every step, outside the loop).
    Whichever a step program does not read is dead code in it.

    Routing, in float32: scores are ``softmax`` (Mixtral) or ``sigmoid``
    (DeepSeek-V3) of the router's logits; the ``top_k`` experts are picked
    by ``scores + bias`` (``bias`` [E]: the auxiliary-loss-free selection
    bias, which never enters a weight); the picked scores are divided by
    their sum (``renormalize``) and multiplied by ``scale``. The defaults
    are Mixtral's.
    """
    topv, topi = _route(h, router_w, top_k, scoring, bias, renormalize,
                        scale, eps)
    e = w_gate.shape[0]
    if expert_form(h.shape[0], e, top_k) == "dense":
        return _einsum_experts(h, topv, topi, w_gate, w_up, w_down)
    return _grouped_experts(h, topv, topi,
                            *(stacked or (w_gate, w_up, w_down, 0)), e)


def expert_stacks(layers: dict):
    """For a serving scan over the stacked ``layers``: ``(layers, stacks)``,
    ``stacks`` the expert weights whole, ``[L, E, ...]`` as ``[L x E, ...]``
    (the same bytes), for the scan's body to close over, and ``layers`` with
    ``first_expert`` [L] beside the weights, so that a layer can hand
    ``routed_experts`` its ``stacked``. Weights that are not plain arrays
    (weight-only quantization dequantizes a layer's slice) have no stacks:
    ``(layers, None)``."""
    weights = [layers[k] for k in ("w_gate", "w_up", "w_down")]
    if not all(isinstance(w, jax.Array) for w in weights):
        return layers, None
    n, e = weights[0].shape[:2]
    first = jnp.arange(n, dtype=jnp.int32) * e
    return ({**layers, "first_expert": first},
            tuple(w.reshape((n * e,) + w.shape[2:]) for w in weights))


def routed_experts_einsum(h: jnp.ndarray, router_w, w_gate, w_up, w_down,
                          top_k: int, *, scoring: str = "softmax", bias=None,
                          renormalize: bool = True, scale: float = 1.0,
                          eps: float = 1e-9) -> jnp.ndarray:
    """``routed_experts`` in its einsum form at every row count: plain XLA,
    so it differentiates (``deepseek``'s training-shaped ``forward`` /
    ``loss_fn``) and partitions over a mesh (``mixtral``'s dense-cache
    inference layer under ``InferenceEngine``'s tensor-parallel mesh)."""
    topv, topi = _route(h, router_w, top_k, scoring, bias, renormalize,
                        scale, eps)
    return _einsum_experts(h, topv, topi, w_gate, w_up, w_down)
