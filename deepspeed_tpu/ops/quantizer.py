"""Block quantization primitives (int8 / int4, symmetric per-block scales).

Role parity with the reference quantizer kernels
(``csrc/quantization/{quantize,dequantize,quant_reduce,swizzled_quantize}.cu``)
used by ZeRO++ (qwZ quantized weights, qgZ quantized gradient collectives) and
inference WOQ. On TPU these are jnp expressions XLA fuses into surrounding
ops; the int4 packing uses two nibbles per int8 lane.
"""

from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp


class QuantizedTensor(NamedTuple):
    values: jnp.ndarray   # int8 payload (int4: packed two-per-byte)
    scales: jnp.ndarray   # f32 per-block scales
    shape: tuple          # original shape
    bits: int             # 8 or 4
    block: int


def _to_blocks(x: jnp.ndarray, block: int):
    flat = x.reshape(-1)
    pad = (-flat.size) % block
    if pad:
        flat = jnp.pad(flat, (0, pad))
    return flat.reshape(-1, block), pad


def _from_blocks(vals: jnp.ndarray, shape: tuple, dtype) -> jnp.ndarray:
    """Inverse of :func:`_to_blocks`: drop padding, restore shape."""
    flat = vals.reshape(-1)
    size = 1
    for s in shape:
        size *= s
    return flat[:size].reshape(shape).astype(dtype)


def quantize(x: jnp.ndarray, bits: int = 8, block: int = 256) -> QuantizedTensor:
    """Symmetric per-block quantization (reference ``quantize.cu`` semantics)."""
    assert bits in (8, 4), bits
    blocks, _ = _to_blocks(x.astype(jnp.float32), block)
    qmax = 127.0 if bits == 8 else 7.0
    absmax = jnp.max(jnp.abs(blocks), axis=-1, keepdims=True)
    scale = jnp.maximum(absmax, 1e-12) / qmax
    q = jnp.clip(jnp.round(blocks / scale), -qmax, qmax).astype(jnp.int8)
    if bits == 4:
        lo = q[:, 0::2] & 0x0F
        hi = (q[:, 1::2] & 0x0F) << 4
        q = (lo | hi).astype(jnp.int8)
    return QuantizedTensor(values=q, scales=scale[:, 0], shape=tuple(x.shape),
                           bits=bits, block=block)


def dequantize(qt: QuantizedTensor, dtype=jnp.float32) -> jnp.ndarray:
    """Reference ``dequantize.cu`` semantics."""
    q = qt.values
    if qt.bits == 4:
        lo = (q << 4).astype(jnp.int8) >> 4          # sign-extend low nibble
        hi = q >> 4                                   # arithmetic shift keeps sign
        q = jnp.stack([lo, hi], axis=-1).reshape(q.shape[0], -1)
    vals = q.astype(jnp.float32) * qt.scales[:, None]
    return _from_blocks(vals, qt.shape, dtype)


def quantize_signs(x: jnp.ndarray, block: int = 256):
    """1-bit quantization (reference ``compressed_allreduce`` payload,
    ``runtime/comm/nccl.py:17`` / ``csrc/quantization/quant_reduce.cu``):
    sign bits packed 8-per-byte + per-block mean-|x| scales. Returns
    ``(packed uint8 [N/8], scales f32 [N/block])`` over the flattened,
    block-padded input; ``block`` must be a multiple of 8."""
    assert block % 8 == 0, block
    blocks, _ = _to_blocks(x.astype(jnp.float32), block)
    scales = jnp.mean(jnp.abs(blocks), axis=-1)
    bits = (blocks >= 0).astype(jnp.uint8).reshape(-1, 8)
    weights = jnp.asarray([1, 2, 4, 8, 16, 32, 64, 128], jnp.uint8)
    packed = jnp.sum(bits * weights[None, :], axis=-1, dtype=jnp.uint8)
    return packed, scales


def dequantize_signs(packed: jnp.ndarray, scales: jnp.ndarray, size: int,
                     block: int = 256, dtype=jnp.float32) -> jnp.ndarray:
    """Inverse of :func:`quantize_signs`: ±scale per element, first ``size``
    elements (flat)."""
    weights = jnp.asarray([1, 2, 4, 8, 16, 32, 64, 128], jnp.uint8)
    bits = (packed.reshape(-1, 1) & weights[None, :]) > 0
    signs = jnp.where(bits, 1.0, -1.0).reshape(-1, block)
    vals = signs * scales[:, None]
    return vals.reshape(-1)[:size].astype(dtype)


def quantize_rows(x: jnp.ndarray, block: int = 128):
    """Shape-preserving symmetric int8 quantization with per-block scales
    along the LAST dim: ``x [..., L] -> (q int8 [..., L], scales [..., L/block])``.

    Unlike :func:`quantize` (which flattens), the output dims map 1:1 onto the
    input dims, so a sharded ``x`` quantizes shard-locally whenever the block
    axis isn't split mid-block — the property the ZeRO++ qwZ gather relies on
    (``parallel/qwz.py``; reference ``csrc/quantization/swizzled_quantize.cu``
    quantizes the local partition before the all-gather).
    """
    L = x.shape[-1]
    pad = (-L) % block
    xf = x.astype(jnp.float32)
    if pad:
        xf = jnp.pad(xf, [(0, 0)] * (x.ndim - 1) + [(0, pad)])
    nb = xf.shape[-1] // block
    blocks = xf.reshape(*xf.shape[:-1], nb, block)
    absmax = jnp.max(jnp.abs(blocks), axis=-1)
    scales = jnp.maximum(absmax, 1e-12) / 127.0
    q = jnp.clip(jnp.round(blocks / scales[..., None]), -127, 127).astype(jnp.int8)
    q = q.reshape(*xf.shape[:-1], nb * block)
    if pad:
        q = q[..., :L]
    return q, scales


def dequantize_rows(q: jnp.ndarray, scales: jnp.ndarray, dtype=jnp.float32,
                    block: int | None = None) -> jnp.ndarray:
    """Inverse of :func:`quantize_rows`. ``block`` must be passed when the
    last dim was padded (it cannot be inferred from the shapes then)."""
    L = q.shape[-1]
    nb = scales.shape[-1]
    if block is None:
        if L % nb:
            raise ValueError(
                f"dequantize_rows: last dim {L} not divisible by {nb} blocks; "
                "pass the block size used at quantization")
        block = L // nb
    pad = nb * block - L
    qf = q.astype(jnp.float32)
    if pad:
        qf = jnp.pad(qf, [(0, 0)] * (q.ndim - 1) + [(0, pad)])
    vals = qf.reshape(*qf.shape[:-1], nb, block) * scales[..., None]
    vals = vals.reshape(*qf.shape[:-1], nb * block)
    if pad:
        vals = vals[..., :L]
    return vals.astype(dtype)


def quantize_dequantize(x: jnp.ndarray, bits: int = 8, block: int = 256) -> jnp.ndarray:
    """Fake-quant round trip (reference ``fake_quantizer.cu``; QAT + tests)."""
    return dequantize(quantize(x, bits=bits, block=block), dtype=x.dtype)


def quantization_error(x: jnp.ndarray, bits: int = 8, block: int = 256) -> jnp.ndarray:
    """Residual for error-feedback compression (1-bit Adam family,
    ``runtime/comm/compressed.py`` semantics)."""
    return x - quantize_dequantize(x, bits=bits, block=block)


# ------------------------------------------------------------------ WOQ params
class QuantizedWeight:
    """A weight stored quantized in a param pytree (weight-only-quant
    inference, reference ``inference/quantization/`` WOQ layers).

    Registered pytree node: (values, scales) are children so the tree flows
    through jit/scan/sharding; (shape, bits, block) are static aux data —
    unlike :class:`QuantizedTensor` (a NamedTuple whose shape ints would be
    traced), reshapes stay static under jit. Stacked layer weights keep a
    leading layer dim on the children; ``shape`` is the PER-LAYER shape, so
    a ``lax.scan`` slice of the tree dequantizes directly.
    """

    def __init__(self, values, scales, shape, bits, block):
        self.values = values
        self.scales = scales
        self.shape = tuple(shape)
        self.bits = int(bits)
        self.block = int(block)

    def tree_flatten(self):
        return (self.values, self.scales), (self.shape, self.bits, self.block)

    @classmethod
    def tree_unflatten(cls, aux, children):
        return cls(children[0], children[1], *aux)


jax.tree_util.register_pytree_node(
    QuantizedWeight,
    lambda qw: qw.tree_flatten(),
    QuantizedWeight.tree_unflatten,
)


def maybe_dequantize(w, dtype):
    """Identity on arrays; dequantize on :class:`QuantizedWeight` — model
    code calls this at the point of use so dequantization happens just in
    time, per scanned layer slice (transient, fused by XLA)."""
    if not isinstance(w, QuantizedWeight):
        return w
    qt = QuantizedTensor(values=w.values, scales=w.scales, shape=w.shape,
                         bits=w.bits, block=w.block)
    return dequantize(qt, dtype)


def dequantize_layer(lp: dict, dtype) -> dict:
    """Just-in-time dequantization of a layer's weight dict (no-op on plain
    arrays); model layer fns call this first, so WOQ dense copies are
    per-scanned-layer transients."""
    return {k: maybe_dequantize(v, dtype) for k, v in lp.items()}


def path_names(path) -> set:
    """The dict keys on a leaf's tree path: what ``skip`` below, and the
    ragged engine's ``ModelSpec.woq_skip`` rule, match a leaf by."""
    return {str(getattr(k, "key", "")) for k in path}


def quantize_params(params, bits: int = 8, block: int = 256,
                    skip: tuple = ("embed",), stacked_key: str = "layers"):
    """Quantize the matrix leaves of a param pytree into
    :class:`QuantizedWeight` (weight-only quantization).

    Leaves under ``stacked_key`` carry a leading layer dim: matrices there
    are ndim >= 3 and quantize per layer (so a decoder ``lax.scan`` slices
    the tree naturally); ndim-2 leaves there are stacked *vectors* (norms)
    and stay dense. Outside the stacked subtree, plain ndim-2 matrices
    quantize whole. Leaves whose path contains a name in ``skip`` stay
    dense (embedding gathers want a plain array)."""

    def q(path, leaf):
        names = path_names(path)
        stacked = stacked_key in names
        min_ndim = 3 if stacked else 2
        if (not hasattr(leaf, "ndim") or leaf.ndim < min_ndim
                or not jnp.issubdtype(leaf.dtype, jnp.floating)
                or names & set(skip)):
            return leaf
        if stacked:  # per-layer blocks
            def qvs(w):
                qt = quantize(w, bits=bits, block=block)
                return qt.values, qt.scales

            vals, scales = jax.vmap(qvs)(leaf)
            return QuantizedWeight(vals, scales, leaf.shape[1:], bits, block)
        qt = quantize(leaf, bits=bits, block=block)
        return QuantizedWeight(qt.values, qt.scales, qt.shape, bits, block)

    return jax.tree_util.tree_map_with_path(q, params)
