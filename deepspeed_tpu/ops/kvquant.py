"""Low-bit paged KV cache: the *block* is the unit of quantization.

Everywhere a KV block lives — the HBM pool, the host/disk tiers
(``inference/kvtier.py``), the prefix-cache retained set, the KVHandoff
wire format (``serving/cluster.py``) — it is stored as a
:class:`QuantizedKV` pair ``(q, s)``: the payload in a 1-byte storage
dtype plus one scale per (token-row, kv-head). Quantization happens ONCE,
at write time inside ``models/paged.write_kv_paged``; dequantization is
fused into the jitted gather on the decode/prefill hot path
(``ops/attention.paged_attention``), so fp copies of pool blocks are
per-dispatch transients XLA fuses away, never residents. Because rows
quantize independently, the incremental scatter stays exact: rewriting
one token's row never re-rounds a neighbour.

Codecs (role parity with the reference's KV quantization in
``inference/v2`` and ZeRO++'s qgZ discipline of compressing ON the wire,
not beside it — see EQuARX for the native-XLA version of the same move):

- ``int8``: symmetric per-row-per-head absmax scaling, payload ``int8``.
- ``fp8``: e4m3 emulated via ``ml_dtypes.float8_e4m3fn`` storage with the
  same absmax pre-scale (amax -> 448); on TPU generations with native fp8
  the storage dtype is already the right one.

With f16 scales at head_dim 64 a block costs ``1 + 2/64`` bytes/element
— ~1.94x the resident blocks per HBM byte vs an fp16 pool (>= the 1.8x
acceptance floor), and the same multiplier applies to handoff bytes,
tier bytes and admission headroom because every consumer derives from
``kv_bytes_per_token()`` over the quantized pytree.

The subsystem is gated by a measured drift budget, not exact parity:
a bounded greedy token-match rate vs the fp16 path (``DRIFT_BUDGET``);
``quant="off"`` (the default) keeps the engine bit-identical to the
unquantized path — the pool is then a plain array pytree and none of this
module's jitted code runs.

The quantized TP logits collective (``quantized_logits_all_gather``)
reuses the packed-collective discipline of ``comm/quantized_collectives``
for the inference side: the vocab-sharded logits all-gather carries an
int8 payload + per-shard scales instead of fp values, an explicit
shard_map region whose HLO all-gather operand is ``s8`` (assertable the
same way the training wire is).
"""

from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

DRIFT_BUDGET = {
    # greedy continuations: fraction of position-wise matching tokens
    # (prefix agreement) vs the fp16 path
    "greedy_match_min": 0.95,
}


class KVQCodec(NamedTuple):
    """One KV-block codec: 1-byte storage + per-row-per-head scales."""

    name: str
    storage: str        # numpy dtype name of the payload
    scale: str          # numpy dtype name of the scales
    qmax: float         # absmax maps onto +-qmax

    @property
    def storage_dtype(self):
        return np.dtype(self.storage)

    @property
    def scale_dtype(self):
        return np.dtype(self.scale)


CODECS = {
    "int8": KVQCodec("int8", "int8", "float16", 127.0),
    # e4m3 finite max is 448; absmax pre-scaling uses the full range
    "fp8": KVQCodec("fp8", "float8_e4m3fn", "float16", 448.0),
}


def get_codec(name: str) -> KVQCodec:
    try:
        return CODECS[name]
    except KeyError:
        raise ValueError(
            f"unknown KV codec {name!r}; supported: {sorted(CODECS)}"
        ) from None


# --------------------------------------------------------------- row codec
def quantize_kv_rows(x: jnp.ndarray, codec: KVQCodec):
    """Quantize KV rows along the last (head_dim) axis: ``x [..., D] ->
    (q storage [..., D], s scale [...])``. The scale is rounded to its
    storage dtype BEFORE the divide so write and read use the identical
    value (no double-rounding skew between quantize and dequantize)."""
    xf = x.astype(jnp.float32)
    amax = jnp.max(jnp.abs(xf), axis=-1)
    s = jnp.where(amax > 0, amax / codec.qmax, 1.0).astype(codec.scale_dtype)
    y = xf / s.astype(jnp.float32)[..., None]
    # clip covers both codecs: int8 range, and e4m3 saturation (the f16
    # scale rounds, so y can peek past qmax by one ulp)
    y = jnp.clip(y, -codec.qmax, codec.qmax)
    if codec.name == "int8":
        q = jnp.round(y).astype(jnp.int8)
    else:
        q = y.astype(jnp.dtype(codec.storage))
    return q, s


def dequantize_kv_rows(q: jnp.ndarray, s: jnp.ndarray,
                       dtype=jnp.float32) -> jnp.ndarray:
    """Inverse of :func:`quantize_kv_rows` (fused into the gather)."""
    return (q.astype(jnp.float32)
            * s.astype(jnp.float32)[..., None]).astype(dtype)


# ------------------------------------------------------------- the pytree
class QuantizedKV:
    """A quantized KV pool (or any block-axis slice of one) as a registered
    pytree node, modeled on ``ops/quantizer.QuantizedWeight``.

    Children ``(q, s)`` flow through jit / lax.scan / tree_map / donation;
    static aux ``(codec, dtype)`` ride along every transform. Both arrays
    have the paged contract's form (``models/paged.py``): blocks on the
    leading axes, a lane-dense row behind them. The payload ``q`` is
    ``[L, nb, bs, Hkv*D]`` (``[blocks, bs, Hkv*D]`` as a layer sees it); the
    scales ``s``, one per (row, head), are one row a block, ``[L, nb,
    bs*Hkv]`` (a ``[.., bs, Hkv]`` array pads its 8 or 25 heads to 128 lanes
    on the chip, and its scatter and its gather each want a layout of
    their own: a copy of all scales a layer). Head count and size are read
    off the two shapes. The properties keep model/engine code
    shape-compatible without edits:

    - ``.shape`` is the payload shape (``kc.shape[1]`` is still the block
      size);
    - ``.dtype`` is the COMPUTE dtype (``cache["k"].dtype`` still picks
      the activation dtype for the forward);
    - ``.nbytes`` is payload + scales, so ``kv_bytes_per_token()``, the
      memledger owners, the tier cost models and ``KVHandoff.nbytes`` are
      quantization-aware for free.

    Picklable (handoff wire format, disk-tier records): arrays are
    pickled as numpy so a record written from device memory reads back
    host-side.
    """

    is_quantized_kv = True

    def __init__(self, q, s, codec: str, dtype: str):
        self.q = q
        self.s = s
        self.codec = codec
        self._dtype_name = dtype

    # -- pytree protocol ---------------------------------------------------
    def tree_flatten(self):
        return (self.q, self.s), (self.codec, self._dtype_name)

    @classmethod
    def tree_unflatten(cls, aux, children):
        return cls(children[0], children[1], *aux)

    # -- array-compatibility surface ---------------------------------------
    @property
    def shape(self):
        return self.q.shape

    @property
    def dtype(self):
        return np.dtype(self._dtype_name)

    @property
    def nbytes(self):
        return int(self.q.nbytes) + int(self.s.nbytes)

    def __repr__(self):
        return (f"QuantizedKV(codec={self.codec!r}, shape={self.shape}, "
                f"dtype={self._dtype_name})")

    # -- pool ops (the two touch points of the paged contract) -------------
    def scatter_rows(self, blk, off, rows):
        """Quantize-at-write: scatter new KV rows ``[T, Hkv, D]`` into
        ``(block, offset)`` cells of the pool ``[blocks, bs, Hkv*D]``
        (``models/paged.write_kv_paged``). A row's ``Hkv`` scales go as one
        window into its block's scale row at lane ``offset * Hkv``."""
        codec = get_codec(self.codec)
        q_rows, s_rows = quantize_kv_rows(rows, codec)
        t, hkv = s_rows.shape
        scales = jax.lax.scatter(
            self.s, jnp.stack([blk, off * hkv], axis=-1), s_rows,
            jax.lax.ScatterDimensionNumbers(
                update_window_dims=(1,), inserted_window_dims=(0,),
                scatter_dims_to_operand_dims=(0, 1)))
        return QuantizedKV(self.q.at[blk, off].set(q_rows.reshape(t, -1)),
                           scales, self.codec, self._dtype_name)

    def gather_dequant(self, tables):
        """Dequant fused into the gather: ``tables [T, MB]`` over the pool
        returns fp32 context ``[T, MB, bs, Hkv, D]`` — a per-dispatch
        transient inside the attention program, fused by XLA with the
        surrounding einsum (``ops/attention``)."""
        q = self.q[tables]                           # [T, MB, bs, Hkv*D]
        s = self.s[tables].reshape(q.shape[:3] + (-1,))
        return dequantize_kv_rows(q.reshape(s.shape + (-1,)), s)

    # -- pickling (handoff / disk spill payloads) --------------------------
    def __getstate__(self):
        return {"q": np.asarray(self.q), "s": np.asarray(self.s),
                "codec": self.codec, "dtype": self._dtype_name}

    def __setstate__(self, state):
        self.q = state["q"]
        self.s = state["s"]
        self.codec = state["codec"]
        self._dtype_name = state["dtype"]


jax.tree_util.register_pytree_node(
    QuantizedKV,
    lambda t: t.tree_flatten(),
    QuantizedKV.tree_unflatten,
)


# ------------------------------------------------------------- pool sizes
def paged_block_bytes(init_fn, num_blocks: int, block_size: int, dtype) -> int:
    """Bytes one UNQUANTIZED block (all layers, k+v) would cost at
    ``dtype`` — the baseline for the bytes-saved counter and the
    resident-block multiplier, computed from shapes only."""
    struct = jax.eval_shape(lambda: init_fn(num_blocks, block_size, dtype))
    total = 0
    for leaf in jax.tree_util.tree_leaves(struct):
        total += (int(leaf.shape[0]) * int(np.prod(leaf.shape[2:]))
                  * np.dtype(leaf.dtype).itemsize)
    return total


# ------------------------------------------------------------ config surface
class ParsedQuant(NamedTuple):
    kv: KVQCodec | None   # KV-block codec (None = fp pool)
    woq_bits: int         # weight-only quant bits (0 = dense weights)
    qcol: bool            # quantize the TP inference collectives


def parse_quant(spec) -> ParsedQuant:
    """Parse the ONE low-bit config surface (``RaggedConfig.quant``).

    Grammar: ``"off"`` | ``"int8"`` | ``"fp8"`` | ``"woq8"`` | ``"woq4"``
    | ``"qcol"``, joined with ``+`` — e.g. ``"int8+woq8+qcol"`` buys the
    full low-bit serving path. ``None``/empty means off.
    """
    if spec is None:
        return ParsedQuant(None, 0, False)
    if not isinstance(spec, str):
        raise ValueError(f"quant must be a string, got {type(spec).__name__}")
    kv, woq, qcol = None, 0, False
    for part in spec.split("+"):
        part = part.strip().lower()
        if part in ("", "off", "none"):
            continue
        elif part in CODECS:
            if kv is not None:
                raise ValueError(f"quant={spec!r}: more than one KV codec")
            kv = CODECS[part]
        elif part in ("woq8", "woq4"):
            if woq:
                raise ValueError(f"quant={spec!r}: more than one woq spec")
            woq = int(part[3:])
        elif part == "qcol":
            qcol = True
        else:
            raise ValueError(
                f"quant={spec!r}: unknown component {part!r}; grammar: "
                "off | int8 | fp8 | woq8 | woq4 | qcol joined with '+'")
    return ParsedQuant(kv, woq, qcol)


# ------------------------------------------------- quantized TP collective
def quantized_logits_all_gather(x: jnp.ndarray, mesh, axis: str = "tensor"):
    """Quantize the vocab-sharded logits all-gather of sharded inference.

    GSPMD inserts the gather implicitly when the sampler consumes
    tensor-sharded logits; this replaces it with an EXPLICIT shard_map
    region (the ``comm/quantized_collectives`` discipline) whose wire
    operand is the int8 payload + one f32 scale per (row, shard) — so the
    collective moves ~1/2 (bf16) to ~1/4 (f32) of the bytes, assertable
    in the compiled HLO as an ``s8`` all-gather operand.

    Identity when there is no mesh, no ``axis`` dimension, a trivial
    shard count, or a vocab that doesn't split evenly (the quantized wire
    is an optimization, never a requirement).
    """
    from jax.sharding import PartitionSpec as P

    from deepspeed_tpu.ops.quantizer import dequantize_rows, quantize_rows

    if mesh is None:
        return x
    n = dict(getattr(mesh, "shape", {})).get(axis, 1)
    if n <= 1 or x.shape[-1] % n:
        return x
    local = x.shape[-1] // n
    gather_dim = x.ndim - 1
    spec_in = P(*([None] * gather_dim), axis)

    def body(xs):
        # one scale per row per shard: block == the local shard width
        q, s = quantize_rows(xs, block=local)
        qg = jax.lax.all_gather(q, axis, axis=gather_dim, tiled=True)
        sg = jax.lax.all_gather(s, axis, axis=gather_dim, tiled=True)
        return dequantize_rows(qg, sg, x.dtype, block=local)

    from deepspeed_tpu.utils.compat import shard_map_compat

    mapped = shard_map_compat(body, mesh=mesh, in_specs=(spec_in,),
                              out_specs=P(), axis_names={axis},
                              check_vma=False)
    return mapped(x)


# ------------------------------------------------------------ drift metrics
def token_match_rate(want: dict, got: dict) -> float:
    """Greedy drift gauge: position-wise prefix agreement of generated
    token lists, averaged over sequences (1.0 = token-identical)."""
    total = match = 0
    for uid, ref in want.items():
        have = got.get(uid) or []
        total += len(ref)
        for a, b in zip(ref, have):
            if a != b:
                break
            match += 1
    return match / total if total else 1.0


def drift_verdict(greedy_match: float, budget: dict | None = None) -> dict:
    """The gate CI/bench applies: measured drift vs ``DRIFT_BUDGET``."""
    b = dict(DRIFT_BUDGET, **(budget or {}))
    return {
        "ok": bool(greedy_match >= b["greedy_match_min"]),
        "greedy_token_match_rate": round(float(greedy_match), 4),
        "budget": b,
    }
