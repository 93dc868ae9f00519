"""The ZeRO stage-3 weight gather, stated: dense, or int8 (ZeRO++ qwZ).

Role parity with the reference's parameter all-gather
(``runtime/zero/partition_parameters.py:1446 all_gather_coalesced``, its
quantized path + ``csrc/quantization/swizzled_quantize.cu``): under ZeRO-3
the dominant collective is the per-layer parameter all-gather.

TPU-native mechanism (not a port): a stage-3 weight is an fsdp-sharded array
and its gather a GSPMD resharding. Nothing obliges the partitioner to MAKE
that resharding: handed ``h @ w`` with ``w`` sharded on its contraction
dimension it builds a ring of partial matmuls instead (a windowed einsum),
which at fsdp = 4 and GPT-2 XL's widths meant K = 400 partial products and
1600-column pieces written into the FFN activation by bare
``dynamic-update-slice`` (ledger PR 31, ``gpt2-xl.train-zero3-x4``: 8.7% of
the step). So the engine says it (``WeightGather``, installed as
``ShardCtx.weight_gather`` at stage 3 over fsdp > 1): at the head of the
scanned layer body every leaf the plan shards over fsdp is constrained to its
spec with the fsdp axis DROPPED — one all-gather a weight, whole matmuls after
it — and the tied table / head outside the scan once a step
(``ShardCtx.whole_weight``). XLA's scheduler overlaps a layer's gathers with
that layer's compute.

``zero_optimization.quantized_weights`` (qwZ) only changes what rides the
wire: the still-sharded slice is quantized shard-locally
(``ops/quantizer.quantize_rows``), the int8 values + scales are what is
constrained, and the far side dequantizes to the compute dtype — half the
bytes. Backward is straight-through for both (``jax.custom_vjp``): the
cotangent of the whole weight is constrained back to the SHARDED spec, a
reduce-scatter (qwZ quantizes the weight wire, never the gradient math — that
is qgZ's job, ``comm/quantized_collectives.py``).

Per-leaf policy: only leaves whose slice is actually fsdp-sharded gather;
tensor/expert-sharded dims KEEP their sharding in the gather target (the hook
composes with TP — only the fsdp axis is gathered); the int8 wire takes
matrices of at least ``min_size`` elements.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec

from deepspeed_tpu.comm.topology import AXIS_FSDP
from deepspeed_tpu.ops.quantizer import dequantize_rows, quantize_rows


def _drop_fsdp(entry):
    """Remove the fsdp axis from one PartitionSpec entry."""
    if entry == AXIS_FSDP:
        return None
    if isinstance(entry, tuple) and AXIS_FSDP in entry:
        rest = tuple(a for a in entry if a != AXIS_FSDP)
        return rest[0] if len(rest) == 1 else (rest if rest else None)
    return entry


def _has_fsdp(spec: PartitionSpec) -> bool:
    return any(e == AXIS_FSDP or (isinstance(e, tuple) and AXIS_FSDP in e)
               for e in spec)


def gather_weight(w, mesh, spec: PartitionSpec, codec: str | None = None,
                  block: int = 128, grad: str = "scatter"):
    """State one stage-3 gather: ``w`` (logical full shape, sharded per
    ``spec``) comes back constrained to ``spec`` with the fsdp axis dropped
    and every other axis kept, so the partitioner has ONE thing it may do
    with the weight before its matmul: all-gather it over fsdp.

    ``codec`` is what rides the wire: ``None`` the weight as it is (the
    compute dtype), ``"int8"`` ``quantize_rows`` values + per-block scales
    (qwZ), dequantized on the far side.

    Backward is straight-through, and ``grad`` says how the whole weight's
    cotangent (a partial sum on every chip) gets back onto the sharded
    ``spec``. ``"scatter"`` constrains it to ``spec`` and nothing else: the
    partitioner fuses the reduce-scatter into the gradient's matmul as a
    ring of fsdp pieces (half an all-reduce's bytes, each hop under the next
    piece's product). ``"all_reduce"`` constrains it to the whole spec
    first: one whole matmul, an asynchronous all-reduce, a slice. On four
    v5e chips at GPT-2 XL's widths (my chip runs, PR 32, ms a step): inside
    the layer loop the ring's M = 400 pieces lose, 540.1 against 528.0,
    because the rest of the layer's backward hides the all-reduce; after the
    loop nothing is left to hide the tied table's 161 MB behind, and its
    ring wins, 528.1 -> 524.8. ``WeightGather`` chooses by that.
    """
    whole = NamedSharding(mesh, PartitionSpec(*(_drop_fsdp(e) for e in spec)))
    shard = NamedSharding(mesh, spec)
    wsc = jax.lax.with_sharding_constraint

    @jax.custom_vjp
    def f(x):
        if codec is None:
            return wsc(x, whole)
        # scales [..., nb]: same leading dims, last dim shrinks by the block
        # factor — the gathered spec transfers dim-for-dim
        q, s = quantize_rows(x, block=block)
        return dequantize_rows(wsc(q, whole), wsc(s, whole), x.dtype,
                               block=block)

    def bwd(_, g):
        if grad == "all_reduce":
            g = wsc(g, whole)
        return (wsc(g, shard),)

    f.defvjp(lambda x: (f(x), None), bwd)
    return f(w)


def quantized_gather(w, mesh, slice_spec: PartitionSpec, block: int):
    """quantize -> gather(int8) -> dequantize: :func:`gather_weight` with the
    int8 codec."""
    return gather_weight(w, mesh, slice_spec, "int8", block)


class WeightGather:
    """The stage-3 gather hook the engine installs as
    ``ShardCtx.weight_gather``: built from the plan's ``param_specs``, it
    gathers exactly the leaves the plan shards over fsdp and passes every
    other leaf through (biases and norms under ``persistence_threshold``, a
    leaf whose only sharded dimension is tensor- or expert-parallel).

    ``codec="int8"`` (``zero_optimization.quantized_weights``) puts the
    scanned layers' matrices of ``min_size`` elements and more on the int8
    wire; smaller leaves, vectors and the leaves outside the scan stay dense.
    """

    def __init__(self, mesh, param_specs, codec: str | None = None,
                 block: int = 128, min_size: int = 65536):
        self.mesh, self.codec, self.block, self.min_size = (
            mesh, codec, block, min_size)
        self.param_specs = param_specs
        # the scan body sees the stacked leaves with the layers dim dropped
        self._layer_specs, self._layer_def = jax.tree_util.tree_flatten(
            jax.tree_util.tree_map(
                lambda s: PartitionSpec(*s[1:]), param_specs.get("layers", {}),
                is_leaf=lambda x: isinstance(x, PartitionSpec)),
            is_leaf=lambda x: isinstance(x, PartitionSpec))

    def _codec_for(self, w):
        return (self.codec if w.ndim >= 2 and w.size >= self.min_size
                else None)

    def layer(self, lp):
        """One scanned layer's weight slice (compute-cast already)."""
        lp_flat, lp_def = jax.tree_util.tree_flatten(lp)
        if lp_def != self._layer_def:
            # a model that hands over something else than a slice of the
            # plan's "layers" subtree (nemotron_h: a per-kind dict, and no
            # such subtree) is left to the partitioner rather than mis-paired
            return lp
        return jax.tree_util.tree_unflatten(lp_def, [
            gather_weight(w, self.mesh, spec, self._codec_for(w), self.block,
                          grad="all_reduce")
            if _gathers(w, spec) else w
            for w, spec in zip(lp_flat, self._layer_specs)])

    def leaf(self, w, *path):
        """A leaf outside the scan, named by its path in the params tree."""
        spec = self.param_specs
        for key in path:
            spec = spec[key]
        return gather_weight(w, self.mesh, spec) if _gathers(w, spec) else w

    def gathered(self, abstract_params):
        """(path, elements, codec, scanned) of every leaf the plan shards
        over fsdp, for the engine's comms plan: the stacked layers whole
        (every layer's slice passes the hook once a pass) and the leaves
        outside the scan."""
        out = []
        flat = jax.tree_util.tree_flatten_with_path(abstract_params)[0]
        specs = jax.tree_util.tree_leaves(
            self.param_specs, is_leaf=lambda x: isinstance(x, PartitionSpec))
        for (path, leaf), spec in zip(flat, specs):
            stacked = getattr(path[0], "key", None) == "layers"
            spec = PartitionSpec(*spec[1:]) if stacked else spec
            shape = leaf.shape[1:] if stacked else leaf.shape
            one = jax.ShapeDtypeStruct(shape, leaf.dtype)
            if _gathers(one, spec):
                out.append((jax.tree_util.keystr(path), leaf.size,
                            self._codec_for(one) if stacked else None,
                            stacked))
        return out


def _gathers(w, spec: PartitionSpec) -> bool:
    return (hasattr(w, "ndim") and jnp.issubdtype(w.dtype, jnp.floating)
            and _has_fsdp(spec))
