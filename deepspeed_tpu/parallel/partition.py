"""Sharding planner: ZeRO stages + tensor parallelism as PartitionSpecs.

This is the TPU-native replacement for three reference subsystems at once:
- ZeRO partitioning machinery (``runtime/zero/stage_1_and_2.py:134``,
  ``stage3.py:148``, ``partition_parameters.py:884``): stages become
  *declarative sharding choices* over the ``fsdp`` mesh axis; XLA's SPMD
  partitioner inserts the allgather/reduce-scatter that the reference
  hand-orchestrates with hooks and bucket streams.
- AutoTP (``module_inject/auto_tp.py:194``, kv-head aware ``tp_shard.py``):
  models declare logical axes per param dim; the planner maps them to the
  ``tensor`` axis, with unit-granularity checks (a kv-head dim is only sharded
  if the *head count*, not just the dim size, divides the axis).
- The ZeRO-3 prefetch coordinator (``partitioned_param_coordinator.py:73``):
  per-layer gather/release falls out of scanning over a layer-stacked param
  pytree whose within-layer dims are fsdp-sharded. The gather itself is NOT
  left to the partitioner: the planner shards the largest divisible dim of a
  leaf, which for ``[1600,1600]`` projections and ``w_out`` is the
  contraction dim, and for such a matmul the partitioner prefers a ring of
  K/fsdp-wide partial products to a gather (four K = 400 products a
  projection, four 1600-column pieces of ``h @ w_in`` each written into the
  ``[4,1024,6400]`` result by a bare ``dynamic-update-slice``: the second
  largest operation of the four-chip step, ledger PR 31,
  ``gpt2-xl.train-zero3-x4``). At stage 3 the engine therefore STATES the
  gather at the head of each scan step (``parallel/qwz.WeightGather`` through
  ``ShardCtx.layer_weights``; the leaves outside the scan through
  ``ShardCtx.whole_weight``): one all-gather a weight, whole matmuls, the
  gradient reduce-scattered back onto ``grad_specs``.

Stage semantics (reference ``runtime/zero/config.py:401``):
  0: params/grads/opt-state replicated (pure DP; grads psum)
  1: opt-state sharded
  2: + grads sharded (psum -> reduce-scatter at the accumulation boundary)
  3: + params sharded (all-gathered at the head of each scan step)
"""

from __future__ import annotations

from dataclasses import dataclass, field
from typing import Any

import jax
from jax.sharding import NamedSharding, PartitionSpec

from deepspeed_tpu.comm.topology import (
    AXIS_DATA,
    AXIS_EXPERT,
    AXIS_FSDP,
    AXIS_PIPE,
    AXIS_SEQ,
    AXIS_TENSOR,
    MeshTopology,
)

# Logical param axis -> mesh axis for model parallelism.
TP_LOGICAL_TO_MESH = {
    "heads": AXIS_TENSOR,
    "kv_heads": AXIS_TENSOR,
    "ffn": AXIS_TENSOR,
    "vocab": AXIS_TENSOR,
    "experts": AXIS_EXPERT,
}
# Axes the fsdp planner may not claim.
_FSDP_EXCLUDED = {"layers", "experts"}


def _spec_for_param(
    axes: tuple,
    shape: tuple,
    topo: MeshTopology,
    shard_params_fsdp: bool,
    use_tp: bool,
    dim_units: dict,
    persistence_threshold: int,
    pp_fsdp: bool = False,
    hierarchical: bool = False,
) -> PartitionSpec:
    assign: list = [None] * len(shape)
    size = 1
    for s in shape:
        size *= s
    # Pipelined layer stacks always shard the layer dim on the pipeline axis.
    # Under the GPipe collective pipeline the stage body is fully-manual SPMD,
    # so within a stage the weights must be whole (no TP/fsdp) — the
    # reference's PP (x) ZeRO<=1 composition constraint. The 1F1B schedule is
    # manual over `pipeline` ONLY, leaving fsdp GSPMD-auto inside the stage
    # block, so fsdp sharding of the stacked weights is allowed there
    # (pp_fsdp=True, set when pipeline.schedule == "1f1b").
    if topo.size(AXIS_PIPE) > 1 and "layers" in axes:
        i = axes.index("layers")
        if shape[i] % topo.size(AXIS_PIPE) == 0:
            assign[i] = AXIS_PIPE
        if pp_fsdp and shard_params_fsdp:
            fsdp_n = topo.size(AXIS_FSDP)
            if fsdp_n > 1 and size > persistence_threshold:
                cands = [j for j in range(len(shape))
                         if assign[j] is None and axes[j] not in _FSDP_EXCLUDED
                         and shape[j] % fsdp_n == 0]
                if cands:
                    assign[max(cands, key=lambda j: shape[j])] = AXIS_FSDP
        return PartitionSpec(*assign)
    for i, logical in enumerate(axes):
        if logical is None:
            continue
        if logical == "layers":
            continue
        mesh_axis = TP_LOGICAL_TO_MESH.get(logical)
        if mesh_axis is None:
            continue
        if mesh_axis == AXIS_TENSOR and not use_tp:
            continue
        n = topo.size(mesh_axis)
        if n <= 1 or shape[i] % n != 0:
            continue
        # unit-granularity check (reference tp_shard.py kv-head awareness):
        # only shard if whole units land on each rank.
        units = dim_units.get(logical)
        if units is not None and units % n != 0:
            continue
        assign[i] = mesh_axis

    fsdp = topo.size(AXIS_FSDP)
    if shard_params_fsdp and fsdp > 1 and size > persistence_threshold:
        # hierarchical (MiCS/hpZ): optimizer/grad state shards over the FULL
        # world (data x fsdp) while the live-param layout keeps fsdp only, so
        # parameter gathers ride the fast intra-group axis
        entry = AXIS_FSDP
        div = fsdp
        if hierarchical and topo.size(AXIS_DATA) > 1:
            # fsdp-major order: each live fsdp shard is SUBDIVIDED along the
            # data axis, so the master->live gather is a pure data-axis
            # collective per fsdp coordinate (the hpZ fast-axis property)
            entry = (AXIS_FSDP, AXIS_DATA)
            div = fsdp * topo.size(AXIS_DATA)
        candidates = [
            i
            for i in range(len(shape))
            if assign[i] is None
            and (axes[i] not in _FSDP_EXCLUDED)
            and shape[i] % div == 0
        ]
        if candidates:
            best = max(candidates, key=lambda i: shape[i])
            assign[best] = entry
        elif hierarchical:
            # fall back to fsdp-only sharding if the world size doesn't divide
            fall = [i for i in range(len(shape))
                    if assign[i] is None and axes[i] not in _FSDP_EXCLUDED
                    and shape[i] % fsdp == 0]
            if fall:
                assign[max(fall, key=lambda i: shape[i])] = AXIS_FSDP
    return PartitionSpec(*assign)


@dataclass
class ShardingPlan:
    """Per-pytree PartitionSpec trees + their NamedShardings."""

    topo: MeshTopology
    param_specs: Any          # sharding of live params (per ZeRO stage)
    shard_specs: Any          # fully sharded layout (stage-3 style) for opt/grad state
    grad_specs: Any           # gradient layout (stage>=2: shard_specs, else param_specs)
    batch_spec: PartitionSpec = field(default=None)

    def named(self, spec_tree):
        mesh = self.topo.mesh
        return jax.tree_util.tree_map(
            lambda s: NamedSharding(mesh, s),
            spec_tree,
            is_leaf=lambda s: isinstance(s, PartitionSpec),
        )

    @property
    def param_shardings(self):
        return self.named(self.param_specs)

    @property
    def grad_shardings(self):
        return self.named(self.grad_specs)

    @property
    def shard_shardings(self):
        return self.named(self.shard_specs)

    @property
    def batch_sharding(self):
        return NamedSharding(self.topo.mesh, self.batch_spec)

    def replicated(self):
        return NamedSharding(self.topo.mesh, PartitionSpec())


def plan_sharding(
    logical_axes: Any,
    abstract_params: Any,
    topo: MeshTopology,
    zero_stage: int = 0,
    use_tp: bool = True,
    dim_units: dict | None = None,
    persistence_threshold: int = 0,
    pp_fsdp: bool = False,
    hierarchical: bool = False,
) -> ShardingPlan:
    """Build the full sharding plan for a model's parameter pytree.

    ``logical_axes``: pytree congruent to params, leaves = tuples of logical
    axis names. ``abstract_params``: params or ShapeDtypeStructs.
    """
    dim_units = dim_units or {}
    axes_leaves = jax.tree_util.tree_leaves(
        logical_axes, is_leaf=lambda x: isinstance(x, tuple)
    )
    param_leaves = jax.tree_util.tree_leaves(abstract_params)
    if len(axes_leaves) != len(param_leaves):
        raise ValueError(
            f"logical_axes tree ({len(axes_leaves)} leaves) does not match params "
            f"({len(param_leaves)} leaves)"
        )
    treedef = jax.tree_util.tree_structure(abstract_params)

    def build(shard_fsdp: bool, hier: bool = False):
        specs = [
            _spec_for_param(
                ax, tuple(p.shape), topo, shard_fsdp, use_tp, dim_units,
                persistence_threshold, pp_fsdp=pp_fsdp, hierarchical=hier,
            )
            for ax, p in zip(axes_leaves, param_leaves)
        ]
        return jax.tree_util.tree_unflatten(treedef, specs)

    shard_specs = build(shard_fsdp=True, hier=hierarchical)
    if zero_stage >= 3:
        if hierarchical:
            # hierarchical keeps LIVE params on the fast (fsdp) axis only —
            # the hpZ secondary partition (partition_parameters.py:1806).
            # Derived from shard_specs by DROPPING the data axis so live and
            # master layouts shard the SAME dim (live is a refinement).
            def _drop_data(spec):
                entries = []
                for e in spec:
                    if isinstance(e, tuple) and AXIS_DATA in e:
                        rest = tuple(a for a in e if a != AXIS_DATA)
                        entries.append(rest[0] if len(rest) == 1
                                       else (rest if rest else None))
                    elif e == AXIS_DATA:
                        entries.append(None)
                    else:
                        entries.append(e)
                return PartitionSpec(*entries)

            param_specs = jax.tree_util.tree_map(
                _drop_data, shard_specs,
                is_leaf=lambda x: isinstance(x, PartitionSpec))
        else:
            param_specs = shard_specs
    else:
        param_specs = build(shard_fsdp=False)
    grad_specs = shard_specs if zero_stage >= 2 else param_specs

    from deepspeed_tpu.comm.topology import batch_spec_entry

    seq_axis = AXIS_SEQ if topo.size(AXIS_SEQ) > 1 else None
    batch_spec = PartitionSpec(batch_spec_entry(topo.mesh), seq_axis)
    return ShardingPlan(
        topo=topo,
        param_specs=param_specs,
        shard_specs=shard_specs,
        grad_specs=grad_specs,
        batch_spec=batch_spec,
    )


def opt_state_shardings(optimizer, abstract_params, plan: ShardingPlan):
    """Optimizer-state shardings: moment buffers inherit the fully-sharded
    (stage-3 style) param layout, scalars replicate.

    This is how ZeRO-1/2 shard optimizer state while keeping live params
    replicated (reference: ``stage_1_and_2.py`` flat fp32 partitions). optax
    states embed param-congruent subtrees (e.g. ``ScaleByAdamState.mu``); each
    state leaf is matched to its param by *path suffix* + shape, so any chain
    of transforms works without optimizer-specific knowledge.
    """
    abstract = jax.tree_util.tree_map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype), abstract_params
    )
    abstract_state = jax.eval_shape(optimizer.init, abstract)

    param_index: dict = {}
    for path, leaf in jax.tree_util.tree_flatten_with_path(abstract)[0]:
        key = tuple(str(k) for k in path)
        spec = _lookup_spec(plan.shard_specs, path)
        param_index[key] = (tuple(leaf.shape), spec)

    mesh = plan.topo.mesh
    replicated = NamedSharding(mesh, PartitionSpec())

    def spec_for_state_leaf(path, leaf):
        key = tuple(str(k) for k in path)
        shape = tuple(leaf.shape)
        for start in range(len(key)):
            hit = param_index.get(key[start:])
            if hit is not None and hit[0] == shape:
                return NamedSharding(mesh, hit[1])
        return replicated

    flat, treedef = jax.tree_util.tree_flatten_with_path(abstract_state)
    shardings = [spec_for_state_leaf(path, leaf) for path, leaf in flat]
    return jax.tree_util.tree_unflatten(treedef, shardings)


def grouped_opt_state_shardings(optimizer, group_leaves: tuple, group_shardings,
                                mesh):
    """Shardings for an optimizer state over a TUPLE of param leaves (the
    offload sub-group representation): state leaves congruent to the i-th
    group leaf (matched by trailing tuple index + shape) inherit its sharding,
    scalars replicate."""
    abstract = tuple(
        jax.ShapeDtypeStruct(tuple(x.shape), x.dtype) for x in group_leaves
    )
    abstract_state = jax.eval_shape(optimizer.init, abstract)
    replicated = NamedSharding(mesh, PartitionSpec())

    def spec(path, leaf):
        last = path[-1] if path else None
        i = getattr(last, "idx", None)
        if (i is not None and i < len(group_leaves)
                and tuple(leaf.shape) == tuple(group_leaves[i].shape)):
            return group_shardings[i]
        return replicated

    flat, treedef = jax.tree_util.tree_flatten_with_path(abstract_state)
    return jax.tree_util.tree_unflatten(
        treedef, [spec(p, l) for p, l in flat]
    )


def _lookup_spec(spec_tree, path):
    node = spec_tree
    for k in path:
        if hasattr(k, "key"):
            node = node[k.key]
        elif hasattr(k, "idx"):
            node = node[k.idx]
        else:
            node = node[k.name]
    return node


def shard_params(params, plan: ShardingPlan):
    """Place (or re-place) a parameter pytree according to the plan."""
    return jax.device_put(params, plan.param_shardings)
