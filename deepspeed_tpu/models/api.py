"""Model API: the contract between models and the engine.

The reference wraps mutable ``nn.Module``s (``runtime/engine.py:235``); the
TPU-native contract is functional: a ``ModelSpec`` bundles pure
``init/forward/loss`` functions over a parameter pytree, plus *logical axis*
names per parameter dimension. The sharding planner (``parallel/partition.py``)
maps logical axes -> mesh axes per ZeRO stage / TP rules — this replaces the
reference's AutoTP module-graph parsing (``module_inject/auto_tp.py:194``):
models declare their sharding structure instead of being reverse-engineered.

Logical axis vocabulary (params):
  "layers"   stacked-layer leading dim (pipeline axis target)
  "embed"    model hidden dim
  "heads"    attention head (q) projection dim       -> TP column-parallel
  "kv_heads" kv projection dim                       -> TP column-parallel
  "ffn"      MLP intermediate dim                    -> TP column-parallel
  "vocab"    vocabulary dim                          -> TP row/column
  "experts"  MoE expert dim                          -> EP
  None       never sharded

Activations: "batch", "seq", "embed_act", "heads_act", "vocab_act".
"""

from __future__ import annotations

from dataclasses import dataclass, field
from typing import Any, Callable, Optional

import jax
import jax.numpy as jnp
import numpy as np

# Mesh-axis mapping for activation sharding constraints (GSPMD hints).
DEFAULT_ACTIVATION_RULES = {
    "batch": ("data", "fsdp", "expert"),
    "seq": "sequence",
    "embed_act": None,
    "heads_act": "tensor",
    "ffn_act": "tensor",
    "vocab_act": "tensor",
    "experts_act": "expert",
}


@dataclass
class ShardCtx:
    """Carries the mesh + activation rules into model code for
    ``with_sharding_constraint`` hints, and dispatches attention through the
    configured sequence-parallel mode. A ``None`` mesh disables constraints
    (single-device or tracing outside the engine)."""

    mesh: Any = None
    rules: dict = field(default_factory=lambda: dict(DEFAULT_ACTIVATION_RULES))
    sp_mode: str = "ulysses"  # ulysses | ring (reference: deepspeed/sequence/)
    attn_impl: str = "auto"
    pp_microbatches: int = 0  # 0 -> pipeline degree
    # activation checkpointing (reference: runtime/activation_checkpointing/):
    # engine fills these from config; model builders default to them
    remat: bool = False
    remat_policy: Any = None
    # ALST sequence tiling (reference ulysses_sp.py TiledMLP/TiledFusedLogitsLoss):
    # 0 = off; otherwise tokens per tile
    loss_tile_size: int = 0
    mlp_tile_size: int = 0
    # FPDT chunked attention w/ host-offloaded residuals (reference
    # sequence/fpdt_layer.py:545): 0 = off; otherwise chunks (>= 2) over the
    # attention-visible sequence (under Ulysses: the full gathered sequence)
    fpdt_chunks: int = 0
    fpdt_offload: bool = True
    # ZeRO stage-3 weight gather (parallel/qwz.WeightGather): installed by the
    # engine at stage 3 with fsdp > 1, dense or int8
    # (zero_optimization.quantized_weights); states each layer's gather at
    # the head of the scanned layer body, and the tied table's / head's once
    weight_gather: Any = None
    # ZeRO-Infinity param-offload hook (runtime/param_offload.py): installed
    # when zero_optimization.offload_param.device != none; streams each
    # scanned layer's host-resident weight slice into HBM + compute-casts it
    param_stream: Any = None

    def _hooks_live(self) -> bool:
        # inside a pipeline's manual region the hints are suspended (GPipe:
        # fully manual, no mesh context; 1F1B: manual over `pipeline` only,
        # where the engine suspends them the same way): the weights there are
        # left to the partitioner
        return not getattr(self, "_suspend_constraints", False)

    def layer_weights(self, lp: dict, dtype) -> dict:
        """Per-layer weight preparation, called first thing in layer bodies:
        just-in-time WOQ dequantization (inference), then the ZeRO-Infinity
        host->HBM stream-in (which also compute-casts), then the stage-3
        gather of the slice (training), when installed and constraints are
        live."""
        from deepspeed_tpu.ops.quantizer import dequantize_layer

        lp = dequantize_layer(lp, dtype)
        if self.param_stream is not None and self._hooks_live():
            lp = self.param_stream(lp, dtype)
        if self.weight_gather is not None and self._hooks_live():
            lp = self.weight_gather.layer(lp)
        return lp

    def whole_weight(self, w, *path):
        """A stage-3 leaf outside the layer scan that feeds a matmul or a
        lookup (the tied table, an untied head), named by its path in the
        params tree: gathered over fsdp where the loss function first uses
        it, so every use in the step shares one gather."""
        if self.weight_gather is None or not self._hooks_live():
            return w
        return self.weight_gather.leaf(w, *path)

    @property
    def sp_degree(self) -> int:
        if self.mesh is None:
            return 1
        return int(self.mesh.shape.get("sequence", 1))

    @property
    def pp_degree(self) -> int:
        if self.mesh is None:
            return 1
        return int(self.mesh.shape.get("pipeline", 1))

    def layer_stack(self, layer_fn, stacked_params, x, pld_theta=None,
                    pld_rng=None, ltd_keep: int = 0, ltd_rng=None):
        """Run the decoder stack: plain ``lax.scan`` normally, the collective
        microbatch pipeline when the ``pipeline`` mesh axis is active.

        With ``pld_theta`` (a traced scalar) + ``pld_rng``, layers are
        stochastically skipped per Progressive Layer Drop
        (``runtime/progressive_layer_drop.py``): depth-scaled keep
        probability, ``lax.cond`` so dropped layers skip their FLOPs, and
        stochastic-depth rescaling of the kept residual delta.

        With ``ltd_keep`` (STATIC int < seq) + ``ltd_rng``, each layer
        processes only a per-layer random subset of ``ltd_keep`` token
        positions — random layerwise token dropping (reference
        ``runtime/data_pipeline/data_routing/basic_layer.py`` +
        ``csrc/random_ltd`` gather/scatter kernels): dropped tokens BYPASS
        the layer (identity residual), kept tokens are gathered, processed
        with their ORIGINAL positions, and scattered back, so gradients flow
        through both routes. ``ltd_keep`` is static because it is a shape;
        the engine buckets the schedule and compiles once per bucket."""
        import jax.lax as lax

        if ltd_keep and pld_theta is not None:
            raise ValueError("random_ltd and progressive_layer_drop do not "
                             "compose (both rewrite the layer stack)")
        if ltd_keep:
            if self.pp_degree > 1:
                raise ValueError("random_ltd does not compose with pipeline "
                                 "parallelism")
            leaves = jax.tree_util.tree_leaves(stacked_params)
            n_layers = leaves[0].shape[0]
            s = x.shape[1]
            if not 0 < ltd_keep < s:
                raise ValueError(f"ltd_keep must be in (0, seq={s}), got "
                                 f"{ltd_keep}")
            # position-free layers (learned embeddings already in x) take
            # (sub, lp) only. Decide by signature, ONCE, outside the traced
            # body — catching TypeError around the call would also swallow
            # genuine TypeErrors raised inside the layer itself
            import inspect
            try:
                params = inspect.signature(layer_fn).parameters
                takes_positions = "positions" in params or any(
                    p.kind is inspect.Parameter.VAR_KEYWORD
                    for p in params.values())
            except (TypeError, ValueError):
                takes_positions = False  # uninspectable callable (C/builtin)

            def body(carry, inp):
                lp, i = inp
                r = jax.random.fold_in(ltd_rng, i)
                # first position always kept (reference keeps attention
                # sinks stable); remaining K-1 sampled without replacement
                perm = 1 + jax.random.permutation(r, s - 1)[: ltd_keep - 1]
                keep = jnp.sort(jnp.concatenate(
                    [jnp.zeros((1,), perm.dtype), perm]))
                sub = jnp.take(carry, keep, axis=1)
                if takes_positions:
                    pos = jnp.broadcast_to(keep[None, :],
                                           (carry.shape[0], ltd_keep))
                    sub = layer_fn(sub, lp, positions=pos)
                else:
                    sub = layer_fn(sub, lp)
                return carry.at[:, keep].set(sub.astype(carry.dtype)), None

            return lax.scan(body, x,
                            (stacked_params, jnp.arange(n_layers)))[0]

        if pld_theta is not None:
            if self.pp_degree > 1:
                raise ValueError("progressive layer drop does not compose "
                                 "with pipeline parallelism")
            leaves = jax.tree_util.tree_leaves(stacked_params)
            n_layers = leaves[0].shape[0]

            def body(carry, inp):
                lp, i = inp
                frac = (i.astype(jnp.float32) + 1.0) / n_layers
                keep_p = 1.0 - frac * (1.0 - pld_theta)
                keep = jax.random.bernoulli(
                    jax.random.fold_in(pld_rng, i), keep_p)

                def kept(c):
                    delta = layer_fn(c, lp) - c
                    return c + delta / keep_p.astype(delta.dtype)

                return lax.cond(keep, kept, lambda c: c, carry), None

            return lax.scan(body, x,
                            (stacked_params, jnp.arange(n_layers)))[0]

        if self.pp_degree <= 1:
            return lax.scan(lambda c, lp: (layer_fn(c, lp), None), x, stacked_params)[0]
        from deepspeed_tpu.parallel.pipeline import pipeline_apply

        # sharding hints inside the manual-over-pipeline region are suspended;
        # GSPMD still propagates layouts for the auto axes from the inputs
        self._suspend_constraints = True
        try:
            return pipeline_apply(layer_fn, stacked_params, x, self.mesh,
                                  num_microbatches=self.pp_microbatches)
        finally:
            self._suspend_constraints = False

    def attention(self, q, k, v, causal: bool = True, impl: str | None = None):
        """Models call attention through here; with an active ``sequence`` axis
        this routes to Ulysses all-to-all or ring/context-parallel attention."""
        impl = impl or self.attn_impl
        if self.fpdt_chunks > 1:
            from deepspeed_tpu.parallel.fpdt import fpdt_attention

            # config True = offload when the backend supports it (probe);
            # False = chunked compute only, residuals stay in HBM
            local = lambda q, k, v: fpdt_attention(  # noqa: E731
                q, k, v, self.fpdt_chunks, causal=causal,
                offload=None if self.fpdt_offload else False)
            if self.sp_degree <= 1:
                return local(q, k, v)
            # FPDT composes with Ulysses (reference FPDT runs on the
            # post-all-to-all full-sequence head-sharded layout)
            from deepspeed_tpu.parallel.ulysses import ulysses_attention

            return ulysses_attention(q, k, v, self.mesh, causal=causal,
                                     local_fn=local)
        if self.sp_degree <= 1:
            return self._local_attention(q, k, v, causal, impl)
        if self.sp_mode == "ring":
            from deepspeed_tpu.parallel.ring_attention import ring_attention

            return ring_attention(q, k, v, self.mesh, causal=causal)
        from deepspeed_tpu.parallel.ulysses import ulysses_attention

        return ulysses_attention(q, k, v, self.mesh, causal=causal, impl=impl)

    def _local_attention(self, q, k, v, causal: bool, impl: str):
        """Attention with nothing to exchange between devices: every batch
        row and every head is its own problem. The XLA path leaves the
        partitioning to GSPMD. The Pallas kernel cannot ("Mosaic kernels
        cannot be automatically partitioned. Please wrap the call in a
        shard_map"), so on a mesh of more than one device it runs manual
        over every axis that is not manual already: batch and heads split
        as their activation rules say, everything else replicated."""
        from deepspeed_tpu.ops.attention import attention, flash_blocks

        mesh = self.mesh
        auto = () if mesh is None else tuple(
            a for a in mesh.axis_names
            if a not in (getattr(self, "_manual_axes", ()) or ()))
        if (getattr(self, "_suspend_constraints", False)
                or all(mesh.shape[a] == 1 for a in auto)
                or flash_blocks(q, k, None, impl) is None):
            return attention(q, k, v, causal=causal, impl=impl)
        from deepspeed_tpu.utils.compat import shard_map_compat

        def split(dim):
            axes = self.rules.get(dim) or ()
            axes = axes if isinstance(axes, tuple) else (axes,)
            return tuple(a for a in axes if a in auto and mesh.shape[a] > 1)

        spec = jax.sharding.PartitionSpec(
            split("batch") or None, None, split("heads_act") or None, None)
        return shard_map_compat(
            lambda q, k, v: attention(q, k, v, causal=causal, impl=impl),
            mesh=mesh, in_specs=(spec, spec, spec), out_specs=spec,
            axis_names=set(auto))(q, k, v)

    def embed_lookup(self, table: jnp.ndarray, ids: jnp.ndarray,
                     *act_dims: Optional[str]) -> jnp.ndarray:
        """Token-embedding gather with multi-chip-friendly sharding.

        Replicates the (possibly vocab/fsdp-sharded) table for the lookup —
        GSPMD otherwise keeps the gather output sharded on the embed dim and
        falls into "involuntary full rematerialization" resharding it to the
        activation layout — then constrains the result to ``act_dims``.
        """
        if self.mesh is not None and not getattr(self, "_suspend_constraints", False):
            table = jax.lax.with_sharding_constraint(
                table, jax.sharding.NamedSharding(
                    self.mesh, jax.sharding.PartitionSpec()))
        x = table[ids]
        return self.constrain(x, *act_dims) if act_dims else x

    def constrain(self, x: jnp.ndarray, *logical_dims: Optional[str]) -> jnp.ndarray:
        if self.mesh is None or getattr(self, "_suspend_constraints", False):
            return x
        # inside a PARTIAL-manual shard_map (e.g. the qgZ step is manual over
        # the data axis only), constraints stay live for the auto axes but
        # must not mention the manual ones
        manual = getattr(self, "_manual_axes", ()) or ()
        spec = []
        for dim in logical_dims:
            axis = self.rules.get(dim) if dim is not None else None
            # drop axes the mesh doesn't parallelize (size 1) to keep specs clean
            if axis is None:
                spec.append(None)
                continue
            axes = axis if isinstance(axis, tuple) else (axis,)
            active = tuple(a for a in axes
                           if self.mesh.shape.get(a, 1) > 1 and a not in manual)
            spec.append(active if len(active) > 1 else (active[0] if active else None))
        pspec = jax.sharding.PartitionSpec(*spec)
        return jax.lax.with_sharding_constraint(
            x, jax.sharding.NamedSharding(self.mesh, pspec)
        )


REMASK_RULES = ("sequential", "low_confidence_static")


@dataclass(frozen=True)
class BlockGen:
    """``ModelSpec.block_gen``: ``length`` positions a block, ``steps``
    denoise passes a block (each unmasks ``length / steps`` of the positions
    still masked), ``remask`` the rule that chooses them (``"sequential"``:
    the leftmost; ``"low_confidence_static"``: those whose pick has the
    highest probability), ``mask_token_id`` what a masked position is
    embedded as. A rule whose number of passes depends on the data
    (``low_confidence_dynamic``: a threshold) is not built: the engine
    schedules a sequence's passes ahead of any readback."""

    length: int
    steps: int
    remask: str
    mask_token_id: int

    def __post_init__(self):
        if self.remask not in REMASK_RULES:
            raise NotImplementedError(
                f"block generation: remask rule {self.remask!r} is not "
                f"implemented (have {REMASK_RULES}): a threshold rule unmasks "
                "a data-dependent number of positions a pass, and the engine "
                "schedules a block's passes before it reads any of them back")
        if self.length < 1 or self.length & (self.length - 1):
            raise ValueError(f"block length {self.length} is no power of two "
                             "(the mask is pos | (length - 1))")
        if self.steps < 1 or self.length % self.steps:
            raise ValueError(f"{self.steps} denoise passes do not divide a "
                             f"block of {self.length}")

    @property
    def unmask(self) -> int:
        """Positions a denoise pass unmasks."""
        return self.length // self.steps



@dataclass(frozen=True)
class BlockSelection:
    """How a family that selects its keys by blocks counts what a query keeps
    (``ModelSpec.index_blocks``): a query at position ``p`` keeps every key
    while ``p + 1 <= dense_len``; past it the ``index_topk / block`` best
    blocks of ``block`` keys up to its own, the own one cut at ``p``. The
    blocks are scored against compressed keys, one the mean of ``kernel`` keys
    every ``stride``; key ``j`` is visible to ``p`` once ``stride * j + kernel
    - 1 <= p``."""
    dense_len: int
    block: int
    kernel: int
    stride: int

    def selects(self, p):
        """Whether the query at position(s) ``p`` selects (numpy)."""
        return p + 1 > self.dense_len

    def kept(self, p, topk: int):
        """Keys the query at position(s) ``p`` keeps (numpy)."""
        p = np.asarray(p)
        cut = np.minimum(p + 1, topk - self.block + p % self.block + 1)
        return np.where(self.selects(p), cut, p + 1)

    def compressed(self, p):
        """Compressed keys visible to the query at position(s) ``p``."""
        return np.maximum((np.asarray(p) - self.kernel + 1) // self.stride + 1,
                          0)


@dataclass
class ModelSpec:
    """Everything the engine needs to train/evaluate a model."""

    name: str
    config: Any
    # init_fn(rng) -> params pytree (fp32 master weights)
    init_fn: Callable
    # loss_fn(params, batch, rng) -> scalar loss (batch: dict of arrays)
    loss_fn: Callable
    # forward_fn(params, input_ids) -> logits
    forward_fn: Callable
    # pytree congruent to params: tuple of logical axis names per dim
    param_logical_axes: Any = None
    # unit counts per logical axis (e.g. {"kv_heads": 8}) for shard-granularity
    # checks (reference tp_shard.py kv-head-aware sharding)
    logical_dim_units: dict = field(default_factory=dict)
    # analytics for MFU / flops profiler
    num_params: int = 0
    flops_per_token: Callable[[int], float] | None = None
    # inference hooks: init_cache_fn(batch, max_len, dtype) -> cache;
    # decode_fn(params, tokens, cache, start_pos) -> (logits, cache)
    init_cache_fn: Callable | None = None
    decode_fn: Callable | None = None
    # ragged/continuous-batching hooks (reference inference/v2):
    # init_paged_cache_fn(num_blocks, block_size, dtype, codec=None,
    #   num_slots=None) -> cache in the paged contract's storage form
    #   (models/paged.py): block leaves, and for a model with a recurrent
    #   state a slot, slot leaves of num_slots rows (max_seqs + 1) under
    #   "slots" -- that is how the engine learns the model has them;
    # ragged_forward_fn(params, tokens, slots, positions, block_tables, cache)
    #   -> (logits [T, V], cache)
    init_paged_cache_fn: Callable | None = None
    ragged_forward_fn: Callable | None = None
    # ragged_forward_fn accepts prefill_tiles=(n_dec, tile_slot, tile_pos0,
    # tile_valid, tile) for the tiled-prefill fast path (SplitFuse kernel)
    supports_prefill_tiles: bool = False
    # moe_form(rows) -> "grouped" | "dense": the form ragged_forward_fn's
    # routed experts take at a step of ``rows`` tokens (models/experts.py's
    # rule on the model's geometry); None for a family with no routed experts
    moe_form: Callable[[int], str] | None = None
    # the smallest decode-row bucket of the tiled step programs (the engine's
    # ladder doubles from it to max_seqs; every bucket is a step program
    # beside each tile count, to compile cold and to load cached). A model
    # whose padding row costs its step next to nothing asks for a coarser
    # ladder than the default
    decode_bucket_min: int = 4
    # a family whose attention reads some of a sequence's cached rows only
    # (a learned selection a query) says how many a query keeps; None: every
    # family that attends over the whole context. The engine counts the
    # selected work beside the context (``sel_pairs``, ``sel_kv_tokens`` on
    # ``engine/dispatch``) and refuses what cannot carry the selection
    index_topk: int | None = None
    # sparse_decode_form(table_tokens) -> "walk" | "gather": how that family's
    # decode rows read the pool in a step program whose block table is
    # ``table_tokens`` wide, from the rule its ragged_forward_fn calls
    # (``sel_decode`` on ``engine/dispatch``); None without ``index_topk``
    sparse_decode_form: Callable[[int], str] | None = None
    # a family that selects by BLOCKS of keys past a dense length, scoring
    # them against compressed keys (``minicpm_sala``), says the rule the
    # engine counts its selected work by (``BlockSelection``; ``index_topk``
    # is then the keys a selecting query keeps at most, blocks x their size);
    # None: ``min(position + 1, index_topk)`` keys a query
    index_blocks: "BlockSelection | None" = None
    # counts only the step program knows (what a router picked): the names,
    # in order, of the per-token int32 counts ``ragged_forward_fn(...,
    # row_counts=True)`` returns as a third result, ``[len(step_counters),
    # T]``. The engine's device-resident step sums them over the step's real
    # rows, hands the sums back with the picked tokens (one readback) and
    # writes them on ``engine/dispatch`` and ``/metrics``
    # (``inference_<name>_total``); () for a family with none, whose step
    # programs have no such output
    step_counters: tuple = ()
    # a family whose layers carry a recurrent state a slot (slot leaves in its
    # paged cache, ``models/paged.py``) names the recurrence: ``"mamba2"`` (a
    # scalar decay a head, the state fed by an outer product), ``"kda"`` (a
    # decay a channel, the delta rule), ``"mamba1"`` (a decay a channel AND
    # state index, fed by an outer product: a scan, no chunk form of matmuls
    # computes it), ``"lightning"`` (a constant decay a head, on the Mamba-2
    # path), ``"shortconv"`` (no recurrence at all: a gated short
    # convolution's carried input rows, a window leaf alone, so the span says
    # neither chunk nor scan tiles). ``state_kind`` on ``engine/dispatch``
    # and the label of ``inference_slot_state_bytes_total``; None: no state
    state_kind: str | None = None
    # a family some of whose attention layers read the last ``sliding_window``
    # keys only keeps those layers' K and V in sliding leaves (``"swa"`` in
    # its paged cache, ``models/paged.py``) behind a block table and a free
    # list of their own, and says the window here: the engine returns a
    # sequence's sliding blocks as its window slides past them, hands the
    # step programs ``(full table, sliding table)``, counts the window's
    # rows on ``engine/dispatch`` (``win_kv_tokens``) and refuses what a
    # prefix of blocks cannot restore; None: every layer reads every row
    sliding_window: int | None = None
    # a family that generates by diffusion over blocks (``sdar``) says how:
    # a decoding sequence's step is then a BLOCK of ``length`` rows that see
    # each other (``models/paged.py``, *Blocks of rows*), passed through the
    # model ``steps`` times (each pass unmasks ``length / steps`` positions on
    # the device, by ``remask``'s rule) and once more to commit its K and V;
    # the logits are unshifted (row ``i`` scores the token AT ``i``). The
    # engine keeps the block a slot on the device, schedules a sequence's
    # passes without a readback, hands a block's tokens on when its last
    # denoise pass reconciles, and refuses what it cannot carry
    # (docs/SERVING.md "Generation by blocks"); None: one token a sequence
    # and step, through a causal mask
    block_gen: "BlockGen | None" = None
    # 1F1B pipeline decomposition (parallel/pipeline_1f1b.py): the tuple
    # (stage0_fn, block_fn, last_fn, split_fn, merge_fn) itself
    pipeline_parts: Any = None
    # MPMD staged runtime (runtime/pipe/): which non-"layers" param key each
    # stage program owns — maps extras key -> "first" | "last". None means
    # the model cannot be staged (e.g. tied embeddings: the shared table
    # would need a cross-stage grad reduction the transport doesn't carry).
    pipeline_extras_owner: dict | None = None
    # whether loss_fn honors batch["pld_theta"] (progressive layer drop);
    # the engine refuses to enable PLD on models that would silently ignore it
    supports_pld: bool = False
    # loss_fn accepts the static ltd_keep kwarg (random layerwise token
    # dropping inside the decoder scan; ShardCtx.layer_stack)
    supports_random_ltd: bool = False
    # param names kept dense under weight-only quantization (tables the model
    # indexes rather than matmuls, e.g. embeddings)
    woq_skip: tuple = ("embed",)


def causal_lm_loss(
    logits: jnp.ndarray,
    input_ids: jnp.ndarray,
    labels: jnp.ndarray | None = None,
    ignore_index: int = -100,
    z_loss: float = 0.0,
) -> jnp.ndarray:
    """Next-token cross entropy in fp32.

    With ``labels=None``, targets are ``input_ids`` shifted left (predict t+1
    from position t). Provided ``labels`` must already be aligned with logits.
    Positions equal to ``ignore_index`` are masked out.
    """
    if labels is None:
        logits = logits[:, :-1]
        targets = input_ids[:, 1:]
    else:
        targets = labels
    logits = logits.astype(jnp.float32)
    mask = (targets != ignore_index).astype(jnp.float32)
    safe_targets = jnp.where(targets == ignore_index, 0, targets)
    logz = jax.nn.logsumexp(logits, axis=-1)
    true_logit = jnp.take_along_axis(logits, safe_targets[..., None], axis=-1)[..., 0]
    nll = (logz - true_logit) * mask
    loss = nll.sum() / jnp.maximum(mask.sum(), 1.0)
    if z_loss > 0.0:
        loss = loss + z_loss * ((logz * mask) ** 2).sum() / jnp.maximum(mask.sum(), 1.0)
    return loss


def count_params(params) -> int:
    return int(sum(x.size for x in jax.tree_util.tree_leaves(params)))
