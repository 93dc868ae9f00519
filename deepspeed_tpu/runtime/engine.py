"""The training engine.

Role parity with the reference ``runtime/engine.py:235 DeepSpeedEngine`` —
config-driven assembly of model + optimizer + schedules + precision + ZeRO
sharding + monitoring, exposing the fwd/bwd/step protocol and the fused
``train_batch``.

TPU-native architecture (not a port):
- The hot path is ONE jitted function per engine: microbatch ``lax.scan`` over
  the gradient-accumulation dim, grad accumulation in fp32 under the ZeRO
  gradient sharding, loss-scale bookkeeping, clip, fused optimizer update and
  loss-scale skip — all inside a single XLA program. The reference's
  IPG buckets / overlapped reduce streams (``stage_1_and_2.py:1277
  average_tensor``, ``stage3.py:1488 __reduce_and_partition_ipg_grads``)
  collapse into a single reduce at the scan boundary, scheduled by XLA.
- ZeRO stages are the sharding plan (``parallel/partition.py``); no hooks, no
  trace cache: XLA's latency-hiding scheduler prefetches next-layer allgathers
  (the stage-3 coordinator's job, ``partitioned_param_coordinator.py:73``).
- ``forward``/``backward``/``step`` remain for API parity
  (``engine.py:2675/3066/3241``): ``backward`` accumulates into a persistent
  sharded gradient buffer, ``step`` applies at the GAS boundary exactly like
  ``_take_model_step:3168``.
"""

from __future__ import annotations

import os
import time
from typing import Any, Callable, Iterator

import jax
import jax.numpy as jnp
import numpy as np
import optax
from jax.sharding import NamedSharding, PartitionSpec

from deepspeed_tpu.comm import comm as dist
from deepspeed_tpu.comm.topology import MeshTopology, get_topology, topology_initialized
from deepspeed_tpu.config.config import Config, load_config
from deepspeed_tpu.models.api import ModelSpec, ShardCtx
from deepspeed_tpu.ops.attention import flash_walk_args
from deepspeed_tpu.ops.optimizers import base_lr, build_optimizer
from deepspeed_tpu.parallel.partition import (
    ShardingPlan,
    opt_state_shardings,
    plan_sharding,
)
from deepspeed_tpu.runtime import precision
from deepspeed_tpu.runtime import sentinel as sentinel_mod
from deepspeed_tpu.runtime.lr_schedules import LRScheduler, build_schedule
from deepspeed_tpu.runtime.precision import LossScaleState
from deepspeed_tpu.utils import faults as _faults
from deepspeed_tpu.utils.logging import log_dist
from deepspeed_tpu.utils.timer import ThroughputTimer
from deepspeed_tpu.utils.tracing import StepTracer, phase, span
from deepspeed_tpu.utils.compat import shard_map_compat

REMAT_POLICIES = {
    "full": None,
    "dots_saveable": "dots_saveable",
    "nothing_saveable": "nothing_saveable",
    "offload_dots": "save_dot_with_no_batch_dims_but_offload",
}


def _resolve_remat_policy(name: str):
    key = REMAT_POLICIES.get(name)
    if key is None:
        return None
    pol = getattr(jax.checkpoint_policies, key, None)
    if pol is None and name == "offload_dots":
        pol = getattr(jax.checkpoint_policies, "dots_saveable", None)
    return pol


def _global_norm(tree) -> jnp.ndarray:
    leaves = jax.tree_util.tree_leaves(tree)
    if not leaves:
        return jnp.float32(0.0)
    return jnp.sqrt(sum(jnp.sum(jnp.square(x.astype(jnp.float32))) for x in leaves))


def _tree_select(pred, new, old):
    return jax.tree_util.tree_map(lambda n, o: jnp.where(pred, n, o), new, old)


class Engine:
    """Config-driven training engine over a ModelSpec."""

    def __init__(
        self,
        model: ModelSpec | Callable[[ShardCtx], ModelSpec],
        config: Config,
        topo: MeshTopology,
        training_data: Iterator | None = None,
        seed: int | None = None,
        initial_params: Any = None,
    ):
        if (config.pipeline.stages > 1
                and not getattr(self, "_supports_staged_pipeline", False)):
            raise ValueError(
                "pipeline.stages > 1 selects the staged MPMD runtime; "
                "construct it through initialize() (which routes to "
                "runtime.pipe.engine.PipeEngine) instead of Engine directly")
        self.config = config
        self.topo = topo
        sp_cfg = config.sequence_parallel
        self.shard_ctx = ShardCtx(
            mesh=topo.mesh,
            sp_mode=sp_cfg.mode,
            pp_microbatches=config.pipeline.num_microbatches,
            remat=config.activation_checkpointing.enabled,
            remat_policy=_resolve_remat_policy(config.activation_checkpointing.policy),
            loss_tile_size=sp_cfg.tile_size if sp_cfg.tiled_logits else 0,
            mlp_tile_size=sp_cfg.tile_size if sp_cfg.tiled_mlp else 0,
            fpdt_chunks=sp_cfg.fpdt_chunks,
            fpdt_offload=sp_cfg.fpdt_offload,
        )
        self.model_spec = model(self.shard_ctx) if callable(model) else model
        self.training_dataloader = training_data

        # AutoSP (reference sequence/auto_sp.py): models NOT written against
        # ShardCtx get sequence parallelism by patching the standard
        # attention entry point during tracing (parallel/auto_sp.py)
        if sp_cfg.auto and topo.size("sequence") > 1:
            import dataclasses as _dc

            from deepspeed_tpu.parallel.auto_sp import wrap_loss_fn

            # a COPY of the spec: mutating the caller's object would
            # double-wrap on re-initialize (elastic restart / A-B runs) and
            # leak the patch into unrelated engines sharing the spec
            self.model_spec = _dc.replace(
                self.model_spec,
                loss_fn=wrap_loss_fn(self.model_spec.loss_fn, topo.mesh,
                                     sp_cfg.mode),
                forward_fn=wrap_loss_fn(self.model_spec.forward_fn, topo.mesh,
                                        sp_cfg.mode))
            log_dist("auto_sp: jax.nn.dot_product_attention routed through "
                     f"{sp_cfg.mode} sequence parallelism", ranks=[0])

        zero = config.zero_optimization
        self.zero_stage = zero.stage
        self._zero3_gathered: list = []
        abstract_params = jax.eval_shape(self.model_spec.init_fn,
                                         jax.random.PRNGKey(0))
        self.plan: ShardingPlan = plan_sharding(
            self.model_spec.param_logical_axes,
            abstract_params,
            topo,
            zero_stage=zero.stage,
            use_tp=topo.size("tensor") > 1,
            dim_units=self.model_spec.logical_dim_units,
            persistence_threshold=zero.persistence_threshold,
            pp_fsdp=config.pipeline.schedule == "1f1b",
            hierarchical=zero.hierarchical_partitioning,
        )

        # ZeRO stage 3 over fsdp > 1 STATES its weight gathers
        # (parallel/qwz.WeightGather; reference partition_parameters.py:1446
        # all_gather_coalesced): at the head of every scanned layer body, and
        # once a step for the table / head outside the scan, each leaf the
        # plan shards over fsdp is constrained to its spec with fsdp dropped.
        # Left unsaid, the partitioner turns gather-then-matmul into rings of
        # K/fsdp-wide partial matmuls (module docstring of
        # parallel/partition.py). zero_optimization.quantized_weights only
        # picks what rides the wire (qwZ: int8 + scales). Installed on the
        # shard_ctx AFTER model build — the model closures hold the (mutable)
        # ctx, so the hook reaches every layer body. Stages 0-2 and fsdp = 1
        # have no sharded live weight and install nothing.
        if zero.quantized_weights and topo.size("pipeline") > 1:
            raise ValueError(
                "quantized_weights does not compose with pipeline "
                "parallelism (the stage body runs manual-SPMD where the "
                "qwZ gather constraint has no meaning); drop one")
        if zero.stage >= 3 and topo.size("fsdp") > 1:
            from deepspeed_tpu.parallel.qwz import WeightGather

            specs = self.plan.param_specs
            stacked = isinstance(specs, dict) and "layers" in specs
            if zero.quantized_weights and not stacked:
                raise ValueError(
                    "quantized_weights requires a model with a stacked "
                    "'layers' param subtree (the scanned stage-3 path)")
            if isinstance(specs, dict):
                self.shard_ctx.weight_gather = WeightGather(
                    topo.mesh, specs,
                    codec="int8" if zero.quantized_weights else None,
                    block=zero.qwz_block)
                # (path, elements, codec, scanned) of the gathered leaves: the
                # comms plan books them as they travel (_fsdp_plan)
                self._zero3_gathered = self.shard_ctx.weight_gather.gathered(
                    abstract_params)
                log_dist(
                    "stage-3 weight all-gather, stated at the head of each "
                    "layer: "
                    + (f"int8 blockwise (qwZ, block={zero.qwz_block})"
                       if zero.quantized_weights
                       else str(jnp.dtype(config.compute_dtype)))
                    + f" over fsdp={topo.size('fsdp')}", ranks=[0])
        elif zero.quantized_weights:
            log_dist(
                "quantized_weights: fsdp axis is 1 — stage-3 has no "
                "weight gather to quantize; running dense", ranks=[0])

        # ZeRO-Infinity parameter offload (reference
        # runtime/zero/parameter_offload.py:117 DeepSpeedZeRoOffload +
        # swap_tensor/partitioned_param_swapper.py:37): master params live in
        # host DRAM (pinned_host memory kind) and stream through HBM per
        # scanned layer — see runtime/param_offload.py for the mechanism.
        from deepspeed_tpu.runtime import offload as offload_mod

        self._param_offload: str = zero.offload_param.device
        self._param_storage = None        # host-kind storage shardings
        self._param_offload_mask = None   # which leaves offload
        if self._param_offload != "none":
            from deepspeed_tpu.config.config import ConfigError
            from deepspeed_tpu.runtime import param_offload as po_mod

            if self._param_offload == "nvme":
                raise ConfigError(
                    "zero_optimization.offload_param.device='nvme' is not "
                    "implemented: the layer stack streams from host DRAM "
                    "(pinned_host memory kind), and a per-layer NVMe fetch "
                    "inside the compiled step would need an io_callback "
                    "path this engine does not have. Use device='cpu' — "
                    "it covers models whose fp32 state exceeds HBM; NVMe "
                    "holds optimizer state (offload_optimizer)")
            if self.zero_stage != 3:
                raise ConfigError(
                    "offload_param streams the stage-3 scanned layer stack; "
                    f"it requires zero_optimization.stage=3 (got {self.zero_stage})")
            if topo.size("pipeline") > 1:
                raise ConfigError(
                    "offload_param does not compose with pipeline parallelism "
                    "(the pipeline owns the layer-stack slicing the host "
                    "stream rides on)")
            if zero.quantized_gradients:
                raise ConfigError(
                    "offload_param does not compose with quantized_gradients "
                    "(device_put to named shardings is unavailable inside the "
                    "qgZ manual region)")
            if not config.activation_checkpointing.enabled:
                raise ConfigError(
                    "offload_param requires activation_checkpointing: without "
                    "rematerialization every streamed layer's weights are "
                    "saved for backward and the full model re-materializes "
                    "in HBM, silently defeating the offload")
            if zero.offload_optimizer.device not in ("cpu", "nvme"):
                raise ConfigError(
                    "offload_param requires offload_optimizer.device cpu|nvme "
                    "(optimizer state is ~2x the params that no longer fit "
                    "in HBM, and the windowed update walk is what streams "
                    "the master params through the optimizer)")
            host_ok = offload_mod.supports_memory_kinds(topo.mesh)
            abstract = jax.eval_shape(self.model_spec.init_fn,
                                      jax.random.PRNGKey(0))
            self._param_storage, self._param_offload_mask = (
                po_mod.storage_shardings(
                    self.plan.param_shardings, abstract,
                    zero.persistence_threshold, host_ok))
            specs = self.plan.param_specs
            if isinstance(specs, dict) and "layers" in specs:
                self.shard_ctx.param_stream = po_mod.build_layer_stream_hook(
                    topo.mesh, specs["layers"],
                    self._param_offload_mask["layers"])
            else:
                log_dist(
                    "offload_param: model has no stacked 'layers' subtree — "
                    "whole-leaf streaming only (no per-layer window)",
                    ranks=[0])
            n_off = sum(jax.tree_util.tree_leaves(self._param_offload_mask))
            log_dist(
                f"offload_param: {n_off} param leaves host-resident, streamed "
                "per scanned layer"
                + ("" if host_ok else
                   " (no host tier on this backend; streaming path only)"),
                ranks=[0])

        # ---- params (fp32 master), placed per plan (reference zero.Init analog)
        param_placement = (self._param_storage if self._param_storage is not None
                           else self.plan.param_shardings)
        seed = seed if seed is not None else config.seed
        init_rng = jax.random.PRNGKey(seed)
        if initial_params is not None:
            # pre-loaded weights (e.g. models.hf_ingest): enforce the fp32
            # master-weight invariant the init_fn path guarantees, then place
            # under the plan
            initial_params = jax.tree_util.tree_map(
                lambda x: x.astype(np.float32)
                if jnp.issubdtype(x.dtype, jnp.floating) else x,
                initial_params,
            )
            self.params = jax.device_put(initial_params, param_placement)
        else:
            self.params = jax.jit(
                self.model_spec.init_fn, out_shardings=param_placement
            )(init_rng)

        # ---- optimizer (lr=1.0; schedule applied inside the step for exact
        # logged-lr == applied-lr, including skipped-step semantics)
        self._base_lr = base_lr(config.optimizer)
        self.lr_schedule = build_schedule(config.scheduler, self._base_lr)
        self.optimizer = build_optimizer(config.optimizer, learning_rate=1.0)
        self._opt_shardings = opt_state_shardings(self.optimizer, self.params, self.plan)

        # Overlap-first DP backward (parallel/grad_overlap.py, ROADMAP item 2):
        # bucketed async ppermute-ring grad reduce-scatter inside a shard_map
        # manual region + optional cross-replica sharded optimizer update
        # (ZeRO-1 without the fsdp axis). `exact: true` is the kill switch —
        # config surface stays but the step routes through the fused baseline
        # program, bit-identical by construction.
        go_cfg = zero.grad_overlap
        self._overlap_enabled = bool(go_cfg.enabled)
        self._grad_overlap = self._overlap_enabled and not go_cfg.exact
        self._overlap_sharded = False
        self._overlap_plan = None
        self._overlap_opt_specs = None
        if self._grad_overlap:
            from deepspeed_tpu.parallel import grad_overlap as go_mod

            dp = topo.size("data")
            others = [a for a in ("fsdp", "tensor", "sequence", "pipeline",
                                  "expert") if topo.size(a) > 1]
            if dp <= 1 or others:
                raise ValueError(
                    "zero_optimization.grad_overlap reduces over a pure "
                    f"data-parallel mesh (data>1, all other axes 1); got "
                    f"data={dp}"
                    + (f", unsupported axes {others}" if others else ""))
            if zero.stage not in (0, 1):
                raise ValueError(
                    "grad_overlap replaces the GSPMD gradient sync on the "
                    "pure-DP path; ZeRO stages 2/3 shard grads/params over "
                    f"the fsdp axis instead (got stage {zero.stage})")
            if zero.offload_optimizer.device != "none":
                raise ValueError(
                    "grad_overlap does not compose with offloaded optimizer "
                    "state (the sharded update owns the optimizer tail)")
            if zero.zenflow.enabled:
                raise ValueError(
                    "grad_overlap and zenflow are mutually exclusive "
                    "(both restructure the optimizer tail)")
            if zero.hierarchical_partitioning:
                raise ValueError(
                    "grad_overlap does not compose with "
                    "hierarchical_partitioning (hpZ masters shard over the "
                    "data axis the overlap rings run manual over)")
            self._overlap_sharded = bool(go_cfg.sharded_update)
            if self._overlap_sharded:
                ot = config.optimizer.type.lower()
                allowed = {"adam", "adamw", "sgd", "lion", "adagrad"}
                if ot not in allowed:
                    raise ValueError(
                        f"grad_overlap.sharded_update requires an elementwise "
                        f"optimizer ({', '.join(sorted(allowed))}); "
                        f"{ot!r} mixes information across the param tree "
                        "(set sharded_update: false to keep the bucketed "
                        "rings with a replicated update)")
            codec = (f"int{int(zero.quantized_gradients_bits)}"
                     if zero.quantized_gradients else "fp32")
            self._overlap_plan = go_mod.plan_buckets(
                self.params, dp, go_cfg.bucket_bytes, codec=codec)
            log_dist("grad_overlap: " + self._overlap_plan.describe()
                     + (", sharded update (1/%d state touch)" % dp
                        if self._overlap_sharded else ", replicated update"),
                     ranks=[0])
        elif self._overlap_enabled:
            log_dist("grad_overlap: exact=true — routing through the fused "
                     "baseline step program (kill switch)", ranks=[0])

        # ZeRO-Offload / ZeRO-Infinity tiers (reference: zero cpu-offload +
        # cpu_adam + runtime/swap_tensor). Offloaded optimizer state is
        # WINDOWED into sub-groups (reference stage3.py:2360 _prepare_sub_group)
        # so only ~one group is HBM-resident during the update:
        #   cpu : per-group states pinned in host DRAM, streamed through HBM
        #         group-by-group inside the jitted step
        #   nvme: per-group states on disk via the AIO engine, prefetch of
        #         group k+1 overlapping the update of group k
        from deepspeed_tpu.runtime import offload as offload_mod

        self._offload_mode: str | None = None
        self._opt_host_ok = False
        self._groups: list[list[int]] | None = None
        self._swapper = None
        param_leaves, self._param_treedef = jax.tree_util.tree_flatten(self.params)
        # leaf-level live/storage shardings: the group walks stream offloaded
        # master params through HBM with these targets
        self._param_dev_leaf_sh = jax.tree_util.tree_leaves(
            self.plan.param_shardings)
        self._param_store_leaf_sh = jax.tree_util.tree_leaves(param_placement)
        dev = zero.offload_optimizer.device
        if dev in ("cpu", "nvme"):
            self._offload_mode = dev
            self._groups = offload_mod.partition_groups(
                [int(x.size) for x in param_leaves], zero.sub_group_size
            )
        if self._offload_mode == "cpu":
            from deepspeed_tpu.parallel.partition import grouped_opt_state_shardings

            host_ok = offload_mod.supports_memory_kinds(topo.mesh)
            self._opt_host_ok = host_ok
            # SuperOffload mixed residency (reference superoffload_stage3.py
            # subgroup_to_device): the first hbm_resident_fraction of groups
            # skip the host tier entirely — no stream round trip for the
            # hottest share of the state
            n_hbm = 0
            if zero.offload_optimizer.super_offload:
                n_hbm = int(round(
                    zero.offload_optimizer.hbm_resident_fraction
                    * len(self._groups)))
            shard_leaves = jax.tree_util.tree_leaves(self.plan.param_shardings)
            self._group_shardings = []  # (device_kind, storage_kind) per group
            self.opt_state = []
            for g, idx in enumerate(self._groups):
                g_leaves = tuple(param_leaves[i] for i in idx)
                g_shards = [shard_leaves[i] for i in idx]
                dev_sh = grouped_opt_state_shardings(
                    self.optimizer, g_leaves, g_shards, topo.mesh)
                store_sh = (dev_sh if (g < n_hbm or not host_ok)
                            else offload_mod.offload_shardings(dev_sh))
                self._group_shardings.append((dev_sh, store_sh))
                self.opt_state.append(
                    jax.jit(self.optimizer.init, out_shardings=store_sh)(g_leaves)
                )
            log_dist(
                f"optimizer state in {len(self._groups)} sub-groups "
                + (f"({n_hbm} HBM-resident, superoffload) " if n_hbm else "")
                + ("pinned in host DRAM" if host_ok else
                   "(no host tier on this backend; windowing only)"),
                ranks=[0],
            )
        elif self._offload_mode == "nvme":
            from deepspeed_tpu.runtime.nvme_swap import AsyncTensorSwapper

            self._swapper = AsyncTensorSwapper(zero.offload_optimizer.nvme_path)
            self._nvme_templates = []
            for g, idx in enumerate(self._groups):
                g_abs = tuple(
                    jax.ShapeDtypeStruct(tuple(param_leaves[i].shape), jnp.float32)
                    for i in idx
                )
                abstract = jax.eval_shape(self.optimizer.init, g_abs)
                zeros = jax.tree_util.tree_map(
                    lambda l: np.zeros(l.shape, l.dtype), abstract)
                self._nvme_templates.append(abstract)
                # windowed init: one group's zeros in host RAM at a time
                self._swapper.wait_keys(
                    self._swapper.swap_out_tree(f"opt_g{g}", zeros))
            self._swapper.commit()
            self.opt_state = None  # never resident: lives on NVMe between steps
            log_dist(
                f"optimizer state on NVMe ({zero.offload_optimizer.nvme_path}) "
                f"in {len(self._groups)} sub-groups", ranks=[0],
            )
        elif self._grad_overlap and self._overlap_sharded:
            # ZeRO-1 flat layout: state over packed [dp, shard] bucket rows,
            # row-sharded over the data axis — each rank holds exactly the
            # 1/dp of the moments its grad shard updates. The bucket plan is
            # deterministic (path-keyed), so this layout is stable across
            # restarts and checkpoint round-trips.
            (self.opt_state, self._overlap_opt_specs,
             self._opt_shardings) = self._init_overlap_opt_state()
        else:
            self.opt_state = jax.jit(
                self.optimizer.init, out_shardings=self._opt_shardings
            )(self.params)

        self.scale_state: LossScaleState = precision.init_loss_scale(config.fp16)
        self.lr_scheduler = LRScheduler(self.lr_schedule)

        # ---- counters (reference engine attributes)
        self.global_steps = 0
        self.global_samples = 0
        self.micro_steps = 0
        self._skip_base = 0              # skips restored from checkpoint
        self._skip_dev = jnp.int32(0)    # async device-side skip accumulator
        self._last_metrics: dict = {}
        # two independent rng streams: the train stream is a frozen base key
        # (per-step keys derived by fold_in, never mutated) so interleaving
        # eval/backward calls — which consume _next_rng() — cannot perturb the
        # training trajectory or break resume-reproducibility
        self._train_rng = jax.random.PRNGKey(seed + 1)
        self._rng = jax.random.PRNGKey(seed + 2)
        # bound the async dispatch pipeline: block on the step that ran
        # _max_inflight steps ago so the host can't run unboundedly ahead on
        # backends without bounded dispatch queues (errors surface within a
        # bounded window; throughput still overlaps across the window)
        self._max_inflight = 8
        self._inflight: list = []

        # ---- grad accumulation buffer for the fwd/bwd parity path
        self._acc_grads = None
        self._acc_count = 0

        self.tput_timer = ThroughputTimer(
            batch_size=config.train_batch_size or 1,
            steps_per_output=config.steps_per_print,
        )
        self._flops_source = "analytic"
        self._model_profile = None  # cached get_model_profile result
        if self.model_spec.flops_per_token and config.sequence_length:
            self.tput_timer.flops_per_sample = (
                self.model_spec.flops_per_token(config.sequence_length)
                * config.sequence_length
            )
        elif config.sequence_length:
            # the model exposes no flops_per_token: fall back to the flops
            # profiler's analytic per-layer count so tflops() reports a real
            # number instead of 0.0 (fwd x3 ~ fwd+bwd training flops).
            # get_model_profile memoizes, so this is computed once per
            # (model, shape) rather than per tflops() scrape.
            try:
                from deepspeed_tpu.profiling.flops_profiler import get_model_profile

                self._model_profile = get_model_profile(
                    self.model_spec, batch=1, seq=config.sequence_length,
                    with_compiled=False)
                if self._model_profile.flops_fwd:
                    self.tput_timer.flops_per_sample = (
                        3.0 * self._model_profile.flops_fwd)
            except Exception as e:
                log_dist(f"analytic flops estimate unavailable: {e}", ranks=[0])

        from deepspeed_tpu.monitor.monitor import MonitorMaster

        self.monitor = MonitorMaster(config.monitor)

        # structured telemetry bus (deepspeed_tpu/telemetry/): step spans, HBM
        # watermarks, comm counters, checkpoint durations — one registry that
        # the JSONL/Prometheus exporters and the monitor bridge all read
        from deepspeed_tpu import telemetry as _telemetry

        self.telemetry = _telemetry.get_telemetry()
        if config.telemetry.enabled:
            self.telemetry.configure(config.telemetry, monitor=self.monitor)
        if self.tput_timer.flops_per_sample:
            if self.telemetry.enabled:
                self.telemetry.gauge(
                    "train_flops_per_sample",
                    "analytic FLOPs per training sample").set(
                        self.tput_timer.flops_per_sample)
            if self.monitor.enabled:
                self.monitor.write_events([(
                    "Train/flops_per_sample",
                    float(self.tput_timer.flops_per_sample), 0)])
        self._prev_step_wall = 0.0  # host wall clock of the previous _after_step
        self._step_miss0 = None  # compile-miss count at the current step's start

        # training step anatomy (telemetry/stepscope.py): per-phase spans +
        # MFU attribution + overlap/goodput gauges. Off by default; enabling
        # settles each step (microscope mode, docs/OBSERVABILITY.md).
        ss_opts = dict(config.telemetry.stepscope or {})
        ss_enabled = bool(ss_opts.get("enabled"))
        if (ss_enabled and ss_opts.get("use_cost_analysis", True)
                and config.sequence_length):
            # refine the analytic estimate with XLA's cost model for the
            # compiled forward — exact for the lowered program
            try:
                from deepspeed_tpu.profiling.flops_profiler import get_model_profile

                self._model_profile = get_model_profile(
                    self.model_spec, batch=1, seq=config.sequence_length,
                    with_compiled=True)
                cflops = float((self._model_profile.compiled or {}).get(
                    "flops", 0.0) or 0.0)
                if cflops > 0.0:
                    self.tput_timer.flops_per_sample = 3.0 * cflops
                    self._flops_source = "cost_analysis"
            except Exception as e:
                log_dist(f"cost-analysis flops unavailable ({e}); "
                         "keeping analytic estimate", ranks=[0])
        if self.telemetry.enabled:
            self.telemetry.gauge(
                "train_flops_source",
                "1 for the flops estimate feeding train_tflops/MFU "
                "(analytic|cost_analysis)").set(1.0, source=self._flops_source)
        from deepspeed_tpu.telemetry.stepscope import StepScope

        self.stepscope = StepScope(
            self.telemetry,
            enabled=ss_enabled,
            batch_size=config.train_batch_size or 1,
            fwd_flops_per_step=(self.tput_timer.flops_per_sample / 3.0)
            * (config.train_batch_size or 1),
            param_count=int(self.model_spec.num_params or 0),
            collective_bytes_per_step=self._grad_wire_bytes(),
            peak_tflops=ss_opts.get("peak_tflops"),
            interconnect_gbps=float(ss_opts.get("interconnect_gbps", 100.0)),
            straggler_warn_ratio=float(
                config.comms_logger.straggler_warn_ratio),
            flops_source=self._flops_source,
        )

        # device-timeline profiler (telemetry/devprof.py): bounded capture
        # windows every profile_interval_steps steps, parsed into measured
        # overlap / wire-time / idle metrics and merged into the trace ring.
        # Requires stepscope (microscope mode settles the step so the window
        # closes cleanly); off by default — the hot path only ever checks
        # `self._devprof is not None`.
        self._devprof = None
        self._devprof_interval = 0
        self._devprof_last = None
        dp_interval = int(ss_opts.get("profile_interval_steps", 0) or 0)
        if self.stepscope.enabled and dp_interval > 0:
            from deepspeed_tpu.telemetry.devprof import DeviceProfiler

            self._devprof_interval = dp_interval
            self._devprof = DeviceProfiler(
                self.telemetry,
                out_dir=str(ss_opts.get("profile_dir")
                            or os.path.join("runs", "devprof")),
                keep=int(ss_opts.get("profile_keep", 4)),
            )

        if (config.progressive_layer_drop.enabled
                and not self.model_spec.supports_pld):
            raise ValueError(
                f"model {self.model_spec.name!r} does not honor "
                "progressive_layer_drop (its loss_fn ignores pld_theta); "
                "enabling it would silently train without PLD")
        if (config.pipeline.schedule == "1f1b" and topo.size("pipeline") > 1
                and (config.progressive_layer_drop.enabled
                     or config.compression_training)):
            raise ValueError(
                "pipeline.schedule='1f1b' bypasses the GAS grad path that "
                "applies progressive_layer_drop / compression_training; "
                "these combinations would silently no-op")

        # compression-aware training (reference deepspeed/compression/):
        # scheduled QAT + pruning applied to the compute-cast params
        self._compression = None
        if config.compression_training:
            from deepspeed_tpu.compression import CompressionScheduler

            heads = (self.model_spec.logical_dim_units or {}).get("heads", 0)
            self._compression = CompressionScheduler(
                config.compression_training, num_heads=int(heads))
            log_dist(
                "compression_training: "
                f"{self._compression.config.enabled_methods()}", ranks=[0])

        # random layerwise token dropping (reference data_routing/
        # basic_layer.py): per-layer token subsets inside the decoder scan;
        # the kept count is a SHAPE, so the schedule is bucketed and the
        # step compiles once per bucket value (self._train_batch_jit is a
        # per-bucket dict)
        ltd_cfg = config.data_efficiency.random_ltd
        self._ltd = ltd_cfg if ltd_cfg.enabled else None
        self._ltd_active = 0
        self._ltd_jits: dict = {}
        if self._ltd is not None:
            if not self.model_spec.supports_random_ltd:
                raise ValueError(
                    f"model {self.model_spec.name!r} does not support "
                    "random_ltd (its loss_fn has no ltd_keep route); "
                    "enabling it would silently train dense")
            conflicts = {
                "progressive_layer_drop": config.progressive_layer_drop.enabled,
                "pipeline parallelism": topo.size("pipeline") > 1,
                "quantized_gradients": bool(zero.quantized_gradients),
                "offloaded optimizer state":
                    zero.offload_optimizer.device != "none",
                "zenflow": zero.zenflow.enabled,
                "grad_overlap": self._grad_overlap,
            }
            bad = [k for k, v in conflicts.items() if v]
            if bad:
                raise ValueError(
                    f"random_ltd does not compose with {', '.join(bad)} "
                    "(each owns the step program this build specializes "
                    "per kept-token bucket)")
            log_dist(
                f"random_ltd: keep ratio {ltd_cfg.start_keep_ratio:.0%} -> "
                f"100% over {ltd_cfg.total_steps} steps, bucket "
                f"{ltd_cfg.bucket} tokens", ranks=[0])

        # jax.profiler capture window + debug-nans trap (reference nvtx
        # instrumentation / sanity-check config, SURVEY §5.1-5.2)
        self.step_tracer = StepTracer(
            config.tracing,
            sync_fn=lambda: jax.block_until_ready(self._last_metrics))
        if config.debug.nans:
            jax.config.update("jax_debug_nans", True)
            log_dist("debug.nans: trapping the first NaN-producing op", ranks=[0])

        # ZeRO++-style quantized gradient reduction (qgZ): grads stay rank-
        # local through the GAS scan inside a shard_map over the data axis and
        # reduce ONCE at the boundary through int8 all-to-all/all-gather with
        # error feedback (comm/quantized_collectives.py)
        self._qgrad = bool(zero.quantized_gradients)
        self._qgrad_bits = int(zero.quantized_gradients_bits)
        self._qgrad_error = None
        # 1-bit-family optimizers compress AFTER their variance warmup
        # (reference onebit/adam.py freeze_step two-phase protocol): the
        # engine runs the dense-wire program until freeze_step, then the
        # compressed program
        self._qgrad_warmup_steps = 0
        self._warm_batch_jit = None
        from deepspeed_tpu.ops.optimizers import is_onebit_family

        if self._qgrad and is_onebit_family(config.optimizer.type):
            op = dict(config.optimizer.params)
            self._qgrad_warmup_steps = int(
                op.get("freeze_step", op.get("warmup_steps",
                                             op.get("var_freeze_step", 100))))
        if self._qgrad:
            others = [a for a in ("tensor", "sequence", "pipeline", "expert")
                      if topo.size(a) > 1]
            if topo.size("data") <= 1 or others:
                raise ValueError(
                    "zero_optimization.quantized_gradients reduces over the "
                    f"data axis (data>1 required; composes with fsdp); got "
                    f"data={topo.size('data')}"
                    + (f", unsupported axes {others}" if others else "")
                )
            if zero.hierarchical_partitioning:
                raise ValueError(
                    "quantized_gradients does not compose with "
                    "hierarchical_partitioning (hpZ masters shard over the "
                    "data axis the quantized reducer runs manual over)")
            if self._offload_mode == "nvme":
                raise ValueError(
                    "quantized_gradients is not supported with NVMe-offloaded "
                    "optimizer state")
            n = topo.size("data")
            if self._grad_overlap:
                # overlap path: one residual per BUCKET (the quantized
                # reduction runs on the packed flat bucket, not per leaf),
                # one row per data rank
                err_sh = NamedSharding(topo.mesh, PartitionSpec("data"))
                self._qgrad_error = tuple(
                    jax.jit(
                        lambda padded=b.padded: jnp.zeros((n, padded),
                                                          jnp.float32),
                        out_shardings=err_sh,
                    )()
                    for b in self._overlap_plan.buckets)
            else:
                # residuals: one per data rank, each carrying the grad's fsdp
                # sharding on the param dims (no replicated full-size buffers)
                err_shardings = jax.tree_util.tree_map(
                    lambda spec: NamedSharding(
                        topo.mesh, PartitionSpec("data", *spec)),
                    self.plan.grad_specs,
                    is_leaf=lambda x: isinstance(x, PartitionSpec))
                self._qgrad_error = jax.jit(
                    lambda: jax.tree_util.tree_map(
                        lambda p: jnp.zeros((n,) + tuple(p.shape), jnp.float32),
                        self.params,
                    ),
                    out_shardings=err_shardings,
                )()
            log_dist(f"gradient reduction: {self._qgrad_bits}-bit quantized "
                     f"wire over the data axis (n={n}) with error feedback"
                     + (f", fsdp={topo.size('fsdp')} auto"
                        if topo.size("fsdp") > 1 else "")
                     + (f", dense until step {self._qgrad_warmup_steps}"
                        if self._qgrad_warmup_steps else ""), ranks=[0])

        # ZenFlow split update over the offloaded tier (runtime/zenflow.py;
        # reference runtime/zenflow/zenflow_stage_1_and_2.py:47)
        zf_cfg = zero.zenflow
        self._zenflow = bool(zf_cfg.enabled)
        if self._zenflow:
            from deepspeed_tpu.runtime import zenflow as zenflow_mod

            if self._offload_mode != "cpu":
                raise ValueError(
                    "zenflow requires zero_optimization.offload_optimizer."
                    "device='cpu' (reference _configure_zenflow: 'Zenflow "
                    "must be used with cpu offload')")
            if self.zero_stage not in (1, 2):
                raise ValueError(
                    "zenflow supports ZeRO stages 1/2 (reference "
                    "ZenFlowZeroOptimizer extends the stage-1/2 optimizer)")
            if self._qgrad:
                raise ValueError(
                    "zenflow and quantized_gradients are mutually exclusive")
            ot = config.optimizer.type.lower()
            if ot not in ("adam", "adamw"):
                raise ValueError(
                    f"zenflow requires an Adam-family optimizer, got {ot!r} "
                    "(reference uses ZenFlowSelectiveAdamW for the hot set)")
            op = dict(config.optimizer.params)
            betas = op.get("betas", (0.9, 0.999))
            self._zf = zenflow_mod
            self._zf_hyper = dict(
                block=zf_cfg.block, b1=float(betas[0]), b2=float(betas[1]),
                eps=float(op.get("eps", 1e-8)),
                weight_decay=float(op.get("weight_decay", 0.0)),
            )
            self._zf_hot = zenflow_mod.init_hot_state(
                param_leaves, zf_cfg.topk_ratio, zf_cfg.block)
            self._zf_acc = None          # cold-gradient accumulator (lazy)
            self._zf_n_acc = 0           # steps since the last cold update
            self._zf_n_dev = jnp.int32(0)  # finite (accumulated) steps, on device
            self._zf_selected = False    # becomes True at the first selection
            self._zf_hot_jit = None
            self._zf_cold_jit = None
            self._zf_select_jit = None
            log_dist(
                f"zenflow: hot top-{zf_cfg.topk_ratio:.0%} blocks on device "
                f"every step, cold update every {zf_cfg.update_interval} "
                f"steps, re-select every {zf_cfg.select_interval}", ranks=[0])

        if (self._offload_mode == "nvme"
                and config.pipeline.schedule == "1f1b"
                and topo.size("pipeline") > 1):
            raise ValueError(
                "pipeline.schedule='1f1b' is not supported with NVMe-offloaded "
                "optimizer state (the NVMe step path uses the GPipe grads "
                "program); use offload_optimizer.device=cpu or schedule=gpipe"
            )

        # self-healing training (runtime/sentinel.py, docs/FAULT_TOLERANCE.md
        # "Training: self-healing"): the device-side anomaly verdict is fused
        # into the step program, the host-side ladder quarantines / rolls
        # back / halts on the settled verdict, and a heartbeat beacon gives
        # the elastic agent wedge visibility. Off by default: the disabled
        # engine traces the exact pre-sentinel step program.
        sent_cfg = config.sentinel
        self._sentinel: sentinel_mod.SentinelPolicy | None = None
        self._sent_state = None
        self._heartbeat = None
        self._lr_scale = 1.0  # sentinel LR backoff; read at trace time
        self._watchdog_timeout = 0.0
        self._last_batch_fps: list[str] = []
        self._last_save_dir: str | None = None
        self.train_rollbacks = 0
        self._fault_injector = _faults.get_fault_injector()
        if sent_cfg.enabled:
            conflicts = {
                "quantized_gradients": self._qgrad,
                "zenflow": self._zenflow,
                "offloaded optimizer state": self._offload_mode is not None,
                "pipeline 1f1b": (config.pipeline.schedule == "1f1b"
                                  and topo.size("pipeline") > 1),
            }
            bad = [k for k, v in conflicts.items() if v]
            if bad:
                raise ValueError(
                    f"sentinel does not compose with {', '.join(bad)} "
                    "(the anomaly verdict is fused into the plain GAS step "
                    "program those paths replace)")
            self._sentinel = sentinel_mod.SentinelPolicy(sent_cfg)
            self._sent_state = sentinel_mod.init_state(sent_cfg)
            self._watchdog_timeout = float(sent_cfg.dispatch_timeout_s)
            # The persistent XLA compilation cache is OFF for sentinel runs:
            # the sentinel step program deserialized from the cache into a
            # process that load_checkpoint()s before its first dispatch
            # miscompiles the donated-buffer aliasing (params silently go
            # NaN, then glibc heap corruption) — observed on the CPU
            # backend, and rollback-and-replay does exactly that restore
            # sequence on every self-heal. Paying the recompile is the
            # robustness trade; sentinel is off by default so other runs
            # keep the cache.
            jax.config.update("jax_enable_compilation_cache", False)
            # the cache singleton may already be initialized (mesh building
            # compiles before the engine exists) — reset it so the disable
            # takes effect for this process
            from jax.experimental.compilation_cache import (
                compilation_cache as _cc)

            _cc.reset_cache()
            log_dist("sentinel: persistent compilation cache disabled "
                     "(deserialized donated-aliasing programs corrupt "
                     "restored state)", ranks=[0])
            if sent_cfg.state_dir:
                import os as _os

                rank = int(_os.environ.get("RANK", jax.process_index()))
                self._heartbeat = sentinel_mod.Heartbeat(
                    sent_cfg.state_dir, rank=rank,
                    interval_s=sent_cfg.heartbeat_interval_s)
            self._apply_quarantine_to_loader()
            log_dist(
                "sentinel: loss EMA+"
                f"{sent_cfg.loss_sigma_k:g}sigma / grad q{sent_cfg.grad_quantile:g}"
                f"x{sent_cfg.grad_quantile_mult:g} gates, window "
                f"{sent_cfg.window_steps} steps, third strike -> "
                f"{sent_cfg.on_third_strike}"
                + (f", dispatch watchdog {self._watchdog_timeout:g}s"
                   if self._watchdog_timeout else "")
                + (f", {len(self._sentinel.quarantined)} quarantined "
                   "fingerprint(s) restored"
                   if self._sentinel.quarantined else ""), ranks=[0])

        self._train_batch_jit = None
        self._accum_jit = None
        self._apply_jit = None
        self._eval_jit = None
        self._grads_jit = None
        self._group_apply_jit = None
        log_dist(
            f"Engine: model={self.model_spec.name} params={self.model_spec.num_params:,} "
            f"zero_stage={self.zero_stage} precision={config.precision_name} "
            f"mesh={topo.describe()} batch={config.train_batch_size}"
            f"(micro={config.train_micro_batch_size_per_device} x gas="
            f"{config.gradient_accumulation_steps} x dp={topo.dp_world_size})",
            ranks=[0],
        )

    # ------------------------------------------------------------------ internals
    @property
    def gas(self) -> int:
        return int(self.config.gradient_accumulation_steps or 1)

    @property
    def devprof_last(self) -> dict | None:
        """Parsed result of the most recent device-profile capture window
        (summary + classified ops + merge count), or None before the first
        window completes."""
        return self._devprof_last

    def _grad_ns(self):
        return self.plan.grad_shardings

    def _constrain_grads(self, grads):
        if getattr(self, "_inside_manual_region", False):
            # qgZ shard_map body: manual over the data axis only — constrain
            # to the grad specs with the manual axis dropped, so fsdp/ZeRO
            # sharding stays declared on the auto axes
            ns = self._manual_grad_ns()
        else:
            ns = self._grad_ns()
        return jax.tree_util.tree_map(
            lambda g, s: jax.lax.with_sharding_constraint(g.astype(jnp.float32), s),
            grads,
            ns,
        )

    def _manual_grad_ns(self):
        """Gradient shardings usable inside the qgZ partial-manual region:
        grad specs with the manual (data) axis entries filtered out."""
        manual = {"data"}

        def filt(spec):
            entries = []
            for e in spec:
                if isinstance(e, tuple):
                    rest = tuple(a for a in e if a not in manual)
                    entries.append(rest[0] if len(rest) == 1
                                   else (rest if rest else None))
                else:
                    entries.append(None if e in manual else e)
            return NamedSharding(self.topo.mesh, PartitionSpec(*entries))

        return jax.tree_util.tree_map(
            filt, self.plan.grad_specs,
            is_leaf=lambda x: isinstance(x, PartitionSpec))

    def _ltd_keep_for_step(self, step: int, seq: int) -> int:
        """Kept tokens per layer this step (0 = dense): the reference
        random-LTD seq schedule — linear ramp from start_keep_ratio back to
        the full sequence over total_steps — bucketed so each value is one
        compiled program."""
        cfg = self._ltd
        frac = min(1.0, step / max(1, cfg.total_steps))
        ratio = cfg.start_keep_ratio + (1.0 - cfg.start_keep_ratio) * frac
        k = int(-(-int(round(ratio * seq)) // cfg.bucket) * cfg.bucket)
        return 0 if k >= seq else max(k, min(cfg.bucket, seq - 1))

    def _cast_params(self, params):
        """Compute-dtype view of the master params. Under parameter offload
        the stacked layers stay host-resident fp32 (the ShardCtx.param_stream
        hook streams+casts each scan slice); other offloaded leaves stream
        whole; everything else casts in place."""
        if self._param_offload_mask is not None:
            from deepspeed_tpu.runtime import param_offload as po_mod

            return po_mod.cast_params_streaming(
                params, self._param_offload_mask, self.plan.param_shardings,
                self.config.compute_dtype,
                layers_key=("layers" if self.shard_ctx.param_stream is not None
                            else None))
        return precision.cast_to_compute(params, self.config.compute_dtype)

    def _microbatch_grads(self, params, mb, rng, scale, step=None):
        """Scaled-loss grads for one microbatch, fp32, ZeRO-sharded."""
        cparams = self._cast_params(params)
        # fault-injection rail (utils/faults.py train.grads / data.batch
        # directive kinds): a NaN multiplier models nan-grads, a large
        # finite one a poisoned/divergent batch — applied INSIDE the tape
        # so the gradients blow up with the loss. Key presence is static
        # per traced program; un-injected steps trace without it.
        loss_mult = mb.get("__loss_mult__")
        if loss_mult is not None:
            mb = {k: v for k, v in mb.items() if k != "__loss_mult__"}

        def scaled_loss(cp):
            if self._compression is not None and step is not None:
                # QAT/pruning INSIDE the tape so masks gate gradients the
                # way the reference's module wrappers do (pruned coords get
                # zero grads; fake-quant flows STE). Runs per microbatch —
                # it must sit inside each microbatch's grad tape, so it
                # cannot be hoisted out of the GAS scan.
                cp = self._compression.apply_to_params(cp, step)
            if self._ltd_active:
                # static kept-token count: this closure is traced once per
                # bucket value (train_batch keys the jit cache by it)
                loss = self.model_spec.loss_fn(cp, mb, rng,
                                               ltd_keep=self._ltd_active)
            else:
                loss = self.model_spec.loss_fn(cp, mb, rng)
            if loss_mult is not None:
                loss = loss * loss_mult.reshape(-1)[0]
            return loss * scale

        loss_scaled, grads = jax.value_and_grad(scaled_loss)(cparams)
        return loss_scaled / scale, self._constrain_grads(grads)

    def _update(self, params, opt_state, scale_state, grad_sum, n_micro, step,
                loss=None, sent_state=None):
        """Shared optimizer-step tail (reference ``_take_model_step:3168``):
        unscale, overflow check, clip, update, loss-scale bookkeeping.

        With ``sent_state`` (divergence sentinel enabled) the anomaly
        verdict is computed HERE, in the same fused program that already
        computes ``finite`` — a finite-but-divergent step (loss spike,
        grad-norm explosion) gates the ``_tree_select`` exactly like an
        overflow, at zero extra D2H syncs — and the call returns a 5-tuple
        with the advanced :class:`sentinel.SentinelState`. Loss-scale
        bookkeeping stays keyed on the raw ``finite`` (fp16 semantics are
        the scaler's, not the sentinel's).

        With the host offload tier, the update walks the optimizer sub-groups
        sequentially inside the same XLA program — each group's state streams
        host->HBM, updates, streams back, so peak HBM holds one group's state
        while XLA's scheduler overlaps the next group's transfer with the
        current group's compute."""
        cfg = self.config
        denom = scale_state.scale * n_micro
        grads = jax.tree_util.tree_map(lambda g: g / denom, grad_sum)
        finite = precision.grads_finite(grads)
        gnorm = _global_norm(grads)
        if cfg.gradient_clipping > 0:
            coef = jnp.minimum(1.0, cfg.gradient_clipping / (gnorm + 1e-6))
            grads = jax.tree_util.tree_map(lambda g: g * coef, grads)
        lr = self.lr_schedule(step)
        if self._lr_scale != 1.0:
            # sentinel third-strike backoff: a host constant folded in at
            # trace time (changing it invalidates the step program)
            lr = lr * jnp.float32(self._lr_scale)

        gate = finite
        new_sent = anomaly = reason = streak = None
        if sent_state is not None:
            new_sent, anomaly, reason, streak = sentinel_mod.verdict(
                sent_state, loss, gnorm, finite, cfg.sentinel)
            gate = jnp.logical_not(anomaly)

        if self._offload_mode == "cpu":
            new_p_leaves, new_opt = self._offload_group_walk(
                jax.tree_util.tree_leaves(params), opt_state,
                jax.tree_util.tree_leaves(grads), lr, gate)
            new_params = jax.tree_util.tree_unflatten(
                self._param_treedef, new_p_leaves)
        else:
            updates, new_opt = self.optimizer.update(grads, opt_state, params)
            updates = jax.tree_util.tree_map(lambda u: u * lr, updates)
            new_params = optax.apply_updates(params, updates)
            new_params = _tree_select(gate, new_params, params)
            new_opt = _tree_select(gate, new_opt, opt_state)
        new_scale = precision.update_loss_scale(scale_state, finite, cfg.fp16)
        metrics = {
            "grad_norm": gnorm,
            "lr": lr,
            "loss_scale": scale_state.scale,
            "skipped": jnp.logical_not(finite),
        }
        if sent_state is not None:
            metrics["anomalous"] = anomaly
            metrics["anomaly_reason"] = reason
            metrics["skip_streak"] = streak
            return new_params, new_opt, new_scale, metrics, new_sent
        return new_params, new_opt, new_scale, metrics

    def _offload_group_walk(self, p_leaves, opt_groups, g_leaves, lr, finite,
                            hot_idx=None):
        """Windowed sub-group update over host-pinned optimizer state
        (reference ``stage3.py:2360 _prepare_sub_group``): stream one group's
        state HBM-ward, update, stream back — shared by the dense offload tail
        and the zenflow cold update. All writes guarded by ``finite``.

        ``hot_idx``: per-leaf ZenFlow hot block indices; when set, the Adam
        moments at hot blocks are restored after the update (the selective
        optimizer owns them — see ``zenflow.restore_hot_opt_state``)."""
        from deepspeed_tpu.runtime import offload as offload_mod

        param_hosted = self._param_storage is not None
        new_p = list(p_leaves)
        new_opt = []
        # Windowing on TPU is MEMORY-PRESSURE-DRIVEN: the groups carry no
        # data dependencies, so when HBM is abundant XLA's latency-hiding
        # scheduler issues several groups' host->HBM copies ahead (measured:
        # the full state when it trivially fits); as the program's memory
        # bound tightens the scheduler serializes copies behind compute and
        # the peak holds ~a group window. Forcing the window with
        # optimization_barrier was measured STRICTLY worse here (mixed
        # host/device operands materialize extra device copies, +20% temp and
        # ~2x step time) — the declarative form wins, so the window is left
        # to the scheduler. The offload bench rung trains a model whose fp32
        # state exceeds HBM, which only completes if this actually windows.
        for g, idx in enumerate(self._groups):
            pg = tuple(p_leaves[i] for i in idx)
            gg = tuple(g_leaves[i] for i in idx)
            dev_sh, store_sh = self._group_shardings[g]
            if param_hosted:
                # ZeRO-Infinity: master params stream through HBM for the
                # update group-by-group, exactly like the optimizer state
                pg = tuple(jax.device_put(p, self._param_dev_leaf_sh[i])
                           for p, i in zip(pg, idx))
            state = offload_mod.stream_in(opt_groups[g], dev_sh)
            updates, new_state = self.optimizer.update(gg, state, pg)
            newp = optax.apply_updates(
                pg, jax.tree_util.tree_map(lambda u: u * lr, updates))
            newp = _tree_select(finite, newp, pg)
            new_state = _tree_select(finite, new_state, state)
            if hot_idx is not None:
                new_state = self._zf.restore_hot_opt_state(
                    new_state, state, tuple(hot_idx[i] for i in idx),
                    self.config.zero_optimization.zenflow.block)
            new_opt.append(offload_mod.stream_out(new_state, store_sh))
            if param_hosted:
                newp = tuple(jax.device_put(p, self._param_store_leaf_sh[i])
                             for p, i in zip(newp, idx))
            for j, i in enumerate(idx):
                new_p[i] = newp[j]
        return new_p, new_opt

    def _gas_grads(self, params, scale_state, step, base_rng, batch):
        """The traced GAS fwd/bwd body shared by the fused step and the
        split (offload) step: per-step rng fold-in, microbatch scan, fp32
        grad accumulation under the ZeRO sharding. Returns (mean loss, acc)."""
        gas = self.gas
        scale = scale_state.scale
        # derive the step's rng on-device: no host random.split round trip
        rng = jax.random.fold_in(base_rng, step)

        if self.config.progressive_layer_drop.enabled:
            # inject the traced theta(t) so the drop schedule advances
            # without recompilation (runtime/progressive_layer_drop.py)
            from deepspeed_tpu.runtime.progressive_layer_drop import pld_theta

            pld_cfg = self.config.progressive_layer_drop
            theta = pld_theta(step, pld_cfg.theta, pld_cfg.gamma)
            batch = dict(batch)
            batch["pld_theta"] = jnp.broadcast_to(theta, (gas,))

        if gas == 1:
            # fast path: no accumulation buffer, no scan machinery
            mb = jax.tree_util.tree_map(lambda x: x[0], batch)
            loss, acc = self._microbatch_grads(params, mb, rng, scale, step=step)
            losses = loss[None]
        else:
            ns = (self._manual_grad_ns()
                  if getattr(self, "_inside_manual_region", False)
                  else self._grad_ns())
            acc0 = jax.tree_util.tree_map(
                lambda p, s: jax.lax.with_sharding_constraint(
                    jnp.zeros(p.shape, jnp.float32), s
                ),
                params,
                ns,
            )

            def micro(acc, idx_mb):
                idx, mb = idx_mb
                r = jax.random.fold_in(rng, idx)
                loss, grads = self._microbatch_grads(params, mb, r, scale,
                                                     step=step)
                return jax.tree_util.tree_map(jnp.add, acc, grads), loss

            acc, losses = jax.lax.scan(micro, acc0, (jnp.arange(gas), batch))
        return jnp.mean(losses), acc

    def _build_train_batch_fn(self, use_qgrad: bool | None = None):
        self._record_comms_plan()
        uq = self._qgrad if use_qgrad is None else use_qgrad
        if self._grad_overlap:
            return self._build_train_batch_fn_overlap(use_qgrad=uq)
        if uq:
            return self._build_train_batch_fn_qgrad()
        if (self.topo.size("pipeline") > 1
                and self.config.pipeline.schedule == "1f1b"):
            return self._build_train_batch_fn_1f1b()

        if self._sentinel is not None:
            # sentinel variant: the rolling-stats state rides the step like
            # LossScaleState (donated, advanced in-program) and the verdict
            # fuses into the update tail — same program count, no extra
            # dispatches, no extra syncs
            def sent_batch_fn(params, opt_state, scale_state, step, base_rng,
                              batch, sent_state):
                loss, acc = self._gas_grads(
                    params, scale_state, step, base_rng, batch)
                new_params, new_opt, new_scale, metrics, new_sent = \
                    self._update(
                        params, opt_state, scale_state, acc, float(self.gas),
                        step, loss=loss, sent_state=sent_state)
                metrics["loss"] = loss
                return new_params, new_opt, new_scale, metrics, new_sent

            return jax.jit(sent_batch_fn, donate_argnums=(0, 1, 2, 6))

        def train_batch_fn(params, opt_state, scale_state, step, base_rng, batch):
            loss, acc = self._gas_grads(params, scale_state, step, base_rng, batch)
            new_params, new_opt, new_scale, metrics = self._update(
                params, opt_state, scale_state, acc, float(self.gas), step
            )
            metrics["loss"] = loss
            return new_params, new_opt, new_scale, metrics

        return jax.jit(train_batch_fn, donate_argnums=(0, 1, 2))

    def _reduction_codec(self) -> tuple[str, float]:
        """(codec, wire bytes/element) of the data-axis gradient reduction.

        Derived from the CONFIG, not ``self._qgrad`` — the stepscope estimate
        is built at ``__init__`` time, before the qgrad attrs exist. A 1-bit-
        family warmup phase runs a dense wire; the estimate deliberately
        reflects the steady-state (post-freeze_step) codec."""
        from deepspeed_tpu.parallel.grad_overlap import wire_bytes_per_element

        zero = self.config.zero_optimization
        if zero.quantized_gradients:
            codec = f"int{int(zero.quantized_gradients_bits)}"
            return codec, wire_bytes_per_element(codec)
        return "fp32", 4.0

    def _record_comms_plan(self) -> None:
        """Static comms plan of the fused step (comms_logging trace ledger).

        GSPMD inserts the gradient-sync collectives from shardings — no
        wrapper call ever fires at trace time — so the per-step plan is
        recorded here once per program build. Bytes follow the ACTIVE
        reduction codec (qgZ quantizes the data-axis wire to intN + blockwise
        fp32 scales; the old fp32 assumption overstated quantized runs ~4x);
        under grad_overlap the plan is per BUCKET, and the bucket geometry is
        exported as ``grad_bucket_*`` gauges (docs/OBSERVABILITY.md)."""
        from deepspeed_tpu.utils.comms_logging import COMMS_LOGGER

        dp, fs = self.topo.size("data"), self.topo.size("fsdp")
        if dp <= 1 and fs <= 1:
            return
        n_elems = sum(
            int(np.prod(l.shape)) for l in jax.tree_util.tree_leaves(self.params))
        codec, bpe = self._reduction_codec()
        if fs > 1:
            rows, gather = self._fsdp_plan()
            for op, nbytes, caller in rows:
                COMMS_LOGGER.append_traced(op, int(nbytes), "fsdp", fs,
                                           caller=caller)
            if gather is not None and self.telemetry.enabled:
                self.telemetry.gauge(
                    "zero3_gathered_leaves",
                    "param leaves the plan shards over fsdp and the step "
                    "gathers (a scanned layer's stacked leaf counts once)"
                ).set(float(gather["leaves"]), codec=gather["codec"])
                self.telemetry.gauge(
                    "zero3_gather_bytes_per_step",
                    "payload bytes of the stage-3 weight gathers a step: "
                    "wire dtype x passes (forward, rematerialized forward) "
                    "x microbatches"
                ).set(float(gather["bytes"]), codec=gather["codec"])
        if dp <= 1:
            return
        if self._grad_overlap:
            plan = self._overlap_plan
            padded = sum(b.padded for b in plan.buckets)
            for b in plan.buckets:
                COMMS_LOGGER.append_traced(
                    "reduce_scatter", b.wire_bytes, "data", dp,
                    caller=f"grad_overlap/bucket{b.index}:{b.codec}")
            if self._overlap_sharded:
                # one ring all-gather of the UPDATED PARAMS (fp32), the
                # ZeRO-1 tail
                COMMS_LOGGER.append_traced(
                    "all_gather", int(4.0 * padded * (dp - 1) / dp), "data",
                    dp, caller="grad_overlap/params")
            else:
                for b in plan.buckets:
                    COMMS_LOGGER.append_traced(
                        "all_gather", b.wire_bytes, "data", dp,
                        caller=f"grad_overlap/bucket{b.index}:{b.codec}")
            if self.telemetry.enabled:
                self.telemetry.gauge(
                    "grad_bucket_count",
                    "grad_overlap bucket count").set(float(len(plan.buckets)))
                g_bytes = self.telemetry.gauge(
                    "grad_bucket_bytes",
                    "grad_overlap per-bucket payload bytes (fp32 accumulate)")
                g_wire = self.telemetry.gauge(
                    "grad_bucket_wire_bytes",
                    "grad_overlap per-bucket ring reduce wire bytes under "
                    "the active codec")
                for b in plan.buckets:
                    g_bytes.set(float(4 * b.elems),
                                bucket=str(b.index), codec=b.codec)
                    g_wire.set(float(b.wire_bytes),
                               bucket=str(b.index), codec=b.codec)
        else:
            caller = ("train_batch_fn" if codec == "fp32"
                      else f"train_batch_fn[{codec}]")
            COMMS_LOGGER.append_traced("all_reduce", int(bpe * n_elems),
                                       "data", dp, caller=caller)

    def _fsdp_plan(self):
        """``([(op, payload bytes, caller)], stated-gather summary or None)``:
        a step's collectives over the fsdp axis.

        ZeRO <= 2 over fsdp: fp32 grads reduce-scattered, the updated fp32
        params gathered (qgZ quantizes the data axis only). Stage 3 with the
        stated gather (``ShardCtx.weight_gather``): a gathered leaf travels
        in the compute dtype (or int8 + one fp32 scale a block, qwZ) once
        for the forward and once more where the backward rematerializes it,
        every microbatch; its gradient comes back in the compute dtype, a
        scanned layer's through an all-reduce and a slice, a leaf's outside
        the scan through a reduce-scatter (``qwz.gather_weight``); nothing is
        gathered after the update, the params stay shards."""
        n_elems = sum(
            int(np.prod(l.shape)) for l in jax.tree_util.tree_leaves(self.params))
        if not self._zero3_gathered:
            return [("reduce_scatter", 4.0 * n_elems, "train_batch_fn"),
                    ("all_gather", 4.0 * n_elems, "train_batch_fn")], None
        zero = self.config.zero_optimization
        itemsize = jnp.dtype(self.config.compute_dtype).itemsize
        wire = {None: float(itemsize), "int8": 1.0 + 4.0 / zero.qwz_block}
        passes = 2 if self.shard_ctx.remat else 1
        gas = self.gas
        codec = ("int8" if zero.quantized_weights
                 else str(jnp.dtype(self.config.compute_dtype)))
        gather_bytes = gas * passes * sum(
            n * wire[c] for _, n, c, _ in self._zero3_gathered)
        grads = {scanned: gas * itemsize * sum(
            n for _, n, _, s in self._zero3_gathered if s == scanned)
            for scanned in (True, False)}
        return ([("all_gather", gather_bytes, f"zero3_gather[{codec}]x{passes}"),
                 ("all_reduce", grads[True], "zero3_gather/layer_grads"),
                 ("reduce_scatter", grads[False], "zero3_gather/leaf_grads")],
                {"leaves": len(self._zero3_gathered), "codec": codec,
                 "bytes": gather_bytes})

    def _grad_wire_bytes(self) -> float:
        """Estimated per-step gradient-sync wire bytes (same plan as
        ``_record_comms_plan``, with ring-collective wire factors): feeds the
        stepscope overlap estimate. Codec-aware — see ``_reduction_codec``."""
        dp, fs = self.topo.size("data"), self.topo.size("fsdp")
        if dp <= 1 and fs <= 1:
            return 0.0
        n_elems = sum(
            int(np.prod(l.shape)) for l in jax.tree_util.tree_leaves(self.params))
        _, bpe = self._reduction_codec()
        wire = 0.0
        if fs > 1:
            # ring reduce-scatter and all-gather each move (n-1)/n of the
            # data, an all-reduce is one of each
            wire += sum((2.0 if op == "all_reduce" else 1.0) * nbytes
                        for op, nbytes, _ in self._fsdp_plan()[0]
                        ) * (fs - 1) / fs
        if dp > 1:
            if self._grad_overlap:
                plan = self._overlap_plan
                rs = float(sum(b.wire_bytes for b in plan.buckets))
                padded = sum(b.padded for b in plan.buckets)
                if self._overlap_sharded:
                    # grad reduce-scatter (codec wire) + fp32 all-gather of
                    # the updated params
                    wire += rs + 4.0 * padded * (dp - 1) / dp
                else:
                    # per-bucket ring reduce-scatter + ring all-gather
                    wire += 2.0 * rs
            else:
                # ring all-reduce = reduce-scatter + all-gather
                wire += 2.0 * bpe * n_elems * (dp - 1) / dp
        return wire

    def _jit_miss_count(self) -> float:
        """Cumulative backend-compile count from the PR 5 monitoring listener
        (used to tag recompile-bearing steps)."""
        if not self.telemetry.enabled:
            return 0.0
        return self.telemetry.registry.counter(
            "jit_cache_misses_total",
            "XLA compilations observed").value(source="monitoring")

    def _step_recompiled(self) -> bool:
        """True when the in-progress step triggered an XLA compilation —
        those steps are excluded from the throughput average (their wall time
        is compile stall, not steady-state step time)."""
        if self._step_miss0 is None:
            return False
        return self._jit_miss_count() > self._step_miss0

    def _build_train_batch_fn_qgrad(self):
        """Fused step with qgZ gradient reduction (reference ZeRO++
        ``all_to_all_quant_reduce``, ``coalesced_collectives.py:31``): the GAS
        fwd/bwd runs PER DATA RANK inside a shard_map that is manual over the
        DATA axis only — fsdp (and the ZeRO-2/3 shardings that live on it)
        stays GSPMD-auto inside the body — then each grad leaf reduces once
        over data through the int8 quantized collective with error feedback;
        the optimizer tail runs on the fsdp-sharded result."""
        from deepspeed_tpu.comm.quantized_collectives import quantized_all_reduce
        from deepspeed_tpu.comm.topology import AXIS_DATA

        mesh = self.topo.mesh

        def train_batch_fn(params, opt_state, scale_state, step, base_rng,
                           batch, qerr):
            def local(params, batch, qerr):
                self._inside_manual_region = True
                self.shard_ctx._manual_axes = {AXIS_DATA}
                try:
                    loss, acc = self._gas_grads(
                        params, scale_state, step, base_rng, batch)
                finally:
                    self._inside_manual_region = False
                    self.shard_ctx._manual_axes = ()
                g_leaves, tdef = jax.tree_util.tree_flatten(acc)
                e_leaves = jax.tree_util.tree_leaves(qerr)
                red, nerr = [], []
                for g, e in zip(g_leaves, e_leaves):
                    r, ne = quantized_all_reduce(g, AXIS_DATA, e[0],
                                                 bits=self._qgrad_bits)
                    red.append(r)
                    nerr.append(ne[None])
                return (jax.lax.pmean(loss, AXIS_DATA),
                        jax.tree_util.tree_unflatten(tdef, red),
                        jax.tree_util.tree_unflatten(tdef, nerr))

            loss, acc, new_qerr = shard_map_compat(
                local, mesh=mesh,
                in_specs=(PartitionSpec(), PartitionSpec(None, AXIS_DATA),
                          PartitionSpec(AXIS_DATA)),
                out_specs=(PartitionSpec(), PartitionSpec(),
                           PartitionSpec(AXIS_DATA)),
                axis_names={AXIS_DATA}, check_vma=False,
            )(params, batch, qerr)
            new_params, new_opt, new_scale, metrics = self._update(
                params, opt_state, scale_state, acc, float(self.gas), step
            )
            metrics["loss"] = loss
            # overflow step: keep the previous residuals — a NaN/Inf error
            # buffer would poison every subsequent step's gradients
            finite = jnp.logical_not(metrics["skipped"])
            new_qerr = _tree_select(finite, new_qerr, qerr)
            return new_params, new_opt, new_scale, metrics, new_qerr

        return jax.jit(train_batch_fn, donate_argnums=(0, 1, 2, 6))

    def _init_overlap_opt_state(self):
        """ZeRO-1 flat optimizer state for the overlap sharded update: pack
        the params into the plan's per-bucket ``[dp, shard]`` rows (the exact
        view the sharded tail updates), init the optimizer over that tuple,
        and row-shard every array leaf over the data axis — each rank holds
        the 1/dp of the moments its grad shard updates. Returns
        ``(state, partition-spec tree, sharding tree)``; the sharding tree
        replaces ``self._opt_shardings`` so checkpoint restore places the
        flat state without special-casing."""
        from deepspeed_tpu.parallel import grad_overlap as go_mod

        plan = self._overlap_plan
        mesh = self.topo.mesh

        def init(params):
            leaves, _ = go_mod.ordered_leaves(params, plan)
            rows = tuple(
                go_mod.pack_bucket(leaves, b).reshape(plan.dp, b.shard)
                for b in plan.buckets)
            return self.optimizer.init(rows)

        abstract = jax.eval_shape(init, self.params)
        specs = jax.tree_util.tree_map(
            lambda l: (PartitionSpec("data") if getattr(l, "ndim", 0) >= 1
                       else PartitionSpec()),
            abstract)
        shardings = jax.tree_util.tree_map(
            lambda s: NamedSharding(mesh, s), specs,
            is_leaf=lambda x: isinstance(x, PartitionSpec))
        state = jax.jit(init, out_shardings=shardings)(self.params)
        return state, specs, shardings

    def _build_train_batch_fn_overlap(self, use_qgrad: bool = False):
        """Overlap-first fused step (docs/TP_OVERLAP.md "grad-sync overlap";
        T3-style fine-grained overlap, arxiv 2401.16677). The GAS fwd/bwd
        runs per data rank inside a shard_map manual over the DATA axis, then
        each size-targeted bucket of the grad tree reduce-scatters through
        its own async ppermute ring. Each ring depends only on its bucket's
        grad leaves — not the full tree, unlike the fused GSPMD all-reduce —
        so XLA's latency-hiding scheduler issues one bucket's transfer while
        backward compute for other buckets is still in flight.

        With ``sharded_update`` the optimizer tail is ZeRO-1 over the data
        axis without fsdp machinery (arxiv 2004.13336): each rank updates
        only its reduce-scattered grad shard against its ``[1, shard]`` slice
        of the flat optimizer state, then ring-all-gathers the updated
        params — optimizer FLOPs and state-touch bytes drop by 1/dp.

        Numerics vs the fused baseline are documented-fp-reorder-bounded
        (ring summation order; local-mean-then-pmean loss); the
        ``grad_overlap.exact`` kill switch routes back through the baseline
        program, which is bit-identical by construction. With ``use_qgrad``
        the buckets ride the qgZ quantized collective (per-bucket error
        feedback) on the same schedule."""
        from deepspeed_tpu.comm.topology import AXIS_DATA
        from deepspeed_tpu.parallel import grad_overlap as go_mod

        if use_qgrad:
            from deepspeed_tpu.comm.quantized_collectives import (
                quantized_all_reduce)

        mesh = self.topo.mesh
        cfg = self.config
        plan = self._overlap_plan
        dp = plan.dp
        n_micro = float(self.gas)
        sharded = self._overlap_sharded
        sentinel = self._sentinel is not None
        P = PartitionSpec

        def _scheduled_lr(step):
            lr = self.lr_schedule(step)
            if self._lr_scale != 1.0:
                lr = lr * jnp.float32(self._lr_scale)
            return lr

        def reduce_buckets(acc, qerr):
            """Per-bucket data-axis reduction inside the manual region.
            ``acc`` is the GAS-SUM of local-batch-mean grads; the ring sum
            / dp (or the quantized collective's mean) makes each bucket the
            rank-mean analog the update denom expects. Returns this rank's
            ``[shard]`` slices when sharded, full ``[padded]`` flats when
            replicated, plus the advanced qgZ residuals."""
            leaves, _ = go_mod.ordered_leaves(acc, plan)
            outs, nerr = [], []
            for b in plan.buckets:
                flat = go_mod.pack_bucket(leaves, b)
                if use_qgrad:
                    red, ne = quantized_all_reduce(
                        flat, AXIS_DATA, qerr[b.index][0],
                        bits=self._qgrad_bits)
                    nerr.append(ne[None])
                    outs.append(go_mod.local_shard(red, AXIS_DATA, dp)
                                if sharded else red)
                else:
                    rs = go_mod.ring_reduce_scatter_sum(flat, AXIS_DATA) / dp
                    outs.append(rs if sharded
                                else go_mod.ring_all_gather(rs, AXIS_DATA))
            return outs, (tuple(nerr) if use_qgrad else None)

        if not sharded:
            # replicated update: per-bucket ring reduce (RS + AG = async
            # all-reduce) feeds the unchanged ``_update`` tail
            def make_step(with_sent):
                def step_fn(params, opt_state, scale_state, step, base_rng,
                            batch, *extra):
                    def local(params, batch, *rest):
                        qerr = rest[0] if use_qgrad else None
                        self._inside_manual_region = True
                        self.shard_ctx._manual_axes = {AXIS_DATA}
                        try:
                            loss, acc = self._gas_grads(
                                params, scale_state, step, base_rng, batch)
                        finally:
                            self._inside_manual_region = False
                            self.shard_ctx._manual_axes = ()
                        fulls, nerr = reduce_buckets(acc, qerr)
                        _, tdef = jax.tree_util.tree_flatten(acc)
                        acc_mean = go_mod.unflatten_buckets(fulls, plan, tdef)
                        out = (jax.lax.pmean(loss, AXIS_DATA), acc_mean)
                        return out + ((nerr,) if use_qgrad else ())

                    in_specs = (P(), P(None, AXIS_DATA))
                    out_specs = (P(), P())
                    operands = (params, batch)
                    if use_qgrad:
                        in_specs += (P(AXIS_DATA),)
                        out_specs += (P(AXIS_DATA),)
                        operands += (extra[0],)
                    res = go_mod.shard_map_compat(
                        local, mesh=mesh, in_specs=in_specs,
                        out_specs=out_specs, axis_names={AXIS_DATA},
                        check_vma=False,
                    )(*operands)
                    loss, acc = res[0], res[1]
                    if with_sent:
                        new_params, new_opt, new_scale, metrics, new_sent = \
                            self._update(
                                params, opt_state, scale_state, acc, n_micro,
                                step, loss=loss, sent_state=extra[0])
                        metrics["loss"] = loss
                        return (new_params, new_opt, new_scale, metrics,
                                new_sent)
                    new_params, new_opt, new_scale, metrics = self._update(
                        params, opt_state, scale_state, acc, n_micro, step)
                    metrics["loss"] = loss
                    if use_qgrad:
                        finite = jnp.logical_not(metrics["skipped"])
                        new_qerr = _tree_select(finite, res[2], extra[0])
                        return (new_params, new_opt, new_scale, metrics,
                                new_qerr)
                    return new_params, new_opt, new_scale, metrics

                return step_fn

            if use_qgrad or sentinel:
                return jax.jit(make_step(sentinel),
                               donate_argnums=(0, 1, 2, 6))
            return jax.jit(make_step(False), donate_argnums=(0, 1, 2))

        # sharded update: the WHOLE optimizer tail lives inside the manual
        # region, mirroring ``_update`` operation-for-operation on 1/dp views
        def make_sharded_step():
            def step_fn(params, opt_state, scale_state, step, base_rng,
                        batch, *extra):
                sent_state = extra[0] if sentinel else None
                qerr = extra[0] if use_qgrad else None

                def local(params, batch, opt_flat, *rest):
                    q = rest[0] if use_qgrad else None
                    self._inside_manual_region = True
                    self.shard_ctx._manual_axes = {AXIS_DATA}
                    try:
                        loss, acc = self._gas_grads(
                            params, scale_state, step, base_rng, batch)
                    finally:
                        self._inside_manual_region = False
                        self.shard_ctx._manual_axes = ()
                    shards, nerr = reduce_buckets(acc, q)
                    loss = jax.lax.pmean(loss, AXIS_DATA)
                    # ---- _update tail on 1/dp shards (same op order)
                    denom = scale_state.scale * n_micro
                    gsh = [s / denom for s in shards]
                    bad = sum(
                        jnp.sum(jnp.logical_not(jnp.isfinite(g))
                                .astype(jnp.int32)) for g in gsh)
                    finite = jax.lax.psum(bad, AXIS_DATA) == 0
                    ssq = sum(jnp.sum(jnp.square(g)) for g in gsh)
                    gnorm = jnp.sqrt(jax.lax.psum(ssq, AXIS_DATA))
                    if cfg.gradient_clipping > 0:
                        coef = jnp.minimum(
                            1.0, cfg.gradient_clipping / (gnorm + 1e-6))
                        gsh = [g * coef for g in gsh]
                    lr = _scheduled_lr(step)
                    gate = finite
                    sent_out = ()
                    if sentinel:
                        new_sent, anomaly, reason, streak = \
                            sentinel_mod.verdict(sent_state, loss, gnorm,
                                                 finite, cfg.sentinel)
                        gate = jnp.logical_not(anomaly)
                        sent_out = (new_sent, anomaly, reason, streak)
                    p_leaves, p_tdef = go_mod.ordered_leaves(params, plan)
                    p_rows = tuple(
                        go_mod.local_shard(
                            go_mod.pack_bucket(p_leaves, b), AXIS_DATA, dp
                        ).reshape(1, -1)
                        for b in plan.buckets)
                    g_rows = tuple(g.reshape(1, -1) for g in gsh)
                    updates, new_opt = self.optimizer.update(
                        g_rows, opt_flat, p_rows)
                    updates = jax.tree_util.tree_map(lambda u: u * lr,
                                                     updates)
                    new_rows = optax.apply_updates(p_rows, updates)
                    new_rows = _tree_select(gate, new_rows, p_rows)
                    new_opt = _tree_select(gate, new_opt, opt_flat)
                    full_flats = [
                        go_mod.ring_all_gather(nr.reshape(-1), AXIS_DATA)
                        for nr in new_rows]
                    new_params = go_mod.unflatten_buckets(
                        full_flats, plan, p_tdef)
                    out = (loss, new_params, new_opt, gnorm, finite)
                    out += sent_out
                    return out + ((tuple(nerr),) if use_qgrad else ())

                in_specs = (P(), P(None, AXIS_DATA), self._overlap_opt_specs)
                out_specs = (P(), P(), self._overlap_opt_specs, P(), P())
                operands = (params, batch, opt_state)
                if sentinel:
                    out_specs += (P(), P(), P(), P())
                if use_qgrad:
                    in_specs += (P(AXIS_DATA),)
                    out_specs += (P(AXIS_DATA),)
                    operands += (qerr,)
                res = go_mod.shard_map_compat(
                    local, mesh=mesh, in_specs=in_specs, out_specs=out_specs,
                    axis_names={AXIS_DATA}, check_vma=False,
                )(*operands)
                loss, new_params, new_opt, gnorm, finite = res[:5]
                new_scale = precision.update_loss_scale(
                    scale_state, finite, cfg.fp16)
                metrics = {
                    "grad_norm": gnorm,
                    "lr": _scheduled_lr(step),
                    "loss_scale": scale_state.scale,
                    "skipped": jnp.logical_not(finite),
                    "loss": loss,
                }
                if sentinel:
                    new_sent, anomaly, reason, streak = res[5:9]
                    metrics["anomalous"] = anomaly
                    metrics["anomaly_reason"] = reason
                    metrics["skip_streak"] = streak
                    return new_params, new_opt, new_scale, metrics, new_sent
                if use_qgrad:
                    new_qerr = _tree_select(finite, res[5], qerr)
                    return new_params, new_opt, new_scale, metrics, new_qerr
                return new_params, new_opt, new_scale, metrics

            return step_fn

        if use_qgrad or sentinel:
            return jax.jit(make_sharded_step(), donate_argnums=(0, 1, 2, 6))
        return jax.jit(make_sharded_step(), donate_argnums=(0, 1, 2))

    def _build_grads_fn(self):
        """Jitted fwd/bwd over the GAS scan WITHOUT the optimizer tail — the
        ZeRO-Infinity step splits there so the update can walk NVMe-resident
        sub-groups on the host."""
        return jax.jit(self._gas_grads)

    def _build_train_batch_fn_1f1b(self):
        """Fused step under the 1F1B pipeline schedule (reference
        ``schedule.py:189 TrainSchedule`` / ``PipelineEngine.train_batch``):
        GAS microbatches ARE the pipeline microbatches; fwd+bwd run manually
        interleaved inside ``parallel/pipeline_1f1b.py`` and the optimizer
        tail is shared with every other path."""
        from deepspeed_tpu.parallel.pipeline_1f1b import pipeline_train_grads

        parts = self.model_spec.pipeline_parts
        if parts is None:
            raise ValueError(
                f"model {self.model_spec.name} provides no pipeline_parts; "
                "the 1f1b schedule needs a stage decomposition"
            )
        stage0_fn, block_fn, last_fn, split_fn, merge_fn = parts
        if self.gas < self.topo.size("pipeline"):
            raise ValueError(
                f"1f1b needs gradient_accumulation_steps (= pipeline "
                f"microbatches, {self.gas}) >= pipeline stages "
                f"({self.topo.size('pipeline')})"
            )
        gas = self.gas

        def train_batch_fn(params, opt_state, scale_state, step, base_rng, batch):
            del base_rng  # no dropout in the pipelined models
            scale = scale_state.scale
            cparams = precision.cast_to_compute(params, self.config.compute_dtype)
            stacked, extras = split_fn(cparams)

            def last_scaled(e, y, t):
                return last_fn(e, y, t) * scale

            # sharding hints are suspended inside the manual-over-pipeline
            # region (GSPMD still propagates the auto axes from the inputs),
            # mirroring ShardCtx.layer_stack's GPipe handling
            self.shard_ctx._suspend_constraints = True
            try:
                loss_scaled, gl, ge = pipeline_train_grads(
                    stage0_fn, block_fn, last_scaled, stacked, extras,
                    batch, batch, self.topo.mesh,
                )
            finally:
                self.shard_ctx._suspend_constraints = False
            # pipeline returns mean-over-microbatch grads; the shared update
            # tail expects the GAS-summed accumulator
            acc = self._constrain_grads(
                jax.tree_util.tree_map(lambda g: g * gas, merge_fn(gl, ge)))
            new_params, new_opt, new_scale, metrics = self._update(
                params, opt_state, scale_state, acc, float(gas), step
            )
            metrics["loss"] = loss_scaled / scale
            return new_params, new_opt, new_scale, metrics

        return jax.jit(train_batch_fn, donate_argnums=(0, 1, 2))

    def _group_apply(self, g: int):
        """Sub-group optimizer apply for group ``g`` (NVMe walk): takes the
        group's param/grad leaf tuples + its NVMe-loaded state, returns the
        updated leaves and state. ``factor`` folds unscale+clip into one
        multiplier (coef / (scale * n_micro)). Under parameter offload the
        group's host-resident masters stream through HBM for the update and
        back (per-group jit: the stream targets are group-specific)."""
        if self._group_apply_jit is None:
            self._group_apply_jit = {}
        param_hosted = self._param_storage is not None
        # with no group-specific sharding targets (plain NVMe tier) the
        # program is identical for every group: ONE shared jit object, so
        # jax's shape-level cache dedups compiles across uniform groups
        cache_key = (g if (param_hosted or self._offload_mode == "cpu")
                     else "shared")
        fn = self._group_apply_jit.get(cache_key)
        if fn is not None:
            return fn
        idx = self._groups[g]
        in_sh = tuple(self._param_dev_leaf_sh[i] for i in idx) \
            if param_hosted else None
        out_sh = tuple(self._param_store_leaf_sh[i] for i in idx) \
            if param_hosted else None
        # cpu tier: the state argument arrives as pinned-host jax arrays and
        # streams through HBM inside this (per-group) program; nvme tier:
        # the state arrives as np host buffers from the swapper
        state_sh = (self._group_shardings[g]
                    if self._offload_mode == "cpu" else None)

        def apply_g(pg, state, gg, factor, lr, finite):
            if param_hosted:
                pg = tuple(jax.device_put(p, s) for p, s in zip(pg, in_sh))
            if state_sh is not None:
                from deepspeed_tpu.runtime import offload as offload_mod

                state = offload_mod.stream_in(state, state_sh[0])
            gg = jax.tree_util.tree_map(lambda x: x * factor, gg)
            updates, new_state = self.optimizer.update(gg, state, pg)
            newp = optax.apply_updates(
                pg, jax.tree_util.tree_map(lambda u: u * lr, updates))
            # the overflow guard rides along on device — under superoffload
            # this replaces the reference's speculative-step CPU rollback
            # (superoffload_stage3.py _handle_overflow_rollback): an
            # overflowed step writes back the unchanged state
            newp = _tree_select(finite, newp, pg)
            new_state = _tree_select(finite, new_state, state)
            if state_sh is not None:
                from deepspeed_tpu.runtime import offload as offload_mod

                new_state = offload_mod.stream_out(new_state, state_sh[1])
            if param_hosted:
                newp = tuple(jax.device_put(p, s) for p, s in zip(newp, out_sh))
            return newp, new_state

        fn = jax.jit(apply_g, donate_argnums=(1,))
        self._group_apply_jit[cache_key] = fn
        return fn

    def _get_pre_jit(self):
        """ONE fused program for the split-step prologue (norm + overflow +
        clip + lr). Eager per-leaf jnp ops here would each dispatch a tiny
        8-device program with its own collective rendezvous — racing the
        AIO threads, that starves nondeterministically on a 1-core host
        (observed as 0%-CPU wedges in the test suite)."""
        if getattr(self, "_pre_jit", None) is None:
            gas = jnp.float32(self.gas)
            clip = self.config.gradient_clipping

            def pre_fn(grad_sum, scale, step):
                denom = scale * gas
                gnorm = _global_norm(grad_sum) / denom
                finite = precision.grads_finite(grad_sum)
                coef = (jnp.minimum(1.0, clip / (gnorm + 1e-6))
                        if clip > 0 else jnp.float32(1.0))
                return gnorm, finite, coef / denom, self.lr_schedule(step)

            self._pre_jit = jax.jit(pre_fn)
        return self._pre_jit

    def _train_batch_grouped(self, batch: dict):
        """Split step for the HOST-pinned tier (and/or parameter offload):
        fwd/bwd in one program, then ONE PROGRAM PER SUB-GROUP for the
        optimizer walk — the reference's per-subgroup step
        (``stage3.py:2360 _prepare_sub_group`` + CPU-Adam-per-group), and the
        only layout whose peak HBM is truly one group's window: inside a
        single fused program the groups carry no data dependencies, so XLA's
        scheduler is free to issue every group's host->HBM copy concurrently —
        measured on TPU as the full optimizer state materializing in HBM and,
        past HBM capacity, a compile-time OOM. Program boundaries are the
        fence. The overflow verdict stays a device scalar inside every
        per-group program (speculative dispatch, no host sync)."""
        if self._grads_jit is None:
            self._grads_jit = self._build_grads_fn()
        scope = self.stepscope if self.stepscope.enabled else None
        dev_batch = self._put_gas_batch(batch)
        self.tput_timer.start()
        _c0 = time.perf_counter() if scope is not None else 0.0
        loss, grad_sum = self._grads_jit(
            self.params, self.scale_state, jnp.int32(self.global_steps),
            self._train_rng, dev_batch,
        )
        gnorm, finite_dev, factor, lr = self._get_pre_jit()(
            grad_sum, self.scale_state.scale, jnp.int32(self.global_steps))
        if scope is not None:
            jax.block_until_ready((loss, gnorm))
            scope.note_phase("compute", _c0, time.perf_counter())
            _o0 = time.perf_counter()
        p_leaves = jax.tree_util.tree_leaves(self.params)
        g_leaves = jax.tree_util.tree_leaves(grad_sum)
        new_p_leaves = list(p_leaves)
        new_opt = []
        for g, idx in enumerate(self._groups):
            pg = tuple(p_leaves[i] for i in idx)
            gg = tuple(g_leaves[i] for i in idx)
            newp, new_state = self._group_apply(g)(
                pg, self.opt_state[g], gg, factor, lr, finite_dev)
            new_opt.append(new_state)
            for j, i in enumerate(idx):
                new_p_leaves[i] = newp[j]
        self.params = jax.tree_util.tree_unflatten(
            self._param_treedef, new_p_leaves)
        self.opt_state = new_opt
        if scope is not None:
            # the per-group walk is host-measured (no attribution needed)
            jax.block_until_ready(new_p_leaves)
            scope.note_phase("optimizer", _o0, time.perf_counter())
        step_scale = self.scale_state.scale
        self.scale_state = precision.update_loss_scale(
            self.scale_state, finite_dev, self.config.fp16)
        metrics = {
            "loss": loss,
            "grad_norm": gnorm,
            "lr": lr,
            "loss_scale": step_scale,
            "skipped": jnp.logical_not(finite_dev),
        }
        # bounded async window (same discipline as the fused path)
        self._inflight.append(metrics["loss"])
        if len(self._inflight) > self._max_inflight:
            jax.block_until_ready(self._inflight.pop(0))
        self.tput_timer.stop(
            global_step=True,
            exclude=self._step_recompiled() or self._devprof_capturing())
        self._after_step(metrics)
        self.micro_steps += self.gas
        return metrics["loss"]

    def _train_batch_nvme(self, batch: dict):
        """Full step with NVMe-resident optimizer state (reference
        ZeRO-Infinity: ``pipelined_optimizer_swapper.py:52`` — prefetch window
        k+1 while window k updates; writes are async with a commit barrier at
        the step end)."""
        if self._grads_jit is None:
            self._grads_jit = self._build_grads_fn()
        scope = self.stepscope if self.stepscope.enabled else None
        dev_batch = self._put_gas_batch(batch)
        self.tput_timer.start()
        _c0 = time.perf_counter() if scope is not None else 0.0
        # issue the group-0 NVMe read NOW: it overlaps the whole fwd/bwd
        # (harmless if the step overflows — the read stays valid for the
        # next step since skipped steps write nothing)
        self._swapper.prefetch_tree("opt_g0", self._nvme_templates[0])
        loss, grad_sum = self._grads_jit(
            self.params, self.scale_state, jnp.int32(self.global_steps),
            self._train_rng, dev_batch,
        )
        cfg = self.config
        gnorm, finite_dev, factor, lr = self._get_pre_jit()(
            grad_sum, self.scale_state.scale, jnp.int32(self.global_steps))
        if scope is not None:
            jax.block_until_ready((loss, gnorm))
            scope.note_phase("compute", _c0, time.perf_counter())
            _o0 = time.perf_counter()
        speculative = cfg.zero_optimization.offload_optimizer.super_offload
        if speculative:
            # SuperOffload speculative step (reference
            # superoffload_stage3.py:204 rollback design): dispatch every
            # group's update WITHOUT waiting for the overflow verdict — the
            # finite predicate stays a device scalar and gates the writes
            # inside the jitted apply, so an overflowed step writes back
            # unchanged state instead of rolling back a mutated one
            run_walk = True
        else:
            run_walk = bool(finite_dev)

        if run_walk:
            p_leaves = jax.tree_util.tree_leaves(self.params)
            g_leaves = jax.tree_util.tree_leaves(grad_sum)
            new_p_leaves = list(p_leaves)
            groups = self._groups
            prev_write_keys: list = []
            for g, idx in enumerate(groups):
                if g + 1 < len(groups):
                    self._swapper.prefetch_tree(
                        f"opt_g{g + 1}", self._nvme_templates[g + 1])
                state = self._swapper.swap_in_tree(
                    f"opt_g{g}", self._nvme_templates[g])
                pg = tuple(p_leaves[i] for i in idx)
                gg = tuple(g_leaves[i] for i in idx)
                newp, new_state = self._group_apply(g)(
                    pg, state, gg, factor, lr, finite_dev)
                # windowed write pipeline: free group g-1's write buffers
                # before snapshotting group g, so host RAM holds ~one group
                self._swapper.wait_keys(prev_write_keys)
                prev_write_keys = self._swapper.swap_out_tree(
                    f"opt_g{g}",
                    jax.tree_util.tree_map(np.asarray, new_state))
                for j, i in enumerate(idx):
                    new_p_leaves[i] = newp[j]
            self.params = jax.tree_util.tree_unflatten(
                self._param_treedef, new_p_leaves)
            self._swapper.commit()
        if scope is not None:
            # NVMe-walk time (swap-in/apply/swap-out) is host-measured
            jax.block_until_ready(jax.tree_util.tree_leaves(self.params))
            scope.note_phase("optimizer", _o0, time.perf_counter())
        step_scale = self.scale_state.scale  # the scale THIS step ran at
        self.scale_state = precision.update_loss_scale(
            self.scale_state, finite_dev, cfg.fp16)
        metrics = {
            "loss": loss,
            "grad_norm": gnorm,
            "lr": lr,
            "loss_scale": step_scale,
            "skipped": jnp.logical_not(finite_dev),
        }
        self.tput_timer.stop(
            global_step=True,
            exclude=self._step_recompiled() or self._devprof_capturing())
        self._after_step(metrics)
        self.micro_steps += self.gas
        return metrics["loss"]

    # ------------------------------------------------------------------ zenflow
    def _build_zf_hot_fn(self):
        """Jitted per-step ZenFlow tail: unscale+clip, selective hot update,
        cold accumulate, loss-scale bookkeeping (reference
        ``ZenFlowSelectiveAdamW.step`` + the stage-1/2 step prologue)."""
        cfg = self.config
        hyper = self._zf_hyper

        def hot_fn(p_leaves, hot, acc_leaves, g_leaves, scale_state, step, n_acc):
            denom = scale_state.scale * jnp.float32(self.gas)
            grads = [g / denom for g in g_leaves]
            finite = precision.grads_finite(grads)
            gnorm = _global_norm(grads)
            if cfg.gradient_clipping > 0:
                coef = jnp.minimum(1.0, cfg.gradient_clipping / (gnorm + 1e-6))
                grads = [g * coef for g in grads]
            lr = self.lr_schedule(step)
            new_p, new_hot, new_acc = self._zf.hot_step(
                p_leaves, hot, grads, acc_leaves, lr, finite, **hyper)
            new_scale = precision.update_loss_scale(scale_state, finite, cfg.fp16)
            metrics = {
                "grad_norm": gnorm,
                "lr": lr,
                "loss_scale": scale_state.scale,
                "skipped": jnp.logical_not(finite),
            }
            # count only the steps that actually accumulated (overflow steps
            # add nothing — dividing by the raw window length would dilute
            # the cold mean)
            new_n = n_acc + jnp.where(finite, 1, 0).astype(jnp.int32)
            return new_p, new_hot, new_acc, new_scale, metrics, new_n

        return jax.jit(hot_fn, donate_argnums=(0, 1, 2, 3))

    def _build_zf_cold_fn(self):
        """Jitted deferred cold update: the standard windowed sub-group walk
        over host-pinned optimizer state, applied to the accumulated cold
        gradients; hot coordinates are restored afterwards (the selective
        optimizer owns them, reference zenflow split). Dispatched async at the
        interval boundary — XLA overlaps its host<->HBM streams with the next
        steps' compute (the reference's overlap_step worker process)."""
        block = self.config.zero_optimization.zenflow.block

        def cold_fn(p_leaves, opt_groups, acc_leaves, idx_leaves, n_acc, step):
            lr = self.lr_schedule(step)
            # n_acc counts only finite (accumulated) steps; a fully-overflowed
            # window must be a no-op, not an adamw step on zero gradients
            any_acc = n_acc > 0
            n = jnp.maximum(n_acc, 1).astype(jnp.float32)
            g_leaves = [a / n for a in acc_leaves]
            new_p, new_opt = self._offload_group_walk(
                p_leaves, opt_groups, g_leaves, lr, any_acc,
                hot_idx=idx_leaves)
            new_p = [
                self._zf.restore_hot(old, new, hidx, block)
                for old, new, hidx in zip(p_leaves, new_p, idx_leaves)
            ]
            new_acc = [jnp.zeros_like(a) for a in acc_leaves]
            return new_p, new_opt, new_acc

        return jax.jit(cold_fn, donate_argnums=(0, 1, 2))

    def _zf_cold_boundary(self, tdef):
        """Apply the deferred cold update and reset the window counters."""
        if self._zf_cold_jit is None:
            self._zf_cold_jit = self._build_zf_cold_fn()
        p_leaves, _ = jax.tree_util.tree_flatten(self.params)
        idx_leaves = [h["idx"] for h in self._zf_hot["leaves"]]
        new_p, self.opt_state, self._zf_acc = self._zf_cold_jit(
            p_leaves, self.opt_state, self._zf_acc, idx_leaves,
            self._zf_n_dev, jnp.int32(self.global_steps),
        )
        self.params = jax.tree_util.tree_unflatten(tdef, new_p)
        self._zf_n_acc = 0
        self._zf_n_dev = jnp.int32(0)

    def _zf_reset_transients(self):
        """Drop selective state (hot moments/indices, cold accumulator) — on
        checkpoint load the restored trajectory must not inherit them; the
        engine runs dense until the next selection boundary."""
        zf = self.config.zero_optimization.zenflow
        p_leaves = jax.tree_util.tree_leaves(self.params)
        self._zf_hot = self._zf.init_hot_state(p_leaves, zf.topk_ratio, zf.block)
        self._zf_acc = None
        self._zf_n_acc = 0
        self._zf_n_dev = jnp.int32(0)
        self._zf_selected = False

    def _train_batch_zenflow(self, batch: dict):
        """Full ZenFlow step (reference ``zenflow_stage_1_and_2.py`` step
        cadence): dense windowed updates during warm-up; then every step runs
        the tiny hot update while cold gradients accumulate, with one deferred
        windowed update per ``update_interval`` steps and importance
        re-selection per ``select_interval``.

        Note: the selective state (hot moments/indices and the cold
        accumulator) is step-transient and not checkpointed; after a resume
        the engine runs dense until the next selection boundary."""
        zf = self.config.zero_optimization.zenflow
        if self._grads_jit is None:
            self._grads_jit = self._build_grads_fn()
        scope = self.stepscope if self.stepscope.enabled else None
        dev_batch = self._put_gas_batch(batch)
        self.tput_timer.start()
        _c0 = time.perf_counter() if scope is not None else 0.0
        loss, grad_sum = self._grads_jit(
            self.params, self.scale_state, jnp.int32(self.global_steps),
            self._train_rng, dev_batch,
        )
        if scope is not None:
            jax.block_until_ready(loss)
            scope.note_phase("compute", _c0, time.perf_counter())
            _o0 = time.perf_counter()
        g_leaves, _ = jax.tree_util.tree_flatten(grad_sum)
        p_leaves, tdef = jax.tree_util.tree_flatten(self.params)
        step = self.global_steps
        warmup = zf.full_warm_up_rounds
        due = step >= warmup - 1 and (
            not self._zf_selected
            or (step - (warmup - 1)) % zf.select_interval == 0)
        if due and bool(precision.grads_finite(g_leaves)):
            # flush the pending cold window under the OLD selection first —
            # re-selecting with gradients still accumulated would apply them
            # at blocks restore_hot is about to claim (signal silently lost)
            if self._zf_selected and self._zf_n_acc > 0:
                self._zf_cold_boundary(tdef)
                p_leaves, _ = jax.tree_util.tree_flatten(self.params)
            # (re-)select from this step's gradients — |.| ordering is
            # loss-scale invariant; overflow steps keep the old selection
            if self._zf_select_jit is None:
                self._zf_select_jit = jax.jit(
                    lambda gl: self._zf.select(gl, zf.topk_ratio, zf.block))
            new_idx = self._zf_select_jit(g_leaves)
            self._zf_hot = self._zf.reset_moments(self._zf_hot, new_idx)
            self._zf_selected = True

        if step < warmup or not self._zf_selected:
            # dense windowed update (reference full_warm_up_rounds)
            if self._apply_jit is None:
                self._apply_jit = self._build_apply_fn()
            self.params, self.opt_state, self.scale_state, metrics = self._apply_jit(
                self.params, self.opt_state, self.scale_state, grad_sum,
                jnp.float32(self.gas), jnp.int32(step),
            )
        else:
            if self._zf_acc is None:
                grad_ns = jax.tree_util.tree_leaves(self._grad_ns())
                self._zf_acc = [
                    jax.device_put(jnp.zeros(p.shape, jnp.float32), s)
                    for p, s in zip(p_leaves, grad_ns)
                ]
            if self._zf_hot_jit is None:
                self._zf_hot_jit = self._build_zf_hot_fn()
            (new_p_leaves, self._zf_hot, self._zf_acc, self.scale_state,
             metrics, self._zf_n_dev) = self._zf_hot_jit(
                p_leaves, self._zf_hot, self._zf_acc, g_leaves,
                self.scale_state, jnp.int32(step), self._zf_n_dev,
            )
            self.params = jax.tree_util.tree_unflatten(tdef, new_p_leaves)
            self._zf_n_acc += 1
            if self._zf_n_acc >= zf.update_interval:
                self._zf_cold_boundary(tdef)
        metrics["loss"] = loss
        if scope is not None:
            # hot/cold update tail (selection + hot apply + cold flush) is
            # host-measured
            jax.block_until_ready(jax.tree_util.tree_leaves(self.params))
            scope.note_phase("optimizer", _o0, time.perf_counter())
        # same bounded async-dispatch window as the fused path
        self._inflight.append(metrics["loss"])
        if len(self._inflight) > self._max_inflight:
            jax.block_until_ready(self._inflight.pop(0))
        self.tput_timer.stop(
            global_step=True,
            exclude=self._step_recompiled() or self._devprof_capturing())
        self._after_step(metrics)
        self.micro_steps += self.gas
        return metrics["loss"]

    def _build_accum_fn(self):
        def accum_fn(params, acc, scale_state, rng, mb):
            loss, grads = self._microbatch_grads(params, mb, rng, scale_state.scale)
            acc = jax.tree_util.tree_map(jnp.add, acc, grads)
            return loss, acc

        return jax.jit(accum_fn, donate_argnums=(1,))

    def _build_apply_fn(self):
        def apply_fn(params, opt_state, scale_state, acc, n_micro, step):
            return self._update(params, opt_state, scale_state, acc, n_micro, step)

        return jax.jit(apply_fn, donate_argnums=(0, 1, 2, 3))

    def _build_eval_fn(self):
        def eval_fn(params, batch, rng):
            cparams = self._cast_params(params)
            return self.model_spec.loss_fn(cparams, batch, rng)

        return jax.jit(eval_fn)

    # ------------------------------------------------------------------ data prep
    def _batch_sharding(self, ndim: int, leading_gas: bool):
        spec = list(self.plan.batch_spec)
        dims = ([None] if leading_gas else []) + spec
        dims += [None] * (ndim - len(dims))
        return NamedSharding(self.topo.mesh, PartitionSpec(*dims[:ndim]))

    def _put_microbatch(self, batch: dict):
        return {
            k: jax.device_put(np.asarray(v), self._batch_sharding(np.asarray(v).ndim, False))
            for k, v in batch.items()
        }

    def _put_gas_batch(self, batch: dict):
        """[B_global, ...] -> [GAS, micro*dp, ...] placed on the mesh."""
        scope = self.stepscope if self.stepscope.enabled else None
        t0 = time.perf_counter() if scope is not None else 0.0
        out = {}
        gas = self.gas
        for k, v in batch.items():
            v = np.asarray(v)
            if v.shape[0] % gas:
                raise ValueError(
                    f"batch dim {v.shape[0]} not divisible by GAS {gas} for '{k}'"
                )
            v = v.reshape((gas, v.shape[0] // gas) + v.shape[1:])
            out[k] = jax.device_put(v, self._batch_sharding(v.ndim, True))
        if scope is not None:
            # settle the transfers so the h2d phase wall is real (microscope
            # mode: anatomy over async-dispatch overlap)
            jax.block_until_ready(out)
            scope.note_phase("h2d", t0, time.perf_counter())
        return out

    def _next_rng(self):
        self._rng, sub = jax.random.split(self._rng)
        return sub

    # ------------------------------------------------------------------ public API
    def train_batch(self, batch: dict | None = None, data_iter: Iterator | None = None):
        """Fused full step: GAS microbatches + optimizer update in one XLA program
        (reference ``PipelineEngine.train_batch:337`` / engine fwd+bwd+step loop)."""
        scope = self.stepscope if self.stepscope.enabled else None
        if scope is not None:
            scope.begin_step(self.global_steps)
            if self._devprof is not None:
                self._devprof_maybe_begin()
        if batch is None:
            if data_iter is None:
                if self.training_dataloader is None:
                    raise ValueError("train_batch needs a batch, data_iter, or training_data")
                data_iter = self.training_dataloader
            _dw0 = time.perf_counter() if scope is not None else 0.0
            micro = [next(data_iter) for _ in range(self.gas)]
            batch = {k: np.concatenate([np.asarray(m[k]) for m in micro]) for k in micro[0]}
            if scope is not None:
                scope.note_phase("data_wait", _dw0, time.perf_counter())
        if self.config.debug.sanity_checks:
            self._sanity_check_batch(batch)
        if self._sentinel is not None or self._fault_injector.enabled:
            batch = self._sentinel_pre_step(batch)
        self._step_miss0 = (self._jit_miss_count()
                            if self.telemetry.enabled else None)
        self.step_tracer.before_step(self.global_steps)
        if self._offload_mode == "nvme":
            return self._train_batch_nvme(batch)
        if self._zenflow:
            return self._train_batch_zenflow(batch)
        if (self._offload_mode == "cpu" and not self._qgrad
                and (self._opt_host_ok or self._param_offload != "none")):
            # a REAL pinned-host tier (or offloaded params): per-group
            # programs so peak HBM is one group's window (see
            # _train_batch_grouped); the in-jit walk below remains for
            # backends where the host kind is a no-op (CPU test mesh) and
            # for qgZ, whose int8 reduction lives in the fused step program
            return self._train_batch_grouped(batch)
        if self._train_batch_jit is None:
            self._train_batch_jit = self._build_train_batch_fn()
        if self._ltd is not None:
            seq = int(np.asarray(batch["input_ids"]).shape[-1])
            k = self._ltd_keep_for_step(self.global_steps, seq)
            # _ltd_active is read at TRACE time (jit traces on first call),
            # so it must hold this dispatch's bucket; the per-bucket jit
            # cache guarantees a cached program was traced with its own k
            self._ltd_active = k
            fn = self._ltd_jits.get(k)
            if fn is None:
                fn = self._build_train_batch_fn()
                self._ltd_jits[k] = fn
            self._train_batch_jit = fn
        with span("train/stage_batch"):
            dev_batch = self._put_gas_batch(batch)
        self.tput_timer.start()
        _c0 = time.perf_counter() if scope is not None else 0.0
        # 1-bit-family two-phase wire: dense program during the optimizer's
        # variance warmup, compressed program after (reference onebit/adam.py
        # all_reduce -> compressed_allreduce handoff at freeze_step)
        in_dense_phase = (self._qgrad
                          and self.global_steps < self._qgrad_warmup_steps)
        try:
            # the enqueue of the step program: it returns before the device
            # is done (the caller's fetch of the loss settles the step)
            with span("train/dispatch", **flash_walk_args()):
                if in_dense_phase:
                    if self._warm_batch_jit is None:
                        self._warm_batch_jit = self._build_train_batch_fn(
                            use_qgrad=False)
                    self.params, self.opt_state, self.scale_state, metrics = \
                        self._warm_batch_jit(
                            self.params, self.opt_state, self.scale_state,
                            jnp.int32(self.global_steps), self._train_rng,
                            dev_batch,
                        )
                elif self._qgrad:
                    (self.params, self.opt_state, self.scale_state, metrics,
                     self._qgrad_error) = self._train_batch_jit(
                        self.params, self.opt_state, self.scale_state,
                        jnp.int32(self.global_steps), self._train_rng, dev_batch,
                        self._qgrad_error,
                    )
                elif self._sentinel is not None:
                    (self.params, self.opt_state, self.scale_state, metrics,
                     self._sent_state) = self._train_batch_jit(
                        self.params,
                        self.opt_state,
                        self.scale_state,
                        jnp.int32(self.global_steps),
                        self._train_rng,
                        dev_batch,
                        self._sent_state,
                    )
                else:
                    self.params, self.opt_state, self.scale_state, metrics = \
                        self._train_batch_jit(
                            self.params,
                            self.opt_state,
                            self.scale_state,
                            jnp.int32(self.global_steps),
                            self._train_rng,
                            dev_batch,
                        )
        except Exception as e:
            # OOM forensics: a RESOURCE_EXHAUSTED dispatch writes the
            # per-owner crash report BEFORE unwinding (the ledger breakdown
            # at the failure instant is the evidence); the error itself
            # still escalates — training has no degradation ladder
            from deepspeed_tpu.telemetry.memledger import (
                is_resource_exhausted, record_oom)

            if is_resource_exhausted(e) \
                    and not getattr(e, "_oom_recorded", False):
                try:
                    e._oom_recorded = True
                except Exception:
                    pass
                record_oom("train", e, context={
                    "global_steps": self.global_steps,
                    "micro_steps": self.micro_steps,
                    "gas": self.gas,
                })
            raise
        if self._sentinel is not None:
            try:
                if self._watchdog_timeout > 0:
                    # dispatch watchdog: fence THIS step under a deadline.
                    # Settling every step trades away the async pipeline's
                    # overlap (microscope-style, like stepscope) — the
                    # deadline is meaningless against a fence that lags
                    # _max_inflight steps behind the wedge.
                    sentinel_mod.watched_call(
                        lambda: (self._fault_injector.fire(
                            _faults.POINT_TRAIN_DISPATCH),
                            jax.block_until_ready(metrics["loss"])),
                        self._watchdog_timeout)
                elif self._fault_injector.enabled:
                    self._fault_injector.fire(_faults.POINT_TRAIN_DISPATCH)
            except sentinel_mod.TrainingWedgeError as e:
                return self._handle_wedge(e)
        elif self._fault_injector.enabled:
            self._fault_injector.fire(_faults.POINT_TRAIN_DISPATCH)
        # NO per-step device sync here: steps pipeline and Python overhead
        # hides under device compute. _after_step syncs only when a
        # consumer (monitor / steps_per_print / fp16 bookkeeping) needs values.
        # A bounded in-flight window (block on the step from _max_inflight ago)
        # keeps the host from running unboundedly ahead; per-step wall times are
        # only accurate at settle points (steps_per_print / window boundary).
        if scope is not None:
            # microscope mode (stepscope): settle the fused program so the
            # device window is a real host wall — anatomy trades away the
            # async pipeline's overlap, by design
            jax.block_until_ready(metrics["loss"])
            scope.note_phase("compute", _c0, time.perf_counter())
        self._inflight.append(metrics["loss"])
        if len(self._inflight) > self._max_inflight:
            jax.block_until_ready(self._inflight.pop(0))
        self.tput_timer.stop(
            global_step=True,
            exclude=self._step_recompiled() or self._devprof_capturing())
        self._after_step(metrics)
        self.micro_steps += self.gas
        if self._sentinel is not None:
            # AFTER the step counters: a rollback in here restores them from
            # the manifest and must not be clobbered by this step's
            # bookkeeping
            self._sentinel_post_step()
        return metrics["loss"]

    def forward(self, batch: dict):
        """Eval-mode loss (reference ``engine.forward:2675``; jitted, no grads)."""
        if self._eval_jit is None:
            self._eval_jit = self._build_eval_fn()
        t0 = time.perf_counter() if self.telemetry.enabled else 0.0
        out = self._eval_jit(self.params, self._put_microbatch(batch),
                             self._next_rng())
        if t0:
            self.telemetry.emit_span("train/forward",
                                     time.perf_counter() - t0,
                                     step=self.global_steps)
        return out

    eval_batch = forward

    def backward(self, batch: dict):
        """Accumulate gradients for one microbatch (reference ``backward:3066``).

        Returns the (unscaled) loss. Gradients live in a persistent buffer
        sharded per the ZeRO plan until ``step()`` consumes them.
        """
        if (self._offload_mode == "nvme" or self._qgrad or self._zenflow
                or self._grad_overlap
                or self.config.progressive_layer_drop.enabled
                or self._compression is not None):
            raise NotImplementedError(
                "the fwd/bwd/step parity path does not support NVMe-offloaded "
                "optimizer state, quantized gradient reduction, zenflow, "
                "grad_overlap, progressive layer drop, or compression "
                "training; use train_batch()"
            )
        if self.config.debug.sanity_checks:
            micro_total = (self.config.train_batch_size or 0) // self.gas or None
            self._sanity_check_batch(batch, expected=micro_total)
        scope = self.stepscope if self.stepscope.enabled else None
        if self._acc_grads is None:
            # a fresh accumulation cycle = a new "step" for the tracer
            self.step_tracer.before_step(self.global_steps)
            self._step_miss0 = (self._jit_miss_count()
                                if self.telemetry.enabled else None)
            if scope is not None:
                scope.begin_step(self.global_steps)
                if self._devprof is not None:
                    self._devprof_maybe_begin()
        if self._accum_jit is None:
            self._accum_jit = self._build_accum_fn()
        if self._acc_grads is None:
            self._acc_grads = jax.tree_util.tree_map(
                lambda p, s: jax.device_put(jnp.zeros(p.shape, jnp.float32), s),
                self.params,
                self._grad_ns(),
            )
            self._acc_count = 0
        t0 = time.perf_counter() if self.telemetry.enabled else 0.0
        loss, self._acc_grads = self._accum_jit(
            self.params,
            self._acc_grads,
            self.scale_state,
            self._next_rng(),
            self._put_microbatch(batch),
        )
        if scope is not None:
            jax.block_until_ready(loss)
            scope.note_phase("compute", t0, time.perf_counter())
        if t0:
            # host-visible fwd+bwd dispatch time (the reference's fwd/bwd
            # timers are the same host wall clock under async dispatch)
            self.telemetry.emit_span("train/backward",
                                     time.perf_counter() - t0,
                                     step=self.global_steps,
                                     micro_step=self.micro_steps)
        self._acc_count += 1
        self.micro_steps += 1
        return loss

    def is_gradient_accumulation_boundary(self) -> bool:
        """Reference ``engine.py:3116``."""
        return self._acc_count >= self.gas

    def step(self):
        """Apply the accumulated gradients at the GAS boundary
        (reference ``step:3241`` / ``_take_model_step:3168``)."""
        if not self.is_gradient_accumulation_boundary():
            return
        if self._apply_jit is None:
            self._apply_jit = self._build_apply_fn()
        scope = self.stepscope if self.stepscope.enabled else None
        t0 = time.perf_counter() if self.telemetry.enabled else 0.0
        self.params, self.opt_state, self.scale_state, metrics = self._apply_jit(
            self.params,
            self.opt_state,
            self.scale_state,
            self._acc_grads,
            jnp.float32(self._acc_count),
            jnp.int32(self.global_steps),
        )
        if scope is not None:
            jax.block_until_ready(metrics)
            scope.note_phase("optimizer", t0, time.perf_counter())
        if t0:
            self.telemetry.emit_span("train/opt_step",
                                     time.perf_counter() - t0,
                                     step=self.global_steps)
        self._acc_grads = None
        self._acc_count = 0
        self._after_step(metrics)

    def compute_eigenvalue(self, batch: dict):
        """Blockwise Hessian top-eigenvalue probe over one microbatch
        (reference engine ``eigenvalue`` integration at the GAS boundary:
        ``runtime/eigenvalue.py``; feeds quantization/compression schedules)."""
        from deepspeed_tpu.runtime.eigenvalue import Eigenvalue

        e = self.config.eigenvalue
        probe = Eigenvalue(
            verbose=e.verbose, max_iter=e.max_iter, tol=e.tol,
            stability=e.stability,
            gas_boundary_resolution=e.gas_boundary_resolution,
            layer_name=e.layer_name, layer_num=e.layer_num)
        cparams = self._cast_params(self.params)
        return probe.compute_eigenvalue(
            self.model_spec.loss_fn, cparams,
            self._put_microbatch(batch), self._next_rng())

    def _sanity_check_batch(self, batch: dict, expected: int | None = None) -> None:
        """Host-side semantic checks (reference ``enable_sanity_checks`` /
        config cross-validation): catches shape/dtype mistakes before they
        become opaque XLA errors. ``expected`` is the required leading dim
        (defaults to the full train batch)."""
        if expected is None:
            expected = self.config.train_batch_size
        if not isinstance(batch, dict) or not batch:
            raise ValueError("sanity: batch must be a non-empty dict of arrays")
        lead = None
        for k, v in batch.items():
            a = np.asarray(v)
            if a.ndim < 1:
                raise ValueError(f"sanity: batch[{k!r}] must have a batch dim")
            if lead is None:
                lead = a.shape[0]
            elif a.shape[0] != lead:
                raise ValueError(
                    f"sanity: batch[{k!r}] leading dim {a.shape[0]} != {lead}")
        if expected and lead != expected:
            raise ValueError(
                f"sanity: batch size {lead} != expected {expected} "
                f"(configured train_batch_size "
                f"{self.config.train_batch_size}, GAS {self.gas})")
        ids = batch.get("input_ids")
        if ids is not None and not np.issubdtype(np.asarray(ids).dtype, np.integer):
            raise ValueError("sanity: input_ids must be an integer array")

    def _devprof_capturing(self) -> bool:
        return self._devprof is not None and self._devprof.capturing

    def _devprof_maybe_begin(self) -> None:
        """Open a device-capture window when the step hits the interval.

        Called right after ``begin_step`` so the window spans the whole step
        (data wait, h2d, dispatch, settle). The window is closed and parsed
        in ``_after_step``, which every step path funnels through.
        """
        if (not self._devprof.capturing
                and self.global_steps > 0
                and self.global_steps % self._devprof_interval == 0):
            self._devprof.begin(tag="stepscope")

    def _after_step(self, metrics):
        profiled = self._devprof is not None and self._devprof.capturing
        if profiled:
            # close the jax session before end_step so the capture stops at
            # the settled step boundary; parse after end_step so the phase
            # spans exist in the ring for the device-op merge to nest under
            self._devprof.stop()
        if self.stepscope.enabled:
            # close the anatomy window (all paths funnel here); the recompile
            # share comes from the compile-listener delta since begin_step
            self.stepscope.end_step(self.global_steps, profiled=profiled)
        if profiled:
            self._devprof_last = self._devprof.finish(kind="train")
        self.global_steps += 1
        self.global_samples += int(self.config.train_batch_size or 0)
        # accumulate skips on-device (async); synced lazily by .skipped_steps
        self._skip_dev = self._skip_dev + metrics["skipped"].astype(jnp.int32)
        self.lr_scheduler.step()
        self._last_metrics = metrics  # device arrays; fetched on demand
        if self.monitor.enabled or self.telemetry.enabled:
            self._last_metrics = {k: np.asarray(v) for k, v in metrics.items()}
        # fp16 per-step overflow visibility WITHOUT a dedicated device sync:
        # the log reads the skip flag only when a consumer (monitor /
        # telemetry) already paid the host fetch above. Otherwise the async
        # skip counter + the steps_per_print settle report skips in
        # aggregate — fp16 and bf16 steady state both stay fully async.
        if (self.config.fp16.enabled
                and isinstance(self._last_metrics["skipped"], np.ndarray)
                and bool(self._last_metrics["skipped"])):
            log_dist(
                f"step {self.global_steps}: overflow, skipping update "
                f"(loss_scale -> {float(self.scale_state.scale)})",
                ranks=[0],
            )
        if self.telemetry.enabled:
            self._emit_step_telemetry(self._last_metrics)
        if self.monitor.enabled:
            # reference tags (engine.py:3360-3390 _write_monitor)
            events = [
                ("Train/Samples/lr", float(self._last_metrics["lr"]), self.global_samples),
                ("Train/Samples/grad_norm", float(self._last_metrics["grad_norm"]),
                 self.global_samples),
            ]
            if "loss" in self._last_metrics:
                events.append(("Train/Samples/train_loss",
                               float(self._last_metrics["loss"]), self.global_samples))
            if self.config.fp16.enabled:
                events.append(("Train/Samples/loss_scale",
                               float(self._last_metrics["loss_scale"]), self.global_samples))
            self.monitor.write_events(events)
        if self.config.steps_per_print and self.global_steps % self.config.steps_per_print == 0:
            # this float() is the periodic settle point for the async pipeline;
            # it also bounds ThroughputTimer drift (between prints the dispatch
            # queue's backpressure makes host step time track device step time)
            loss = self._last_metrics.get("loss")
            loss_str = f"loss={float(loss):.4f} " if loss is not None else ""
            skips = self.skipped_steps
            skip_str = f"skipped={skips} " if skips else ""
            log_dist(
                f"step={self.global_steps} {loss_str}"
                f"lr={float(self._last_metrics['lr']):.3e} "
                f"grad_norm={float(self._last_metrics['grad_norm']):.3f} {skip_str}",
                ranks=[0],
            )
            if self.stepscope.enabled:
                # symmetric settle point on every host: safe spot for the
                # straggler-skew allgather
                self.stepscope.refresh_skew()
        if self._heartbeat is not None:
            # liveness beacon, written HERE (training thread, step boundary)
            # and never from a helper thread: a wedged dispatch must stop
            # the beat so the elastic agent's staleness poll sees it
            self._heartbeat.beat(self.global_steps)
        self.step_tracer.after_step(self.global_steps - 1)

    # ------------------------------------------------------------------ sentinel
    def _sentinel_pre_step(self, batch):
        """Fingerprint the step's microbatches and consult the train.grads /
        data.batch fault seams (utils/faults.py directive kinds). Returns
        the (possibly poisoned) batch — injection rides a ``__loss_mult__``
        key consumed inside the grad tape (``_microbatch_grads``), so the
        loss AND its gradients blow up together like a real poisoned batch.
        Only called when the sentinel or the fault injector is enabled."""
        gas = self.gas
        lead = int(np.asarray(next(iter(batch.values()))).shape[0])
        if lead % gas == 0:
            # per-microbatch content fingerprints, computed exactly as the
            # quarantining loaders will see the batches (concatenate here /
            # re-split there round-trips the arrays bit-identically)
            fps = []
            for i in range(gas):
                mb = {}
                for k, v in batch.items():
                    v = np.asarray(v)
                    mb[k] = v.reshape(
                        (gas, v.shape[0] // gas) + v.shape[1:])[i]
                fps.append(sentinel_mod.batch_fingerprint(mb))
            self._last_batch_fps = fps
        inj = self._fault_injector
        if not inj.enabled:
            return batch
        directive = inj.fire(_faults.POINT_TRAIN_GRADS)
        if directive is None:
            for fp in self._last_batch_fps:
                directive = inj.fire(_faults.POINT_DATA_BATCH,
                                     request_id=fp)
                if directive is not None:
                    break
        if directive is None:
            return batch
        mult = (float("nan") if directive == "nan-grads"
                else sentinel_mod.SPIKE_LOSS_MULT)
        log_dist(f"fault injection: {directive} directive at step "
                 f"{self.global_steps} (loss x {mult})", ranks=[0])
        batch = dict(batch)
        batch["__loss_mult__"] = np.full((lead,), mult, np.float32)
        return batch

    def _sentinel_post_step(self):
        """The policy half of the sentinel: settle this step's verdict and
        walk the escalation ladder. This read is the ONE documented host
        sync the enabled sentinel adds per step — detection itself ran
        inside the fused program."""
        pol = self._sentinel
        cfg = self.config.sentinel
        m = self._last_metrics
        if not bool(m["anomalous"]):
            pol.tick()
            return
        reason = int(m["anomaly_reason"])
        streak = int(m["skip_streak"])
        if (self.config.fp16.enabled
                and reason == sentinel_mod.REASON_NONFINITE
                and streak < cfg.max_consecutive_skips):
            # a routine fp16 overflow is the loss scaler's business, not
            # the ladder's — only a skip STREAK the scaler fails to adapt
            # away (or a spike, or nonfinite grads without dynamic scaling)
            # counts as a strike
            pol.tick()
            return
        names = sentinel_mod.reason_names(reason)
        fps = list(self._last_batch_fps)
        if self.telemetry.enabled:
            ctr = self.telemetry.counter(
                "sentinel_anomalies_total",
                "anomalous training steps flagged by the sentinel")
            for n in names:
                ctr.inc(reason=n)
        tag = None
        ckpt_dir = self._sentinel_ckpt_dir()
        if ckpt_dir:
            from deepspeed_tpu.checkpoint.engine import latest_tag

            tag = latest_tag(ckpt_dir)
        action = pol.observe(reason, fps, latest_tag=tag)
        self._apply_quarantine_to_loader()
        ctx = {
            "global_steps": self.global_steps,
            "micro_steps": self.micro_steps,
            "reason": names,
            "skip_streak": streak,
            "loss": float(m["loss"]),
            "grad_norm": float(m["grad_norm"]),
            "fingerprints": fps,
            "quarantined": list(pol.quarantined),
            "strikes_in_window": pol.strikes_in_window,
            "action": action,
        }
        log_dist(
            f"sentinel: anomalous step {self.global_steps - 1} "
            f"({'+'.join(names)}; update skipped) -> {action}", ranks=[0])
        path = sentinel_mod.write_forensics(
            cfg.report_dir, action.replace("-", "_"), ctx)
        if action == "rollback":
            self._sentinel_rollback(ctx)
        elif action == "reduce-lr":
            self._sentinel_lr_backoff()
        elif action == "halt":
            raise sentinel_mod.DivergenceHaltError(
                f"sentinel: third strike at step {self.global_steps - 1} "
                f"({'+'.join(names)}) — halting per "
                "sentinel.on_third_strike='halt'", report=path)

    def _sentinel_ckpt_dir(self) -> str | None:
        return self.config.sentinel.checkpoint_dir or self._last_save_dir

    def _apply_quarantine_to_loader(self) -> None:
        dl = self.training_dataloader
        pol = self._sentinel
        if (pol is not None and pol.quarantined and dl is not None
                and hasattr(dl, "quarantine")):
            dl.quarantine(pol.quarantined)

    def _sentinel_rollback(self, ctx: dict) -> None:
        """Restore the tag pinned at strike 1 (pre-anomaly — a later save
        would bake in the batch-stream misalignment the skipped step caused)
        and replay; the loaders skip the quarantined batches, so the
        stitched trajectory matches a clean run that never saw them."""
        pol = self._sentinel
        cfg = self.config.sentinel
        ckpt_dir = self._sentinel_ckpt_dir()
        tag = pol.rollback_tag
        if not ckpt_dir or tag is None:
            path = sentinel_mod.write_forensics(cfg.report_dir, "halt", {
                **ctx, "error": "rollback requested but no checkpoint "
                "is available"})
            raise sentinel_mod.DivergenceHaltError(
                "sentinel: rollback requested but no verified checkpoint is "
                "available (set sentinel.checkpoint_dir or save one first)",
                report=path)
        log_dist(f"sentinel: rolling back to checkpoint {tag!r}; replaying "
                 "with quarantined batches skipped", ranks=[0])
        t0 = time.perf_counter()
        self.load_checkpoint(ckpt_dir, tag=tag)
        dur = time.perf_counter() - t0
        pol.rollbacks += 1
        self.train_rollbacks += 1
        if self.telemetry.enabled:
            self.telemetry.counter(
                "train_rollbacks_total",
                "sentinel rollback-and-replay restores").inc()
        if self.stepscope.enabled:
            # goodput ledger: healing time is overhead, attributed to its
            # own category (the load also appears under "checkpoint")
            self.stepscope.note_overhead("rollback", dur)

    def _sentinel_lr_backoff(self) -> None:
        pol = self._sentinel
        cfg = self.config.sentinel
        self._lr_scale *= float(cfg.lr_backoff)
        pol.lr_backoffs += 1
        # the scale folds in at trace time: rebuild the step programs
        self._train_batch_jit = None
        self._warm_batch_jit = None
        self._ltd_jits = {}
        if self.telemetry.enabled:
            self.telemetry.counter(
                "sentinel_lr_backoffs_total",
                "sentinel third-strike LR reductions").inc()
        log_dist(f"sentinel: third strike -> lr backoff x{cfg.lr_backoff:g} "
                 f"(cumulative scale {self._lr_scale:g})", ranks=[0])

    def _handle_wedge(self, err):
        """Dispatch-fence timeout: the step may never settle, so none of its
        results can be trusted or waited on. Record forensics, abandon the
        in-flight window, and roll back; halt when the window's wedge budget
        or the checkpoint supply is exhausted."""
        pol = self._sentinel
        cfg = self.config.sentinel
        if self.telemetry.enabled:
            self.telemetry.counter(
                "train_wedge_timeouts_total",
                "training dispatch fences past the watchdog deadline").inc()
        action = pol.observe_wedge()
        ckpt_dir = self._sentinel_ckpt_dir()
        tag = pol.rollback_tag
        if ckpt_dir and tag is None:
            from deepspeed_tpu.checkpoint.engine import latest_tag

            tag = latest_tag(ckpt_dir)
        ctx = {
            "global_steps": self.global_steps,
            "micro_steps": self.micro_steps,
            "reason": ["wedge"],
            "timeout_s": self._watchdog_timeout,
            "error": str(err),
            "action": action,
        }
        path = sentinel_mod.write_forensics(cfg.report_dir, "wedge", ctx)
        log_dist(f"sentinel: {err} -> {action}", ranks=[0])
        if action == "rollback" and ckpt_dir and tag is not None:
            self._inflight = []  # the wedged futures must never be awaited
            pol.rollback_tag = tag
            self._sentinel_rollback(ctx)
            return float("nan")  # the wedged step's loss is unknowable
        raise sentinel_mod.DivergenceHaltError(
            f"sentinel: training dispatch wedged past "
            f"{self._watchdog_timeout:g}s and no rollback is available "
            f"(action {action!r})", report=path) from err

    def _emit_step_telemetry(self, vals: dict) -> None:
        """Per-step span + gauges + HBM watermark (telemetry enabled only).

        ``vals`` are host numpy scalars (the conversion is this path's settle
        point — same cost the monitor path already pays). The span duration is
        the fused-path host wall clock from ThroughputTimer; the fwd/bwd/step
        parity path falls back to the inter-step delta.
        """
        tel = self.telemetry
        now = time.perf_counter()
        dur = self.tput_timer.last_duration or (
            now - self._prev_step_wall if self._prev_step_wall else 0.0)
        self._prev_step_wall = now
        step = self.global_steps
        skipped = bool(vals["skipped"])
        attrs = {
            "lr": float(vals["lr"]),
            "grad_norm": float(vals["grad_norm"]),
            "skipped": skipped,
        }
        if "loss" in vals:
            attrs["loss"] = float(vals["loss"])
        if "loss_scale" in vals:
            attrs["loss_scale"] = float(vals["loss_scale"])
        tel.emit_span("train/step", dur, step=step, **attrs)
        tel.counter("train_steps_total", "optimizer steps taken").inc()
        tel.counter("train_samples_total", "samples consumed").inc(
            int(self.config.train_batch_size or 0))
        g = tel.gauge
        g("train_loss", "last step loss").set(attrs.get("loss", 0.0))
        g("train_grad_norm", "last step global grad norm").set(attrs["grad_norm"])
        g("train_lr", "last step learning rate").set(attrs["lr"])
        g("train_samples_per_second", "throughput").set(
            self.tput_timer.throughput())
        if self.tput_timer.flops_per_sample:
            g("train_tflops", "achieved TFLOPS").set(self.tput_timer.tflops())
        if "loss_scale" in attrs:
            g("train_loss_scale", "dynamic loss scale").set(attrs["loss_scale"])
        if skipped:
            tel.counter("train_overflow_steps_total",
                        "fp16 overflow-skipped steps").inc()
            tel.event("train/overflow", step=step,
                      loss_scale=attrs.get("loss_scale"))
        self._register_memory_owners(tel)
        tel.sample_memory(step=step)

    def _register_memory_owners(self, tel) -> None:
        """Attribute params/optimizer/grad-buffer bytes to the memory
        ledger. Lazy (first telemetry-enabled step) because telemetry is
        often configured after engine construction; re-registration is a
        no-op via the handle cache."""
        led = tel.memledger
        if led is None or getattr(self, "_memledger_handles", None):
            return
        h = {"params": led.register("params", "engine/model_params",
                                    self.params)}
        if self.opt_state is not None:
            h["optimizer_shards"] = led.register(
                "optimizer_shards", "engine/opt_state", self.opt_state)
        self._memledger_handles = h
        import weakref

        ref = weakref.ref(self)

        def _grad_bytes():
            eng = ref()
            if eng is None:
                return None
            from deepspeed_tpu.telemetry.memledger import tree_nbytes

            total = 0
            for acc in (getattr(eng, "_acc_grads", None),
                        getattr(eng, "_zf_acc", None)):
                if acc is not None:
                    total += tree_nbytes(acc)
            return total

        led.register_provider("grads", "engine/grad_accum", _grad_bytes)

    # ------------------------------------------------------------------ checkpoint
    def _rng_state_dict(self) -> dict:
        """Host-serializable snapshot of the engine's RNG streams so a resume
        replays the identical trajectory (``_rng`` feeds eval/forward draws;
        ``_train_rng`` is folded by step inside the jitted step but is saved
        for completeness)."""
        def key_bits(k):
            try:
                return np.asarray(k)
            except TypeError:  # typed PRNG key arrays
                return np.asarray(jax.random.key_data(k))
        return {"_rng": key_bits(self._rng).tolist(),
                "_train_rng": key_bits(self._train_rng).tolist()}

    def _load_rng_state(self, state: dict | None) -> None:
        if not state:
            return
        if "_rng" in state:
            self._rng = jnp.asarray(np.asarray(state["_rng"], np.uint32))
        if "_train_rng" in state:
            self._train_rng = jnp.asarray(
                np.asarray(state["_train_rng"], np.uint32))

    def _manifest_extra(self) -> dict:
        """Extra manifest rows contributed by engine subclasses (the staged
        pipeline records its partition + fragment layout here)."""
        return {}

    def _collect_ckpt_payloads(self, stage_dir: str) -> list:
        """Host-snapshot every sharded payload this engine persists.

        Returns ``[(name, part, (payload, index)), ...]`` where ``part`` is
        the fragment-file suffix (empty for the single-program engine,
        ``_s{v}`` per virtual stage for the pipeline). ``flush`` writes each
        as ``{name}_shard_p{proc}{part}.npz`` and finalizes one index per
        unique ``name``."""
        import os

        from deepspeed_tpu.checkpoint import sharded

        payloads = [("model", "",
                     sharded.collect_fragments(self.params, "model"))]
        if self._offload_mode == "nvme":
            # state lives on disk between steps; stream it GROUP BY GROUP into
            # per-group fragment files so host RAM never holds the full
            # optimizer state (a [None]*g placeholder list reproduces the
            # grouped-save key layout; the index's per-fragment file names
            # point the loader at the right group file)
            import jax as _jax

            os.makedirs(stage_dir, exist_ok=True)
            index: dict = {}
            for g, t in enumerate(self._nvme_templates):
                state = self._swapper.swap_in_tree(f"opt_g{g}", t)
                p, ix = sharded.collect_fragments(
                    [None] * g + [state], f"optimizer_g{g}")
                np.savez(os.path.join(
                    stage_dir,
                    f"optimizer_g{g}_shard_p{_jax.process_index()}.npz"), **p)
                index.update(ix)
                del state, p
            payloads.append(("optimizer", "", ({}, index)))
        else:
            payloads.append(("optimizer", "", sharded.collect_fragments(
                self.opt_state, "optimizer")))
        return payloads

    def _restore_sharded_model(self, ckpt_dir: str) -> None:
        from deepspeed_tpu.checkpoint import sharded

        self.params = sharded.load_sharded(self.params, ckpt_dir, "model")

    def _restore_sharded_optimizer(self, ckpt_dir: str) -> None:
        from deepspeed_tpu.checkpoint import sharded

        self.opt_state = sharded.load_sharded(
            self.opt_state, ckpt_dir, "optimizer")

    def save_checkpoint(self, save_dir: str, tag: str | None = None,
                        client_state: dict | None = None, save_latest: bool = True):
        """Reference ``engine.py:4557 save_checkpoint``: tagged dir + manifest +
        per-process sharded model/optimizer fragment files + ``latest``.

        Every process writes only its own unique (replica-0) shards — the
        reference's per-rank ``zero_pp_rank_*`` files, in universal-fragment
        form (``ds_to_universal.py``) so any mesh can load them. With
        ``checkpoint.async_save`` the host snapshot happens here (the double
        buffer) and the disk flush runs on a writer thread.

        Crash safety is a two-phase commit (checkpoint/engine.py): all files
        land in ``{save_dir}/.tmp-{tag}/``, get fsynced and checksummed into
        the manifest, and one ``os.replace`` promotes the directory before
        the ``latest`` pointer moves — a kill at any instruction leaves the
        previous checkpoint intact and loadable."""
        import os
        import threading

        from deepspeed_tpu.checkpoint import engine as ckpt
        from deepspeed_tpu.checkpoint import sharded
        inj = _faults.get_fault_injector()
        ckpt_t0 = time.perf_counter()
        tag = tag or f"global_step{self.global_steps}"
        self._last_save_dir = save_dir  # sentinel rollback target default
        stage_dir = ckpt.staging_dir(save_dir, str(tag))
        manifest = {
            "tag": tag,
            "framework_version": __import__("deepspeed_tpu").__version__,
            "model_name": self.model_spec.name,
            "zero_stage": self.zero_stage,
            "global_steps": self.global_steps,
            "global_samples": self.global_samples,
            "micro_steps": self.micro_steps,
            "skipped_steps": self.skipped_steps,
            "loss_scale": float(self.scale_state.scale),
            "scale_state": {k: float(v) for k, v in self.scale_state._asdict().items()},
            "lr_scheduler": self.lr_scheduler.state_dict(),
            "rng_state": self._rng_state_dict(),
            "dataloader_state": (
                self.training_dataloader.state_dict()
                if hasattr(self.training_dataloader, "state_dict") else None),
            "world_size": self.topo.world_size,
            "mesh": dict(self.topo.sizes),
            "config": self.config.to_dict(),
            "client_state": client_state or {},
        }
        manifest.update(self._manifest_extra())
        # snapshot to host now (double buffer); flush sync or on writer thread
        inj.fire(_faults.POINT_CKPT_COLLECT)
        payloads = self._collect_ckpt_payloads(stage_dir)

        # the host double buffer is real memory for the collect→flush window:
        # attribute it to the ledger so an OOM during an async save shows the
        # snapshot bytes instead of an unattributed spike
        led = self.telemetry.memledger
        stage_handle = None
        if led is not None:
            from deepspeed_tpu.telemetry.memledger import tree_nbytes

            stage_handle = led.register(
                "staging_buffers", f"ckpt/{tag}/host_snapshot",
                sum(tree_nbytes(p[0]) for _, _, p in payloads))

        def flush():
            import jax as _jax

            try:
                # phase 1 (prepare): everything goes into the staging dir
                inj.fire(_faults.POINT_CKPT_FLUSH)
                for name, part, payload in payloads:
                    sharded.write_fragments(stage_dir, name, *payload,
                                            part=part)
                    inj.fire(_faults.POINT_CKPT_FLUSH, path=os.path.join(
                        stage_dir,
                        f"{name}_shard_p{_jax.process_index()}{part}.npz"))
                dist.barrier("save_checkpoint")
                if _jax.process_index() == 0:
                    for name in dict.fromkeys(n for n, _, _ in payloads):
                        sharded.finalize_index(stage_dir, name)
                    # phase 2 (commit): checksum + manifest + atomic promote
                    ckpt_dir = ckpt.commit_checkpoint(
                        save_dir, str(tag), manifest)
                    if save_latest:
                        ckpt.write_latest(save_dir, str(tag))
                    ckpt.rotate_checkpoints(
                        save_dir, self.config.checkpoint.keep_n_latest,
                        protect=str(tag))
                    log_dist(f"saved checkpoint {ckpt_dir}", ranks=[0])
            finally:
                if stage_handle is not None:
                    led.release(stage_handle)

        self._join_ckpt_writer()
        import jax as _jax

        # async flush only off the main thread when the barrier is a no-op
        # (single process): a collective barrier on a writer thread could
        # interleave with training collectives on multi-host
        if self.config.checkpoint.async_save and _jax.process_count() == 1:
            def flush_capturing():
                try:
                    flush()
                except BaseException as e:  # surfaced on the next join
                    self._ckpt_writer_error = e

            # non-daemon: interpreter exit waits for the flush, so the last
            # checkpoint of a run cannot be silently lost
            self._ckpt_writer = threading.Thread(target=flush_capturing)
            self._ckpt_writer.start()
        else:
            flush()
        if self.telemetry.enabled:
            # async saves report the dispatch (snapshot) cost — the training
            # stall they actually cause — not the background flush
            dur = time.perf_counter() - ckpt_t0
            self.telemetry.emit_span(
                "checkpoint/save", dur, step=self.global_steps, tag=str(tag),
                async_flush=bool(self.config.checkpoint.async_save))
            self.telemetry.gauge(
                "checkpoint_last_save_seconds",
                "wall clock of the last checkpoint save").set(dur)
            self.telemetry.counter(
                "checkpoint_saves_total", "checkpoints written").inc()
            if self.stepscope.enabled:
                self.stepscope.note_overhead("checkpoint", dur)
        return os.path.join(save_dir, str(tag))

    def _join_ckpt_writer(self):
        """Wait for an in-flight async checkpoint flush; raises its error."""
        w = getattr(self, "_ckpt_writer", None)
        if w is not None:
            w.join()
            self._ckpt_writer = None
        err = getattr(self, "_ckpt_writer_error", None)
        if err is not None:
            self._ckpt_writer_error = None
            raise RuntimeError("async checkpoint flush failed") from err

    def _resolve_verified_checkpoint(self, load_dir: str, tag: str | None,
                                     verify: bool = True):
        """Pick the checkpoint to load: the requested/``latest`` tag if it
        verifies, else walk the fallback ladder — every other committed tag,
        newest first by the step parsed from the tag — to the newest one
        that does. Returns ``(tag, ckpt_dir, manifest)``; ``(None, None,
        None)`` when the directory holds no checkpoints at all; raises
        :class:`~deepspeed_tpu.checkpoint.engine.CheckpointCorruptError`
        (stage=``exhausted``) when candidates exist but none survives
        verification."""
        import os

        from deepspeed_tpu.checkpoint import engine as ckpt
        from deepspeed_tpu.checkpoint import serialization as ser

        requested = tag or ckpt.latest_tag(load_dir)
        candidates = list(ckpt.list_tags(load_dir))
        if requested is not None and requested not in candidates:
            candidates.insert(0, requested)
        elif requested is not None:
            candidates.remove(requested)
            candidates.insert(0, requested)
        if not candidates:
            return None, None, None
        inj = _faults.get_fault_injector()
        tel = self.telemetry
        fallbacks = 0
        for cand in candidates:
            cdir = os.path.join(load_dir, str(cand))
            if inj.enabled and os.path.isdir(cdir):
                # hand the file-mutating fault kinds (truncate/corrupt-bytes)
                # the candidate's biggest payload file: bit-rot discovered at
                # read time, which verification must catch and ladder past
                files = [os.path.join(cdir, f) for f in os.listdir(cdir)
                         if f != "manifest.json"]
                files = [f for f in files if os.path.isfile(f)]
                if files:
                    inj.fire(_faults.POINT_CKPT_LOAD,
                             path=max(files, key=os.path.getsize))
            v0 = time.perf_counter()
            try:
                if verify:
                    manifest = ckpt.verify_checkpoint(cdir)
                else:
                    manifest = ser.load_json(
                        os.path.join(cdir, ckpt.MANIFEST))
            except (ckpt.CheckpointCorruptError, OSError, ValueError) as e:
                stage = getattr(e, "stage", "manifest-unreadable")
                log_dist(
                    f"checkpoint {cand} failed verification "
                    f"({stage}): {e}; walking back", ranks=[0])
                if tel.enabled:
                    tel.counter(
                        "checkpoint_corrupt_total",
                        "checkpoint integrity failures, by verification "
                        "stage").inc(stage=stage)
                fallbacks += 1
                continue
            finally:
                if tel.enabled:
                    tel.histogram(
                        "checkpoint_verify_seconds",
                        "wall clock of checkpoint verification").observe(
                            time.perf_counter() - v0)
            if fallbacks and tel.enabled:
                tel.counter(
                    "checkpoint_fallback_total",
                    "loads that fell back past a corrupt checkpoint",
                ).inc(fallbacks)
            return str(cand), cdir, manifest
        if tel.enabled:
            tel.counter(
                "checkpoint_corrupt_total",
                "checkpoint integrity failures, by verification stage",
            ).inc(stage="exhausted")
        raise ckpt.CheckpointCorruptError(
            f"no verifiable checkpoint under {load_dir} "
            f"(tried {len(candidates)}: {candidates[:8]})",
            stage="exhausted", tag=str(requested or ""))

    def load_checkpoint(self, load_dir: str, tag: str | None = None,
                        load_optimizer_states: bool = True,
                        load_lr_scheduler_states: bool = True,
                        verify: bool = True):
        """Reference ``engine.py:4079 load_checkpoint``. Arrays are re-placed
        under the *current* sharding plan, so loading across a different mesh /
        ZeRO stage / world size is automatic (UCP semantics).

        Every candidate is checksum-verified (commit marker, per-file
        sha256, fragment coverage) BEFORE any engine state is touched; on
        corruption the loader walks the tag ladder back to the newest
        verifiable checkpoint and only raises when none survives."""
        import os

        from deepspeed_tpu.checkpoint import engine as ckpt
        from deepspeed_tpu.checkpoint import serialization as ser

        from deepspeed_tpu.checkpoint import sharded
        ckpt_t0 = time.perf_counter()
        self._join_ckpt_writer()
        _faults.get_fault_injector().fire(_faults.POINT_CKPT_LOAD)
        tag, ckpt_dir, manifest = self._resolve_verified_checkpoint(
            load_dir, tag, verify=verify)
        if tag is None:
            log_dist(f"no checkpoint found under {load_dir}", ranks=[0])
            return None, {}

        if sharded.is_sharded(ckpt_dir, "model"):
            # assemble only this process's target shards from the fragments
            self._restore_sharded_model(ckpt_dir)
            if load_optimizer_states and sharded.is_sharded(ckpt_dir, "optimizer"):
                try:
                    if self._offload_mode == "nvme":
                        # stream back group by group: one group in host RAM
                        for g, t in enumerate(self._nvme_templates):
                            template = [None] * g + [jax.tree_util.tree_map(
                                lambda l: np.zeros(tuple(l.shape), l.dtype), t)]
                            state = sharded.load_sharded(
                                template, ckpt_dir, "optimizer")[g]
                            self._swapper.wait_keys(
                                self._swapper.swap_out_tree(f"opt_g{g}", state))
                        self._swapper.commit()
                    else:
                        self._restore_sharded_optimizer(ckpt_dir)
                except KeyError as e:
                    raise ValueError(
                        "optimizer checkpoint layout does not match this "
                        "engine's offload configuration (offloaded optimizer "
                        "state is stored in sub-groups). Load with the same "
                        "offload_optimizer/sub_group_size settings it was "
                        "saved under, or pass load_optimizer_states=False"
                    ) from e
                scale_kw = manifest.get("scale_state")
                if scale_kw:
                    self.scale_state = LossScaleState(
                        scale=jnp.float32(scale_kw["scale"]),
                        good_steps=jnp.int32(scale_kw["good_steps"]),
                        hysteresis=jnp.int32(scale_kw["hysteresis"]),
                        dynamic=jnp.asarray(bool(scale_kw["dynamic"])),
                    )
        else:
            # legacy single-file universal layout
            if self._offload_mode is not None and load_optimizer_states:
                raise ValueError(
                    "legacy-format checkpoints cannot restore optimizer state "
                    "into an offloaded (sub-grouped) engine; pass "
                    "load_optimizer_states=False or load without offload"
                )
            engine_io = ckpt.CheckpointEngine()
            names = ["model"] + (["optimizer"] if load_optimizer_states else [])
            state = engine_io.load(ckpt_dir, names)

            params_host = ser.arrays_to_tree(
                jax.tree_util.tree_map(np.asarray, self.params), state["model"]
            )
            self.params = jax.device_put(
                params_host,
                self._param_storage if self._param_storage is not None
                else self.plan.param_shardings)
            if load_optimizer_states and "optimizer" in state:
                opt_arrays = {k: v for k, v in state["optimizer"].items()
                              if not k.startswith("__scale__")}
                opt_host = ser.arrays_to_tree(
                    jax.tree_util.tree_map(np.asarray, self.opt_state), opt_arrays
                )
                self.opt_state = jax.device_put(opt_host, self._opt_shardings)
                scale_kw = {k[len("__scale__"):]: jnp.asarray(v)
                            for k, v in state["optimizer"].items()
                            if k.startswith("__scale__")}
                if scale_kw:
                    self.scale_state = LossScaleState(**scale_kw)
        self.global_steps = int(manifest["global_steps"])
        self.global_samples = int(manifest["global_samples"])
        self.micro_steps = int(manifest["micro_steps"])
        self.skipped_steps = int(manifest["skipped_steps"])
        if load_lr_scheduler_states:
            self.lr_scheduler.load_state_dict(manifest["lr_scheduler"])
        # exact resume: restore the host RNG streams and the data-iterator
        # position so the resumed run replays the identical loss trajectory
        self._load_rng_state(manifest.get("rng_state"))
        dl_state = manifest.get("dataloader_state")
        if dl_state is not None and hasattr(self.training_dataloader,
                                            "load_state_dict"):
            self.training_dataloader.load_state_dict(dl_state)
        if self._zenflow:
            self._zf_reset_transients()
        if self._sentinel is not None:
            # the rolling stats describe a trajectory position that no
            # longer exists: restart them at the restored step, and re-skip
            # the quarantined batches on the freshly positioned loader (the
            # manifest predates the quarantine)
            self._sent_state = sentinel_mod.init_state(self.config.sentinel)
            self._apply_quarantine_to_loader()
        log_dist(
            f"loaded checkpoint {ckpt_dir} (saved at world_size="
            f"{manifest['world_size']}, now {self.topo.world_size})",
            ranks=[0],
        )
        if self.telemetry.enabled:
            dur = time.perf_counter() - ckpt_t0
            self.telemetry.emit_span(
                "checkpoint/load", dur, step=self.global_steps, tag=str(tag))
            self.telemetry.gauge(
                "checkpoint_last_load_seconds",
                "wall clock of the last checkpoint load").set(dur)
            if self.stepscope.enabled:
                self.stepscope.note_overhead("checkpoint", dur)
        return ckpt_dir, manifest.get("client_state", {})

    # ------------------------------------------------------------------ accessors
    @property
    def skipped_steps(self) -> int:
        """Total overflow-skipped steps (syncs the async device accumulator)."""
        return self._skip_base + int(self._skip_dev)

    @skipped_steps.setter
    def skipped_steps(self, value: int):
        self._skip_base = int(value)
        self._skip_dev = jnp.int32(0)

    @property
    def loss_scale(self) -> float:
        return float(self.scale_state.scale)

    def get_lr(self):
        return [float(self.lr_schedule(jnp.int32(max(0, self.global_steps - 1))))]

    def get_global_grad_norm(self) -> float:
        gn = self._last_metrics.get("grad_norm")
        return float(gn) if gn is not None else 0.0

    @property
    def train_batch_size(self) -> int:
        return int(self.config.train_batch_size)

    def module_state(self):
        return self.params

    def monitor_memory(self):
        from deepspeed_tpu.accelerator.real_accelerator import get_accelerator

        return get_accelerator().memory_stats()

    # ------------------------------------------------------------------ teardown
    def destroy(self) -> None:
        """Engine teardown (reference ``engine.destroy``): stop the trace
        capture (so an in-window run still lands its profile on disk), join
        any async checkpoint flush, and flush/close monitor + telemetry
        sinks. Idempotent; the StepTracer's own ``atexit`` hook covers
        callers that never get here."""
        if getattr(self, "_destroyed", False):
            return
        self._destroyed = True
        self.step_tracer.close()
        try:
            self._join_ckpt_writer()
        except RuntimeError:
            raise
        finally:
            self.monitor.close()
            if self.telemetry.enabled:
                self.telemetry.flush()


def initialize(
    model: ModelSpec | Callable[[ShardCtx], ModelSpec] | None = None,
    config: Config | dict | str | None = None,
    training_data: Iterator | None = None,
    mesh_devices: list | None = None,
    seed: int | None = None,
    initial_params: Any = None,
    **_ignored,
):
    """Build the engine (reference ``deepspeed.initialize`` ``__init__.py:93``).

    Returns ``(engine, optimizer, training_dataloader, lr_scheduler)``.
    """
    if model is None:
        raise ValueError("initialize() requires a model (ModelSpec or builder callable)")
    cfg = load_config(config)
    mics = cfg.zero_optimization.mics_shard_size
    if mics > 0:
        # MiCS (reference mics.py:63): shard degree = group size k < world.
        # Derive the mesh split — fsdp=k intra-group, data=world/k replica
        # groups — instead of making the user hand-shape the mesh.
        from deepspeed_tpu.config.config import ConfigError

        if cfg.mesh.is_explicit and cfg.mesh.fsdp not in (-1, 1, mics):
            raise ConfigError(
                f"mesh.fsdp={cfg.mesh.fsdp} contradicts "
                f"zero_optimization.mics_shard_size={mics}; drop one")
        for ax in ("tensor", "sequence", "expert", "pipeline"):
            if getattr(cfg.mesh, ax) > 1:
                raise ConfigError(
                    f"mics_shard_size derives a data x fsdp mesh; it does "
                    f"not compose with an explicit {ax} axis yet")
        cfg.mesh.fsdp = mics
        cfg.mesh.data = -1  # world / k replica groups
    if topology_initialized():
        topo = get_topology()
        # an EXPLICIT mesh request that contradicts the live topology must
        # not be silently ignored (e.g. an inference engine built a pure-DP
        # mesh earlier in the process): rebuild on the requested shape. An
        # implicit (default) mesh honors whatever topology the user built.
        wanted = {a: getattr(cfg.mesh, a)
                  for a in ("data", "fsdp", "tensor", "sequence", "expert",
                            "pipeline")}
        mismatch = [a for a, v in wanted.items()
                    if v not in (-1, topo.size(a))]
        if mismatch and cfg.mesh.is_explicit:
            from deepspeed_tpu.comm.topology import reset_topology

            log_dist(
                f"mesh config requests {wanted} but the process topology is "
                f"{dict(topo.sizes)}; rebuilding the mesh", ranks=[0])
            reset_topology()
            topo = dist.init_distributed(cfg.mesh, devices=mesh_devices)
    else:
        topo = dist.init_distributed(cfg.mesh, devices=mesh_devices)
    cfg.resolve_batch_sizes(topo.dp_world_size)
    dist.configure(cfg.comms_logger)
    # state placement and sharding: once a process
    with phase("train/init", zero_stage=cfg.zero_optimization.stage):
        if cfg.pipeline.stages > 1:
            # the staged MPMD runtime: per-stage programs + schedule executor
            # (stages in (0, 1) keep the single fused program — bit-identical)
            from deepspeed_tpu.runtime.pipe.engine import PipeEngine

            engine = PipeEngine(model, cfg, topo, training_data=training_data,
                                seed=seed, initial_params=initial_params)
        else:
            engine = Engine(model, cfg, topo, training_data=training_data,
                            seed=seed, initial_params=initial_params)
    return engine, engine.optimizer, engine.training_dataloader, engine.lr_scheduler
