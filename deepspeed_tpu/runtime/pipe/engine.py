"""PipeEngine: MPMD staged training on top of the single-program Engine.

The execution model (arxiv 2412.14374, MPMD pipeline parallelism): the
scanned layer stack is split into S contiguous stage programs, each stage
owns its param slice + optimizer shard, and a thread per stage walks a
deterministic GPipe/1F1B instruction list, exchanging activations and
activation-cotangents over the transport seam. Nothing about the math
changes versus the fused single-program step — the parity gate in
``tests/unit/test_pipe.py`` holds the 2-stage loss trajectory to the
baseline step-for-step — only WHERE each piece runs:

- forward: stage v runs ``block_fn`` over its layer slice (stage 0 embeds
  first, the last stage adds final-norm + head + loss);
- backward: the last stage fuses F+B per microbatch
  (``value_and_grad`` over (params, input)); inner stages stash their
  INPUT activation and recompute through ``jax.vjp`` when the cotangent
  arrives (the P-deep-stash discipline of ``parallel/pipeline_1f1b.py``);
- update: per-stage grad accumulators reduce at the schedule boundary —
  finite is ANDed and the global grad-norm combines per-stage sum-of-squares
  on the host (f64) — then every stage runs the exact ``Engine._update``
  tail expression over its own shard; loss-scale and sentinel verdicts
  settle here, once per step, like the fused program's.

Failure semantics: a stage thread death aborts the transport, the step
replays from untouched params (updates only commit at the boundary), and a
SIGKILLed process restarts under the ElasticAgent from the per-stage
checkpoint fragments — see docs/PIPELINE.md.
"""

from __future__ import annotations

import threading
import time
from typing import Any, Iterator

import jax
import jax.numpy as jnp
import numpy as np

from deepspeed_tpu.runtime import precision
from deepspeed_tpu.runtime import sentinel as sentinel_mod
from deepspeed_tpu.runtime.engine import Engine, _global_norm, _tree_select
from deepspeed_tpu.runtime.pipe.partition import (
    StagePlan, merge_params, plan_stages, split_params)
from deepspeed_tpu.runtime.pipe.schedule import (
    build_schedule, thread_program, validate_schedule)
from deepspeed_tpu.runtime.pipe.transport import (
    ACT, GRAD, InProcTransport, TransportAborted)
from deepspeed_tpu.telemetry.tracing import format_traceparent
from deepspeed_tpu.utils import faults as _faults
from deepspeed_tpu.utils.logging import log_dist

try:
    import optax
except ImportError:  # pragma: no cover - optax ships with the toolchain
    optax = None


class _StepCtx:
    """Per-attempt mutable state of one scheduled step."""

    __slots__ = ("microbatches", "mults", "accs", "losses", "stash",
                 "errors", "recv_wait", "busy", "scale", "measure",
                 "trace_hdr")

    def __init__(self, microbatches, mults, accs, n_stages, scale, measure,
                 trace_hdr=None):
        self.microbatches = microbatches
        self.mults = mults
        self.accs = accs
        self.losses = [None] * len(microbatches)
        self.stash: dict = {}
        self.errors: dict = {}
        self.recv_wait = [0.0] * n_stages
        self.busy = [0.0] * n_stages
        self.scale = scale
        self.measure = measure
        # the step's W3C traceparent (None with tracing off): every
        # cross-stage send carries it so receivers record the hop as a
        # span under one step-wide trace_id (fleet trace stitching)
        self.trace_hdr = trace_hdr


class PipeEngine(Engine):
    """Staged MPMD drop-in for :class:`Engine` (``pipeline.stages > 1``)."""

    _supports_staged_pipeline = True

    def __init__(self, model, config, topo, training_data: Iterator | None = None,
                 seed: int | None = None, initial_params: Any = None):
        super().__init__(model, config, topo, training_data=training_data,
                         seed=seed, initial_params=initial_params)
        pipe_cfg = config.pipeline
        self._validate_staging(pipe_cfg)

        parts = self.model_spec.pipeline_parts
        (self._stage0_fn, self._block_fn, self._last_fn,
         self._split_fn, self._merge_fn) = parts
        self._extras_owner = dict(self.model_spec.pipeline_extras_owner)

        layers, _extras = self._split_fn(self.params)
        n_layers = int(jax.tree_util.tree_leaves(layers)[0].shape[0])
        self.stage_plan: StagePlan = plan_stages(
            n_layers, pipe_cfg.stages, pipe_cfg.interleave,
            method=pipe_cfg.partition_method)

        # per-virtual-stage master params (subset trees: checkpoint keystrs
        # coincide with the single-program tree) + optimizer shards; the
        # full trees are dropped — every consumer goes through the stages
        self.stage_params = split_params(self.params, self.stage_plan,
                                         self._extras_owner)
        self.stage_opt = [jax.jit(self.optimizer.init)(sp)
                          for sp in self.stage_params]
        self.params = None
        self.opt_state = None

        self._n_micro = self.gas
        sched = build_schedule(pipe_cfg.schedule, self.stage_plan.n_virtual,
                               self._n_micro)
        validate_schedule(sched, self.stage_plan.n_virtual,
                          self.stage_plan.n_stages, self._n_micro)
        self._thread_programs = [
            thread_program(sched, s, self.stage_plan.n_stages)
            for s in range(self.stage_plan.n_stages)]
        self.transport = InProcTransport()
        self._progs: dict = {}
        self._max_stage_retries = 2
        self._schedule_timeout_s = 600.0
        self.stage_restarts = 0  # in-process stage replays (chaos visibility)
        self._last_stage_busy: list[float] = []
        self._last_stage_wall = 0.0

        # per-stage liveness beacons for the elastic agent: the SAME
        # heartbeat files the process-rank beacon uses, suffixed _s{thread},
        # beaten from inside each stage thread — a single wedged stage goes
        # stale while the process rank keeps beating
        self._stage_heartbeats = None
        sent_cfg = config.sentinel
        if sent_cfg.enabled and sent_cfg.state_dir:
            import os as _os

            rank = int(_os.environ.get("RANK", jax.process_index()))
            self._stage_heartbeats = [
                sentinel_mod.Heartbeat(
                    sent_cfg.state_dir, rank=f"{rank}_s{s}",
                    interval_s=sent_cfg.heartbeat_interval_s)
                for s in range(self.stage_plan.n_stages)]

        log_dist(
            f"PipeEngine: {self.stage_plan.describe()}, schedule="
            f"{pipe_cfg.schedule}"
            + (f" x{pipe_cfg.interleave} interleaved"
               if pipe_cfg.interleave > 1 else "")
            + f", microbatches={self._n_micro}, transport=inproc", ranks=[0])

    # ------------------------------------------------------------ validation
    def _validate_staging(self, pipe_cfg):
        cfg = self.config
        conflicts = {
            "quantized gradient reduction": self._qgrad,
            "zenflow": bool(self._zenflow),
            "offloaded optimizer state": self._offload_mode is not None,
            "offloaded params": self._param_offload != "none",
            "compression training": self._compression is not None,
            "progressive layer drop": cfg.progressive_layer_drop.enabled,
            "random_ltd": self._ltd is not None,
            "an in-jit pipeline mesh axis": self.topo.size("pipeline") > 1,
        }
        bad = [k for k, v in conflicts.items() if v]
        if bad:
            raise ValueError(
                f"pipeline.stages={pipe_cfg.stages} (MPMD staged runtime) "
                f"does not compose with {', '.join(bad)}")
        if self.topo.world_size != 1 or jax.process_count() != 1:
            raise ValueError(
                "the staged MPMD runtime is single-process/single-device "
                "for now (stage programs dispatch from threads over the "
                "in-process transport); shrink the mesh or drop "
                "pipeline.stages")
        if pipe_cfg.transport != "inproc":
            raise ValueError(
                f"pipeline.transport={pipe_cfg.transport!r}: only 'inproc' "
                "is implemented (the device transport is a reserved seam)")
        if self.model_spec.pipeline_parts is None:
            raise ValueError(
                f"model {self.model_spec.name!r} exposes no pipeline_parts "
                "decomposition; it cannot run staged")
        if self.model_spec.pipeline_extras_owner is None:
            raise ValueError(
                f"model {self.model_spec.name!r} declares no "
                "pipeline_extras_owner (tied embeddings need a cross-stage "
                "grad reduction the transport does not carry); untie the "
                "embeddings or drop pipeline.stages")
        if pipe_cfg.num_microbatches not in (0, self.gas):
            raise ValueError(
                f"pipeline.num_microbatches={pipe_cfg.num_microbatches} must "
                f"equal gradient_accumulation_steps={self.gas} (or 0): the "
                "staged runtime pipelines the GAS microbatches")

    # ------------------------------------------------------------ programs
    def _cast_stage(self, sp):
        return precision.cast_to_compute(sp, self.config.compute_dtype)

    @staticmethod
    def _split_extras(cp):
        return {k: w for k, w in cp.items() if k != "layers"}

    def _fwd_prog(self, v: int):
        """Forward program for a non-last virtual stage: (params, x|mb) -> y."""
        key = ("fwd", v)
        fn = self._progs.get(key)
        if fn is None:
            first = v == 0

            def fwd(sp, xin):
                cp = self._cast_stage(sp)
                extras = self._split_extras(cp)
                x = self._stage0_fn(extras, xin) if first else xin
                return self._block_fn(cp["layers"], extras, x)

            fn = self._progs[key] = jax.jit(fwd)
        return fn

    def _last_prog(self, v: int, has_mult: bool):
        """Fused F+B for the last virtual stage:
        (params, acc, x, mb, scale[, mult]) -> (loss, acc', dx)."""
        key = ("last", v, has_mult)
        fn = self._progs.get(key)
        if fn is None:

            def last(sp, acc, x, mb, scale, *mult):
                cp = self._cast_stage(sp)

                def scaled(cp_tree, xin):
                    extras = self._split_extras(cp_tree)
                    y = self._block_fn(cp_tree["layers"], extras, xin)
                    loss = self._last_fn(extras, y, mb)
                    if mult:
                        loss = loss * mult[0].reshape(-1)[0]
                    return loss * scale

                loss_scaled, (gp, dx) = jax.value_and_grad(
                    scaled, argnums=(0, 1))(cp, x)
                g32 = jax.tree_util.tree_map(
                    lambda g: g.astype(jnp.float32), gp)
                new_acc = jax.tree_util.tree_map(jnp.add, acc, g32)
                return loss_scaled / scale, new_acc, dx

            fn = self._progs[key] = jax.jit(last)
        return fn

    def _bwd_prog(self, v: int):
        """Recompute-backward for an inner (non-first, non-last) stage:
        (params, acc, x, dy) -> (acc', dx)."""
        key = ("bwd", v)
        fn = self._progs.get(key)
        if fn is None:

            def bwd(sp, acc, x, dy):
                cp = self._cast_stage(sp)

                def f(cp_tree, xin):
                    extras = self._split_extras(cp_tree)
                    return self._block_fn(cp_tree["layers"], extras, xin)

                _y, vjp = jax.vjp(f, cp, x)
                gp, dx = vjp(dy)
                g32 = jax.tree_util.tree_map(
                    lambda g: g.astype(jnp.float32), gp)
                new_acc = jax.tree_util.tree_map(jnp.add, acc, g32)
                return new_acc, dx

            fn = self._progs[key] = jax.jit(bwd)
        return fn

    def _bwd0_prog(self):
        """Recompute-backward for virtual stage 0 (params only; the
        microbatch is data, not a differentiable input):
        (params, acc, mb, dy) -> acc'."""
        key = ("bwd0",)
        fn = self._progs.get(key)
        if fn is None:

            def bwd0(sp, acc, mb, dy):
                cp = self._cast_stage(sp)

                def f(cp_tree):
                    extras = self._split_extras(cp_tree)
                    x = self._stage0_fn(extras, mb)
                    return self._block_fn(cp_tree["layers"], extras, x)

                _y, vjp = jax.vjp(f, cp)
                (gp,) = vjp(dy)
                g32 = jax.tree_util.tree_map(
                    lambda g: g.astype(jnp.float32), gp)
                return jax.tree_util.tree_map(jnp.add, acc, g32)

            fn = self._progs[key] = jax.jit(bwd0)
        return fn

    def _reduce_prog(self):
        """Boundary reduction over ALL stage accumulators:
        (accs, scale) -> (finite, gnorm). The per-stage grads are merged
        back into the full tree (an exact concatenate) so ``grads_finite``
        and ``_global_norm`` see the identical leaf order and reduction
        shapes the fused program's tail sees — the clip coefficient must be
        the SAME fp32 scalar or the parity gate drifts one ulp per step."""
        key = ("reduce",)
        fn = self._progs.get(key)
        if fn is None:
            n_micro = self._n_micro

            def reduce_fn(accs, scale):
                denom = scale * n_micro
                stage_grads = [
                    jax.tree_util.tree_map(lambda g: g / denom, a)
                    for a in accs]
                merged = merge_params(stage_grads, self.stage_plan)
                return precision.grads_finite(merged), _global_norm(merged)

            fn = self._progs[key] = jax.jit(reduce_fn)
        return fn

    def _update_prog(self, v: int):
        """Per-stage optimizer tail: mirrors ``Engine._update`` expression
        for expression over the stage shard (gnorm/gate arrive as settled
        cross-stage scalars)."""
        key = ("update", v, self._lr_scale)
        fn = self._progs.get(key)
        if fn is None:
            cfg = self.config
            n_micro = self._n_micro
            lr_scale = self._lr_scale

            def update(sp, so, acc, scale, gnorm, gate, step):
                denom = scale * n_micro
                grads = jax.tree_util.tree_map(lambda g: g / denom, acc)
                if cfg.gradient_clipping > 0:
                    coef = jnp.minimum(
                        1.0, cfg.gradient_clipping / (gnorm + 1e-6))
                    grads = jax.tree_util.tree_map(
                        lambda g: g * coef, grads)
                lr = self.lr_schedule(step)
                if lr_scale != 1.0:
                    lr = lr * jnp.float32(lr_scale)
                updates, new_opt = self.optimizer.update(grads, so, sp)
                updates = jax.tree_util.tree_map(lambda u: u * lr, updates)
                new_p = optax.apply_updates(sp, updates)
                new_p = _tree_select(gate, new_p, sp)
                new_opt = _tree_select(gate, new_opt, so)
                return new_p, new_opt

            fn = self._progs[key] = jax.jit(update)
        return fn

    def _zero_acc(self, v: int):
        return jax.tree_util.tree_map(
            lambda p: jnp.zeros(p.shape, jnp.float32), self.stage_params[v])

    # ------------------------------------------------------------ executor
    def _timed(self, thread: int, ctx: _StepCtx, fn, *args):
        if not ctx.measure:
            return fn(*args)
        t0 = time.perf_counter()
        out = fn(*args)
        jax.block_until_ready(out)
        ctx.busy[thread] += time.perf_counter() - t0
        return out

    def _exec_instr(self, ins, ctx: _StepCtx):
        P = self.stage_plan.n_virtual
        v, op, m = ins.v, ins.op, ins.mb
        thread = self.stage_plan.thread_of(v)
        tp = self.transport
        if op == "F":
            if v == P - 1:
                x, waited = tp.recv(v - 1, v, ACT, m)
                ctx.recv_wait[thread] += waited
                mult = ctx.mults[m] if ctx.mults is not None else None
                args = [self.stage_params[v], ctx.accs[v], x,
                        ctx.microbatches[m], ctx.scale]
                if mult is not None:
                    args.append(mult)
                loss, new_acc, dx = self._timed(
                    thread, ctx, self._last_prog(v, mult is not None), *args)
                ctx.accs[v] = new_acc
                ctx.losses[m] = loss
                ctx.stash[("dx", v, m)] = dx
            elif v == 0:
                y = self._timed(thread, ctx, self._fwd_prog(0),
                                self.stage_params[0], ctx.microbatches[m])
                tp.send(0, 1, ACT, m, y, traceparent=ctx.trace_hdr)
            else:
                x, waited = tp.recv(v - 1, v, ACT, m)
                ctx.recv_wait[thread] += waited
                ctx.stash[("in", v, m)] = x
                y = self._timed(thread, ctx, self._fwd_prog(v),
                                self.stage_params[v], x)
                tp.send(v, v + 1, ACT, m, y, traceparent=ctx.trace_hdr)
        else:  # "B"
            if v == P - 1:
                # the fused F+B already produced this microbatch's cotangent
                dx = ctx.stash.pop(("dx", v, m))
                tp.send(v, v - 1, GRAD, m, dx, traceparent=ctx.trace_hdr)
            elif v == 0:
                dy, waited = tp.recv(1, 0, GRAD, m)
                ctx.recv_wait[thread] += waited
                ctx.accs[0] = self._timed(
                    thread, ctx, self._bwd0_prog(), self.stage_params[0],
                    ctx.accs[0], ctx.microbatches[m], dy)
            else:
                dy, waited = tp.recv(v + 1, v, GRAD, m)
                ctx.recv_wait[thread] += waited
                x = ctx.stash.pop(("in", v, m))
                new_acc, dx = self._timed(
                    thread, ctx, self._bwd_prog(v), self.stage_params[v],
                    ctx.accs[v], x, dy)
                ctx.accs[v] = new_acc
                tp.send(v, v - 1, GRAD, m, dx, traceparent=ctx.trace_hdr)

    def _stage_thread(self, thread: int, ctx: _StepCtx):
        inj = self._fault_injector
        hb = (self._stage_heartbeats[thread]
              if self._stage_heartbeats is not None else None)
        try:
            for ins in self._thread_programs[thread]:
                if inj.enabled:
                    inj.fire(_faults.POINT_PIPE_STAGE,
                             request_id=f"stage{thread}")
                self._exec_instr(ins, ctx)
                if hb is not None:
                    hb.beat(self.global_steps)
        except TransportAborted:
            pass  # peer failed; the step replays
        except BaseException as e:  # noqa: BLE001 - surfaced by the replay loop
            ctx.errors[thread] = e
            self.transport.abort()

    def _run_schedule(self, mbs, mults):
        """Execute one step's schedule, replaying on in-process stage death
        (params/optimizer are untouched until the boundary update, so a
        replay is exact). Returns the completed :class:`_StepCtx` + wall."""
        S = self.stage_plan.n_stages
        measure = self.stepscope.enabled
        tracer = self.telemetry.tracer
        attempts = 0
        while True:
            step_trace = tracer.extract(None) if tracer.enabled else None
            ctx = _StepCtx(
                mbs, mults,
                [self._zero_acc(v) for v in range(self.stage_plan.n_virtual)],
                S, self.scale_state.scale, measure,
                trace_hdr=(format_traceparent(step_trace)
                           if step_trace is not None else None))
            self.transport.reset()
            t0 = time.perf_counter()
            threads = [threading.Thread(
                target=self._stage_thread, args=(s, ctx), daemon=True,
                name=f"pipe-stage-{s}") for s in range(S)]
            for t in threads:
                t.start()
            deadline = t0 + self._schedule_timeout_s
            for t in threads:
                t.join(max(0.1, deadline - time.perf_counter()))
            if any(t.is_alive() for t in threads):
                self.transport.abort()
                for t in threads:
                    t.join(10.0)
                raise sentinel_mod.TrainingWedgeError(
                    f"pipeline schedule wedged past "
                    f"{self._schedule_timeout_s:.0f}s at step "
                    f"{self.global_steps}")
            wall = time.perf_counter() - t0
            if not ctx.errors:
                if step_trace is not None:
                    tracer.finish(step_trace, "pipe/step", t0, t0 + wall,
                                  step=self.global_steps, stages=S)
                return ctx, wall
            attempts += 1
            err = next(iter(ctx.errors.values()))
            if attempts > self._max_stage_retries:
                raise RuntimeError(
                    f"pipeline stage failed {attempts}x at step "
                    f"{self.global_steps}; giving up") from err
            self.stage_restarts += 1
            log_dist(
                f"pipe: stage thread died ({type(err).__name__}: {err}); "
                f"replaying step {self.global_steps} "
                f"(attempt {attempts + 1})", ranks=[0])

    # ------------------------------------------------------------ train step
    def train_batch(self, batch: dict | None = None,
                    data_iter: Iterator | None = None):
        scope = self.stepscope if self.stepscope.enabled else None
        if scope is not None:
            scope.begin_step(self.global_steps)
        if batch is None:
            if data_iter is None:
                if self.training_dataloader is None:
                    raise ValueError(
                        "train_batch needs a batch, data_iter, or "
                        "training_data")
                data_iter = self.training_dataloader
            _dw0 = time.perf_counter() if scope is not None else 0.0
            micro = [next(data_iter) for _ in range(self.gas)]
            batch = {k: np.concatenate([np.asarray(m[k]) for m in micro])
                     for k in micro[0]}
            if scope is not None:
                scope.note_phase("data_wait", _dw0, time.perf_counter())
        if self.config.debug.sanity_checks:
            self._sanity_check_batch(batch)
        if self._sentinel is not None or self._fault_injector.enabled:
            batch = self._sentinel_pre_step(batch)
        self._step_miss0 = (self._jit_miss_count()
                            if self.telemetry.enabled else None)
        self.step_tracer.before_step(self.global_steps)
        dev_batch = self._put_gas_batch(batch)
        mults = None
        if "__loss_mult__" in dev_batch:
            mv = dev_batch.pop("__loss_mult__")
            mults = [mv[i] for i in range(self._n_micro)]
        mbs = [jax.tree_util.tree_map(lambda x, i=i: x[i], dev_batch)
               for i in range(self._n_micro)]
        self.tput_timer.start()
        sched_t0 = time.perf_counter()
        try:
            ctx, wall = self._run_schedule(mbs, mults)
            metrics = self._boundary_update(ctx)
        except sentinel_mod.TrainingWedgeError as e:
            if self._sentinel is not None:
                return self._handle_wedge(e)
            raise
        if self._fault_injector.enabled:
            self._fault_injector.fire(_faults.POINT_TRAIN_DISPATCH)
        if scope is not None:
            jax.block_until_ready(metrics["loss"])
            # the pipe's device window is carved as the step residual; the
            # measured fill/drain + recv-wait idle gets its own phase so the
            # phase-sum == step-wall pin keeps holding under pipelining
            busy = ctx.busy
            mean_idle = sum(max(0.0, wall - b) for b in busy) / len(busy)
            scope.note_phase("pipe_bubble", sched_t0,
                             sched_t0 + min(mean_idle, wall))
            scope.note_pipe_stages(busy, wall)
            self._last_stage_busy = list(busy)
            self._last_stage_wall = wall
        self._inflight.append(metrics["loss"])
        if len(self._inflight) > self._max_inflight:
            jax.block_until_ready(self._inflight.pop(0))
        self.tput_timer.stop(
            global_step=True,
            exclude=self._step_recompiled() or self._devprof_capturing())
        self._after_step(metrics)
        self.micro_steps += self.gas
        if self._sentinel is not None:
            self._sentinel_post_step()
        return metrics["loss"]

    def _boundary_update(self, ctx: _StepCtx):
        """Settle the step: cross-stage reductions, sentinel/loss-scale
        verdicts, and the per-stage optimizer tails."""
        cfg = self.config
        P = self.stage_plan.n_virtual
        scale = self.scale_state.scale
        loss = jnp.mean(jnp.stack(ctx.losses))
        finite_j, gnorm_j = self._reduce_prog()(ctx.accs, scale)

        gate_j = finite_j
        sent_extra = {}
        if self._sentinel is not None:
            new_sent, anomaly, reason, streak = sentinel_mod.verdict(
                self._sent_state, loss, gnorm_j, finite_j, cfg.sentinel)
            self._sent_state = new_sent
            gate_j = jnp.logical_not(anomaly)
            sent_extra = {"anomalous": anomaly, "anomaly_reason": reason,
                          "skip_streak": streak}

        step_j = jnp.int32(self.global_steps)
        for v in range(P):
            new_p, new_opt = self._update_prog(v)(
                self.stage_params[v], self.stage_opt[v], ctx.accs[v],
                scale, gnorm_j, gate_j, step_j)
            self.stage_params[v] = new_p
            self.stage_opt[v] = new_opt

        lr = self.lr_schedule(step_j)
        if self._lr_scale != 1.0:
            lr = lr * jnp.float32(self._lr_scale)
        metrics = {
            "loss": loss,
            "grad_norm": gnorm_j,
            "lr": lr,
            "loss_scale": self.scale_state.scale,
            "skipped": jnp.logical_not(finite_j),
            **sent_extra,
        }
        self.scale_state = precision.update_loss_scale(
            self.scale_state, finite_j, cfg.fp16)
        return metrics

    # ------------------------------------------------------------ surfaces
    def module_state(self):
        return merge_params(self.stage_params, self.stage_plan)

    def forward(self, batch: dict):
        raise NotImplementedError(
            "PipeEngine is a training runtime; eval the merged params "
            "(module_state()) on a single-program engine")

    eval_batch = forward

    def backward(self, batch: dict):
        raise NotImplementedError(
            "the fwd/bwd/step parity path does not run staged; use "
            "train_batch()")

    step = backward

    # ------------------------------------------------------------ checkpoint
    def _boxes_for(self, tree, v: int) -> dict:
        """Global-coordinate boxes for every layer-stacked leaf of a stage
        tree (params or optimizer state): dim 0 is the layer axis, offset by
        the stage's layer range."""
        lo, _hi = self.stage_plan.layer_range(v)
        n_layers = self.stage_plan.n_layers
        boxes = {}
        for path, leaf in jax.tree_util.tree_flatten_with_path(tree)[0]:
            key = jax.tree_util.keystr(path)
            if "['layers']" in key:
                boxes[key] = (lo, (n_layers,) + tuple(np.shape(leaf))[1:])
        return boxes

    def _collect_ckpt_payloads(self, stage_dir: str) -> list:
        from deepspeed_tpu.checkpoint import sharded

        payloads = []
        for v in range(self.stage_plan.n_virtual):
            part = f"_s{v}"
            payloads.append(("model", part, sharded.collect_fragments(
                self.stage_params[v], "model", part=part,
                boxes=self._boxes_for(self.stage_params[v], v))))
            payloads.append(("optimizer", part, sharded.collect_fragments(
                self.stage_opt[v], "optimizer", part=part,
                boxes=self._boxes_for(self.stage_opt[v], v))))
        return payloads

    def _manifest_extra(self) -> dict:
        import jax as _jax

        proc = _jax.process_index()
        plan = self.stage_plan
        return {"pipeline": {
            "stages": plan.n_stages,
            "interleave": plan.interleave,
            "schedule": self.config.pipeline.schedule,
            "n_layers": plan.n_layers,
            "boundaries": list(plan.boundaries),
            "fragments": {
                str(v): [f"model_shard_p{proc}_s{v}.npz",
                         f"optimizer_shard_p{proc}_s{v}.npz"]
                for v in range(plan.n_virtual)},
        }}

    def _restore_sharded_model(self, ckpt_dir: str):
        from deepspeed_tpu.checkpoint import sharded

        self.stage_params = [
            sharded.load_sharded(self.stage_params[v], ckpt_dir, "model",
                                 boxes=self._boxes_for(self.stage_params[v], v))
            for v in range(self.stage_plan.n_virtual)]

    def _restore_sharded_optimizer(self, ckpt_dir: str):
        from deepspeed_tpu.checkpoint import sharded

        self.stage_opt = [
            sharded.load_sharded(self.stage_opt[v], ckpt_dir, "optimizer",
                                 boxes=self._boxes_for(self.stage_opt[v], v))
            for v in range(self.stage_plan.n_virtual)]
