"""Training step anatomy: phase decomposition, MFU attribution, goodput.

PR 5 gave the *serving* path causal tracing; training still reported one
``train_tflops`` number with no account of where the rest of each step goes.
This module decomposes every training step into named phases and feeds three
consumers at once:

- **Trace ring** (:mod:`deepspeed_tpu.telemetry.tracing`): each step becomes a
  ``train/step`` span with ``train/phase/*`` children, exported alongside
  serving traces via ``telemetry.dump_trace()`` / ``GET /debug/trace`` and
  loadable in Perfetto.
- **Metrics registry**: ``step_phase_seconds{phase=}`` histograms,
  ``train_overlap_fraction`` / ``train_goodput`` / ``train_mfu`` /
  ``train_phase_mfu{phase=}`` / ``train_step_skew_ratio`` gauges,
  ``train_goodput_seconds_total{category=}`` counters, and per-phase HBM
  watermark deltas (``step_phase_hbm_delta_bytes{phase=}`` +
  ``step_hbm_peak_bytes{phase=}`` naming the phase that owns the step's
  memory peak — the when-complement to the memory ledger's who).
- **Plain data**: :meth:`StepScope.summary` is the whole anatomy as one
  JSON-able dict (``tests/unit/test_stepscope.py``).

Measurement model. The engine's fused step is ONE XLA program dispatched
asynchronously, so the host can only directly time the boundaries it owns:

- *measured* phases — ``data_wait`` (iterator pull), ``h2d`` (batch staging),
  ``recompile`` (per-step delta of the PR 5 ``jit_compile_seconds`` listener),
  ``checkpoint`` (save/restore stalls, recorded between steps), and the
  dispatch→settle window of device work (``compute`` marks).
- *attributed* phases — the device window is split into ``forward`` /
  ``backward`` / ``grad_comm`` / ``optimizer`` using the FLOPs model from
  :mod:`deepspeed_tpu.profiling.flops_profiler` (fwd : bwd : opt weights) and
  a wire-time estimate for the gradient collectives. Exposed collective time
  is estimated as ``min(est_wire_time, max(0, measured - roofline_compute))``
  and ``train_overlap_fraction = 1 - exposed / est_wire_time`` — the
  acceptance metric for ROADMAP item #4. Attributed spans carry
  ``attributed: true`` so dashboards can tell model-based splits from
  host-measured ones. On split step paths (grouped/NVMe offload, the
  fwd/bwd/step parity API) the optimizer walk IS host-measured and the
  attribution covers only the fwd/bwd program.
- a ``host`` residual closes the sum: every step's phase durations add up to
  the step wall clock by construction, and the residual makes Python glue
  overhead visible instead of silently vanishing.

Enabling stepscope is *microscope mode*: the engine settles each step
(``jax.block_until_ready``) so phase walls are real, trading the async
pipeline's overlap for visibility. Disabled (the default) the engine hot path
performs zero stepscope work — no calls into this module at all, pinned by
tracemalloc in ``tests/unit/test_stepscope.py``.

Goodput: ``train_goodput = productive_step_seconds / wall_seconds`` since the
scope was created, where recompile, checkpoint stalls, and init/warmup (engine
construction to first step) are carved out as non-productive categories.
Per-host skew reuses the comms-logging straggler machinery: an allgather of
mean step time at refresh points, warned past ``straggler_warn_ratio``.
"""

from __future__ import annotations

import time
import uuid
from collections import deque
from contextlib import contextmanager

from deepspeed_tpu.telemetry.compile_watch import COMPILE_BUCKETS
from deepspeed_tpu.telemetry.tracing import TraceContext, _new_span_id
from deepspeed_tpu.utils.logging import log_dist

# attribution order = synthetic span layout order inside the device window
ATTRIBUTED_PHASES = ("forward", "backward", "grad_comm", "optimizer")

# AdamW update chain is ~18 elementwise flops/param (m, v, bias correction,
# sqrt, divide, decay, apply) — only used to weight the optimizer's share of
# the fused window, so the constant's exact value is second-order
_OPT_FLOPS_PER_PARAM = 18.0

# bf16 peak FLOPs/s per chip generation (public spec sheets)
_PEAK_TABLE = {
    "v6": 918e12,
    "v5p": 459e12,
    "v5": 197e12,   # v5e / v5 lite (checked after v5p)
    "v4": 275e12,
    "v3": 123e12,
    "v2": 45e12,
}


def device_peak_flops() -> float:
    """Peak FLOPs/s of the local TPU; a ``device_kind`` the table does not
    list is an error, never an assumed peak. Off the chip (the CPU test
    mesh) the denominator is a nominal 1e12 — what an MFU means there is
    the benchmark's to settle, not this table's."""
    import jax

    if jax.default_backend() != "tpu":
        return 1e12
    kind = jax.devices()[0].device_kind.lower()
    for key, peak in _PEAK_TABLE.items():
        if key in kind:
            return peak
    raise ValueError(
        f"stepscope: device kind {kind!r} is not in _PEAK_TABLE; add its "
        "published peak there rather than assuming one")


class StepScope:
    """Per-step phase recorder owned by the engine (one per training run).

    All public methods no-op when ``enabled`` is False, but the engine guards
    every call site on a single ``stepscope.enabled`` attribute read so the
    disabled hot path never enters this module.
    """

    def __init__(self, telemetry, enabled: bool = False, *,
                 batch_size: int = 1,
                 fwd_flops_per_step: float = 0.0,
                 param_count: int = 0,
                 collective_bytes_per_step: float = 0.0,
                 peak_tflops: float | None = None,
                 interconnect_gbps: float = 100.0,
                 straggler_warn_ratio: float = 2.0,
                 flops_source: str = "analytic"):
        self.telemetry = telemetry
        self.enabled = bool(enabled) and bool(getattr(telemetry, "enabled",
                                                      False))
        self.batch_size = int(batch_size)
        self.fwd_flops_per_step = float(fwd_flops_per_step)
        self.param_count = int(param_count)
        self.collective_bytes_per_step = float(collective_bytes_per_step)
        self.straggler_warn_ratio = float(straggler_warn_ratio)
        self.flops_source = flops_source
        self._peak = (float(peak_tflops) * 1e12 if peak_tflops
                      else device_peak_flops())
        self._ici_bw = max(0.0, float(interconnect_gbps)) * 1e9
        self._t_created = time.perf_counter()
        self._trace_id = uuid.uuid4().hex

        # per-step state
        self._step_t0: float | None = None
        self._marks: list[tuple[str, float, float]] = []
        self._c0_compile = 0.0
        # per-phase HBM watermarks (host-side dict reads, no device sync);
        # a backend without memory stats flips _mem_broken and the feature
        # goes permanently silent, like HbmWatermarkSampler
        self._mem_broken = False
        self._mem_marks: list[tuple[str, int]] = []

        # run accumulators (summary() + gauges)
        self._steps = 0
        self._step_s = 0.0
        self._phase_totals: dict[str, float] = {}
        self._productive_s = 0.0
        self._recompile_s = 0.0
        self._checkpoint_s = 0.0
        self._overhead_s = 0.0   # all note_overhead time (excluded from warmup)
        self._warmup_s = 0.0
        # capture-bearing steps (devprof windows): span-visible but excluded
        # from every run average, like recompile-bearing steps
        self._profiled_steps = 0
        self._profiling_s = 0.0
        self._saw_step = False
        self._exposed_s = 0.0
        self._coll_s = 0.0
        self._model_flops_s = 0.0  # model flops issued (for run MFU)
        self._recent: deque = deque(maxlen=64)  # recent step walls (skew)

        self._phase_hist = None
        self._compile_hist = None
        self._c_goodput = None
        self._g_overlap = self._g_goodput = self._g_skew = None
        self._g_pipe_bubble = None
        self._g_mfu = self._g_phase_mfu = None
        self._g_phase_hbm = self._g_peak_hbm = None
        if self.enabled:
            reg = telemetry.registry
            self._phase_hist = reg.histogram(
                "step_phase_seconds",
                "training step time by phase (measured + attributed)")
            self._compile_hist = reg.histogram(
                "jit_compile_seconds",
                "XLA trace/lower/compile phase durations",
                buckets=COMPILE_BUCKETS)
            self._c_goodput = reg.counter(
                "train_goodput_seconds_total",
                "wall-clock by goodput category "
                "(productive|recompile|checkpoint|warmup|profiling)")
            self._g_overlap = reg.gauge(
                "train_overlap_fraction",
                "fraction of collective time hidden under compute "
                "(source=estimate: analytic wire-time model; "
                "source=measured: devprof device-timeline capture)")
            self._g_goodput = reg.gauge(
                "train_goodput",
                "productive step seconds / wall seconds since scope start")
            self._g_skew = reg.gauge(
                "train_step_skew_ratio",
                "max/min per-host mean step time (straggler indicator); "
                "stage=<s> rows: per-pipeline-stage busy/mean-busy ratio")
            self._g_pipe_bubble = reg.gauge(
                "train_pipe_bubble_fraction",
                "measured idle fraction of the pipeline schedule window "
                "(fill/drain + recv-wait, averaged over stage threads)")
            self._g_mfu = reg.gauge(
                "train_mfu", "model FLOPs utilization over measured steps")
            self._g_phase_mfu = reg.gauge(
                "train_phase_mfu",
                "per-phase achieved/roofline FLOPs (attributed phases)")
            self._g_phase_hbm = reg.gauge(
                "step_phase_hbm_delta_bytes",
                "HBM watermark delta across each host-measured phase "
                "(which phase grows device memory)")
            self._g_peak_hbm = reg.gauge(
                "step_hbm_peak_bytes",
                "step's highest HBM watermark, labeled by the phase whose "
                "boundary observed it (which phase owns the peak)")
            # pre-set so a scrape sees the series before the first step
            self._g_overlap.set(1.0, source="estimate")
            self._g_goodput.set(0.0)
            self._g_skew.set(1.0)

    # ------------------------------------------------------------ per step
    def begin_step(self, step: int) -> None:
        if not self.enabled:
            return
        now = time.perf_counter()
        if not self._saw_step:
            # init/restart + warmup: engine construction to the first step,
            # minus overheads already accounted (e.g. a checkpoint restore)
            self._saw_step = True
            self._warmup_s = max(0.0,
                                 now - self._t_created - self._overhead_s)
            self._c_goodput.inc(self._warmup_s, category="warmup")
        self._step_t0 = now
        self._marks = []
        self._c0_compile = self._compile_hist.sum(phase="backend_compile")
        self._mem_marks = []
        m = self._read_mem()
        if m >= 0:
            self._mem_marks.append(("begin", m))

    def note_phase(self, name: str, t0: float, t1: float) -> None:
        """Record a host-measured phase window (perf_counter stamps)."""
        if not self.enabled or self._step_t0 is None:
            return
        self._marks.append((name, t0, max(t0, t1)))
        m = self._read_mem()
        if m >= 0:
            self._mem_marks.append((name, m))

    def _read_mem(self) -> int:
        """Current HBM bytes_in_use, or -1 when the backend reports none
        (one failed probe disables the feature for the run)."""
        if self._mem_broken:
            return -1
        try:
            from deepspeed_tpu.accelerator.real_accelerator import (
                get_accelerator,
            )

            v = (get_accelerator().memory_stats() or {}).get("bytes_in_use")
        except Exception:
            v = None
        if v is None:
            self._mem_broken = True
            return -1
        return int(v)

    @contextmanager
    def phase(self, name: str):
        t0 = time.perf_counter()
        try:
            yield
        finally:
            self.note_phase(name, t0, time.perf_counter())

    def compile_seconds(self) -> float:
        """Cumulative backend-compile seconds (PR 5 listener)."""
        if self._compile_hist is None:
            return 0.0
        return self._compile_hist.sum(phase="backend_compile")

    def end_step(self, step: int | None = None, profiled: bool = False,
                 **attrs) -> dict | None:
        """Close the step: attribute the device window, emit spans/metrics.

        ``profiled=True`` marks a capture-bearing step (a devprof window was
        open): its spans are still emitted — the device-op merge needs host
        phases to nest under — but the step is excluded from every run
        average (phase histograms/totals, goodput, overlap, MFU, skew),
        exactly like recompile-bearing steps are excluded from throughput.

        Returns the per-phase seconds dict (None when disabled/unstarted).
        """
        if not self.enabled or self._step_t0 is None:
            return None
        t1 = time.perf_counter()
        t0 = self._step_t0
        self._step_t0 = None
        total = max(t1 - t0, 1e-9)

        recompile_s = max(0.0, self._compile_hist.sum(phase="backend_compile")
                          - self._c0_compile)
        compute = [(a, b) for n, a, b in self._marks if n == "compute"]
        measured = [(n, a, b) for n, a, b in self._marks if n != "compute"]
        spans: list[tuple[str, float, float, bool]] = [
            (n, a, b, False) for n, a, b in measured]
        if compute:
            ca, cb = compute[0][0], compute[-1][1]
            cdur = sum(b - a for a, b in compute)
        else:
            # unwired path: the device window is the residual after the
            # host-measured phases
            ca, cb = t0, t1
            cdur = max(0.0, total - sum(b - a for _, a, b in measured))
        recompile_s = min(recompile_s, cdur)
        comp_s = max(0.0, cdur - recompile_s)

        measured_names = {n for n, _, _ in measured}
        parts, exposed_s, est_coll_s = self._attribute(
            comp_s, opt_measured="optimizer" in measured_names)

        # lay the carved phases consecutively over the device window so the
        # Perfetto children tile their parent (compile happens at dispatch,
        # so recompile leads)
        cursor = ca
        if recompile_s > 0.0:
            spans.append(("recompile", cursor, cursor + recompile_s, False))
            cursor += recompile_s
        for name in ATTRIBUTED_PHASES:
            s = parts.get(name, 0.0)
            if s > 0.0:
                spans.append((name, cursor, cursor + s, True))
                cursor += s
        accounted = sum(b - a for _, a, b, _ in spans)
        host_s = max(0.0, total - accounted)
        if host_s > 0.0:
            # python glue between phase boundaries; closes the phase sum to
            # the step wall clock
            spans.append(("host", t1 - host_s, t1, True))

        tracer = self.telemetry.tracer
        step_ctx = None
        if tracer.enabled:
            step_ctx = TraceContext(self._trace_id, _new_span_id(), None)
        for name, a, b, attributed in spans:
            dur = b - a
            if not profiled:
                self._phase_hist.observe(dur, phase=name)
                self._phase_totals[name] = (
                    self._phase_totals.get(name, 0.0) + dur)
            if step_ctx is not None:
                tracer.finish(
                    TraceContext(self._trace_id, _new_span_id(),
                                 step_ctx.span_id),
                    f"train/phase/{name}", a, b, phase=name,
                    attributed=True if attributed else None,
                    profiled=True if profiled else None)

        # per-phase HBM watermark deltas: each boundary sample is charged to
        # the phase that just ended, and the step's highest watermark names
        # the phase that owns the peak (the memory-ledger complement: the
        # ledger says WHO holds the bytes, this says WHEN they appear)
        if len(self._mem_marks) >= 2:
            prev = self._mem_marks[0][1]
            peak_phase, peak_bytes = self._mem_marks[0]
            for name, m in self._mem_marks[1:]:
                self._g_phase_hbm.set(float(m - prev), phase=name)
                if m > peak_bytes:
                    peak_phase, peak_bytes = name, m
                prev = m
            self._g_peak_hbm.set(float(peak_bytes), phase=peak_phase)

        if profiled:
            # the profiler's own overhead (trace start/stop, device dumps)
            # pollutes the wall; charge the whole step to a "profiling"
            # goodput category and keep it out of every run average
            self._profiled_steps += 1
            self._profiling_s += total
            self._c_goodput.inc(total, category="profiling")
            if step_ctx is not None:
                tracer.finish(step_ctx, "train/step", t0, t1, step=step,
                              profiled=True, **attrs)
            out = {n: b - a for n, a, b, _ in spans}
            out["total"] = total
            return out

        # goodput: a recompiling step is productive only for its non-compile
        # remainder
        productive = total - recompile_s
        self._steps += 1
        self._step_s += total
        self._productive_s += productive
        self._recompile_s += recompile_s
        self._recent.append(total)
        self._c_goodput.inc(productive, category="productive")
        if recompile_s > 0.0:
            self._c_goodput.inc(recompile_s, category="recompile")

        self._exposed_s += exposed_s
        self._coll_s += est_coll_s
        overlap = self.overlap_fraction()
        goodput = self.goodput()
        self._g_overlap.set(overlap, source="estimate")
        self._g_goodput.set(goodput)

        model_flops = (3.0 * self.fwd_flops_per_step
                       + _OPT_FLOPS_PER_PARAM * self.param_count)
        self._model_flops_s += model_flops
        mfu = 0.0
        if self._peak > 0.0 and self._step_s > 0.0:
            mfu = self._model_flops_s / (self._peak * self._step_s)
            self._g_mfu.set(mfu)
            for name, flops in (("forward", self.fwd_flops_per_step),
                                ("backward", 2.0 * self.fwd_flops_per_step),
                                ("optimizer",
                                 _OPT_FLOPS_PER_PARAM * self.param_count)):
                s = parts.get(name, 0.0)
                if s > 0.0 and flops > 0.0:
                    self._g_phase_mfu.set(flops / (self._peak * s),
                                          phase=name)

        if step_ctx is not None:
            tracer.finish(step_ctx, "train/step", t0, t1, step=step,
                          overlap_fraction=round(overlap, 4),
                          goodput=round(goodput, 4),
                          mfu=round(mfu, 4) if mfu else None, **attrs)
        out = {n: b - a for n, a, b, _ in spans}
        out["total"] = total
        return out

    def _attribute(self, comp_s: float, opt_measured: bool = False):
        """Split the device window by the FLOPs model; exposed collective
        time = min(est_wire_time, overshoot past the compute roofline)."""
        fwd = self.fwd_flops_per_step
        bwd = 2.0 * fwd
        opt = 0.0 if opt_measured else _OPT_FLOPS_PER_PARAM * self.param_count
        model_flops = fwd + bwd + opt
        est_coll = (self.collective_bytes_per_step / self._ici_bw
                    if self._ici_bw > 0.0 else 0.0)
        roofline = model_flops / self._peak if self._peak > 0.0 else 0.0
        exposed = min(est_coll, max(0.0, comp_s - roofline))
        rest = max(0.0, comp_s - exposed)
        parts = {"grad_comm": exposed}
        if model_flops > 0.0:
            for name, w in (("forward", fwd), ("backward", bwd),
                            ("optimizer", opt)):
                parts[name] = rest * w / model_flops
        else:
            parts["forward"] = rest  # no flops model: undivided compute
        return parts, exposed, est_coll

    # ------------------------------------------------------- between steps
    def note_overhead(self, kind: str, dur_s: float) -> None:
        """Account a non-step stall (checkpoint save/restore, ...) against
        goodput; recorded as a root-level ``train/<kind>_stall`` span."""
        if not self.enabled:
            return
        dur_s = max(0.0, float(dur_s))
        self._overhead_s += dur_s
        if kind == "checkpoint":
            self._checkpoint_s += dur_s
        self._phase_hist.observe(dur_s, phase=kind)
        self._c_goodput.inc(dur_s, category=kind)
        self._g_goodput.set(self.goodput())
        tracer = self.telemetry.tracer
        if tracer.enabled:
            now = time.perf_counter()
            tracer.finish(TraceContext(self._trace_id, _new_span_id(), None),
                          f"train/{kind}_stall", now - dur_s, now, kind=kind)

    # ------------------------------------------------------------- derived
    def overlap_fraction(self) -> float:
        if self._coll_s <= 0.0:
            return 1.0  # no collectives to expose
        return max(0.0, min(1.0, 1.0 - self._exposed_s / self._coll_s))

    def goodput(self) -> float:
        wall = max(time.perf_counter() - self._t_created, 1e-9)
        return max(0.0, min(1.0, self._productive_s / wall))

    def note_pipe_stages(self, busy: list, wall: float) -> None:
        """Per-step pipeline occupancy (MPMD runtime): ``busy[s]`` is stage
        thread s's measured program-execution seconds inside a ``wall``-long
        schedule window. Sets the measured bubble fraction and per-stage
        skew rows (``train_step_skew_ratio{stage=s}`` = busy_s / mean busy —
        an unbalanced partition shows up as rows far from 1.0)."""
        if not self.enabled or not busy or wall <= 0.0:
            return
        idle = [max(0.0, wall - b) for b in busy]
        self._g_pipe_bubble.set(
            min(1.0, sum(idle) / (len(busy) * wall)))
        mean_busy = sum(busy) / len(busy)
        if mean_busy > 0:
            for s, b in enumerate(busy):
                self._g_skew.set(b / mean_busy, stage=str(s))

    def refresh_skew(self) -> float:
        """Per-host step-time skew (comms-logging straggler machinery): an
        allgather of the recent mean step wall; gauge = max/min ratio.
        Collective — call only at points every host reaches (summary, the
        steps_per_print settle). Single-process: 1.0."""
        if not self.enabled:
            return 1.0
        ratio = 1.0
        try:
            import jax

            if jax.process_count() > 1 and self._recent:
                import numpy as np
                from jax.experimental import multihost_utils

                mine = float(sum(self._recent) / len(self._recent))
                allv = np.asarray(multihost_utils.process_allgather(
                    np.asarray([mine], np.float32))).reshape(-1)
                ratio = float(allv.max()) / max(float(allv.min()), 1e-9)
        except Exception:
            ratio = 1.0
        self._g_skew.set(ratio)
        if self.straggler_warn_ratio > 0 and ratio > self.straggler_warn_ratio:
            log_dist(
                f"stepscope: per-host step-time skew {ratio:.2f}x exceeds "
                f"straggler_warn_ratio={self.straggler_warn_ratio:g} — "
                "straggling host in the data-parallel group", ranks=[0])
        return ratio

    # ------------------------------------------------------------- summary
    def summary(self) -> dict:
        """The full anatomy as plain data."""
        if not self.enabled:
            return {"enabled": False}
        skew = self.refresh_skew()
        steps = max(self._steps, 1)
        wall = max(time.perf_counter() - self._t_created, 1e-9)
        phase_total = dict(sorted(self._phase_totals.items()))
        step_phase_s = {k: v for k, v in phase_total.items()
                        if k not in ("checkpoint",)}
        mfu = (self._model_flops_s / (self._peak * self._step_s)
               if self._peak > 0.0 and self._step_s > 0.0 else 0.0)
        return {
            "enabled": True,
            "steps": self._steps,
            "profiled_steps": self._profiled_steps,
            "step_seconds_total": self._step_s,
            "step_seconds_mean": self._step_s / steps,
            "phase_seconds_total": phase_total,
            "phase_seconds_mean": {k: v / steps
                                   for k, v in phase_total.items()},
            "phase_sum_over_step_ratio": (
                sum(step_phase_s.values()) / self._step_s
                if self._step_s > 0.0 else 0.0),
            "overlap_fraction": self.overlap_fraction(),
            "collective_seconds_estimated": self._coll_s,
            "collective_seconds_exposed": self._exposed_s,
            "goodput": self.goodput(),
            "goodput_seconds": {
                "productive": self._productive_s,
                "recompile": self._recompile_s,
                "checkpoint": self._checkpoint_s,
                "warmup": self._warmup_s,
                "profiling": self._profiling_s,
                "wall": wall,
            },
            "mfu": mfu,
            "flops_source": self.flops_source,
            "peak_flops": self._peak,
            "step_skew_ratio": skew,
        }
