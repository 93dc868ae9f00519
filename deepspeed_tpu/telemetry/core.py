"""The telemetry bus: one process-local singleton joining the metrics
registry, the span/event log, the HBM watermark sampler, and the exporters.

Disabled (the default) it is a no-op behind a single ``if not self.enabled``
flag check on every emit path — no clocks read, no dicts written — so the
training/inference hot paths pay nothing until a run opts in via the
``telemetry: {...}`` config block (see docs/OBSERVABILITY.md).

Event records share one shape across sinks::

    {"type": "span"|"event"|"gauge"|"snapshot", "name": ..., "ts": <unix s>,
     "step": <optional>, "dur_s": <spans>, ...free-form attrs...}
"""

from __future__ import annotations

import threading
import time
from contextlib import contextmanager

from deepspeed_tpu.telemetry.compile_watch import WATCH
from deepspeed_tpu.telemetry.registry import (
    Counter,
    Gauge,
    Histogram,
    MetricsRegistry,
)
from deepspeed_tpu.telemetry.tracing import Tracer


def _as_cfg_dict(cfg) -> dict:
    if cfg is None:
        return {}
    if isinstance(cfg, dict):
        return dict(cfg)
    if hasattr(cfg, "to_dict"):
        return dict(cfg.to_dict())
    # plain dataclass / namespace
    return {k: v for k, v in vars(cfg).items() if not k.startswith("_")}


class Telemetry:
    """Process-local telemetry bus (module singleton: ``TELEMETRY``)."""

    def __init__(self):
        self.enabled = False
        self.registry = MetricsRegistry()
        # the tracer object is permanent (engines cache a reference at
        # construction); only its ``enabled`` flag toggles with configure()
        self.tracer = Tracer(self.registry)
        self._slo = None
        self._costmeter = None
        self._compile_watch = None
        self._memledger = None
        self._fleet = None
        self._sinks: list = []
        self._prometheus = None
        self._sampler = None
        self._hbm_watermarks = True
        self._flush_interval = 100
        self._since_flush = 0
        self._lock = threading.Lock()

    # ------------------------------------------------------------- configure
    def configure(self, cfg=None, monitor=None, **overrides) -> "Telemetry":
        """(Re)build sinks from a ``TelemetryConfig`` / dict / kwargs.

        Idempotent: reconfiguring tears down the previous sinks and HTTP
        server first, so multiple engines in one process share one bus.
        """
        opts = _as_cfg_dict(cfg)
        opts.update(overrides)
        with self._lock:
            self._teardown_locked()
            self.enabled = bool(opts.get("enabled", True))
            if not self.enabled:
                return self
            self._hbm_watermarks = bool(opts.get("hbm_watermarks", True))
            self._flush_interval = max(1, int(opts.get("flush_interval_events",
                                                       100)))
            jsonl_path = opts.get("jsonl_path")
            if jsonl_path:
                from deepspeed_tpu.telemetry.exporters import JsonlSink

                self._sinks.append(JsonlSink(str(jsonl_path)))
            if opts.get("monitor_sink") and monitor is not None:
                from deepspeed_tpu.telemetry.exporters import MonitorSink

                self._sinks.append(MonitorSink(monitor))
            prom = opts.get("prometheus") or {}
            if prom.get("enabled"):
                from deepspeed_tpu.telemetry.exporters import PrometheusExporter

                self._prometheus = PrometheusExporter(
                    self.registry,
                    host=str(prom.get("host", "127.0.0.1")),
                    port=int(prom.get("port", 9464)),
                )
            tracing = opts.get("tracing") or {}
            if tracing is True:
                tracing = {"enabled": True}
            stepscope = opts.get("stepscope") or {}
            if stepscope is True:
                stepscope = {"enabled": True}
            if stepscope.get("enabled") and not tracing.get("enabled"):
                # step-anatomy spans land in the trace ring; an enabled
                # stepscope without explicit tracing opts implies tracing on
                tracing = {"enabled": True}
            if tracing.get("enabled"):
                self.tracer.configure(
                    enabled=True,
                    sample_rate=float(tracing.get("sample_rate", 1.0)),
                    ring_capacity=int(tracing.get("ring_capacity", 4096)),
                )
            cm = opts.get("costmeter") or {}
            if cm is True:
                cm = {"enabled": True}
            slo = opts.get("slo") or {}
            if slo is True:
                slo = {"enabled": True}
            if slo.get("enabled"):
                from deepspeed_tpu.telemetry.slo import (
                    SloMonitor,
                    default_class_objectives,
                    default_objectives,
                )

                # per-SLA-class objectives: explicit per-class threshold
                # dicts, bare True for the defaults, or implied by an
                # enabled costmeter (class accounting is its whole point)
                classes = slo.get("classes")
                if classes is None and cm.get("enabled"):
                    classes = True
                class_objs = None
                if classes is True:
                    class_objs = default_class_objectives(
                        window_s=float(slo.get("window_s", 300.0)),
                        target=float(slo.get("target", 0.99)))
                elif classes:
                    class_objs = {
                        cls: default_objectives(
                            ttft_threshold_s=float(
                                c.get("ttft_threshold_s", 0.5)),
                            decode_threshold_s=float(
                                c.get("decode_threshold_s", 0.05)),
                            target=float(c.get("target",
                                               slo.get("target", 0.99))),
                            window_s=float(c.get("window_s",
                                                 slo.get("window_s", 300.0))),
                        ) for cls, c in classes.items()}
                self._slo = SloMonitor(
                    default_objectives(
                        ttft_threshold_s=float(
                            slo.get("ttft_threshold_s", 0.5)),
                        decode_threshold_s=float(
                            slo.get("decode_threshold_s", 0.05)),
                        target=float(slo.get("target", 0.99)),
                        window_s=float(slo.get("window_s", 300.0)),
                    ),
                    self.registry,
                    burn_threshold=float(slo.get("burn_threshold", 1.0)),
                    replica=slo.get("replica"),
                    class_objectives=class_objs,
                )
                self._slo.refresh_gauges()
            if cm.get("enabled"):
                from deepspeed_tpu.telemetry.costmeter import CostMeter

                self._costmeter = CostMeter(
                    self.registry,
                    max_tenants=int(cm.get("max_tenants", 32)),
                    window_s=float(cm.get("window_s", 300.0)),
                    top_k=int(cm.get("top_k", 10)),
                    fairness_weight=float(cm.get("fairness_weight", 1.0)),
                )
            if opts.get("compile_metrics", True):
                self._compile_watch = WATCH.attach(self.registry)
            fleet = opts.get("fleet") or {}
            if fleet is True:
                fleet = {"enabled": True}
            if fleet.get("enabled"):
                from deepspeed_tpu.telemetry.fleet import FleetReporter

                self._fleet = FleetReporter(
                    self,
                    out_dir=str(fleet.get("dir", "runs/fleet")),
                    worker=fleet.get("worker"),
                    labels=fleet.get("labels"),
                    interval_s=float(fleet.get("interval_s", 0.0)),
                    spill_traces=bool(fleet.get("spill_traces", True)),
                ).start()
            ml = opts.get("memledger") or {}
            if ml is True:
                ml = {"enabled": True}
            if ml.get("enabled"):
                from deepspeed_tpu.telemetry.memledger import MemoryLedger

                self._memledger = MemoryLedger(
                    self,
                    census_interval_steps=int(
                        ml.get("census_interval_steps", 50)),
                    drift_threshold=float(ml.get("drift_threshold", 0.05)),
                    drift_consecutive=int(ml.get("drift_consecutive", 3)),
                    report_dir=str(ml.get("report_dir", "oom_reports")),
                )
        self.event("telemetry/configured",
                   sinks=[type(s).__name__ for s in self._sinks],
                   prometheus_port=(self._prometheus.port
                                    if self._prometheus else None),
                   tracing=self.tracer.enabled,
                   slo=self._slo is not None,
                   costmeter=self._costmeter is not None,
                   memledger=self._memledger is not None,
                   fleet=(self._fleet.worker if self._fleet else None))
        return self

    @property
    def prometheus_port(self) -> int | None:
        return self._prometheus.port if self._prometheus else None

    # ------------------------------------------------------------- metrics
    def counter(self, name: str, help: str = "") -> Counter:
        return self.registry.counter(name, help)

    def gauge(self, name: str, help: str = "") -> Gauge:
        return self.registry.gauge(name, help)

    def histogram(self, name: str, help: str = "", **kw) -> Histogram:
        return self.registry.histogram(name, help, **kw)

    # ------------------------------------------------------------- events
    def emit(self, record: dict) -> None:
        """Append one record to every sink (stamps ``ts`` if absent)."""
        if not self.enabled:
            return
        record.setdefault("ts", time.time())
        for sink in self._sinks:
            try:
                sink.emit(record)
            except Exception:
                pass  # a broken sink must never take down the step loop
        self._since_flush += 1
        if self._since_flush >= self._flush_interval:
            self.flush()

    def event(self, name: str, step: int | None = None, **attrs) -> None:
        if not self.enabled:
            return
        record = {"type": "event", "name": name}
        if step is not None:
            record["step"] = int(step)
        record.update({k: v for k, v in attrs.items() if v is not None})
        self.emit(record)

    def emit_span(self, name: str, dur_s: float, step: int | None = None,
                  **attrs) -> None:
        """Record a pre-measured span: JSONL record + latency histogram."""
        if not self.enabled:
            return
        record = {"type": "span", "name": name, "dur_s": float(dur_s)}
        if step is not None:
            record["step"] = int(step)
        record.update({k: v for k, v in attrs.items() if v is not None})
        self.emit(record)
        self.registry.histogram(
            "span_seconds", "span durations by name").observe(dur_s, name=name)

    @contextmanager
    def span(self, name: str, step: int | None = None, **attrs):
        """Context manager measuring wall clock around a block."""
        if not self.enabled:
            yield
            return
        t0 = time.perf_counter()
        try:
            yield
        finally:
            self.emit_span(name, time.perf_counter() - t0, step=step, **attrs)

    def sample_memory(self, step: int | None = None) -> dict:
        """Per-step HBM watermark gauges (no device sync)."""
        if not self.enabled:
            return {}
        led = self._memledger
        if led is not None:
            led.maybe_census(step)
        if not self._hbm_watermarks:
            return {}
        if self._sampler is None:
            from deepspeed_tpu.telemetry.memory import HbmWatermarkSampler

            self._sampler = HbmWatermarkSampler(self)
        return self._sampler.sample(step)

    @property
    def memledger(self):
        """The configured :class:`MemoryLedger`, or None (hot paths guard
        on this one attribute read — off means zero allocations)."""
        return self._memledger

    # ------------------------------------------------------------- tracing
    def export_chrome_trace(self, trace_id: str | None = None) -> dict:
        """Chrome trace-event JSON of the span ring (Perfetto-loadable)."""
        return self.tracer.export_chrome(trace_id)

    def dump_trace(self, path: str | None = None,
                   trace_id: str | None = None, fleet=False) -> dict:
        """Export the span ring as Chrome trace JSON; writes ``path`` when
        given, returns the trace dict either way.

        ``fleet=True`` merges every worker's spilled ring from the
        configured fleet dir (or pass a fleet-dir path as ``fleet``) into
        ONE timeline with a per-process track per worker — see
        :func:`deepspeed_tpu.telemetry.fleet.merge_fleet_traces`.
        """
        if fleet:
            from deepspeed_tpu.telemetry.fleet import merge_fleet_traces

            fleet_dir = fleet if isinstance(fleet, str) else (
                self._fleet.out_dir if self._fleet is not None
                else "runs/fleet")
            trace = merge_fleet_traces(fleet_dir, local_tracer=self.tracer,
                                       trace_id=trace_id)
            if path is not None:
                import json
                import os

                parent = os.path.dirname(os.path.abspath(path))
                if parent:
                    os.makedirs(parent, exist_ok=True)
                with open(path, "w") as f:
                    json.dump(trace, f)
            return trace
        if path is None:
            return self.tracer.export_chrome(trace_id)
        return self.tracer.dump(path, trace_id)

    # ------------------------------------------------------------- fleet
    @property
    def fleet(self):
        """The configured :class:`FleetReporter`, or None (hot paths guard
        on this one attribute read)."""
        return self._fleet

    # ------------------------------------------------------------- slo
    @property
    def slo(self):
        """The configured :class:`SloMonitor`, or None."""
        return self._slo

    def observe_slo(self, objective: str, value_s: float,
                    sla_class: str | None = None) -> None:
        """Record a request latency against an SLO objective (no-op when
        no monitor is configured). ``sla_class`` additionally scores the
        sample against that class's own objectives when configured."""
        slo = self._slo
        if slo is not None:
            slo.record(objective, value_s, sla_class=sla_class)

    # ------------------------------------------------------------- costmeter
    @property
    def costmeter(self):
        """The configured :class:`CostMeter`, or None (the engine guards
        every metering seam on this one attribute read — off means zero
        costmeter code runs)."""
        return self._costmeter

    # ------------------------------------------------------------- compile
    @property
    def compile_watch(self):
        """The process's :class:`CompileWatch` while it feeds this bus's
        registry (``compile_metrics``), else None."""
        return self._compile_watch

    # ------------------------------------------------------------- snapshot
    def snapshot(self) -> dict:
        """The full registry as plain data (JSON-serializable), and under
        ``startup`` the compile watch's start-up phases and program builds
        (None while its listeners are not installed; it listens whether or
        not this bus is enabled)."""
        return {"ts": time.time(), "metrics": self.registry.snapshot(),
                "startup": WATCH.snapshot()}

    def dump(self, path: str) -> dict:
        """Persist ``snapshot()`` as a JSON file; returns the snapshot."""
        import json
        import os

        snap = self.snapshot()
        parent = os.path.dirname(os.path.abspath(path))
        if parent:
            os.makedirs(parent, exist_ok=True)
        with open(path, "w") as f:
            json.dump(snap, f, indent=2, default=str)
        return snap

    # ------------------------------------------------------------- lifecycle
    def flush(self) -> None:
        self._since_flush = 0
        for sink in self._sinks:
            try:
                sink.flush()
            except Exception:
                pass

    def close(self) -> None:
        """Emit a final registry snapshot record, then tear down all sinks."""
        if self.enabled and self._sinks:
            self.emit({"type": "snapshot", **self.snapshot()})
        with self._lock:
            self._teardown_locked()
        self.enabled = False

    def reset(self) -> None:
        """Back to the pristine disabled state (test isolation): the compile
        watch's logs empty too, its listeners stay."""
        with self._lock:
            self._teardown_locked()
        self.enabled = False
        self.registry.reset()
        WATCH.reset()

    def _teardown_locked(self) -> None:
        for sink in self._sinks:
            try:
                sink.close()
            except Exception:
                pass
        self._sinks = []
        if self._prometheus is not None:
            try:
                self._prometheus.close()
            except Exception:
                pass
            self._prometheus = None
        self._sampler = None
        self._memledger = None
        if self._fleet is not None:
            try:
                self._fleet.stop(final_flush=False)
            except Exception:
                pass
            self._fleet = None
        self._since_flush = 0
        self.tracer.reset()
        self._slo = None
        self._costmeter = None
        if self._compile_watch is not None:
            # the listeners and the logs are the process's and stay
            self._compile_watch.detach()
            self._compile_watch = None


TELEMETRY = Telemetry()
