"""Device-timeline profiler: measured overlap, wire time, kernel attribution.

Everything stepscope reports about the device is estimated from host-side
timestamps and an analytic wire-time model.  This module captures the actual
device timeline through ``jax.profiler.start_trace``/``stop_trace`` over a
bounded window, classifies every XLA op (collective / compute / copy /
infeed-outfeed), and derives *measured* metrics from the classified intervals:

- ``train_overlap_fraction{source="measured"}`` — collective time overlapped
  with compute divided by total collective time (interval-union math, not
  per-op pairing);
- per-collective wire-time histograms (``devprof_collective_seconds{op=}``);
- H2D/D2H copy seconds, device idle/bubble fraction, and a top-K op table.

Captured device ops are also merged as spans into the host Perfetto trace
ring (telemetry.tracing), parented under the smallest stepscope phase span
that contains them, so host phases and device kernels render as one nested
timeline in ``chrome://tracing`` / Perfetto.

Two triggers exist upstream of this module: the training engine captures a
window every ``telemetry.stepscope.profile_interval_steps`` steps, and the
serving frontend exposes ``GET /debug/profile?steps=N`` (via
:func:`capture_serving`). ``tests/unit/test_devprof.py`` drives both.

Design constraints honoured here:

- **Single capture per process.**  jax allows one active profiler session;
  a module-level non-blocking lock models that, and doubles as the
  concurrent-capture rejection for ``/debug/profile`` (HTTP 409).
- **Backend-independent parser.**  The Chrome-trace parser and all derived
  math are pure stdlib — CPU-only CI exercises the full path against real
  CPU captures and a checked-in synthetic fixture.
- **Zero allocation when off.**  Nothing in this module runs on the hot path
  unless a capture window is open; the engine guards every call site on a
  plain attribute check (pinned by tracemalloc in tests/unit/test_devprof.py).
- **Bounded disk.**  Capture dirs default under ``runs/`` (gitignored) and
  are rotated: at most ``keep`` capture subdirectories survive.

Clock alignment: trace-event timestamps live in the profiler's own
microsecond epoch.  ``begin()`` emits a ``jax.profiler.TraceAnnotation``
anchor and records ``time.perf_counter()`` at that instant; the parser finds
the anchor event and shifts every device op by
``t_anchor_host − anchor_ts_us·1e-6`` so device spans land in the same
perf_counter domain the host Tracer ring uses.
"""

from __future__ import annotations

import glob
import gzip
import json
import logging
import os
import shutil
import threading
import time
import uuid
from typing import Any, Dict, List, Optional, Sequence, Tuple

from deepspeed_tpu.telemetry.tracing import TraceContext, Tracer, _new_span_id

logger = logging.getLogger(__name__)

ANCHOR_NAME = "devprof/anchor"

# One jax profiler session may exist per process (jax raises on a second
# start_trace).  This lock models that limit and backs the HTTP 409 path.
_CAPTURE_LOCK = threading.Lock()

#: Wire-time histogram buckets.  Collective device ops run µs→s; the default
#: telemetry latency buckets start at 0.5 ms and would collapse everything
#: into one bucket on small models.
COLLECTIVE_BUCKETS = (
    1e-6, 5e-6, 1e-5, 5e-5, 1e-4, 5e-4, 1e-3, 5e-3,
    1e-2, 5e-2, 0.1, 0.5, 1.0, 5.0,
)

# Op families, matched as prefixes of the normalised family name (lowercase,
# '%' and trailing '.<id>' / '-start' / '-done' stripped).
_COLLECTIVE_PREFIXES = (
    "all-reduce", "allreduce",
    "all-gather", "allgather",
    "reduce-scatter", "reducescatter",
    "all-to-all", "alltoall",
    "collective-permute", "collectivepermute",
    "collective-broadcast",
    "psum", "pmean", "ppermute",
    "send", "recv",
)
_COPY_PREFIXES = ("copy", "memcpy", "transpose-copy", "dynamic-memcpy")
_INFEED_PREFIXES = ("infeed", "outfeed", "host-transfer")

CLASS_COLLECTIVE = "collective"
CLASS_COMPUTE = "compute"
CLASS_COPY = "copy"
CLASS_INFEED = "infeed_outfeed"
OP_CLASSES = (CLASS_COLLECTIVE, CLASS_COMPUTE, CLASS_COPY, CLASS_INFEED)


# --------------------------------------------------------------------------
# Op-name heuristics
# --------------------------------------------------------------------------

def op_family(name: str) -> str:
    """Collapse an HLO op instance name to its bounded-cardinality family.

    ``%all-gather-start.3`` → ``all-gather``; ``fusion.12`` → ``fusion``;
    ``MemcpyH2D`` → ``memcpyh2d``.  Families are what metric labels and
    merged span names are keyed on, so they must stay bounded.
    """
    fam = name.strip().lower().lstrip("%")
    # strip trailing ".<digits>" instance id
    dot = fam.rfind(".")
    if dot > 0 and fam[dot + 1:].isdigit():
        fam = fam[:dot]
    for suffix in ("-start", "-done"):
        if fam.endswith(suffix):
            fam = fam[: -len(suffix)]
    return fam or "unknown"


def classify_op(name: str) -> str:
    """Classify a device op name into collective / compute / copy / infeed."""
    fam = op_family(name)
    for p in _INFEED_PREFIXES:
        if fam.startswith(p):
            return CLASS_INFEED
    for p in _COLLECTIVE_PREFIXES:
        if fam.startswith(p):
            return CLASS_COLLECTIVE
    for p in _COPY_PREFIXES:
        if fam.startswith(p):
            return CLASS_COPY
    if "h2d" in fam or "d2h" in fam:
        return CLASS_COPY
    return CLASS_COMPUTE


def _copy_direction(fam: str) -> str:
    if "h2d" in fam:
        return "h2d"
    if "d2h" in fam:
        return "d2h"
    return "device"


# --------------------------------------------------------------------------
# Chrome-trace parsing (pure stdlib; exercised against the synthetic fixture)
# --------------------------------------------------------------------------

def parse_chrome_trace(
    trace: Dict[str, Any], anchor_name: str = ANCHOR_NAME
) -> Tuple[List[Dict[str, Any]], Optional[float]]:
    """Walk Chrome trace events and extract the device-op timeline.

    Returns ``(ops, anchor_ts_us)``.  Each op is a dict with keys
    ``name``/``family``/``cls``/``t0``/``t1`` where t0/t1 are seconds in the
    trace's own epoch (shift with :func:`shift_ops` to align clocks).

    A complete event counts as a device op when it carries an ``hlo_op``
    arg (how jax tags XLA ops on CPU/GPU) or when it sits on a thread named
    ``XLA Ops`` of a ``/device:`` process (how TPU device tracks look).
    Restricting the device-pid rule to the "XLA Ops" lane avoids
    double-counting the aggregate "Steps"/"XLA Modules" lanes.
    """
    events = trace.get("traceEvents") or []
    proc_names: Dict[Any, str] = {}
    thread_names: Dict[Tuple[Any, Any], str] = {}
    for ev in events:
        if ev.get("ph") != "M":
            continue
        args = ev.get("args") or {}
        if ev.get("name") == "process_name":
            proc_names[ev.get("pid")] = str(args.get("name", ""))
        elif ev.get("name") == "thread_name":
            thread_names[(ev.get("pid"), ev.get("tid"))] = str(args.get("name", ""))

    ops: List[Dict[str, Any]] = []
    anchor_ts_us: Optional[float] = None
    for ev in events:
        if ev.get("ph") != "X":
            continue
        name = ev.get("name")
        ts = ev.get("ts")
        if name is None or ts is None:
            continue
        if name == anchor_name:
            anchor_ts_us = float(ts)
            continue
        args = ev.get("args") or {}
        hlo = args.get("hlo_op")
        if hlo is None:
            pid = ev.get("pid")
            tid = ev.get("tid")
            if "/device:" not in proc_names.get(pid, ""):
                continue
            if "xla ops" not in thread_names.get((pid, tid), "").lower():
                continue
            op_name = str(name)
        else:
            op_name = str(hlo)
        dur = float(ev.get("dur", 0.0) or 0.0)
        if dur <= 0.0:
            continue
        t0 = float(ts) * 1e-6
        fam = op_family(op_name)
        ops.append(
            {
                "name": op_name,
                "family": fam,
                "cls": classify_op(op_name),
                "t0": t0,
                "t1": t0 + dur * 1e-6,
            }
        )
    ops.sort(key=lambda o: o["t0"])
    return ops, anchor_ts_us


def shift_ops(ops: List[Dict[str, Any]], offset_s: float) -> List[Dict[str, Any]]:
    """Shift op timestamps in place by ``offset_s`` (trace → host clock)."""
    for op in ops:
        op["t0"] += offset_s
        op["t1"] += offset_s
    return ops


def load_trace_dir(trace_dir: str) -> Tuple[Optional[Dict[str, Any]], Optional[str]]:
    """Load the newest ``*.trace.json[.gz]`` written under ``trace_dir``.

    jax writes ``<dir>/plugins/profile/<timestamp>/<host>.trace.json.gz``;
    we also accept a flat layout for tests.  Returns ``(trace, path)`` or
    ``(None, None)`` when nothing parseable exists.
    """
    patterns = (
        os.path.join(trace_dir, "plugins", "profile", "*", "*.trace.json.gz"),
        os.path.join(trace_dir, "plugins", "profile", "*", "*.trace.json"),
        os.path.join(trace_dir, "*.trace.json.gz"),
        os.path.join(trace_dir, "*.trace.json"),
    )
    candidates: List[str] = []
    for pat in patterns:
        candidates.extend(glob.glob(pat))
    if not candidates:
        return None, None
    path = max(candidates, key=os.path.getmtime)
    try:
        if path.endswith(".gz"):
            with gzip.open(path, "rt", encoding="utf-8") as f:
                return json.load(f), path
        with open(path, "r", encoding="utf-8") as f:
            return json.load(f), path
    except (OSError, ValueError) as exc:  # truncated/corrupt capture
        logger.warning("devprof: failed to load trace %s: %s", path, exc)
        return None, None


# --------------------------------------------------------------------------
# Interval math + derived timeline metrics
# --------------------------------------------------------------------------

def _union(intervals: Sequence[Tuple[float, float]]) -> List[Tuple[float, float]]:
    """Merge overlapping/touching intervals; returns sorted disjoint spans."""
    if not intervals:
        return []
    ivs = sorted(intervals)
    out = [list(ivs[0])]
    for a, b in ivs[1:]:
        if a <= out[-1][1]:
            if b > out[-1][1]:
                out[-1][1] = b
        else:
            out.append([a, b])
    return [(a, b) for a, b in out]


def _union_len(intervals: Sequence[Tuple[float, float]]) -> float:
    return sum(b - a for a, b in _union(intervals))


def _intersect_len(iv: Tuple[float, float], union: Sequence[Tuple[float, float]]) -> float:
    a, b = iv
    total = 0.0
    for u0, u1 in union:
        if u1 <= a:
            continue
        if u0 >= b:
            break
        total += min(b, u1) - max(a, u0)
    return total


def derive_timeline(
    ops: Sequence[Dict[str, Any]],
    window: Optional[Tuple[float, float]] = None,
    top_k: int = 12,
) -> Dict[str, Any]:
    """Derive measured metrics from a classified device-op timeline.

    ``overlap_fraction_measured`` is interval-union math: the union of
    compute intervals is intersected with each collective interval; the
    fraction is overlapped-collective-time / total-collective-time.  With no
    collectives in the window (single-device runs) it is vacuously 1.0 —
    there is no wire time to expose.
    """
    class_ivs: Dict[str, List[Tuple[float, float]]] = {c: [] for c in OP_CLASSES}
    class_ops: Dict[str, int] = {c: 0 for c in OP_CLASSES}
    fam_seconds: Dict[str, Dict[str, Any]] = {}
    copy_seconds = {"h2d": 0.0, "d2h": 0.0, "device": 0.0}
    for op in ops:
        cls = op["cls"]
        iv = (op["t0"], op["t1"])
        class_ivs[cls].append(iv)
        class_ops[cls] += 1
        fam = op["family"]
        slot = fam_seconds.setdefault(fam, {"op": fam, "class": cls, "seconds": 0.0, "count": 0})
        slot["seconds"] += iv[1] - iv[0]
        slot["count"] += 1
        if cls == CLASS_COPY:
            copy_seconds[_copy_direction(fam)] += iv[1] - iv[0]

    class_seconds = {c: _union_len(class_ivs[c]) for c in OP_CLASSES}
    compute_union = _union(class_ivs[CLASS_COMPUTE])
    collective_s = sum(b - a for a, b in class_ivs[CLASS_COLLECTIVE])
    overlapped_s = sum(
        _intersect_len(iv, compute_union) for iv in class_ivs[CLASS_COLLECTIVE]
    )
    overlap = (overlapped_s / collective_s) if collective_s > 0.0 else 1.0

    all_ivs = [iv for ivs in class_ivs.values() for iv in ivs]
    busy_s = _union_len(all_ivs)
    if window is None and all_ivs:
        window = (min(a for a, _ in all_ivs), max(b for _, b in all_ivs))
    window_s = (window[1] - window[0]) if window else 0.0
    idle_fraction = (
        max(0.0, 1.0 - busy_s / window_s) if window_s > 0.0 else 0.0
    )

    top_ops = sorted(fam_seconds.values(), key=lambda s: s["seconds"], reverse=True)[:top_k]
    collectives = [s for s in fam_seconds.values() if s["class"] == CLASS_COLLECTIVE]
    collectives.sort(key=lambda s: s["seconds"], reverse=True)
    return {
        "op_count": len(ops),
        "window_s": window_s,
        "device_busy_s": busy_s,
        "idle_fraction": idle_fraction,
        "class_seconds": class_seconds,
        "class_ops": class_ops,
        "collective_seconds": collective_s,
        "collective_overlapped_seconds": overlapped_s,
        "overlap_fraction_measured": overlap,
        "copy_seconds": copy_seconds,
        "top_ops": top_ops,
        "collectives": collectives,
    }


# --------------------------------------------------------------------------
# Merging device ops into the host Perfetto trace ring
# --------------------------------------------------------------------------

_HOST_PARENT_PREFIXES = ("train/phase/", "train/step", "engine/", "request/")


def merge_into_ring(
    tracer: Optional[Tracer],
    ops: Sequence[Dict[str, Any]],
    max_ops: int = 768,
) -> int:
    """Retro-record device ops as spans in the host trace ring.

    Each op is parented under the *smallest* host span (stepscope phase,
    step, or serving span) whose interval contains the op's midpoint, so the
    Perfetto export nests device kernels under the owning host phase.  Ops
    with no containing host span hang off a synthetic ``device/window``
    root.  At most ``max_ops`` ops are merged (largest by duration) so a
    dense capture cannot evict the host spans from the bounded ring.
    """
    if tracer is None or not tracer.enabled or not ops:
        return 0
    hosts = [
        s
        for s in tracer.snapshot()
        if s["name"].startswith(_HOST_PARENT_PREFIXES)
    ]
    host_ivs = [(s["t0"], s["t0"] + s["dur_s"], s) for s in hosts]
    sel = sorted(ops, key=lambda o: o["t1"] - o["t0"], reverse=True)[:max_ops]
    sel.sort(key=lambda o: o["t0"])

    orphan_ctx: Optional[TraceContext] = None
    orphan_window: Optional[List[float]] = None
    merged = 0
    for op in sel:
        mid = 0.5 * (op["t0"] + op["t1"])
        best = None
        best_dur = float("inf")
        for h0, h1, span in host_ivs:
            if h0 <= mid <= h1 and (h1 - h0) < best_dur:
                best, best_dur = span, h1 - h0
        if best is not None:
            ctx = TraceContext(best["trace_id"], _new_span_id(), best["span_id"])
        else:
            if orphan_ctx is None:
                orphan_ctx = TraceContext(uuid.uuid4().hex, _new_span_id(), None)
                orphan_window = [op["t0"], op["t1"]]
            orphan_window[0] = min(orphan_window[0], op["t0"])
            orphan_window[1] = max(orphan_window[1], op["t1"])
            ctx = TraceContext(orphan_ctx.trace_id, _new_span_id(), orphan_ctx.span_id)
        tracer.finish(
            ctx,
            f"device/{op['cls']}/{op['family']}",
            op["t0"],
            op["t1"],
            hlo_op=op["name"],
            device=True,
        )
        merged += 1
    if orphan_ctx is not None:
        tracer.finish(
            orphan_ctx, "device/window", orphan_window[0], orphan_window[1], device=True
        )
    return merged


# --------------------------------------------------------------------------
# Capture driver
# --------------------------------------------------------------------------

class DeviceProfiler:
    """On-demand bounded-window device capture with rotation and metrics.

    Lifecycle: ``begin()`` (acquires the process-wide capture slot, starts
    the jax trace, stamps the clock anchor) → ``stop()`` (ends the jax
    session; call after settling the step so the window closes cleanly) →
    ``finish()`` (parse, derive, export metrics, merge into the trace ring,
    rotate old capture dirs, release the slot).  ``end()`` is
    stop+finish for one-shot use.  All methods are safe to call when no
    capture is active.
    """

    def __init__(
        self,
        telemetry: Any = None,
        out_dir: str = os.path.join("runs", "devprof"),
        keep: int = 4,
        merge_max_ops: int = 768,
    ) -> None:
        self.telemetry = telemetry
        self.out_dir = out_dir
        self.keep = max(1, int(keep))
        self.merge_max_ops = int(merge_max_ops)
        self.capturing = False
        self._stopped = False
        self._seq = 0
        self._dir: Optional[str] = None
        self._tag = "capture"
        self._t_anchor = 0.0
        self._t_begin = 0.0
        self._t_stop = 0.0
        self.last: Optional[Dict[str, Any]] = None

    # -- lifecycle -----------------------------------------------------

    def begin(self, tag: str = "capture") -> bool:
        """Start a capture window; False if one is already active anywhere."""
        if self.capturing:
            return False
        if not _CAPTURE_LOCK.acquire(blocking=False):
            self._count_rejected(tag)
            return False
        self._seq += 1
        # pid-scoped dir name: multiple worker processes sharing one
        # runs/devprof must never collide on cap-{seq} (each process's
        # sequence starts at 1), and rotation below stays per-worker
        cap_dir = os.path.join(self.out_dir,
                               f"cap-{os.getpid()}-{self._seq:06d}")
        try:
            import jax

            os.makedirs(cap_dir, exist_ok=True)
            jax.profiler.start_trace(cap_dir)
            with jax.profiler.TraceAnnotation(ANCHOR_NAME):
                self._t_anchor = time.perf_counter()
        except Exception as exc:  # another session (StepTracer) or no backend
            logger.warning("devprof: start_trace failed (%s); capture skipped", exc)
            shutil.rmtree(cap_dir, ignore_errors=True)
            _CAPTURE_LOCK.release()
            self._count_rejected(tag)
            return False
        self._dir = cap_dir
        self._tag = tag
        self._t_begin = time.perf_counter()
        self._stopped = False
        self.capturing = True
        return True

    def stop(self) -> None:
        """End the jax profiler session (parse deferred to ``finish``)."""
        if not self.capturing or self._stopped:
            return
        try:
            import jax

            jax.profiler.stop_trace()
        except Exception as exc:
            logger.warning("devprof: stop_trace failed: %s", exc)
        self._t_stop = time.perf_counter()
        self._stopped = True

    def finish(self, kind: str = "train", tracer: Optional[Tracer] = None) -> Optional[Dict[str, Any]]:
        """Parse the closed window, export metrics, merge, rotate, release."""
        if not self.capturing:
            return None
        if not self._stopped:
            self.stop()
        self.capturing = False
        self._stopped = False
        try:
            trace, path = load_trace_dir(self._dir)
            ops: List[Dict[str, Any]] = []
            anchor_us: Optional[float] = None
            if trace is not None:
                ops, anchor_us = parse_chrome_trace(trace)
            if ops:
                if anchor_us is not None:
                    shift_ops(ops, self._t_anchor - anchor_us * 1e-6)
                else:
                    # no anchor event survived; pin the window end to stop()
                    shift_ops(ops, self._t_stop - max(o["t1"] for o in ops))
            summary = derive_timeline(ops)
            summary["wall_window_s"] = max(0.0, self._t_stop - self._t_begin)
            summary["trigger"] = self._tag
            self._export_metrics(summary, ops, kind)
            merged = 0
            tr = tracer
            if tr is None and self.telemetry is not None:
                tr = getattr(self.telemetry, "tracer", None)
            if tr is not None:
                merged = merge_into_ring(tr, ops, self.merge_max_ops)
            self.last = {
                "kind": kind,
                "summary": summary,
                "ops": ops,
                "merged_spans": merged,
                "trace_path": path,
                "trace_dir": self._dir,
            }
            self._rotate()
            return self.last
        finally:
            self._dir = None
            _CAPTURE_LOCK.release()

    def end(self, kind: str = "train", tracer: Optional[Tracer] = None) -> Optional[Dict[str, Any]]:
        """Convenience: ``stop()`` then ``finish()``."""
        if not self.capturing:
            return None
        self.stop()
        return self.finish(kind=kind, tracer=tracer)

    def abort(self) -> None:
        """Tear down an open window without parsing (error paths)."""
        if not self.capturing:
            return
        self.stop()
        self.capturing = False
        self._stopped = False
        if self._dir:
            shutil.rmtree(self._dir, ignore_errors=True)
            self._dir = None
        _CAPTURE_LOCK.release()

    # -- internals -----------------------------------------------------

    def _rotate(self) -> None:
        # per-worker rotation: only THIS process's captures are eligible —
        # a sibling worker profiling into the same shared dir must never
        # have its captures deleted out from under it
        try:
            caps = sorted(glob.glob(
                os.path.join(self.out_dir, f"cap-{os.getpid()}-*")))
            for stale in caps[: -self.keep]:
                shutil.rmtree(stale, ignore_errors=True)
        except OSError:
            pass

    def _count_rejected(self, tag: str) -> None:
        tel = self.telemetry
        if tel is not None and getattr(tel, "enabled", False):
            tel.registry.counter(
                "devprof_captures_rejected_total",
                "Capture attempts rejected because a profiler session was active.",
            ).inc(1, trigger=tag)

    def _export_metrics(self, summary: Dict[str, Any], ops: Sequence[Dict[str, Any]], kind: str) -> None:
        tel = self.telemetry
        if tel is None or not getattr(tel, "enabled", False):
            return
        reg = tel.registry
        reg.counter(
            "devprof_captures_total", "Completed device-profile capture windows."
        ).inc(1, trigger=summary.get("trigger", "capture"))
        reg.gauge(
            "devprof_overlap_fraction",
            "Measured collective/compute overlap fraction from the last capture.",
        ).set(summary["overlap_fraction_measured"], kind=kind)
        reg.gauge(
            "devprof_idle_fraction",
            "Device idle/bubble fraction of the last capture window.",
        ).set(summary["idle_fraction"], kind=kind)
        g_class = reg.gauge(
            "devprof_class_seconds",
            "Busy seconds per op class in the last capture window.",
        )
        for cls, secs in summary["class_seconds"].items():
            g_class.set(secs, **{"class": cls, "kind": kind})
        c_ops = reg.counter(
            "devprof_ops_total", "Device ops observed across capture windows."
        )
        for cls, n in summary["class_ops"].items():
            if n:
                c_ops.inc(n, **{"class": cls})
        h_coll = reg.histogram(
            "devprof_collective_seconds",
            "Per-collective device wire time (one observation per op).",
            buckets=COLLECTIVE_BUCKETS,
        )
        for op in ops:
            if op["cls"] == CLASS_COLLECTIVE:
                h_coll.observe(op["t1"] - op["t0"], op=op["family"])
        c_copy = reg.counter(
            "devprof_copy_seconds_total", "Copy seconds by direction across captures."
        )
        for direction, secs in summary["copy_seconds"].items():
            if secs:
                c_copy.inc(secs, direction=direction)
        g_top = reg.gauge(
            "devprof_top_op_seconds",
            "Seconds per op family (top-K of the last capture window).",
        )
        for slot in summary["top_ops"]:
            g_top.set(slot["seconds"], op=slot["op"])
        if kind == "train":
            reg.gauge(
                "train_overlap_fraction",
                "Fraction of collective time hidden under compute.",
            ).set(summary["overlap_fraction_measured"], source="measured")


# --------------------------------------------------------------------------
# Serving-side capture (GET /debug/profile)
# --------------------------------------------------------------------------

def capture_serving(
    loops: Sequence[Any],
    steps: int = 8,
    max_wait_s: float = 5.0,
    poll_s: float = 0.005,
    telemetry: Any = None,
    out_dir: str = os.path.join("runs", "devprof"),
    profiler: Optional[DeviceProfiler] = None,
) -> Optional[Dict[str, Any]]:
    """Capture a device window spanning ~``steps`` engine-loop steps.

    Polls the loops' step counters until the requested number of steps has
    elapsed or ``max_wait_s`` passes (idle engines produce an empty but
    valid capture).  Returns a JSON-serializable summary, or None when a
    capture is already in progress (the frontend maps that to HTTP 409).
    """
    prof = profiler or DeviceProfiler(telemetry=telemetry, out_dir=out_dir)

    def _count() -> int:
        return sum(int(getattr(lp, "steps", 0)) for lp in loops)

    base = _count()
    if not prof.begin(tag="http"):
        return None
    t0 = time.perf_counter()
    deadline = t0 + max(0.05, max_wait_s)
    while time.perf_counter() < deadline and _count() - base < steps:
        time.sleep(poll_s)
    observed = _count() - base
    prof.stop()
    res = prof.finish(kind="serving")
    if res is None:
        return None
    return {
        "enabled": True,
        "trigger": "http",
        "requested_steps": int(steps),
        "observed_steps": int(observed),
        "wait_s": round(time.perf_counter() - t0, 6),
        "summary": res["summary"],
        "merged_spans": res["merged_spans"],
        "trace_dir": res["trace_dir"],
    }
