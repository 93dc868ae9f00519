"""Compile observability: every program build of the process, on the record.

The biggest TPU tail-latency hazard in the ragged serving path is a request
whose shape falls outside the warmed program zoo: jax silently traces and
builds a new program mid-decode and the whole batch stalls for seconds. The
same work, done once at start-up, is most of a server's set-up time. Both are
read from one place: ``WATCH``, the process's ONE set of ``jax.monitoring``
listeners, however many engines there are and whether or not ``telemetry`` is
enabled (the metrics registry is fed only while one is attached).

What jax tells a listener, and on which thread: it calls on the thread that
builds, once where a stage begins (``record_scalar(event, start_time,
fun_name=...)``) and once where it ends (``record_event_duration_secs(event,
seconds, fun_name=...)``), for the three stages ``jaxpr_trace_duration``
(Python tracing), ``jaxpr_to_mlir_module_duration`` (lowering: the jaxpr to
MLIR, Mosaic kernel bodies serialised) and ``backend_compile_duration``. The
last wraps ``compile_or_get_cached``: it fires on a persistent-cache HIT too,
and then holds the retrieval (``/jax/compilation_cache/
cache_retrieval_time_sec``) and not a compile. So a build delimits itself, and
nothing marks one on a step path: on one thread a build opens at the first
stage begun while none is open and closes at the end of its
``backend_compile_duration``, whose ``fun_name`` names it (``jit(f)``, kept
here as ``jit_f``, the name of the cache's entry). Stages begun inside an open
stage are the build's own (the kernel wrappers a step program traces inside
its trace, a constant built eagerly while tracing): they are counted
(``inner_traces``, ``inner_builds``) and their time is the outer stage's. A
trace that no compile follows (``.lower()`` alone, ``eval_shape``) closes
when the thread opens its next build and is kept under its own name.

The persistent cache: jax fires ``cache_misses`` only where it WRITES an
entry (a compile under the cache's time or size threshold fires neither
event), so a build that ASKED is one with ``compile_requests_use_cache``
while a cache directory is set (jax fires it without one as well), a HIT one
with ``cache_hits``, and the rest of those that asked are misses;
``written`` says that jax wrote the entry, so that the next process can hit.

The build log (``snapshot()["builds"]``) holds one record for each OUTERMOST
build, ``LOG_BOUND`` of them and then counters only (``overflowed``): times
``t0``/``t1`` are ``time.perf_counter()``'s, stage seconds are jax's own.
The start-up log (``snapshot()["phases"]``) is written by
``utils/tracing.phase``. ``telemetry.snapshot()["startup"]`` is both.

Metrics, while a registry is attached (``telemetry`` enabled):

- ``jit_cache_misses_total{source="monitoring"}``  program builds in this
  process: each is an in-process jit cache miss. NOT "real XLA compiles": a
  build whose executable came from the persistent cache counts too.
- ``jit_compile_seconds{phase=}``   histogram of every stage's seconds, nested
  ones included (``phase`` = ``jaxpr_trace`` | ``jaxpr_to_mlir_module`` |
  ``backend_compile``, the last holding retrievals as well as compiles)
- ``persistent_cache_hits_total`` / ``persistent_cache_misses_total``  builds
  that asked the persistent cache and hit / did not (the rule above)
- ``program_build_seconds{program=,stage=}``  gauge: seconds this process
  spent in ``stage`` (``trace`` | ``lower`` | ``compile`` | ``retrieve``) of
  outermost builds of ``program``, summed (``PROGRAM_LABELS`` names at most,
  the rest under ``program="other"``)
- ``startup_phase_seconds{phase=}``  gauge: seconds in a start-up phase
"""

from __future__ import annotations

import threading
import time

COMPILE_EVENT_PREFIX = "/jax/core/compile/"
TRACE_EVENT = COMPILE_EVENT_PREFIX + "jaxpr_trace_duration"
LOWER_EVENT = COMPILE_EVENT_PREFIX + "jaxpr_to_mlir_module_duration"
BACKEND_COMPILE_EVENT = COMPILE_EVENT_PREFIX + "backend_compile_duration"
CACHE_ASKED_EVENT = "/jax/compilation_cache/compile_requests_use_cache"
PERSISTENT_HIT_EVENT = "/jax/compilation_cache/cache_hits"
PERSISTENT_MISS_EVENT = "/jax/compilation_cache/cache_misses"  # = a write
CACHE_RETRIEVAL_EVENT = "/jax/compilation_cache/cache_retrieval_time_sec"
CACHE_SAVED_EVENT = "/jax/compilation_cache/compile_time_saved_sec"

# a stage's place in a build, and the record's field its seconds go to
STAGES = {TRACE_EVENT: (0, "trace_s"), LOWER_EVENT: (1, "lower_s"),
          BACKEND_COMPILE_EVENT: (2, "compile_s")}

LOG_BOUND = 512
PROGRAM_LABELS = 128

# compile times span 10ms CPU traces to multi-minute TPU fusions
COMPILE_BUCKETS = (0.01, 0.05, 0.1, 0.25, 0.5, 1.0, 2.5, 5.0, 10.0,
                   30.0, 60.0, 120.0, 300.0, 600.0)

_MISSES_HELP = ("program builds observed (each is an in-process jit cache "
                "miss; the executable may still come from the persistent "
                "cache)")


def _base(fun_name: str) -> str:
    """``f`` of ``jit(f)``: the trace stage says ``f``, the others ``jit(f)``."""
    i, j = fun_name.find("("), fun_name.rfind(")")
    return fun_name[i + 1:j] if 0 <= i < j else fun_name


def _name(fun_name: str) -> str:
    """``jit(f)`` as ``jit_f``, what the cache's entry and the device trace
    call the program."""
    return fun_name.replace("(", "_").replace(")", "")


class _Stage:
    """An open stage; a ``backend_compile`` one collects its cache events."""

    __slots__ = ("event", "asked", "hit", "written", "retrieval_s", "saved_s")

    def __init__(self, event: str):
        self.event = event
        self.asked = self.hit = self.written = False
        self.retrieval_s = self.saved_s = 0.0


class _Thread:
    """One thread's open stages and the build they belong to."""

    __slots__ = ("stack", "build")

    def __init__(self):
        self.stack: list[_Stage] = []
        self.build: dict | None = None


class CompileWatch:
    """The process's ``jax.monitoring`` listeners, its build log and its
    start-up log. Use the module's ``WATCH``; a second instance is a second
    set of listeners."""

    def __init__(self):
        self._lock = threading.Lock()
        self._installed = False
        self._registry = None
        self._jax_config = None     # jax.config, from ``install``
        self._threads: dict[int, _Thread] = {}
        self._builds: list[dict] = []
        self._phases: list[dict] = []
        self._stage_sums: dict[tuple[str, str], float] = {}
        self.builds_total = 0       # outermost builds closed, logged or not
        self.builds_dropped = 0     # of them, those the full log dropped
        self.cache_hits = 0         # builds (nested too) that asked and hit
        self.cache_misses = 0       # ... that asked and did not
        self._cache_writes = 0      # ... whose executable jax wrote

    # ------------------------------------------------------------ listeners
    def _state(self) -> _Thread:
        ident = threading.get_ident()
        st = self._threads.get(ident)
        if st is None:
            with self._lock:
                st = self._threads.setdefault(ident, _Thread())
        return st

    def _on_stage_begin(self, event: str, _start: float, fun_name: str = "",
                        **_) -> None:
        stage = STAGES.get(event)
        if stage is None:
            return
        st = self._state()
        st.stack.append(_Stage(event))
        if len(st.stack) > 1:
            return
        now = time.perf_counter()
        b = st.build
        if (b is not None and stage[0] > b["_rank"]
                and _base(fun_name) == b["_base"]):
            return  # the open build's next stage
        if b is not None:  # a trace or a lowering that nothing followed
            self._close(st, b)
        st.build = {
            "program": _name(fun_name),
            "thread": threading.current_thread().name,
            "t0": now, "t1": now, "trace_s": 0.0, "lower_s": 0.0,
            "compile_s": 0.0, "retrieval_s": 0.0, "saved_s": 0.0,
            "inner_traces": 0, "inner_builds": 0, "cache": "unasked",
            "written": False, "compiled": False, "_rank": -1,
            "_base": _base(fun_name)}

    def _on_duration(self, event: str, seconds: float, fun_name: str = "",
                     **_) -> None:
        stage = STAGES.get(event)
        if stage is None:
            field = _CACHE_SECONDS_FIELD.get(event)
            stack = self._state().stack if field else None
            if stack and stack[-1].event == BACKEND_COMPILE_EVENT:
                setattr(stack[-1], field, seconds)
            return
        st = self._state()
        frame = st.stack.pop() if st.stack else None
        reg = self._registry
        if reg is not None:
            reg.histogram("jit_compile_seconds",
                          "jax trace/lower/backend-compile stage durations",
                          buckets=COMPILE_BUCKETS).observe(
                              seconds, phase=event[len(COMPILE_EVENT_PREFIX):
                                                   -len("_duration")])
        compiled = event == BACKEND_COMPILE_EVENT
        if compiled:
            self._count_build(reg, frame)
        b = st.build
        if frame is None or frame.event != event or b is None:
            # installed in mid-stage: nothing to attribute it to
            del st.stack[:]
            return
        if st.stack:  # inside an open stage: the outer stage holds its time
            if compiled:
                b["inner_builds"] += 1
            elif event == TRACE_EVENT:
                b["inner_traces"] += 1
            return
        b[stage[1]] += seconds
        b["_rank"] = stage[0]
        b["t1"] = time.perf_counter()
        b["program"] = _name(fun_name)
        if compiled:
            b["compiled"] = True
            if frame.asked:
                b["cache"] = "hit" if frame.hit else "miss"
            b["written"] = frame.written
            b["retrieval_s"], b["saved_s"] = frame.retrieval_s, frame.saved_s
            self._close(st, b)

    def _on_event(self, event: str, **_) -> None:
        field = _CACHE_EVENT_FIELD.get(event)
        if field is None:
            return
        st = self._state()
        if st.stack and st.stack[-1].event == BACKEND_COMPILE_EVENT:
            # jax "asks" with no cache directory set, too: nobody answers
            if (field == "asked"
                    and not self._jax_config.jax_compilation_cache_dir):
                return
            setattr(st.stack[-1], field, True)

    def _count_build(self, reg, frame) -> None:
        """A ``backend_compile_duration`` ended (outermost or not)."""
        asked, hit, written = ((frame.asked, frame.hit, frame.written)
                               if frame else (False, False, False))
        with self._lock:
            self.cache_hits += asked and hit
            self.cache_misses += asked and not hit
            self._cache_writes += written
        if reg is None:
            return
        reg.counter("jit_cache_misses_total",
                    _MISSES_HELP).inc(source="monitoring")
        if asked:
            reg.counter(
                "persistent_cache_hits_total" if hit
                else "persistent_cache_misses_total",
                "program builds that asked the persistent compilation cache "
                "and " + ("hit" if hit else "did not hit")).inc()

    def _close(self, st: _Thread, b: dict) -> None:
        st.build = None
        del b["_rank"], b["_base"]
        with self._lock:
            self.builds_total += 1
            if len(self._builds) < LOG_BOUND:
                self._builds.append(b)
            else:
                self.builds_dropped += 1
            sums = self._stage_sums
            name = b["program"]
            if (name, "trace") not in sums and len(sums) >= 4 * PROGRAM_LABELS:
                name = "other"
            for stage, s in (("trace", b["trace_s"]), ("lower", b["lower_s"]),
                             ("compile", b["compile_s"] - b["retrieval_s"]),
                             ("retrieve", b["retrieval_s"])):
                sums[name, stage] = sums.get((name, stage), 0.0) + s
            reg = self._registry
            if reg is not None:
                self._publish_build(reg, name)

    def _publish_build(self, reg, name: str) -> None:
        g = reg.gauge("program_build_seconds",
                      "seconds this process spent building programs of this "
                      "name, by stage (outermost builds, summed)")
        for stage in ("trace", "lower", "compile", "retrieve"):
            g.set(self._stage_sums[name, stage], program=name, stage=stage)

    # ------------------------------------------------------- start-up phases
    def note_phase(self, name: str, t0: float, t1: float, args: dict) -> None:
        """One start-up phase (``utils/tracing.phase``), on this thread."""
        rec = {"name": name, "thread": threading.current_thread().name,
               "t0": t0, "t1": t1, "args": dict(args)}
        with self._lock:
            if len(self._phases) < LOG_BOUND:
                self._phases.append(rec)
            reg = self._registry
        if reg is not None:
            self._publish_phase(reg, rec)

    @staticmethod
    def _publish_phase(reg, rec: dict) -> None:
        reg.gauge("startup_phase_seconds",
                  "seconds of a start-up phase (the last of that name)").set(
                      rec["t1"] - rec["t0"], phase=rec["name"])

    # ---------------------------------------------------------------- reads
    def cache_writes(self) -> int:
        """Builds whose executable jax wrote to the persistent cache: misses
        that cost a compile the cache was willing to keep (the ragged
        engine's cold-cache probe reads this before and after its first step
        program)."""
        return self._cache_writes

    def snapshot(self) -> dict | None:
        """The logs as plain data, None while the listeners are not
        installed. A trace nothing has followed yet is listed as it stands
        (``compiled`` false)."""
        with self._lock:
            if not self._installed:
                return None
            builds = [dict(b) for b in self._builds]
            for st in self._threads.values():
                b = st.build
                if b is not None and not st.stack and b["_rank"] >= 0:
                    builds.append({k: v for k, v in b.items()
                                   if not k.startswith("_")})
            return {"clock": "perf_counter", "bound": LOG_BOUND,
                    "overflowed": self.builds_dropped > 0,
                    "builds_total": self.builds_total,
                    "builds_dropped": self.builds_dropped,
                    "cache_hits": self.cache_hits,
                    "cache_misses": self.cache_misses,
                    "cache_writes": self._cache_writes,
                    "phases": [dict(p) for p in self._phases],
                    "builds": builds}

    # --------------------------------------------------------- install/undo
    def install(self) -> "CompileWatch":
        """Register the listeners (idempotent: one set a process)."""
        if self._installed:
            return self
        with self._lock:
            if self._installed:
                return self
            import jax
            from jax import monitoring

            self._jax_config = jax.config
            monitoring.register_scalar_listener(self._on_stage_begin)
            monitoring.register_event_duration_secs_listener(self._on_duration)
            monitoring.register_event_listener(self._on_event)
            self._installed = True
        return self

    def uninstall(self) -> None:
        """Remove exactly our callbacks (never ``clear_event_listeners()``,
        which would take other tooling's) and forget the open stages; the
        logs stay."""
        with self._lock:
            if not self._installed:
                return
            from jax import monitoring

            monitoring.unregister_scalar_listener(self._on_stage_begin)
            monitoring.unregister_event_duration_listener(self._on_duration)
            monitoring.unregister_event_listener(self._on_event)
            self._installed = False
            self._threads.clear()

    def attach(self, registry) -> "CompileWatch":
        """Feed ``registry`` from now on, and what the logs hold already."""
        self.install()
        # pre-create the series so /metrics exposes the counter at zero
        # (an operator alerting on it must see it before the first build)
        registry.counter("jit_cache_misses_total",
                         _MISSES_HELP).inc(0.0, source="monitoring")
        with self._lock:
            self._registry = registry
            for name in {n for n, _ in self._stage_sums}:
                self._publish_build(registry, name)
            for rec in self._phases:
                self._publish_phase(registry, rec)
        return self

    def detach(self) -> None:
        with self._lock:
            self._registry = None

    def reset(self) -> None:
        """Empty logs and zero counts (test isolation)."""
        with self._lock:
            self._builds, self._phases, self._stage_sums = [], [], {}
            self._threads.clear()
            self.builds_total = self.builds_dropped = 0
            self.cache_hits = self.cache_misses = self._cache_writes = 0


_CACHE_EVENT_FIELD = {CACHE_ASKED_EVENT: "asked", PERSISTENT_HIT_EVENT: "hit",
                      PERSISTENT_MISS_EVENT: "written"}
_CACHE_SECONDS_FIELD = {CACHE_RETRIEVAL_EVENT: "retrieval_s",
                        CACHE_SAVED_EVENT: "saved_s"}

WATCH = CompileWatch()
