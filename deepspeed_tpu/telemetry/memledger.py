"""Framework-wide HBM memory ledger: per-owner byte attribution, a
``jax.live_arrays()`` leak census, OOM forensics, and headroom math.

The telemetry stack measures *time* exhaustively (request traces, training
stepscope); this module is the matching byte-truth layer. Every long-lived
device allocation registers under an **owner tag** from a fixed taxonomy —
``params``, ``optimizer_shards``, ``grads``, ``kv_pool``,
``prefix_cache_retained``, ``device_sched_state``, ``staging_buffers``,
``kv_handoff`` — with pytree-computed nbytes. Two attribution shapes exist:

- **handles** (``register`` / ``update`` / ``release``): a fixed allocation
  whose size changes only at explicit lifecycle events (params, the paged KV
  pool, device scheduler rows);
- **providers** (``register_provider``): pool-style owners whose byte count
  is derived state (prefix-cache retained blocks × block bytes, parked
  handoff blocks, the staging cache) — a zero-argument callable read at
  gauge-refresh time, held via weakref-style None-pruning so a dead engine
  never leaks through the ledger. A provider whose bytes are a *subset* of
  another owner's allocation (retained/handoff blocks live inside the
  ``kv_pool`` arrays) registers with ``carveout_of``: its bytes move out of
  the parent's attribution instead of adding to the total, so
  ``attributed_bytes`` counts each real byte exactly once.

``census()`` sums every live jax array in the process and reconciles it
against the ledger: ``memory_unattributed_bytes = live − attributed`` is a
live leak detector — a steadily growing gap is an allocation nobody owns.
The drift alarm fires (``memledger_drift_alarms_total``) when the
unattributed fraction exceeds a threshold for N *consecutive* censuses, so a
transient spike (a step's temps caught mid-flight) never pages anyone.

Per-compiled-program temp/activation footprints ride along via
``note_program(key, compiled)`` using the same ``cost_analysis`` /
``memory_analysis`` idiom as profiling/flops_profiler.py, keyed on the
engine's existing specialization keys — so "how much scratch does program X
need" is recorded once per compile, not guessed.

**OOM forensics** (``record_oom``): when a ``RESOURCE_EXHAUSTED`` surfaces
at a dispatch/alloc/engine seam, the full per-owner breakdown + census +
device watermarks are snapshotted into a crash-report JSON under
``report_dir`` and ``oom_events_total{seam=}`` bumps — the postmortem is
written the instant the body is warm, not reconstructed from gauges later.

Off is free: the ledger only exists when the ``telemetry.memledger`` config
block enables it; every hot-path call site guards on
``telemetry.memledger is None`` (one attribute read, zero allocations).
"""

from __future__ import annotations

import json
import os
import threading
import time

# The owner taxonomy. Fixed and small on purpose: gauges stay low-
# cardinality and a breakdown is readable at a glance. New subsystems claim
# an existing owner before minting a new one.
OWNERS = (
    "params",                  # model weights (train master / serving cast)
    "optimizer_shards",        # optimizer state (resident groups only)
    "grads",                   # persistent gradient buffers (accumulators)
    "kv_pool",                 # the paged KV cache block pool
    "prefix_cache_retained",   # refcount-0 published blocks held in the LRU
    "device_sched_state",      # device-resident scheduler rows/block table
    "staging_buffers",         # H2D staging + checkpoint host snapshots
    "kv_handoff",              # parked KV blocks awaiting disagg export
    "host_kv_tier",            # demoted KV blocks in the host-RAM arena
    "disk_kv_tier",            # demoted KV blocks spilled to disk
)

_OOM_MARKERS = ("RESOURCE_EXHAUSTED", "OUT OF MEMORY", "OUT_OF_MEMORY")
# The TPU compiler reports a program it refuses under the same status: a
# kernel over its scoped VMEM ("Ran out of memory in memory space vmem"),
# a program that cannot fit HBM ("XLA:TPU compile permanent error"). Those
# are decided at compile time from shapes — the same program is refused
# again on any retry, and no amount of freed device memory changes that.
_COMPILE_REFUSAL_MARKERS = ("MEMORY SPACE VMEM", "COMPILE PERMANENT ERROR")


def is_resource_exhausted(exc: BaseException) -> bool:
    """True when ``exc`` is a RUN-TIME out-of-device-memory failure (XLA/PJRT
    surfaces these as RESOURCE_EXHAUSTED status text). Shared by the
    dispatch watchdog and the engine seams so every layer agrees on what
    counts as an OOM. A compile-time refusal carries the same status word
    but is not one: it must stop the run, never walk a degradation ladder
    onto a program the compiler happens to accept."""
    msg = f"{type(exc).__name__}: {exc}".upper()
    if any(m in msg for m in _COMPILE_REFUSAL_MARKERS):
        return False
    return any(m in msg for m in _OOM_MARKERS)


def tree_nbytes(tree) -> int:
    """Total bytes across a pytree's array leaves (ints pass through)."""
    if tree is None:
        return 0
    if isinstance(tree, (int, float)):
        return int(tree)
    import jax

    total = 0
    for leaf in jax.tree_util.tree_leaves(tree):
        nb = getattr(leaf, "nbytes", None)
        if nb is None:
            size = getattr(leaf, "size", None)
            itemsize = getattr(getattr(leaf, "dtype", None), "itemsize", None)
            nb = size * itemsize if size is not None and itemsize else 0
        total += int(nb)
    return total


class LedgerHandle:
    """One registered allocation (returned by ``MemoryLedger.register``)."""

    __slots__ = ("owner", "name", "nbytes", "_live")

    def __init__(self, owner: str, name: str, nbytes: int):
        self.owner = owner
        self.name = name
        self.nbytes = int(nbytes)
        self._live = True

    def __repr__(self):  # pragma: no cover - debugging aid
        return f"LedgerHandle({self.owner}/{self.name}: {self.nbytes}B)"


class MemoryLedger:
    """Per-owner byte attribution + live-array census (owned by the
    ``Telemetry`` singleton; one per process)."""

    def __init__(self, telemetry, *,
                 census_interval_steps: int = 50,
                 drift_threshold: float = 0.05,
                 drift_consecutive: int = 3,
                 report_dir: str = "oom_reports"):
        self.telemetry = telemetry
        self.census_interval_steps = max(1, int(census_interval_steps))
        self.drift_threshold = float(drift_threshold)
        self.drift_consecutive = max(1, int(drift_consecutive))
        self.report_dir = str(report_dir)
        self._lock = threading.Lock()
        self._handles: list[LedgerHandle] = []
        self._providers: list[list] = []  # [owner, name, fn] (fn->None prunes)
        self._programs: dict[str, dict] = {}
        self._drift_streak = 0
        self.drift_alarms = 0
        self._steps_since_census = 0
        self._last_census: dict | None = None
        self._oom_seq = 0
        self.oom_reports: list[str] = []

    # ------------------------------------------------------------- handles
    def register(self, owner: str, name: str, tree_or_nbytes) -> LedgerHandle:
        """Attribute an allocation to ``owner``; nbytes is pytree-summed.
        Returns a handle for later ``update``/``release``."""
        if owner not in OWNERS:
            raise ValueError(f"unknown memory owner {owner!r} (taxonomy: "
                             f"{OWNERS})")
        h = LedgerHandle(owner, name, tree_nbytes(tree_or_nbytes))
        with self._lock:
            self._handles.append(h)
        return h

    def update(self, handle: LedgerHandle, tree_or_nbytes) -> None:
        """Re-measure a handle after the underlying allocation was swapped
        (e.g. the KV cache rebuilt by crash containment)."""
        handle.nbytes = tree_nbytes(tree_or_nbytes)

    def release(self, handle: LedgerHandle) -> None:
        """Drop a handle's attribution (the allocation was freed)."""
        with self._lock:
            handle._live = False
            handle.nbytes = 0
            try:
                self._handles.remove(handle)
            except ValueError:
                pass  # double release is harmless

    def register_provider(self, owner: str, name: str, fn,
                          carveout_of: str | None = None,
                          offdevice: bool = False) -> None:
        """Attribute a *derived* byte count: ``fn()`` is read at every gauge
        refresh / census / breakdown. A provider returning None is pruned
        (the weakref-holding idiom: closures over ``weakref.ref(engine)``
        return None once the engine dies, so the ledger never pins it).

        ``carveout_of`` marks the provider as a *subset* of another owner's
        already-registered bytes (prefix-LRU retained blocks and parked
        handoff blocks live inside the ``kv_pool`` arrays): the bytes show
        under the provider's own owner in the breakdown but are subtracted
        from the parent, so the attributed total counts each real byte
        exactly once — double-counting would inflate ``attributed_bytes``
        past the census and shrink the unattributed leak signal the census
        exists to catch.

        ``offdevice`` marks bytes that do NOT live in device memory (the
        host-RAM/disk KV tiers): they appear in the breakdown and the
        ``memory_bytes{owner=}`` gauges, but the census reconciliation
        against ``jax.live_arrays()`` excludes them — host bytes counted
        against a device census would read as phantom overattribution."""
        if owner not in OWNERS:
            raise ValueError(f"unknown memory owner {owner!r}")
        if carveout_of is not None and carveout_of not in OWNERS:
            raise ValueError(f"unknown carveout parent {carveout_of!r}")
        with self._lock:
            self._providers.append([owner, name, fn, carveout_of, offdevice])

    # ------------------------------------------------------------ programs
    def note_program(self, key, compiled) -> dict | None:
        """Record one compiled program's temp/activation footprint from its
        ``memory_analysis()`` / ``cost_analysis()`` (AOT objects or anything
        quacking like them), keyed by the caller's specialization key."""
        key = str(key)
        with self._lock:
            if key in self._programs:
                return self._programs[key]
        fp: dict = {}
        try:
            ma = compiled.memory_analysis()
            for attr in ("temp_size_in_bytes", "argument_size_in_bytes",
                         "output_size_in_bytes", "generated_code_size_in_bytes"):
                v = getattr(ma, attr, None)
                if v is not None:
                    fp[attr.replace("_size_in_bytes", "_bytes")] = int(v)
        except Exception:
            pass
        try:
            ca = compiled.cost_analysis()
            if isinstance(ca, (list, tuple)):
                ca = ca[0] if ca else {}
            if ca and "flops" in ca:
                fp["flops"] = float(ca["flops"])
        except Exception:
            pass
        if not fp:
            return None
        with self._lock:
            self._programs[key] = fp
        tel = self.telemetry
        if fp.get("temp_bytes") is not None and tel.enabled:
            tel.gauge(
                "program_temp_bytes",
                "per-compiled-program temp/activation footprint",
            ).set(fp["temp_bytes"], program=key[:80])
        return fp

    def program_footprints(self) -> dict:
        with self._lock:
            return {k: dict(v) for k, v in self._programs.items()}

    # ----------------------------------------------------------- breakdown
    def owner_bytes(self, *, device_only: bool = False) -> dict:
        """``{owner: attributed_bytes}`` over every live handle + provider
        (all owners present, zero-filled, so dashboards never miss series).
        Carve-out providers move bytes out of their parent owner rather
        than adding new ones, so the dict sums to each real byte once.
        ``device_only=True`` skips off-device providers (host/disk KV
        tiers) — the census reconciles that variant against the device's
        live arrays."""
        out = {o: 0 for o in OWNERS}
        with self._lock:
            handles = list(self._handles)
            providers = list(self._providers)
        for h in handles:
            out[h.owner] += h.nbytes
        dead = []
        for p in providers:
            if device_only and p[4]:
                continue
            try:
                v = p[2]()
            except Exception:
                v = 0
            if v is None:
                dead.append(p)
                continue
            v = int(v)
            parent = p[3]
            if parent is not None:
                # a subset of the parent's bytes changes attribution, not
                # the total; never drive the parent negative (an over-
                # reporting carve-out would then shrink the sum and show
                # up as census overattribution — its own smell)
                v = min(v, max(0, out[parent]))
                out[parent] -= v
            out[p[0]] += v
        if dead:
            with self._lock:
                self._providers = [p for p in self._providers if p not in dead]
        return out

    def attributed_bytes(self) -> int:
        return sum(self.owner_bytes().values())

    def breakdown(self) -> dict:
        """Full attribution snapshot (the ``/debug/memory`` payload body)."""
        owners = self.owner_bytes()
        with self._lock:
            entries = [
                {"owner": h.owner, "name": h.name, "nbytes": h.nbytes}
                for h in self._handles
            ]
            providers = [
                {"owner": o, "name": n,
                 **({"carveout_of": c} if c else {}),
                 **({"offdevice": True} if d else {})}
                for o, n, _, c, d in self._providers
            ]
        return {
            "owners": owners,
            "attributed_bytes": sum(owners.values()),
            "entries": entries,
            "providers": providers,
            "programs": dict(self._programs),
        }

    # -------------------------------------------------------------- census
    def census(self, step: int | None = None, *,
               update_state: bool = True) -> dict:
        """Reconcile ledger vs reality: sum every live jax array, compute
        the unattributed gap, update gauges, and run the drift alarm.

        ``update_state=False`` is the read-only variant for the
        ``/debug/memory`` endpoint and OOM forensics: it reports the same
        reconciliation but never touches the drift-alarm state machine —
        the alarm's "N *consecutive* censuses" semantics belong to the
        step-loop cadence, and a scrape at an arbitrary cadence mutating
        ``_drift_streak`` would fire or suppress it spuriously."""
        import jax

        live_bytes = 0
        live_count = 0
        for a in jax.live_arrays():
            try:
                live_bytes += int(a.nbytes)
                live_count += 1
            except Exception:
                continue
        owners = self.owner_bytes()
        # reconcile DEVICE bytes only: the host-RAM/disk KV tiers are real
        # attributed bytes for the breakdown, but they are invisible to
        # jax.live_arrays() and would read as phantom overattribution here
        attributed = sum(self.owner_bytes(device_only=True).values())
        offdevice = max(0, sum(owners.values()) - attributed)
        unattributed = max(0, live_bytes - attributed)
        # attribution exceeding the census means stale handles (e.g. a
        # donated buffer whose handle was never updated) — its own smell
        overattributed = max(0, attributed - live_bytes)
        frac = unattributed / live_bytes if live_bytes else 0.0
        alarm = False
        if update_state:
            with self._lock:  # the endpoint thread races the step loop
                if frac > self.drift_threshold:
                    self._drift_streak += 1
                    if self._drift_streak >= self.drift_consecutive:
                        alarm = True
                        self.drift_alarms += 1
                        self._drift_streak = 0
                else:
                    self._drift_streak = 0
        out = {
            "live_bytes": live_bytes,
            "live_arrays": live_count,
            "attributed_bytes": attributed,
            "offdevice_bytes": offdevice,
            "unattributed_bytes": unattributed,
            "overattributed_bytes": overattributed,
            "unattributed_fraction": round(frac, 6),
            "drift_alarm": alarm,
            "drift_alarms_total": self.drift_alarms,
        }
        if update_state:
            self._last_census = out
        tel = self.telemetry
        if tel.enabled:
            g = tel.gauge
            g("memory_census_bytes",
              "total bytes across jax.live_arrays()").set(live_bytes)
            g("memory_unattributed_bytes",
              "live-array bytes no ledger owner claims (leak detector)"
              ).set(unattributed)
            g("memory_overattributed_bytes",
              "ledger bytes exceeding the live-array census (stale handles)"
              ).set(overattributed)
            if alarm:
                tel.counter(
                    "memledger_drift_alarms_total",
                    "censuses where the unattributed fraction stayed above "
                    "threshold for drift_consecutive rounds").inc()
                tel.event("memledger/drift_alarm", step=step,
                          unattributed_bytes=unattributed,
                          fraction=round(frac, 4))
        self.refresh_gauges(owners)
        return out

    def maybe_census(self, step: int | None = None) -> dict | None:
        """Census every ``census_interval_steps`` calls (the per-step hook);
        gauge refresh happens every call — it is just dict reads."""
        self._steps_since_census += 1
        if self._steps_since_census >= self.census_interval_steps:
            self._steps_since_census = 0
            return self.census(step)
        self.refresh_gauges()
        return None

    def refresh_gauges(self, owners: dict | None = None) -> None:
        """Write ``memory_bytes{owner=}`` + push a Perfetto counter-track
        sample when tracing is live."""
        tel = self.telemetry
        if not tel.enabled:
            return
        if owners is None:
            owners = self.owner_bytes()
        gauge = tel.gauge("memory_bytes",
                          "ledger-attributed device bytes by owner")
        for owner, nbytes in owners.items():
            gauge.set(nbytes, owner=owner)
        tracer = tel.tracer
        if tracer.enabled:
            tracer.counter_sample(
                "memory_bytes", {o: b for o, b in owners.items() if b})

    # ------------------------------------------------------------ endpoint
    def debug_payload(self) -> dict:
        """The ``GET /debug/memory`` response: breakdown + fresh census +
        device watermarks in one JSON-serializable dict."""
        payload = self.breakdown()
        # read-only census: scraping the endpoint must not perturb the
        # step-loop drift-alarm state machine
        payload["census"] = self.census(update_state=False)
        payload["device"] = self._device_stats()
        payload["enabled"] = True
        return payload

    @staticmethod
    def _device_stats() -> dict:
        try:
            from deepspeed_tpu.accelerator.real_accelerator import (
                get_accelerator,
            )

            return dict(get_accelerator().memory_stats() or {})
        except Exception:
            return {}

    # ------------------------------------------------------------ forensics
    def oom_report(self, seam: str, exc: BaseException | None = None,
                   context: dict | None = None) -> str | None:
        """Snapshot the full breakdown + census into a crash-report JSON.
        Never raises — forensics must not worsen the failure it documents."""
        try:
            with self._lock:
                self._oom_seq += 1
                seq = self._oom_seq
            report = {
                "type": "oom_report",
                "seam": seam,
                "ts": time.time(),
                "pid": os.getpid(),
                "error": f"{type(exc).__name__}: {exc}" if exc else None,
                "context": context or {},
                **self.breakdown(),
            }
            # read-only: forensics must document the drift state, not
            # advance it (an OOM mid-window would otherwise skew the
            # consecutive-census alarm)
            report["census"] = self.census(update_state=False)
            report["device"] = self._device_stats()
            os.makedirs(self.report_dir, exist_ok=True)
            path = os.path.join(
                self.report_dir,
                f"oom_{seam.replace('/', '_').replace('.', '_')}"
                f"_{os.getpid()}_{seq}.json")
            with open(path, "w") as f:
                json.dump(report, f, indent=2, default=str)
            with self._lock:
                self.oom_reports.append(path)
            tel = self.telemetry
            if tel.enabled:
                tel.event("memledger/oom", seam=seam, report=path,
                          attributed_bytes=report["attributed_bytes"])
            return path
        except Exception:
            return None

    # ------------------------------------------------------------ headroom
    @staticmethod
    def free_headroom_bytes(stats: dict | None = None,
                            guard_fraction: float = 0.05) -> int:
        """Measured free device bytes minus a guard band; -1 = unknown
        (backend reports no ``bytes_limit``, e.g. the CPU test accelerator)."""
        if stats is None:
            stats = MemoryLedger._device_stats()
        limit = int(stats.get("bytes_limit") or 0)
        if limit <= 0:
            return -1
        free = limit - int(stats.get("bytes_in_use") or 0)
        return max(0, free - int(guard_fraction * limit))


def record_oom(seam: str, exc: BaseException | None = None,
               context: dict | None = None) -> str | None:
    """Module-level OOM hook for the dispatch/alloc/engine seams: bump
    ``oom_events_total{seam=}`` and, when the ledger is live, write the
    crash-report JSON. Returns the report path (or None). Never raises."""
    try:
        from deepspeed_tpu.telemetry import get_telemetry

        tel = get_telemetry()
        if tel.enabled:
            tel.counter(
                "oom_events_total",
                "RESOURCE_EXHAUSTED failures caught at engine seams",
            ).inc(seam=seam)
        led = tel.memledger
        if led is None:
            return None
        return led.oom_report(seam, exc, context)
    except Exception:
        return None
