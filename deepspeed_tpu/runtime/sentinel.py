"""Self-healing training: divergence sentinel, quarantine, rollback ladder,
liveness (docs/FAULT_TOLERANCE.md "Training: self-healing").

The training loop already decides *overflow* skips without a host sync
(``precision.grads_finite`` + ``_tree_select`` inside the fused step). This
module extends that verdict into a full anomaly verdict computed in the SAME
XLA program — a finite-but-divergent step (loss spike, grad-norm explosion)
takes the identical skip path — and adds the host-side machinery that turns
verdicts into recovery:

- :func:`verdict` — device-side anomaly decision over a rolling
  :class:`SentinelState` (loss EMA + k·σ gate, grad-norm ring-quantile gate,
  consecutive-skip streak). Threaded through the jitted step like
  ``LossScaleState``; detection adds zero extra D2H syncs.
- :class:`SentinelPolicy` — the escalation ladder over settled verdicts:
  strike 1 in the window quarantines the offending batch fingerprints,
  strike 2 restores the last verified checkpoint (PR 9's fallback ladder)
  and replays with quarantined batches skipped, strike 3 reduces LR or halts
  loudly with a forensics JSON (modeled on the memory ledger's OOM reports).
- :func:`batch_fingerprint` — content hash that names a batch across runs
  and process restarts (the quarantine list keys on it; the loaders in
  ``runtime/dataloader.py`` skip it).
- :class:`Heartbeat` — a per-worker liveness file written at STEP BOUNDARIES
  from the training thread (never a background thread: a wedged dispatch
  must stop the beat), polled by ``elasticity.agent.ElasticAgent`` so a
  wedged-but-alive worker is SIGKILLed and the world restarts.
- :func:`watched_call` — the dispatch watchdog's deadline fence; raises
  :class:`TrainingWedgeError` (transient in the ``utils/faults.py``
  ``classify_transient`` taxonomy) when the device fence exceeds it.

Everything here is off-by-default; with the sentinel disabled the engine
traces the exact step program it traced before this module existed.
"""

from __future__ import annotations

import hashlib
import json
import os
import threading
import time
from typing import NamedTuple

import numpy as np

from deepspeed_tpu.telemetry import get_telemetry
from deepspeed_tpu.utils.logging import log_dist

# Anomaly reason bitmask (device i32; host decodes with reason_names)
REASON_NONFINITE = 1    # non-finite grads/loss (the classic overflow skip)
REASON_LOSS_SPIKE = 2   # loss > EMA + k*sigma
REASON_GRAD_SPIKE = 4   # grad norm > mult * rolling quantile
REASON_SKIP_STREAK = 8  # consecutive-skip streak crossed the threshold
REASON_WEDGE = 16       # host-side: dispatch fence exceeded the deadline

_REASON_LABELS = (
    (REASON_NONFINITE, "nonfinite"),
    (REASON_LOSS_SPIKE, "loss-spike"),
    (REASON_GRAD_SPIKE, "grad-spike"),
    (REASON_SKIP_STREAK, "skip-streak"),
    (REASON_WEDGE, "wedge"),
)

# Injection magnitudes for the directive fault kinds (utils/faults.py
# train.grads / data.batch seams): the loss multiplier the engine folds into
# the batch. NaN models nan-grads; the finite factor models a poisoned /
# divergent batch whose loss AND grads blow up together.
SPIKE_LOSS_MULT = 1.0e4


def reason_names(mask: int) -> list[str]:
    return [name for bit, name in _REASON_LABELS if mask & bit]


class DivergenceHaltError(RuntimeError):
    """Third strike: the run is diverging faster than the ladder can heal.
    Raised loudly after the forensics JSON is written; ``report`` carries
    its path."""

    def __init__(self, message: str, report: str | None = None):
        super().__init__(message)
        self.report = report


class TrainingWedgeError(TimeoutError):
    """The training dispatch fence exceeded the watchdog deadline (a wedged
    device program or stuck transfer). Subclasses ``TimeoutError`` so the
    shared ``utils.faults.classify_transient`` taxonomy treats it as
    transient — the recovery is rollback/restart, not crash."""


# --------------------------------------------------------------- device side
class SentinelState(NamedTuple):
    """Device-resident rolling statistics threaded through the jitted step
    (same discipline as ``precision.LossScaleState``: donated, updated with
    ``jnp.where``, never synced to decide anything)."""

    loss_ema: "jnp.ndarray"     # f32 EMA of accepted-step loss
    loss_var: "jnp.ndarray"     # f32 EMA of squared deviation from the EMA
    gnorm_ring: "jnp.ndarray"   # f32[grad_window] last accepted grad norms
    ring_pos: "jnp.ndarray"     # i32 next ring write slot
    seen: "jnp.ndarray"         # i32 accepted steps folded into the stats
    skip_streak: "jnp.ndarray"  # i32 consecutive anomalous steps


def init_state(cfg) -> SentinelState:
    import jax.numpy as jnp

    return SentinelState(
        loss_ema=jnp.float32(0.0),
        loss_var=jnp.float32(0.0),
        gnorm_ring=jnp.zeros((int(cfg.grad_window),), jnp.float32),
        ring_pos=jnp.int32(0),
        seen=jnp.int32(0),
        skip_streak=jnp.int32(0),
    )


def verdict(state: SentinelState, loss, gnorm, finite, cfg):
    """The fused anomaly decision. Pure; traced inside the train step.

    Returns ``(new_state, anomaly, reason, streak)`` — all device scalars.
    The rolling stats ingest ONLY accepted (non-anomalous) steps: a spike
    chased into the EMA would mask the next one, and a NaN would poison the
    statistics permanently. The streak counter mirrors
    ``precision.update_loss_scale``'s ``good_steps`` semantics exactly:
    reset to zero by any single accepted step, incremented by each skip.
    """
    import jax.numpy as jnp

    nonfinite = jnp.logical_or(jnp.logical_not(finite),
                               jnp.logical_not(jnp.isfinite(loss)))

    warm_loss = state.seen >= cfg.warmup_steps
    sigma = jnp.sqrt(jnp.maximum(state.loss_var, 0.0))
    # relative floor: early in training the variance estimate is tiny and a
    # purely statistical gate would flag ordinary fluctuation
    sigma = jnp.maximum(sigma, cfg.loss_rel_floor * jnp.abs(state.loss_ema))
    loss_spike = jnp.logical_and(
        warm_loss, loss > state.loss_ema + cfg.loss_sigma_k * sigma)

    warm_gnorm = state.seen >= cfg.grad_window
    q = jnp.quantile(state.gnorm_ring, cfg.grad_quantile)
    gnorm_spike = jnp.logical_and(
        warm_gnorm, gnorm > cfg.grad_quantile_mult * jnp.maximum(q, 1e-12))

    anomaly = nonfinite | loss_spike | gnorm_spike
    streak = jnp.where(anomaly, state.skip_streak + 1, 0)
    reason = (nonfinite.astype(jnp.int32) * REASON_NONFINITE
              + loss_spike.astype(jnp.int32) * REASON_LOSS_SPIKE
              + gnorm_spike.astype(jnp.int32) * REASON_GRAD_SPIKE
              + (streak >= cfg.max_consecutive_skips).astype(jnp.int32)
              * REASON_SKIP_STREAK)

    ok = jnp.logical_not(anomaly)
    beta = jnp.float32(cfg.loss_ema_beta)
    first = state.seen == 0
    ema = jnp.where(first, loss, beta * state.loss_ema + (1.0 - beta) * loss)
    dev = loss - ema
    var = jnp.where(first, jnp.float32(0.0),
                    beta * state.loss_var + (1.0 - beta) * dev * dev)
    ring = jnp.where(ok, state.gnorm_ring.at[state.ring_pos].set(gnorm),
                     state.gnorm_ring)
    new_state = SentinelState(
        loss_ema=jnp.where(ok, ema, state.loss_ema),
        loss_var=jnp.where(ok, var, state.loss_var),
        gnorm_ring=ring,
        ring_pos=jnp.where(ok, (state.ring_pos + 1) % cfg.grad_window,
                           state.ring_pos),
        seen=state.seen + ok.astype(jnp.int32),
        skip_streak=streak,
    )
    return new_state, anomaly, reason, streak


# ---------------------------------------------------------------- host side
def batch_fingerprint(batch: dict) -> str:
    """Content hash naming a batch across runs/restarts (key-order
    independent). The quarantine machinery keys on it: same data → same
    fingerprint, so a poisoned batch stays quarantined through rollback,
    process death, and elastic restarts."""
    h = hashlib.sha1()
    for k in sorted(batch):
        v = np.asarray(batch[k])
        h.update(k.encode())
        h.update(str(v.shape).encode())
        h.update(str(v.dtype).encode())
        h.update(np.ascontiguousarray(v).tobytes())
    return h.hexdigest()[:16]


def quarantine_path(state_dir: str) -> str:
    return os.path.join(state_dir, "quarantine.json")


def load_quarantine(state_dir: str) -> list[str]:
    """Read the persisted quarantine list; a torn/garbage file (a worker
    killed mid-write before atomic replace existed, or disk rot) reads as
    empty rather than crashing the restart."""
    path = quarantine_path(state_dir)
    try:
        with open(path) as f:
            data = json.load(f)
        if isinstance(data, list):
            return [str(x) for x in data]
    except (OSError, ValueError):
        pass
    return []


def save_quarantine(state_dir: str, fingerprints: list[str]) -> None:
    """Atomic persist (tmp + fsync + rename) so a kill mid-write can never
    leave a torn list a restarted worker would half-honor."""
    os.makedirs(state_dir, exist_ok=True)
    path = quarantine_path(state_dir)
    tmp = path + ".tmp"
    with open(tmp, "w") as f:
        json.dump(sorted(set(fingerprints)), f)
        f.flush()
        os.fsync(f.fileno())
    os.replace(tmp, path)


_FORENSICS_LOCK = threading.Lock()
_FORENSICS_SEQ = 0


def write_forensics(report_dir: str, event: str, context: dict) -> str | None:
    """Crash/recovery report JSON, same shape discipline as the memory
    ledger's OOM reports (``telemetry/memledger.py``): one self-contained
    file per event, written before anything escalates. Never raises."""
    global _FORENSICS_SEQ
    try:
        with _FORENSICS_LOCK:
            _FORENSICS_SEQ += 1
            seq = _FORENSICS_SEQ
        report = {
            "type": "sentinel_report",
            "event": event,
            "ts": time.time(),
            "pid": os.getpid(),
            **context,
        }
        os.makedirs(report_dir, exist_ok=True)
        path = os.path.join(
            report_dir, f"sentinel_{event}_{os.getpid()}_{seq}.json")
        with open(path, "w") as f:
            json.dump(report, f, indent=2, default=str)
        tel = get_telemetry()
        if tel.enabled:
            tel.event("sentinel/" + event, report=path)
        return path
    except Exception:
        return None


class SentinelPolicy:
    """The host-side escalation ladder over settled device verdicts.

    Strikes are counted on a monotonic tick (one per observed step — NOT
    ``global_steps``, which a rollback rewinds) and expire after
    ``window_steps`` ticks. Within one window:

    ====== ==================================================================
    strike action
    ====== ==================================================================
    1      quarantine the step's batch fingerprints; pin ``rollback_tag`` to
           the newest checkpoint (saved from pre-anomaly params)
    2      quarantine + ``"rollback"`` — the engine restores the pinned tag
           and replays with quarantined batches skipped
    3      ``"reduce-lr"`` or ``"halt"`` per ``on_third_strike``
    ====== ==================================================================

    Wedge timeouts are tracked separately (``observe_wedge``): a wedge needs
    immediate rollback (the step may never complete), and ``max_wedges`` of
    them in the window escalate to halt.
    """

    def __init__(self, cfg):
        self.cfg = cfg
        self.quarantined: list[str] = []
        self.rollback_tag: str | None = None
        self.rollbacks = 0
        self.lr_backoffs = 0
        self.anomalies = 0
        self.wedges = 0
        self._tick = 0
        self._strikes: list[int] = []
        self._wedge_ticks: list[int] = []
        if cfg.state_dir:
            self.quarantined = load_quarantine(cfg.state_dir)

    # -------------------------------------------------------------- verdicts
    @property
    def strikes_in_window(self) -> int:
        return len([t for t in self._strikes
                    if self._tick - t <= self.cfg.window_steps])

    def tick(self) -> None:
        """One accepted (non-anomalous) step observed."""
        self._tick += 1

    def observe(self, reason: int, fingerprints: list[str],
                latest_tag: str | None = None) -> str:
        """One anomalous step observed → ladder action:
        ``"quarantine" | "rollback" | "reduce-lr" | "halt"``."""
        self._tick += 1
        self.anomalies += 1
        w = self.cfg.window_steps
        self._strikes = [t for t in self._strikes if self._tick - t <= w]
        self._strikes.append(self._tick)
        self.quarantine(fingerprints)
        n = len(self._strikes)
        if n == 1:
            # pin the rollback target NOW: the newest checkpoint predates
            # this anomaly, so replaying from it rewrites every step the
            # divergence (and the stream misalignment a skipped batch
            # causes) touched
            self.rollback_tag = latest_tag
            return "quarantine"
        if n == 2 and self.cfg.rollback:
            return "rollback"
        return ("reduce-lr" if self.cfg.on_third_strike == "reduce-lr"
                else "halt")

    def observe_wedge(self) -> str:
        """A dispatch-fence timeout → ``"rollback"`` (immediately: the step
        may never settle) or ``"halt"`` once the window's wedge budget is
        spent."""
        self._tick += 1
        self.wedges += 1
        w = self.cfg.window_steps
        self._wedge_ticks = [t for t in self._wedge_ticks
                             if self._tick - t <= w]
        self._wedge_ticks.append(self._tick)
        if len(self._wedge_ticks) >= self.cfg.max_wedges:
            return "halt"
        return "rollback" if self.cfg.rollback else "halt"

    # ------------------------------------------------------------ quarantine
    def quarantine(self, fingerprints: list[str]) -> list[str]:
        """Add fingerprints to the quarantine (persisted when ``state_dir``
        is set). Returns the newly added ones."""
        new = [f for f in fingerprints if f and f not in self.quarantined]
        if not new:
            return []
        self.quarantined.extend(new)
        if self.cfg.state_dir:
            save_quarantine(self.cfg.state_dir, self.quarantined)
        tel = get_telemetry()
        if tel.enabled:
            tel.counter(
                "sentinel_quarantined_batches_total",
                "batch fingerprints quarantined by the sentinel",
            ).inc(len(new))
        log_dist(f"sentinel: quarantined {len(new)} batch fingerprint(s) "
                 f"({', '.join(new)})", ranks=[0])
        return new


# ----------------------------------------------------------------- liveness
def heartbeat_path(state_dir: str, rank) -> str:
    """Beacon file for a worker rank. ``rank`` is an int for process ranks
    or a string like ``"0_s1"`` for a per-stage beacon (rank 0, pipeline
    stage thread 1) — the MPMD runtime beats one per stage thread so a
    single wedged stage goes stale on its own."""
    rank = rank if isinstance(rank, str) else int(rank)
    return os.path.join(state_dir, f"heartbeat_{rank}.json")


class Heartbeat:
    """Per-worker liveness beacon, written from the TRAINING THREAD at step
    boundaries (``Engine._after_step``) — deliberately not a background
    thread, so a wedged dispatch stops the beat and the agent's staleness
    poll catches a worker that is alive but making no progress."""

    def __init__(self, state_dir: str, rank: int = 0,
                 interval_s: float = 1.0):
        os.makedirs(state_dir, exist_ok=True)
        self.path = heartbeat_path(state_dir, rank)
        self._interval = float(interval_s)
        self._last = 0.0

    def beat(self, step: int) -> bool:
        """Touch the beacon (throttled to ``interval_s``). Returns True if
        a write happened. The mtime is the liveness signal; the payload is
        forensic context."""
        now = time.monotonic()
        if now - self._last < self._interval:
            return False
        self._last = now
        tmp = self.path + ".tmp"
        try:
            with open(tmp, "w") as f:
                json.dump({"step": int(step), "pid": os.getpid(),
                           "ts": time.time()}, f)
            os.replace(tmp, self.path)
        except OSError:
            return False
        return True


def watched_call(fn, timeout_s: float):
    """Run ``fn`` under the dispatch watchdog's deadline: the call executes
    on a daemon worker thread and :class:`TrainingWedgeError` is raised if
    it has not returned within ``timeout_s`` (the worker thread is left
    behind — by definition it is stuck, and killing threads is not a thing).
    Exceptions from ``fn`` propagate unchanged."""
    done: dict = {}

    def run():
        try:
            done["value"] = fn()
        except BaseException as e:  # re-raised on the caller's thread
            done["error"] = e

    t = threading.Thread(target=run, name="sentinel-fence", daemon=True)
    t.start()
    t.join(timeout_s)
    if t.is_alive():
        raise TrainingWedgeError(
            f"training dispatch fence exceeded {timeout_s:.1f}s "
            "(wedged device program or stuck transfer)")
    if "error" in done:
        raise done["error"]
    return done.get("value")
