"""Autotuner: measured search over the full knob space, both engines.

Role parity with the reference ``autotuning/autotuner.py:42`` (``tune:404``):
the reference first PROFILES the model (param count -> per-stage memory
estimates) to prune the search space, then generates ZeRO-stage x micro-batch
experiments, runs each, and refines around the best
(``run_tuning_micro_batch_sizes:741``). Same shape here, in two drivers:

- ``Autotuner`` — the original in-process training sweep (phase 1 prunes and
  sweeps stage x micro-batch; phase 2 refines the winner across the
  offload/TP/SP/qgZ dimensions; phase 3 a bounded joint sweep).
- ``KnobSearch`` — the general driver over the ``knobs.KnobSpace`` registry
  (docs/AUTOTUNING.md): coordinate-ascent over BOTH engines' knobs, each
  candidate headroom-pruned *before paying a compile* via the
  ``ModelInfo`` memory math + knob cost hints, measured by a short bounded
  ``bench.py`` probe leg in a child process (train legs scored by
  goodput x MFU; serving legs by tokens/s x SLO-good fraction, with the
  census and token-parity gates as hard disqualifiers), refined around the
  winner on the continuous knobs, and persisted as a content-keyed profile
  (profiles.py) that ``deepspeed_tpu.initialize`` and the serving router
  load at startup.

The reference schedules experiments across free cluster nodes via the
launcher; on TPU a trial is a fresh engine in a child process (jit-compiled,
measured for a few steps), so the whole search runs where the job runs. OOMs
and compile failures are caught and recorded as failed trials, exactly like
the reference's experiment records.
"""

from __future__ import annotations

import json
import os
import time
from dataclasses import dataclass, field

import numpy as np

from deepspeed_tpu.utils.logging import log_dist

TUNING_METRICS = ("throughput", "latency")

# fp32 master + Adam m/v = 12, fp32 grad accumulator = 4, bf16 compute cast
# = 2 bytes/param on the fused path (matches bench.py's ladder sizing)
_STATE_BYTES_PER_PARAM = 18.0
_SHARDED_BYTES_PER_PARAM = 16.0  # the shardable share (master+opt+grads)


@dataclass
class TrialResult:
    overrides: dict
    samples_per_sec: float = 0.0
    step_ms: float = 0.0
    error: str | None = None
    # KnobSearch probe legs: the scalar objective + the probe's full metric
    # dict (goodput/MFU/overlap or tokens_per_s/SLO burn/gates)
    score: float = 0.0
    metrics: dict = field(default_factory=dict)

    @property
    def ok(self) -> bool:
        return self.error is None

    @property
    def skipped(self) -> bool:
        return bool(self.error) and self.error.startswith("pruned:")


@dataclass
class ModelInfo:
    """Reference ``model_info`` analog: what the pruner knows up front."""

    num_params: int
    hidden_size: int
    num_layers: int

    def state_bytes(self, stage: int, shards: int,
                    sharded_update: bool = False) -> float:
        p = float(self.num_params)
        if shards <= 1 or (stage <= 0 and not sharded_update):
            return p * _STATE_BYTES_PER_PARAM
        # stages shard progressively more of the 18 bytes/param:
        # 1: opt state (12), 2: + grads (16), 3: + the bf16 live params (18)
        shardable = ({1: 12.0, 2: 16.0, 3: 18.0}[min(stage, 3)]
                     if stage >= 1 else 0.0)
        # grad_overlap.sharded_update shards the fp32 master + Adam m/v
        # (12 bytes/param, the ZeRO-1 share) even at stage 0 — without this
        # the pruner rejects overlap configs that actually fit (PR 18)
        if sharded_update:
            shardable = max(shardable, 12.0)
        resident = _STATE_BYTES_PER_PARAM - shardable
        return p * (resident + shardable / shards)

    def activation_bytes(self, micro_batch: int, seq_len: int) -> float:
        # ~20 bf16 activation copies of [B, S, H] per layer without remat
        # (attention + MLP intermediates); a deliberate overestimate the
        # remat variant halves — pruning only needs the right order
        return 2.0 * 20 * micro_batch * seq_len * self.hidden_size * self.num_layers


def probe_model_info(model_builder, spec=None) -> ModelInfo:
    """Build the spec once (no weights) and read its static facts."""
    from deepspeed_tpu.models.api import ShardCtx

    if spec is None:
        spec = model_builder(ShardCtx()) if callable(model_builder) else model_builder
    cfg = getattr(spec, "config", None)
    return ModelInfo(
        num_params=int(getattr(spec, "num_params", 0) or 0),
        hidden_size=int(getattr(cfg, "hidden_size", 0) or 0),
        num_layers=int(getattr(cfg, "num_layers", 1) or 1),
    )


def device_memory_bytes() -> float | None:
    """Per-device memory when the backend reports it (TPU does; the CPU test
    mesh does not -> no pruning)."""
    import jax

    try:
        stats = jax.devices()[0].memory_stats()
        if stats and "bytes_limit" in stats:
            return float(stats["bytes_limit"])
    except Exception:
        pass
    return None


@dataclass
class Autotuner:
    """Measured config search (call ``tune()``)."""

    model_builder: object
    base_config: dict
    metric: str = "throughput"
    steps_per_trial: int = 3
    results: list = field(default_factory=list)

    def _apply_overrides(self, overrides: dict) -> dict:
        cfg = dict(self.base_config)
        zero = dict(cfg.get("zero_optimization", {}))
        if "zero_stage" in overrides:
            zero["stage"] = overrides["zero_stage"]
        if "offload" in overrides and overrides["offload"] != "none":
            zero["offload_optimizer"] = {"device": overrides["offload"]}
        if overrides.get("quantized_gradients"):
            zero["quantized_gradients"] = True
        cfg["zero_optimization"] = zero
        if "micro_batch" in overrides:
            cfg["train_micro_batch_size_per_device"] = overrides["micro_batch"]
            cfg.pop("train_batch_size", None)
        if "remat" in overrides:
            cfg["activation_checkpointing"] = {"enabled": overrides["remat"]}
        tp = overrides.get("tp", 1)
        sp = overrides.get("sp", 1)
        if tp > 1 or sp > 1:
            mesh = dict(cfg.get("mesh", {}))
            mesh.update({"data": -1, "tensor": tp, "sequence": sp})
            cfg["mesh"] = mesh
        cfg["steps_per_print"] = 0
        return cfg

    def _run_trial(self, overrides: dict, seq_len: int, vocab: int) -> TrialResult:
        import deepspeed_tpu
        from deepspeed_tpu.comm.topology import reset_topology

        cfg = self._apply_overrides(overrides)
        try:
            reset_topology()
            engine, _, _, _ = deepspeed_tpu.initialize(model=self.model_builder, config=cfg)
            # trial timing must not bleed across the async dispatch window:
            # settle every step (the production pipeline keeps _max_inflight)
            engine._max_inflight = 0
            rng = np.random.default_rng(0)

            def batch():
                return {"input_ids": rng.integers(
                    0, vocab, (engine.train_batch_size, seq_len), dtype=np.int32)}

            float(engine.train_batch(batch()))  # compile + settle
            t0 = time.perf_counter()
            for _ in range(self.steps_per_trial):
                loss = engine.train_batch(batch())
            float(loss)  # settle before reading the clock
            dt = (time.perf_counter() - t0) / self.steps_per_trial
            return TrialResult(
                overrides=overrides,
                samples_per_sec=engine.train_batch_size / dt,
                step_ms=dt * 1000,
            )
        except Exception as e:  # OOM / compile failure = failed experiment
            return TrialResult(overrides=overrides, error=f"{type(e).__name__}: {e}"[:300])

    def _record(self, res: TrialResult) -> None:
        self.results.append(res)
        log_dist(
            f"autotune {res.overrides}: "
            + (f"{res.samples_per_sec:.1f} samples/s" if res.ok
               else f"{'SKIPPED' if res.skipped else 'FAILED'} {res.error}"),
            ranks=[0],
        )

    def tune(
        self,
        micro_batch_sizes: list[int] = (1, 2, 4, 8),
        zero_stages: list[int] = (0, 1, 2, 3),
        seq_len: int = 128,
        vocab: int = 1024,
        try_remat: bool = False,
        offload_devices: list[str] = ("none",),
        tp_degrees: list[int] = (1,),
        sp_degrees: list[int] = (1,),
        try_qgz: bool = False,
        memory_bytes: float | None = None,
    ) -> dict:
        """Two-phase measured search; returns the best override dict
        (reference ``tune:404``).

        Phase 1: stage x micro-batch grid, pruned by the model-info memory
        estimate when the device reports its memory (reference model-profile
        pruning); larger micro batches per stage stop at the first OOM.
        Phase 2: the offload/TP/SP/qgZ dimensions sweep AROUND the phase-1
        winner (the reference's refinement loop), each varied independently.
        Phase 3: a bounded JOINT sweep over the dimensions that improved —
        pairwise products + the all-winners combo (capped at 8 trials) — so
        interactions the independent pass misses (offload x remat, tp x sp)
        still get tried, without the reference's full cartesian cost.
        """
        import jax

        self.results = []
        info = probe_model_info(self.model_builder)
        limit = memory_bytes if memory_bytes is not None else device_memory_bytes()
        n_dev = len(jax.devices())

        base_remat = bool(self.base_config.get(
            "activation_checkpointing", {}).get("enabled"))
        for stage in zero_stages:
            for mb in micro_batch_sizes:
                overrides = {"zero_stage": stage, "micro_batch": mb}
                if limit and info.num_params:
                    act = info.activation_bytes(mb, seq_len)
                    if try_remat or base_remat:
                        act /= 2  # prune against the BEST variant to be tried
                    est = info.state_bytes(stage, n_dev) + act
                    if est > 0.9 * limit:
                        self._record(TrialResult(
                            overrides=overrides,
                            error=f"pruned: est {est/1e9:.1f} GB > "
                                  f"0.9 x {limit/1e9:.1f} GB"))
                        continue
                variants = [dict(overrides)]
                if try_remat:
                    variants.append({**overrides, "remat": True})
                oomed = False
                for ov in variants:
                    res = self._run_trial(ov, seq_len, vocab)
                    self._record(res)
                    if not res.ok and "Resource" in (res.error or ""):
                        oomed = True
                if oomed:
                    break  # bigger micro batches will OOM too

        good = [r for r in self.results if r.ok]
        if not good:
            raise RuntimeError("autotuning: every trial failed")
        best = (max(good, key=lambda r: r.samples_per_sec)
                if self.metric == "throughput" else min(good, key=lambda r: r.step_ms))

        # phase 2: refine the winner along the remaining dimensions
        phase1_best = best
        refinements: list[tuple[str, dict]] = []  # (dimension, addition)
        for dev in offload_devices:
            if dev != "none":
                refinements.append(("offload", {"offload": dev}))
        for tp in tp_degrees:
            if tp > 1 and n_dev % tp == 0:
                refinements.append(("tp", {"tp": tp}))
        for sp in sp_degrees:
            if sp > 1 and n_dev % sp == 0 and seq_len % sp == 0:
                refinements.append(("sp", {"sp": sp}))
        if try_qgz and best.overrides.get("zero_stage", 0) >= 1:
            refinements.append(("qgz", {"quantized_gradients": True}))
        dim_best: dict[str, tuple[float, dict]] = {}
        for dim, add in refinements:
            res = self._run_trial({**best.overrides, **add}, seq_len, vocab)
            self._record(res)
            if res.ok and (dim not in dim_best
                           or res.samples_per_sec > dim_best[dim][0]):
                dim_best[dim] = (res.samples_per_sec, add)

        # phase 3: bounded JOINT sweep (round-4 weak #8 — independently
        # varied dimensions never try offload x tp-style interactions, which
        # the reference's fuller product sweep catches). Combine every
        # dimension whose best phase-2 value beat the phase-1 winner:
        # pairwise products plus the all-winners combo, capped.
        better = [(dim, add) for dim, (sps, add) in dim_best.items()
                  if sps > phase1_best.samples_per_sec]
        combos: list[dict] = []
        for i in range(len(better)):
            for j in range(i + 1, len(better)):
                combos.append({**better[i][1], **better[j][1]})
        if len(better) > 2:
            allw: dict = {}
            for _, add in better:
                allw.update(add)
            combos.append(allw)
        tried = {tuple(sorted(r.overrides.items())) for r in self.results}
        for add in combos[:8]:
            ov = {**phase1_best.overrides, **add}
            if tuple(sorted(ov.items())) in tried:
                continue
            res = self._run_trial(ov, seq_len, vocab)
            self._record(res)

        good = [r for r in self.results if r.ok]
        best = (max(good, key=lambda r: r.samples_per_sec)
                if self.metric == "throughput" else min(good, key=lambda r: r.step_ms))
        log_dist(f"autotune best: {best.overrides} ({best.samples_per_sec:.1f} samples/s)",
                 ranks=[0])
        return best.overrides


# ------------------------------------------------------------- knob search
def _bump(name: str, help_text: str) -> None:
    from deepspeed_tpu.telemetry import get_telemetry

    tel = get_telemetry()
    if tel.enabled:
        tel.counter(name, help_text).inc()


def default_probe_runner(kind: str, overrides: dict, steps: int = 3,
                         timeout: float = 180.0,
                         workload: str = "default"):
    """Shell out to ``bench.py --mode probe`` (the ``BENCH_PROBE`` child):
    bounded wall clock, JSON-only result, OOM/compile failures returned as
    structured errors instead of a dead child. Returns ``(dict|None, err)``."""
    import subprocess
    import sys

    root = os.path.dirname(os.path.dirname(
        os.path.dirname(os.path.abspath(__file__))))
    bench = os.path.join(root, "bench.py")
    env = dict(os.environ)
    env["BENCH_PROBE"] = "1"
    env["BENCH_PROBE_SPEC"] = json.dumps(
        {"kind": kind, "overrides": overrides, "steps": steps,
         "workload": workload})
    try:
        proc = subprocess.run(
            [sys.executable, bench], env=env, capture_output=True,
            text=True, timeout=timeout, cwd=root)
    except subprocess.TimeoutExpired:
        return None, {"reason": f"probe timed out after {timeout:g}s"}
    for line in reversed(proc.stdout.strip().splitlines()):
        if line.startswith("{"):
            try:
                res = json.loads(line)
            except json.JSONDecodeError:
                continue
            if res.get("error"):
                return None, res["error"]
            return res, None
    return None, {"reason": "no JSON in probe output", "rc": proc.returncode,
                  "stderr": (proc.stderr or "")[-2000:]}


@dataclass
class KnobSearch:
    """General measured search over the KnobSpace registry (one subsystem
    per search; run one for each engine). See the module docstring for the
    shape: coordinate ascent + headroom pruning + bounded probe legs +
    neighborhood refinement + content-keyed persistence."""

    subsystem: str  # knobs.TRAIN | knobs.SERVE
    model_info: ModelInfo | None = None
    space: object = None  # KnobSpace; DEFAULT_SPACE when None
    knob_names: tuple | None = None  # trim the sweep for a probe budget
    probe_runner: object = None  # (kind, overrides, steps) -> (dict, err)
    steps: int = 3
    seq_len: int = 128
    # device-byte budget for pruning and device count; None = what the
    # default-config probe reports it ran on (the CPU test mesh reports no
    # budget -> pruning off, every candidate is measured). This parent
    # never asks a jax backend itself: it would then hold the chip its
    # probe children need.
    memory_bytes: float | None = None
    n_devices: int | None = None
    cost_ctx: dict = field(default_factory=dict)  # knob cost-hint inputs
    workload: str = "default"
    profile_dir: str | None = None  # persist the winner when set
    max_trials: int = 32
    results: list = field(default_factory=list)

    # ----------------------------------------------------------- plumbing
    def _space(self):
        if self.space is None:
            from deepspeed_tpu.autotuning.knobs import DEFAULT_SPACE

            self.space = DEFAULT_SPACE
        return self.space

    def _knob_default(self, name):
        return self._space().get(name).default

    # ------------------------------------------------------------ pruning
    def _estimate_bytes(self, overrides: dict) -> float | None:
        """Candidate device-byte estimate, paid BEFORE any compile.

        Train: the ModelInfo state/activation formula on the candidate's
        stage x micro-batch x remat x sharded-update corner (the knobs
        interact — one formula, not summed hints). Serve: the sum of the
        knob cost hints over ``cost_ctx`` (extra bytes vs default)."""
        from deepspeed_tpu.autotuning import knobs as K

        ov = overrides
        if self.subsystem == K.TRAIN:
            info = self.model_info
            if info is None or not info.num_params:
                return None
            g = lambda n: ov.get(n, self._knob_default(n))  # noqa: E731
            stage = g("zero_optimization.stage")
            mb = g("train_micro_batch_size_per_device")
            sharded = (g("zero_optimization.grad_overlap.enabled")
                       and g("zero_optimization.grad_overlap.sharded_update"))
            act = info.activation_bytes(mb, self.seq_len)
            if g("activation_checkpointing.enabled"):
                act /= 2
            if self.n_devices is None:
                raise ValueError(
                    "KnobSearch: no device count — pass n_devices, or use a "
                    "probe runner whose result carries a 'device' block")
            return (info.state_bytes(stage, self.n_devices,
                                     sharded_update=sharded) + act)
        est = 0.0
        for name, value in ov.items():
            try:
                est += self._space().get(name).cost_bytes(value, self.cost_ctx)
            except KeyError:
                continue
        return est if est > 0.0 else None

    def _prune_reason(self, overrides: dict) -> str | None:
        limit = self.memory_bytes
        if not limit:
            return None
        est = self._estimate_bytes(overrides)
        if est is not None and est > 0.9 * limit:
            return (f"pruned: est {est/1e9:.2f} GB > "
                    f"0.9 x {limit/1e9:.2f} GB")
        return None

    # ------------------------------------------------------------- trials
    def _record(self, res: TrialResult) -> TrialResult:
        self.results.append(res)
        log_dist(
            f"autotune[{self.subsystem}] {res.overrides}: "
            + (f"score {res.score:.4g}" if res.ok
               else f"{'SKIPPED' if res.skipped else 'FAILED'} {res.error}"),
            ranks=[0],
        )
        return res

    def _probe(self, overrides: dict) -> TrialResult:
        runner = self.probe_runner or default_probe_runner
        _bump("autotune_trials_total",
              "autotune probe legs actually measured (pruned excluded)")
        result, err = runner(self.subsystem, overrides, self.steps)
        if result is None:
            _bump("autotune_failed_total",
                  "autotune probe legs that errored or tripped a gate")
            reason = (err or {}).get("reason") if isinstance(err, dict) else err
            return self._record(TrialResult(
                overrides=overrides, error=str(reason or "probe failed")[:300]))
        # hard disqualifiers: a perf config that changes tokens or leaks
        # memory is a non-result regardless of its score
        gates = [g for g in ("parity_ok", "census_ok")
                 if result.get(g) is False]
        if gates:
            _bump("autotune_failed_total",
                  "autotune probe legs that errored or tripped a gate")
            return self._record(TrialResult(
                overrides=overrides, metrics=result,
                error="gate: " + ", ".join(gates)))
        return self._record(TrialResult(
            overrides=overrides,
            score=float(result.get("score", 0.0)),
            samples_per_sec=float(result.get("samples_per_sec", 0.0) or 0.0),
            step_ms=float(result.get("step_ms", 0.0) or 0.0),
            metrics=result))

    def _try(self, overrides: dict, tried: set, best: TrialResult):
        key = tuple(sorted(overrides.items()))
        if key in tried:
            return best
        tried.add(key)
        measured = sum(1 for r in self.results if not r.skipped)
        if measured >= self.max_trials:
            return best
        reason = self._prune_reason(overrides)
        if reason:
            _bump("autotune_pruned_total",
                  "autotune candidates rejected by the headroom cost model "
                  "before compiling")
            self._record(TrialResult(overrides=overrides, error=reason))
            return best
        res = self._probe(overrides)
        # strict >: ties keep the earlier (simpler / closer-to-default) config
        if res.ok and res.score > best.score:
            return res
        return best

    # -------------------------------------------------------------- search
    def tune(self) -> dict:
        """Run the search; returns the summary dict (winner + bookkeeping).

        Coordinate ascent in registry order: each knob's domain is swept on
        top of the best-so-far override set, then the continuous knobs get a
        halve/double neighborhood pass around the winner. The hand-written
        default is trial 0, so ``best_score >= baseline_score`` holds by
        construction — the tuned profile can only ever match or beat it on
        the probe objective."""
        from deepspeed_tpu.autotuning import knobs as K

        space = self._space()
        sweep = space.knobs(self.subsystem, self.knob_names)
        if not sweep:
            raise ValueError(f"no knobs registered for {self.subsystem!r}")
        self.results = []
        tried: set = {()}
        baseline = self._probe({})
        if not baseline.ok:
            raise RuntimeError(
                f"autotuning: the default-config probe failed: {baseline.error}")
        device = baseline.metrics.get("device") or {}
        if self.n_devices is None:
            self.n_devices = device.get("count")
        if self.memory_bytes is None:
            self.memory_bytes = device.get("bytes_limit")
        best = baseline
        for knob in sweep:
            for value in knob.domain:
                cand = dict(best.overrides)
                if value == knob.default:
                    cand.pop(knob.name, None)
                else:
                    cand[knob.name] = value
                best = self._try(cand, tried, best)
        # neighborhood refinement around the winner (continuous knobs only)
        for knob in sweep:
            if not knob.continuous or knob.name not in best.overrides:
                continue
            for nv in knob.neighbors(best.overrides[knob.name]):
                best = self._try({**best.overrides, knob.name: nv},
                                 tried, best)

        pruned = sum(1 for r in self.results if r.skipped)
        failed = sum(1 for r in self.results if not r.ok and not r.skipped)
        gate_failures = sum(1 for r in self.results
                            if (r.error or "").startswith("gate:"))
        summary = {
            "subsystem": self.subsystem,
            "workload": self.workload,
            "best_overrides": best.overrides,
            "best_score": best.score,
            "baseline_score": baseline.score,
            "baseline_metrics": baseline.metrics,
            "best_metrics": best.metrics,
            "trials": len(self.results) - pruned,
            "pruned": pruned,
            "failed": failed,
            "gate_failures": gate_failures,
            # accepted (scored) trials passed every gate by construction;
            # violators are disqualified above and never become the winner
            "gate_violations_accepted": 0,
            "profile_path": None,
        }
        if self.profile_dir and self.model_info is not None:
            from deepspeed_tpu.autotuning import profiles

            summary["profile_path"] = profiles.save_profile(
                self.profile_dir,
                subsystem=(K.TRAIN if self.subsystem == K.TRAIN else K.SERVE),
                fingerprint=profiles.model_fingerprint(self.model_info),
                topology=device.get("topology"),
                workload=self.workload,
                overrides=best.overrides,
                score=best.score,
                baseline_score=baseline.score,
                space=space)
        log_dist(
            f"autotune[{self.subsystem}] best: {best.overrides} "
            f"(score {best.score:.4g} vs default {baseline.score:.4g}; "
            f"{summary['trials']} measured, {pruned} pruned, "
            f"{failed} failed)", ranks=[0])
        return summary
