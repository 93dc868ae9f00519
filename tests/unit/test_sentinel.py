"""Self-healing training: device verdict, quarantine, policy ladder,
liveness, and the engine-level heal loop (docs/FAULT_TOLERANCE.md
"Training: self-healing")."""

import json
import os
import time
import tracemalloc

import jax.numpy as jnp
import numpy as np
import pytest

import deepspeed_tpu
from deepspeed_tpu.config.config import SentinelConfig
from deepspeed_tpu.models import llama
from deepspeed_tpu.runtime import sentinel
from deepspeed_tpu.runtime.dataloader import (CheckpointableLoader,
                                              RepeatingLoader)
from deepspeed_tpu.utils.faults import classify_transient, get_fault_injector

VOCAB = 97


def _vcfg(**kw):
    kw.setdefault("enabled", True)
    kw.setdefault("warmup_steps", 3)
    kw.setdefault("grad_window", 4)
    kw.setdefault("grad_quantile", 0.75)
    return SentinelConfig(**kw)


def _feed(st, cfg, n, loss=1.0, gnorm=1.0):
    """Push n accepted steps through the verdict; returns the state."""
    for i in range(n):
        st, anom, _, _ = sentinel.verdict(
            st, jnp.float32(loss + 0.01 * i), jnp.float32(gnorm),
            jnp.asarray(True), cfg)
        assert not bool(anom)
    return st


# ------------------------------------------------------------- device verdict
class TestVerdict:
    def test_warmup_gates_loss_spike(self):
        """Before warmup_steps accepted steps the loss gate is unarmed: a
        huge-but-finite first loss is ordinary early training, not anomaly."""
        cfg = _vcfg(warmup_steps=5)
        st = sentinel.init_state(cfg)
        st, anom, reason, _ = sentinel.verdict(
            st, jnp.float32(1e4), jnp.float32(1.0), jnp.asarray(True), cfg)
        assert not bool(anom) and int(reason) == 0
        assert int(st.seen) == 1  # accepted into the stats

    def test_nonfinite_flags_even_in_warmup(self):
        cfg = _vcfg(warmup_steps=100)
        st = sentinel.init_state(cfg)
        st, anom, reason, _ = sentinel.verdict(
            st, jnp.float32(1.0), jnp.float32(1.0), jnp.asarray(False), cfg)
        assert bool(anom)
        assert int(reason) & sentinel.REASON_NONFINITE
        _, anom2, reason2, _ = sentinel.verdict(
            st, jnp.float32(float("nan")), jnp.float32(1.0),
            jnp.asarray(True), cfg)
        assert bool(anom2) and int(reason2) & sentinel.REASON_NONFINITE

    def test_loss_spike_flagged_and_stats_not_poisoned(self):
        cfg = _vcfg()
        st = _feed(sentinel.init_state(cfg), cfg, 5)
        ema0, var0, seen0 = st.loss_ema, st.loss_var, int(st.seen)
        st, anom, reason, _ = sentinel.verdict(
            st, jnp.float32(100.0), jnp.float32(1.0), jnp.asarray(True), cfg)
        assert bool(anom)
        assert "loss-spike" in sentinel.reason_names(int(reason))
        # the spike must NOT be chased into the rolling stats — an ingested
        # spike would mask the next one
        assert float(st.loss_ema) == float(ema0)
        assert float(st.loss_var) == float(var0)
        assert int(st.seen) == seen0

    def test_gnorm_spike_flagged(self):
        cfg = _vcfg()
        st = _feed(sentinel.init_state(cfg), cfg, 5)
        _, anom, reason, _ = sentinel.verdict(
            st, jnp.float32(1.0), jnp.float32(500.0), jnp.asarray(True), cfg)
        assert bool(anom)
        assert "grad-spike" in sentinel.reason_names(int(reason))

    def test_streak_counts_and_resets_like_good_steps(self):
        """The streak mirrors precision.update_loss_scale's good_steps: one
        accepted step zeroes it, each skip increments it, and crossing
        max_consecutive_skips raises REASON_SKIP_STREAK."""
        cfg = _vcfg(max_consecutive_skips=2)
        st = _feed(sentinel.init_state(cfg), cfg, 5)
        st, _, reason, streak = sentinel.verdict(
            st, jnp.float32(1.0), jnp.float32(1.0), jnp.asarray(False), cfg)
        assert int(streak) == 1
        assert not int(reason) & sentinel.REASON_SKIP_STREAK
        st, _, reason, streak = sentinel.verdict(
            st, jnp.float32(1.0), jnp.float32(1.0), jnp.asarray(False), cfg)
        assert int(streak) == 2
        assert int(reason) & sentinel.REASON_SKIP_STREAK
        st, anom, _, streak = sentinel.verdict(
            st, jnp.float32(1.0), jnp.float32(1.0), jnp.asarray(True), cfg)
        assert not bool(anom) and int(streak) == 0


# ------------------------------------------------------------- fingerprinting
class TestFingerprint:
    def test_key_order_independent(self):
        a = {"x": np.arange(6, dtype=np.int32),
             "y": np.ones((2, 3), np.float32)}
        b = dict(reversed(list(a.items())))
        assert sentinel.batch_fingerprint(a) == sentinel.batch_fingerprint(b)

    def test_content_shape_dtype_sensitive(self):
        base = {"x": np.arange(6, dtype=np.int32)}
        fp = sentinel.batch_fingerprint(base)
        bumped = {"x": np.arange(6, dtype=np.int32)}
        bumped["x"][3] += 1
        assert sentinel.batch_fingerprint(bumped) != fp
        assert sentinel.batch_fingerprint(
            {"x": np.arange(6, dtype=np.int64)}) != fp
        assert sentinel.batch_fingerprint(
            {"x": np.arange(6, dtype=np.int32).reshape(2, 3)}) != fp

    def test_concat_resplit_round_trip(self):
        """The engine fingerprints GAS microbatches by reshaping the
        concatenated batch; that must reproduce the fingerprints of the
        original loader-delivered microbatches bit-for-bit."""
        rng = np.random.default_rng(0)
        micro = [{"input_ids": rng.integers(0, VOCAB, (4, 8), np.int32)}
                 for _ in range(3)]
        want = [sentinel.batch_fingerprint(m) for m in micro]
        cat = {"input_ids": np.concatenate([m["input_ids"] for m in micro])}
        got = []
        for i in range(3):
            v = cat["input_ids"]
            got.append(sentinel.batch_fingerprint(
                {"input_ids": v.reshape((3, v.shape[0] // 3) + v.shape[1:])[i]}))
        assert got == want


# ------------------------------------------------------- loaders + quarantine
def _batches(n, seed=0):
    rng = np.random.default_rng(seed)
    return [{"input_ids": rng.integers(0, VOCAB, (2, 4), np.int32)}
            for _ in range(n)]


class TestLoaderQuarantine:
    def test_repeating_loader_skips_and_counts_raw(self):
        data = _batches(4)
        dl = RepeatingLoader(data)
        bad = sentinel.batch_fingerprint(data[1])
        dl.quarantine([bad])
        first, second = next(dl), next(dl)
        np.testing.assert_array_equal(first["input_ids"],
                                      data[0]["input_ids"])
        np.testing.assert_array_equal(second["input_ids"],
                                      data[2]["input_ids"])  # 1 skipped
        assert dl.quarantined_skipped == 1
        # position counts RAW pulls (3: delivered 0, skipped 1, delivered 2)
        assert dl.state_dict()["pos"] == 3
        assert dl.state_dict()["quarantine"] == [bad]

    def test_repeating_loader_state_round_trip(self):
        data = _batches(5, seed=1)
        dl = RepeatingLoader(data)
        bad = sentinel.batch_fingerprint(data[2])
        dl.quarantine([bad])
        for _ in range(3):  # delivers 0, 1, 3 (2 skipped)
            next(dl)
        state = dl.state_dict()
        fresh = RepeatingLoader(_batches(5, seed=1))
        fresh.load_state_dict(state)
        np.testing.assert_array_equal(next(fresh)["input_ids"],
                                      data[4]["input_ids"])
        assert fresh.quarantined == [bad]  # unioned, never cleared

    def test_checkpointable_loader_state_round_trip(self):
        def factory(skip):
            def gen():
                i = skip
                while True:
                    r = np.random.default_rng(100 + i)
                    yield {"input_ids": r.integers(0, VOCAB, (2, 4), np.int32)}
                    i += 1
            return gen()

        dl = CheckpointableLoader(factory)
        bad = sentinel.batch_fingerprint(next(factory(1)))
        dl.quarantine([bad])
        got = [next(dl) for _ in range(2)]  # stream 0 and 2 (1 skipped)
        np.testing.assert_array_equal(got[1]["input_ids"],
                                      next(factory(2))["input_ids"])
        assert dl.batches_consumed == 3  # raw pulls, skip included
        fresh = CheckpointableLoader(factory)
        fresh.load_state_dict(dl.state_dict())
        np.testing.assert_array_equal(next(fresh)["input_ids"],
                                      next(factory(3))["input_ids"])
        assert fresh.quarantined == [bad]


# ------------------------------------------------------------- policy ladder
def _pcfg(tmp_path=None, **kw):
    kw.setdefault("enabled", True)
    kw.setdefault("window_steps", 10)
    if tmp_path is not None:
        kw.setdefault("state_dir", str(tmp_path / "state"))
    return SentinelConfig(**kw)


class TestPolicyLadder:
    def test_three_strikes_escalate(self, tmp_path):
        pol = sentinel.SentinelPolicy(_pcfg(tmp_path))
        assert pol.observe(sentinel.REASON_LOSS_SPIKE, ["aaa"],
                           latest_tag="global_step3") == "quarantine"
        assert pol.rollback_tag == "global_step3"  # pinned at strike 1
        assert pol.observe(sentinel.REASON_LOSS_SPIKE, ["bbb"],
                           latest_tag="global_step5") == "rollback"
        # the pin must NOT chase the newest checkpoint: global_step5 was
        # saved after the first anomaly skewed the batch stream
        assert pol.rollback_tag == "global_step3"
        assert pol.observe(sentinel.REASON_GRAD_SPIKE, []) == "halt"
        assert pol.quarantined == ["aaa", "bbb"]
        assert pol.anomalies == 3

    def test_reduce_lr_third_strike(self):
        pol = sentinel.SentinelPolicy(_pcfg(on_third_strike="reduce-lr"))
        pol.observe(1, [])
        pol.observe(1, [])
        assert pol.observe(1, []) == "reduce-lr"

    def test_rollback_rung_skippable(self):
        pol = sentinel.SentinelPolicy(_pcfg(rollback=False))
        assert pol.observe(1, []) == "quarantine"
        assert pol.observe(1, []) == "halt"  # rung 2 disabled -> escalate

    def test_window_expiry_resets_ladder(self):
        pol = sentinel.SentinelPolicy(_pcfg(window_steps=5))
        assert pol.observe(1, ["aaa"]) == "quarantine"
        for _ in range(10):  # accepted steps age the strike out
            pol.tick()
        assert pol.observe(1, ["bbb"]) == "quarantine"  # strike 1 again
        assert pol.strikes_in_window == 1
        assert pol.quarantined == ["aaa", "bbb"]  # quarantine is monotonic

    def test_wedge_budget(self):
        pol = sentinel.SentinelPolicy(_pcfg(max_wedges=2))
        assert pol.observe_wedge() == "rollback"
        assert pol.observe_wedge() == "halt"  # budget spent
        pol2 = sentinel.SentinelPolicy(_pcfg(max_wedges=3, rollback=False))
        assert pol2.observe_wedge() == "halt"  # no rollback rung -> halt

    def test_quarantine_persistence_and_torn_file(self, tmp_path):
        state = str(tmp_path / "state")
        cfg = _pcfg(state_dir=state)
        pol = sentinel.SentinelPolicy(cfg)
        pol.quarantine(["bbb", "aaa", "", "aaa"])  # empty/dup dropped
        assert sentinel.load_quarantine(state) == ["aaa", "bbb"]
        # a fresh policy (restarted worker) reloads the healing memory
        assert sentinel.SentinelPolicy(cfg).quarantined == ["aaa", "bbb"]
        # a torn file reads as empty rather than crashing the restart
        with open(sentinel.quarantine_path(state), "w") as f:
            f.write('["aaa", "bb')
        assert sentinel.load_quarantine(state) == []
        assert sentinel.SentinelPolicy(cfg).quarantined == []


# ------------------------------------------------------------------ liveness
class TestLiveness:
    def test_watched_call_passes_values_and_errors(self):
        assert sentinel.watched_call(lambda: 42, timeout_s=5.0) == 42
        with pytest.raises(KeyError):
            sentinel.watched_call(lambda: {}["missing"], timeout_s=5.0)

    def test_watched_call_wedge_is_transient(self):
        with pytest.raises(sentinel.TrainingWedgeError) as ei:
            sentinel.watched_call(lambda: time.sleep(5), timeout_s=0.05)
        # shared taxonomy with the serving dispatch fence: a wedge is
        # transient (recovery = rollback/restart), not a crash
        assert classify_transient(ei.value)

    def test_heartbeat_throttles(self, tmp_path):
        hb = sentinel.Heartbeat(str(tmp_path), rank=0, interval_s=60.0)
        assert hb.beat(1)
        assert not hb.beat(2)  # inside the throttle window
        payload = json.loads(open(hb.path).read())
        assert payload["step"] == 1 and payload["pid"] == os.getpid()
        hb2 = sentinel.Heartbeat(str(tmp_path), rank=0, interval_s=0.0)
        assert hb2.beat(3) and hb2.beat(4)  # interval 0 -> every step


# -------------------------------------------------------------- engine level
def _builder():
    return lambda ctx: llama.build(llama.LlamaConfig.tiny(VOCAB), ctx=ctx)


def _config(sentinel_over=None, **over):
    cfg = {
        "train_micro_batch_size_per_device": 2,
        "gradient_accumulation_steps": 1,
        "steps_per_print": 0,
        "optimizer": {"type": "adamw", "params": {"lr": 1e-3}},
        "zero_optimization": {"stage": 1},
        "mesh": {"data": 8},
        "bf16": {"enabled": False},
        "seed": 7,
    }
    cfg.update(over)
    if sentinel_over is not None:
        sent = {"enabled": True, "warmup_steps": 3, "window_steps": 50}
        sent.update(sentinel_over)
        cfg["sentinel"] = sent
    return cfg


def _batch_for(i, batch=16, seq=16):
    rng = np.random.default_rng(1000 + i)
    return {"input_ids": rng.integers(0, VOCAB, (batch, seq), np.int32)}


def _stream_factory(skip):
    def gen():
        i = skip
        while True:
            yield _batch_for(i)
            i += 1
    return gen()


class TestEngineSentinel:
    def test_disabled_trajectory_identical(self):
        """sentinel.enabled=False must trace the exact pre-sentinel step
        program: bit-identical losses to a config with no sentinel block."""
        from deepspeed_tpu.comm.topology import reset_topology

        engine_a, _, _, _ = deepspeed_tpu.initialize(
            model=_builder(), config=_config(), seed=11)
        base = [float(engine_a.train_batch(_batch_for(i))) for i in range(4)]
        reset_topology()
        engine_b, _, _, _ = deepspeed_tpu.initialize(
            model=_builder(),
            config=_config(sentinel_over={"enabled": False}), seed=11)
        off = [float(engine_b.train_batch(_batch_for(i))) for i in range(4)]
        assert base == off

    def test_disabled_after_step_never_syncs_skip_flag(self):
        """Satellite pin: steady state (no monitor/telemetry) must not
        host-sync the skip flag in _after_step — bf16 AND fp16. A guard
        object that raises on bool() rides through the metrics dict."""

        class GuardScalar:
            def astype(self, dtype):
                return jnp.int32(0)

            def __bool__(self):
                raise AssertionError(
                    "_after_step host-synced the skip flag on the hot path")

        from deepspeed_tpu.comm.topology import reset_topology

        for precision_cfg in ({"bf16": {"enabled": True}},
                              {"fp16": {"enabled": True}}):
            reset_topology()
            engine, _, _, _ = deepspeed_tpu.initialize(
                model=_builder(), config=_config(**precision_cfg), seed=11)
            engine.train_batch(_batch_for(0))
            engine._after_step({"skipped": GuardScalar()})  # must not raise

    def test_disabled_hot_path_allocates_nothing_from_sentinel(self):
        engine, _, _, _ = deepspeed_tpu.initialize(
            model=_builder(), config=_config(), seed=11)
        for i in range(2):  # warm the jit + host caches
            engine.train_batch(_batch_for(i))
        tracemalloc.start()
        try:
            engine.train_batch(_batch_for(2))
            snap = tracemalloc.take_snapshot()
        finally:
            tracemalloc.stop()
        stats = snap.filter_traces(
            [tracemalloc.Filter(True, "*/runtime/sentinel.py")]).statistics(
                "lineno")
        assert not stats, stats

    def test_detects_spike_and_quarantines(self, tmp_path):
        """A loss-spike directive at the train.grads seam is flagged by the
        fused verdict; strike 1 quarantines the batch fingerprints and
        writes forensics."""
        report_dir = str(tmp_path / "reports")
        state_dir = str(tmp_path / "state")
        get_fault_injector().configure([
            {"point": "train.grads", "kind": "loss-spike",
             "after": 4, "times": 1}])
        engine, _, _, _ = deepspeed_tpu.initialize(
            model=_builder(),
            config=_config(sentinel_over={"report_dir": report_dir,
                                          "state_dir": state_dir}),
            seed=11)
        for i in range(6):
            engine.train_batch(_batch_for(i))
        pol = engine._sentinel
        want_fp = sentinel.batch_fingerprint(_batch_for(4))
        assert pol.anomalies == 1
        assert pol.quarantined == [want_fp]
        assert sentinel.load_quarantine(state_dir) == [want_fp]
        reports = os.listdir(report_dir)
        assert any(r.startswith("sentinel_quarantine_") for r in reports)
        ctx = json.loads(open(os.path.join(report_dir, reports[0])).read())
        assert ctx["action"] == "quarantine"
        assert ctx["fingerprints"] == [want_fp]
        assert "loss-spike" in ctx["reason"]

    def test_rollback_replay_matches_clean_run(self, tmp_path):
        """The full heal: nan-grads (strike 1, quarantine + pin), poisoned
        batch (strike 2, rollback to the pinned tag + replay with the
        quarantine honored). The stitched trajectory must equal a clean
        sentinel-enabled run that never saw the quarantined batches."""
        from deepspeed_tpu.comm.topology import reset_topology

        total, save_every = 10, 3
        ckpt = str(tmp_path / "ckpt")
        poison_fp = sentinel.batch_fingerprint(_batch_for(6))
        get_fault_injector().configure([
            {"point": "train.grads", "kind": "nan-grads",
             "after": 3, "times": 1},
            {"point": "data.batch", "kind": "poison-batch",
             "request_id": poison_fp, "times": 1}])
        sent = {"report_dir": str(tmp_path / "reports"),
                "state_dir": str(tmp_path / "state"),
                "checkpoint_dir": ckpt}
        engine, _, _, _ = deepspeed_tpu.initialize(
            model=_builder(), config=_config(sentinel_over=sent), seed=11,
            training_data=CheckpointableLoader(_stream_factory))
        healed: dict[int, float] = {}
        rollbacks = 0
        while engine.global_steps < total:
            step = engine.global_steps
            loss = engine.train_batch()
            if engine.global_steps <= step:
                rollbacks += 1
                continue  # rolled back mid-call; the replay rewrites steps
            healed[step] = float(loss)
            if engine.global_steps % save_every == 0:
                engine.save_checkpoint(ckpt)
        assert rollbacks == 1
        assert engine.train_rollbacks == 1
        quarantined = set(engine._sentinel.quarantined)
        assert quarantined == {sentinel.batch_fingerprint(_batch_for(3)),
                               poison_fp}

        # clean reference: same stream, quarantine pre-seeded, no faults
        get_fault_injector().reset()
        reset_topology()
        ref_state = str(tmp_path / "ref_state")
        sentinel.save_quarantine(ref_state, sorted(quarantined))
        ref_sent = {"report_dir": str(tmp_path / "ref_reports"),
                    "state_dir": ref_state}
        ref, _, _, _ = deepspeed_tpu.initialize(
            model=_builder(), config=_config(sentinel_over=ref_sent), seed=11,
            training_data=CheckpointableLoader(_stream_factory))
        ref._apply_quarantine_to_loader()
        clean = [float(ref.train_batch()) for _ in range(total)]
        assert set(healed) == set(range(total))
        np.testing.assert_allclose([healed[i] for i in range(total)], clean,
                                   rtol=1e-6, atol=0.0)

    def test_rollback_without_checkpoint_halts(self, tmp_path):
        """Strike 2 with no verified checkpoint anywhere: the ladder halts
        loudly with a forensics report instead of limping on."""
        report_dir = str(tmp_path / "reports")
        get_fault_injector().configure([
            {"point": "train.grads", "kind": "nan-grads",
             "after": 3, "times": 2}])
        engine, _, _, _ = deepspeed_tpu.initialize(
            model=_builder(),
            config=_config(sentinel_over={"report_dir": report_dir}),
            seed=11)
        with pytest.raises(sentinel.DivergenceHaltError) as ei:
            for i in range(6):
                engine.train_batch(_batch_for(i))
        assert ei.value.report and os.path.exists(ei.value.report)
        report = json.loads(open(ei.value.report).read())
        assert report["type"] == "sentinel_report"
