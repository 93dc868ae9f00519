"""Tuned-profile persistence + the KnobSpace search driver (pure host-side:
no engines are built — probe legs are faked, so everything runs in-process).

Covers the docs/AUTOTUNING.md contracts: content-key stability across
restarts, stale-profile rejection when the model fingerprint or device
count changes, config-file-wins precedence on both engines, torn-file
tolerance (the PR 9 temp+fsync+os.replace commit protocol), headroom
pruning before compile, and the parity/census hard gates."""

import json
import os

import pytest

from deepspeed_tpu.autotuning import (
    DEFAULT_SPACE,
    SERVE,
    TRAIN,
    Knob,
    KnobSearch,
    KnobSpace,
    ModelInfo,
    profiles,
)
from deepspeed_tpu.config.config import Config, load_config

INFO = ModelInfo(num_params=600_000, hidden_size=128, num_layers=2)
FP = profiles.model_fingerprint(INFO)
TOPO = "tpu:8:TPU v4"


class TestProfilePersistence:
    def _save(self, d, **kw):
        args = dict(subsystem=TRAIN, fingerprint=FP, topology=TOPO,
                    workload="default",
                    overrides={"train_micro_batch_size_per_device": 8},
                    score=2.0, baseline_score=1.0)
        args.update(kw)
        return profiles.save_profile(str(d), **args)

    def test_content_key_stable_across_restarts(self):
        k1 = profiles.profile_key(FP, TOPO, "default", TRAIN)
        k2 = profiles.profile_key(FP, TOPO, "default", TRAIN)
        assert k1 == k2
        # any identity component changing moves the key
        assert profiles.profile_key("p1-h2-l3", TOPO, "default", TRAIN) != k1
        assert profiles.profile_key(FP, "tpu:16:TPU v4", "default", TRAIN) != k1
        assert profiles.profile_key(FP, TOPO, "long-context", TRAIN) != k1
        assert profiles.profile_key(FP, TOPO, "default", SERVE) != k1

    def test_round_trip(self, tmp_path):
        path = self._save(tmp_path)
        assert os.path.exists(path)
        prof = profiles.load_profile(str(tmp_path), subsystem=TRAIN,
                                     fingerprint=FP, topology=TOPO)
        assert prof is not None
        assert prof["overrides"] == {"train_micro_batch_size_per_device": 8}
        assert prof["score"] == 2.0 and prof["baseline_score"] == 1.0

    def test_stale_rejected_on_model_change(self, tmp_path):
        self._save(tmp_path)
        assert profiles.load_profile(
            str(tmp_path), subsystem=TRAIN, fingerprint="p999-h1-l1",
            topology=TOPO) is None

    def test_stale_rejected_on_device_count_change(self, tmp_path):
        self._save(tmp_path)
        assert profiles.load_profile(
            str(tmp_path), subsystem=TRAIN, fingerprint=FP,
            topology="tpu:16:TPU v4") is None

    def test_tampered_file_rejected(self, tmp_path):
        """A file copied to the right key but recording a different
        identity inside (rsync'd between machines) is rejected."""
        path = self._save(tmp_path)
        prof = json.load(open(path))
        prof["fingerprint"] = "p999-h1-l1"
        with open(path, "w") as f:
            json.dump(prof, f)
        assert profiles.load_profile(str(tmp_path), subsystem=TRAIN,
                                     fingerprint=FP, topology=TOPO) is None

    def test_torn_file_tolerated(self, tmp_path):
        path = self._save(tmp_path)
        full = open(path).read()
        with open(path, "w") as f:
            f.write(full[: len(full) // 2])  # simulated torn write
        assert profiles.load_profile(str(tmp_path), subsystem=TRAIN,
                                     fingerprint=FP, topology=TOPO) is None

    def test_atomic_commit_leaves_no_temp_files(self, tmp_path):
        self._save(tmp_path)
        assert not [n for n in os.listdir(tmp_path) if ".tmp." in n]

    def test_knobspace_change_invalidates(self, tmp_path):
        self._save(tmp_path)
        other = KnobSpace(version=DEFAULT_SPACE.version + 1)
        other.register(Knob("train_micro_batch_size_per_device", TRAIN,
                            (1, 2), 2))
        assert profiles.load_profile(
            str(tmp_path), subsystem=TRAIN, fingerprint=FP, topology=TOPO,
            space=other) is None


class TestPrecedence:
    PROF = {"overrides": {
        "zero_optimization.stage": 2,
        "train_micro_batch_size_per_device": 8,
        "activation_checkpointing.enabled": True,
    }}

    def test_config_file_wins_over_tuned(self):
        raw = {"zero_optimization": {"stage": 3},
               "train_micro_batch_size_per_device": 2}
        cfg = load_config(raw)
        rec = profiles.apply_train_profile(cfg, raw, self.PROF)
        # explicitly-written keys keep their config-file values
        assert cfg.zero_optimization.stage == 3
        assert cfg.train_micro_batch_size_per_device == 2
        # the unwritten knob is filled from the profile
        assert cfg.activation_checkpointing.enabled is True
        assert "zero_optimization.stage" in rec["skipped"]
        assert "activation_checkpointing.enabled" in rec["applied"]

    def test_unwritten_knobs_filled(self):
        raw = {}
        cfg = load_config(raw)
        rec = profiles.apply_train_profile(cfg, raw, self.PROF)
        assert cfg.zero_optimization.stage == 2
        assert cfg.train_micro_batch_size_per_device == 8
        assert len(rec["applied"]) == 3 and not rec["skipped"]

    def test_legacy_zero_alias_counts_as_written(self):
        raw = {"zero": {"stage": 1}, "train_batch_size": 4}
        cfg = load_config(raw)
        profiles.apply_train_profile(cfg, raw, self.PROF)
        assert cfg.zero_optimization.stage == 1

    def test_batch_triangle_pin_blocks_tuned_micro_batch(self):
        """A pinned train_batch_size means the tuned micro-batch must not
        silently change gradient accumulation."""
        raw = {"train_batch_size": 64}
        cfg = load_config(raw)
        rec = profiles.apply_train_profile(cfg, raw, self.PROF)
        assert cfg.train_micro_batch_size_per_device is None
        assert "train_micro_batch_size_per_device" in rec["skipped"]

    def test_programmatic_config_default_wins(self):
        """No raw dict (Config built in code): a knob off its dataclass
        default counts as user-written."""
        cfg = Config()
        cfg.zero_optimization.stage = 1
        rec = profiles.apply_train_profile(cfg, None, self.PROF)
        assert cfg.zero_optimization.stage == 1  # user's value kept
        assert cfg.activation_checkpointing.enabled is True
        assert "zero_optimization.stage" in rec["skipped"]

    @pytest.mark.parametrize(
        "stale", [{}, {"a_knob_since_removed": 8},
                  {"sched_steps": 16, "spec_draft": 4}],
        ids=["current", "stale", "k_step_knobs"])
    def test_serving_profile_fills_defaults_only(self, stale):
        """Config wins, defaults are filled, and a saved profile that names
        a knob ``RaggedConfig`` no longer has is skipped, not an error."""
        from deepspeed_tpu.inference.ragged import RaggedConfig

        rcfg = RaggedConfig(prefill_tile=4)  # operator-written
        rec = profiles.apply_serving_profile(
            rcfg, {"overrides": {"prefill_tile": 16,
                                 "enable_prefix_cache": True, **stale}})
        assert rcfg.prefill_tile == 4  # config wins
        assert rcfg.enable_prefix_cache is True  # still-default field filled
        assert rec["skipped"] == {"prefill_tile": 16, **stale}
        assert rec["applied"] == {"enable_prefix_cache": True}
        assert not any(hasattr(rcfg, name) for name in stale)


class TestKnobSpace:
    def test_registry_shape(self):
        train = DEFAULT_SPACE.knobs(TRAIN)
        serve = DEFAULT_SPACE.knobs(SERVE)
        assert len(train) >= 5 and len(serve) == 5
        for k in train + serve:
            assert k.default in k.domain
        # a serve knob is a RaggedConfig field: a knob for a field that is
        # gone would tune nothing
        from deepspeed_tpu.inference.ragged import RaggedConfig

        assert all(hasattr(RaggedConfig, k.name) for k in serve)

    def test_trim_and_order(self):
        names = ("activation_checkpointing.enabled",
                 "train_micro_batch_size_per_device")
        got = [k.name for k in DEFAULT_SPACE.knobs(TRAIN, names)]
        assert got == list(names)
        with pytest.raises(KeyError):
            DEFAULT_SPACE.knobs(TRAIN, ("no_such_knob",))

    def test_neighbors_respect_domain_hull(self):
        mb = DEFAULT_SPACE.get("train_micro_batch_size_per_device")
        assert set(mb.neighbors(4)) == {2, 8}
        assert mb.neighbors(16) == [8]  # 32 is past the hull
        guard = DEFAULT_SPACE.get("headroom_guard_fraction")
        assert 0.04 in guard.neighbors(0.02)
        remat = DEFAULT_SPACE.get("activation_checkpointing.enabled")
        assert remat.neighbors(True) == []  # discrete: no neighborhood

    def test_cost_hint_quant_credits_pool_bytes(self):
        q = DEFAULT_SPACE.get("quant")
        assert q.cost_bytes("int8", {"kv_pool_bytes": 1000}) == -500.0


class TestModelInfoShardedUpdate:
    def test_sharded_update_shards_master_and_opt(self):
        p = float(INFO.num_params)
        # stage 0 + sharded update == the ZeRO-1 estimate (master+opt = 12
        # of the 18 bytes/param shard across the data axis)
        assert INFO.state_bytes(0, 8, sharded_update=True) == \
            INFO.state_bytes(1, 8)
        assert INFO.state_bytes(0, 8, sharded_update=True) == \
            p * (6.0 + 12.0 / 8)
        # no shards -> no effect; higher stages already shard >= 12
        assert INFO.state_bytes(0, 1, sharded_update=True) == \
            INFO.state_bytes(0, 1)
        assert INFO.state_bytes(2, 8, sharded_update=True) == \
            INFO.state_bytes(2, 8)
        # positional call signature unchanged (existing callers)
        assert INFO.state_bytes(3, 8) < INFO.state_bytes(1, 8)


def _fake_runner(scores, calls=None, gates=None):
    """Probe runner stub: scores[frozenset(overrides.items())] -> score."""
    def runner(kind, overrides, steps):
        if calls is not None:
            calls.append(dict(overrides))
        key = frozenset(overrides.items())
        out = {"score": scores.get(key, 1.0), "samples_per_sec": 1.0}
        out.update((gates or {}).get(key, {}))
        return out, None
    return runner


class TestKnobSearch:
    MB = "train_micro_batch_size_per_device"
    REMAT = "activation_checkpointing.enabled"

    def test_headroom_prunes_before_probing(self):
        calls = []
        search = KnobSearch(
            TRAIN, model_info=INFO, n_devices=1, seq_len=128,
            knob_names=(self.MB,),
            # mb=8 fits, mb=16 must prune without a probe call
            memory_bytes=(INFO.state_bytes(0, 1)
                          + INFO.activation_bytes(8, 128)) * 1.01 / 0.9,
            probe_runner=_fake_runner({}, calls))
        out = search.tune()
        assert out["pruned"] >= 1
        assert not any(ov.get(self.MB) == 16 for ov in calls)
        pruned = [r for r in search.results if r.skipped]
        assert pruned and pruned[0].overrides[self.MB] == 16
        assert pruned[0].error.startswith("pruned:")

    def test_remat_halves_the_activation_estimate(self):
        est = lambda ov: KnobSearch(  # noqa: E731
            TRAIN, model_info=INFO, n_devices=1,
            seq_len=128)._estimate_bytes(ov)
        assert (est({self.MB: 8, self.REMAT: True})
                == est({self.MB: 8}) - INFO.activation_bytes(8, 128) / 2)

    def test_sharded_update_unlocks_pruned_corner(self):
        """The PR 18 fix: grad_overlap.sharded_update shrinks the stage-0
        state estimate so the pruner admits configs that actually fit."""
        ov_dense = {self.MB: 2}
        ov_sharded = {self.MB: 2,
                      "zero_optimization.grad_overlap.enabled": True,
                      "zero_optimization.grad_overlap.sharded_update": True}
        search = KnobSearch(TRAIN, model_info=INFO, n_devices=8, seq_len=128)
        assert (search._estimate_bytes(ov_sharded)
                < search._estimate_bytes(ov_dense))
        limit = search._estimate_bytes(ov_sharded) * 1.01 / 0.9
        search.memory_bytes = limit
        assert search._prune_reason(ov_dense)
        assert search._prune_reason(ov_sharded) is None

    def test_best_never_below_baseline_and_ascends(self):
        scores = {frozenset(): 1.0,
                  frozenset({(self.MB, 4)}): 2.0,
                  frozenset({(self.MB, 4), (self.REMAT, True)}): 3.0}
        out = KnobSearch(TRAIN, model_info=INFO, n_devices=1,
                         knob_names=(self.MB, self.REMAT),
                         probe_runner=_fake_runner(scores)).tune()
        assert out["best_overrides"] == {self.MB: 4, self.REMAT: True}
        assert out["best_score"] == 3.0 and out["baseline_score"] == 1.0

    def test_gate_violation_disqualifies(self):
        """A faster config that trips parity or census can never win."""
        key = frozenset({("prefill_tile", 16)})
        scores = {frozenset(): 1.0, key: 100.0}
        out = KnobSearch(SERVE, knob_names=("prefill_tile",),
                         probe_runner=_fake_runner(
                             scores, gates={key: {"parity_ok": False}})
                         ).tune()
        assert "prefill_tile" not in out["best_overrides"]
        assert out["gate_failures"] == 1
        assert out["gate_violations_accepted"] == 0

    @pytest.mark.parametrize("from_probe", [False, True],
                             ids=["given", "from_probe"])
    def test_winner_persists_and_reloads(self, tmp_path, monkeypatch,
                                         from_probe):
        """``from_probe``: the search parent is told nothing about the
        device and must not ask a jax backend (it would hold the chip its
        probe children need) — count, byte limit and topology come from
        the default-config probe's ``device`` block."""
        scores = {frozenset({(self.MB, 4)}): 5.0}
        runner = _fake_runner(scores)
        if from_probe:
            import jax

            def no_backend(*a, **k):
                raise AssertionError("search parent touched a jax backend")

            monkeypatch.setattr(profiles, "current_topology", no_backend)
            monkeypatch.setattr(jax, "devices", no_backend)
            mb8 = (INFO.state_bytes(0, 4) + INFO.activation_bytes(8, 128))

            def runner(kind, overrides, steps, inner=runner):
                out, err = inner(kind, overrides, steps)
                out["device"] = {"topology": TOPO, "count": 4,
                                 "bytes_limit": mb8 * 1.01 / 0.9}
                return out, err
        else:
            monkeypatch.setattr(profiles, "current_topology", lambda: TOPO)
        search = KnobSearch(TRAIN, model_info=INFO,
                            n_devices=None if from_probe else 1,
                            knob_names=(self.MB,), probe_runner=runner,
                            profile_dir=str(tmp_path))
        out = search.tune()
        assert out["profile_path"] and os.path.exists(out["profile_path"])
        prof = profiles.load_profile(str(tmp_path), subsystem=TRAIN,
                                     fingerprint=FP, topology=TOPO)
        assert prof["overrides"] == {self.MB: 4}
        assert prof["score"] == 5.0
        if from_probe:
            assert search.n_devices == 4 and out["pruned"] >= 1

    def test_counters_bump_when_telemetry_on(self):
        from deepspeed_tpu import telemetry

        telemetry.configure(enabled=True, hbm_watermarks=False)
        try:
            KnobSearch(
                TRAIN, model_info=INFO, n_devices=1, knob_names=(self.MB,),
                memory_bytes=(INFO.state_bytes(0, 1)
                              + INFO.activation_bytes(8, 128)) * 1.01 / 0.9,
                probe_runner=_fake_runner({})).tune()
            snap = telemetry.snapshot()["metrics"]
            trials = snap["autotune_trials_total"]["series"][0]["value"]
            pruned = snap["autotune_pruned_total"]["series"][0]["value"]
            assert trials >= 2 and pruned >= 1
        finally:
            telemetry.configure(enabled=False)
