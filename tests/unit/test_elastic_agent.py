"""Elastic agent: worker supervision, scale-down restart, preemption
checkpointing (reference ``elasticity/elastic_agent.py`` + checkpoint-based
recovery, SURVEY §5.3)."""

import os
import signal
import sys

import numpy as np
import pytest

from deepspeed_tpu.elasticity.agent import ElasticAgent, PreemptionHandler, WorkerSpec


def _worker_cmd(tmp_path, rank, world, die_rank=None):
    """A worker that writes its (rank, world), optionally dies once."""
    marker = tmp_path / f"died_once_{rank}"
    code = f"""
import os, sys, time
open({str(tmp_path)!r} + f"/seen_{{os.environ['RANK']}}_{{os.environ['WORLD_SIZE']}}", "w").close()
if os.environ['RANK'] == {die_rank!r} and not os.path.exists({str(marker)!r}):
    open({str(marker)!r}, "w").close()
    # die only once rank 0 of this wave is up: the agent tears the wave down
    # within a poll of the death, and a loaded machine starts python slowly
    for _ in range(200):
        if os.path.exists({str(tmp_path)!r} + f"/seen_0_{{os.environ['WORLD_SIZE']}}"):
            break
        time.sleep(0.05)
    sys.exit(17)
time.sleep(0.2)
"""
    return [sys.executable, "-c", code]


class TestElasticAgent:
    def test_scale_down_restart(self, tmp_path):
        """A dying worker triggers relaunch at the next admissible world size
        with the remaining capacity."""

        def make(rank, world):
            env = dict(os.environ, RANK=str(rank), WORLD_SIZE=str(world))
            return WorkerSpec(cmd=_worker_cmd(tmp_path, rank, world, die_rank="1"),
                              env=env)

        agent = ElasticAgent(
            target_batch_size=32,
            micro_batch_candidates=[1, 2, 4],
            make_worker=make,
            max_world_size=4,
            poll_interval=0.1,
        )
        assert agent.admissible_world_sizes() == [1, 2, 4]
        assert agent.run() == 0
        # first wave at world=4 (rank 1 died once), second wave at world<=3 -> 2
        assert (tmp_path / "seen_0_4").exists()
        assert (tmp_path / "seen_0_2").exists()
        assert not (tmp_path / "seen_0_3").exists()  # 3 inadmissible for batch 32

    def test_no_admissible_size_raises(self):
        agent = ElasticAgent(
            target_batch_size=7,
            micro_batch_candidates=[2],
            make_worker=lambda r, w: WorkerSpec(cmd=["true"]),
            max_world_size=4,
        )
        with pytest.raises(ValueError, match="no admissible"):
            agent.admissible_world_sizes()

    def test_sigkilled_preemption_restarts(self, tmp_path):
        """A SIGKILL'd worker (negative returncode — a preempted host) must
        take the same restart branch as a nonzero exit."""
        marker = tmp_path / "killed_once"

        def make(rank, world):
            code = f"""
import os, signal, time
open({str(tmp_path)!r} + f"/ran_{{os.environ['RANK']}}_{{os.environ['WORLD_SIZE']}}", "w").close()
if not os.path.exists({str(marker)!r}):
    open({str(marker)!r}, "w").close()
    os.kill(os.getpid(), signal.SIGKILL)
time.sleep(0.2)
"""
            env = dict(os.environ, RANK=str(rank), WORLD_SIZE=str(world))
            return WorkerSpec(cmd=[sys.executable, "-c", code], env=env)

        agent = ElasticAgent(
            target_batch_size=8, micro_batch_candidates=[2, 4, 8],
            make_worker=make, max_world_size=2, min_world_size=1,
            poll_interval=0.1)
        assert agent.run() == 0
        assert agent.restarts == 1
        assert (tmp_path / "ran_0_2").exists()
        assert (tmp_path / "ran_0_1").exists()  # relaunched smaller

    def test_heartbeat_stale_worker_killed(self, tmp_path):
        """A worker that stays alive but never beats past the grace window
        is wedged: the agent SIGKILLs it and the relaunch completes."""
        hb_dir = tmp_path / "state"
        hb_dir.mkdir()
        marker = tmp_path / "wedged_once"

        def make(rank, world):
            code = f"""
import json, os, time
hb = os.path.join({str(hb_dir)!r}, "heartbeat_0.json")
if not os.path.exists({str(marker)!r}):
    open({str(marker)!r}, "w").close()
    time.sleep(600)  # wedged-but-alive: no beacon ever written
with open(hb, "w") as f:
    json.dump({{"step": 1}}, f)
time.sleep(0.2)
"""
            return WorkerSpec(cmd=[sys.executable, "-c", code],
                              env=dict(os.environ))

        agent = ElasticAgent(
            target_batch_size=4, micro_batch_candidates=[4],
            make_worker=make, max_world_size=1, min_world_size=1,
            poll_interval=0.1, heartbeat_dir=str(hb_dir),
            heartbeat_timeout=0.5, heartbeat_grace=1.5)
        assert agent.run() == 0
        assert agent.heartbeat_kills == 1
        assert agent.restarts == 1

    def test_sweep_stale_state(self, tmp_path):
        """Launch sweeps per-incarnation heartbeat beacons and torn
        quarantine files; a valid quarantine list (healing memory) stays."""
        hb_dir = tmp_path / "state"
        hb_dir.mkdir()
        (hb_dir / "heartbeat_0.json").write_text('{"step": 3}')
        (hb_dir / "heartbeat_1.json").write_text("torn{")
        (hb_dir / "quarantine.json").write_text('["abc123"]')

        agent = ElasticAgent(
            target_batch_size=4, micro_batch_candidates=[4],
            make_worker=lambda r, w: WorkerSpec(
                cmd=[sys.executable, "-c", "pass"], env=dict(os.environ)),
            max_world_size=1, poll_interval=0.1,
            heartbeat_dir=str(hb_dir), heartbeat_timeout=5.0)
        assert agent.run() == 0
        assert not (hb_dir / "heartbeat_0.json").exists()
        assert not (hb_dir / "heartbeat_1.json").exists()
        assert (hb_dir / "quarantine.json").read_text() == '["abc123"]'

        # torn quarantine is removed at the next launch
        (hb_dir / "quarantine.json").write_text('["abc123"')  # torn write
        agent2 = ElasticAgent(
            target_batch_size=4, micro_batch_candidates=[4],
            make_worker=lambda r, w: WorkerSpec(
                cmd=[sys.executable, "-c", "pass"], env=dict(os.environ)),
            max_world_size=1, poll_interval=0.1,
            heartbeat_dir=str(hb_dir), heartbeat_timeout=5.0)
        assert agent2.run() == 0
        assert not (hb_dir / "quarantine.json").exists()


class TestPreemptionHandler:
    def test_sigterm_checkpoints_and_stops(self, tmp_path):
        import deepspeed_tpu
        from deepspeed_tpu.comm.topology import reset_topology
        from deepspeed_tpu.models import llama

        reset_topology()
        engine, _, _, _ = deepspeed_tpu.initialize(
            model=lambda ctx: llama.build(llama.LlamaConfig.tiny(256), ctx=ctx),
            config={
                "train_micro_batch_size_per_device": 2,
                "gradient_accumulation_steps": 1,
                "steps_per_print": 0,
                "optimizer": {"type": "adamw", "params": {"lr": 1e-3}},
                "zero_optimization": {"stage": 1},
                "mesh": {"data": 8},
            },
        )
        handler = PreemptionHandler(engine, str(tmp_path))
        try:
            rng = np.random.default_rng(0)
            steps = 0
            for _ in range(5):
                if handler.should_stop:
                    break
                engine.train_batch(
                    {"input_ids": rng.integers(0, 256, (16, 16), dtype=np.int32)})
                steps += 1
                if steps == 2:  # the preemption notice arrives mid-run
                    os.kill(os.getpid(), signal.SIGTERM)
            path = handler.checkpoint_if_needed()
            assert handler.should_stop and steps == 2
            assert path is not None and (tmp_path / "preempt").is_dir()
            assert handler.checkpoint_if_needed() is None  # at most once
        finally:
            handler.restore()

    def test_drain_callbacks_engine_free(self):
        """Serving-style registration: no training engine, immediate hooks
        fire inside the signal handler, deferred hooks via drain(), each at
        most once."""
        handler = PreemptionHandler(signals=(signal.SIGTERM,))
        fired = []
        handler.register("stop-admission", lambda: fired.append("now") or "ok",
                         immediate=True)
        handler.register("flush", lambda: fired.append("later") or 7)
        with pytest.raises(ValueError, match="already registered"):
            handler.register("flush", lambda: None)
        try:
            assert handler.drain() == {}  # no signal yet -> no-op
            os.kill(os.getpid(), signal.SIGTERM)
            assert handler.should_stop and handler.stop_event.is_set()
            assert fired == ["now"]  # immediate hook ran in the handler
            results = handler.drain()
            assert fired == ["now", "later"]
            assert results == {"stop-admission": "ok", "flush": 7}
            assert handler.drain() == results  # at most once per hook
            assert handler.checkpoint_if_needed() is None  # engine-free
        finally:
            handler.restore()

    def test_engine_requires_save_dir(self):
        with pytest.raises(ValueError, match="save_dir"):
            PreemptionHandler(engine=object())
