"""Profiler tracing + debug/sanity modes (reference: nvtx instrumentation,
``enable_sanity_checks``, SURVEY §5.1-5.2)."""

import os

import numpy as np
import pytest

import deepspeed_tpu
from deepspeed_tpu.comm.topology import reset_topology
from deepspeed_tpu.models import llama


def _engine(tmp_path, extra):
    reset_topology()
    cfg = {
        "train_micro_batch_size_per_device": 2,
        "gradient_accumulation_steps": 1,
        "steps_per_print": 0,
        "optimizer": {"type": "adamw", "params": {"lr": 1e-3}},
        "zero_optimization": {"stage": 0},
        "mesh": {"data": 8},
        **extra,
    }
    engine, _, _, _ = deepspeed_tpu.initialize(
        model=lambda ctx: llama.build(llama.LlamaConfig.tiny(256), ctx=ctx),
        config=cfg,
    )
    return engine


def _batch(n=16):
    return {"input_ids": np.random.default_rng(0).integers(0, 256, (n, 16),
                                                           dtype=np.int32)}


def test_trace_window_produces_capture(tmp_path):
    trace_dir = str(tmp_path / "trace")
    engine = _engine(tmp_path, {
        # the window opens after the step program's two compiles: a compile
        # inside it is a minute of the profiler's Python events
        "tracing": {"enabled": True, "trace_dir": trace_dir,
                    "start_step": 2, "num_steps": 2},
    })
    for _ in range(5):
        engine.train_batch(_batch())
    engine.step_tracer.close()
    # a profile capture landed on disk (xplane proto under plugins/profile)
    found = [f for root, _, files in os.walk(trace_dir) for f in files]
    assert found, "no trace files written"


def test_capture_survives_raising_step(tmp_path):
    """A step that raises inside the capture window must not wedge the next
    capture: stop_trace() is idempotent and exception-safe, and the stale
    StepTraceAnnotation is exited on the next before_step."""
    trace_dir = str(tmp_path / "trace")
    engine = _engine(tmp_path, {
        "tracing": {"enabled": True, "trace_dir": trace_dir,
                    "start_step": 2, "num_steps": 2},
    })
    for _ in range(2):      # the step program's two compiles, outside
        engine.train_batch(_batch())
    orig = engine._put_gas_batch

    def boom(batch):
        raise RuntimeError("injected step failure")

    engine._put_gas_batch = boom
    with pytest.raises(RuntimeError, match="injected"):
        engine.train_batch(_batch())  # fails inside the open window
    engine._put_gas_batch = orig
    # the window recovers: subsequent steps run and the capture closes
    for _ in range(3):
        engine.train_batch(_batch())
    # double stop: second call is a no-op, not an unmatched-stop crash
    engine.step_tracer.stop_trace()
    engine.step_tracer.stop_trace()
    engine.step_tracer.close()
    found = [f for root, _, files in os.walk(trace_dir) for f in files]
    assert found, "no trace files written after mid-window failure"


def test_sanity_checks_catch_bad_batches(tmp_path):
    engine = _engine(tmp_path, {"debug": {"sanity_checks": True}})
    engine.train_batch(_batch())  # good batch passes
    with pytest.raises(ValueError, match="train_batch_size"):
        engine.train_batch(_batch(n=8))
    with pytest.raises(ValueError, match="integer"):
        engine.train_batch({"input_ids": np.zeros((16, 16), np.float32)})
    with pytest.raises(ValueError, match="leading dim"):
        engine.train_batch({"input_ids": _batch()["input_ids"],
                            "labels": np.zeros((4, 16), np.int32)})


def test_debug_nans_config_flag(tmp_path):
    import jax

    engine = _engine(tmp_path, {"debug": {"nans": True}})
    try:
        assert jax.config.jax_debug_nans
        engine.train_batch(_batch())  # clean step passes under the trap
    finally:
        jax.config.update("jax_debug_nans", False)
