"""Engine end-to-end on the 8-device CPU mesh: loss decreases, ZeRO-stage loss
parity, fp16 loss scaling, GAS equivalence, fwd/bwd/step parity path
(reference test style: ``tests/unit/runtime`` train-and-compare suites)."""

import functools
import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import deepspeed_tpu
from deepspeed_tpu.models import gpt2, llama
from deepspeed_tpu.runtime.dataloader import random_token_loader

VOCAB = 256


def _builder(kind="llama"):
    if kind == "llama":
        return lambda ctx: llama.build(llama.LlamaConfig.tiny(VOCAB), ctx=ctx)
    return lambda ctx: gpt2.build(gpt2.GPT2Config.tiny(VOCAB), ctx=ctx)


def _config(stage=0, **over):
    cfg = {
        "train_micro_batch_size_per_device": 2,
        "gradient_accumulation_steps": over.pop("gas", 1),
        "steps_per_print": 0,
        "optimizer": {"type": "adamw", "params": {"lr": 1e-3}},
        "zero_optimization": {"stage": stage},
        "mesh": over.pop("mesh", {"data": 8}),
        "bf16": {"enabled": over.pop("bf16", False)},
        "seed": 7,
    }
    cfg.update(over)
    return cfg


def _fixed_batches(n, batch, seq=16, seed=0):
    rng = np.random.default_rng(seed)
    return [
        {"input_ids": rng.integers(0, VOCAB, (batch, seq), dtype=np.int32)}
        for _ in range(n)
    ]


def _run(stage, mesh=None, **options):
    """``(engine, losses)`` of one configuration, once a process: an engine is
    two compiles of its step, and the three stages' parity cases share their
    stage-0 baseline (the sharding case, the stage-3 engine)."""
    return _trained(stage, json.dumps(mesh), json.dumps(options, sort_keys=True))


@functools.lru_cache(maxsize=None)
def _trained(stage, mesh, options):
    return _train(stage, mesh=json.loads(mesh), **json.loads(options))


def _train(stage, n_steps=6, gas=1, mesh=None, kind="llama", bf16=False, fp16=None, seed=0):
    cfg = _config(stage=stage, gas=gas, mesh=mesh or {"data": 8}, bf16=bf16)
    if fp16:
        cfg["fp16"] = fp16
        cfg["bf16"] = {"enabled": False}
    engine, _, _, _ = deepspeed_tpu.initialize(model=_builder(kind), config=cfg, seed=11)
    batches = _fixed_batches(n_steps, engine.train_batch_size, seed=seed)
    losses = [float(engine.train_batch(b)) for b in batches]
    return engine, losses


def test_train_loss_decreases():
    engine, losses = _run(stage=0, n_steps=8)
    assert losses[-1] < losses[0], losses
    assert engine.global_steps == 8
    assert engine.global_samples == 8 * engine.train_batch_size


@pytest.mark.parametrize("stage", [1, 2, 3])
def test_zero_stage_loss_parity(stage):
    """All ZeRO stages must produce the same loss trajectory as stage 0
    (reference: zero suites comparing vs unpartitioned baseline)."""
    _, base = _run(stage=0, n_steps=5, mesh={"data": 1, "fsdp": 8})
    _, test = _run(stage=stage, n_steps=5, mesh={"data": 1, "fsdp": 8})
    np.testing.assert_allclose(base, test, rtol=2e-4, atol=2e-5)


def test_zero3_params_actually_sharded():
    engine, _ = _run(stage=3, n_steps=5, mesh={"data": 1, "fsdp": 8})
    wq = engine.params["layers"]["wq"]
    assert wq.addressable_shards[0].data.size == wq.size // 8
    mu = engine.opt_state[0].mu["layers"]["wq"]
    assert mu.addressable_shards[0].data.size == mu.size // 8


def test_gas_matches_big_batch():
    """GAS=4 with micro=2 must match GAS=1 with micro=8 (same global batch)."""
    cfg_a = _config(stage=0, gas=4)
    cfg_b = _config(stage=0, gas=1)
    cfg_b["train_micro_batch_size_per_device"] = 8

    batches = _fixed_batches(4, 64, seed=3)
    engine_a, _, _, _ = deepspeed_tpu.initialize(model=_builder(), config=cfg_a, seed=11)
    losses_a = [float(engine_a.train_batch(b)) for b in batches]
    from deepspeed_tpu.comm.topology import reset_topology

    reset_topology()
    engine_b, _, _, _ = deepspeed_tpu.initialize(model=_builder(), config=cfg_b, seed=11)
    losses_b = [float(engine_b.train_batch(b)) for b in batches]
    np.testing.assert_allclose(losses_a, losses_b, rtol=1e-4)


def test_forward_backward_step_parity_with_train_batch():
    """The fwd/bwd/step protocol must match the fused train_batch path."""
    batches = _fixed_batches(2, 16, seed=5)

    engine_a, _, _, _ = deepspeed_tpu.initialize(
        model=_builder(), config=_config(stage=2, gas=2), seed=11
    )
    for b in batches:
        loss_a = engine_a.train_batch(b)

    from deepspeed_tpu.comm.topology import reset_topology

    reset_topology()
    engine_b, _, _, _ = deepspeed_tpu.initialize(
        model=_builder(), config=_config(stage=2, gas=2), seed=11
    )
    for b in batches:
        half = b["input_ids"].shape[0] // 2
        l1 = engine_b.backward({"input_ids": b["input_ids"][:half]})
        assert not engine_b.is_gradient_accumulation_boundary()
        l2 = engine_b.backward({"input_ids": b["input_ids"][half:]})
        assert engine_b.is_gradient_accumulation_boundary()
        engine_b.step()
        loss_b = (float(l1) + float(l2)) / 2

    leaves_a = jax.tree_util.tree_leaves(engine_a.params)
    leaves_b = jax.tree_util.tree_leaves(engine_b.params)
    for a, b_ in zip(leaves_a, leaves_b):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b_), rtol=2e-4, atol=1e-5)
    assert float(loss_a) == pytest.approx(loss_b, rel=1e-4)


def test_fp16_loss_scaling_and_overflow_skip():
    engine, losses = _run(
        stage=0,
        n_steps=3,
        fp16={"enabled": True, "initial_scale_power": 4, "loss_scale_window": 2},
        kind="gpt2",
    )
    assert engine.loss_scale >= 16.0  # grew after window or stayed
    assert all(np.isfinite(losses))

    # force an overflow: blow up a parameter so grads go inf
    engine.params["wte"] = engine.params["wte"].at[0, 0].set(jnp.float32(3e38))
    before = jax.tree_util.tree_map(np.asarray, engine.params["layers"])
    scale_before = engine.loss_scale
    engine.train_batch(_fixed_batches(1, engine.train_batch_size, seed=9)[0])
    assert engine.skipped_steps >= 1
    assert engine.loss_scale <= scale_before
    after = engine.params["layers"]
    np.testing.assert_array_equal(np.asarray(after["wq"]), before["wq"])  # update skipped


def test_bf16_trains():
    engine, losses = _run(stage=2, n_steps=5, bf16=True, mesh={"data": 2, "fsdp": 4})
    assert losses[-1] < losses[0]
    # master weights stay fp32
    assert engine.params["layers"]["wq"].dtype == jnp.float32


def test_gradient_clipping():
    cfg = _config(stage=0)
    cfg["gradient_clipping"] = 1e-6  # clip everything to ~zero update
    engine, _, _, _ = deepspeed_tpu.initialize(model=_builder(), config=cfg, seed=11)
    before = np.asarray(engine.params["layers"]["wq"]).copy()
    engine.train_batch(_fixed_batches(1, engine.train_batch_size)[0])
    after = np.asarray(engine.params["layers"]["wq"])
    assert np.abs(after - before).max() < 1e-4
    assert engine.get_global_grad_norm() > 0


def test_train_with_data_iter():
    cfg = _config(stage=0, gas=2)
    engine, _, _, _ = deepspeed_tpu.initialize(model=_builder("gpt2"), config=cfg, seed=11)
    loader = random_token_loader(engine.config.train_micro_batch_size_per_device * 8,
                                 16, VOCAB, seed=1)
    loss = engine.train_batch(data_iter=loader)
    assert np.isfinite(float(loss))
    assert engine.micro_steps == 2


def test_tp_plus_dp_trains():
    engine, losses = _run(stage=0, n_steps=4, mesh={"data": 2, "tensor": 4})
    assert losses[-1] < losses[0]
    wq = engine.params["layers"]["wq"]
    assert "tensor" in str(wq.sharding.spec)
