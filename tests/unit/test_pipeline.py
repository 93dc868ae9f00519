"""Pipeline parallelism: exactness of the collective microbatch pipeline vs the
plain layer scan, and end-to-end PP training parity
(reference: ``tests/unit/runtime/pipe/``)."""

import functools
import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import deepspeed_tpu
from deepspeed_tpu.comm.comm import init_distributed
from deepspeed_tpu.comm.topology import reset_topology
from deepspeed_tpu.config.config import MeshConfig
from deepspeed_tpu.models import llama
from deepspeed_tpu.parallel.pipeline import pipeline_apply

VOCAB = 256


def test_pipeline_apply_matches_scan():
    topo = init_distributed(MeshConfig(data=2, pipeline=4))
    # toy layer: x @ w + b, stacked [L=8, D, D]
    L, B, D = 8, 8, 16
    ks = jax.random.split(jax.random.PRNGKey(0), 3)
    params = {
        "w": jax.random.normal(ks[0], (L, D, D)) * 0.1,
        "b": jax.random.normal(ks[1], (L, D)) * 0.1,
    }
    x = jax.random.normal(ks[2], (B, D))

    def layer(c, lp):
        return jnp.tanh(c @ lp["w"] + lp["b"])

    ref = jax.lax.scan(lambda c, lp: (layer(c, lp), None), x, params)[0]
    out = jax.jit(
        lambda p, x: pipeline_apply(layer, p, x, topo.mesh, num_microbatches=4)
    )(params, x)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), rtol=1e-5, atol=1e-5)


def test_pipeline_grads_match_scan():
    topo = init_distributed(MeshConfig(data=2, pipeline=4))
    L, B, D = 4, 4, 8
    ks = jax.random.split(jax.random.PRNGKey(1), 3)
    params = {"w": jax.random.normal(ks[0], (L, D, D)) * 0.1}
    x = jax.random.normal(ks[1], (B, D))

    def layer(c, lp):
        return jnp.tanh(c @ lp["w"])

    def loss_pipe(p):
        return jnp.sum(pipeline_apply(layer, p, x, topo.mesh, num_microbatches=2) ** 2)

    def loss_ref(p):
        return jnp.sum(jax.lax.scan(lambda c, lp: (layer(c, lp), None), x, p)[0] ** 2)

    g_pipe = jax.jit(jax.grad(loss_pipe))(params)
    g_ref = jax.grad(loss_ref)(params)
    np.testing.assert_allclose(np.asarray(g_pipe["w"]), np.asarray(g_ref["w"]),
                               rtol=2e-5, atol=2e-5)


def _cfg(mesh, n_micro=0, gas=1, schedule="gpipe", stage=0, batch=64):
    cfg = {
        "train_batch_size": batch,
        "gradient_accumulation_steps": gas,
        "steps_per_print": 0,
        "optimizer": {"type": "adamw", "params": {"lr": 1e-3}},
        "zero_optimization": {"stage": stage},
        "pipeline": {"num_microbatches": n_micro, "schedule": schedule},
        "mesh": mesh,
        "seed": 7,
    }
    if schedule == "1f1b":
        # fp32 keeps the many-tick schedule fast enough on the bf16-emulating
        # CPU test mesh (the 40s collective watchdog is real here)
        cfg["bf16"] = {"enabled": False}
    return cfg


def _run(mesh, **options):
    """``(engine, its three losses)`` of one configuration, once a process: an
    engine is its compiles, and two cases look at the same one."""
    return _trained(json.dumps(mesh), json.dumps(options, sort_keys=True))


@functools.lru_cache(maxsize=None)
def _trained(mesh, options):
    return _train(json.loads(mesh), **json.loads(options))


def _train(mesh, n_micro=0, n=3, gas=1, schedule="gpipe", stage=0, batch=64,
           schedule_base_fp32=False):
    reset_topology()
    cfg = _cfg(mesh, n_micro, gas=gas, schedule=schedule, stage=stage, batch=batch)
    if schedule_base_fp32:
        cfg["bf16"] = {"enabled": False}
    engine, _, _, _ = deepspeed_tpu.initialize(
        model=lambda ctx: llama.build(llama.LlamaConfig.tiny(VOCAB), ctx=ctx),
        config=cfg,
        seed=11,
    )
    rng = np.random.default_rng(3)
    losses = []
    for _ in range(n):
        b = {"input_ids": rng.integers(0, VOCAB, (engine.train_batch_size, 16), dtype=np.int32)}
        losses.append(float(engine.train_batch(b)))
    return engine, losses


def test_pp_training_loss_parity():
    """PP=2 (tiny model has 2 layers) must match the DP-only trajectory."""
    _, base = _run({"data": 8})
    _, pp = _run({"data": 4, "pipeline": 2}, n_micro=2)
    np.testing.assert_allclose(base, pp, rtol=3e-4, atol=3e-5)


def test_pp_1f1b_training_loss_parity():
    """1F1B engine schedule (GAS microbatches = pipeline microbatches) must
    match the same-precision DP-only trajectory with the same GAS."""
    _, base = _run({"data": 8}, gas=4, schedule_base_fp32=True, batch=32)
    _, pp = _run({"data": 4, "pipeline": 2}, gas=4, schedule="1f1b", batch=32)
    np.testing.assert_allclose(base, pp, rtol=3e-4, atol=3e-5)


def test_pp_1f1b_composes_with_fsdp():
    """pp=2 x fsdp=2 under ZeRO-2 with the 1F1B schedule: stacked layer
    weights carry BOTH the pipeline and fsdp axes in the grad/opt layout and
    the trajectory matches DP."""
    _, base = _run({"data": 8}, gas=4, stage=2, schedule_base_fp32=True, batch=32)
    engine, pp = _run({"data": 2, "pipeline": 2, "fsdp": 2}, gas=4,
                      schedule="1f1b", stage=2, batch=32)
    np.testing.assert_allclose(base, pp, rtol=3e-4, atol=3e-5)
    spec = str(engine.plan.shard_specs["layers"]["wq"])
    assert "pipeline" in spec and "fsdp" in spec


def test_pp_layers_sharded_over_pipeline_axis():
    engine, _ = _run({"data": 4, "pipeline": 2}, n_micro=2)
    wq = engine.params["layers"]["wq"]
    assert "pipeline" in str(wq.sharding.spec)
    # 2 layers over 2 stages: each device holds one layer slice
    assert wq.addressable_shards[0].data.shape[0] == 1
