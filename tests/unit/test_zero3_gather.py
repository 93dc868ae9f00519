"""ZeRO stage 3 states its weight gathers (``parallel/qwz.WeightGather``,
``ShardCtx.layer_weights`` / ``whole_weight``): the hook changes where a
weight is gathered and nothing of the mathematics, gathers only the fsdp axis,
hands the gradient back on the sharded spec, and is installed by stage 3 over
fsdp > 1 alone. The chip's HLO is ``test_compile_tpu.py``'s."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import NamedSharding, PartitionSpec as P

import deepspeed_tpu
from deepspeed_tpu.comm.comm import init_distributed
from deepspeed_tpu.comm.topology import reset_topology
from deepspeed_tpu.config.config import MeshConfig
from deepspeed_tpu.models import gpt2, llama
from deepspeed_tpu.parallel.qwz import WeightGather, gather_weight

VOCAB = 256


def _four_chips(mesh: dict):
    """The cell's mesh: four devices of the eight the tests have."""
    reset_topology()
    return init_distributed(MeshConfig(**mesh), devices=jax.devices()[:4]).mesh


def _engine(family="gpt2", stage=3, mesh=None, **zero):
    mesh = mesh or {"data": 1, "fsdp": 4}
    _four_chips(mesh)   # initialize() keeps a live topology of the same shape
    build = {
        "gpt2": lambda ctx: gpt2.build(gpt2.GPT2Config.tiny(VOCAB), ctx=ctx),
        "llama": lambda ctx: llama.build(llama.LlamaConfig.tiny(VOCAB), ctx=ctx),
    }[family]
    cfg = {
        "train_micro_batch_size_per_device": 2,
        "gradient_accumulation_steps": 1,
        "steps_per_print": 0,
        "gradient_clipping": 1.0,
        "bf16": {"enabled": True},
        "optimizer": {"type": "adamw", "params": {"lr": 1e-2}},
        "zero_optimization": {"stage": stage, **zero},
        "activation_checkpointing": {"enabled": True, "policy": "full"},
        "mesh": mesh,
        "seed": 5,
    }
    return deepspeed_tpu.initialize(model=build, config=cfg, seed=11)[0]


def _batches(engine, n, seq=16):
    rng = np.random.default_rng(0)
    return [{"input_ids": rng.integers(
        0, VOCAB, (engine.train_batch_size, seq), dtype=np.int32)}
        for _ in range(n)]


def _train(engine, n=3):
    losses = [float(engine.train_batch(b)) for b in _batches(engine, n)]
    return losses, jax.tree_util.tree_map(np.asarray, engine.params)


# ------------------------------------------------------------------ the hook
@pytest.mark.parametrize("grad", ["scatter", "all_reduce"])
def test_gather_drops_fsdp_and_keeps_the_tensor_axis(grad):
    mesh = _four_chips({"data": 1, "fsdp": 2, "tensor": 2})
    spec = P("fsdp", "tensor")
    w = jax.device_put(jnp.ones((64, 32), jnp.bfloat16),
                       NamedSharding(mesh, spec))
    out = jax.jit(lambda w: gather_weight(w, mesh, spec))(w)
    assert out.sharding.spec == P(None, "tensor")
    # and the gradient comes back summed and on the sharded spec, by either
    # of the two ways to get it there
    x = jax.device_put(jnp.ones((8, 64), jnp.bfloat16),
                       NamedSharding(mesh, P("fsdp", None)))
    g = jax.jit(jax.grad(
        lambda w: (x @ gather_weight(w, mesh, spec, grad=grad)).astype(
            jnp.float32).sum()))(w)
    assert g.sharding.spec == spec
    np.testing.assert_array_equal(np.asarray(g, np.float32), 8.0)


def test_hook_passes_unsharded_and_foreign_leaves_through():
    mesh = _four_chips({"data": 1, "fsdp": 4})
    specs = {"wte": P(None, "fsdp"), "lnf": P(),
             "layers": {"w": P(None, "fsdp", None), "b": P(None, None),
                        "t": P(None, None, "tensor")}}
    hook = WeightGather(mesh, specs)
    lp = {"w": jnp.ones((8, 4)), "b": jnp.ones((4,)), "t": jnp.ones((4, 4))}
    seen = []

    def layer(lp):
        out = hook.layer(lp)
        seen.extend(k for k in lp if out[k] is not lp[k])
        return out

    jax.jit(layer)(lp)
    assert seen == ["w"]        # `b` replicated, `t` tensor-parallel only
    # something else than a slice of the plan's `layers` subtree: untouched
    other = {"w": jnp.ones((8, 4))}
    assert hook.layer(other) is other
    assert hook.leaf(lp["b"], "lnf") is lp["b"]
    # the comms plan's list: the stacked leaf whole, the table once
    abstract = {"wte": jax.ShapeDtypeStruct((16, 8), jnp.float32),
                "lnf": jax.ShapeDtypeStruct((8,), jnp.float32),
                "layers": {"w": jax.ShapeDtypeStruct((3, 8, 4), jnp.float32),
                           "b": jax.ShapeDtypeStruct((3, 4), jnp.float32),
                           "t": jax.ShapeDtypeStruct((3, 4, 4), jnp.float32)}}
    assert sorted(row[1:] for row in hook.gathered(abstract)) == [
        (96, None, True), (128, None, False)]


# ---------------------------------------------------------------- the engine
@pytest.mark.parametrize("family", ["gpt2", "llama"])
def test_three_steps_equal_the_same_steps_with_the_hook_removed(family):
    with_hook = _engine(family)
    assert with_hook.shard_ctx.weight_gather is not None
    losses, params = _train(with_hook)
    without = _engine(family)
    without.shard_ctx.weight_gather = None  # before the step program is built
    ref_losses, ref_params = _train(without)
    # bf16 compute: a whole product against the partitioner's partial sums
    np.testing.assert_allclose(losses, ref_losses, rtol=2e-3)
    drift = max(float(np.abs(a - b).max()) for a, b in zip(
        jax.tree_util.tree_leaves(params), jax.tree_util.tree_leaves(ref_params)))
    # three AdamW steps at lr 1e-2 move a weight by up to 3e-2: a gradient
    # whose sign flipped under bf16 reordering costs 2e-2 once, no more
    assert drift < 2.5e-2, drift
    moved = max(float(np.abs(a - np.asarray(b)).max()) for a, b in zip(
        jax.tree_util.tree_leaves(params),
        jax.tree_util.tree_leaves(jax.jit(with_hook.model_spec.init_fn)(
            jax.random.PRNGKey(11)))))
    assert moved > drift


def test_gradients_land_on_the_plans_grad_specs():
    engine = _engine("gpt2", mesh={"data": 1, "fsdp": 2, "tensor": 2})
    batch = jax.tree_util.tree_map(jnp.asarray, _batches(engine, 1)[0])
    _, grads = jax.jit(lambda p, mb: engine._microbatch_grads(
        p, mb, jax.random.PRNGKey(0), jnp.float32(1.0)))(engine.params, batch)
    specs = jax.tree_util.tree_leaves(
        engine.plan.grad_specs, is_leaf=lambda x: isinstance(x, P))
    sharded = 0
    for g, spec in zip(jax.tree_util.tree_leaves(grads), specs):
        assert g.dtype == jnp.float32
        want = NamedSharding(engine.topo.mesh, spec)
        assert g.sharding.is_equivalent_to(want, g.ndim), (g.sharding, spec)
        sharded += "fsdp" in jax.tree_util.tree_leaves(tuple(spec))
    assert sharded > 4
    # the tensor-parallel dimension of a projection is still a shard
    assert engine.plan.param_specs["layers"]["wq"] == P(None, "fsdp", "tensor")


@pytest.mark.parametrize("stage,mesh", [(2, {"data": 1, "fsdp": 4}),
                                        (3, {"data": 4, "fsdp": 1}),
                                        (0, {"data": 2, "fsdp": 2})])
def test_only_stage_three_over_fsdp_installs_the_hook(stage, mesh):
    engine = _engine(stage=stage, mesh=mesh)
    assert engine.shard_ctx.weight_gather is None
    assert np.isfinite(float(engine.train_batch(_batches(engine, 1)[0])))


def test_param_offload_streams_a_slice_and_the_gather_follows_it():
    """``offload_param`` owns where a layer's slice LIVES (host, streamed in
    and compute-cast by ``ctx.param_stream``); the gather then takes the
    streamed shard as it takes any other."""
    engine = _engine(
        "llama", offload_param={"device": "cpu"},
        offload_optimizer={"device": "cpu"})
    ctx = engine.shard_ctx
    assert ctx.param_stream is not None and ctx.weight_gather is not None
    order = []
    stream, gather = ctx.param_stream, ctx.weight_gather.layer
    ctx.param_stream = lambda lp, dt: order.append("stream") or stream(lp, dt)
    ctx.weight_gather.layer = lambda lp: order.append("gather") or gather(lp)
    losses = [float(engine.train_batch(b)) for b in _batches(engine, 2)]
    assert np.isfinite(losses).all()
    assert order[:2] == ["stream", "gather"]


def test_pipeline_stage_bodies_are_left_to_the_partitioner():
    """Inside a pipeline's manual region the hints are suspended and the
    hook with them (``ShardCtx._hooks_live``)."""
    from deepspeed_tpu.models.api import ShardCtx

    ctx = ShardCtx()
    calls = []
    ctx.weight_gather = type("H", (), {
        "layer": lambda self, lp: calls.append("layer") or lp,
        "leaf": lambda self, w, *p: calls.append("leaf") or w})()
    ctx._suspend_constraints = True
    lp = {"w": jnp.ones((2, 2))}
    assert ctx.layer_weights(lp, jnp.float32)["w"] is lp["w"]
    assert ctx.whole_weight(lp["w"], "w") is lp["w"]
    ctx._suspend_constraints = False
    ctx.layer_weights(lp, jnp.float32), ctx.whole_weight(lp["w"], "w")
    assert calls == ["layer", "leaf"]


# ------------------------------------------------------------ the comms plan
@pytest.mark.parametrize("qwz", [False, True], ids=["dense", "int8"])
def test_comms_plan_books_the_gather_as_it_travels(qwz):
    from deepspeed_tpu.telemetry import TELEMETRY
    from deepspeed_tpu.utils.comms_logging import COMMS_LOGGER

    _four_chips({"data": 1, "fsdp": 4})
    engine = deepspeed_tpu.initialize(
        model=lambda ctx: gpt2.build(gpt2.GPT2Config.tiny(VOCAB), ctx=ctx),
        config={
            "train_micro_batch_size_per_device": 2,
            "gradient_accumulation_steps": 2,
            "steps_per_print": 0,
            "bf16": {"enabled": True},
            "optimizer": {"type": "adamw", "params": {"lr": 1e-2}},
            "zero_optimization": {"stage": 3, "quantized_weights": qwz,
                                  "qwz_block": 64},
            "activation_checkpointing": {"enabled": True, "policy": "full"},
            "mesh": {"data": 1, "fsdp": 4},
            "comms_logger": {"enabled": True},
            "telemetry": {"enabled": True},
        }, seed=11)[0]
    engine.train_batch(_batches(engine, 1)[0])
    specs = jax.tree_util.tree_leaves(
        engine.plan.param_specs, is_leaf=lambda x: isinstance(x, P))
    leaves = jax.tree_util.tree_leaves(engine.params)
    sharded = [l for l, s in zip(leaves, specs) if "fsdp" in tuple(s)]
    scanned = jax.tree_util.tree_leaves(engine.params["layers"])
    assert sharded and len(scanned) < len(sharded)
    elems = sum(l.size for l in sharded)
    # min_size: no matrix of the tiny model reaches the int8 wire's 65,536
    big = sum(l.size for l in sharded if l.ndim == 3
              and l.size // l.shape[0] >= 65536)
    wire = 2 * (elems - big) + (1 + 4 / 64) * big if qwz else 2 * elems
    passes, gas = 2, 2      # forward + rematerialized forward; microbatches
    ag = COMMS_LOGGER.traced["all_gather"]
    assert ag.count == 1 and ag.total_bytes == int(wire * passes * gas)
    # gradients in bf16: a layer's all-reduced and sliced, the table's and
    # the positions' reduce-scattered
    in_scan = sum(l.size for l in scanned)
    assert COMMS_LOGGER.traced["all_reduce"].total_bytes == 2 * in_scan * gas
    assert COMMS_LOGGER.traced["reduce_scatter"].total_bytes == (
        2 * (elems - in_scan) * gas)
    snap = TELEMETRY.registry.snapshot()
    codec = "int8" if qwz else "bfloat16"
    (n,) = snap["zero3_gathered_leaves"]["series"]
    (b,) = snap["zero3_gather_bytes_per_step"]["series"]
    assert n["value"] == len(sharded) and n["labels"] == {"codec": codec}
    assert b["value"] == ag.total_bytes and b["labels"] == {"codec": codec}
