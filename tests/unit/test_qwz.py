"""ZeRO++ qwZ: int8 quantized weight all-gather on the stage-3 path
(reference ``partition_parameters.py:1446`` quantized all_gather_coalesced +
``csrc/quantization/swizzled_quantize.cu``).

Verifies the three claims that make qwZ real: (1) the rowwise quantizer
round-trips within int8 blockwise error, (2) the compiled stage-3 program
moves the weight all-gather onto an int8 payload (HLO-level bytes drop ~2x),
(3) training loss stays at parity with the bf16 gather."""

import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import NamedSharding, PartitionSpec as P

import deepspeed_tpu
from deepspeed_tpu.comm.comm import init_distributed
from deepspeed_tpu.comm.topology import reset_topology
from deepspeed_tpu.config.config import Config, ConfigError, MeshConfig
from deepspeed_tpu.models import llama
from deepspeed_tpu.ops.quantizer import dequantize_rows, quantize_rows
from deepspeed_tpu.parallel.qwz import quantized_gather

VOCAB = 256


# ------------------------------------------------------------------ quantizer
def test_quantize_rows_roundtrip():
    x = jax.random.normal(jax.random.PRNGKey(0), (64, 256), jnp.float32)
    q, s = quantize_rows(x, block=128)
    assert q.shape == x.shape and q.dtype == jnp.int8
    assert s.shape == (64, 2)
    y = dequantize_rows(q, s, jnp.float32)
    # int8 symmetric: error bounded by scale/2 = absmax/254 per block
    err = np.abs(np.asarray(y - x))
    bound = np.asarray(jnp.max(jnp.abs(x)) / 254.0 + 1e-6)
    assert err.max() <= bound * 1.01


def test_quantize_rows_padding():
    # last dim not divisible by block: padded internally, shape preserved
    x = jax.random.normal(jax.random.PRNGKey(1), (4, 100), jnp.float32)
    q, s = quantize_rows(x, block=64)
    assert q.shape == (4, 100) and s.shape == (4, 2)
    y = dequantize_rows(q, s, jnp.float32, block=64)
    assert y.shape == (4, 100)
    assert np.abs(np.asarray(y - x)).max() < 0.05


# ------------------------------------------------------------------ HLO bytes
_DTYPE_BYTES = {"f32": 4, "bf16": 2, "f16": 2, "s8": 1, "u8": 1, "s32": 4,
                "pred": 1, "f64": 8, "s64": 8, "u32": 4}


def _all_gather_bytes(hlo: str) -> dict:
    """Sum all-gather result bytes per element type from HLO text."""
    out: dict = {}
    for m in re.finditer(
            r"=\s*(\w+)\[([\d,]*)\](?:\{[^}]*\})?\s+all-gather", hlo):
        dt, dims = m.group(1), m.group(2)
        n = 1
        for d in dims.split(","):
            if d:
                n *= int(d)
        out[dt] = out.get(dt, 0) + n * _DTYPE_BYTES.get(dt, 4)
    return out


def test_gather_rides_int8():
    reset_topology()
    topo = init_distributed(MeshConfig(data=1, fsdp=8))
    mesh = topo.mesh
    w_sh = NamedSharding(mesh, P("fsdp", None))
    rep = NamedSharding(mesh, P())
    x = jax.random.normal(jax.random.PRNGKey(0), (4096, 512), jnp.bfloat16)
    w = jax.device_put(
        jax.random.normal(jax.random.PRNGKey(1), (512, 512), jnp.bfloat16), w_sh)

    # baseline: the stage-3 gather-on-use, made explicit the same way the
    # qwZ path makes its int8 gather explicit
    def dense(w, x):
        return x @ jax.lax.with_sharding_constraint(w, rep)

    def qwz(w, x):
        return x @ quantized_gather(w, mesh, P("fsdp", None), 128)

    hlo_dense = jax.jit(dense, in_shardings=(w_sh, None),
                        out_shardings=rep).lower(w, x).compile().as_text()
    hlo_qwz = jax.jit(qwz, in_shardings=(w_sh, None),
                      out_shardings=rep).lower(w, x).compile().as_text()
    bd = _all_gather_bytes(hlo_dense)
    bq = _all_gather_bytes(hlo_qwz)
    # dense gathers the weight in a float type (CPU upcasts bf16 -> f32 on
    # the wire; TPU keeps bf16) — either way, full float weight bytes
    assert sum(bd.values()) >= 512 * 512 * 2, f"dense should gather the weight: {bd}"
    assert bq.get("s8", 0) == 512 * 512, f"qwz should gather the int8 weight: {bq}"
    # scales ride beside the payload but are tiny (1/block of the elements)
    float_bytes = sum(v for k, v in bq.items() if k != "s8")
    assert float_bytes <= 0.1 * bq["s8"], f"qwz float side-channel too big: {bq}"
    # vs the bf16-equivalent wire: int8 + scales ~= 0.5x + epsilon
    assert sum(bq.values()) < 0.65 * (512 * 512 * 2)


def test_gather_backward_is_straight_through():
    reset_topology()
    topo = init_distributed(MeshConfig(data=1, fsdp=8))
    mesh = topo.mesh
    w = jax.random.normal(jax.random.PRNGKey(1), (256, 128), jnp.float32)

    def loss(w, x):
        return jnp.sum(x @ quantized_gather(w, mesh, P("fsdp", None), 64))

    x = jax.random.normal(jax.random.PRNGKey(2), (4, 256), jnp.float32)
    g = jax.grad(loss)(w, x)
    # STE: d(sum(x@w))/dw = sum of x rows broadcast — exact, unquantized
    expect = jnp.broadcast_to(x.sum(0)[:, None], (256, 128))
    np.testing.assert_allclose(np.asarray(g), np.asarray(expect), rtol=1e-5)


# ------------------------------------------------------------------ config
def test_config_qwz_requires_stage3():
    with pytest.raises(ConfigError, match="stage 3"):
        Config.from_dict({
            "train_micro_batch_size_per_device": 1,
            "zero_optimization": {"stage": 2, "quantized_weights": True},
        })


def test_config_reference_spelling_maps():
    cfg = Config.from_dict({
        "train_micro_batch_size_per_device": 1,
        "zero_optimization": {"stage": 3, "zero_quantized_weights": True},
    })
    assert cfg.zero_optimization.quantized_weights


# ------------------------------------------------------------------ engine
def _engine(qwz: bool, mesh=None):
    reset_topology()
    cfg = {
        "train_micro_batch_size_per_device": 2,
        "gradient_accumulation_steps": 2,
        "steps_per_print": 0,
        "gradient_clipping": 1.0,
        "optimizer": {"type": "adamw", "params": {"lr": 1e-2}},
        "zero_optimization": {"stage": 3, "quantized_weights": qwz,
                              "qwz_block": 64},
        "mesh": mesh or {"data": 2, "fsdp": 4},
        "seed": 5,
    }
    engine, _, _, _ = deepspeed_tpu.initialize(
        model=lambda ctx: llama.build(llama.LlamaConfig.tiny(VOCAB), ctx=ctx),
        config=cfg, seed=11,
    )
    return engine


def _batches(n, seed=0):
    rng = np.random.default_rng(seed)
    return [{"input_ids": rng.integers(0, VOCAB, (16, 16), dtype=np.int32)}
            for _ in range(n)]


class TestQwzEngine:
    def test_loss_parity_vs_bf16_gather(self):
        # one repeated batch: memorization descends through the int8 weight
        # noise floor (varied tiny batches would not at this scale)
        batch = _batches(1)[0]
        ref = _engine(qwz=False)
        ref_losses = [float(ref.train_batch(batch)) for _ in range(8)]
        assert ref.shard_ctx.weight_gather.codec is None  # the same hook
        qw = _engine(qwz=True)
        assert qw.shard_ctx.weight_gather.codec == "int8"
        qw_losses = [float(qw.train_batch(batch)) for _ in range(8)]
        assert all(np.isfinite(qw_losses))
        assert qw_losses[-1] < qw_losses[0]
        # int8 blockwise weight error perturbs the trajectory only slightly
        np.testing.assert_allclose(qw_losses, ref_losses, rtol=0.05)

    @pytest.mark.parametrize("qwz", [False, True], ids=["dense", "int8"])
    def test_composes_with_tensor_axis(self, qwz):
        engine = _engine(qwz=qwz, mesh={"data": 1, "fsdp": 4, "tensor": 2})
        losses = [float(engine.train_batch(b)) for b in _batches(3)]
        assert all(np.isfinite(losses))

    def test_rejected_with_pipeline(self):
        with pytest.raises(ValueError, match="pipeline"):
            _engine(qwz=True, mesh={"data": 1, "fsdp": 2, "pipeline": 4})
