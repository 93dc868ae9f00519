"""Pallas flash attention vs the XLA reference (interpret mode on CPU;
the same kernel compiles for real on TPU). Reference test style:
``tests/unit/ops`` kernel-vs-eager numerics."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deepspeed_tpu.ops.attention import xla_attention
from deepspeed_tpu.ops.pallas.flash_attention import flash_attention


def _qkv(b=2, sq=128, skv=128, hq=4, hkv=4, d=32, seed=0, dtype=jnp.float32):
    ks = jax.random.split(jax.random.PRNGKey(seed), 3)
    q = jax.random.normal(ks[0], (b, sq, hq, d), dtype)
    k = jax.random.normal(ks[1], (b, skv, hkv, d), dtype)
    v = jax.random.normal(ks[2], (b, skv, hkv, d), dtype)
    return q, k, v


@pytest.mark.parametrize("causal", [True, False])
def test_matches_xla(causal):
    q, k, v = _qkv()
    ref = xla_attention(q, k, v, causal=causal)
    out = flash_attention(q, k, v, causal, None, 64, 64)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), rtol=2e-5, atol=2e-5)


def test_gqa_head_mapping():
    q, k, v = _qkv(hq=8, hkv=2)
    ref = xla_attention(q, k, v, causal=True)
    out = flash_attention(q, k, v, True, None, 64, 64)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), rtol=2e-5, atol=2e-5)


def test_multiple_kv_blocks_online_softmax():
    q, k, v = _qkv(sq=64, skv=256)
    ref = xla_attention(q, k, v, causal=False)
    out = flash_attention(q, k, v, False, None, 64, 64)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), rtol=2e-5, atol=2e-5)


def test_grads_match_xla():
    q, k, v = _qkv(sq=64, skv=64, hq=4, hkv=2)

    def loss_flash(q, k, v):
        return jnp.sum(flash_attention(q, k, v, True, None, 32, 32) ** 2)

    def loss_ref(q, k, v):
        return jnp.sum(xla_attention(q, k, v, causal=True) ** 2)

    g_f = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
    g_r = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(g_f, g_r):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=5e-5, atol=5e-5)


def test_unsupported_shape_raises():
    q, k, v = _qkv(hq=3, hkv=2)  # 3 % 2 != 0
    with pytest.raises(NotImplementedError):
        flash_attention(q, k, v, True, None, 64, 64)


def test_bf16_inputs():
    q, k, v = _qkv(dtype=jnp.bfloat16)
    ref = xla_attention(q, k, v, causal=True)
    out = flash_attention(q, k, v, True, None, 64, 64)
    np.testing.assert_allclose(np.asarray(out, np.float32), np.asarray(ref, np.float32),
                               rtol=2e-2, atol=2e-2)


def test_kernel_runs_manual_on_a_mesh():
    """GSPMD cannot partition a Mosaic kernel ("wrap the call in a
    shard_map"): on a mesh of several devices ``ShardCtx.attention`` runs the
    kernel manual over the mesh, batch and heads split by the activation
    rules — forward and backward equal to the XLA path GSPMD partitions."""
    from deepspeed_tpu.comm.comm import init_distributed
    from deepspeed_tpu.config.config import MeshConfig
    from deepspeed_tpu.models.api import ShardCtx

    mesh = init_distributed(MeshConfig(data=2, fsdp=2, tensor=2)).mesh
    ctx = ShardCtx(mesh=mesh)
    q, k, v = _qkv(b=4, sq=64, skv=64, hq=4, hkv=2)

    def loss(impl, q, k, v):
        return jnp.sum(ctx.attention(q, k, v, causal=True, impl=impl) ** 2)

    for impl in ("pallas", "xla"):
        val, grads = jax.jit(jax.value_and_grad(
            lambda *a, i=impl: loss(i, *a), argnums=(0, 1, 2)))(q, k, v)
        if impl == "pallas":
            got = (val, *grads)
            text = jax.jit(lambda *a: loss("pallas", *a)).lower(q, k, v).as_text()
            assert "shard_map" in text or "manual" in text
        else:
            for a, b in zip(got, (val, *grads)):
                np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                           rtol=5e-5, atol=5e-5)


# --------------------------------------------------------- the sub-block walk
# (sq, skv, hq, hkv, block_q, block_k, sub, causal, dtype). One block a
# sequence makes the walk static (unrolled); a grid of several blocks makes
# its bounds program ids.
WALKS = {
    "one_sub_block": (16, 16, 2, 2, 64, 64, 16, True, jnp.float32),
    "two_sub_blocks": (32, 32, 2, 2, 64, 64, 16, True, jnp.float32),
    "three_sub_blocks": (48, 48, 2, 2, 64, 64, 16, True, jnp.float32),
    # the first q sub-block meets exactly one K/V sub-block, the last all four
    "four_sub_blocks": (64, 64, 2, 2, 64, 64, 16, True, jnp.float32),
    "grid_2x2": (64, 64, 2, 2, 32, 32, 16, True, jnp.float32),
    "grid_2x4_uneven_blocks": (64, 64, 2, 2, 32, 16, 8, True, jnp.float32),
    "not_causal": (64, 64, 2, 2, 64, 64, 16, False, jnp.float32),
    "not_causal_grid_2x2": (64, 64, 2, 2, 32, 32, 16, False, jnp.float32),
    "not_causal_sq_ne_skv": (32, 64, 2, 2, 64, 64, 16, False, jnp.float32),
    "gqa_4_to_1": (64, 64, 4, 1, 64, 64, 16, True, jnp.float32),
    "gqa_grid_2x2": (64, 64, 8, 2, 32, 32, 16, True, jnp.float32),
    "bf16": (64, 64, 2, 2, 64, 64, 16, True, jnp.bfloat16),
    "bf16_grid_2x2": (64, 64, 2, 2, 32, 32, 16, True, jnp.bfloat16),
}


@pytest.mark.parametrize("case", sorted(WALKS))
def test_walk_matches_xla_forward_and_gradients(case):
    sq, skv, hq, hkv, bq, bk, sub, causal, dtype = WALKS[case]
    q, k, v = _qkv(b=1, sq=sq, skv=skv, hq=hq, hkv=hkv, d=32, dtype=dtype)
    w = jax.random.normal(jax.random.PRNGKey(7), (1, sq, hq, 32), jnp.float32)

    def loss(fn, q, k, v):
        out = fn(q, k, v)
        return jnp.sum(out.astype(jnp.float32) * w), out

    def flash(q, k, v):
        return flash_attention(q, k, v, causal, None, bq, bk, sub)

    def ref(q, k, v):
        return xla_attention(q, k, v, causal=causal)

    (_, out), grads = jax.value_and_grad(
        lambda *a: loss(flash, *a), argnums=(0, 1, 2), has_aux=True)(q, k, v)
    (_, want), want_grads = jax.value_and_grad(
        lambda *a: loss(ref, *a), argnums=(0, 1, 2), has_aux=True)(q, k, v)
    tol, gtol = (2e-5, 5e-5) if dtype == jnp.float32 else (2e-2, 2e-2)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(want, np.float32), rtol=tol, atol=tol)
    for a, b in zip(grads, want_grads):
        np.testing.assert_allclose(np.asarray(a, np.float32),
                                   np.asarray(b, np.float32),
                                   rtol=gtol, atol=gtol)


@pytest.mark.parametrize("seq,blocks", [
    (384, (1024, 1024, 128)),    # 384 = 1.5 x 256: the halving lands on 128
    (96, (1024, 1024, 256)),     # shorter than a sub-block: one, the sequence
])
def test_rule_fits_a_sequence_that_is_no_multiple_of_the_sub_block(seq, blocks):
    from deepspeed_tpu.ops.attention import attention, flash_blocks

    q, k, v = _qkv(b=1, sq=seq, skv=seq, hq=2, hkv=1, d=32)
    assert flash_blocks(q, k, None, "pallas") == blocks

    def loss(impl, q, k, v):
        return jnp.sum(attention(q, k, v, causal=True, impl=impl) ** 2)

    got = jax.value_and_grad(lambda *a: loss("pallas", *a), (0, 1, 2))(q, k, v)
    want = jax.value_and_grad(lambda *a: loss("xla", *a), (0, 1, 2))(q, k, v)
    for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=5e-5, atol=5e-5)


@pytest.mark.parametrize("seq,blocks", [
    (1024, (1024, 1024, 256)), (2048, (1024, 1024, 256)),
    (1536, (512, 512, 256)), (320, (1024, 1024, 320)),
    (1088, None),   # blocks of 64 rows: no whole lane tile for dK / dV's lse
])
def test_rule_on_the_chip_by_sequence_length(monkeypatch, seq, blocks):
    from deepspeed_tpu.ops import attention as ops

    monkeypatch.setattr(ops, "_on_tpu", lambda: True)
    q = jax.ShapeDtypeStruct((1, seq, 4, 64), jnp.bfloat16)
    assert ops.flash_blocks(q, q) == blocks


def test_causal_with_more_keys_than_queries_keeps_its_mask():
    """``sq != skv``: the kernel's mask is ``q_pos >= k_pos`` with no offset
    (a decode-style suffix goes through ``xla_attention``), as before the
    walk."""
    q, k, v = _qkv(b=1, sq=32, skv=64, hq=2, hkv=2, d=32)
    bias = jnp.where(jnp.arange(32)[:, None] >= jnp.arange(64)[None, :],
                     0.0, -1e30)
    ref = xla_attention(q, k, v, causal=False, bias=bias)
    for bq, bk, sub in ((64, 64, 16), (16, 32, 8)):
        out = flash_attention(q, k, v, True, None, bq, bk, sub)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("sub,pairs", [(128, 36), (256, 10), (512, 3),
                                       (1024, 1)])
def test_kernels_multiply_only_the_causal_sub_block_pairs(sub, pairs):
    """At 1,024 tokens one block is the whole sequence, so the walk is known
    when the kernel is traced: a strip of ``sub`` rows takes its products
    (2 / 4 / 3 in forward / dK dV / dQ) against the rows it meets and no
    further, so the score-shaped products (one in forward, two in each
    backward kernel) cover the causal sub-block pairs once each, with one
    mask's compare a strip; ``flash_pair_share`` counts the same pairs."""
    import re

    from deepspeed_tpu.ops.attention import flash_pair_share

    n = 1024 // sub
    assert flash_pair_share(1024, 1024, sub) == pairs / n ** 2
    assert flash_pair_share(1024, 1024, sub, causal=False) == 1.0
    q = jax.ShapeDtypeStruct((1, 1024, 2, 64), jnp.bfloat16)

    def bodies(causal):
        text = str(jax.make_jaxpr(jax.grad(
            lambda q, k, v: flash_attention(
                q, k, v, causal, None, 1024, 1024, sub).astype(
                    jnp.float32).sum(), (0, 1, 2)))(q, q, q))
        products = re.findall(r"f32\[(\d+),(\d+)\] = dot_general", text)
        scores = sum(int(a) * int(b) for a, b in products if int(b) != 64)
        return len(products), scores // sub ** 2, text.count(" = ge ")

    assert bodies(True) == ((2 + 4 + 3) * n, 5 * pairs, 3 * n)
    assert bodies(False) == ((2 + 4 + 3) * n, 5 * n ** 2, 0)


def test_no_environment_variable_chooses_the_blocks():
    """``flash_blocks`` decides from the shapes: the two ``DSTPU_FLASH_BLOCK``
    tunables are gone from the package."""
    import inspect
    import pathlib

    import deepspeed_tpu
    from deepspeed_tpu.ops import attention as ops

    root = pathlib.Path(deepspeed_tpu.__file__).parent
    hits = [str(p) for p in root.rglob("*.py")
            if "DSTPU_FLASH_BLOCK" in p.read_text()]
    assert not hits
    rule = inspect.getsource(ops.flash_blocks) + inspect.getsource(ops._fit)
    assert "environ" not in rule and "getenv" not in rule


def test_train_dispatch_span_says_which_walk_the_step_runs(monkeypatch):
    """The walk engages by shape, so its counter is static: once a step
    program with flash calls is traced, every ``train/dispatch`` span carries
    the sub-block width and the share of the square's pairs it leaves."""
    import contextlib

    import deepspeed_tpu
    from deepspeed_tpu.comm.topology import reset_topology
    from deepspeed_tpu.models import gpt2
    from deepspeed_tpu.ops import attention as ops
    from deepspeed_tpu.runtime import engine as engine_mod

    spans = []

    @contextlib.contextmanager
    def recording(name, **args):
        spans.append((name, args))
        yield

    monkeypatch.setattr(engine_mod, "span", recording)
    monkeypatch.setattr(ops, "_on_tpu", lambda: True)   # flash, interpreted
    monkeypatch.setattr(ops, "FLASH_SUB", 16)
    monkeypatch.setattr(ops, "_traced_walk", {})
    reset_topology()
    engine, _, _, _ = deepspeed_tpu.initialize(
        model=lambda ctx: gpt2.build(gpt2.GPT2Config.tiny(128), ctx=ctx),
        config={"train_micro_batch_size_per_device": 1,
                "gradient_accumulation_steps": 1, "steps_per_print": 0,
                "optimizer": {"type": "adamw", "params": {"lr": 1e-3}},
                "zero_optimization": {"stage": 0}, "mesh": {"data": 8}})
    batch = {"input_ids": np.random.default_rng(0).integers(
        0, 128, (8, 64), dtype=np.int32)}
    for _ in range(2):
        float(engine.train_batch(batch))
    engine.destroy()
    reset_topology()
    first, second = [a for n, a in spans if n == "train/dispatch"]
    assert first == {}      # read before the step program was traced
    assert second == {"flash_sub": 16, "flash_pair_share": 0.625}
