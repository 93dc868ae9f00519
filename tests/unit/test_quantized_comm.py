"""Quantized collectives (ZeRO++ qgZ / 1-bit comm) — int8 on the wire,
error-feedback convergence, engine training parity
(reference: ``tests/unit/comm``, ``tests/unit/runtime/comm`` + onebit suites)."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import deepspeed_tpu
from deepspeed_tpu.comm.comm import init_distributed
from deepspeed_tpu.comm.quantized_collectives import quantized_all_reduce_arrays
from deepspeed_tpu.comm.topology import reset_topology
from deepspeed_tpu.config.config import MeshConfig
from deepspeed_tpu.models import llama

VOCAB = 256
# one layer: what these cases compare (two reductions of the same gradients,
# two optimizers on the same wire) does not depend on depth, and an engine's
# cost here is its two compiles of the step, which grow with it
MODEL = dataclasses.replace(llama.LlamaConfig.tiny(VOCAB), num_layers=1)


@pytest.fixture
def data_mesh():
    return init_distributed(MeshConfig(data=8)).mesh


class TestQuantizedAllReduce:
    def test_mean_within_quantization_tolerance(self, data_mesh):
        rng = np.random.default_rng(0)
        x = jnp.asarray(rng.normal(size=(8, 1000)).astype(np.float32))
        err = jnp.zeros_like(x)
        mean, _ = jax.jit(
            lambda x, e: quantized_all_reduce_arrays(x, e, data_mesh, "data")
        )(x, err)
        true = np.asarray(x).mean(axis=0)
        rel = np.abs(np.asarray(mean)[0] - true).max() / np.abs(true).max()
        assert rel < 0.02, rel

    def test_error_feedback_kills_bias(self, data_mesh):
        """Averaging repeated reductions of the SAME tensor must converge to
        the exact mean — the error-feedback property 1-bit Adam relies on."""
        rng = np.random.default_rng(1)
        x = jnp.asarray(rng.normal(size=(8, 512)).astype(np.float32))
        e = jnp.zeros_like(x)
        f = jax.jit(lambda x, e: quantized_all_reduce_arrays(x, e, data_mesh, "data"))
        acc = np.zeros(512)
        n = 40
        for _ in range(n):
            m, e = f(x, e)
            acc += np.asarray(m)[0]
        true = np.asarray(x).mean(axis=0)
        one_shot = np.abs(np.asarray(f(x, jnp.zeros_like(x))[0])[0] - true).max()
        with_ef = np.abs(acc / n - true).max()
        assert with_ef < one_shot / 5, (with_ef, one_shot)

    def test_wire_dtype_is_int8(self, data_mesh):
        """The 'done' criterion: the collective operands in the
        compiled HLO are s8, i.e. compression happens ON THE WIRE, not just
        numerically."""
        x = jnp.zeros((8, 256), jnp.float32)
        f = jax.jit(lambda x, e: quantized_all_reduce_arrays(x, e, data_mesh, "data"))
        txt = f.lower(x, jnp.zeros_like(x)).compile().as_text()
        a2a = [l for l in txt.splitlines() if "all-to-all" in l]
        ag = [l for l in txt.splitlines() if "all-gather" in l]
        assert a2a and any("s8[" in l for l in a2a), "all-to-all payload not int8"
        assert ag and any("s8[" in l for l in ag), "all-gather payload not int8"

    @pytest.mark.parametrize("bits,dtype_tag,chunk_bytes", [
        # for n=8 ranks, 4096 elements -> 512-element chunks: the per-chunk
        # wire payload is 64 sign-bytes (1-bit, n/8) or 256 nibble-bytes
        # (4-bit, n/2)
        (1, "u8[", 64),
        (4, "s8[", 256),
    ])
    def test_low_bit_wire_bytes(self, data_mesh, bits, dtype_tag, chunk_bytes):
        """Round-4 item 4 'done' criterion: the all-to-all operand IS the
        packed payload — byte count ~ n/8 (1-bit) and n/2 (int4). XLA may
        lower the all-to-all as one [n, B] operand or a tuple of [1, B]
        per-destination pieces; both count, as long as the payload bytes per
        chunk match the packed size."""
        x = jnp.zeros((8, 4096), jnp.float32)
        f = jax.jit(lambda x, e: quantized_all_reduce_arrays(
            x, e, data_mesh, "data", bits=bits, block=64))
        txt = f.lower(x, jnp.zeros_like(x)).compile().as_text()
        a2a = [l for l in txt.splitlines() if "all-to-all" in l
               and dtype_tag in l]
        assert a2a, f"no {dtype_tag} all-to-all operand (bits={bits})"
        import re

        sizes = set()
        for line in a2a:
            for m in re.finditer(re.escape(dtype_tag) + r"([0-9,]+)\]", line):
                dims = [int(d) for d in m.group(1).split(",")]
                p = 1
                for d in dims:
                    p *= d
                sizes.add(p)
        assert sizes & {chunk_bytes, 8 * chunk_bytes}, (sizes, chunk_bytes)

    def test_one_bit_error_feedback_converges(self, data_mesh):
        """1-bit wire + error feedback: the running average of repeated
        reductions converges to the exact mean (the compressed-allreduce
        guarantee 1-bit Adam is built on)."""
        rng = np.random.default_rng(5)
        x = jnp.asarray(rng.normal(size=(8, 512)).astype(np.float32))
        e = jnp.zeros_like(x)
        f = jax.jit(lambda x, e: quantized_all_reduce_arrays(
            x, e, data_mesh, "data", bits=1, block=64))
        true = np.asarray(x).mean(axis=0)
        acc = np.zeros(512)
        errs = {}
        for i in range(240):
            m, e = f(x, e)
            acc += np.asarray(m)[0]
            if i + 1 in (120, 240):
                errs[i + 1] = np.abs(acc / (i + 1) - true).max()
        one_shot = np.abs(np.asarray(f(x, jnp.zeros_like(x))[0])[0] - true).max()
        # O(1/n) telescoping: doubling the horizon ~halves the running-mean
        # error (measured 0.24 -> 0.127), and the long average beats the
        # one-shot sign noise by >5x
        assert errs[240] < one_shot / 5, (errs, one_shot)
        assert errs[240] < errs[120] * 0.7, errs


def _train(config_extra, optimizer=None, steps=6, seed=3, mesh=None, stage=1,
           model=MODEL):
    reset_topology()
    cfg = {
        "train_micro_batch_size_per_device": 2,
        "gradient_accumulation_steps": 2,
        "steps_per_print": 0,
        "gradient_clipping": 1.0,
        "optimizer": optimizer or {"type": "adamw", "params": {"lr": 1e-2}},
        "zero_optimization": {"stage": stage, **config_extra},
        "mesh": mesh or {"data": 8},
        "seed": 7,
    }
    engine, _, _, _ = deepspeed_tpu.initialize(
        model=lambda ctx: llama.build(model, ctx=ctx),
        config=cfg, seed=11,
    )
    rng = np.random.default_rng(seed)
    batch = {"input_ids": rng.integers(0, VOCAB, (32, 16), dtype=np.int32)}
    return [float(engine.train_batch(batch)) for _ in range(steps)]


class TestQuantizedTraining:
    def test_convergence_parity_vs_exact_reduction(self):
        """qgZ-compressed training must track the exact-reduction trajectory
        closely (not bit-exact — int8 wire — but convergent and close)."""
        base = _train({})
        quant = _train({"quantized_gradients": True})
        assert quant[-1] < quant[0] * 0.8  # converges
        np.testing.assert_allclose(quant, base, rtol=0.06)

    def test_composes_with_fsdp_stage2(self):
        """qgZ over data must compose with fsdp-sharded grads/opt state
        (reference qgZ exists FOR ZeRO: coalesced_collectives.py:31) —
        manual over data, fsdp GSPMD-auto inside."""
        mesh = {"data": 2, "fsdp": 4}
        base = _train({}, mesh=mesh, stage=2)
        quant = _train({"quantized_gradients": True}, mesh=mesh, stage=2)
        assert quant[-1] < quant[0] * 0.8
        np.testing.assert_allclose(quant, base, rtol=0.06)

    def test_composes_with_fsdp_stage3(self):
        mesh = {"data": 2, "fsdp": 4}
        base = _train({}, mesh=mesh, stage=3)
        quant = _train({"quantized_gradients": True}, mesh=mesh, stage=3)
        assert quant[-1] < quant[0] * 0.8
        np.testing.assert_allclose(quant, base, rtol=0.06)

    def test_requires_data_axis(self):
        reset_topology()
        with pytest.raises(ValueError, match="data"):
            deepspeed_tpu.initialize(
                model=lambda ctx: llama.build(MODEL, ctx=ctx),
                config={
                    "train_micro_batch_size_per_device": 2,
                    "optimizer": {"type": "adamw", "params": {"lr": 1e-3}},
                    "zero_optimization": {"stage": 1, "quantized_gradients": True},
                    "mesh": {"fsdp": 8},
                },
            )


class TestOnebitAdam:
    def test_matches_adamw_during_warmup(self):
        """With freeze_step beyond the run, 1-bit Adam IS Adam(W wd=0)."""
        adam = _train({}, optimizer={"type": "adam", "params": {"lr": 1e-2}})
        onebit = _train({}, optimizer={
            "type": "onebit_adam",
            "params": {"lr": 1e-2, "freeze_step": 1000},
        })
        np.testing.assert_allclose(onebit, adam, rtol=1e-4)

    def test_frozen_variance_with_quantized_comm_converges(self):
        """The full 1-bit Adam recipe: warmup with exact stats, then frozen
        variance + compressed gradient communication."""
        losses = _train(
            {"quantized_gradients": True},
            optimizer={"type": "onebit_adam",
                       "params": {"lr": 3e-3, "freeze_step": 5}},
            steps=10,
        )
        # keeps descending THROUGH the freeze point (step 5)
        assert losses[-1] < losses[5] < losses[0] * 0.85, losses


class TestOnebitLamb:
    """1-bit LAMB semantics (reference ``runtime/fp16/onebit/lamb.py``)."""

    def test_matches_lamb_during_warmup(self):
        import optax

        from deepspeed_tpu.config.config import OptimizerConfig
        from deepspeed_tpu.ops.optimizers import build_optimizer

        tx = build_optimizer(OptimizerConfig(
            type="onebit_lamb",
            params={"lr": 1e-2, "freeze_step": 1000}), learning_rate=1e-2)
        ref = optax.lamb(1e-2, weight_decay=0.0)
        params = {"w": jnp.ones((8, 8)) * 0.5, "b": jnp.arange(8.0)}
        s1, s2 = tx.init(params), ref.init(params)
        rng = np.random.default_rng(0)
        p1 = p2 = params
        for _ in range(4):
            g = {"w": jnp.asarray(rng.normal(size=(8, 8)), jnp.float32) * 0.1,
                 "b": jnp.asarray(rng.normal(size=(8,)), jnp.float32) * 0.1}
            u1, s1 = tx.update(g, s1, p1)
            u2, s2 = ref.update(g, s2, p2)
            p1 = optax.apply_updates(p1, u1)
            p2 = optax.apply_updates(p2, u2)
        for k in p1:
            np.testing.assert_allclose(np.asarray(p1[k]), np.asarray(p2[k]),
                                       rtol=1e-4, atol=1e-6)

    def test_variance_freezes_and_updates_stay_normalized(self):
        from deepspeed_tpu.ops.optimizers import scale_by_onebit_lamb

        # min_coeff=0: the low-side bound exists for degenerate tiny updates,
        # not to defeat normalization of huge ones
        tx = scale_by_onebit_lamb(warmup_steps=3, min_coeff=0.0)
        params = {"w": jnp.ones((16,))}
        state = tx.init(params)
        rng = np.random.default_rng(1)
        nu_frozen = None
        for i in range(8):
            g = {"w": jnp.asarray(rng.normal(size=(16,)) * (10.0 ** i),
                                  jnp.float32)}
            u, state = tx.update(g, state, params)
            if i == 2:  # step count == 3 == freeze point
                nu_frozen = np.asarray(state.nu["w"]).copy()
        np.testing.assert_array_equal(np.asarray(state.nu["w"]), nu_frozen)
        # the live trust ratio keeps the applied norm pinned to ||p|| even as
        # momentum drifts over the frozen variance (the stability property)
        un = float(jnp.linalg.norm(u["w"]))
        pn = float(jnp.linalg.norm(params["w"]))
        assert un <= pn * 1.01, (un, pn)

    def test_converges_with_quantized_comm(self):
        losses = _train(
            {"quantized_gradients": True},
            optimizer={"type": "onebit_lamb",
                       "params": {"lr": 5e-3, "freeze_step": 5}},
            steps=10,
        )
        # trust-ratio scaling makes LAMB deliberate at tiny scale: require
        # monotone-ish descent through the freeze point, not a big drop
        assert losses[-1] < losses[5] < losses[0], losses


class TestOneBitWire:
    """1-bit Adam with a REAL 1-bit wire (round-4 item 4): dense reduction
    during freeze_step warmup, sign+scale compressed reduction after."""

    def test_one_bit_adam_compressed_wire_parity(self):
        opt = {"type": "onebit_adam", "params": {"lr": 5e-3, "freeze_step": 3}}
        # two layers: with one, sign-only gradients fall behind by more than
        # the 25% below (29% at step 10)
        two = llama.LlamaConfig.tiny(VOCAB)
        base = _train({}, optimizer=opt, steps=10, model=two)
        comp = _train({"quantized_gradients": True,
                       "quantized_gradients_bits": 1},
                      optimizer=opt, steps=10, model=two)
        assert comp[-1] < comp[0] * 0.9  # still converges on the 1-bit wire
        # warmup steps are dense-wire: EXACTLY equal trajectories there
        np.testing.assert_allclose(comp[:3], base[:3], rtol=1e-5)
        # compressed phase tracks loosely (sign-only gradients)
        np.testing.assert_allclose(comp, base, rtol=0.25)

    def test_dense_phase_leaves_error_buffers_untouched(self):
        """Observable phase switch: during freeze_step the compressed program
        must not run, so the error-feedback residuals stay exactly zero."""
        import deepspeed_tpu
        from deepspeed_tpu.comm.topology import reset_topology

        reset_topology()
        cfg = {
            "train_micro_batch_size_per_device": 2,
            "gradient_accumulation_steps": 2,
            "steps_per_print": 0,
            "optimizer": {"type": "onebit_adam",
                          "params": {"lr": 1e-3, "freeze_step": 4}},
            "zero_optimization": {"stage": 1, "quantized_gradients": True,
                                  "quantized_gradients_bits": 1},
            "mesh": {"data": 8},
            "seed": 7,
        }
        engine, _, _, _ = deepspeed_tpu.initialize(
            model=lambda ctx: llama.build(MODEL, ctx=ctx),
            config=cfg, seed=11)
        assert engine._qgrad_warmup_steps == 4
        rng = np.random.default_rng(3)
        batch = {"input_ids": rng.integers(0, VOCAB, (32, 16), dtype=np.int32)}
        for _ in range(2):
            engine.train_batch(batch)
        err = np.concatenate([np.asarray(x).ravel() for x in
                              jax.tree_util.tree_leaves(engine._qgrad_error)])
        assert not err.any()
        for _ in range(3):  # cross freeze_step
            engine.train_batch(batch)
        err = np.concatenate([np.asarray(x).ravel() for x in
                              jax.tree_util.tree_leaves(engine._qgrad_error)])
        assert err.any()  # compressed wire engaged, residuals now live


class TestZeroOneAdam:
    """0/1 Adam semantics (reference ``runtime/fp16/onebit/zoadam.py``)."""

    def test_sparse_variance_refresh_schedule(self):
        from deepspeed_tpu.ops.optimizers import scale_by_zero_one_adam

        tx = scale_by_zero_one_adam(var_freeze_step=100, var_update_scaler=4)
        params = {"w": jnp.ones((8,))}
        state = tx.init(params)
        g = {"w": jnp.ones((8,), jnp.float32)}
        refreshes = []
        prev = np.asarray(state.nu["w"]).copy()
        for _ in range(16):
            _, state = tx.update(g, state, params)
            cur = np.asarray(state.nu["w"])
            refreshes.append(not np.array_equal(cur, prev))
            prev = cur.copy()
        # dense refresh in the first interval, sparser later
        assert all(refreshes[:4])
        assert sum(refreshes[8:]) < 8

    def test_variance_fully_frozen_after_freeze_step(self):
        from deepspeed_tpu.ops.optimizers import scale_by_zero_one_adam

        tx = scale_by_zero_one_adam(var_freeze_step=4, var_update_scaler=2)
        params = {"w": jnp.ones((8,))}
        state = tx.init(params)
        rng = np.random.default_rng(2)
        for i in range(12):
            g = {"w": jnp.asarray(rng.normal(size=(8,)), jnp.float32)}
            _, state = tx.update(g, state, params)
            if i == 3:
                frozen = np.asarray(state.nu["w"]).copy()
        np.testing.assert_array_equal(np.asarray(state.nu["w"]), frozen)

    def test_trains(self):
        losses = _train(
            {},
            optimizer={"type": "zero_one_adam",
                       "params": {"lr": 3e-3, "var_freeze_step": 5,
                                  "var_update_scaler": 2}},
            steps=8,
        )
        assert losses[-1] < losses[0] * 0.9, losses


class TestLoco:
    """LOCO reducer (reference ``coalesced_collectives.py:81``)."""

    def test_mean_within_tolerance(self, data_mesh):
        from deepspeed_tpu.comm.quantized_collectives import (
            loco_quantized_all_reduce_arrays,
        )

        rng = np.random.default_rng(4)
        x = jnp.asarray(rng.normal(size=(8, 1024)), jnp.float32)
        el = jnp.zeros_like(x)
        es = jnp.zeros((8, 1024 // 8), jnp.float32)
        mean, _, _ = jax.jit(
            lambda x, el, es: loco_quantized_all_reduce_arrays(
                x, el, es, data_mesh, "data"))(x, el, es)
        np.testing.assert_allclose(np.asarray(mean[0]),
                                   np.asarray(x.mean(axis=0)),
                                   rtol=0.0, atol=0.05)

    def test_error_feedback_kills_bias(self, data_mesh):
        from deepspeed_tpu.comm.quantized_collectives import (
            loco_quantized_all_reduce_arrays,
        )

        rng = np.random.default_rng(5)
        x = jnp.asarray(rng.normal(size=(8, 1024)), jnp.float32)
        true = np.asarray(x.mean(axis=0))
        el = jnp.zeros_like(x)
        es = jnp.zeros((8, 1024 // 8), jnp.float32)
        f = jax.jit(lambda x, el, es: loco_quantized_all_reduce_arrays(
            x, el, es, data_mesh, "data"))
        acc = np.zeros_like(true)
        n_rounds = 24
        for _ in range(n_rounds):
            mean, el, es = f(x, el, es)
            acc += np.asarray(mean[0])
        # the time-average converges to the true mean (both residual sinks
        # re-inject their quantization error)
        np.testing.assert_allclose(acc / n_rounds, true, rtol=0.0, atol=5e-3)
