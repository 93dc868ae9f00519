"""ZeRO-Infinity parameter offload (reference ``runtime/zero/
parameter_offload.py`` + ``swap_tensor/partitioned_param_swapper.py``):
host-resident master params streamed through HBM per scanned layer.

On the CPU test mesh the pinned-host memory kind is rejected by the SPMD
partitioner (see ``runtime/offload.supports_memory_kinds``), so storage
falls back to device while the full streaming code path — the
``ShardCtx.param_stream`` per-slice hook, the whole-leaf stream cast, the
group-walk param streaming — stays live; the memory claim itself needs the
chip and no chip run asserts it (ROADMAP D4)."""

import numpy as np
import pytest

import deepspeed_tpu
from deepspeed_tpu.comm.topology import reset_topology
from deepspeed_tpu.config.config import ConfigError
from deepspeed_tpu.models import llama

VOCAB = 256


def _cfg(stage=3, offload_param="cpu", offload_opt="cpu", remat=True,
         **over):
    cfg = {
        "train_micro_batch_size_per_device": 2,
        "gradient_accumulation_steps": 2,
        "steps_per_print": 0,
        "gradient_clipping": 1.0,
        "optimizer": {"type": "adamw", "params": {"lr": 1e-2}},
        "zero_optimization": {
            "stage": stage,
            "sub_group_size": 30_000,
            "offload_param": {"device": offload_param},
            "offload_optimizer": {"device": offload_opt},
        },
        "activation_checkpointing": {"enabled": remat},
        "mesh": {"data": 2, "fsdp": 4},
        "seed": 7,
    }
    cfg.update(over)
    return cfg


def _engine(cfg):
    reset_topology()
    engine, _, _, _ = deepspeed_tpu.initialize(
        model=lambda ctx: llama.build(llama.LlamaConfig.tiny(VOCAB), ctx=ctx),
        config=cfg, seed=11,
    )
    return engine


@pytest.fixture(scope="module")
def offloaded():
    """``(engine, its losses over _batches(6))`` of the offloaded engine the
    trajectory cases compare against, trained once a module (an engine here
    is ~25 s of compiles, six steps are nothing)."""
    eng = _engine(_cfg())
    return eng, [float(eng.train_batch(b)) for b in _batches(6)]


def _batches(n, seed=0):
    rng = np.random.default_rng(seed)
    return [{"input_ids": rng.integers(0, VOCAB, (32, 16), dtype=np.int32)}
            for _ in range(n)]


class TestParamOffload:
    def test_loss_parity_vs_dense_stage3(self, offloaded):
        """Streaming the layer stack per scan slice tracks the plain stage-3
        engine's trajectory. Tolerance is bf16-loose: the baseline casts the
        whole stack to bf16 BEFORE the scan (layer-grad accumulation in bf16)
        while the streaming path casts per-slice inside it (accumulation in
        fp32) — the offloaded grads are the more precise of the two."""
        batches = _batches(4)
        base = [float(_engine(_cfg(offload_param="none", offload_opt="none",
                                   remat=True)).train_batch(b))
                for b in batches]
        eng, got = offloaded
        assert eng.shard_ctx.param_stream is not None
        assert eng._param_offload_mask is not None
        # the stacked layer leaves are all marked for offload
        import jax

        assert all(jax.tree_util.tree_leaves(eng._param_offload_mask["layers"]))
        assert abs(got[0] - base[0]) < 1e-6  # identical first forward
        np.testing.assert_allclose(got[:4], base, rtol=2e-2)

    def test_checkpoint_roundtrip(self, tmp_path, offloaded):
        """Save under offload, load into a fresh offloaded engine, keep
        training: trajectories match an uninterrupted run."""
        batches = _batches(6)
        _, ref_losses = offloaded

        eng = _engine(_cfg())
        for b in batches[:3]:
            eng.train_batch(b)
        eng.save_checkpoint(str(tmp_path), tag="s3")
        eng2 = _engine(_cfg())
        eng2.load_checkpoint(str(tmp_path), tag="s3")
        got = [float(eng2.train_batch(b)) for b in batches[3:]]
        np.testing.assert_allclose(got, ref_losses[3:], rtol=2e-4, atol=2e-5)


class TestParamOffloadConfigGuards:
    def test_requires_stage3(self):
        with pytest.raises((ConfigError, ValueError), match="stage"):
            _engine(_cfg(stage=2))

    def test_requires_remat(self):
        with pytest.raises((ConfigError, ValueError),
                           match="activation_checkpointing"):
            _engine(_cfg(remat=False))

    def test_requires_offloaded_optimizer(self):
        with pytest.raises((ConfigError, ValueError),
                           match="offload_optimizer"):
            _engine(_cfg(offload_opt="none"))

    def test_nvme_raises_loudly(self):
        """No silent no-op: the NVMe param tier is not implemented and must
        say so (the round-4 verdict's minimum bar)."""
        with pytest.raises((ConfigError, ValueError), match="nvme"):
            _engine(_cfg(offload_param="nvme"))
