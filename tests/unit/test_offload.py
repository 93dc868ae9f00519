"""Offload tiers: windowed sub-group optimizer state on host / NVMe
(reference: ``tests/unit/runtime/zero`` offload suites +
``test_nvme_checkpointing.py``)."""

import os

import jax
import numpy as np
import pytest

import deepspeed_tpu
from deepspeed_tpu.comm.topology import reset_topology
from deepspeed_tpu.models import llama
from deepspeed_tpu.runtime.offload import partition_groups

VOCAB = 256


def test_partition_groups():
    groups = partition_groups([10, 10, 50, 5, 100, 1], 60)
    assert groups == [[0, 1], [2, 3], [4], [5]]
    assert partition_groups([200], 60) == [[0]]  # oversized leaf -> own group
    assert partition_groups([], 60) == []


def _engine(offload_device, tmp_path, stage=2, sub_group=30_000):
    reset_topology()
    cfg = {
        "train_micro_batch_size_per_device": 2,
        "gradient_accumulation_steps": 2,
        "steps_per_print": 0,
        "gradient_clipping": 1.0,
        "optimizer": {"type": "adamw", "params": {"lr": 1e-2}},
        "zero_optimization": {
            "stage": stage,
            "sub_group_size": sub_group,
            "offload_optimizer": {
                "device": offload_device,
                "nvme_path": str(tmp_path / "nvme"),
            },
        },
        "mesh": {"data": 2, "fsdp": 4},
        "seed": 7,
    }
    engine, _, _, _ = deepspeed_tpu.initialize(
        model=lambda ctx: llama.build(llama.LlamaConfig.tiny(VOCAB), ctx=ctx),
        config=cfg, seed=11,
    )
    return engine


def _batches(n, seed=0):
    rng = np.random.default_rng(seed)
    return [{"input_ids": rng.integers(0, VOCAB, (32, 16), dtype=np.int32)}
            for _ in range(n)]


def _run(engine, batches):
    return [float(engine.train_batch(b)) for b in batches]


@pytest.fixture(scope="module")
def trained(tmp_path_factory):
    """``trained(device)``: ``(engine, its losses over _batches(4))`` of one
    engine a tier, built and trained once a module. An engine here is two
    compiles of its step (~12 s) and four steps are nothing: the cases that
    compare trajectories, or look at a trained engine, share them."""
    runs = {}

    def get(device):
        if device not in runs:
            eng = _engine(device, tmp_path_factory.mktemp(device))
            runs[device] = (eng, _run(eng, _batches(4)))
        return runs[device]

    return get


class TestWindowedOffload:
    def test_nvme_training_matches_baseline(self, trained):
        """offload_optimizer.device=nvme: identical loss trajectory to the
        un-offloaded engine, optimizer state never device-resident."""
        _, base = trained("none")
        eng, got = trained("nvme")
        assert len(eng._groups) > 1   # genuinely windowed
        np.testing.assert_allclose(got, base, rtol=2e-4, atol=2e-5)
        # on disk after training, and never materialized on the engine
        assert eng.opt_state is None
        nvme = eng.config.zero_optimization.offload_optimizer.nvme_path
        swp = [f for f in os.listdir(nvme) if f.endswith(".swp")]
        assert len(swp) >= len(eng._groups)

    def test_cpu_windowed_matches_baseline(self, trained):
        """Host-tier path: grouped in-jit update (memory kinds are a no-op on
        the CPU test backend, but the windowed group walk is exercised)."""
        _, base = trained("none")
        eng, got = trained("cpu")
        assert isinstance(eng.opt_state, list) and len(eng.opt_state) > 1
        np.testing.assert_allclose(got, base, rtol=2e-4, atol=2e-5)

    def test_nvme_checkpoint_roundtrip(self, tmp_path, trained):
        """Save/load with NVMe-offloaded state: resumed run matches the
        continuous one (reference test_nvme_checkpointing.py)."""
        batches = _batches(4)
        _, cont_losses = trained("nvme")

        half = _engine("nvme", tmp_path / "b")
        assert half.opt_state is None  # state lives on NVMe, not in HBM
        _run(half, batches[:2])
        half.save_checkpoint(str(tmp_path / "ckpt"))

        resumed = _engine("nvme", tmp_path / "c")
        resumed.load_checkpoint(str(tmp_path / "ckpt"))
        got = _run(resumed, batches[2:])
        np.testing.assert_allclose(got, cont_losses[2:], rtol=2e-4, atol=2e-5)

    def test_backward_path_guarded_under_nvme(self, trained):
        eng, _ = trained("nvme")
        with pytest.raises(NotImplementedError):
            eng.backward(_batches(1)[0])

    @pytest.mark.parametrize("device", ["cpu", "nvme"])
    def test_tensor_fragment_api_with_offload(self, trained, device):
        """safe_get_full_optimizer_state resolves moments across the grouped
        and NVMe representations (reference test_zero_tensor_fragment.py)."""
        from deepspeed_tpu.utils.tensor_fragment import (
            safe_get_full_optimizer_state,
        )

        eng, _ = trained(device)
        mu = safe_get_full_optimizer_state(eng, "layers/wq", "exp_avg")
        nu = safe_get_full_optimizer_state(eng, "layers/wq", "exp_avg_sq")
        assert mu.shape == np.asarray(eng.params["layers"]["wq"]).shape
        assert float(np.abs(mu).sum()) > 0 and float(nu.sum()) > 0
