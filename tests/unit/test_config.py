"""Config-tree tests (reference test analog: config round-trips, batch triangle)."""

import os

import pytest

from deepspeed_tpu.config.base import AUTO, ConfigError
from deepspeed_tpu.config.config import Config, load_config


def test_default_config():
    cfg = Config.from_dict({})
    assert cfg.bf16.enabled
    assert cfg.zero_optimization.stage == 0
    assert cfg.optimizer.type == "adamw"


def test_round_trip():
    src = {
        "train_micro_batch_size_per_device": 4,
        "gradient_accumulation_steps": 2,
        "gradient_clipping": 1.0,
        "optimizer": {"type": "adamw", "params": {"lr": 3e-4, "weight_decay": 0.1}},
        "zero_optimization": {"stage": 3, "offload_optimizer": {"device": "cpu"}},
        "mesh": {"fsdp": 4, "data": 2},
    }
    cfg = Config.from_dict(src)
    dumped = cfg.to_dict()
    cfg2 = Config.from_dict(dumped)
    assert cfg2.to_dict() == dumped
    assert cfg2.zero_optimization.stage == 3
    assert cfg2.zero_optimization.offload_optimizer.device == "cpu"
    assert cfg2.mesh.fsdp == 4


def test_unknown_key_rejected():
    with pytest.raises(ConfigError, match="unknown config key"):
        Config.from_dict({"not_a_real_key": 1})
    with pytest.raises(ConfigError, match="unknown config key"):
        Config.from_dict({"zero_optimization": {"stage": 1, "bogus": True}})


def test_deprecated_alias_migrates():
    cfg = Config.from_dict({"train_micro_batch_size_per_gpu": 8})
    assert cfg.train_micro_batch_size_per_device == 8


def test_auto_fields():
    cfg = Config.from_dict({"train_batch_size": "auto", "train_micro_batch_size_per_device": 2})
    assert cfg.train_batch_size == AUTO
    cfg.resolve_batch_sizes(dp_world_size=4)
    assert cfg.train_batch_size == 8
    with pytest.raises(ConfigError, match="'auto' is not supported"):
        Config.from_dict({"steps_per_print": "auto"})


def test_batch_triangle_resolution():
    cfg = Config.from_dict({"train_batch_size": 32, "train_micro_batch_size_per_device": 2})
    cfg.resolve_batch_sizes(dp_world_size=4)
    assert cfg.gradient_accumulation_steps == 4

    cfg = Config.from_dict({"train_batch_size": 32, "gradient_accumulation_steps": 2})
    cfg.resolve_batch_sizes(dp_world_size=4)
    assert cfg.train_micro_batch_size_per_device == 4

    cfg = Config.from_dict(
        {"train_batch_size": 30, "train_micro_batch_size_per_device": 4}
    )
    with pytest.raises(ConfigError, match="not divisible"):
        cfg.resolve_batch_sizes(dp_world_size=4)

    cfg = Config.from_dict({
        "train_batch_size": 16,
        "train_micro_batch_size_per_device": 2,
        "gradient_accumulation_steps": 4,
    })
    with pytest.raises(ConfigError, match="Inconsistent"):
        cfg.resolve_batch_sizes(dp_world_size=4)


def test_invalid_values():
    with pytest.raises(ConfigError):
        Config.from_dict({"zero_optimization": {"stage": 5}})
    with pytest.raises(ConfigError):
        Config.from_dict({"optimizer": {"type": "rmsprop_nope"}})
    with pytest.raises(ConfigError, match="cannot both"):
        Config.from_dict({"fp16": {"enabled": True}, "bf16": {"enabled": True}})


def test_fp16_alone_disables_bf16_default():
    cfg = Config.from_dict({"fp16": {"enabled": True}})
    assert cfg.fp16.enabled is True and cfg.bf16.enabled is False
    assert cfg.precision_name == "fp16"


def test_legacy_cpu_offload_bool():
    cfg = Config.from_dict({"zero_optimization": {"stage": 2, "cpu_offload": True}})
    assert cfg.zero_optimization.offload_optimizer.device == "cpu"
    cfg = Config.from_dict({"zero_optimization": {"cpu_offload": False}})
    assert cfg.zero_optimization.offload_optimizer.device == "none"


def test_triangle_only_train_batch():
    cfg = Config.from_dict({"train_batch_size": 32})
    cfg.resolve_batch_sizes(dp_world_size=4)
    assert cfg.train_micro_batch_size_per_device == 8
    assert cfg.gradient_accumulation_steps == 1


def test_load_config_from_json(tmp_path):
    p = tmp_path / "ds.json"
    p.write_text('{"train_micro_batch_size_per_device": 2, "fp16": {"enabled": true}, "bf16": {"enabled": false}}')
    cfg = load_config(str(p))
    assert cfg.fp16.enabled and not cfg.bf16.enabled
    import jax.numpy as jnp

    assert cfg.compute_dtype == jnp.float16


# ------------------------------------------- the documents name what exists
# ``RaggedConfig`` fields that are gone (PR 28, PR 43): a document that still
# names one describes an engine nobody can build
REMOVED_RAGGED_FIELDS = ("decode_run_ahead", "run_ahead_admission_cap",
                         "fused_chunk", "pipeline_depth", "sched_steps",
                         "spec_draft", "spec_ngram",
                         # constants of ``inference/ragged.py`` since PR 55
                         "headroom_guard_fraction", "kv_tier_disk_gbps",
                         "kv_tier_prefetch", "retry_backoff_max_s",
                         "retry_jitter")
REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def _read(path):
    with open(os.path.join(REPO, path), encoding="utf-8") as f:
        return f.read()


@pytest.mark.parametrize("path", [
    "README.md", "docs/SERVING.md", "docs/OBSERVABILITY.md",
    "docs/FAULT_TOLERANCE.md", "docs/PROFILING.md"])
def test_no_document_names_a_removed_serving_option(path):
    text = _read(path)
    assert [name for name in REMOVED_RAGGED_FIELDS if name in text] == []


def test_serving_guide_names_every_serving_option():
    from dataclasses import fields

    from deepspeed_tpu.inference.ragged import RaggedConfig

    names = [f.name for f in fields(RaggedConfig)]
    assert len(names) == 21 and not set(names) & set(REMOVED_RAGGED_FIELDS)
    text = _read("docs/SERVING.md")
    assert [name for name in names if f"`{name}`" not in text] == []
