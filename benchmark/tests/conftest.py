"""The harness on the CPU at tiny presets: counts and control flow only. A CPU
run never prints a device metric; ``run.py``'s ``main`` refuses the CPU, so the
tests call ``run_cell`` and ``result_line`` themselves."""

import json
import os
import shutil
import sys

os.environ.setdefault("JAX_PLATFORMS", "cpu")
os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "")
                           + " --xla_force_host_platform_device_count=4")

import pytest  # noqa: E402

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
REPO = os.path.dirname(BENCH)
for p in (BENCH, REPO):
    if p not in sys.path:
        sys.path.insert(0, p)

TINY_GPT2 = {
    "source": "test", "family": "gpt2", "config_class": "GPT2Config",
    "fields": {"vocab_size": "vocab_size", "hidden_size": "n_embd",
               "num_layers": "n_layer", "num_heads": "n_head",
               "max_seq_len": "n_positions"},
    "n_embd": 64, "n_head": 4, "n_layer": 2, "n_positions": 128,
    "vocab_size": 256, "reduced": [],
    "serve": {"dtype": "bfloat16",
              "engine": {"block_size": 16, "num_blocks": 65, "max_seqs": 8,
                         "max_tokens_per_step": 64, "max_blocks_per_seq": 8,
                         "prefill_tile": 16},
              "router": {"max_queue_tokens": 65536}},
    "train": {"zero_stage": 3, "mesh": {"data": 1, "fsdp": 4},
              "micro_batch_per_device": 1, "sequence_length": 64,
              "remat": "full", "bf16_master_weights": True,
              "optimizer": {"type": "adamw", "params": {"lr": 1e-4}},
              "scheduler": {"type": "WarmupLR", "params": {
                  "warmup_min_lr": 0.0, "warmup_max_lr": 1e-4,
                  "warmup_num_steps": 4, "warmup_type": "linear"}},
              "gradient_clipping": 1.0},
}
TINY_MIXTRAL = {
    "source": "test", "family": "mixtral", "config_class": "MixtralConfig",
    "fields": {"vocab_size": "vocab_size", "hidden_size": "hidden_size",
               "intermediate_size": "intermediate_size",
               "num_layers": "num_hidden_layers",
               "num_heads": "num_attention_heads",
               "num_kv_heads": "num_key_value_heads",
               "num_experts": "num_local_experts",
               "top_k": "num_experts_per_tok",
               "max_seq_len": "max_position_embeddings"},
    "vocab_size": 256, "hidden_size": 64, "intermediate_size": 96,
    "num_hidden_layers": 2, "num_attention_heads": 4,
    "num_key_value_heads": 2, "num_local_experts": 4,
    "num_experts_per_tok": 2, "max_position_embeddings": 2048,
    "reduced": [],
    "serve": TINY_GPT2["serve"],
}
TINY_CHAT = {
    "kind": "open_loop", "arrivals": {"process": "gamma", "cv": 2.0, "pattern_seed": 5},
    "prompt_tokens": {"dist": "lognormal", "median": 24, "sigma": 0.5,
                      "min": 4, "max": 60},
    "output_tokens": {"dist": "lognormal", "median": 8, "sigma": 0.5,
                      "min": 2, "max": 24},
    "total_tokens_max": 100, "stream": True, "lead_seconds": 1,
    "grace_seconds": 20, "warm_requests": 2, "warm_max_tokens": 4,
    "limits": {"ttft_ms": 2000, "gap_ms": 200},
}
TINY_POOL = {
    "kind": "closed_loop",
    "prompt_tokens": {"dist": "lognormal", "median": 40, "sigma": 0.4,
                      "min": 20, "max": 90},
    "output_tokens": {"dist": "uniform", "min": 2, "max": 8},
    "total_tokens_max": 100, "stream": False, "lead_seconds": 1,
    "grace_seconds": 20, "warm_requests": 2, "warm_max_tokens": 4,
    "limits": {"ttft_ms": 2000, "gap_ms": 200},
}
TINY_TRAIN = {"kind": "train_job", "sequence_length": 64,
              "steps_before_window": 2, "reference_sample_sequences": 4}


@pytest.fixture()
def copy(tmp_path):
    """A temporary copy of ``BENCHMARK.json`` and ``benchmark/`` to which a
    test adds files and entries, as a later PR would; returns ``add``."""
    root = str(tmp_path / "checkout")
    shutil.copytree(BENCH, os.path.join(root, "benchmark"),
                    ignore=shutil.ignore_patterns("__pycache__", "tests"))
    shutil.copy(os.path.join(REPO, "BENCHMARK.json"), root)

    def add(files: dict, **entries) -> str:
        """Write new ``files`` (relative path -> JSON object or text) and
        append ``entries`` (key of BENCHMARK.json -> list of new entries)."""
        for rel, content in files.items():
            path = os.path.join(root, rel)
            assert not os.path.exists(path), f"{rel} would edit a file"
            os.makedirs(os.path.dirname(path), exist_ok=True)
            with open(path, "w") as f:
                f.write(content if isinstance(content, str)
                        else json.dumps(content))
        with open(os.path.join(root, "BENCHMARK.json")) as f:
            bench = json.load(f)
        for key, new in entries.items():
            bench[key].extend(new)
        with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
            json.dump(bench, f)
        return root

    return add
