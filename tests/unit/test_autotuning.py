"""Autotuner: small measured grid search (reference: ``tests/unit/autotuning``).

The tuner builds many engines over VARIED meshes back-to-back — exactly the
in-process multi-mesh churn that can wedge XLA's emulated CPU collectives
(tests/unit/isolation.py) — so each scenario runs subprocess-isolated.
"""

from deepspeed_tpu.autotuning.autotuner import probe_model_info
from deepspeed_tpu.models import llama
from isolation import run_isolated  # tests/unit is rootdir-inserted by pytest

VOCAB = 256

# one layer: a trial is an engine, an engine here is its compiles, and what
# the tuner is asked (which trials it runs, skips and picks) does not depend
# on the depth
_SETUP = """
import dataclasses
from deepspeed_tpu.autotuning.autotuner import Autotuner
from deepspeed_tpu.models import llama
VOCAB = 256
MODEL = dataclasses.replace(llama.LlamaConfig.tiny(VOCAB), num_layers=1)
builder = lambda ctx: llama.build(MODEL, ctx=ctx)
"""


def test_autotuner_picks_a_working_config():
    run_isolated(_SETUP + """
tuner = Autotuner(
    model_builder=builder,
    base_config={"optimizer": {"type": "adamw", "params": {"lr": 1e-3}},
                 "mesh": {"data": 8}},
    steps_per_trial=1,
)
best = tuner.tune(micro_batch_sizes=[2, 4], zero_stages=[0, 1],
                  seq_len=16, vocab=VOCAB)
assert best["zero_stage"] in (0, 1)
assert best["micro_batch"] in (2, 4)
ok = [r for r in tuner.results if r.ok]
assert len(ok) == 4  # all trials viable at this size
assert max(r.samples_per_sec for r in ok) == \\
    next(r for r in ok if r.overrides == best).samples_per_sec
print("TUNE_OK")
""", "TUNE_OK")


def test_model_info_probe():
    """The model-profile estimates order correctly (pure math, in-process)."""
    builder = lambda ctx: llama.build(llama.LlamaConfig.tiny(VOCAB), ctx=ctx)  # noqa: E731
    info = probe_model_info(builder)
    assert info.num_params > 0 and info.hidden_size == 64
    # sharding 8 ways shrinks the estimate; stage 3 shards the most
    assert info.state_bytes(3, 8) < info.state_bytes(1, 8) < info.state_bytes(0, 8)
    assert info.activation_bytes(4, 128) == 2 * info.activation_bytes(2, 128)


def test_model_info_pruning_skips_oversized_configs():
    """With a (synthetic) tiny memory limit, the model-profile estimate
    prunes stage-0 configs before any engine is built (reference
    model-info pruning, autotuner.py:42)."""
    run_isolated(_SETUP + """
from deepspeed_tpu.autotuning.autotuner import probe_model_info
info = probe_model_info(builder)
limit = info.state_bytes(0, 8) * 0.5
assert info.state_bytes(3, 8) < 0.9 * limit < info.state_bytes(0, 8)
tuner = Autotuner(
    model_builder=builder,
    base_config={"optimizer": {"type": "adamw", "params": {"lr": 1e-3}},
                 "mesh": {"data": 1, "fsdp": 8}},
    steps_per_trial=1,
)
best = tuner.tune(micro_batch_sizes=[2], zero_stages=[0, 3],
                  seq_len=16, vocab=VOCAB, memory_bytes=limit)
skipped = [r for r in tuner.results if r.skipped]
assert skipped and skipped[0].overrides["zero_stage"] == 0
assert best["zero_stage"] == 3
print("PRUNE_OK")
""", "PRUNE_OK")


def test_refinement_dimensions_swept():
    """Phase 2 sweeps offload/TP/qgZ around the phase-1 winner and can
    return a refined config."""
    run_isolated(_SETUP + """
tuner = Autotuner(
    model_builder=builder,
    base_config={"optimizer": {"type": "adamw", "params": {"lr": 1e-3}},
                 "mesh": {"data": 8}},
    steps_per_trial=1,
)
best = tuner.tune(micro_batch_sizes=[2], zero_stages=[1],
                  seq_len=16, vocab=VOCAB,
                  offload_devices=("none", "cpu"), tp_degrees=(1, 2),
                  try_qgz=True)
tried = [r.overrides for r in tuner.results]
assert any("offload" in ov for ov in tried)
assert any(ov.get("tp") == 2 for ov in tried)
assert any(ov.get("quantized_gradients") for ov in tried)
assert best["zero_stage"] == 1 and best["micro_batch"] == 2
print("REFINE_OK")
""", "REFINE_OK")


def test_joint_sweep_finds_interaction():
    """Phase 3 (round-4 weak #8): dimensions that each improve are ALSO
    tried together, and an interaction win (combo > either alone) is
    found. Trials are synthetic (monkeypatched) so the interaction is
    deterministic."""
    from deepspeed_tpu.autotuning.autotuner import Autotuner, TrialResult

    speeds = {
        (): 1.0,                      # phase-1 winner baseline
        ("offload",): 2.0,            # each dim improves alone...
        ("tp",): 3.0,
        ("offload", "tp"): 10.0,      # ...and MORE together
    }

    def fake_trial(self, overrides, seq_len, vocab):
        key = tuple(sorted(
            k for k in ("offload", "tp") if overrides.get(k) not in
            (None, "none", 1)))
        sps = speeds.get(key, 0.5)
        return TrialResult(overrides=dict(overrides),
                           samples_per_sec=sps, step_ms=1000.0 / sps)

    tuner = Autotuner(model_builder=None, base_config={}, steps_per_trial=1)
    tuner._run_trial = fake_trial.__get__(tuner)
    best = tuner.tune(micro_batch_sizes=[2], zero_stages=[1],
                      seq_len=16, vocab=VOCAB,
                      offload_devices=("none", "cpu"), tp_degrees=(1, 2),
                      memory_bytes=0)
    assert best.get("offload") == "cpu" and best.get("tp") == 2, best
    combos = [r.overrides for r in tuner.results
              if r.overrides.get("offload") == "cpu"
              and r.overrides.get("tp") == 2]
    assert combos, "joint combo never tried"
