"""Staged MPMD pipeline runtime (runtime/pipe/, docs/PIPELINE.md): the
partitioner's boundary math and subset/merge round-trip, closed-form
schedule validity, exact loss-trajectory parity of the 2-stage engine
against the fused single-program baseline (fp16 scaling + accumulation +
clipping on), per-stage checkpoint fragments with cross-topology restore,
in-process stage-crash replay, the pipe observability gauges, and the
staging-refusal guardrails."""

import functools
import json
import os

import jax
import numpy as np
import pytest

import deepspeed_tpu
from deepspeed_tpu.checkpoint import engine as ckpt
from deepspeed_tpu.models import llama
from deepspeed_tpu.runtime.engine import Engine
from deepspeed_tpu.runtime.pipe import partition, schedule
from deepspeed_tpu.runtime.pipe.engine import PipeEngine
from deepspeed_tpu.utils import faults

VOCAB = 97


def _builder(n_layers=4, tie=False):
    def build(ctx):
        return llama.build(llama.LlamaConfig(
            vocab_size=VOCAB, hidden_size=32, intermediate_size=64,
            num_layers=n_layers, num_heads=4, num_kv_heads=2,
            max_seq_len=64, tie_embeddings=tie), ctx=ctx)
    return build


def _config(extra=None, gas=2):
    cfg = {
        "train_micro_batch_size_per_device": 4,
        "gradient_accumulation_steps": gas,
        "steps_per_print": 0,
        "optimizer": {"type": "adamw", "params": {"lr": 1e-3}},
        "mesh": {"data": 1},
        "fp16": {"enabled": True, "initial_scale_power": 8},
        "gradient_clipping": 1.0,
        "seed": 7,
    }
    cfg.update(extra or {})
    return cfg


def _batches(n, bsz, seq=16, seed=0):
    rng = np.random.default_rng(seed)
    return [{"input_ids": rng.integers(0, VOCAB, (bsz, seq), dtype=np.int32)}
            for _ in range(n)]


def _run(extra, n=4, n_layers=4, gas=2, seed=0):
    eng, _, _, _ = deepspeed_tpu.initialize(
        model=_builder(n_layers), config=_config(extra, gas=gas), seed=11,
        mesh_devices=jax.devices()[:1])
    losses = [float(eng.train_batch(b))
              for b in _batches(n, eng.train_batch_size, seed=seed)]
    return eng, losses


STEPS = 16
PIPE_1F1B = {"pipeline": {"stages": 2, "schedule": "1f1b"}}


@functools.lru_cache(maxsize=None)
def _trained(extra=None, n_layers=4, gas=2):
    """``(engine, its STEPS losses)`` of one configuration (``extra`` as
    JSON), trained once a process. An engine is ~8 s (fused) or ~16 s
    (staged) of XLA compiles and a step 0.02 s, and the cases ask for the
    same few configurations: a shorter run is the start of this one."""
    return _run(extra and json.loads(extra), n=STEPS, n_layers=n_layers,
                gas=gas)


def _losses(extra=None, n=STEPS, **model):
    return _trained(extra and json.dumps(extra), **model)[1][:n]


# ---------------------------------------------------------------- partitioner

def test_plan_stages_uniform_and_uneven():
    plan = partition.plan_stages(4, 2)
    assert plan.boundaries == (0, 2, 4)
    # remainder spreads over the leading chunks
    plan = partition.plan_stages(7, 3)
    assert plan.boundaries == (0, 3, 5, 7)
    assert [plan.layer_range(v) for v in range(3)] == [(0, 3), (3, 5), (5, 7)]
    # interleaved: virtual chunks pinned to thread v % S
    plan = partition.plan_stages(8, 2, interleave=2)
    assert plan.n_virtual == 4 and plan.boundaries == (0, 2, 4, 6, 8)
    assert plan.chunks_of(0) == [0, 2] and plan.chunks_of(1) == [1, 3]


def test_plan_stages_parameters_method_balances_cost():
    # heavy head: cost-balanced boundary moves left of the uniform midpoint
    costs = [10.0, 10.0, 1.0, 1.0, 1.0, 1.0]
    plan = partition.plan_stages(6, 2, method="parameters",
                                 layer_costs=costs)
    assert plan.boundaries[1] <= 2
    # without cost data the method degrades to uniform
    plan = partition.plan_stages(6, 2, method="parameters")
    assert plan.boundaries == (0, 3, 6)


def test_plan_stages_rejects_bad_plans():
    with pytest.raises(ValueError, match="at least one layer"):
        partition.plan_stages(2, 4)
    with pytest.raises(ValueError, match="at least one layer"):
        partition.plan_stages(4, 2, interleave=4)
    with pytest.raises(ValueError, match="partition_method"):
        partition.plan_stages(4, 2, method="zigzag")


def test_split_merge_roundtrip():
    rng = np.random.default_rng(0)

    def arr(*shape):
        return rng.normal(size=shape).astype(np.float32)

    params = {
        "layers": {"w": arr(6, 3), "b": arr(6)},
        "embed": arr(5, 3),
        "head": arr(3, 5),
    }
    plan = partition.plan_stages(6, 3)
    owner = {"embed": "first", "head": "last"}
    trees = partition.split_params(params, plan, owner)
    assert "embed" in trees[0] and "embed" not in trees[1]
    assert "head" in trees[2] and "head" not in trees[0]
    assert trees[1]["layers"]["w"].shape == (2, 3)
    merged = partition.merge_params(trees, plan)
    for key in ("embed", "head"):
        np.testing.assert_array_equal(merged[key], params[key])
    np.testing.assert_array_equal(merged["layers"]["w"], params["layers"]["w"])
    # an unowned extra key is a loud error, not a silently dropped tensor
    with pytest.raises(ValueError, match="no stage owner"):
        partition.split_params(params, plan, {"embed": "first"})


# ------------------------------------------------------------------ schedules

@pytest.mark.parametrize("sched", ["gpipe", "1f1b"])
@pytest.mark.parametrize("n_stages,interleave,n_micro", [
    (2, 1, 1), (2, 1, 4), (2, 2, 4), (3, 1, 5), (4, 2, 8),
])
def test_schedules_validate(sched, n_stages, interleave, n_micro):
    n_virtual = n_stages * interleave
    instrs = schedule.build_schedule(sched, n_virtual, n_micro)
    schedule.validate_schedule(instrs, n_virtual, n_stages, n_micro)
    frac = schedule.bubble_fraction(sched, n_virtual, n_micro)
    assert 0.0 < frac < 1.0
    # more microbatches shrink the bubble
    assert schedule.bubble_fraction(sched, n_virtual, 4 * n_micro) < frac


def test_validate_schedule_catches_corruption():
    instrs = schedule.build_schedule("1f1b", 2, 2)
    with pytest.raises(ValueError, match="permutation"):
        schedule.validate_schedule(instrs[:-1], 2, 2, 2)
    # swapping two ops within a thread breaks the dependency order
    broken = [schedule.Instr(i.t, i.v, "B" if i.op == "F" else "F", i.mb)
              for i in instrs]
    with pytest.raises(ValueError):
        schedule.validate_schedule(broken, 2, 2, 2)


# --------------------------------------------------------------------- parity

def test_1f1b_parity_16_steps():
    """Acceptance pin: the 2-stage 1F1B loss trajectory against the fused
    baseline over 16 steps with GAS and gradient clipping on.

    In float32 the two are **bit-identical**, with the clip really biting
    (norm ~0.8 against 0.5): the boundary update reduces over the merged
    gradient tree, so the norm and the clip coefficient are the same fp32
    scalars (docs/PIPELINE.md).

    Under fp16 loss scaling they are not, and need not be: the staged run
    is other XLA programs than the fused one, the compiler places the
    roundings to float16 inside each program as it fuses, and now and then
    (by the batch's values: here at step 5, with the batches reversed at
    another step, in some orders never in 16 steps) one activation rounds
    the other way. The norm is still the same scalar at that step and the
    clip is idle; from there the two trajectories drift apart slowly
    (measured 2.5e-6 relative at 16 steps here, <= 5.6e-6 over the depths,
    splits and seeds tried). One float16 ulp is 2**-11 relative; the pin
    is 2**-16, 1/32 of it, on a loss that is a mean over 128 tokens."""
    exact = {"fp16": {"enabled": False}, "gradient_clipping": 0.5}
    base = _losses(exact)
    eng, pipe = _trained(json.dumps({**exact, **PIPE_1F1B}))
    assert isinstance(eng, PipeEngine)
    assert len(base) == 16 and base == pipe, (base, pipe)
    base, pipe = _losses(), _losses(PIPE_1F1B)
    rel = max(abs(a - b) / max(abs(a), 1e-12) for a, b in zip(base, pipe))
    assert rel <= 2.0 ** -16, (rel, base, pipe)


def test_gpipe_and_interleaved_parity():
    base = _losses(n=3)
    _, gp = _run({"pipeline": {"stages": 2, "schedule": "gpipe"}}, n=3)
    assert base == gp, (base, gp)
    # interleaved 1F1B: 8 layers, 2 stages x 2 chunks = 4 virtual stages
    _, base8 = _run(None, n=3, n_layers=8, gas=4)
    _, il = _run({"pipeline": {"stages": 2, "interleave": 2,
                               "schedule": "1f1b"}},
                 n=3, n_layers=8, gas=4)
    assert base8 == il, (base8, il)


def test_stages_1_degenerates_to_plain_engine():
    eng0, l0 = _trained()
    eng1, l1 = _run({"pipeline": {"stages": 1}}, n=1)
    assert type(eng0) is Engine and type(eng1) is Engine
    assert l0[:1] == l1


# ---------------------------------------------------------------- checkpoints

def test_pipeline_checkpoint_fragments_and_cross_stage_restore(tmp_path):
    save_dir = str(tmp_path / "ckpt")
    pipe_eng, _ = _trained(json.dumps(PIPE_1F1B))
    pipe_eng.save_checkpoint(save_dir, tag="t2")
    cont = _batches(4, pipe_eng.train_batch_size)[2:4]
    after = [float(pipe_eng.train_batch(b)) for b in cont]

    # per-stage fragment naming + the manifest's pipeline row
    files = sorted(os.listdir(os.path.join(save_dir, "t2")))
    for name in ("model_shard_p0_s0.npz", "model_shard_p0_s1.npz",
                 "optimizer_shard_p0_s0.npz", "optimizer_shard_p0_s1.npz"):
        assert name in files, files
    with open(os.path.join(save_dir, "t2", "manifest.json")) as f:
        man = json.load(f)
    row = man["pipeline"]
    assert row["stages"] == 2 and row["schedule"] == "1f1b"
    assert row["boundaries"] == [0, 2, 4]
    assert set(row["fragments"]) == {"0", "1"}

    # 2-stage save -> 2-stage restore: exact resume
    p2, _, _, _ = deepspeed_tpu.initialize(
        model=_builder(), config=_config({"pipeline": {"stages": 2}}),
        seed=11, mesh_devices=jax.devices()[:1])
    p2.load_checkpoint(save_dir, tag="t2")
    assert [float(p2.train_batch(b)) for b in cont] == after

    # 2-stage save -> single-program merged restore: exact resume (into the
    # fused engine the parity cases trained: a load replaces all it holds)
    p1, _ = _trained()
    p1.load_checkpoint(save_dir, tag="t2")
    assert [float(p1.train_batch(b)) for b in cont] == after


def test_verify_checkpoint_flags_missing_pipeline_fragment(tmp_path):
    man = {"pipeline": {"stages": 2,
                        "fragments": {"0": ["model_shard_p0_s0.npz"],
                                      "1": ["model_shard_p0_s1.npz"]}}}
    with pytest.raises(ckpt.CheckpointCorruptError) as err:
        ckpt._verify_pipeline_fragments(str(tmp_path), "t0", man)
    assert err.value.stage == "pipeline-fragments"


# ------------------------------------------------------------ failure + scope

def test_stage_crash_replays_exactly():
    inj = faults.get_fault_injector()
    inj.reset()
    try:
        clean = _losses(PIPE_1F1B, n=3)
        inj.configure([{"point": "pipe.stage", "kind": "raise", "times": 1,
                        "request_id": "stage1", "after": 6}])
        eng, crashed = _run(PIPE_1F1B, n=3)
        assert eng.stage_restarts >= 1
        assert clean == crashed, (clean, crashed)
    finally:
        inj.reset()


def test_pipe_observability_gauges():
    from deepspeed_tpu.telemetry import TELEMETRY

    eng, _ = _run({"pipeline": {"stages": 2, "schedule": "1f1b"},
                   "telemetry": {"enabled": True,
                                 "stepscope": {"enabled": True}}}, n=2)
    assert len(eng._last_stage_busy) == 2 and eng._last_stage_wall > 0
    assert eng.stepscope._g_pipe_bubble.value() > 0.0
    prom = TELEMETRY.registry.render_prometheus()
    assert "train_pipe_bubble_fraction" in prom
    assert 'train_step_skew_ratio{stage="0"}' in prom
    assert 'train_step_skew_ratio{stage="1"}' in prom
    # the pipe_bubble phase joins the ledger without breaking the wall pin
    summary = eng.stepscope.summary()
    assert summary["phase_seconds_total"].get("pipe_bubble", 0.0) > 0.0
    assert abs(summary["phase_sum_over_step_ratio"] - 1.0) <= 0.05


def test_staging_refuses_unsupported_features():
    # tied embeddings: no stage owner for the shared table
    with pytest.raises(ValueError, match="tie"):
        deepspeed_tpu.initialize(
            model=_builder(tie=True),
            config=_config({"pipeline": {"stages": 2}}),
            seed=11, mesh_devices=jax.devices()[:1])
    # in-jit pipeline mesh axis + staged runtime is a contradiction
    with pytest.raises(ValueError):
        deepspeed_tpu.initialize(
            model=_builder(),
            config=_config({"pipeline": {"stages": 2},
                            "mesh": {"data": 1, "pipeline": 2}}),
            seed=11, mesh_devices=jax.devices()[:2])
