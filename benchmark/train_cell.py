"""A training cell: ``deepspeed_tpu.initialize`` -> ``engine.train_batch``.

A fresh seeded batch every step, made on the host by a thread while the step
runs; every step's loss is fetched (the fetch settles the step) and the step
is timed by the host clock around it. The window opens after
``steps_before_window`` completed steps and closes with the last step that
ends inside ``seconds``; tokens per second is the tokens of those steps over
the seconds they took, so that a whole number of steps is never divided by a
window it does not fill.
"""

from __future__ import annotations

import gc
import os
import queue
import threading
import time

import trace_reduce
from cellspec import kernels, model
from serve_cell import compile_counters, say

TRACE_STEPS = 4


def engine_config(train: dict, seed: int) -> dict:
    return {
        "train_micro_batch_size_per_device": train["micro_batch_per_device"],
        "gradient_accumulation_steps": 1,
        "steps_per_print": 0,
        "gradient_clipping": train["gradient_clipping"],
        "sequence_length": train["sequence_length"],
        "seed": seed,
        "bf16": {"enabled": True,
                 "master_weights": train["bf16_master_weights"]},
        "optimizer": train["optimizer"],
        "scheduler": train["scheduler"],
        "zero_optimization": {"stage": train["zero_stage"]},
        "mesh": train["mesh"],
        "activation_checkpointing": {"enabled": train["remat"] != "none",
                                     "policy": train["remat"]},
    }


class Batches:
    """Seeded batches from a host thread, two ahead of the step."""

    def __init__(self, seed: int, vocab: int, shape):
        self.seed, self.vocab, self.shape = seed, vocab, shape
        self._q: queue.Queue = queue.Queue(maxsize=2)
        self._stop = threading.Event()
        self._thread = threading.Thread(target=self._fill, daemon=True)
        self._thread.start()

    def make(self, step: int):
        import numpy as np

        return np.random.default_rng([self.seed, step]).integers(
            0, self.vocab, self.shape, dtype=np.int32)

    def _fill(self) -> None:
        step = 0
        while not self._stop.is_set():
            batch = self.make(step)
            while not self._stop.is_set():
                try:
                    self._q.put(batch, timeout=0.1)
                    break
                except queue.Full:
                    continue
            step += 1

    def next(self):
        return {"input_ids": self._q.get(timeout=60)}

    def close(self) -> None:
        self._stop.set()
        self._thread.join(timeout=5)


def reference_loss(reference, cfg, params, ids) -> float:
    """Mean next-token cross entropy of ``ids`` [B, S] under the plain
    float32 "highest"-precision forward, one sequence at a time."""
    import jax
    import jax.numpy as jnp

    def nll(p, seq):
        logits = reference.forward(cfg, p, seq, jnp.float32)[:-1]
        logz = jax.nn.logsumexp(logits, axis=-1)
        return (logz - jnp.take_along_axis(logits, seq[1:, None], -1)[:, 0]).sum()

    fn = jax.jit(nll)
    with jax.default_matmul_precision("highest"):
        total = sum(float(fn(params, jnp.asarray(seq))) for seq in ids)
    return total / (ids.shape[0] * (ids.shape[1] - 1))


def run(spec: dict, seed: int, seconds: float, trace: bool, out_dir: str) -> dict:
    import jax
    import numpy as np

    import deepspeed_tpu
    from deepspeed_tpu import telemetry
    from deepspeed_tpu.comm.topology import reset_topology

    os.makedirs(out_dir, exist_ok=True)
    if not telemetry.TELEMETRY.enabled:  # the compile counters
        telemetry.configure(enabled=True)
    family, cfg, reference = model(spec)
    train, mix, cell = spec["config"]["train"], spec["mix"], spec["cell"]
    seq_len = mix["sequence_length"]
    reset_topology()
    engine, _, _, _ = deepspeed_tpu.initialize(
        model=lambda ctx: family.build(cfg, ctx=ctx),
        config=engine_config({**train, "sequence_length": seq_len}, seed))
    batches = Batches(seed, cfg.vocab_size, (engine.train_batch_size, seq_len))
    tokens_per_step = engine.train_batch_size * seq_len
    losses, ends = [], []

    def step() -> None:
        losses.append(float(engine.train_batch(batches.next())))
        ends.append(time.perf_counter())

    try:
        for _ in range(mix["steps_before_window"]):
            step()
        say(phase="setup", steps=len(losses), losses=losses,
            compile=compile_counters())
        t_window = ends[-1]
        first = len(ends)
        compiles0 = compile_counters()["compiles"]
        reduced = traced_at = None
        while time.perf_counter() - t_window < seconds:
            if (trace and traced_at is None
                    and time.perf_counter() - t_window > seconds / 3):
                traced_at = len(ends)
                trace_dir = os.path.join(out_dir, "trace_window")
                with trace_reduce.recording(trace_dir):
                    for _ in range(TRACE_STEPS):
                        step()
                reduced = trace_reduce.reduce_dir(trace_dir, kernels(spec))
            else:
                step()
    finally:
        batches.close()
    inside = [i for i in range(first, len(ends)) if ends[i] - t_window <= seconds]
    if traced_at is not None:  # steps the profiler started or stopped in
        inside = [i for i in inside
                  if not traced_at - 1 <= i <= traced_at + TRACE_STEPS]
    step_s = [ends[i] - ends[i - 1] for i in inside]
    compiles = compile_counters()["compiles"] - compiles0
    peak = max((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
               for d in jax.devices())
    say(phase="window", steps=len(step_s), first_losses=losses[:12],
        last_loss=losses[-1], compiles_in_window=compiles)

    # correctness, outside the window: the loss of step 0 against the plain
    # reference on the same batch and the same seeded weights, and the loss
    # at a fixed step inside the band the cell's file holds
    global_batch = engine.train_batch_size
    engine.destroy()
    engine.params = engine.opt_state = None
    gc.collect()
    t = time.perf_counter()
    ids = batches.make(0)[:mix["reference_sample_sequences"]]
    params = jax.jit(lambda key: family.init_params(cfg, key))(
        jax.random.PRNGKey(seed))
    want = reference_loss(reference, cfg, params, ids)
    del params
    # bf16 compute against float32: a relative 2e-3 on a mean over >= 16K
    # tokens (7e-6 measured on the chip, PR 22); a sample of the batch
    # instead of all of it adds the spread between sequences at random init
    whole = len(ids) >= global_batch
    step0_ok = bool(np.isclose(losses[0], want, rtol=2e-3 if whole else 2e-2))
    band = cell.get("loss_at_step")
    at = band["step"] if band else min(10, len(losses) - 1)
    band_ok = (band is None or bool(
        np.isclose(losses[at], band["value"], rtol=band["rtol"])))
    finite = bool(np.isfinite(losses).all())
    say(phase="check", seconds=time.perf_counter() - t, step0_loss=losses[0],
        reference_step0_loss=want, step0_ok=step0_ok,
        loss_at_step=[at, losses[at]], band=band, band_ok=band_ok)
    return {
        "correct": step0_ok and band_ok and finite,
        "attempted": len(step_s), "failed": 0,
        "metrics": {"train_tokens_per_s":
                    tokens_per_step * len(step_s) / sum(step_s)},
        "window": {"step_s": step_s, "tokens_per_step": tokens_per_step,
                   "trace": reduced, "t_window": t_window, "seconds": seconds,
                   "counters": {"compiles": compiles},
                   "memory_peak_bytes": peak, "seq_len": seq_len},
        "context": {"cfg": cfg, "reference": reference},
    }
