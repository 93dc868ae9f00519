"""Single-chip training throughput benchmark.

Trains the flagship Llama-family decoder for a few steps on the local TPU chip
and reports model FLOPs utilization. Target from BASELINE.json: Llama-3-8B
ZeRO-3 bf16 @ >=45% MFU on v5p-64; single-chip MFU is the per-chip proxy
(``vs_baseline`` = MFU / 0.45).

One process per chip: this parent never initializes a JAX backend (a parent
that holds the chip makes every child that needs it fail or hang); each
candidate config and each rung runs in its own subprocess, one at a time. On a
trial failure the ladder backs off to a smaller config; configs are sized from
the device's HBM capacity by generation, not guessed.

The default run needs the chip: without one it is an error, and a rung that
fails makes the exit code non-zero. The ``--mode`` legs that give verdicts
rather than times run wherever the caller points JAX (``JAX_PLATFORMS=cpu``).

Prints ONE JSON line: {"metric": ..., "value": N, "unit": ..., "vs_baseline": N}.
"""

import json
import os
import shutil
import subprocess
import sys
import time

# (bf16 peak FLOPs/s, HBM bytes) per chip by TPU generation (public spec sheets)
CHIP_TABLE = {
    "v5 lite": (197e12, 16e9), "v5e": (197e12, 16e9),
    "v5p": (459e12, 95e9),
    "v4": (275e12, 32e9),
    "v6 lite": (918e12, 32e9), "v6e": (918e12, 32e9),
    "v3": (123e12, 16e9),
    "v2": (45e12, 8e9),
    "v5": (459e12, 95e9),
}


def chip_spec(device_kind: str):
    kind = device_kind.lower()
    for key, val in CHIP_TABLE.items():
        if key in kind:
            return val
    raise ValueError(
        f"bench: device kind {device_kind!r} is not in CHIP_TABLE; add its "
        "published peak and HBM size there rather than assuming one")


def candidate_ladder(hbm_bytes: float):
    """Descending ladder of (hidden, ffn, layers, vocab, heads, kv, batch, seq).

    State bytes/param on the fused step path: fp32 master + Adam m/v (12) +
    fp32 grad accumulator (4) + transient bf16 cast (2) = ~18. Each rung keeps
    18*params plus a logits/activation estimate within ~80% of HBM; the
    subprocess trial is still the ground truth.
    """
    if hbm_bytes >= 90e9:      # v5p-class
        ladder = [
            (4096, 14336, 16, 32768, 32, 8, 8, 2048),
            (4096, 14336, 12, 32768, 32, 8, 8, 2048),
            (2048, 5632, 16, 32768, 16, 8, 8, 2048),
        ]
    elif hbm_bytes >= 30e9:    # v4 / v6e-class
        ladder = [
            (2048, 5632, 16, 32768, 16, 8, 8, 2048),
            (2048, 5632, 12, 32768, 16, 8, 8, 2048),
            (2048, 5632, 8, 32768, 16, 8, 8, 2048),
        ]
    else:                      # 16 GB-class (v5e, v3)
        ladder = [
            (2048, 5632, 8, 32768, 16, 8, 8, 2048),
            (2048, 5632, 8, 32768, 16, 8, 4, 2048),
            (2048, 5632, 6, 32768, 16, 8, 4, 2048),
            (1536, 4096, 8, 32768, 16, 8, 4, 2048),
        ]
    ladder.append((1024, 2816, 6, 16384, 16, 8, 4, 1024))  # safety net
    return ladder


def _child_error(reason, proc=None, flag=None):
    """Structured child-process failure record: every trial/bench failure
    carries the child's rc + tail stderr instead of an opaque string.
    Serializable — top-level failures emit it under an ``"error"`` key in
    the JSON output."""
    err = {"reason": reason, "rc": None, "stderr": ""}
    if flag:
        err["flag"] = flag
    if proc is not None:
        err["rc"] = proc.returncode
        err["stderr"] = (proc.stderr or proc.stdout or "")[-2000:]
    return err


def _err_text(err):
    """Human-readable rendering of a _child_error dict (or legacy string)."""
    if isinstance(err, dict):
        head = f"reason={err.get('reason')} rc={err.get('rc')}"
        if err.get("flag"):
            head += f" flag={err['flag']}"
        tail = err.get("stderr") or ""
        return head + ("\n" + tail if tail else "")
    return str(err)


def _fail_json(err):
    """Emit the structured error as the bench's JSON line (stdout) so
    automation parses a real ``error`` field instead of grepping stderr."""
    print(json.dumps(
        {"error": err if isinstance(err, dict) else {"reason": str(err)}}))


def run_trial_subprocess(cfg_tuple, steps: int, timeout: float = 900.0,
                         zero_stage: int | None = None):
    env = dict(os.environ)
    hidden, ffn, layers, vocab, heads, kv, batch, seq = cfg_tuple
    env.update(
        BENCH_TRIAL="1",
        BENCH_HIDDEN=str(hidden), BENCH_FFN=str(ffn), BENCH_LAYERS=str(layers),
        BENCH_VOCAB=str(vocab), BENCH_HEADS=str(heads), BENCH_KV=str(kv),
        BENCH_BATCH=str(batch), BENCH_SEQ=str(seq), BENCH_STEPS=str(steps),
    )
    if zero_stage is not None:  # else the operator's BENCH_STAGE (if any) pins it
        env["BENCH_STAGE"] = str(zero_stage)
    try:
        proc = subprocess.run(
            [sys.executable, os.path.abspath(__file__)],
            env=env, capture_output=True, text=True, timeout=timeout,
            cwd=os.path.dirname(os.path.abspath(__file__)),
        )
    except subprocess.TimeoutExpired:
        return None, _child_error(f"trial timed out after {timeout:g}s")
    if proc.returncode != 0:
        return None, _child_error("trial child exited nonzero", proc)
    for line in reversed(proc.stdout.strip().splitlines()):
        if line.startswith("{"):
            try:
                return json.loads(line), None
            except json.JSONDecodeError:
                continue
    return None, _child_error("no JSON in trial output", proc)


def trial_main():
    """Child process: build the engine from env, time steps, print one JSON line."""
    import numpy as np
    import jax

    import deepspeed_tpu
    from deepspeed_tpu.models import llama

    e = os.environ
    model_cfg = llama.LlamaConfig(
        vocab_size=int(e["BENCH_VOCAB"]),
        hidden_size=int(e["BENCH_HIDDEN"]),
        intermediate_size=int(e["BENCH_FFN"]),
        num_layers=int(e["BENCH_LAYERS"]),
        num_heads=int(e["BENCH_HEADS"]),
        num_kv_heads=int(e["BENCH_KV"]),
        max_seq_len=int(e["BENCH_SEQ"]),
    )
    seq, batch, steps = int(e["BENCH_SEQ"]), int(e["BENCH_BATCH"]), int(e["BENCH_STEPS"])
    stage = int(e.get("BENCH_STAGE", "0"))

    # stage 3 shards over fsdp: claim every device for it (on a single chip
    # the plan degenerates to stage 0 — real sharding overhead needs a pod)
    n_dev = len(jax.devices())
    mesh = {"data": 1, "fsdp": n_dev} if stage >= 3 and n_dev > 1 else {"data": -1}
    config = {
        "train_micro_batch_size_per_device": batch,
        "gradient_accumulation_steps": 1,
        "steps_per_print": 0,
        "gradient_clipping": 1.0,
        "sequence_length": seq,
        "optimizer": {"type": "adamw", "params": {"lr": 1e-4}},
        "zero_optimization": {"stage": stage},
        "mesh": mesh,
        "activation_checkpointing": {
            "enabled": e.get("BENCH_REMAT", "1") == "1",
            "policy": e.get("BENCH_REMAT_POLICY", "dots_saveable"),
        },
    }
    if e.get("BENCH_TILED_LOGITS") == "1":
        # ALST tiled logits loss: trades the [B*S, V] logits buffer for
        # tiled compute — frees HBM for larger batches
        config["sequence_parallel"] = {
            "tiled_logits": True,
            "tile_size": int(e.get("BENCH_TILE", "2048")),
        }
    # every bench run doubles as a telemetry fixture: step spans, HBM
    # watermarks, and the final registry snapshot land in a JSONL under
    # runs/ (gitignored; docs/OBSERVABILITY.md)
    tel_path = e.get("BENCH_TELEMETRY_JSONL", os.path.join(
        os.path.dirname(os.path.abspath(__file__)), "runs",
        "BENCH_telemetry.jsonl"))
    config["telemetry"] = {"enabled": True, "jsonl_path": tel_path}
    engine, _, _, _ = deepspeed_tpu.initialize(
        # remat/policy inherit from the config via ShardCtx (single source)
        model=lambda ctx: llama.build(model_cfg, ctx=ctx),
        config=config,
    )

    rng = np.random.default_rng(0)

    def make_batch():
        return {"input_ids": rng.integers(0, model_cfg.vocab_size, (batch, seq), dtype=np.int32)}

    # settle via value fetch (a fetched scalar waits for the step)
    float(engine.train_batch(make_batch()))  # compile
    float(engine.train_batch(make_batch()))  # warm
    t0 = time.perf_counter()
    for _ in range(steps):
        loss = engine.train_batch(make_batch())
    loss = float(loss)  # steps dispatch async; settle before timing
    elapsed = time.perf_counter() - t0

    tokens_per_s = steps * batch * seq / elapsed
    flops_per_token = llama.flops_per_token(model_cfg, seq)
    # an MFU needs a listed chip: chip_spec raises on anything else
    peak, _ = chip_spec(jax.devices()[0].device_kind)
    mfu = tokens_per_s * flops_per_token / peak
    from deepspeed_tpu import telemetry

    telemetry.TELEMETRY.close()  # appends the final registry snapshot record
    print(json.dumps({
        "metric": "llama_train_mfu_single_chip",
        "zero_stage": stage,
        "value": round(mfu, 4),
        "unit": "fraction_of_peak",
        "vs_baseline": round(mfu / 0.45, 4),
        "tokens_per_s": round(tokens_per_s, 1),
        "model_params": llama.num_params(model_cfg),
        "seq_len": seq,
        "batch": batch,
        "final_loss": round(loss, 4),
        "device": str(jax.devices()[0].device_kind),
        "backend": jax.default_backend(),
        "telemetry_jsonl": tel_path,
    }))


def serve_trial_main():
    """Child process: mixed prefill/decode serving throughput — the ragged
    continuous-batching engine vs (a) the dense padded-batch engine and (b) a
    naive per-request loop, same model + workload for all three.

    Reference bar: FastGen's 2.3x effective throughput vs padded serving
    (``blogs/deepspeed-fastgen/README.md:28``). Useful tokens (prompt +
    generated) are identical across systems; only wall time differs.
    Prints one JSON line of serving metrics.
    """
    import numpy as np
    import jax

    from deepspeed_tpu.inference.engine import InferenceEngine
    from deepspeed_tpu.inference.ragged import RaggedConfig, RaggedInferenceEngine
    from deepspeed_tpu.models import llama

    e = os.environ
    on_tpu = jax.default_backend() == "tpu"
    if on_tpu:
        model_cfg = llama.LlamaConfig(
            vocab_size=int(e.get("BENCH_VOCAB", 32768)),
            hidden_size=int(e.get("BENCH_HIDDEN", 2048)),
            intermediate_size=int(e.get("BENCH_FFN", 5632)),
            num_layers=int(e.get("BENCH_LAYERS", 8)),
            num_heads=int(e.get("BENCH_HEADS", 16)),
            num_kv_heads=int(e.get("BENCH_KV", 8)),
            max_seq_len=1024,
        )
        n_req, max_new, max_prompt = 32, 48, 512
        prompt_lens = [64, 128, 256, 512]
        # budget/max_seqs sized so the whole load admits in one wave and
        # prefill takes few dispatches (what a host dispatch costs on a
        # local chip is not measured on today's code)
        max_seqs, budget, block, tile = 32, 1024, 32, 128
    else:
        model_cfg = llama.LlamaConfig(
            vocab_size=512, hidden_size=256, intermediate_size=688,
            num_layers=2, num_heads=4, num_kv_heads=2, max_seq_len=256,
        )
        n_req, max_new, max_prompt = 6, 8, 64
        prompt_lens = [16, 32, 64]
        max_seqs, budget, block, tile = 4, 64, 16, 16

    # request-lifecycle spans (queue wait, TTFT, per-token decode latency,
    # preemptions) for every ragged request in this trial
    from deepspeed_tpu import telemetry

    tel_path = e.get("BENCH_TELEMETRY_JSONL", os.path.join(
        os.path.dirname(os.path.abspath(__file__)), "runs",
        "BENCH_serve_telemetry.jsonl"))
    telemetry.configure(enabled=True, jsonl_path=tel_path)

    rng = np.random.default_rng(0)
    lens = [int(prompt_lens[i % len(prompt_lens)]) for i in range(n_req)]
    rng.shuffle(lens)
    prompts = [rng.integers(0, model_cfg.vocab_size, (L,), dtype=np.int32)
               for L in lens]
    useful_tokens = sum(lens) + n_req * max_new

    mbs = -(-(max_prompt + max_new) // block)
    rcfg = RaggedConfig(
        max_tokens_per_step=budget, max_seqs=max_seqs, block_size=block,
        num_blocks=max_seqs * mbs + 1, max_blocks_per_seq=mbs,
        # tiled prefill: one KV-block fetch per tile instead of per token
        # (the per-token decode kernel is O(context) DMA per token,
        # ~tile x redundant on prefill chunks)
        prefill_tile=int(e.get("BENCH_PREFILL_TILE", tile)),
    )
    ragged = RaggedInferenceEngine(
        model=lambda ctx: llama.build(model_cfg, ctx=ctx),
        ragged_config=rcfg, seed=0,
    )
    # turns the persistent compile cache on (compiles nothing itself)
    t0 = time.perf_counter()
    nwarm = ragged.warmup()
    print(f"# ragged warmup: {nwarm} programs in "
          f"{time.perf_counter() - t0:.1f}s", file=sys.stderr)

    def run_ragged():
        for i, p in enumerate(prompts):
            ragged.put(("r", i), p, max_new_tokens=max_new)
        out = ragged.generate_all()
        assert all(len(v) == max_new for v in out.values())

    # warmup: one full untimed pass compiles every bucket size the workload
    # hits (jit specializes per token-batch bucket)
    run_ragged()
    t0 = time.perf_counter()
    run_ragged()
    ragged_s = time.perf_counter() - t0

    dense = InferenceEngine(
        model=lambda ctx: llama.build(model_cfg, ctx=ctx), seed=0)

    def pad_batch(batch_prompts):
        out = np.zeros((len(batch_prompts), max_prompt), np.int32)
        for i, p in enumerate(batch_prompts):
            out[i, :len(p)] = p  # left-aligned; generation timing unaffected
        return out

    def run_dense():
        # padded static batches of max_seqs (the v1-engine serving shape)
        for i in range(0, n_req, max_seqs):
            dense.generate(pad_batch(prompts[i:i + max_seqs]),
                           max_new_tokens=max_new)

    run_dense()  # warm: compiles every batch shape incl. the partial tail
    t0 = time.perf_counter()
    run_dense()
    dense_s = time.perf_counter() - t0

    def run_naive():
        # one request at a time, padded to the max prompt (single compile)
        for p in prompts:
            dense.generate(pad_batch([p]), max_new_tokens=max_new)

    dense.generate(pad_batch([prompts[0]]), max_new_tokens=max_new)  # compile
    t0 = time.perf_counter()
    run_naive()
    naive_s = time.perf_counter() - t0

    sched = ragged.tokens_scheduled + ragged.tokens_padded

    # ------------------------------------------------- staggered arrivals
    # The FastGen effective-throughput scenario: requests ARRIVE over time.
    # Dense serving must run wave-by-wave (whoever has arrived pads into a
    # full batch and later arrivals wait out the whole generation);
    # continuous batching admits mid-flight. Latency = finish - arrival.
    interval = (0.15 if on_tpu else 0.5)  # seconds between arrivals
    arrivals = [i * interval for i in range(n_req)]

    def run_ragged_staggered(tag):
        lat = {}
        t0 = time.perf_counter()
        nxt = 0
        while nxt < n_req or ragged.has_work:
            now = time.perf_counter() - t0
            while nxt < n_req and arrivals[nxt] <= now:
                ragged.put((tag, nxt), prompts[nxt], max_new_tokens=max_new)
                nxt += 1
            if ragged.has_work:
                done_before = ragged.finished_uids
                ragged.step()
                for uid in ragged.finished_uids - done_before:
                    lat[uid] = (time.perf_counter() - t0) - arrivals[uid[1]]
            elif nxt < n_req:
                time.sleep(max(0.0, arrivals[nxt] - (time.perf_counter() - t0)))
        return lat

    def run_dense_staggered():
        lat = {}
        t0 = time.perf_counter()
        nxt = 0
        while nxt < n_req:
            now = time.perf_counter() - t0
            if arrivals[nxt] > now:
                time.sleep(arrivals[nxt] - now)
            now = time.perf_counter() - t0
            wave = []
            while nxt < n_req and arrivals[nxt] <= now and len(wave) < max_seqs:
                wave.append(nxt)
                nxt += 1
            # always the warmed full-batch program: a per-wave-size program
            # would compile inside the timed region, and the full-batch
            # padding IS dense serving's cost under continuous load
            batch = pad_batch([prompts[i] for i in wave]
                              + [prompts[0]] * (max_seqs - len(wave)))
            dense.generate(batch, max_new_tokens=max_new)
            fin = time.perf_counter() - t0
            for i in wave:
                lat[i] = fin - arrivals[i]
        return lat

    run_ragged_staggered("w")  # warm: compiles the staggered-mix programs
    disp0, tok0 = ragged.dispatch_count, ragged.tokens_emitted
    rag_lat = list(run_ragged_staggered("s").values())
    stag_dispatches = ragged.dispatch_count - disp0
    stag_generated = ragged.tokens_emitted - tok0
    den_lat = list(run_dense_staggered().values())
    rag_mean = sum(rag_lat) / len(rag_lat)
    den_mean = sum(den_lat) / len(den_lat)
    telemetry.TELEMETRY.close()
    print(json.dumps({
        "ragged_tokens_per_s": round(useful_tokens / ragged_s, 1),
        "dense_tokens_per_s": round(useful_tokens / dense_s, 1),
        "naive_tokens_per_s": round(useful_tokens / naive_s, 1),
        "ragged_vs_dense": round(dense_s / ragged_s, 3),
        "ragged_vs_naive": round(naive_s / ragged_s, 3),
        "ragged_padding_frac": round(ragged.tokens_padded / max(sched, 1), 4),
        # staggered-arrival (continuous) load: mean per-request latency and
        # the dense/ragged ratio — >1 means continuous batching wins.
        # Mixed prefill/decode steps emit ~1 token/seq/dispatch while the
        # dense baseline amortizes a whole wave into one scan, so the ratio
        # moves with what one host dispatch costs.
        "staggered_ragged_mean_latency_s": round(rag_mean, 3),
        "staggered_dense_mean_latency_s": round(den_mean, 3),
        "staggered_latency_ratio": round(den_mean / rag_mean, 3),
        # dispatch economy under continuous load (the round-4 target:
        # <= 0.25 dispatches per generated token)
        "staggered_dispatches": stag_dispatches,
        "staggered_dispatches_per_token": round(
            stag_dispatches / max(stag_generated, 1), 4),
        "serve_reqs": n_req,
        "serve_useful_tokens": useful_tokens,
        "serve_max_new": max_new,
        "telemetry_jsonl": tel_path,
    }))


def train_anatomy_main():
    """Child process: training step anatomy (telemetry/stepscope.py).

    Runs a short training loop with stepscope enabled — per-step phase
    decomposition (data wait / H2D / forward / backward / grad collectives /
    optimizer / recompile / checkpoint stall), MFU attribution, overlap
    fraction and goodput — and emits the full breakdown as one JSON line so
    BENCH_r0x records track overlap/goodput alongside MFU (ROADMAP item #4's
    measurement harness). Also exports the step→phase trace and reports span
    counts plus the scrape-visibility of the headline gauges, which the CI
    smoke step asserts on.
    """
    import tempfile

    import jax
    import numpy as np

    import deepspeed_tpu
    from deepspeed_tpu.models import llama
    from deepspeed_tpu.telemetry import TELEMETRY

    e = os.environ
    model_cfg = llama.LlamaConfig(
        vocab_size=int(e.get("BENCH_ANATOMY_VOCAB", 512)),
        hidden_size=int(e.get("BENCH_ANATOMY_HIDDEN", 128)),
        intermediate_size=int(e.get("BENCH_ANATOMY_FFN", 256)),
        num_layers=int(e.get("BENCH_ANATOMY_LAYERS", 2)),
        num_heads=int(e.get("BENCH_ANATOMY_HEADS", 4)),
        num_kv_heads=int(e.get("BENCH_ANATOMY_KV", 2)),
        max_seq_len=int(e.get("BENCH_ANATOMY_SEQ", 128)),
    )
    seq = int(e.get("BENCH_ANATOMY_SEQ", 128))
    steps = int(e.get("BENCH_ANATOMY_STEPS", 8))
    gas = int(e.get("BENCH_ANATOMY_GAS", 2))
    # default batch covers gas x dp (8 simulated devices on the CPU backend)
    batch = int(e.get("BENCH_ANATOMY_BATCH",
                      max(8, gas * jax.device_count())))
    # device-capture window every N steps (0 disables); the default lands
    # one window inside the default step budget, past warmup/compile
    profile_interval = int(e.get("BENCH_ANATOMY_PROFILE_INTERVAL", 4))

    runs_dir = os.path.join(os.path.dirname(os.path.abspath(__file__)), "runs")
    os.makedirs(runs_dir, exist_ok=True)
    config = {
        "train_batch_size": batch,
        "gradient_accumulation_steps": gas,
        "sequence_length": seq,
        "steps_per_print": 0,
        "optimizer": {"type": "adamw", "params": {"lr": 1e-3}},
        "zero_optimization": {"stage": 0},
        "mesh": {"data": -1},
        "telemetry": {
            "enabled": True,
            "jsonl_path": os.path.join(runs_dir,
                                       "BENCH_train_anatomy_telemetry.jsonl"),
            "stepscope": {
                "enabled": True,
                "profile_interval_steps": profile_interval,
                "profile_dir": os.path.join(runs_dir, "devprof"),
            },
        },
    }
    def run_leg(overlap_on: bool, checkpoint: bool = False):
        """One training leg: identical data/seed, grad_overlap toggled.

        Returns the stepscope summary, devprof capture, final params and the
        per-leg overlap gauges — the off leg is the fused baseline the on
        leg's parity and latency-hiding claims are measured against."""
        from deepspeed_tpu.comm.topology import reset_topology

        reset_topology()
        # fresh trace ring + registry per leg: the exported trace and the
        # scrape asserts below see only the on leg's spans/gauges
        TELEMETRY.reset()
        leg_cfg = json.loads(json.dumps(config))
        # the overlap path needs a data axis to reduce over; single-device
        # runs degrade to an off-vs-off A/B (parity trivially exact)
        if overlap_on and jax.device_count() > 1:
            leg_cfg["zero_optimization"]["grad_overlap"] = {
                "enabled": True,
                "bucket_bytes": int(e.get("BENCH_ANATOMY_BUCKET_BYTES",
                                          4 << 20)),
            }
        engine, _, _, _ = deepspeed_tpu.initialize(
            model=lambda ctx: llama.build(model_cfg, ctx=ctx), config=leg_cfg)

        rng = np.random.default_rng(0)

        def data_iter():
            while True:
                yield {"input_ids": rng.integers(
                    0, model_cfg.vocab_size,
                    (batch // gas, seq), dtype=np.int32)}

        it = data_iter()
        for _ in range(steps):
            engine.train_batch(data_iter=it)
        if checkpoint:
            # one checkpoint save so the goodput ledger has a checkpoint entry
            with tempfile.TemporaryDirectory() as ckpt_dir:
                engine.save_checkpoint(ckpt_dir)
        summary = engine.stepscope.summary()
        devprof_last = engine.devprof_last
        devprof_summary = (devprof_last or {}).get("summary")
        phase_totals = summary.get("phase_seconds_total") or {}
        total_phase = sum(phase_totals.values()) or 1.0
        leg = {
            "summary": summary,
            "devprof_last": devprof_last,
            "devprof_summary": devprof_summary,
            "params": jax.tree_util.tree_map(np.asarray, engine.params),
            "overlap_fraction_estimate": summary.get("overlap_fraction"),
            "overlap_fraction_measured":
                (devprof_summary or {}).get("overlap_fraction_measured"),
            # ZeRO-1 sharded update: the optimizer phase share should SHRINK
            # on the on leg (each rank updates 1/dp of every bucket)
            "optimizer_phase_share":
                phase_totals.get("optimizer", 0.0) / total_phase,
            # per-bucket wire time: the devprof families feeding the
            # devprof_collective_seconds{op=} histogram
            "collective_wire": [
                {"op": c.get("op"), "seconds": c.get("seconds"),
                 "count": c.get("count")}
                for c in (devprof_summary or {}).get("collectives", [])],
        }
        return engine, leg

    # leg A: fused baseline (overlap off); leg B: bucketed async overlap.
    # Same seed, same data stream — leg B's params must stay inside the
    # documented fp-reorder bound of leg A's.
    off_engine, leg_off = run_leg(overlap_on=False)
    off_engine.destroy()
    engine, leg_on = run_leg(overlap_on=True, checkpoint=True)

    parity_drift = max(
        float(np.max(np.abs(a - b)))
        for a, b in zip(jax.tree_util.tree_leaves(leg_off["params"]),
                        jax.tree_util.tree_leaves(leg_on["params"])))
    # documented fp-reorder bound (ring sum order + local-mean-then-pmean;
    # docs/TP_OVERLAP.md "grad-sync overlap") at bf16 compute precision
    parity_ok = parity_drift < float(e.get("BENCH_ANATOMY_PARITY_TOL", 5e-3))

    summary = leg_on["summary"]
    devprof_last = leg_on["devprof_last"]
    devprof_summary = leg_on["devprof_summary"]
    measured_overlap = leg_on["overlap_fraction_measured"]

    trace_path = os.path.join(runs_dir, "BENCH_train_anatomy_trace.json")
    trace = TELEMETRY.dump_trace(trace_path)
    events = trace.get("traceEvents", [])
    step_spans = [ev for ev in events if ev.get("name") == "train/step"]
    step_ids = {ev.get("args", {}).get("span_id") for ev in step_spans}
    phase_spans = [ev for ev in events
                   if str(ev.get("name", "")).startswith("train/phase/")]
    nested = [ev for ev in phase_spans
              if ev.get("args", {}).get("parent_id") in step_ids]
    phase_ids = {ev.get("args", {}).get("span_id") for ev in phase_spans}
    host_ids = step_ids | phase_ids
    device_spans = [ev for ev in events
                    if str(ev.get("name", "")).startswith("device/")]
    device_nested = [ev for ev in device_spans
                     if ev.get("args", {}).get("parent_id") in host_ids]
    prom = TELEMETRY.registry.render_prometheus()

    engine.destroy()
    print(json.dumps({
        "error": None,
        "anatomy": summary,
        "steps": steps,
        "train_batch_size": batch,
        "gas": gas,
        "overlap_fraction_estimate": summary.get("overlap_fraction"),
        "overlap_fraction_measured": measured_overlap,
        # A/B overlap anatomy: fused baseline (off) vs bucketed async
        # grad collectives + sharded update (on), same seed and data
        "overlap": {
            "enabled": jax.device_count() > 1,
            "parity_max_drift": parity_drift,
            "parity_ok": parity_ok,
            "off": {
                "overlap_fraction_estimate":
                    leg_off["overlap_fraction_estimate"],
                "overlap_fraction_measured":
                    leg_off["overlap_fraction_measured"],
                "optimizer_phase_share": leg_off["optimizer_phase_share"],
                "collective_wire": leg_off["collective_wire"],
            },
            "on": {
                "overlap_fraction_estimate":
                    leg_on["overlap_fraction_estimate"],
                "overlap_fraction_measured":
                    leg_on["overlap_fraction_measured"],
                "optimizer_phase_share": leg_on["optimizer_phase_share"],
                "collective_wire": leg_on["collective_wire"],
            },
        },
        "devprof": {
            "enabled": profile_interval > 0,
            "summary": devprof_summary,
            "merged_spans": (devprof_last or {}).get("merged_spans", 0),
            "op_count": (devprof_summary or {}).get("op_count", 0),
        },
        "trace_path": trace_path,
        "trace_step_spans": len(step_spans),
        "trace_phase_spans": len(phase_spans),
        "trace_nested_phase_spans": len(nested),
        "trace_device_spans": len(device_spans),
        "trace_nested_device_spans": len(device_nested),
        "scrape_has_overlap": "train_overlap_fraction" in prom,
        "scrape_has_estimate_overlap":
            'train_overlap_fraction{source="estimate"}' in prom,
        "scrape_has_measured_overlap":
            'train_overlap_fraction{source="measured"}' in prom,
        "scrape_has_devprof_capture": "devprof_captures_total" in prom,
        "scrape_has_goodput": "train_goodput" in prom,
        "scrape_has_phase_histogram": "step_phase_seconds" in prom,
        "scrape_has_flops_source": "train_flops_source" in prom,
    }))
    return 0


def run_train_anatomy_subprocess(timeout: float = 900.0):
    # the overlap A/B needs a data axis: on the CPU backend simulate the
    # 8-device mesh (tests/conftest.py's strategy); real accelerators keep
    # their native device count
    extra = None
    flags = os.environ.get("XLA_FLAGS", "")
    if (os.environ.get("JAX_PLATFORMS") == "cpu"
            and "xla_force_host_platform_device_count" not in flags):
        extra = {"XLA_FLAGS":
                 (flags + " --xla_force_host_platform_device_count=8").strip()}
    return _run_flagged_subprocess("BENCH_TRAIN_ANATOMY", timeout,
                                   extra_env=extra)


def infinity_trial_main():
    """Child process: ZeRO-Infinity offload rung — train a model whose fp32
    training state EXCEEDS the chip's HBM (params + Adam moments + grads),
    only possible because master params/optimizer state live in pinned host
    DRAM and stream through HBM per scanned layer / per optimizer sub-group
    (runtime/param_offload.py; round-4 item 1 'done' criterion). Prints one
    JSON line of offload metrics."""
    import numpy as np
    import jax
    import jax.numpy as jnp

    import deepspeed_tpu
    from deepspeed_tpu.comm.topology import reset_topology
    from deepspeed_tpu.models import llama

    on_tpu = jax.default_backend() == "tpu"
    if on_tpu:
        _, hbm = chip_spec(getattr(jax.devices()[0], "device_kind", ""))
        # ~1.15B params: fp32 state = params(4) + m(4) + v(4) + grads(4)
        # = 16 bytes/param = 18.4 GB > the 16 GB-class chip this runs on
        # (on bigger chips the claim is still reported, just not exceeded)
        model_cfg = llama.LlamaConfig(
            vocab_size=8192, hidden_size=2048, intermediate_size=5504,
            num_layers=24, num_heads=16, num_kv_heads=8, max_seq_len=512)
        batch_sz, seq = 2, 512
    else:
        hbm = 16e9
        model_cfg = llama.LlamaConfig(
            vocab_size=512, hidden_size=128, intermediate_size=344,
            num_layers=4, num_heads=4, num_kv_heads=2, max_seq_len=128)
        batch_sz, seq = 2, 64
    reset_topology()
    engine, _, _, _ = deepspeed_tpu.initialize(
        model=lambda ctx: llama.build(model_cfg, ctx=ctx),
        config={
            "train_micro_batch_size_per_device": batch_sz,
            "gradient_accumulation_steps": 1, "steps_per_print": 0,
            "optimizer": {"type": "adamw", "params": {"lr": 1e-4}},
            "zero_optimization": {
                "stage": 3, "sub_group_size": 100_000_000,
                "offload_param": {"device": "cpu"},
                "offload_optimizer": {"device": "cpu"}},
            "activation_checkpointing": {"enabled": True},
            "mesh": {"data": 1, "fsdp": 1}, "seed": 7,
        }, seed=7)
    n_params = engine.model_spec.num_params
    state_bytes = n_params * 16
    rng = np.random.default_rng(0)

    def make_batch():
        return {"input_ids": rng.integers(
            0, model_cfg.vocab_size, (batch_sz, seq), dtype=np.int32)}

    l0 = float(engine.train_batch(make_batch()))  # compile
    t0 = time.perf_counter()
    l1 = float(engine.train_batch(make_batch()))
    jax.block_until_ready(engine.params)
    step_s = time.perf_counter() - t0
    # device footprint of the fwd/bwd program: host args hold the masters
    dev_arg = host_arg = -1
    try:
        if engine._grads_jit is None:
            engine._grads_jit = engine._build_grads_fn()
        db = engine._put_gas_batch(make_batch())
        ma = engine._grads_jit.lower(
            engine.params, engine.scale_state, jnp.int32(0),
            engine._train_rng, db).compile().memory_analysis()
        dev_arg = int(ma.argument_size_in_bytes)
        host_arg = int(ma.host_argument_size_in_bytes)
    except Exception:
        pass
    print(json.dumps({
        "infinity_params": n_params,
        "infinity_state_gb": round(state_bytes / 2**30, 1),
        "infinity_hbm_gb": round(hbm / 2**30, 1),
        "infinity_state_exceeds_hbm": bool(state_bytes > hbm),
        "infinity_step_s": round(step_s, 2),
        "infinity_loss_finite": bool(np.isfinite(l0) and np.isfinite(l1)),
        "infinity_device_arg_bytes": dev_arg,
        "infinity_host_arg_bytes": host_arg,
    }))


def run_infinity_subprocess(timeout: float = 900.0):
    return _run_flagged_subprocess("BENCH_INFINITY", timeout)


def learn_trial_main():
    """Child process: learning-evidence rung — byte-level LM on real text
    (this repo's own source corpus; the environment has no network egress, so
    a local natural-text corpus approximates BASELINE.json's loss-curve-parity
    bar within this sandbox). ~50 steps must show clear descent: the MFU
    headline ships with evidence the step actually learns, not just runs.
    Prints one JSON line of learning metrics.
    """
    import numpy as np
    import jax

    import deepspeed_tpu
    from deepspeed_tpu.models import llama

    on_tpu = jax.default_backend() == "tpu"
    here = os.path.dirname(os.path.abspath(__file__))
    chunks = []
    for root, _, files in sorted(os.walk(os.path.join(here, "deepspeed_tpu"))):
        for f in sorted(files):
            if f.endswith(".py"):
                with open(os.path.join(root, f), "rb") as fh:
                    chunks.append(fh.read())
    corpus = np.frombuffer(b"\n".join(chunks), np.uint8).astype(np.int32)

    if on_tpu:
        model_cfg = llama.LlamaConfig(
            vocab_size=256, hidden_size=384, intermediate_size=1024,
            num_layers=6, num_heads=6, num_kv_heads=6, max_seq_len=512)
        steps, batch, seq = 50, 32, 512
    else:
        model_cfg = llama.LlamaConfig(
            vocab_size=256, hidden_size=128, intermediate_size=344,
            num_layers=2, num_heads=4, num_kv_heads=2, max_seq_len=128)
        steps, batch, seq = 20, 8, 128

    config = {
        "train_micro_batch_size_per_device": batch,
        "gradient_accumulation_steps": 1,
        "steps_per_print": 0,
        "gradient_clipping": 1.0,
        "sequence_length": seq,
        "optimizer": {"type": "adamw", "params": {"lr": 3e-4,
                                                  "weight_decay": 0.01}},
        "scheduler": {"type": "WarmupLR",
                      "params": {"warmup_max_lr": 3e-4, "warmup_num_steps": 10}},
        "mesh": {"data": -1},
    }
    engine, _, _, _ = deepspeed_tpu.initialize(
        model=lambda ctx: llama.build(model_cfg, ctx=ctx), config=config)

    rng = np.random.default_rng(1)

    def make_batch():
        starts = rng.integers(0, len(corpus) - seq - 1, batch)
        return {"input_ids": np.stack([corpus[s:s + seq] for s in starts])}

    losses = [float(engine.train_batch(make_batch())) for _ in range(steps)]
    initial = float(np.mean(losses[:3]))
    final = float(np.mean(losses[-3:]))
    print(json.dumps({
        "learn_initial_loss": round(initial, 4),
        "learn_final_loss": round(final, 4),
        "learn_steps": steps,
        "learn_corpus_bytes": int(len(corpus)),
        # pass bar: clear descent on real text (random-init byte LM starts
        # near ln(256)=5.55; structure should cut it well under 70% by ~50
        # steps at this scale)
        "learn_pass": bool(final < 0.7 * initial),
    }))


def _run_flagged_subprocess(env_flag: str, timeout: float = 900.0,
                            extra_env: dict | None = None):
    """Re-exec this file with ``env_flag=1`` and parse the trailing JSON line
    (the serve/learn trial pattern; run_trial_subprocess builds its env from
    shape vars so it stays separate)."""
    env = dict(os.environ)
    env[env_flag] = "1"
    if extra_env:
        env.update(extra_env)
    try:
        proc = subprocess.run(
            [sys.executable, os.path.abspath(__file__)],
            env=env, capture_output=True, text=True, timeout=timeout,
            cwd=os.path.dirname(os.path.abspath(__file__)),
        )
    except subprocess.TimeoutExpired:
        return None, _child_error(f"timed out after {timeout:g}s",
                                  flag=env_flag)
    if proc.returncode != 0:
        return None, _child_error("child exited nonzero", proc, flag=env_flag)
    for line in reversed(proc.stdout.strip().splitlines()):
        if line.startswith("{"):
            try:
                return json.loads(line), None
            except json.JSONDecodeError:
                continue
    return None, _child_error(f"no JSON in {env_flag} output", proc,
                              flag=env_flag)


def run_learn_subprocess(timeout: float = 900.0):
    return _run_flagged_subprocess("BENCH_LEARN", timeout)


def run_serve_subprocess(timeout: float = 900.0):
    return _run_flagged_subprocess("BENCH_SERVE", timeout)


def serving_bench_main():
    """Child process: the full serving tier under open-loop Poisson load.

    Where serve_trial_main measures the *engine* (closed workload, direct
    ``put()``/``generate_all()``), this drives the whole stack a deployment
    would run — HTTP frontend → router admission → EngineLoop → ragged
    engine — with a Poisson open-loop client (arrivals don't wait for
    completions, the standard serving-bench discipline: closed-loop clients
    hide queueing collapse). Reports the latencies a user would see:
    p50/p99 TTFT, per-token decode latency, rejected-request rate (429s),
    and goodput (useful tokens/s over wall time). One JSON line out.
    """
    import http.client
    import threading

    import numpy as np
    import jax

    from deepspeed_tpu import telemetry
    from deepspeed_tpu.inference.ragged import RaggedConfig, RaggedInferenceEngine
    from deepspeed_tpu.models import llama
    from deepspeed_tpu.serving import RouterConfig, build_server

    e = os.environ
    on_tpu = jax.default_backend() == "tpu"
    if on_tpu:
        model_cfg = llama.LlamaConfig(
            vocab_size=32768, hidden_size=2048, intermediate_size=5632,
            num_layers=8, num_heads=16, num_kv_heads=8, max_seq_len=1024)
        n_req, max_new, rate = 48, 48, 8.0
        prompt_lens = [64, 128, 256, 512]
        max_seqs, budget, block, tile = 32, 1024, 32, 128
        max_prompt = 512
    else:
        model_cfg = llama.LlamaConfig(
            vocab_size=512, hidden_size=256, intermediate_size=688,
            num_layers=2, num_heads=4, num_kv_heads=2, max_seq_len=256)
        n_req, max_new, rate = 10, 8, 4.0
        prompt_lens = [16, 32, 64]
        max_seqs, budget, block, tile = 4, 64, 16, 16
        max_prompt = 64
    n_req = int(e.get("BENCH_SERVING_REQUESTS", n_req))
    rate = float(e.get("BENCH_SERVING_RATE", rate))  # arrivals per second
    # shared-prefix workload (--shared-prefix-tokens): every prompt opens
    # with the same N tokens (system prompt / few-shot template traffic) and
    # the engine runs with the block-level prefix cache on — after the first
    # request retires, later prefills splice the shared blocks instead of
    # recomputing them
    shared_prefix = int(e.get("BENCH_SERVING_SHARED_PREFIX", 0))
    # tiered KV cache (--kv-tier): shrink the HBM pool so the shared-prefix
    # working set overflows it by >=3x, and let the engine demote evicted
    # prefix blocks host-ward instead of dropping them (docs/SERVING.md)
    kv_tier = e.get("BENCH_SERVING_KV_TIER", "") not in ("", "0")
    # low-bit KV serving (--kv-quant): the tiered workload with the pool,
    # tier payloads, prefix splices and handoffs all running the named
    # codec (docs/SERVING.md "Low-bit serving"). Implies --kv-tier so the
    # combined hit rate measures restores of *quantized* payloads, and
    # adds a quant-vs-fp drift probe to the verdict.
    kv_quant = e.get("BENCH_SERVING_KV_QUANT", "")
    if kv_quant in ("0", "off"):
        kv_quant = ""
    if kv_quant:
        kv_tier = True
    if kv_tier and shared_prefix == 0:
        shared_prefix = 2 * block  # two full blocks per prefix group

    tel_path = e.get("BENCH_TELEMETRY_JSONL", os.path.join(
        os.path.dirname(os.path.abspath(__file__)), "runs",
        "BENCH_serving_telemetry.jsonl"))
    telemetry.configure(enabled=True, jsonl_path=tel_path, memledger=True)

    if shared_prefix >= max_prompt:
        raise SystemExit(f"BENCH_SERVING_SHARED_PREFIX={shared_prefix} must "
                         f"be < the max prompt length ({max_prompt})")
    mbs = -(-(max_prompt + max_new) // block)
    num_blocks = max_seqs * mbs + 1
    if kv_tier:
        # tiny HBM budget: roughly two in-flight requests' worth, so the
        # n_groups x (prefix + tails) working set is >=3x the pool and
        # every reuse after churn crosses a tier boundary
        num_blocks = 2 * mbs + 1
    rcfg = RaggedConfig(
        max_tokens_per_step=budget, max_seqs=max_seqs, block_size=block,
        num_blocks=num_blocks, max_blocks_per_seq=mbs,
        prefill_tile=tile,
        enable_prefix_cache=shared_prefix > 0 or kv_tier,
        kv_tier=kv_tier,
        kv_tier_host_blocks=4 * mbs,
        kv_tier_disk_blocks=8 * mbs,
        kv_tier_dir=os.path.join(
            os.path.dirname(os.path.abspath(__file__)), "runs", "kvtier",
            f"bench-{os.getpid()}"),
        quant=kv_quant or "off")
    engine = RaggedInferenceEngine(
        model=lambda ctx: llama.build(model_cfg, ctx=ctx),
        ragged_config=rcfg, seed=0)
    engine.warmup()

    frontend, router, loops = build_server(
        [engine], router_cfg=RouterConfig(
            max_queue_tokens=int(e.get("BENCH_SERVING_QUEUE_TOKENS", 2048))))

    rng = np.random.default_rng(0)
    if kv_tier:
        # n_groups distinct shared prefixes, every unique prompt issued
        # TWICE with identical sampling params: deterministic per-request
        # seeds make the pair token-identical whether the second admission
        # re-prefilled, spliced HBM blocks, or restored demoted tiers —
        # so occurrence parity is the end-to-end tiering check
        n_groups = 3
        n_req = int(e.get("BENCH_SERVING_REQUESTS", 2 * n_groups * 4))
        n_uniq = max(n_groups, n_req // 2)
        prefixes = [rng.integers(0, model_cfg.vocab_size, (shared_prefix,),
                                 dtype=np.int32).tolist()
                    for _ in range(n_groups)]
        reqs = []  # (uniq_id, prompt, sampling-extras)
        for u in range(n_uniq):
            p = prefixes[u % n_groups] + rng.integers(
                0, model_cfg.vocab_size, (max_prompt - shared_prefix,),
                dtype=np.int32).tolist()
            extra = {} if u % 2 == 0 else \
                {"temperature": 0.9, "top_k": 20, "seed": 1000 + u}
            reqs.append((u, p, extra))
        reqs = [reqs[i % n_uniq] for i in range(n_req)]
        rng.shuffle(reqs)
        prompts = [r[1] for r in reqs]
    else:
        prefix = rng.integers(0, model_cfg.vocab_size, (shared_prefix,),
                              dtype=np.int32).tolist()
        prompts = [prefix + rng.integers(
            0, model_cfg.vocab_size,
            (max(1, int(prompt_lens[i % len(prompt_lens)]) - shared_prefix),),
            dtype=np.int32).tolist() for i in range(n_req)]
        rng.shuffle(prompts)
        reqs = [(i, p, {}) for i, p in enumerate(prompts)]
    # open-loop schedule: exponential inter-arrival gaps, fixed before the
    # clock starts so client-side jitter can't thin the offered load
    gaps = rng.exponential(1.0 / rate, n_req)
    arrivals = np.cumsum(gaps)

    results = []  # dicts: {rejected, ttft, token_times, useful}
    results_lock = threading.Lock()

    def one_request(prompt, extra=None, uniq_id=None):
        conn = http.client.HTTPConnection(frontend.host, frontend.port,
                                          timeout=120)
        body = json.dumps({"prompt": prompt, "max_tokens": max_new,
                           "stream": True, **(extra or {})})
        t_send = time.perf_counter()
        rec = {"rejected": False, "ttft": None, "token_times": [],
               "useful": 0, "tokens": [], "uniq_id": uniq_id}
        try:
            conn.request("POST", "/v1/completions", body=body,
                         headers={"Content-Type": "application/json"})
            resp = conn.getresponse()
            if resp.status == 429:
                rec["rejected"] = True
                resp.read()
                return rec
            while True:
                line = resp.readline()
                if not line:
                    break
                if not line.startswith(b"data:"):
                    continue
                data = line[5:].strip()
                if data == b"[DONE]":
                    break
                frame = json.loads(data)
                if "token" in frame:
                    now = time.perf_counter()
                    if rec["ttft"] is None:
                        rec["ttft"] = now - t_send
                    rec["token_times"].append(now)
                    rec["tokens"].append(frame["token"])
            rec["useful"] = len(prompt) + len(rec["token_times"])
        finally:
            conn.close()
        return rec

    if kv_tier:
        # serial per-group warmup: publish each prefix once before the open
        # loop so group misses are the warmups, not a thundering-herd race
        for g in range(n_groups):
            one_request(prefixes[g] + [1, 2, 3], extra={"max_tokens": 1})

    threads = []
    t0 = time.perf_counter()
    for i in range(n_req):
        delay = t0 + arrivals[i] - time.perf_counter()
        if delay > 0:
            time.sleep(delay)

        def fire(r=reqs[i]):
            rec = one_request(r[1], extra=r[2], uniq_id=r[0])
            with results_lock:
                results.append(rec)

        th = threading.Thread(target=fire, daemon=True)
        th.start()
        threads.append(th)
    for th in threads:
        th.join(timeout=300)
    wall = time.perf_counter() - t0
    frontend.drain(timeout=60)

    done = [r for r in results if not r["rejected"] and r["ttft"] is not None]
    rejected = sum(1 for r in results if r["rejected"])
    ttfts = [r["ttft"] for r in done]
    gaps_s = [g for r in done
              for g in np.diff(r["token_times"]).tolist()]
    goodput = sum(r["useful"] for r in done) / wall if wall > 0 else 0.0
    decided = engine.prefix_hits + engine.prefix_misses
    cache_stats = {
        "serving_shared_prefix_tokens": shared_prefix,
        "serving_prefix_cache_hits": engine.prefix_hits,
        "serving_prefix_cache_hit_rate":
            round(engine.prefix_hits / decided, 4) if decided else 0.0,
        "serving_prefill_tokens_saved": engine.prefix_tokens_reused,
        "serving_prefix_cache_evictions": engine.allocator.evictions,
        "serving_tokens_scheduled": engine.tokens_scheduled,
    } if shared_prefix > 0 else {}
    kv_tier_stats = {}
    if kv_tier:
        st = engine.kv_tier_stats() or {}
        # occurrence parity: both sends of a unique prompt must stream the
        # same tokens — the tiered splice may never show in the output
        by_uniq = {}
        for r in done:
            if r.get("uniq_id") is not None:
                by_uniq.setdefault(r["uniq_id"], []).append(r["tokens"])
        pairs = [v for v in by_uniq.values() if len(v) >= 2]
        parity_ok = all(all(t == v[0] for t in v[1:]) for v in pairs)
        promoted = (st.get("promoted_admissions_host", 0)
                    + st.get("promoted_admissions_disk", 0))
        kv_tier_stats = {
            "enabled": True,
            "hbm_blocks": rcfg.num_blocks,
            "combined_hit_rate":
                round(engine.prefix_hits / decided, 4) if decided else 0.0,
            "hits_from_hbm": engine.prefix_hits - promoted,
            "hits_via_host_restore": st.get("promoted_admissions_host", 0),
            "hits_via_disk_restore": st.get("promoted_admissions_disk", 0),
            "parity_pairs_checked": len(pairs),
            "parity_ok": parity_ok,
            **{f"kvtier_{k}": v for k, v in st.items()},
        }
    kv_quant_stats = {}
    if kv_quant:
        from deepspeed_tpu.inference import kvquant as _kvq

        qst = engine.kv_quant_stats() or {}

        # drift probe: the SAME prompts through a quant-off and a quant-on
        # engine, judged by the greedy token-match rate
        def _probe(qspec):
            pcfg = RaggedConfig(
                max_tokens_per_step=budget, max_seqs=2, block_size=block,
                num_blocks=2 * mbs + 1, max_blocks_per_seq=mbs, quant=qspec)
            pe = RaggedInferenceEngine(
                model=lambda ctx: llama.build(model_cfg, ctx=ctx),
                ragged_config=pcfg, seed=0)
            for i in range(3):
                pe.put(i, [int(t) for t in prompts[i][:32]],
                       max_new_tokens=12)
            return pe.generate_all()

        match = _kvq.token_match_rate(_probe("off"), _probe(kv_quant))
        kv_quant_stats = {
            "enabled": True,
            "codec": qst.get("codec", kv_quant),
            "resident_block_multiplier":
                round(qst.get("resident_multiplier_vs_fp16", 0.0), 4),
            "kv_block_bytes": qst.get("block_bytes"),
            "fp16_block_bytes": qst.get("fp16_block_bytes"),
            "blocks_allocated_total": qst.get("blocks_allocated_total"),
            "bytes_saved_total": qst.get("bytes_saved_total"),
            "drift": _kvq.drift_verdict(match),
        }
    # memory-ledger picture BEFORE close() tears the ledger down: per-owner
    # bytes + the final census gap (the leak detector's reading for the run)
    led = telemetry.TELEMETRY.memledger
    memory = {}
    if led is not None:
        census = led.census()
        memory = {
            "owners": {k: v for k, v in led.owner_bytes().items() if v},
            "attributed_bytes": census["attributed_bytes"],
            "live_bytes": census["live_bytes"],
            "unattributed_bytes": census["unattributed_bytes"],
            "unattributed_fraction": census["unattributed_fraction"],
            "drift_alarm": census["drift_alarm"],
            "oom_reports": list(led.oom_reports),
        }
        if kv_tier:
            # per-tier residency so the off-device bytes the census excludes
            # from reconciliation are still visible next to the device pool
            st = engine.kv_tier_stats() or {}
            memory["kv_tier_bytes"] = {
                "host": st.get("host_bytes", 0),
                "disk": st.get("disk_bytes", 0),
            }
            memory["offdevice_bytes"] = census.get("offdevice_bytes", 0)
    if kv_tier and engine._kvtier is not None:
        # per-pid spill directory: drop it with the run so repeated bench
        # invocations don't accumulate dead records under runs/kvtier/
        engine._kvtier.close()
        import shutil
        shutil.rmtree(rcfg.kv_tier_dir, ignore_errors=True)
    telemetry.TELEMETRY.close()
    print(json.dumps({
        "metric": "serving_frontend_poisson",
        "serving_requests": n_req,
        "serving_rate_rps": rate,
        **cache_stats,
        **({"kv_tier": kv_tier_stats} if kv_tier_stats else {}),
        **({"kv_quant": kv_quant_stats} if kv_quant_stats else {}),
        "serving_completed": len(done),
        "serving_rejected": rejected,
        "serving_rejected_rate": round(rejected / max(1, len(results)), 4),
        "serving_ttft_p50_ms": round(float(np.percentile(ttfts, 50)) * 1e3, 2)
        if ttfts else None,
        "serving_ttft_p99_ms": round(float(np.percentile(ttfts, 99)) * 1e3, 2)
        if ttfts else None,
        "serving_token_latency_ms": round(float(np.mean(gaps_s)) * 1e3, 2)
        if gaps_s else None,
        "serving_goodput_tokens_per_s": round(goodput, 1),
        "serving_wall_s": round(wall, 2),
        "memory": memory,
        "backend": jax.default_backend(),
        "telemetry_jsonl": tel_path,
    }))
    return 0


def run_serving_subprocess(timeout: float = 900.0):
    return _run_flagged_subprocess("BENCH_SERVING", timeout)


def tenant_bench_main():
    """Child process: multi-tenant metering + fair-share trial
    (``--mode serving --tenants N``, docs/OBSERVABILITY.md).

    N tenants share one replica under open-loop load. Tenant 0 ("hog") is
    a batch-class capacity hog — long prompts, long decodes, the highest
    arrival rate; the rest are interactive-class bystanders. The verdict
    checks the cost-attribution plane end to end: per-tenant block-seconds
    must sum to the pool occupancy integral (+-5%), per-class SLO series
    must exist, the ``/debug/tenants`` ledger must rank the hog first, and
    the interactive tenants must actually complete (the fair-share signal
    protecting them from the hog's backlog). One JSON line out.
    """
    import http.client
    import threading

    import numpy as np
    import jax

    from deepspeed_tpu import telemetry
    from deepspeed_tpu.inference.ragged import (
        RaggedConfig, RaggedInferenceEngine)
    from deepspeed_tpu.models import llama
    from deepspeed_tpu.serving import RouterConfig, build_server

    e = os.environ
    n_tenants = max(2, int(e.get("BENCH_TENANTS_N", 2)))
    model_cfg = llama.LlamaConfig(
        vocab_size=512, hidden_size=256, intermediate_size=688,
        num_layers=2, num_heads=4, num_kv_heads=2, max_seq_len=256)
    max_seqs, budget, block, max_prompt, max_new = 4, 64, 16, 64, 8
    hog_reqs = int(e.get("BENCH_TENANTS_HOG_REQUESTS", 8))
    int_reqs = int(e.get("BENCH_TENANTS_INTERACTIVE_REQUESTS", 5))
    rate = float(e.get("BENCH_SERVING_RATE", 6.0))

    tel_path = e.get("BENCH_TELEMETRY_JSONL", os.path.join(
        os.path.dirname(os.path.abspath(__file__)), "runs",
        "BENCH_tenants_telemetry.jsonl"))
    telemetry.configure(enabled=True, jsonl_path=tel_path,
                        costmeter={"enabled": True},
                        slo={"enabled": True, "classes": True})

    mbs = -(-(max_prompt + max_new) // block)
    rcfg = RaggedConfig(
        max_tokens_per_step=budget, max_seqs=max_seqs, block_size=block,
        num_blocks=max_seqs * mbs + 1, max_blocks_per_seq=mbs,
        enable_prefix_cache=True)
    engine = RaggedInferenceEngine(
        model=lambda ctx: llama.build(model_cfg, ctx=ctx),
        ragged_config=rcfg, seed=0)
    engine.warmup()
    frontend, router, loops = build_server(
        [engine], router_cfg=RouterConfig(
            max_queue_tokens=int(e.get("BENCH_SERVING_QUEUE_TOKENS", 768))))

    # workload: tenant 0 hogs (batch class, long prompts+decodes, front-
    # loaded arrivals); tenants 1..N-1 are interactive bystanders. Distinct
    # random prompts per request keep the block-seconds integral exact
    # (shared blocks would be N x counted per tenant vs once in the pool).
    rng = np.random.default_rng(0)
    work = []  # (tenant, sla_class, prompt, max_tokens)
    for _ in range(hog_reqs):
        p = rng.integers(0, model_cfg.vocab_size, (max_prompt,),
                         dtype=np.int32).tolist()
        work.append(("hog", "batch", p, max_new))
    for t in range(1, n_tenants):
        for _ in range(int_reqs):
            p = rng.integers(0, model_cfg.vocab_size, (16,),
                             dtype=np.int32).tolist()
            work.append((f"tenant{t}", "interactive", p, 4))
    order = rng.permutation(len(work))
    gaps = rng.exponential(1.0 / rate, len(work))
    arrivals = np.cumsum(gaps)

    results = []
    results_lock = threading.Lock()

    def one_request(tenant, sla_class, prompt, mx):
        conn = http.client.HTTPConnection(frontend.host, frontend.port,
                                          timeout=120)
        body = json.dumps({"prompt": prompt, "max_tokens": mx,
                           "stream": False, "tenant": tenant,
                           "sla_class": sla_class})
        t_send = time.perf_counter()
        rec = {"tenant": tenant, "sla_class": sla_class, "rejected": False,
               "latency": None, "tokens": 0, "echo_ok": False}
        try:
            conn.request("POST", "/v1/completions", body=body,
                         headers={"Content-Type": "application/json"})
            resp = conn.getresponse()
            data = resp.read()
            if resp.status == 429:
                rec["rejected"] = True
                return rec
            if resp.status == 200:
                rec["latency"] = time.perf_counter() - t_send
                payload = json.loads(data)
                rec["tokens"] = int(
                    (payload.get("usage") or {}).get("completion_tokens", 0))
                rec["echo_ok"] = (payload.get("tenant") == tenant
                                  and payload.get("sla_class") == sla_class)
        finally:
            conn.close()
        return rec

    def http_get(path):
        conn = http.client.HTTPConnection(frontend.host, frontend.port,
                                          timeout=30)
        try:
            conn.request("GET", path)
            return conn.getresponse().read().decode("utf-8")
        finally:
            conn.close()

    threads = []
    t0 = time.perf_counter()
    for i, j in enumerate(order):
        delay = t0 + arrivals[i] - time.perf_counter()
        if delay > 0:
            time.sleep(delay)

        def fire(w=work[j]):
            rec = one_request(*w)
            with results_lock:
                results.append(rec)

        th = threading.Thread(target=fire, daemon=True)
        th.start()
        threads.append(th)
    for th in threads:
        th.join(timeout=300)
    wall = time.perf_counter() - t0

    metrics_text = http_get("/metrics")
    debug_tenants = json.loads(http_get("/debug/tenants"))
    frontend.drain(timeout=60)

    # --- per-tenant / per-class rollups from the client's view
    by_tenant: dict[str, dict] = {}
    by_class: dict[str, list] = {"interactive": [], "batch": []}
    for r in results:
        d = by_tenant.setdefault(r["tenant"], {
            "sla_class": r["sla_class"], "requests": 0, "completed": 0,
            "rejected": 0, "tokens": 0, "latencies": []})
        d["requests"] += 1
        if r["rejected"]:
            d["rejected"] += 1
        elif r["latency"] is not None:
            d["completed"] += 1
            d["tokens"] += r["tokens"]
            d["latencies"].append(r["latency"])
            by_class[r["sla_class"]].append(r["latency"])

    # --- ledger view: block-seconds share + the occupancy-integral check
    rows = debug_tenants.get("tenants") or {}
    pool_s = float(debug_tenants.get("pool_block_seconds") or 0.0)
    tenant_s = {t: float(r.get("kv_block_seconds", 0.0))
                + float(r.get("retained_block_seconds", 0.0))
                for t, r in rows.items()}
    total_s = sum(tenant_s.values())
    integral_rel_err = (abs(total_s - pool_s) / pool_s if pool_s > 0
                        else None)
    integral_ok = integral_rel_err is not None and integral_rel_err <= 0.05

    tenant_labels = set()
    slo_classes = set()
    for line in metrics_text.splitlines():
        if line.startswith("request_cost_") and 'tenant="' in line:
            tenant_labels.add(line.split('tenant="', 1)[1].split('"', 1)[0])
        if line.startswith("slo_good_fraction") and 'sla_class="' in line:
            slo_classes.add(
                line.split('sla_class="', 1)[1].split('"', 1)[0])

    top = debug_tenants.get("top_by_block_seconds") or []
    interactive_done = sum(
        d["completed"] for d in by_tenant.values()
        if d["sla_class"] == "interactive")
    interactive_total = sum(
        d["requests"] for d in by_tenant.values()
        if d["sla_class"] == "interactive")
    # the fair-share verdict: every interactive request completed (the hog
    # never starved the bystanders), every tenant shows up in the ledger,
    # the hog tops the block-seconds ranking, and the echo held
    fair_share_ok = bool(
        interactive_total > 0
        and interactive_done == interactive_total
        and all(t in tenant_s for t in by_tenant)
        and top and top[0]["tenant"] == "hog"
        and all(r["echo_ok"] for r in results
                if not r["rejected"] and r["latency"] is not None))

    def p99_ms(vals):
        return (round(float(np.percentile(vals, 99)) * 1e3, 2)
                if vals else None)

    telemetry.TELEMETRY.close()
    print(json.dumps({
        "metric": "serving_tenant_metering",
        "tenants_requested": n_tenants,
        "serving_wall_s": round(wall, 2),
        "tenants": {
            t: {
                "sla_class": d["sla_class"],
                "requests": d["requests"],
                "completed": d["completed"],
                "rejected": d["rejected"],
                "tokens_per_s": round(d["tokens"] / wall, 2) if wall else 0.0,
                "latency_p99_ms": p99_ms(d["latencies"]),
                "block_seconds": round(tenant_s.get(t, 0.0), 6),
                "block_seconds_share": round(tenant_s.get(t, 0.0) / total_s,
                                             4) if total_s else 0.0,
            } for t, d in by_tenant.items()},
        "per_class": {
            cls: {"completed": len(v), "p99_latency_ms": p99_ms(v)}
            for cls, v in by_class.items()},
        "pool_block_seconds": round(pool_s, 6),
        "tenant_block_seconds_sum": round(total_s, 6),
        "integral_rel_err": (round(integral_rel_err, 4)
                             if integral_rel_err is not None else None),
        "block_seconds_integral_ok": integral_ok,
        "metrics_tenant_labels": sorted(tenant_labels),
        "slo_class_series": sorted(slo_classes),
        "debug_tenants_top": top,
        "fair_share_ok": fair_share_ok,
        "backend": jax.default_backend(),
        "telemetry_jsonl": tel_path,
    }))
    return 0


def run_tenants_subprocess(n_tenants: int = 2, timeout: float = 900.0):
    return _run_flagged_subprocess(
        "BENCH_TENANTS", timeout,
        extra_env={"BENCH_TENANTS_N": str(n_tenants)})


def disagg_bench_main():
    """Child process: disaggregated prefill/decode serving measurement
    (``--mode serving --disagg``, docs/SERVING.md).

    Builds a one-process cluster — 1 prefill replica, 2 decode replicas
    sharing the same params — and reports what the disagg tier adds over
    the plain serving bench: KV-transfer volume, handoff latency, cluster
    prefix-index hit rate, and autoscale events, plus a parity verdict
    (cluster output token-identical to a single-replica engine, greedy AND
    seeded). One JSON line out.
    """
    import http.client

    import numpy as np
    import jax

    from deepspeed_tpu import telemetry
    from deepspeed_tpu.inference.ragged import (
        RaggedConfig, RaggedInferenceEngine)
    from deepspeed_tpu.models import llama
    from deepspeed_tpu.serving import (
        ClusterConfig, DecodeAutoscaler, EngineLoop, RouterConfig,
        build_cluster_server)

    e = os.environ
    on_tpu = jax.default_backend() == "tpu"
    if on_tpu:
        model_cfg = llama.LlamaConfig(
            vocab_size=32768, hidden_size=2048, intermediate_size=5632,
            num_layers=8, num_heads=16, num_kv_heads=8, max_seq_len=1024)
        max_new, shared, n_shared = 32, 128, 12
        max_seqs, budget, block, max_prompt = 16, 512, 32, 512
    else:
        model_cfg = llama.LlamaConfig(
            vocab_size=512, hidden_size=256, intermediate_size=688,
            num_layers=2, num_heads=4, num_kv_heads=2, max_seq_len=256)
        max_new, shared, n_shared = 6, 16, 4
        max_seqs, budget, block, max_prompt = 3, 64, 8, 64
    max_new = int(e.get("BENCH_DISAGG_MAX_NEW", max_new))
    n_shared = int(e.get("BENCH_DISAGG_REQUESTS", n_shared))

    tel_path = e.get("BENCH_TELEMETRY_JSONL", os.path.join(
        os.path.dirname(os.path.abspath(__file__)), "runs",
        "BENCH_disagg_telemetry.jsonl"))
    telemetry.configure(enabled=True, jsonl_path=tel_path, slo=True)

    mbs = -(-(max_prompt + max_new) // block)
    rcfg = RaggedConfig(
        max_tokens_per_step=budget, max_seqs=max_seqs, block_size=block,
        num_blocks=max_seqs * mbs + 1, max_blocks_per_seq=mbs,
        enable_prefix_cache=True)

    def mk(params=None):
        return RaggedInferenceEngine(
            model=lambda ctx: llama.build(model_cfg, ctx=ctx),
            ragged_config=rcfg, seed=0, params=params)

    pre = mk()
    params = pre.params
    frontend, cluster, loops = build_cluster_server(
        [pre], [mk(params), mk(params)],
        cluster_cfg=ClusterConfig(min_decode_replicas=1,
                                  max_decode_replicas=4,
                                  autoscale_cooldown_s=0.0),
        router_cfg=RouterConfig(max_queue_tokens=4096))

    rng = np.random.default_rng(0)
    prefix = rng.integers(1, model_cfg.vocab_size,
                          (shared,), dtype=np.int32).tolist()

    def post(body: dict) -> dict:
        conn = http.client.HTTPConnection(frontend.host, frontend.port,
                                          timeout=300)
        conn.request("POST", "/v1/completions", body=json.dumps(body),
                     headers={"Content-Type": "application/json"})
        resp = conn.getresponse()
        out = json.loads(resp.read())
        conn.close()
        if resp.status != 200:
            raise RuntimeError(f"HTTP {resp.status}: {out}")
        return out

    error = None
    parity = {}
    try:
        # ---- parity probe: cluster vs single-replica, greedy + seeded ---
        ref = mk(params)
        probe = prefix + rng.integers(
            1, model_cfg.vocab_size, (8,), dtype=np.int32).tolist()
        for name, sampling in (
                ("greedy", {}),
                ("seeded", {"temperature": 0.9, "top_k": 20, "seed": 123})):
            ref.put(f"p-{name}", probe, max_new_tokens=max_new,
                    temperature=sampling.get("temperature", 0.0),
                    top_k=sampling.get("top_k", 0),
                    seed=sampling.get("seed", 0))
            while f"p-{name}" not in ref.finished_uids:
                ref.step()
            want = ref._results[f"p-{name}"].generated
            got = post({"prompt": probe, "max_tokens": max_new,
                        **sampling})["choices"][0]["tokens"]
            parity[name] = bool(got == want)

        # ---- shared-prefix workload: cluster-level reuse ----------------
        t0 = time.perf_counter()
        for i in range(n_shared):
            tail = rng.integers(1, model_cfg.vocab_size,
                                (8,), dtype=np.int32).tolist()
            post({"prompt": prefix + tail, "max_tokens": max_new})
        wall = time.perf_counter() - t0

        # ---- autoscaler: forced up + down tick (policy demonstration) ---
        def factory(name):
            return EngineLoop(mk(params), name=name, role="decode")

        scaler = DecodeAutoscaler(cluster, factory, cfg=cluster.cfg,
                                  burn_fn=lambda: 2.0)
        up = scaler.tick()
        scaler._burn_fn = lambda: 0.0
        down = scaler.tick()
        scaler.stop()
        autoscale_ok = up == 1 and down == -1
    except Exception as ex:  # noqa: BLE001 - bench child must emit JSON
        error = f"{type(ex).__name__}: {ex}"
        wall = 0.0
        autoscale_ok = False
    finally:
        cluster.begin_drain()
        for lp in loops:
            lp.join(timeout=60)
        frontend.close()

    cs = cluster.cluster_stats()
    idx = cs["prefix_index"]
    looked = idx["hits"] + idx["misses"]
    handoffs = cs["handoffs"]["ok"] + cs["handoffs"]["failed"]
    telemetry.TELEMETRY.close()
    print(json.dumps({
        "metric": "serving_disagg",
        "error": error,
        "disagg_parity": parity,
        "disagg_requests": cs["disagg_requests"],
        "disagg_completed_wall_s": round(wall, 2),
        "kv_transfer_bytes": cs["kv_transfer"]["bytes"],
        "kv_transfer_count": cs["kv_transfer"]["transfers"],
        "handoffs_ok": cs["handoffs"]["ok"],
        "handoffs_failed": cs["handoffs"]["failed"],
        "handoff_latency_ms": round(
            cs["handoffs"]["seconds"] / handoffs * 1e3, 2) if handoffs
        else None,
        "cluster_prefix_hits": idx["hits"],
        "cluster_prefix_hit_rate": round(idx["hits"] / looked, 4)
        if looked else 0.0,
        "cluster_prefix_entries": idx["entries"],
        "prefix_transfers": cs["prefix_transfers"],
        "fallbacks": cs["fallbacks"],
        "autoscale_events": cs["autoscale_events"],
        "autoscale_up_down_ok": autoscale_ok,
        "replica_roles": cs["roles"],
        "backend": jax.default_backend(),
        "telemetry_jsonl": tel_path,
    }))
    return 0 if error is None else 1


def run_disagg_subprocess(timeout: float = 900.0):
    return _run_flagged_subprocess("BENCH_SERVING_DISAGG", timeout)


def fleet_worker_main():
    """Grandchild process: ONE fleet worker (``BENCH_FLEET_WORKER`` =
    prefill|decode) in the 2-process ``--mode fleet`` topology.

    Both roles configure telemetry with tracing + a FleetReporter, write a
    liveness beacon, run their half of a disaggregated request, then flush
    metric snapshot + trace spill into the shared fleet dir. The prefill
    worker exports the KVHandoff (traceparent stamped) to a file; the
    decode worker imports it, finishes the decode under the SAME trace,
    then serves the rollup HTTP surface (``/debug/fleet``,
    ``/metrics/fleet``, ``/healthz``) and probes it. One JSON line out.
    """
    import http.client

    import numpy as np
    import jax

    from deepspeed_tpu import telemetry
    from deepspeed_tpu.elasticity.agent import publish_heartbeat_ages
    from deepspeed_tpu.inference.ragged import (
        KVHandoff, RaggedConfig, RaggedInferenceEngine)
    from deepspeed_tpu.models import llama
    from deepspeed_tpu.serving import (
        EngineLoop, ReplicaRouter, ServingFrontend)

    e = os.environ
    role = e["BENCH_FLEET_WORKER"]
    fleet_dir = e["BENCH_FLEET_DIR"]
    hb_dir = e["BENCH_FLEET_HEARTBEATS"]
    handoff_path = e["BENCH_FLEET_HANDOFF"]
    rank = 0 if role == "prefill" else 1
    worker = f"{role}-0"

    telemetry.configure(
        enabled=True, tracing=True,
        slo={"enabled": True, "replica": worker},
        fleet={"enabled": True, "dir": fleet_dir, "worker": worker,
               "labels": {"role": role}})
    tel = telemetry.TELEMETRY
    tracer = tel.tracer

    # tiny model on every backend: this leg measures the observability
    # plane (federation + stitching), not model throughput
    model_cfg = llama.LlamaConfig(
        vocab_size=512, hidden_size=256, intermediate_size=688,
        num_layers=2, num_heads=4, num_kv_heads=2, max_seq_len=256)
    max_new, max_prompt, block, max_seqs = 6, 16, 8, 3
    mbs = -(-(max_prompt + max_new) // block)
    rcfg = RaggedConfig(
        max_tokens_per_step=64, max_seqs=max_seqs, block_size=block,
        num_blocks=max_seqs * mbs + 1, max_blocks_per_seq=mbs,
        enable_prefix_cache=True)
    # seed=0 on both sides -> identical params, a genuine resume
    eng = RaggedInferenceEngine(
        model=lambda ctx: llama.build(model_cfg, ctx=ctx),
        ragged_config=rcfg, seed=0)

    # liveness beacon (sentinel heartbeat protocol), then surface beacon
    # ages as gauges so they federate; the sleep keeps the youngest age
    # strictly nonzero for the CI assert
    with open(os.path.join(hb_dir, f"heartbeat_{rank}.json"), "w") as f:
        json.dump({"rank": rank, "role": role, "pid": os.getpid()}, f)
    time.sleep(0.06)

    out = {"worker": worker, "role": role, "pid": os.getpid(),
           "backend": jax.default_backend()}
    uid = "fleet-req"
    t0 = time.perf_counter()
    if role == "prefill":
        rng = np.random.default_rng(0)
        prompt = rng.integers(1, model_cfg.vocab_size,
                              (12,), dtype=np.int32).tolist()
        root = tracer.extract(None)
        eng.put(uid, prompt, max_new_tokens=max_new, handoff=True,
                trace=root)
        while uid not in eng.finished_uids:
            eng.step()
        rec = eng.export_handoff(uid)
        if rec is None or rec.traceparent is None:
            raise RuntimeError("prefill produced no traced handoff")
        buf = rec.to_bytes()
        with open(handoff_path + ".tmp", "wb") as f:
            f.write(buf)
        os.replace(handoff_path + ".tmp", handoff_path)
        tracer.finish(root, "fleet/request", t0, time.perf_counter(),
                      role=role, uid=uid)
        out.update(trace_id=root.trace_id, handoff_bytes=len(buf),
                   wall_s=round(time.perf_counter() - t0, 3))
    else:
        with open(handoff_path, "rb") as f:
            rec = KVHandoff.from_bytes(f.read())
        if not eng.import_handoff(rec):
            raise RuntimeError("decode replica could not adopt the handoff")
        while rec.uid not in eng.finished_uids:
            eng.step()
        gen = list(eng.get_request(rec.uid).generated)
        out.update(trace_id=(rec.traceparent or "--").split("-")[1],
                   generated_tokens=len(gen), resumed_from_pos=rec.pos,
                   wall_s=round(time.perf_counter() - t0, 3))

    publish_heartbeat_ages(hb_dir, telemetry=tel)
    tel.fleet.flush()  # metrics snapshot + trace spill, atomically

    if role == "decode":
        # both workers' snapshots are on disk now (prefill ran first):
        # serve the rollup surface off a cold replica router and probe it
        frontend = ServingFrontend(
            ReplicaRouter([EngineLoop(eng, name=worker, role="decode")]),
            fleet_dir=fleet_dir).start()

        def get(path: str) -> tuple[int, dict | str]:
            conn = http.client.HTTPConnection(frontend.host, frontend.port,
                                              timeout=60)
            conn.request("GET", path)
            resp = conn.getresponse()
            body = resp.read().decode("utf-8", "replace")
            conn.close()
            ctype = resp.getheader("Content-Type") or ""
            return resp.status, (json.loads(body)
                                 if "json" in ctype else body)
        try:
            st_d, debug = get("/debug/fleet")
            st_m, prom = get("/metrics/fleet")
            st_h, health = get("/healthz")
        finally:
            frontend.close()
        import re
        out.update(
            http_debug_fleet={
                "status": st_d,
                "workers": len(debug.get("workers", []))
                if isinstance(debug, dict) else 0,
                "verdict": (debug.get("health") or {}).get("verdict")
                if isinstance(debug, dict) else None,
                "heartbeat_ages": debug.get("heartbeat_ages")
                if isinstance(debug, dict) else None,
            },
            http_metrics_fleet={
                "status": st_m,
                "worker_labels": sorted(set(
                    re.findall(r'worker="([^"]+)"', prom)))
                if isinstance(prom, str) else [],
            },
            http_healthz={
                "status": st_h,
                "state": health.get("status")
                if isinstance(health, dict) else None,
                "fleet": health.get("fleet")
                if isinstance(health, dict) else None,
            })

    telemetry.TELEMETRY.close()
    print(json.dumps(out))
    return 0


def fleet_bench_main():
    """Child process: the 2-process fleet observability trial
    (``--mode fleet``, docs/OBSERVABILITY.md).

    Runs a prefill worker and then a decode worker as SEPARATE processes
    sharing only a fleet dir, a heartbeat dir, and a KVHandoff file — one
    after the other, each to its exit, because a chip belongs to one
    process at a time and this orchestrator never touches jax — then
    verifies the fleet plane end to end: a single stitched trace_id whose
    spans come from both worker pids in the merged Perfetto export, a
    federated scrape carrying >= 2 distinct ``worker=`` label values, and
    nonzero heartbeat-age gauges. One JSON line out.
    """
    import re

    from deepspeed_tpu import telemetry
    from deepspeed_tpu.elasticity.agent import (
        beacon_ages, publish_heartbeat_ages)
    from deepspeed_tpu.telemetry.fleet import (
        FleetAggregator, merge_fleet_traces)

    base = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                        "runs", "BENCH_fleet")
    shutil.rmtree(base, ignore_errors=True)
    fleet_dir = os.path.join(base, "fleet")
    hb_dir = os.path.join(base, "heartbeats")
    os.makedirs(fleet_dir, exist_ok=True)
    os.makedirs(hb_dir, exist_ok=True)
    handoff_path = os.path.join(base, "handoff.bin")

    def run_worker(role: str) -> dict:
        env = dict(os.environ)
        env.pop("BENCH_FLEET", None)  # a worker must never recurse
        env["BENCH_FLEET_WORKER"] = role
        env["BENCH_FLEET_DIR"] = fleet_dir
        env["BENCH_FLEET_HEARTBEATS"] = hb_dir
        env["BENCH_FLEET_HANDOFF"] = handoff_path
        proc = subprocess.run(
            [sys.executable, os.path.abspath(__file__)], env=env,
            capture_output=True, text=True, timeout=300,
            cwd=os.path.dirname(os.path.abspath(__file__)))
        if proc.returncode != 0:
            raise RuntimeError(
                f"{role} worker exited {proc.returncode}:\n"
                + proc.stderr[-2000:])
        for line in reversed(proc.stdout.strip().splitlines()):
            if line.startswith("{"):
                return json.loads(line)
        raise RuntimeError(f"no JSON from {role} worker:\n"
                           + proc.stdout[-2000:])

    error = None
    workers = {}
    try:
        # in turn, never together: subprocess.run returns at the worker's
        # exit (or raises at its 300 s limit, which fails the leg)
        workers["prefill"] = run_worker("prefill")
        workers["decode"] = run_worker("decode")
    except Exception as ex:  # noqa: BLE001 - bench child must emit JSON
        error = f"{type(ex).__name__}: {ex}"

    # offline rollup in the parent: aggregate the dir both workers fed
    telemetry.configure(enabled=True)
    agg = FleetAggregator(fleet_dir, ttl_s=300.0,
                          registry=telemetry.TELEMETRY.registry)
    debug = agg.debug_payload()
    prom = agg.render_prometheus()
    fed_workers = sorted(set(re.findall(r'worker="([^"]+)"', prom)))

    merged = merge_fleet_traces(fleet_dir)
    tids = merged["otherData"]["trace_ids"]
    want_tid = workers.get("prefill", {}).get("trace_id")
    stitched_pids = {ev["pid"] for ev in merged["traceEvents"]
                     if ev.get("ph") == "X"
                     and ev["args"].get("trace_id") == want_tid}
    trace_path = os.path.join(base, "fleet_trace.json")
    with open(trace_path, "w") as f:
        json.dump(merged, f)

    ages = beacon_ages(hb_dir)
    publish_heartbeat_ages(hb_dir, telemetry=telemetry.TELEMETRY)

    same_tid = (want_tid is not None
                and workers.get("decode", {}).get("trace_id") == want_tid)
    stitched_ok = bool(same_tid and len(stitched_pids) >= 2
                       and tids == [want_tid])
    federated_ok = len(fed_workers) >= 2
    heartbeat_ok = (len(ages) >= 2
                    and all(a > 0.0 for a in ages.values()))
    http_ok = all(
        v.get("status") == 200 for v in (
            workers.get("decode", {}).get("http_debug_fleet", {}),
            workers.get("decode", {}).get("http_metrics_fleet", {}),
            workers.get("decode", {}).get("http_healthz", {})))
    fleet_ok = bool(error is None and stitched_ok and federated_ok
                    and heartbeat_ok and http_ok
                    and len(debug["workers"]) >= 2)
    telemetry.TELEMETRY.close()
    print(json.dumps({
        "metric": "fleet_observability",
        "error": error,
        "fleet_ok": fleet_ok,
        "stitched_trace_id": want_tid,
        "stitched_trace_ids_total": len(tids),
        "stitched_span_pids": sorted(stitched_pids),
        "stitched_spans": merged["otherData"]["spans"],
        "stitched_ok": stitched_ok,
        "trace_workers": merged["otherData"]["workers"],
        "trace_path": trace_path,
        "federated_worker_labels": fed_workers,
        "federated_ok": federated_ok,
        "debug_workers": len(debug["workers"]),
        "fleet_health": debug["health"]["verdict"],
        "fleet_health_reasons": debug["health"]["reasons"],
        "heartbeat_ages_s": {str(r): round(a, 3)
                             for r, a in sorted(ages.items())},
        "heartbeat_ok": heartbeat_ok,
        "http_ok": http_ok,
        "workers": workers,
    }))
    return 0 if fleet_ok else 1


def run_fleet_subprocess(timeout: float = 900.0):
    return _run_flagged_subprocess("BENCH_FLEET", timeout)


def chaos_bench_main():
    try:
        return _chaos_bench_impl()
    except Exception as ex:  # noqa: BLE001 - chaos child must emit JSON
        import traceback
        traceback.print_exc()
        print(json.dumps({"metric": "serving_chaos", "chaos_ok": False,
                          "error": {"reason": f"{type(ex).__name__}: {ex}"}}))
        return 1


def _chaos_bench_impl():
    """Child process: chaos smoke over the full serving path.

    Arms a FIXED, seeded fault schedule (deepspeed_tpu/serving/faults.py) —
    transient dispatch raise, readback hang, a dispatch burst long enough
    to trip automatic degradation, and a block-allocation fault — then
    drives concurrent HTTP requests with pinned per-request seeds and
    checks the fault-tolerance contract end to end: zero hung requests,
    zero leaked KV blocks after drain, completed requests token-identical
    to a fault-free reference run, and at least one automatic
    device_state→host-staged fallback visible in /healthz and telemetry.
    One JSON line out; ``chaos_ok`` + a structured ``error`` field carry
    the verdict (see docs/FAULT_TOLERANCE.md).
    """
    import http.client
    import threading

    import numpy as np
    import jax

    from deepspeed_tpu import telemetry
    from deepspeed_tpu.inference.ragged import (
        RaggedConfig,
        RaggedInferenceEngine,
    )
    from deepspeed_tpu.models import llama
    from deepspeed_tpu.serving import RouterConfig, build_server, faults

    e = os.environ
    telemetry.configure(enabled=True)

    model_cfg = llama.LlamaConfig(
        vocab_size=97, hidden_size=32, intermediate_size=64, num_layers=2,
        num_heads=4, num_kv_heads=2, max_seq_len=128)

    def make_engine():
        rcfg = RaggedConfig(
            max_tokens_per_step=16, max_seqs=3, block_size=4, num_blocks=49,
            max_blocks_per_seq=16, prefill_tile=8, device_state=True,
            dispatch_retries=2, retry_backoff_s=0.01, degrade_after=2)
        return RaggedInferenceEngine(
            model=lambda ctx: llama.build(model_cfg, ctx=ctx),
            ragged_config=rcfg, seed=0)

    n_req = int(e.get("BENCH_CHAOS_REQUESTS", 8))
    max_new = 8
    rng = np.random.default_rng(0)
    prompts = [rng.integers(0, 97, (int(n),), dtype=np.int32).tolist()
               for n in rng.integers(4, 20, n_req)]

    # fault-free reference FIRST (injector still disarmed): per-request
    # seeds pin the sampled tokens, so the chaos run must reproduce these
    # exactly for every request the faults didn't kill
    ref_eng = make_engine()
    for i, p in enumerate(prompts):
        ref_eng.put(f"ref-{i}", p, max_new_tokens=max_new, temperature=0.8,
                    seed=1000 + i)
    ref_out = ref_eng.generate_all()
    reference = {i: ref_out[f"ref-{i}"] for i in range(n_req)}
    del ref_eng

    engine = make_engine()
    frontend, router, loops = build_server(
        [engine], router_cfg=RouterConfig())
    inj = faults.get_fault_injector()
    inj.configure([
        # one transient dispatch blip: the watchdog retries it away
        {"point": faults.POINT_DISPATCH, "kind": "raise", "after": 1},
        # a wedged readback surfacing as TimeoutError: also transient
        {"point": faults.POINT_READBACK, "kind": "hang", "after": 6,
         "delay_s": 0.01},
        # a dispatch failure burst: with degrade_after=2 this forces the
        # automatic device_state→host-staged fallback (and possibly the
        # plain-step rung after it)
        {"point": faults.POINT_DISPATCH, "kind": "raise", "after": 10,
         "times": 4},
        # one block-allocation fault mid-admission
        {"point": faults.POINT_ALLOC, "kind": "raise", "after": 2},
    ], seed=int(e.get("BENCH_CHAOS_SEED", 0)))

    results: dict = {}
    lock = threading.Lock()

    def one_request(i):
        conn = http.client.HTTPConnection(frontend.host, frontend.port,
                                          timeout=120)
        body = json.dumps({"prompt": prompts[i], "max_tokens": max_new,
                           "temperature": 0.8, "seed": 1000 + i})
        try:
            conn.request("POST", "/v1/completions", body=body,
                         headers={"Content-Type": "application/json"})
            resp = conn.getresponse()
            data = json.loads(resp.read() or b"{}")
            with lock:
                results[i] = (resp.status, data)
        except Exception as ex:  # noqa: BLE001 - a dropped conn is a result
            with lock:
                results[i] = (None, {"error": {"reason": str(ex)}})
        finally:
            conn.close()

    threads = [threading.Thread(target=one_request, args=(i,), daemon=True)
               for i in range(n_req)]
    for th in threads:
        th.start()
        time.sleep(0.05)  # stagger arrivals so faults land mid-flight
    for th in threads:
        th.join(timeout=180)
    hung = sum(1 for th in threads if th.is_alive())

    # health + metrics BEFORE drain: degradation must be visible live
    conn = http.client.HTTPConnection(frontend.host, frontend.port,
                                      timeout=30)
    conn.request("GET", "/healthz")
    healthz = json.loads(conn.getresponse().read())
    conn.request("GET", "/metrics")
    metrics_text = conn.getresponse().read().decode("utf-8")
    conn.close()

    fired = inj.counts()
    inj.reset()  # disarm before drain so shutdown can't re-fire
    drained = frontend.drain(timeout=60)
    leaked = (engine.cfg.num_blocks - 1) - engine.allocator.free_blocks

    completed = [i for i, (st, _) in results.items() if st == 200]
    mismatches = [
        i for i in completed
        if results[i][1]["choices"][0]["tokens"] != reference[i]
    ]
    metric_degraded = any(
        line.split()[-1] not in ("0", "0.0")
        for line in metrics_text.splitlines()
        if line.startswith(("degraded_mode", "replica_degraded_mode")))
    checks = {
        "no_hung_requests": hung == 0,
        "no_leaked_blocks": leaked == 0,
        "drained_clean": bool(drained),
        "all_responses_terminal": len(results) == n_req,
        "parity_with_fault_free_run": not mismatches and bool(completed),
        "auto_degraded": engine.degraded_mode >= 1,
        "healthz_degraded": healthz.get("status") == "degraded",
        "metrics_degraded": metric_degraded,
    }
    ok = all(checks.values())
    telemetry.TELEMETRY.close()
    print(json.dumps({
        "metric": "serving_chaos",
        "chaos_ok": ok,
        "error": None if ok else {
            "reason": "chaos assertions failed",
            "failed": sorted(k for k, v in checks.items() if not v)},
        "chaos_checks": checks,
        "chaos_requests": n_req,
        "chaos_completed": len(completed),
        "chaos_failed": len(results) - len(completed),
        "chaos_hung": hung,
        "chaos_leaked_blocks": leaked,
        "chaos_parity_mismatches": len(mismatches),
        "chaos_degraded_mode": engine.degraded_mode,
        "chaos_degraded_reason": engine.degraded_reason,
        "chaos_step_retries": engine.step_retries,
        "chaos_step_failures": engine.step_failures,
        "chaos_loop_crashes": loops[0].crash_count,
        "chaos_loop_respawns": loops[0].respawn_count,
        "chaos_faults_fired": fired,
        "chaos_healthz": healthz.get("status"),
        "backend": jax.default_backend(),
    }))
    return 0


def run_chaos_subprocess(timeout: float = 600.0):
    return _run_flagged_subprocess("BENCH_CHAOS", timeout)


def train_chaos_worker_main():
    """Chaos-harness training worker (child of ``--mode train-chaos``).

    Trains a tiny llama with a fully deterministic data stream (batch i is a
    pure function of i via :class:`CheckpointableLoader`), checkpointing
    every ``CHAOS_SAVE_EVERY`` steps into ``CHAOS_DIR/ckpt``; on start it
    resumes from the newest VERIFIED checkpoint (the fallback ladder).
    Armed faults arrive as JSON in ``CHAOS_FAULTS`` — including ``kill``
    kinds that SIGKILL this process mid-flush/mid-commit. Every trained
    step's loss is appended (fsynced) to ``CHAOS_DIR/trajectory.jsonl`` and
    lifecycle events to ``CHAOS_DIR/status.jsonl`` so the orchestrator can
    stitch and judge the run."""
    import numpy as np

    import deepspeed_tpu
    from deepspeed_tpu.checkpoint import engine as ckpt
    from deepspeed_tpu.models import llama
    from deepspeed_tpu.runtime.dataloader import CheckpointableLoader
    from deepspeed_tpu.serving import faults

    e = os.environ
    work_dir = e["CHAOS_DIR"]
    ckpt_dir = os.path.join(work_dir, "ckpt")
    total_steps = int(e.get("CHAOS_TOTAL_STEPS", 10))
    save_every = int(e.get("CHAOS_SAVE_EVERY", 2))
    batch, seq, vocab = 4, 32, 97

    def append_event(path, obj):
        with open(path, "a") as f:
            f.write(json.dumps(obj) + "\n")
            f.flush()
            os.fsync(f.fileno())

    status_path = os.path.join(work_dir, "status.jsonl")
    traj_path = os.path.join(work_dir, "trajectory.jsonl")

    model_cfg = llama.LlamaConfig(
        vocab_size=vocab, hidden_size=32, intermediate_size=64, num_layers=1,
        num_heads=4, num_kv_heads=2, max_seq_len=seq)

    def batch_for(i):
        rng = np.random.default_rng(777 + i)
        return {"input_ids": rng.integers(0, vocab, (batch, seq),
                                          dtype=np.int32)}

    def factory(skip):
        def gen():
            i = skip
            while True:
                yield batch_for(i)
                i += 1
        return gen()

    loader = CheckpointableLoader(factory)
    config = {
        "train_batch_size": batch,
        "gradient_accumulation_steps": 1,
        "sequence_length": seq,
        "steps_per_print": 0,
        "optimizer": {"type": "adamw", "params": {"lr": 1e-2}},
        "zero_optimization": {"stage": 0},
        "mesh": {"data": -1},
        "checkpoint": {"keep_n_latest": 3},
        "seed": 5,
    }
    if e.get("CHAOS_SENTINEL"):
        # self-healing legs: the divergence sentinel with quarantine state
        # persisted under the work dir (a pre-seeded quarantine.json is how
        # the clean-reference run skips the batches the chaos run healed
        # around) and the heartbeat beacon the elastic agent polls
        config["sentinel"] = {
            "enabled": True,
            "warmup_steps": 3,
            "report_dir": os.path.join(work_dir, "reports"),
            "state_dir": os.path.join(work_dir, "state"),
            "checkpoint_dir": ckpt_dir,
        }
    mesh_devices = None
    if e.get("CHAOS_PIPE"):
        # staged-pipeline leg: 4 scanned layers split across 2 stage
        # programs on one device, 4 microbatches per 1F1B round (the step
        # pulls GAS loader items, so each step consumes 4 stream entries);
        # the orchestrator SIGKILLs a stage thread mid-schedule via the
        # pipe.stage fault point and expects exact stitched resume
        import jax
        model_cfg = llama.LlamaConfig(
            vocab_size=vocab, hidden_size=32, intermediate_size=64,
            num_layers=4, num_heads=4, num_kv_heads=2, max_seq_len=seq)
        config["train_batch_size"] = batch * 4
        config["gradient_accumulation_steps"] = 4
        config["mesh"] = {"data": 1}
        config["pipeline"] = {"stages": 2, "schedule": "1f1b"}
        mesh_devices = jax.devices()[:1]
    engine, _, _, _ = deepspeed_tpu.initialize(
        model=lambda ctx: llama.build(model_cfg, ctx=ctx), config=config,
        training_data=loader, seed=5, mesh_devices=mesh_devices)

    # arm the orchestrator's fault schedule BEFORE the resume: the
    # corrupt-at-load attempt models read-time bit-rot discovered during
    # this run's own verification pass, and kill specs at save seams are
    # untouched by load-point fires (per-spec hit counters)
    specs = json.loads(e.get("CHAOS_FAULTS", "[]"))
    if specs:
        faults.get_fault_injector().configure(
            specs, seed=int(e.get("CHAOS_SEED", 0)))

    # resume from the newest verified checkpoint (ladders past corruption)
    latest_before = ckpt.latest_tag(ckpt_dir) if os.path.isdir(ckpt_dir) else None
    try:
        path, _ = engine.load_checkpoint(ckpt_dir)
    except ckpt.CheckpointCorruptError as ex:
        append_event(status_path, {"event": "exhausted", "stage": ex.stage})
        return 4
    append_event(status_path, {
        "event": "resume" if path else "fresh",
        "tag": os.path.basename(path) if path else None,
        "latest": latest_before, "step": engine.global_steps})

    while engine.global_steps < total_steps:
        step = engine.global_steps
        loss = engine.train_batch()
        if engine.global_steps <= step:
            # the sentinel rolled back: the step counter rewound to the
            # pinned checkpoint. Don't log the anomalous loss — the replay
            # re-logs every step from the restore point (last write wins in
            # the orchestrator's stitched-parity check).
            append_event(status_path, {"event": "rollback", "from": step,
                                       "to": engine.global_steps})
            continue
        append_event(traj_path, {"step": step,
                                 "loss": float(np.asarray(loss))})
        if engine.global_steps % save_every == 0:
            tag = f"global_step{engine.global_steps}"
            engine.save_checkpoint(ckpt_dir)
            append_event(status_path, {"event": "saved", "tag": tag})
    done = {"event": "done", "step": engine.global_steps}
    if engine._sentinel is not None:
        done["rollbacks"] = engine.train_rollbacks
        done["quarantined"] = engine._sentinel.quarantined
    engine.destroy()
    append_event(status_path, done)
    print("CHAOS_WORKER_DONE")
    return 0


def train_chaos_main():
    try:
        return _train_chaos_impl()
    except Exception as ex:  # noqa: BLE001 - chaos child must emit JSON
        import traceback
        traceback.print_exc()
        print(json.dumps({"metric": "train_chaos", "train_chaos_ok": False,
                          "error": {"reason": f"{type(ex).__name__}: {ex}"}}))
        return 1


def _train_chaos_impl():
    """Kill–resume chaos harness for the training checkpoint path
    (docs/FAULT_TOLERANCE.md "Training: crash-safe checkpoints").

    Protocol: (1) run an uninterrupted reference worker and record its loss
    trajectory; (2) run the same workload under a seeded kill schedule —
    SIGKILL mid-flush, mid-commit, at the latest-pointer update (via the
    injector's ``kill`` fault kind, which dies AT the seam), plus one
    wall-clock-timer kill and one corrupt-bytes-at-load attempt — restarting
    after every death; (3) supervise the same worker under an
    :class:`ElasticAgent` whose second worker slot dies, forcing a restart
    at a reduced world size. Verdicts: a verified checkpoint always loads
    after every kill, the stitched chaos trajectory is step-identical to
    the reference, corruption triggered the fallback ladder (never a
    crash), and the agent finished at the smaller world size."""
    import random
    import shutil
    import signal as _signal
    import tempfile

    import jax

    from deepspeed_tpu.elasticity.agent import ElasticAgent, WorkerSpec

    e = os.environ
    seed = int(e.get("BENCH_TRAIN_CHAOS_SEED", 0))
    total_steps = int(e.get("BENCH_TRAIN_CHAOS_STEPS", 10))
    rng = random.Random(seed)
    bench_path = os.path.abspath(__file__)
    root = tempfile.mkdtemp(prefix="train_chaos_")

    def worker_env(work_dir, faults=None, sentinel=False, total=None,
                   save_every=None, pipe=False):
        env = dict(os.environ)
        env.pop("BENCH_TRAIN_CHAOS", None)
        env.update(
            BENCH_TRAIN_CHAOS_WORKER="1",
            CHAOS_DIR=work_dir,
            CHAOS_TOTAL_STEPS=str(total if total is not None else total_steps),
            CHAOS_SAVE_EVERY=str(save_every if save_every is not None
                                 else int(e.get("CHAOS_SAVE_EVERY", 2))),
            CHAOS_SEED=str(seed),
            CHAOS_FAULTS=json.dumps(faults or []),
        )
        if sentinel:
            env["CHAOS_SENTINEL"] = "1"
        if pipe:
            env["CHAOS_PIPE"] = "1"
        return env

    def read_jsonl(path):
        out = []
        if not os.path.exists(path):
            return out
        with open(path) as f:
            for line in f:
                try:
                    out.append(json.loads(line))
                except json.JSONDecodeError:
                    pass  # torn trailing line from a kill mid-append
        return out

    def run_worker(work_dir, faults=None, kill_after=None, log_name="w",
                   **env_kw):
        """One worker run. Returns the exit code (negative = signal)."""
        os.makedirs(work_dir, exist_ok=True)
        log = open(os.path.join(work_dir, f"{log_name}.log"), "ab")
        proc = subprocess.Popen(
            [sys.executable, bench_path],
            env=worker_env(work_dir, faults, **env_kw),
            stdout=log, stderr=log, cwd=os.path.dirname(bench_path))
        try:
            if kill_after is not None:
                try:
                    proc.wait(timeout=kill_after)
                except subprocess.TimeoutExpired:
                    proc.send_signal(_signal.SIGKILL)
            proc.wait(timeout=600)
        finally:
            log.close()
        return proc.returncode

    # ---- phase 1: uninterrupted reference trajectory
    ref_dir = os.path.join(root, "ref")
    rc = run_worker(ref_dir, log_name="ref")
    if rc != 0:
        raise RuntimeError(f"reference worker failed rc={rc} (see {ref_dir})")
    reference = {r["step"]: r["loss"] for r in read_jsonl(
        os.path.join(ref_dir, "trajectory.jsonl"))}
    if len(reference) != total_steps:
        raise RuntimeError(
            f"reference covered {len(reference)}/{total_steps} steps")

    # ---- phase 2: seeded kill schedule, restart after every death
    chaos_dir = os.path.join(root, "chaos")
    attempts = [
        # kill mid-flush: model fragments staged, optimizer not yet written
        ("kill@ckpt.flush", [{"point": "ckpt.flush", "kind": "kill",
                              "after": 3 + rng.randrange(3)}], None),
        # kill during device→host fragment collection: nothing staged yet
        ("kill@ckpt.collect", [{"point": "ckpt.collect", "kind": "kill",
                                "after": 1}], None),
        # kill mid-commit: manifest sealed in staging, promote never runs
        ("kill@ckpt.commit", [{"point": "ckpt.commit", "kind": "kill",
                               "after": rng.randrange(2)}], None),
        # kill at the latest-pointer update: dir promoted, pointer stale
        ("kill@ckpt.latest", [{"point": "ckpt.latest", "kind": "kill",
                               "after": rng.randrange(2)}], None),
        # wall-clock kill: lands wherever the run happens to be
        ("kill@timer", None, 4.0 + 6.0 * rng.random()),
        # silent bit-rot on the newest checkpoint, discovered at load time:
        # verification must catch it and ladder back, not crash
        ("corrupt@ckpt.load", [{"point": "ckpt.load", "kind": "corrupt-bytes",
                                "times": 1}], None),
    ]
    kills = []
    runs = []
    for i, (label, faults, kill_after) in enumerate(attempts):
        # no early exit on a clean run: a completed workload just means the
        # remaining attempts resume at the final step instantly — but the
        # corrupt-at-load attempt must still run to exercise the ladder
        rc = run_worker(chaos_dir, faults=faults, kill_after=kill_after,
                        log_name=f"attempt{i}")
        runs.append({"label": label, "rc": rc})
        if rc is not None and rc < 0:
            kills.append(label)
    extra = 0
    while runs[-1]["rc"] != 0 and extra < 5:
        extra += 1
        rc = run_worker(chaos_dir, log_name=f"extra{extra}")
        runs.append({"label": f"clean{extra}", "rc": rc})
    completed = runs[-1]["rc"] == 0

    status = read_jsonl(os.path.join(chaos_dir, "status.jsonl"))
    saves = [s for s in status if s["event"] == "saved"]
    resumes = [s for s in status if s["event"] == "resume"]
    fresh_starts = [s for s in status if s["event"] == "fresh"]
    exhausted = [s for s in status if s["event"] == "exhausted"]
    # every restart AFTER the first committed save must find a loadable
    # verified checkpoint — a "fresh" start past that point means a save
    # was lost; "exhausted" means verification found nothing at all
    first_save_at = status.index(saves[0]) if saves else len(status)
    late_fresh = [s for s in fresh_starts if status.index(s) > first_save_at]
    always_loadable = completed and not late_fresh and not exhausted
    # the corrupt-at-load attempt must have laddered back: some resume
    # loaded a tag older than what the latest pointer named
    fallbacks = [r for r in resumes
                 if r.get("latest") and r.get("tag") != r.get("latest")]

    trajectory = read_jsonl(os.path.join(chaos_dir, "trajectory.jsonl"))
    by_step: dict = {}
    for r in trajectory:
        by_step.setdefault(r["step"], []).append(r["loss"])
    coverage = sorted(by_step.keys())
    full_coverage = coverage == list(range(total_steps))
    max_rel = 0.0
    for s, losses in by_step.items():
        ref = reference.get(s)
        if ref is None:
            max_rel = float("inf")
            continue
        for l in losses:
            max_rel = max(max_rel, abs(l - ref) / max(1e-12, abs(ref)))
    parity = full_coverage and max_rel <= 1e-5

    # ---- phase 3: the ElasticAgent gets the same treatment — worker slot 1
    # dies mid-run, the agent restarts at a reduced world size, the trainer
    # resumes from its checkpoint and finishes
    elastic_dir = os.path.join(root, "elastic")
    os.makedirs(elastic_dir, exist_ok=True)
    elastic_log = open(os.path.join(elastic_dir, "trainer.log"), "ab")

    def make_worker(rank, world):
        if rank == 0:
            return WorkerSpec(cmd=[sys.executable, bench_path],
                              env=worker_env(elastic_dir))
        # a host that evicts mid-run (exactly once: at the reduced world
        # size the agent never fills this slot again)
        return WorkerSpec(cmd=[sys.executable, "-c",
                               "import time,sys; time.sleep(6); sys.exit(3)"])

    agent = ElasticAgent(
        target_batch_size=4, micro_batch_candidates=[2, 4],
        make_worker=make_worker, max_world_size=2, min_world_size=1,
        poll_interval=0.3, max_restarts=3)
    agent_rc = agent.run()
    elastic_log.close()
    elastic_traj = read_jsonl(os.path.join(elastic_dir, "trajectory.jsonl"))
    elastic_steps = {r["step"] for r in elastic_traj}
    elastic_parity = all(
        abs(r["loss"] - reference[r["step"]])
        <= 1e-5 * max(1e-12, abs(reference[r["step"]]))
        for r in elastic_traj if r["step"] in reference)
    elastic_ok = (agent_rc == 0
                  and elastic_steps == set(range(total_steps))
                  and elastic_parity)
    world_reduced = getattr(agent, "world_size", 2) == 1

    # ---- phase 4: divergence leg — self-healing from poisoned math.
    # One run eats a nan-grads fault (strike 1: quarantine + pin the
    # pre-anomaly tag) and a content-keyed poison-batch fault (strike 2:
    # rollback to the pin and replay with the quarantine applied). Then a
    # clean reference run — pre-armed with the chaos run's final quarantine
    # so its data stream is aligned — must produce a step-identical loss
    # trajectory: the healed run is indistinguishable from one that never
    # saw the poison.
    import numpy as np

    from deepspeed_tpu.runtime import sentinel as sentinel_mod

    sent_total, sent_save = 16, 3

    def chaos_batch_for(i):  # mirrors the worker's deterministic stream
        brng = np.random.default_rng(777 + i)
        return {"input_ids": brng.integers(0, 97, (4, 32), dtype=np.int32)}

    poison_fp = sentinel_mod.batch_fingerprint(chaos_batch_for(10))
    sent_chaos = os.path.join(root, "sent_chaos")
    sent_rc = run_worker(
        sent_chaos,
        faults=[
            {"point": "train.grads", "kind": "nan-grads", "after": 6,
             "times": 1},
            {"point": "data.batch", "kind": "poison-batch",
             "request_id": poison_fp, "times": 1},
        ],
        log_name="sent_chaos", sentinel=True, total=sent_total,
        save_every=sent_save)
    sent_status = read_jsonl(os.path.join(sent_chaos, "status.jsonl"))
    sent_rollbacks = [s for s in sent_status if s["event"] == "rollback"]
    sent_done = [s for s in sent_status if s["event"] == "done"]
    sent_quarantine = sentinel_mod.load_quarantine(
        os.path.join(sent_chaos, "state"))
    report_dir = os.path.join(sent_chaos, "reports")
    sent_reports = []
    if os.path.isdir(report_dir):
        for name in sorted(os.listdir(report_dir)):
            with open(os.path.join(report_dir, name)) as f:
                sent_reports.append((name, json.load(f)))

    # clean reference: same workload, no faults, quarantine pre-seeded so
    # the stream skips exactly the batches the chaos run learned to avoid
    sent_ref = os.path.join(root, "sent_ref")
    os.makedirs(os.path.join(sent_ref, "state"), exist_ok=True)
    sentinel_mod.save_quarantine(os.path.join(sent_ref, "state"),
                                 sent_quarantine)
    sent_ref_rc = run_worker(sent_ref, log_name="sent_ref", sentinel=True,
                             total=sent_total, save_every=sent_save)
    ref_last = {r["step"]: r["loss"] for r in read_jsonl(
        os.path.join(sent_ref, "trajectory.jsonl"))}
    chaos_last = {r["step"]: r["loss"] for r in read_jsonl(
        os.path.join(sent_chaos, "trajectory.jsonl"))}
    sent_max_rel = 0.0
    for s in range(sent_total):
        a, b = chaos_last.get(s), ref_last.get(s)
        if a is None or b is None or a != a or b != b:
            sent_max_rel = float("inf")
            continue
        sent_max_rel = max(sent_max_rel, abs(a - b) / max(1e-12, abs(b)))
    sent_parity = (set(chaos_last) == set(range(sent_total))
                   and sent_max_rel <= 1e-5)
    sent_forensics_ok = (
        bool(sent_reports)
        and any(n.startswith("sentinel_rollback") for n, _ in sent_reports)
        and all(r for _, r in sent_reports))

    # ---- phase 5: liveness leg — a wedge fault blocks the device fence
    # forever; the worker's heartbeat beacon goes stale, the agent SIGKILLs
    # the wedged-but-alive process, and the relaunch (no fault armed) heals
    # from the last checkpoint
    hb_dir = os.path.join(root, "wedge")
    os.makedirs(hb_dir, exist_ok=True)
    wedge_total, wedge_save = 8, 2
    wedge_armed = {"first": True}

    def make_wedge_worker(rank, world):
        faults = []
        if wedge_armed["first"]:
            # arm only the first incarnation: the relaunch must run clean
            wedge_armed["first"] = False
            faults = [{"point": "train.dispatch", "kind": "wedge",
                       "delay_s": 600.0, "after": 4, "times": 1}]
        return WorkerSpec(cmd=[sys.executable, bench_path],
                          env=worker_env(hb_dir, faults, sentinel=True,
                                         total=wedge_total,
                                         save_every=wedge_save))

    wedge_agent = ElasticAgent(
        target_batch_size=4, micro_batch_candidates=[2, 4],
        make_worker=make_wedge_worker, max_world_size=1, min_world_size=1,
        poll_interval=0.5, max_restarts=3,
        heartbeat_dir=os.path.join(hb_dir, "state"),
        heartbeat_timeout=5.0, heartbeat_grace=60.0)
    wedge_rc = wedge_agent.run()
    wedge_status = read_jsonl(os.path.join(hb_dir, "status.jsonl"))
    wedge_done = [s for s in wedge_status if s["event"] == "done"]
    wedge_kills = getattr(wedge_agent, "heartbeat_kills", 0)
    wedge_ok = (wedge_rc == 0 and bool(wedge_done)
                and wedge_kills >= 1
                and getattr(wedge_agent, "restarts", 0) >= 1)

    # ---- phase 6: staged-pipeline leg — SIGKILL a stage thread mid-1F1B
    # (pipe.stage fault point, request_id keyed to the stage-1 thread),
    # restart, and the stitched trajectory must be step-identical to an
    # uninterrupted 2-stage run (docs/PIPELINE.md "Failure semantics")
    pipe_total, pipe_save = 6, 2
    pipe_ref_dir = os.path.join(root, "pipe_ref")
    pipe_ref_rc = run_worker(pipe_ref_dir, log_name="pipe_ref", pipe=True,
                             total=pipe_total, save_every=pipe_save)
    pipe_ref_traj = {r["step"]: r["loss"] for r in read_jsonl(
        os.path.join(pipe_ref_dir, "trajectory.jsonl"))}

    # stage 1 executes 2*M = 8 schedule instructions per step; after=19
    # lands the kill inside the third step's 1F1B round, one step past the
    # step-2 checkpoint, so the restart must resume and replay exactly
    pipe_dir = os.path.join(root, "pipe")
    pipe_runs = []
    pipe_kill_rc = run_worker(
        pipe_dir,
        faults=[{"point": "pipe.stage", "kind": "kill",
                 "request_id": "stage1", "after": 19}],
        log_name="pipe_kill", pipe=True, total=pipe_total,
        save_every=pipe_save)
    pipe_runs.append({"label": "kill@pipe.stage", "rc": pipe_kill_rc})
    extra = 0
    while pipe_runs[-1]["rc"] != 0 and extra < 4:
        extra += 1
        rc = run_worker(pipe_dir, log_name=f"pipe_extra{extra}", pipe=True,
                        total=pipe_total, save_every=pipe_save)
        pipe_runs.append({"label": f"pipe_clean{extra}", "rc": rc})
    pipe_traj: dict = {}
    for r in read_jsonl(os.path.join(pipe_dir, "trajectory.jsonl")):
        pipe_traj[r["step"]] = r["loss"]  # replayed steps: last write wins
    pipe_max_rel = 0.0
    for s in range(pipe_total):
        a, b = pipe_traj.get(s), pipe_ref_traj.get(s)
        if a is None or b is None:
            pipe_max_rel = float("inf")
            continue
        pipe_max_rel = max(pipe_max_rel, abs(a - b) / max(1e-12, abs(b)))
    pipe_killed = pipe_kill_rc is not None and pipe_kill_rc < 0
    pipe_parity = (pipe_ref_rc == 0 and pipe_runs[-1]["rc"] == 0
                   and set(pipe_traj) == set(range(pipe_total))
                   and pipe_max_rel <= 1e-6)

    checks = {
        "completed": completed,
        "always_loadable": always_loadable,
        "kills_ge_3": len(kills) >= 3,
        "killed_mid_commit": "kill@ckpt.commit" in kills,
        "full_coverage": full_coverage,
        "trajectory_parity": parity,
        "fallback_observed": bool(fallbacks),
        "elastic_ok": elastic_ok,
        "elastic_world_reduced": world_reduced,
        "sentinel_self_heals": sent_rc == 0 and bool(sent_done),
        "sentinel_quarantined_two": len(sent_quarantine) == 2,
        "sentinel_one_rollback": len(sent_rollbacks) == 1,
        "sentinel_stitched_parity": sent_ref_rc == 0 and sent_parity,
        "sentinel_forensics": sent_forensics_ok,
        "wedge_heartbeat_kill": wedge_ok,
        "pipe_stage_killed": pipe_killed,
        "pipe_stitched_parity": pipe_parity,
    }
    ok = all(checks.values())
    if ok:
        shutil.rmtree(root, ignore_errors=True)
    print(json.dumps({
        "metric": "train_chaos",
        "train_chaos_ok": ok,
        "error": None if ok else {
            "reason": "train-chaos assertions failed (artifacts kept in "
                      f"{root})",
            "failed": sorted(k for k, v in checks.items() if not v)},
        "train_chaos_checks": checks,
        "train_chaos_runs": runs,
        "train_chaos_kills": kills,
        "train_chaos_saves": len(saves),
        "train_chaos_resumes": len(resumes),
        "train_chaos_fallbacks": len(fallbacks),
        "train_chaos_max_rel_loss_diff": max_rel,
        "train_chaos_steps": total_steps,
        "elastic_agent_rc": agent_rc,
        "elastic_agent_restarts": getattr(agent, "restarts", None),
        "elastic_agent_world": getattr(agent, "world_size", None),
        "sentinel_rollbacks": len(sent_rollbacks),
        "sentinel_quarantined": sent_quarantine,
        "sentinel_reports": [n for n, _ in sent_reports],
        "sentinel_max_rel_loss_diff": sent_max_rel,
        "wedge_heartbeat_kills": wedge_kills,
        "wedge_agent_rc": wedge_rc,
        "wedge_agent_restarts": getattr(wedge_agent, "restarts", None),
        "pipe_runs": pipe_runs,
        "pipe_max_rel_loss_diff": pipe_max_rel,
        "backend": jax.default_backend(),
    }))
    return 0 if ok else 1


def run_train_chaos_subprocess(timeout: float = 1350.0):
    return _run_flagged_subprocess("BENCH_TRAIN_CHAOS", timeout)


def pipeline_bench_main():
    """Child process: staged-pipeline trial (runtime/pipe/, docs/PIPELINE.md).

    Trains the same tiny llama twice — single fused program, then a 2-stage
    1F1B pipeline over the identical deterministic batch stream — and
    reports the parity verdict (the staged run must reproduce the fused
    loss trajectory to <=1e-6 rel; on CPU it is bit-exact), the measured
    bubble fraction from stepscope's ``train_pipe_bubble_fraction`` gauge
    next to the schedule's analytic value, and the per-stage wall
    breakdown (busy seconds per stage thread vs schedule wall)."""
    import numpy as np
    import jax

    import deepspeed_tpu
    from deepspeed_tpu.models import llama
    from deepspeed_tpu.runtime.pipe.schedule import bubble_fraction
    from deepspeed_tpu.telemetry import TELEMETRY

    e = os.environ
    steps = int(e.get("BENCH_PIPELINE_STEPS", 8))
    stages = int(e.get("BENCH_PIPELINE_STAGES", 2))
    gas = int(e.get("BENCH_PIPELINE_GAS", 4))
    sched = e.get("BENCH_PIPELINE_SCHEDULE", "1f1b")
    n_layers, vocab, seq = 2 * stages, 97, 32

    model_cfg = llama.LlamaConfig(
        vocab_size=vocab, hidden_size=64, intermediate_size=128,
        num_layers=n_layers, num_heads=4, num_kv_heads=2, max_seq_len=seq)

    def batches():
        rng = np.random.default_rng(42)
        return [{"input_ids": rng.integers(0, vocab, (8, seq),
                                           dtype=np.int32)}
                for _ in range(steps)]

    def config(pipeline):
        cfg = {
            "train_micro_batch_size_per_device": 8 // gas,
            "gradient_accumulation_steps": gas,
            "steps_per_print": 0,
            "optimizer": {"type": "adamw", "params": {"lr": 1e-3}},
            "mesh": {"data": 1},
            "fp16": {"enabled": True, "initial_scale_power": 8},
            "gradient_clipping": 1.0,
            "seed": 7,
        }
        if pipeline:
            cfg["pipeline"] = {"stages": stages, "schedule": sched}
            cfg["telemetry"] = {"enabled": True,
                                "stepscope": {"enabled": True}}
        return cfg

    def run(pipeline):
        engine, _, _, _ = deepspeed_tpu.initialize(
            model=lambda ctx: llama.build(model_cfg, ctx=ctx),
            config=config(pipeline), seed=11,
            mesh_devices=jax.devices()[:1])
        losses = [float(engine.train_batch(b)) for b in batches()]
        return engine, losses

    _, base = run(False)
    pipe_engine, pipe = run(True)

    max_rel = max(abs(a - b) / max(1e-12, abs(a))
                  for a, b in zip(base, pipe))
    parity_ok = max_rel <= 1e-6

    busy = list(pipe_engine._last_stage_busy)
    wall = pipe_engine._last_stage_wall
    measured_bubble = pipe_engine.stepscope._g_pipe_bubble.value()
    plan = pipe_engine.stage_plan
    analytic_bubble = bubble_fraction(sched, plan.n_virtual, gas)
    prom = TELEMETRY.registry.render_prometheus()

    checks = {
        "loss_parity": parity_ok,
        "bubble_gauge_nonzero": measured_bubble > 0.0,
        "stage_breakdown": len(busy) == stages and wall > 0.0,
        "scrape_has_pipe_bubble": "train_pipe_bubble_fraction" in prom,
        "scrape_has_stage_skew":
            'train_step_skew_ratio{stage="0"}' in prom,
    }
    ok = all(checks.values())
    pipe_engine.destroy()
    print(json.dumps({
        "metric": "pipeline",
        "pipeline_ok": ok,
        "error": None if ok else {
            "reason": "pipeline assertions failed",
            "failed": sorted(k for k, v in checks.items() if not v)},
        "pipeline_checks": checks,
        "stages": stages,
        "schedule": sched,
        "n_microbatches": gas,
        "steps": steps,
        "max_rel_loss_diff": max_rel,
        "bubble_fraction_measured": measured_bubble,
        "bubble_fraction_analytic": analytic_bubble,
        "stage_busy_s": [round(b, 4) for b in busy],
        "schedule_wall_s": round(wall, 4),
        "stage_restarts": pipe_engine.stage_restarts,
        "backend": jax.default_backend(),
    }))
    return 0 if ok else 1


def run_pipeline_subprocess(timeout: float = 900.0):
    return _run_flagged_subprocess("BENCH_PIPELINE", timeout)


def probe_device():
    """Backend and device kind, asked of a throwaway child so that this
    parent never holds the chip. One attempt; a failure is the error."""
    code = (
        "import jax, json;"
        "d = jax.devices()[0];"
        "print(json.dumps({'backend': jax.default_backend(),"
        " 'kind': d.device_kind}))"
    )
    try:
        proc = subprocess.run([sys.executable, "-c", code],
                              capture_output=True, text=True, timeout=300)
    except subprocess.TimeoutExpired:
        raise SystemExit("bench: device probe did not answer in 300 s")
    if proc.returncode != 0:
        raise SystemExit("bench: device probe failed:\n" + proc.stderr[-2000:])
    return json.loads(proc.stdout.strip().splitlines()[-1])


def smoke_main():
    """On-accelerator smoke suite (<5 min warm): the kernels and engine
    paths the CPU test mesh can only interpret-check run HERE, where Pallas
    actually lowers (round-4 item 9). Prints one JSON line with per-check
    status; exit code 1 on any failure."""
    import numpy as np
    import jax
    import jax.numpy as jnp

    checks: dict = {}
    perf: dict = {}
    t_all = time.perf_counter()

    def run(name):
        def deco(fn):
            t0 = time.perf_counter()
            try:
                fn()
                checks[name] = {"ok": True,
                                "s": round(time.perf_counter() - t0, 2)}
            except Exception as e:  # noqa: BLE001 - report, don't crash suite
                checks[name] = {"ok": False, "error": str(e)[:300],
                                "s": round(time.perf_counter() - t0, 2)}
            return fn
        return deco

    @run("flash_attention_fwd_bwd")
    def _flash():
        from deepspeed_tpu.ops.attention import attention, xla_attention

        rng = np.random.default_rng(0)
        q = jnp.asarray(rng.normal(size=(2, 256, 8, 64)), jnp.bfloat16)
        k = jnp.asarray(rng.normal(size=(2, 256, 4, 64)), jnp.bfloat16)
        v = jnp.asarray(rng.normal(size=(2, 256, 4, 64)), jnp.bfloat16)

        def loss_fl(q, k, v):
            return attention(q, k, v, causal=True, impl="pallas").astype(
                jnp.float32).sum()

        def loss_ref(q, k, v):
            return xla_attention(q, k, v, causal=True).astype(
                jnp.float32).sum()

        o = jax.jit(lambda *a: attention(*a, causal=True, impl="pallas"))(
            q, k, v)
        o_ref = jax.jit(lambda *a: xla_attention(*a, causal=True))(q, k, v)
        np.testing.assert_allclose(np.asarray(o, np.float32),
                                   np.asarray(o_ref, np.float32),
                                   atol=3e-2, rtol=3e-2)
        g = jax.jit(jax.grad(loss_fl, argnums=(0, 1, 2)))(q, k, v)
        g_ref = jax.jit(jax.grad(loss_ref, argnums=(0, 1, 2)))(q, k, v)
        for a, b in zip(g, g_ref):
            np.testing.assert_allclose(np.asarray(a, np.float32),
                                       np.asarray(b, np.float32),
                                       atol=6e-2, rtol=6e-2)

    @run("paged_decode_kernel_vs_gather")
    def _paged():
        from deepspeed_tpu.ops.attention import paged_attention

        rng = np.random.default_rng(1)
        t, mb, bs, hq, hkv, d = 16, 8, 32, 16, 8, 64
        nb = t * mb + 1
        q = jnp.asarray(rng.normal(size=(t, hq, d)), jnp.bfloat16)
        kp = jnp.asarray(rng.normal(size=(nb, bs, hkv * d)), jnp.bfloat16)
        vp = jnp.asarray(rng.normal(size=(nb, bs, hkv * d)), jnp.bfloat16)
        slots = jnp.arange(t, dtype=jnp.int32)
        positions = jnp.asarray(rng.integers(1, mb * bs, (t,)), jnp.int32)
        # read-only parity check: aliased blocks across rows are fine
        bt = jnp.asarray(rng.integers(1, nb, (t + 1, mb)), jnp.int32)
        a = jax.jit(lambda *x: paged_attention(*x, impl="pallas"))(
            q, kp, vp, slots, positions, bt)
        b = jax.jit(lambda *x: paged_attention(*x, impl="xla"))(
            q, kp, vp, slots, positions, bt)
        np.testing.assert_allclose(np.asarray(a, np.float32),
                                   np.asarray(b, np.float32),
                                   atol=3e-2, rtol=3e-2)

    @run("zero3_train_step")
    def _z3():
        import deepspeed_tpu
        from deepspeed_tpu.comm.topology import reset_topology
        from deepspeed_tpu.models import llama

        reset_topology()
        eng, _, _, _ = deepspeed_tpu.initialize(
            model=lambda ctx: llama.build(llama.LlamaConfig.tiny(512),
                                          ctx=ctx),
            config={"train_micro_batch_size_per_device": 4,
                    "gradient_accumulation_steps": 1, "steps_per_print": 0,
                    "optimizer": {"type": "adamw", "params": {"lr": 1e-3}},
                    "zero_optimization": {"stage": 3}, "mesh": {"data": -1},
                    "seed": 3}, seed=3)
        rng = np.random.default_rng(0)
        batch = {"input_ids": rng.integers(0, 512, (4, 64), dtype=np.int32)}
        l0 = float(eng.train_batch(batch))
        l1 = float(eng.train_batch(batch))
        assert np.isfinite(l0) and np.isfinite(l1) and l1 < l0, (l0, l1)

    @run("zero_infinity_memory")
    def _inf():
        import deepspeed_tpu
        from deepspeed_tpu.comm.topology import reset_topology
        from deepspeed_tpu.models import llama

        reset_topology()
        mcfg = llama.LlamaConfig(
            vocab_size=2048, hidden_size=512, intermediate_size=1536,
            num_layers=8, num_heads=8, num_kv_heads=4, max_seq_len=512)
        eng, _, _, _ = deepspeed_tpu.initialize(
            model=lambda ctx: llama.build(mcfg, ctx=ctx),
            config={"train_micro_batch_size_per_device": 2,
                    "gradient_accumulation_steps": 1, "steps_per_print": 0,
                    "optimizer": {"type": "adamw", "params": {"lr": 1e-3}},
                    "zero_optimization": {
                        "stage": 3, "sub_group_size": 4_000_000,
                        "offload_param": {"device": "cpu"},
                        "offload_optimizer": {"device": "cpu"}},
                    "activation_checkpointing": {"enabled": True},
                    "mesh": {"data": 1, "fsdp": 1}, "seed": 3}, seed=3)
        param_bytes = eng.model_spec.num_params * 4
        kinds = {x.sharding.memory_kind
                 for x in jax.tree_util.tree_leaves(eng.params)}
        assert kinds == {"pinned_host"}, kinds
        # the round-4 'done' criterion: peak HBM param bytes < total param
        # bytes — the grads program's device footprint must exclude the
        # host-resident masters (they are host args, streamed per layer)
        if eng._grads_jit is None:
            eng._grads_jit = eng._build_grads_fn()
        rng = np.random.default_rng(0)
        db = eng._put_gas_batch(
            {"input_ids": rng.integers(0, 2048, (2, 256), dtype=np.int32)})
        ma = eng._grads_jit.lower(
            eng.params, eng.scale_state, jnp.int32(0), eng._train_rng, db
        ).compile().memory_analysis()
        assert ma.argument_size_in_bytes < param_bytes / 4, \
            ma.argument_size_in_bytes
        assert ma.host_argument_size_in_bytes >= param_bytes, \
            ma.host_argument_size_in_bytes
        loss = float(eng.train_batch(
            {"input_ids": rng.integers(0, 2048, (2, 256), dtype=np.int32)}))
        assert np.isfinite(loss)

    @run("evoformer_sparse_perf")
    def _science():
        # perf evidence for the science kernels (round-4 weak #7): timed on
        # the real accelerator vs dense attention at the same shape; numbers
        # land in the smoke JSON
        from deepspeed_tpu.ops.evoformer import evoformer_attention
        from deepspeed_tpu.ops.sparse_attention import (
            blocksparse_attention,
            make_local_layout,
        )
        from deepspeed_tpu.ops.attention import xla_attention

        rng = np.random.default_rng(3)

        def timeit(f, *a, iters=10):
            o = f(*a)
            jax.block_until_ready(o)
            t0 = time.perf_counter()
            for _ in range(iters):
                o = f(*a)
            jax.block_until_ready(o)
            return (time.perf_counter() - t0) / iters * 1e3

        # evoformer: [B, S, R, H, D] MSA-row attention with pair biases
        q = jnp.asarray(rng.normal(size=(1, 8, 256, 4, 32)), jnp.bfloat16)
        b1 = jnp.asarray(rng.normal(size=(1, 8, 1, 1, 256)), jnp.float32)
        b2 = jnp.asarray(rng.normal(size=(1, 1, 4, 256, 256)), jnp.float32)
        evo = jax.jit(lambda q, b1, b2: evoformer_attention(
            q, q, q, (b1, b2), chunk_size=64))
        perf["evoformer_ms"] = round(timeit(evo, q, b1, b2), 2)

        # blocksparse local attention vs dense at seq 2048
        s, blk = 2048, 64
        layout = make_local_layout(s // blk, window=4)
        qs = jnp.asarray(rng.normal(size=(2, s, 8, 64)), jnp.bfloat16)
        sp = jax.jit(lambda q: blocksparse_attention(
            q, q, q, layout, blk, causal=True))
        dn = jax.jit(lambda q: xla_attention(q, q, q, causal=True))
        perf["sparse_local_ms"] = round(timeit(sp, qs), 2)
        perf["dense_same_shape_ms"] = round(timeit(dn, qs), 2)

    @run("ragged_tiled_serve")
    def _serve():
        from deepspeed_tpu.inference.engine import InferenceEngine
        from deepspeed_tpu.inference.ragged import (
            RaggedConfig,
            RaggedInferenceEngine,
        )
        from deepspeed_tpu.models import llama

        mcfg = llama.LlamaConfig(
            vocab_size=512, hidden_size=256, intermediate_size=688,
            num_layers=2, num_heads=4, num_kv_heads=2, max_seq_len=256)
        rng = np.random.default_rng(2)
        prompts = {i: rng.integers(0, 512, (int(L),), dtype=np.int32)
                   for i, L in enumerate([9, 17, 33])}
        # fp32: greedy argmax parity between the dense-cache and paged-pool
        # attention orders must not hinge on bf16 ties
        dense = InferenceEngine(lambda ctx: llama.build(mcfg, ctx=ctx),
                                dtype=jnp.float32, seed=0)
        want = {u: list(np.asarray(
            dense.generate(p[None], max_new_tokens=8))[0, len(p):])
            for u, p in prompts.items()}
        eng = RaggedInferenceEngine(
            model=lambda ctx: llama.build(mcfg, ctx=ctx), seed=0,
            dtype=jnp.float32,
            ragged_config=RaggedConfig(
                max_tokens_per_step=64, max_seqs=4, block_size=16,
                num_blocks=33, max_blocks_per_seq=8, prefill_tile=16))
        for u, p in prompts.items():
            eng.put(u, p, max_new_tokens=8)
        got = eng.generate_all()
        assert got == want, "tiled serve != dense greedy"

    ok = all(c["ok"] for c in checks.values())
    print(json.dumps({"smoke_ok": ok, "checks": checks, "perf": perf,
                      "total_s": round(time.perf_counter() - t_all, 1),
                      "backend": __import__("jax").default_backend()}))
    return 0 if ok else 1


# ------------------------------------------------------------- autotuning
# shared tiny model for autotune probe legs: the parent search computes the
# profile fingerprint from the SAME spec the probe children measure, so the
# persisted winner round-trips through initialize()/router lookup by key
_PROBE_MODEL = dict(vocab_size=512, hidden_size=128, intermediate_size=256,
                    num_layers=2, num_heads=4, num_kv_heads=2,
                    max_seq_len=256)
_PROBE_SEQ = 128


def _probe_model_builder():
    from deepspeed_tpu.models import llama

    cfg = llama.LlamaConfig(**_PROBE_MODEL)
    return cfg, (lambda ctx: llama.build(cfg, ctx=ctx))


def _set_dotted(d: dict, dotted: str, value):
    node = d
    parts = dotted.split(".")
    for p in parts[:-1]:
        node = node.setdefault(p, {})
    node[parts[-1]] = value


def _probe_train(overrides, steps):
    """One bounded train probe leg: tiny engine + stepscope, scored by
    goodput x MFU (samples/s standing in for MFU on backends without a
    peak-FLOPs model) x (1 + overlap fraction)."""
    import numpy as np

    import deepspeed_tpu
    from deepspeed_tpu.comm.topology import reset_topology
    from deepspeed_tpu.telemetry import TELEMETRY

    model_cfg, builder = _probe_model_builder()
    config = {
        "train_micro_batch_size_per_device": 2,
        "sequence_length": _PROBE_SEQ,
        "steps_per_print": 0,
        "optimizer": {"type": "adamw", "params": {"lr": 1e-3}},
        "zero_optimization": {"stage": 0},
        "mesh": {"data": -1},
        "telemetry": {"enabled": True,
                      "stepscope": {"enabled": True,
                                    "profile_interval_steps": 0}},
    }
    for name, value in overrides.items():
        _set_dotted(config, name, value)
    reset_topology()
    TELEMETRY.reset()
    engine, _, _, _ = deepspeed_tpu.initialize(model=builder, config=config)
    rng = np.random.default_rng(0)

    def batch():
        return {"input_ids": rng.integers(
            0, model_cfg.vocab_size,
            (engine.train_batch_size, _PROBE_SEQ), dtype=np.int32)}

    float(engine.train_batch(batch()))  # compile + settle
    t0 = time.perf_counter()
    loss = None
    for _ in range(max(steps, 1)):
        loss = engine.train_batch(batch())
    float(loss)  # settle before reading the clock
    dt = (time.perf_counter() - t0) / max(steps, 1)
    summary = engine.stepscope.summary()
    goodput = float(summary.get("goodput") or 0.0)
    mfu = float(summary.get("mfu") or 0.0)
    overlap = float(summary.get("overlap_fraction") or 0.0)
    samples_per_sec = engine.train_batch_size / dt
    engine.destroy()
    return {
        "score": goodput * (mfu if mfu > 0.0 else samples_per_sec)
        * (1.0 + overlap),
        "goodput": round(goodput, 4),
        "mfu": round(mfu, 6),
        "overlap_fraction": round(overlap, 4),
        "samples_per_sec": round(samples_per_sec, 2),
        "step_ms": round(dt * 1000, 2),
        "phase_seconds_total": summary.get("phase_seconds_total"),
    }


def _probe_serve(overrides, steps):
    """One bounded serving probe leg: tiny ragged engine on a pure-decode
    workload, scored by tokens/s x SLO-good fraction; the memory census
    (<= 5% unattributed) and token parity vs the plain host-staged path
    are HARD gates — a perf config that leaks or changes tokens is a
    non-result whatever its throughput."""
    import numpy as np

    from deepspeed_tpu import telemetry
    from deepspeed_tpu.inference.ragged import RaggedConfig, RaggedInferenceEngine
    from deepspeed_tpu.telemetry import SloMonitor, default_objectives

    model_cfg, builder = _probe_model_builder()
    n_req, prompt_len = 4, 16
    max_new = max(8, 4 * int(steps))
    block = 16
    mbs = -(-(prompt_len + max_new) // block)
    base = dict(max_tokens_per_step=64, max_seqs=n_req, block_size=block,
                num_blocks=n_req * mbs + 1, max_blocks_per_seq=mbs)
    rng = np.random.default_rng(0)
    prompts = [rng.integers(0, model_cfg.vocab_size, (prompt_len,),
                            dtype=np.int32) for _ in range(n_req)]

    def build(device_state=True, **over):
        kw = dict(base)
        kw.update(over)
        return RaggedInferenceEngine(
            model=builder, seed=0,
            ragged_config=RaggedConfig(device_state=device_state, **kw))

    def run(engine, tag):
        for i, p in enumerate(prompts):
            engine.put((tag, i), p, max_new_tokens=max_new)
        return engine.generate_all()

    tel = telemetry.configure(enabled=True, memledger={"enabled": True},
                              hbm_watermarks=False)
    try:
        engine = build(**overrides)
        run(engine, "warm")  # compiles every bucket this workload hits
        t0 = time.perf_counter()
        out = run(engine, "run")
        dt = max(time.perf_counter() - t0, 1e-9)
        toks = sum(len(v) for v in out.values())
        tokens_per_s = toks / dt
        # census while the candidate is the only live engine: its pool +
        # params must be attributed, or the config is disqualified
        led = tel.memledger
        census = led.census(update_state=False) if led is not None else None
        census_ok = (census is None
                     or census["unattributed_fraction"] <= 0.05)
        # SLO burn over the measured leg: per-token decode latency samples
        mon = SloMonitor(default_objectives(), tel.registry)
        per_tok = dt / max(toks, 1)
        for i in range(n_req):
            mon.record("decode_latency", per_tok, now=float(i))
        slo = mon.stats("decode_latency", now=float(n_req))
    finally:
        telemetry.configure(enabled=False)

    # token parity: the candidate's dispatch path vs the plain host-staged
    # baseline under the SAME codec/cache knobs, greedy + seeded sampling
    def parity_run(engine):
        for i, p in enumerate(prompts[:3]):
            kw = {} if i == 0 else dict(temperature=0.9, top_k=20,
                                        top_p=0.9, seed=7 + i)
            engine.put(i, p, max_new_tokens=6, **kw)
        return engine.generate_all()

    plain = {k: v for k, v in overrides.items() if k != "prefill_tile"}
    parity_ok = (parity_run(build(device_state=False, **plain))
                 == parity_run(build(**overrides)))

    return {
        "score": tokens_per_s * slo["good_fraction"],
        "tokens_per_s": round(tokens_per_s, 2),
        "slo_good_fraction": round(slo["good_fraction"], 4),
        "slo_burn_rate": round(slo["burn_rate"], 4),
        "census_unattributed_fraction":
            None if census is None else census["unattributed_fraction"],
        "census_ok": census_ok,
        "parity_ok": parity_ok,
        "tokens": toks,
        "wall_s": round(dt, 3),
    }


def probe_main():
    """Child process: ONE bounded autotuner probe leg (``--mode probe``).

    JSON-only output. An OOM/compile failure inside the leg prints a
    structured ``{"error": ...}`` line and exits 0 — the PR 6 child-error
    discipline: rc != 0 is reserved for a dead interpreter, and the hard
    wall-clock timeout lives in the parent (run_probe_subprocess)."""
    try:
        spec = json.loads(os.environ.get("BENCH_PROBE_SPEC") or "{}")
    except json.JSONDecodeError as e:
        _fail_json({"reason": f"bad BENCH_PROBE_SPEC: {e}"})
        return 0
    kind = spec.get("kind", "train")
    overrides = dict(spec.get("overrides") or {})
    steps = int(spec.get("steps", 3))
    try:
        if kind == "train":
            out = _probe_train(overrides, steps)
        elif kind == "serve":
            out = _probe_serve(overrides, steps)
        else:
            _fail_json({"reason": f"unknown probe kind {kind!r}"})
            return 0
    except Exception as e:  # OOM / compile failure = structured result
        _fail_json({"reason": f"{type(e).__name__}: {e}"[:500],
                    "kind": kind, "overrides": overrides})
        return 0
    # what the leg ran on: a search parent that has not touched jax (it
    # must not hold the chip its probe children need) learns it from here
    import jax

    from deepspeed_tpu.autotuning import device_memory_bytes, profiles

    out.update(error=None, kind=kind, overrides=overrides, steps=steps,
               device={"topology": profiles.current_topology(),
                       "count": jax.device_count(),
                       "bytes_limit": device_memory_bytes()})
    print(json.dumps(out))
    return 0


def run_probe_subprocess(spec: dict, timeout: float | None = None):
    """Bounded probe leg with a hard wall-clock timeout; returns
    ``(result, None)`` or ``(None, structured_error)``."""
    t = float(spec.get("timeout_s") or timeout or 180.0)
    result, err = _run_flagged_subprocess(
        "BENCH_PROBE", t, extra_env={"BENCH_PROBE_SPEC": json.dumps(spec)})
    if result is not None and result.get("error"):
        return None, result["error"]
    return result, err


def autotune_bench_main():
    """Child process: the end-to-end measurement-driven autotune loop on a
    tiny model (``--mode autotune``, the CI smoke budget).

    Search both engines over trimmed knob sets via bounded probe legs
    (each leg a run_probe_subprocess child sharing the jit cache), with a
    synthetic headroom budget sized so at least one candidate is pruned
    before compiling; persist the winners as content-keyed profiles; then
    prove the round trip — a fresh ``initialize`` picks the tuned train
    knobs up (and an explicitly-written config key beats them), and the
    serving router loads the serve profile at startup. One JSON line.

    One process per chip: while probe children run, this process does not
    touch a jax backend (the searches learn the device from their probes);
    it initializes one only for the round trips, after the last child."""
    import deepspeed_tpu
    from deepspeed_tpu import telemetry
    from deepspeed_tpu.autotuning import (
        SERVE,
        TRAIN,
        KnobSearch,
        probe_model_info,
        profiles,
    )
    from deepspeed_tpu.comm.topology import reset_topology
    from deepspeed_tpu.telemetry import TELEMETRY

    t_all = time.perf_counter()
    runs_dir = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                            "runs")
    profile_dir = (os.environ.get("BENCH_AUTOTUNE_DIR")
                   or os.path.join(runs_dir, "autotune"))
    steps = int(os.environ.get("BENCH_AUTOTUNE_STEPS", 3))
    _, builder = _probe_model_builder()
    info = probe_model_info(builder)
    fp = profiles.model_fingerprint(info)
    # counters (autotune_{trials,pruned,failed}_total) land in the registry
    telemetry.configure(enabled=True, hbm_watermarks=False)

    def runner(kind, overrides, probe_steps):
        return run_probe_subprocess({
            "kind": kind, "overrides": overrides, "steps": probe_steps,
            "timeout_s": float(os.environ.get("BENCH_AUTOTUNE_PROBE_TIMEOUT",
                                              120.0))})

    # synthetic headroom budget: the CPU backend reports no bytes_limit, so
    # an explicit budget stands in for the TPU's measured one — sized so
    # micro_batch=8 fits and the 16 corner is pruned without compiling
    est8 = info.state_bytes(0, 1) + info.activation_bytes(8, _PROBE_SEQ)
    limit = est8 * 1.3 / 0.9

    train = KnobSearch(
        TRAIN, model_info=info, steps=steps, seq_len=_PROBE_SEQ,
        memory_bytes=limit,
        knob_names=("train_micro_batch_size_per_device",
                    "activation_checkpointing.enabled"),
        probe_runner=runner, profile_dir=profile_dir).tune()
    serve = KnobSearch(
        SERVE, model_info=info, steps=steps,
        knob_names=("prefill_tile",),
        probe_runner=runner, profile_dir=profile_dir).tune()

    # --- round trip 1: a fresh initialize() loads the train profile ------
    import jax

    topo = profiles.current_topology()
    reset_topology()
    TELEMETRY.reset()
    telemetry.configure(enabled=True, hbm_watermarks=False)
    raw = {
        "sequence_length": _PROBE_SEQ,
        "steps_per_print": 0,
        "optimizer": {"type": "adamw", "params": {"lr": 1e-3}},
        "autotuning": {"enabled": True, "profile_dir": profile_dir},
    }
    tuned_mb = train["best_overrides"].get(
        "train_micro_batch_size_per_device")
    if tuned_mb is None:  # profile carries no batch knob: pin one ourselves
        raw["train_micro_batch_size_per_device"] = 2
    engine, _, _, _ = deepspeed_tpu.initialize(model=builder, config=raw)

    def _cfg_get(cfg, dotted):
        node = cfg
        for part in dotted.split("."):
            node = getattr(node, part)
        return node

    reloaded_by_engine = all(
        _cfg_get(engine.config, k) == v
        for k, v in train["best_overrides"].items())
    engine_gauge_ok = ("tuned_profile_loaded"
                      in TELEMETRY.registry.render_prometheus())
    engine.destroy()

    # --- round trip 2: an explicitly-written config key beats the profile
    reset_topology()
    raw2 = dict(raw, train_micro_batch_size_per_device=1)
    engine2, _, _, _ = deepspeed_tpu.initialize(model=builder, config=raw2)
    config_wins_ok = engine2.config.train_micro_batch_size_per_device == 1
    engine2.destroy()

    # --- round trip 3: the serving router loads the serve profile --------
    from deepspeed_tpu.inference.ragged import RaggedConfig, RaggedInferenceEngine
    from deepspeed_tpu.serving.engine_loop import EngineLoop
    from deepspeed_tpu.serving.router import ReplicaRouter, RouterConfig

    prof = profiles.load_profile(profile_dir, subsystem=SERVE,
                                 fingerprint=fp, workload="default")
    rcfg = RaggedConfig(max_tokens_per_step=64, max_seqs=4, block_size=16,
                        num_blocks=17, max_blocks_per_seq=4)
    applied = (profiles.apply_serving_profile(rcfg, prof)
               if prof else {"applied": {}, "skipped": {}})
    serve_applied_ok = all(getattr(rcfg, k) == v
                           for k, v in serve["best_overrides"].items())
    sengine = RaggedInferenceEngine(model=builder, ragged_config=rcfg,
                                    seed=0)
    router = ReplicaRouter(
        [EngineLoop(sengine, name="replica-0")],
        RouterConfig(autotune_profile_dir=profile_dir,
                     autotune_fingerprint=fp))
    reloaded_by_router = (router.tuned_overrides()
                          == serve["best_overrides"])
    router.refresh_metrics()
    router_gauge_ok = ('tuned_profile_loaded{kind="serving"}'
                       in TELEMETRY.registry.render_prometheus())

    def _leg(summary):
        return {k: summary[k] for k in (
            "best_overrides", "best_score", "baseline_score", "trials",
            "pruned", "failed", "gate_failures", "gate_violations_accepted",
            "profile_path")}

    autotune_ok = bool(
        train["pruned"] + serve["pruned"] >= 1
        and train["best_score"] >= train["baseline_score"]
        and serve["best_score"] >= serve["baseline_score"]
        and train["gate_violations_accepted"] == 0
        and serve["gate_violations_accepted"] == 0
        and reloaded_by_engine and engine_gauge_ok and config_wins_ok
        and serve_applied_ok and reloaded_by_router and router_gauge_ok)
    print(json.dumps({
        "error": None,
        "autotune_ok": autotune_ok,
        "backend": jax.default_backend(),
        "fingerprint": fp,
        "topology": topo,
        "train": _leg(train),
        "serve": _leg(serve),
        "pruned_total": train["pruned"] + serve["pruned"],
        "gate_violations_accepted": (train["gate_violations_accepted"]
                                     + serve["gate_violations_accepted"]),
        "profile": {
            "dir": profile_dir,
            "reloaded_by_engine": reloaded_by_engine,
            "engine_gauge_ok": engine_gauge_ok,
            "config_wins_ok": config_wins_ok,
            "serve_applied": applied["applied"],
            "serve_applied_ok": serve_applied_ok,
            "reloaded_by_router": reloaded_by_router,
            "router_gauge_ok": router_gauge_ok,
        },
        "total_s": round(time.perf_counter() - t_all, 1),
    }))
    return 0 if autotune_ok else 1


def run_autotune_subprocess(timeout: float = 900.0):
    return _run_flagged_subprocess("BENCH_AUTOTUNE", timeout)


def enable_compile_cache():
    """The one cache rule (deepspeed_tpu/utils/compile_cache.py), for the
    children that compile; imported late so the parent stays off jax."""
    from deepspeed_tpu.utils.compile_cache import enable_compile_cache as on

    return on()


def main():
    if "--mode" in sys.argv:
        mode = sys.argv[sys.argv.index("--mode") + 1:][:1]
        if mode == ["chaos"]:
            result, err = run_chaos_subprocess()
            if result is None:
                print(f"chaos bench failed:\n{_err_text(err)}",
                      file=sys.stderr)
                _fail_json(err)
                return 1
            print(json.dumps(result))
            return 0 if result.get("chaos_ok") else 1
        if mode == ["train-anatomy"]:
            result, err = run_train_anatomy_subprocess()
            if result is None:
                print(f"train-anatomy bench failed:\n{_err_text(err)}",
                      file=sys.stderr)
                _fail_json(err)
                return 1
            print(json.dumps(result))
            return 0
        if mode == ["train-chaos"]:
            result, err = run_train_chaos_subprocess()
            if result is None:
                print(f"train-chaos bench failed:\n{_err_text(err)}",
                      file=sys.stderr)
                _fail_json(err)
                return 1
            print(json.dumps(result))
            return 0 if result.get("train_chaos_ok") else 1
        if mode == ["pipeline"]:
            result, err = run_pipeline_subprocess()
            if result is None:
                print(f"pipeline bench failed:\n{_err_text(err)}",
                      file=sys.stderr)
                _fail_json(err)
                return 1
            print(json.dumps(result))
            return 0 if result.get("pipeline_ok") else 1
        if mode == ["fleet"]:
            result, err = run_fleet_subprocess()
            if result is None:
                print(f"fleet bench failed:\n{_err_text(err)}",
                      file=sys.stderr)
                _fail_json(err)
                return 1
            print(json.dumps(result))
            return 0 if result.get("fleet_ok") else 1
        if mode == ["probe"]:
            # one bounded autotuner probe leg; spec JSON via --probe-spec
            spec = {}
            if "--probe-spec" in sys.argv:
                val = sys.argv[sys.argv.index("--probe-spec") + 1:][:1]
                try:
                    spec = json.loads(val[0]) if val else {}
                except json.JSONDecodeError as e:
                    print(f"bench: bad --probe-spec: {e}", file=sys.stderr)
                    return 2
            result, err = run_probe_subprocess(spec)
            if result is None:
                print(f"probe failed:\n{_err_text(err)}", file=sys.stderr)
                _fail_json(err)
                return 1
            print(json.dumps(result))
            return 0
        if mode == ["autotune"]:
            # end-to-end measurement-driven autotune loop (docs/AUTOTUNING.md)
            result, err = run_autotune_subprocess()
            if result is None:
                print(f"autotune bench failed:\n{_err_text(err)}",
                      file=sys.stderr)
                _fail_json(err)
                return 1
            print(json.dumps(result))
            return 0 if result.get("autotune_ok") else 1
        if mode != ["serving"]:
            print(f"bench: unknown --mode {mode or '(missing)'}; "
                  "supported: serving, chaos, train-anatomy, "
                  "train-chaos, pipeline, fleet, probe, autotune",
                  file=sys.stderr)
            return 2
        if "--tenants" in sys.argv:
            # multi-tenant metering trial: N tenants (one batch-class hog +
            # interactive bystanders) against one replica with the cost
            # meter on — per-tenant tokens/s and block-seconds share, the
            # occupancy-integral check, per-class SLO series and the
            # fair-share verdict in the JSON line (docs/OBSERVABILITY.md)
            val = sys.argv[sys.argv.index("--tenants") + 1:][:1]
            if not val or not val[0].isdigit():
                print("bench: --tenants needs an integer", file=sys.stderr)
                return 2
            result, err = run_tenants_subprocess(int(val[0]))
            if result is None:
                print(f"tenant bench failed:\n{_err_text(err)}",
                      file=sys.stderr)
                _fail_json(err)
                return 1
            print(json.dumps(result))
            return 0 if result.get("fair_share_ok") else 1
        if "--disagg" in sys.argv:
            # disaggregated prefill/decode cluster trial (docs/SERVING.md):
            # parity verdict, KV-transfer volume, handoff latency, cluster
            # prefix hit rate, autoscale policy check
            result, err = run_disagg_subprocess()
            if result is None:
                print(f"disagg bench failed:\n{_err_text(err)}",
                      file=sys.stderr)
                _fail_json(err)
                return 1
            print(json.dumps(result))
            return 0 if result.get("error") is None else 1
        if "--shared-prefix-tokens" in sys.argv:
            # shared-prompt workload: prompts share an N-token prefix and
            # the engine serves with the block-level prefix cache enabled
            val = sys.argv[sys.argv.index("--shared-prefix-tokens") + 1:][:1]
            if not val or not val[0].isdigit():
                print("bench: --shared-prefix-tokens needs an integer",
                      file=sys.stderr)
                return 2
            os.environ["BENCH_SERVING_SHARED_PREFIX"] = val[0]
        if "--kv-tier" in sys.argv:
            # hierarchical KV-cache tiering trial: tiny HBM pool + host/disk
            # tiers, repeated shared-prefix prompts, occurrence-parity and
            # demotion/promotion/prefetch counters in the JSON verdict
            os.environ["BENCH_SERVING_KV_TIER"] = "1"
        if "--kv-quant" in sys.argv:
            # low-bit KV serving trial: the tiered workload with an int8
            # (or fp8: `--kv-quant fp8`) pool — resident-block multiplier,
            # combined tier hit rate over quantized payloads, and the
            # quant-vs-fp drift verdict in the JSON line
            val = sys.argv[sys.argv.index("--kv-quant") + 1:][:1]
            codec = val[0] if val and val[0] in ("int8", "fp8") else "int8"
            os.environ["BENCH_SERVING_KV_QUANT"] = codec
        result, err = run_serving_subprocess()
        if result is None:
            print(f"serving bench failed:\n{_err_text(err)}", file=sys.stderr)
            _fail_json(err)
            return 1
        print(json.dumps(result))
        return 0
    if "--smoke" in sys.argv or os.environ.get("BENCH_SMOKE"):
        enable_compile_cache()
        return smoke_main()
    if os.environ.get("BENCH_PROBE"):
        # checked before BENCH_AUTOTUNE: the autotune orchestrator's flag
        # leaks into its probe children's environments, and a probe leg
        # must never recurse into orchestration. Probe legs share the jit
        # cache so repeated tiny-model compiles amortize across the search.
        enable_compile_cache()
        return probe_main()
    if os.environ.get("BENCH_AUTOTUNE"):
        enable_compile_cache()
        return autotune_bench_main()
    if os.environ.get("BENCH_TRAIN_CHAOS_WORKER"):
        # checked before BENCH_TRAIN_CHAOS: the orchestrator's own env flag
        # leaks into inherited worker environments unless popped there, and
        # a worker must never recurse into orchestration
        return train_chaos_worker_main()
    if os.environ.get("BENCH_TRAIN_CHAOS"):
        # no jit cache: workers are SIGKILL'd mid-write by design and must
        # not leave torn entries in the shared compile cache
        return train_chaos_main()
    if os.environ.get("BENCH_CHAOS"):
        # no jit cache: the chaos child runs a deliberately tiny model and
        # must not pollute the shared compile cache with fault-path programs
        return chaos_bench_main()
    if os.environ.get("BENCH_PIPELINE"):
        # no jit cache: per-stage programs are tiny and the parity verdict
        # must not hinge on a cache-deserialized fused baseline
        return pipeline_bench_main()
    if os.environ.get("BENCH_FLEET_WORKER"):
        # checked before BENCH_FLEET for the same reason as the train-chaos
        # worker: the orchestrator flag leaks into worker environments and
        # a fleet worker must never recurse into orchestration
        enable_compile_cache()
        return fleet_worker_main()
    if os.environ.get("BENCH_FLEET"):
        # the orchestrator itself never touches jax and runs its two
        # workers one after the other; they share the compile cache, so
        # the second reuses the first's programs
        return fleet_bench_main()
    if os.environ.get("BENCH_SERVING_DISAGG"):
        enable_compile_cache()
        return disagg_bench_main()
    if os.environ.get("BENCH_TENANTS"):
        # checked before BENCH_SERVING: the tenant leg is its own child and
        # must never fall through into the plain serving trial
        enable_compile_cache()
        return tenant_bench_main()
    if os.environ.get("BENCH_SERVING"):
        enable_compile_cache()
        return serving_bench_main()
    if os.environ.get("BENCH_SERVE"):
        enable_compile_cache()
        return serve_trial_main()
    if os.environ.get("BENCH_TRAIN_ANATOMY"):
        # no shared jit cache: recompile accounting is part of what this
        # trial measures, so cold compiles must be real
        return train_anatomy_main()
    if os.environ.get("BENCH_LEARN"):
        enable_compile_cache()
        return learn_trial_main()
    if os.environ.get("BENCH_INFINITY"):
        enable_compile_cache()
        return infinity_trial_main()
    if os.environ.get("BENCH_TRIAL"):
        enable_compile_cache()
        return trial_main()

    info = probe_device()
    if info["backend"] != "tpu":
        # a time or an MFU from a CPU is not a device metric: no result
        print(f"bench: the default run needs a TPU chip; jax found "
              f"{info['backend']!r} ({info['kind']!r})", file=sys.stderr)
        return 1

    _, hbm = chip_spec(info["kind"])
    steps = int(os.environ.get("BENCH_STEPS", 10))

    # explicit shape overrides pin a single config (no ladder)
    shape_vars = ("BENCH_HIDDEN", "BENCH_FFN", "BENCH_LAYERS", "BENCH_VOCAB",
                  "BENCH_HEADS", "BENCH_KV", "BENCH_BATCH", "BENCH_SEQ")
    if any(v in os.environ for v in shape_vars):
        e = os.environ
        rung = (int(e.get("BENCH_HIDDEN", 2048)), int(e.get("BENCH_FFN", 5632)),
                int(e.get("BENCH_LAYERS", 8)), int(e.get("BENCH_VOCAB", 32768)),
                int(e.get("BENCH_HEADS", 16)), int(e.get("BENCH_KV", 8)),
                int(e.get("BENCH_BATCH", 8)), int(e.get("BENCH_SEQ", 2048)))
        result, err = run_trial_subprocess(rung, steps=steps)
        if result is None:
            print(f"pinned bench config {rung} failed:\n{_err_text(err)}",
                  file=sys.stderr)
            _fail_json(err)
            return 1
        print(json.dumps(result))
        return 0

    errors = []
    for rung in candidate_ladder(hbm):
        result, err = run_trial_subprocess(rung, steps=steps)
        if result is not None:
            # the north-star path is ZeRO-3 (BASELINE: Llama-3-8B stage 3);
            # report its MFU on the same rung alongside the headline number
            # (single-chip stage 3 measures the code path's overhead — the
            # sharding itself needs the fsdp axis of a real pod)
            failed = []
            r3, err3 = run_trial_subprocess(rung, steps=steps, zero_stage=3)
            if r3 is not None:
                result["mfu_zero3"] = r3["value"]
                result["tokens_per_s_zero3"] = r3.get("tokens_per_s")
            else:
                failed.append(("stage-3", err3))
            # serving ladder rung: ragged continuous batching vs dense padding
            # (reference FastGen effective-throughput headline); then the
            # learning-evidence rung (real-text byte LM, loss must descend)
            # and the ZeRO-Infinity rung (fp32 training state > HBM,
            # host-resident masters streamed per layer/sub-group)
            for name, run in (("serving", run_serve_subprocess),
                              ("learning", run_learn_subprocess),
                              ("infinity", run_infinity_subprocess)):
                out, err = run()
                if out is not None:
                    result.update(out)
                else:
                    failed.append((name, err))
            for name, err in failed:
                print(f"{name} rung failed:\n{_err_text(err)}",
                      file=sys.stderr)
            result["failed_rungs"] = [name for name, _ in failed]
            print(json.dumps(result))
            return 1 if failed else 0
        errors.append(
            f"config {rung}: {_err_text(err)[-300:] if err else 'unknown'}")
        print(f"bench rung {rung} failed, backing off:\n{_err_text(err)}",
              file=sys.stderr)
    print("all bench rungs failed:\n" + "\n".join(errors), file=sys.stderr)
    _fail_json({"reason": "all bench rungs failed", "rungs": errors})
    return 1


if __name__ == "__main__":
    sys.exit(main())
