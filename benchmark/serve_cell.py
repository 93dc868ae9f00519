"""A serving cell: the model behind ``serving.build_server``, load from a child.

``ServeRig`` is the set-up (weights from the seed in one jitted call, the
engine, every step program dispatched once, the HTTP server, a short replay
of the mix); ``window`` runs one measured window; ``check`` compares what was
served with the plain reference; ``close`` drains. ``run`` strings them
together for ``run.py``; ``sweep.py`` runs several windows on one rig.
"""

from __future__ import annotations

import gc
import json
import os
import random
import subprocess
import sys
import time

import reduce
import trace_reduce
import trafficgen
from cellspec import kernels, model

# the serving `correct` check, a copy of chip_smoke.py's (PR 21), see there
# for the argument: teacher-forced on the served history, the served token's
# logit within 4x the deviation a plain bf16 forward shows from the float32
# "highest"-precision reference, and an exact greedy match rate >= 0.9
LOGIT_GAP_NOISE_FACTOR = 4.0
MATCH_RATE_MIN = 0.9
CHECK_SAMPLE = 3
CHECK_LEN_BUCKET = 1024  # reference sequences are padded to a multiple of it
TRACE_SLICE_S = 4.0
# a window during which the load generator's own process stood still for this
# long (``loadgen.heartbeat``) is void and is measured again, once: the
# machine stood still, not the server (a paused virtual machine froze both
# for 11.5 s in one of ten windows of PR 22's steadiness runs, PERF.md 6)
FREEZE_S = 0.5
VOID_WINDOWS_MAX = 1

ENGINE_COUNTERS = ("dispatch_count", "tokens_emitted", "tokens_scheduled",
                   "tokens_padded", "preemptions", "program_cold_dispatches",
                   "host_stage_ns")


def say(**fields) -> None:
    """A progress line on stdout; the contract's line is the last one."""
    print(json.dumps(fields), flush=True)


def compile_counters() -> dict:
    """What telemetry/compile_watch.py counts (jax's own compile events)."""
    from deepspeed_tpu import telemetry

    metrics = telemetry.snapshot()["metrics"]

    def total(name, field):
        return sum(s[field] for s in
                   (metrics.get(name) or {}).get("series", []))

    return {"compile_seconds": total("jit_compile_seconds", "sum"),
            "compiles": total("jit_cache_misses_total", "value"),
            "cache_hits": total("persistent_cache_hits_total", "value"),
            "cache_misses": total("persistent_cache_misses_total", "value")}


class ServeRig:
    def __init__(self, spec: dict, seed: int, out_dir: str):
        import jax
        import jax.numpy as jnp

        from deepspeed_tpu import serving, telemetry
        from deepspeed_tpu.inference.ragged import (RaggedConfig,
                                                    RaggedInferenceEngine)

        self.spec, self.seed, self.out_dir = spec, seed, out_dir
        os.makedirs(out_dir, exist_ok=True)
        if not telemetry.TELEMETRY.enabled:  # /metrics and compile counters
            telemetry.configure(enabled=True)
        self.family, self.cfg, self.reference = model(spec)
        serve = spec["config"]["serve"]
        sizes = {**serve["engine"], **spec["cell"].get("engine", {})}
        self.rcfg = RaggedConfig(**sizes)
        dtype = getattr(jnp, serve["dtype"])
        t0 = time.perf_counter()
        family, cfg = self.family, self.cfg
        params = jax.jit(lambda key: jax.tree_util.tree_map(
            lambda x: x.astype(dtype), family.init_params(cfg, key)))(
                jax.random.PRNGKey(seed))
        self.engine = RaggedInferenceEngine(
            lambda ctx: family.build(cfg, ctx=ctx), self.rcfg, dtype=dtype,
            params=params, seed=seed)
        self.engine.warmup()
        jax.block_until_ready(self.engine.params)
        t1 = time.perf_counter()
        programs = self._dispatch_every_program()
        t2 = time.perf_counter()
        self.frontend, self.router, self.loops = serving.build_server(
            [self.engine],
            router_cfg=serving.RouterConfig(**serve.get("router", {})))
        self._closed = False
        self._replay_warm()
        say(phase="setup", weights_engine_s=t1 - t0, programs=programs,
            programs_s=t2 - t1, replay_s=time.perf_counter() - t2,
            engine=sizes, compile=compile_counters())

    # ------------------------------------------------------------ warm-up
    def _dispatch_every_program(self) -> int:
        """Run each step program the scheduler can pick, once, through the
        engine's ``put``/``step``: for every decode bucket (0 and 4, 8, ...,
        ``max_seqs``) enough decoding sequences to select it, and beside them
        a prompt of each tile count the step's token budget allows. The program zoo
        is a function of the engine's sizes alone, so this covers whatever
        the traffic can make the scheduler dispatch (one table width:
        ``max_blocks_per_seq`` <= 64)."""
        import jax.numpy as jnp

        eng, rc = self.engine, self.rcfg
        tile, budget = rc.prefill_tile, rc.max_tokens_per_step
        if not tile or rc.max_blocks_per_seq > 64:
            raise SystemExit("benchmark: the warm-up enumerates the tiled "
                             "step programs of one table width; this engine "
                             "size has others")
        pool = (rc.num_blocks - 1) * rc.block_size
        new_tokens = min(256, rc.max_seq_len - 16,
                         (pool - 2 * budget) // rc.max_seqs - rc.block_size)
        buckets, b = [0], 4
        while b < rc.max_seqs:
            buckets.append(b)
            b *= 2
        buckets.append(rc.max_seqs)
        uids = iter(range(10**9))
        decoders: list = []
        before = len(eng._dev_step_jits)

        def decoding(uid) -> bool:
            seq = eng.get_request(uid)
            return seq is not None and not seq.finished and seq.in_decode

        def settle(n: int) -> None:
            """Exactly ``n`` live sequences, all of them decoding."""
            for _ in range(10_000):
                decoders[:] = [u for u in decoders
                               if not eng.get_request(u).finished]
                while len(decoders) < n:
                    uid = next(uids)
                    eng.put(uid, [1 + uid % 7] * 8, max_new_tokens=new_tokens)
                    decoders.append(uid)
                if all(decoding(u) for u in decoders):
                    return
                eng.step()
            raise SystemExit("benchmark: warm-up decoders never settled")

        for prev, nd in zip([0] + buckets, buckets):
            # any count in (prev, nd] runs the nd-row program; the smallest
            # leaves slots free for the prompt beside the decoders
            live = prev + 1 if nd else 0
            cap = (budget - nd) // tile
            tiles = sorted({t for t in (1, 2, 4, 8, 16, 32) if t <= cap} | {cap})
            for nt in [t for t in tiles if t >= 1]:
                settle(live)
                probe = next(uids)
                eng.put(probe, [3] * min(nt * tile, rc.max_seq_len - 2),
                        max_new_tokens=1)
                for _ in range(64):
                    eng.step()
                    if probe in eng.finished_uids:
                        break
            settle(live)
            eng.step()
            eng.step()
        for uid in decoders:
            eng.cancel(uid)
        for _ in range(256):
            if not eng.has_work:
                break
            eng.step()
        if eng.has_work or eng.allocator.free_blocks != rc.num_blocks - 1:
            raise SystemExit("benchmark: the engine did not come back whole "
                             "from the warm-up")
        # the block-table delta upload compiles once per power-of-two count
        # of dirtied rows (``_sync_bt``); how many rows a step dirties is the
        # traffic's business, so run each size once on the padding row, which
        # is what its own padding entries write
        rows = 1
        while True:
            eng._bt_dev = eng._bt_row_jit(
                eng._bt_dev, jnp.full(rows, rc.max_seqs, jnp.int32),
                jnp.zeros((rows, rc.max_blocks_per_seq), jnp.int32))
            if rows >= rc.max_seqs:
                break
            rows *= 2
        return len(eng._dev_step_jits) - before

    def _replay_warm(self) -> None:
        """A few requests of the mix (its own stream of the seed) over HTTP,
        all at once: the front end's threads, the streaming path and the
        row-update programs run before the window does."""
        mix = self.spec["mix"]
        recs = self._loadgen({"mode": "burst", "n": mix["warm_requests"],
                              "stream": "warm", "seconds": 0.0,
                              "t0": time.monotonic()}, "warm")()["records"]
        bad = [r for r in recs if not reduce.ok(r)]
        if bad or len(recs) != mix["warm_requests"]:
            raise SystemExit(f"benchmark: warm-up requests failed: {bad[:2]}")
        for _ in range(200):
            if not self.engine.has_work:
                return
            time.sleep(0.05)
        raise SystemExit("benchmark: the engine did not idle after warm-up")

    # ------------------------------------------------------------- window
    def _loadgen(self, fields: dict, tag: str):
        """Start the load generator child; returns the function that waits
        for its end and reads what it wrote (``kill=True``: ends it instead,
        so that a run that fails leaves no process behind)."""
        spec_path = os.path.join(self.out_dir, f"loadgen_{tag}_spec.json")
        out_path = os.path.join(self.out_dir, f"loadgen_{tag}.json")
        with open(spec_path, "w") as f:
            json.dump({"host": self.frontend.host, "port": self.frontend.port,
                       "mix": self.spec["mix"], "seed": self.seed,
                       "vocab": self.cfg.vocab_size, **fields}, f)
        env = {k: v for k, v in os.environ.items()
               if not k.startswith(("JAX_", "XLA_", "TPU_"))}
        proc = subprocess.Popen(
            [sys.executable, os.path.join(self.spec["base"], "loadgen.py"),
             spec_path, out_path], env=env, stdout=subprocess.DEVNULL)
        return lambda kill=False: self._collect(proc, out_path, fields, kill)

    def _collect(self, proc, out_path: str, fields: dict, kill: bool):
        limit = (fields["seconds"] + self.spec["mix"]["lead_seconds"]
                 + self.spec["mix"]["grace_seconds"] + 60.0)
        try:
            rc = proc.wait(timeout=0 if kill else limit)
        except subprocess.TimeoutExpired:
            proc.kill()
            proc.wait()
            if kill:
                return None
            raise SystemExit("benchmark: the load generator overran")
        if kill:
            return None
        if rc != 0:
            raise SystemExit(f"benchmark: the load generator exited {rc}")
        with open(out_path) as f:
            return json.load(f)

    def counters(self) -> dict:
        out = {k: getattr(self.engine, k) for k in ENGINE_COUNTERS}
        out.update(compile_counters())
        return out

    def window(self, seconds: float, trace: bool, rate: float | None = None,
               tag: str = "window", seed: int | None = None) -> dict:
        """One measured window; returns records, counter deltas, the reduced
        trace (``trace``) and the window's start on the host clock."""
        import jax

        mix, cell = self.spec["mix"], self.spec["cell"]
        fields = {"stream": 0, "seconds": seconds,
                  "seed": self.seed if seed is None else seed,
                  "t0": time.monotonic() + mix["lead_seconds"] + 2.0,
                  "sample_hz": 4 if trace else 0}
        if mix["kind"] == "open_loop":
            fields.update(mode="open", rate=rate or cell["rate"])
        else:
            fields.update(mode="closed", clients=cell["clients"])
        collect = self._loadgen(fields, tag)
        t0 = fields["t0"]

        def sleep_until(t: float) -> None:
            time.sleep(max(0.0, t - time.monotonic()))

        try:
            sleep_until(t0)
            t_window = time.perf_counter()
            c0 = self.counters()
            trace_dir = os.path.join(self.out_dir, "trace_" + tag)
            if trace:
                sleep_until(t0 + max(0.0, (seconds - TRACE_SLICE_S) / 2))
                with trace_reduce.recording(trace_dir):
                    time.sleep(min(TRACE_SLICE_S, seconds / 2))
            sleep_until(t0 + seconds)
            c1 = self.counters()
            peak = max((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
                       for d in jax.devices())
            got = collect()
        except BaseException:
            collect(kill=True)
            raise
        return {"records": got["records"], "samples": got["samples"],
                "stalls": got["stalls"],
                "counters": {k: c1[k] - c0[k] for k in c1}, "trace": None,
                "trace_dir": trace_dir if trace else None,
                "seconds": seconds, "t_window": t_window,
                "memory_peak_bytes": peak,
                "open_loop": mix["kind"] == "open_loop"}

    def reduce_trace(self, win: dict) -> None:
        """Fill ``win["trace"]`` from the window's recorded slice. Called
        after the last window: parsing a trace is seconds of Python beside
        the engine loop."""
        if win["trace_dir"]:
            win["trace"] = trace_reduce.reduce_dir(win["trace_dir"],
                                                   kernels(self.spec))

    def idle(self, limit: float = 90.0) -> None:
        """Wait until the engine has nothing left of the last window."""
        t_end = time.monotonic() + limit
        while self.engine.has_work and time.monotonic() < t_end:
            time.sleep(0.1)

    # -------------------------------------------------------------- close
    def close(self) -> None:
        """Stop admitting, let the loops finish, close the listener."""
        if self._closed:
            return
        self._closed = True
        self.router.begin_drain()
        drained = all([lp.join(timeout=60) for lp in self.loops])
        self.frontend.close()
        eng = self.engine
        whole = (drained and eng.degraded_mode == 0 and eng.step_failures == 0
                 and not eng.has_work
                 and eng.allocator.free_blocks == self.rcfg.num_blocks - 1)
        if not whole:
            raise SystemExit(
                f"benchmark: the engine is not whole after the drain: "
                f"drained={drained} degraded={eng.degraded_mode} "
                f"failures={eng.step_failures} work={eng.has_work} "
                f"free={eng.allocator.free_blocks}/{self.rcfg.num_blocks - 1}")

    # -------------------------------------------------------------- check
    def check(self, records: list) -> dict:
        """Served tokens against the plain reference (after ``close``): the
        KV pool's bytes go to the float32 reference first."""
        import jax
        import jax.numpy as jnp
        import numpy as np

        self.engine.cache = None
        gc.collect()
        good = [r for r in records if reduce.ok(r)]
        picks = random.Random(f"{self.seed}:check").sample(
            good, min(CHECK_SAMPLE, len(good)))
        if not picks:
            return {"ok": False, "why": "no finished request to check"}
        cfg, params, ref = self.cfg, self.engine.params, self.reference
        f32 = jax.jit(lambda p, ids: ref.forward(cfg, p, ids, jnp.float32))
        low = jax.jit(lambda p, ids: ref.forward(cfg, p, ids, jnp.bfloat16))
        matches = total = 0
        max_gap = noise = 0.0
        for r in picks:
            prompt = trafficgen.prompt_tokens(self.seed, r["stream_id"], r["i"],
                                              r["prompt_len"], cfg.vocab_size)
            seq = prompt + r["tokens"]
            padded = min(-(-len(seq) // CHECK_LEN_BUCKET) * CHECK_LEN_BUCKET,
                         cfg.max_seq_len)
            ids = np.zeros(padded, np.int32)  # causal: the padding is inert
            ids[:len(seq)] = seq
            rows = np.arange(len(prompt) - 1, len(seq) - 1)  # row i predicts i+1
            with jax.default_matmul_precision("highest"):
                want = np.asarray(f32(params, ids)[rows], np.float32)
            plain = np.asarray(low(params, ids)[rows], np.float32)
            if not np.isfinite(want).all():
                return {"ok": False, "why": "reference logits not finite"}
            picked = want[np.arange(len(rows)), r["tokens"]]
            max_gap = max(max_gap, float((want.max(-1) - picked).max()))
            noise = max(noise, float(np.abs(plain - want).max()))
            matches += int((want.argmax(-1) == np.asarray(r["tokens"])).sum())
            total += len(r["tokens"])
        rate = matches / total
        match_min = self.spec["config"]["serve"].get(
            "check", {}).get("match_rate_min", MATCH_RATE_MIN)
        return {"ok": bool(max_gap <= LOGIT_GAP_NOISE_FACTOR * noise
                           and rate >= match_min),
                "requests": len(picks), "tokens": total,
                "greedy_match_rate": rate, "max_logit_gap": max_gap,
                "bf16_logit_noise": noise,
                "gap_limit": LOGIT_GAP_NOISE_FACTOR * noise,
                "match_rate_min": match_min}


def end_to_end(win: dict, mix: dict, names) -> dict:
    """The contract's counts and those of the end-to-end metrics ``names``
    (the cell's, from BENCHMARK.json) that a serving window yields: open
    loop ``ttft_<stat>_ms`` / ``itl_<stat>_ms`` (``reduce.latency_metric``),
    closed loop ``serve_tokens_per_s``."""
    seconds = win["seconds"]
    tried = reduce.attempted(win["records"], seconds, win["open_loop"])
    out = {"attempted": len(tried),
           "failed": sum(1 for r in tried if reduce.failed(r)), "metrics": {}}
    for name in names:
        if win["open_loop"]:
            value = reduce.latency_metric(
                name, tried, reduce.missing_ttft_ms(seconds, mix))
        elif name == "serve_tokens_per_s":
            value = reduce.window_tokens(win["records"], seconds) / seconds
        else:
            value = None
        if value is not None:
            out["metrics"][name] = value
    return out


def frozen(win: dict, mix: dict) -> list:
    """The times the load generator's own process stood still for
    ``FREEZE_S`` or longer between the start of the lead-in and the end of
    the window."""
    return [s for s in win["stalls"] if s["seconds"] >= FREEZE_S
            and s["at"] + s["seconds"] > -mix["lead_seconds"]
            and s["at"] < win["seconds"]]


def measured_window(rig: ServeRig, seconds: float, trace: bool) -> dict:
    """One window, measured again (``VOID_WINDOWS_MAX`` times at most, on an
    idle engine, the same schedule) if the machine stood still during it.
    Set-up ends where the first window starts."""
    mix = rig.spec["mix"]
    win = rig.window(seconds, trace)
    t_window = win["t_window"]
    for again in range(VOID_WINDOWS_MAX):
        held = frozen(win, mix)
        if not held:
            break
        say(phase="void", why="the load generator's own process stood still, "
            "so the machine did and the server with it", stalls=held)
        rig.idle()
        win = rig.window(seconds, trace, tag=f"window{again + 2}")
    if frozen(win, mix):
        say(phase="frozen", why="the machine stood still in the last window "
            "allowed too; its numbers stand", stalls=frozen(win, mix))
    rig.reduce_trace(win)
    win["t_window"] = t_window
    return win


def run(spec: dict, seed: int, seconds: float, trace: bool, out_dir: str) -> dict:
    rig = ServeRig(spec, seed, out_dir)
    try:
        win = measured_window(rig, seconds, trace)
    finally:
        rig.close()
    t = time.perf_counter()
    verdict = rig.check(reduce.attempted(win["records"], seconds,
                                         win["open_loop"]))
    say(phase="check", seconds=time.perf_counter() - t, **verdict)
    say(phase="window", counters=win["counters"])
    result = end_to_end(win, spec["mix"],
                        [m["name"] for m in spec["end_to_end"]])
    result.update(correct=verdict["ok"], window=win,
                  context={"cfg": rig.cfg, "reference": rig.reference,
                           "rcfg": rig.rcfg})
    return result
