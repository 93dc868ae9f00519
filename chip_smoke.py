"""The quickest proof that the system still starts on the chip.

One process, one TPU v5e chip, no size or device option: the two main paths
driven once through the entry points a user calls, at the published GPT-2 XL
widths (n_embd 1600, 25 heads of 64, FFN 6400, vocab 50257, 1024 positions).
Widths are never cut; depth is the only cut, and only where ``reduced`` says.
Weights are random, made from ``SEED``.

Phases, each one JSON line on stdout, any failure a non-zero exit at once:

1. device   ``jax.devices()``; anything but ``tpu`` stops the run. No CPU branch.
2. kernels  every Pallas kernel of the two paths against its XLA counterpart
            on a small seeded input, at the GPT-2 XL and the Llama-3-8B /
            Mixtral-8x7B head geometry (the first is what the phases below
            run; the second is what the scoped-VMEM repair was for).
3. train    ``deepspeed_tpu.initialize`` -> bf16 + fp32 masters, AdamW, ZeRO
            stage 3, sequence 1024; loss falls on a fixed batch; the flash
            kernel is in the step program; a checkpoint round trip reproduces
            the next step's loss.
4. serve    all 48 layers in a ``RaggedInferenceEngine`` behind
            ``serving.build_server``: real HTTP completions (JSON and SSE, at
            least four in flight), ``/healthz``, ``/metrics``, drain; then the
            engine is whole (no degradation, no failed step, every KV block
            back) and what it served agrees with the plain ``gpt2.forward``.

``--chips 4`` runs only the sharded training on a four-chip host: stage 3
over ``{"fsdp": 4}`` against stage 0 over ``{"data": 4}`` at the one-chip
depth (same loss curve, every leaf split four ways), then all 48 layers.

The phases are plain functions of a model config: the CPU rehearsal and the
tests call them at ``GPT2Config.tiny()`` size themselves. ``main()`` refuses
anything but the chip. Last line of stdout:
``{"ok": true, "device": {"platform": "tpu", "kind": "...", "count": 1}}``.
"""

from __future__ import annotations

import argparse
import dataclasses
import gc
import http.client
import json
import sys
import tempfile
import threading
import time
from functools import partial

import numpy as np

SEED = 0

# ---- sizes of the default run, from the sandbox rehearsal (PERF.md §4):
# the engine's own train step and ragged step programs were compiled for a
# described v5e device at these shapes and compiled.memory_analysis() read.
# Training: the step program fits 15.75 GiB of HBM up to 28 layers (15.57
# GiB; 32 is refused), but load_checkpoint holds the old and the new
# optimizer state together — 20 B/param, more than the step's peak — and
# the process also keeps its executables on the device, so 20 layers
# (12.1 GiB for the step, 13.0 GiB in the load) is the largest with a
# gibibyte to spare.
TRAIN_LAYERS = 20
TRAIN_MICRO_BATCH = 4
TRAIN_STEPS = 10
SEQ_LEN = 1024
# Serving: 512 usable blocks x 32 tokens = 16,384 tokens of KV (4.7 GiB of
# bf16 at 48 layers, 5.9 GiB as the compiler lays it out) beside 2.9 GiB of
# bf16 weights: 9.3 GiB for a single-step program.
KV_BLOCK = 32
KV_BLOCKS = 513
MAX_SEQS = 8
STEP_TOKENS = 512
PREFILL_TILE = 128

# A served token may differ from the reference's greedy pick only where the
# reference itself cannot tell them apart: the engine computes in bf16, the
# reference in float32 at "highest" matmul precision, and a pick is an argmax
# over 50,257 logits whose top two are often closer than bf16 resolves (at
# random init the logits are ~N(0, 0.8), so the typical top-1/top-2 gap is
# ~0.2 while bf16 keeps 8 bits). So two conditions, both teacher-forced on
# the served history:
#  - how far below the reference's best logit the served token sits must be
#    within LOGIT_GAP_NOISE_FACTOR x the deviation a plain bf16 forward of the
#    same weights shows from the float32 reference on the same positions
#    (2x is the bound if both bf16 paths err alike; 4x leaves room for the
#    engine's different summation order). A wrong block, position or mask
#    moves a pick by whole logit standard deviations, tens of times that.
#  - the exact greedy match rate must be at least MATCH_RATE_MIN. Near-ties
#    make 1.0 unreachable in bf16 (343 of 344 sampled tokens matched on the
#    chip, PR 21, where the worst gap was 0.003 against a bf16 noise of
#    0.069); a broken cache or scheduler gives ~1/vocab.
LOGIT_GAP_NOISE_FACTOR = 4.0
MATCH_RATE_MIN = 0.9

# --chips 4: the two placements of one training job must trace the same loss
# curve. bf16 keeps 8 bits (2**-8 = 0.4%), and the placements sum gradients in
# different orders (all-reduce of whole gradients against reduce-scatter of
# shards), so steps may differ by a few bf16 ulps that compound over the run.
SHARDED_LOSS_RTOL = 1e-2
SHARDED_BYTES_SPREAD = 0.25


class SmokeFailure(RuntimeError):
    """A phase's check did not hold."""


def _require(cond, message: str) -> None:
    if not cond:
        raise SmokeFailure(message)


def _emit(phase: str, **fields) -> dict:
    line = {"phase": phase, **fields}
    print(json.dumps(line), flush=True)
    return line


def _shapes(cfg, **more) -> dict:
    return {"hidden": cfg.hidden_size, "heads": cfg.num_heads, "ffn": cfg.ffn,
            "vocab": cfg.vocab_size, "num_layers": cfg.num_layers, **more}


def _reduced(cfg, full_layers: int) -> dict:
    """The only cut there is: depth."""
    return ({"num_layers": [full_layers, cfg.num_layers]}
            if cfg.num_layers != full_layers else {})


def _memory(device=None) -> dict:
    import jax

    stats = (device or jax.devices()[0]).memory_stats() or {}
    return {k: stats.get(k) for k in
            ("bytes_in_use", "peak_bytes_in_use", "bytes_limit")}


class _CompileMeter:
    """Deltas of what telemetry/compile_watch.py counts: seconds spent in
    trace/lower/backend-compile (retrievals from the persistent cache
    included), program builds, persistent-cache hits and misses.
    Turns telemetry on, as a deployment does: the counters and the
    ``/metrics`` page only exist with it."""

    def __init__(self):
        from deepspeed_tpu import telemetry

        if not telemetry.TELEMETRY.enabled:
            telemetry.configure(enabled=True)
        self._last = self._read()

    @staticmethod
    def _read() -> dict:
        from deepspeed_tpu import telemetry

        metrics = telemetry.snapshot()["metrics"]

        def total(name, field):
            series = (metrics.get(name) or {}).get("series", [])
            return sum(s[field] for s in series)

        return {
            "compile_seconds": total("jit_compile_seconds", "sum"),
            "compiles": total("jit_cache_misses_total", "value"),
            "cache_hits": total("persistent_cache_hits_total", "value"),
            "cache_misses": total("persistent_cache_misses_total", "value"),
        }

    def take(self) -> dict:
        now = self._read()
        delta = {k: round(now[k] - self._last[k], 3) for k in now}
        self._last = now
        return delta


# ------------------------------------------------------------------ device
def device_phase(chips: int) -> dict:
    """What jax found. Not a TPU, or not ``chips`` of them: no result."""
    import jax

    devices = jax.devices()
    device = {"platform": devices[0].platform,
              "kind": devices[0].device_kind, "count": len(devices)}
    if device["platform"] != "tpu":
        raise SystemExit(f"chip_smoke: needs a TPU; jax found {device}")
    if device["count"] != chips:
        raise SystemExit(f"chip_smoke: asked for {chips} chip(s); jax found "
                         f"{device}")
    return device


# ----------------------------------------------------------------- kernels
def kernels_phase(geometries, seq_len: int = 256, tile: int = PREFILL_TILE,
                  block: int = KV_BLOCK, ssm=(16, 128, 8192, 8),
                  experts=(16, 1024, 2688, 64, 6),
                  ssd=(102, 32, 3, 128),
                  kda=(258, 128, 32, 128), chunk=(3, 128, 16),
                  selscan=(514, 16, 5120, 256, 3, 128),
                  swa=(16, 28, 4, 128, 4096, 8192, 128),
                  blocks=(96, 4, 32, 4, 128, 1300, 128)) -> dict:
    """Each Pallas kernel of the train and serve paths against the XLA path
    on the same seeded bf16 input, at each ``(q heads, kv heads, head size)``.
    Off the chip the kernels interpret; on it this is their first execution.
    Beside the attention kernels, once each: the Mamba-2 decode kernel at
    ``ssm`` = (state rows, state size, heads x head size, groups) against
    XLA's gather -> update -> scatter, the chunk form at a group a head
    (``ssd_chunk``) at ``ssd`` = (state rows, heads of 128 x 128, tiles, rows
    a tile; the MiniCPM-SALA cell's 6 x 17 rows, 32 heads and a mixed step's
    3 tiles x 128 rows in bfloat16, two tiles of one slot and a fresh one)
    against ``ssd_chunk_xla``, both timed (``ssd_chunk_ms``), the grouped expert kernel's
    two-matrix ``relu**2`` form at ``experts`` = (held, latent, ffn, routed,
    top k), a held share, against the all-experts einsum, and the KDA decode
    kernel at ``kda`` = (state rows, key channels = values a head, heads,
    decode rows; the Kimi-Linear cell's 128 rows x 32 heads x 128 x 128)
    against its XLA form, both timed (``kda_decode_ms``: the state donated,
    the median of five calls), and the KDA chunk kernel over the same state
    at ``chunk`` = (tiles, rows a tile, sub-chunk; a mixed step's 3 tiles x
    128 rows) against ``kda_tiles`` between slices (``kda_chunk_ms``), two
    tiles of one slot and a fresh one, the strongest decay on a quarter of
    the heads; float32 both, so ``kda_chunk_*`` are held to 1e-5. The two
    Mamba-1 kernels at ``selscan`` = (state rows, state size, channels, decode
    rows, tiles, rows a tile; the Jamba cell's 256 rows and 3 tiles of 128 over
    [16, 5120] states): ``selscan_decode`` against gather -> update -> scatter
    and ``selscan_tile`` against a ``lax.scan`` over a tile's rows, both timed
    with the state donated (``selscan_decode_ms``, ``selscan_tile_ms``),
    ``dt`` log-uniform in the seeded draw's [0.001, 0.1] and ``A[n] = n + 1``;
    float32 and the same operations in the same order, held to 1e-5. Last, a
    sliding-window layer's decode walk at ``swa`` = (rows, q heads, kv heads,
    head size, window, context, block; the SmallThinker cell's 16 rows x 28
    heads x 4,096 of an 8,192 context) and its tile kernel over four tiles of
    ``tile`` rows around the window's edge (the last tile under it, the first
    past it, the table's last, a sequence's first), each against XLA's gather
    of the whole table under the same mask, all timed (``swa_decode_ms``,
    ``swa_prefill_ms``); the queries are scaled so that a few keys hold a
    row's weight and an edge off by a block would show. And a block model's
    decode walk at ``blocks`` = (sequences, block length, q heads, kv heads, head
    size, context, pool block; the SDAR cell's 96 blocks x 4 rows x 32 heads
    over ~1.3K contexts): ``blk_decode`` (a block ONE row of the walk, its
    context fetched once for its queries by KV head) against XLA's gather
    under the block-causal mask, and beside it the same rows as four single-query ``paged_decode``
    walks (every row its block's last position: the same keys, fetched four
    times), all three timed (``blk_decode_ms``)."""
    import jax
    import jax.numpy as jnp

    from deepspeed_tpu.ops import attention as ops

    t0 = time.perf_counter()
    worst = {}

    def close(name, got, want, tol=3e-2):
        """bf16 outputs: max deviation relative to the largest value."""
        got, want = got.astype(jnp.float32), want.astype(jnp.float32)
        err = float(jnp.max(jnp.abs(got - want)) / jnp.max(jnp.abs(want)))
        _require(np.isfinite(err) and err <= tol,
                 f"kernel {name}: max |pallas - xla| / max |xla| = {err} "
                 f"> {tol}")
        worst[name] = round(max(worst.get(name, 0.0), err), 8)

    for hq, hkv, d in geometries:
        keys = iter(jax.random.split(jax.random.PRNGKey(SEED), 8))
        q = jax.random.normal(next(keys), (1, seq_len, hq, d), jnp.bfloat16)
        k = jax.random.normal(next(keys), (1, seq_len, hkv, d), jnp.bfloat16)
        v = jax.random.normal(next(keys), (1, seq_len, hkv, d), jnp.bfloat16)

        def flash(impl):
            def loss(q, k, v):
                out = ops.attention(q, k, v, causal=True, impl=impl)
                return jnp.sum(out.astype(jnp.float32) ** 2), out

            (_, out), grads = jax.jit(jax.value_and_grad(
                loss, argnums=(0, 1, 2), has_aux=True))(q, k, v)
            return (out, *grads)

        for name, got, want in zip(
                ("flash_fwd", "flash_bwd_dq", "flash_bwd_dk", "flash_bwd_dv"),
                flash("pallas"), flash("xla")):
            close(name, got, want)

        # a pool of 2 sequences x enough blocks for seq_len, blocks shuffled
        mb = -(-seq_len // block)
        rng = np.random.default_rng(SEED)
        bt = np.zeros((3, mb), np.int32)
        bt[:2] = rng.permutation(np.arange(1, 2 * mb + 1)).reshape(2, mb)
        bt = jnp.asarray(bt)
        kp = jax.random.normal(next(keys), (2 * mb + 1, block, hkv * d),
                               jnp.bfloat16)
        vp = jax.random.normal(next(keys), (2 * mb + 1, block, hkv * d),
                               jnp.bfloat16)
        slots = jnp.asarray([0, 1, 0, 1, 0, 1, 0, 1], jnp.int32)
        pos = jnp.asarray(rng.integers(0, seq_len, 8), jnp.int32)
        qd = jax.random.normal(next(keys), (8, hq, d), jnp.bfloat16)
        close("paged_decode",
              jax.jit(lambda *a: ops.paged_attention(*a, impl="pallas"))(
                  qd, kp, vp, slots, pos, bt),
              jax.jit(lambda *a: ops.paged_attention(*a, impl="xla"))(
                  qd, kp, vp, slots, pos, bt))

        # two tiles of sequence 0 (the second partly valid), one of sequence 1
        ts = jnp.asarray([0, 0, 1], jnp.int32)
        tp = jnp.asarray([5, 5 + tile, 0], jnp.int32)
        tv = jnp.asarray([tile, min(tile - 3, seq_len - 5 - tile), tile // 2],
                         jnp.int32)
        _require(int(tv[1]) > 0, "kernels_phase: seq_len too short for tiles")
        qp = jax.random.normal(next(keys), (3 * tile, hq, d), jnp.bfloat16)
        outs = {
            impl: jax.jit(lambda *a, i=impl: ops.ragged_prefill_attention(
                *a, tile, impl=i))(qp, kp, vp, ts, tp, tv, bt)
            for impl in ("pallas", "xla")}
        valid = (jnp.arange(3 * tile) % tile) < jnp.repeat(tv, tile)
        close("tiled_prefill",
              jnp.where(valid[:, None, None], outs["pallas"], 0),
              jnp.where(valid[:, None, None], outs["xla"], 0))

    from deepspeed_tpu.models import experts as moe
    from deepspeed_tpu.ops.pallas.ssm import ssm_decode, ssm_decode_xla

    keys = iter(jax.random.split(jax.random.PRNGKey(SEED + 1), 10))
    rows_n, n, hp, groups = ssm
    rows = jnp.asarray(np.random.default_rng(SEED).permutation(rows_n)[:8],
                       jnp.int32)
    args = (jax.random.normal(next(keys), (rows_n, n, hp), jnp.float32), rows,
            jax.random.uniform(next(keys), (8, hp), jnp.float32),
            jax.random.normal(next(keys), (8, hp), jnp.float32),
            jax.random.normal(next(keys), (8, n, groups), jnp.float32),
            jax.random.normal(next(keys), (8, n, groups), jnp.float32))
    for name, got, want in zip(("ssm_decode_state", "ssm_decode_y"),
                               jax.jit(ssm_decode)(*args),
                               jax.jit(ssm_decode_xla)(*args)):
        close(name, got, want, tol=1e-5)

    def median_ms(forms, outs, args):
        """Each form again from the state it left, which it donates: the
        median of five calls, ms."""
        ms = {}
        for name, fn in forms.items():
            s_run, times = outs[name][0], []
            for _ in range(5):
                t1 = time.perf_counter()
                s_run, y = fn(s_run, *args)
                jax.block_until_ready((s_run, y))
                times.append((time.perf_counter() - t1) * 1e3)
            ms[name] = round(sorted(times)[2], 3)
        return ms

    def tiles_of(n_i, slot, other, spare):
        """A chunk kernel's step of ``n_i`` tiles: all but the last go on in
        ``slot`` (parking zeros in ``spare`` until its last), the last is
        fresh in ``other`` -> (rows, rows_w, fresh, cont, write)."""
        return (jnp.asarray([slot] * (n_i - 1) + [other], jnp.int32),
                jnp.asarray([spare] * (n_i - 2) + [slot, other], jnp.int32),
                jnp.arange(n_i) == n_i - 1,
                (jnp.arange(n_i) > 0) & (jnp.arange(n_i) < n_i - 1),
                jnp.arange(n_i) >= n_i - 2)

    from deepspeed_tpu.ops.pallas.ssm import ssd_chunk

    rows_n, heads, n_i, r = ssd
    draws = iter(jax.random.split(jax.random.PRNGKey(SEED + 7), 6))
    state = jax.random.normal(next(draws), (rows_n, 128, heads * 128),
                              jnp.float32)
    x, b, c = (jax.random.normal(next(draws), (n_i, r, heads * 128),
                                 jnp.bfloat16) * scale
               for scale in (1.0, 128 ** -0.5, 128 ** -0.5))
    tiles = (*tiles_of(n_i, *np.random.default_rng(SEED).permutation(
                 rows_n)[:3].tolist()),
             x, jax.random.uniform(next(draws), (n_i, r, heads), jnp.float32),
             -jnp.exp(jax.random.uniform(next(draws), (heads,), jnp.float32,
                                         np.log(1e-3), np.log(0.5))), b, c)
    forms = {name: jax.jit(lambda s, *a, name=name: ssd_chunk(
        s, *a, impl=name), donate_argnums=0) for name in ("pallas", "xla")}
    outs = {name: fn(state + 0.0, *tiles) for name, fn in forms.items()}
    for name, got, want in zip(("ssd_chunk_state", "ssd_chunk_y"),
                               outs["pallas"], outs["xla"]):
        # bfloat16 operands: an operand rounded the other way moves a sum
        close(name, got, want, tol=2e-3)
    ssd_ms = median_ms(forms, outs, tiles)

    held, lat, ffn, routed, top_k = experts
    h = jax.random.normal(next(keys), (256, lat), jnp.bfloat16)
    router = jax.random.normal(next(keys), (lat, routed), jnp.float32)
    w_up = (jax.random.normal(next(keys), (held, lat, ffn), jnp.float32)
            * lat ** -0.5).astype(jnp.bfloat16)
    w_down = (jax.random.normal(next(keys), (held, ffn, lat), jnp.float32)
              * ffn ** -0.5).astype(jnp.bfloat16)
    share = (held, routed)   # the second share of routed // held
    topv, topi = moe._route(h, router, top_k, "sigmoid", None, True, 5.0, 1e-20)
    close("moe_gmm_relu2",
          jax.jit(lambda *a: moe._grouped_experts(*a, None, w_up, w_down, 0,
                                                  held, share))(h, topv, topi),
          jax.jit(lambda *a: moe._einsum_experts(*a, None, w_up, w_down,
                                                 share))(h, topv, topi))

    from deepspeed_tpu.ops.pallas.kda import kda_decode, kda_decode_xla

    rows_n, kd, heads, t = kda
    keys = iter(jax.random.split(jax.random.PRNGKey(SEED + 2), 6))
    state = jax.random.normal(next(keys), (rows_n, kd, heads * kd), jnp.float32)
    k_in = jax.random.normal(next(keys), (t, kd, heads), jnp.float32)
    step = (jnp.asarray(np.random.default_rng(SEED).permutation(rows_n)[:t],
                        jnp.int32),
            jax.random.uniform(next(keys), (t, kd, heads), jnp.float32, 0.2, 1.0),
            k_in / jnp.linalg.norm(k_in, axis=1, keepdims=True),
            jax.random.normal(next(keys), (t, kd, heads), jnp.float32),
            jax.random.normal(next(keys), (t, heads * kd), jnp.float32),
            jnp.repeat(jax.random.uniform(next(keys), (t, heads), jnp.float32),
                       kd, axis=1))
    forms = {"pallas": jax.jit(lambda *a: kda_decode(*a, impl="pallas"),
                               donate_argnums=0),
             "xla": jax.jit(kda_decode_xla, donate_argnums=0)}
    outs = {name: fn(state + 0.0, *step) for name, fn in forms.items()}
    for name, got, want in zip(("kda_decode_state", "kda_decode_y"),
                               outs["pallas"], outs["xla"]):
        close(name, got, want, tol=1e-5)
    kda_ms = median_ms(forms, outs, step)

    from deepspeed_tpu.ops.pallas.kda import kda_chunk

    n_i, r, sub = chunk
    keys = iter(jax.random.split(jax.random.PRNGKey(SEED + 3), 6))

    def unit(key):
        x = jax.random.normal(key, (n_i, r, heads, kd), jnp.float32)
        return x / jnp.linalg.norm(x, axis=-1, keepdims=True)

    # the seeded gates' range (``kimi_linear.init_params``), and -1.6 a
    # token, the strongest a channel can draw, on a quarter of the heads
    g = -jnp.exp(jax.random.uniform(next(keys), (n_i, r, heads, kd),
                                    jnp.float32, np.log(1e-3), np.log(1.6)))
    g = g.at[:, :, :max(heads // 4, 1)].set(-1.6)
    tiles = (*tiles_of(n_i, *(int(x) for x in step[0][:3])),
             *((x.reshape(n_i, r, -1) for x in (
                 unit(next(keys)) * kd ** -0.5, unit(next(keys)), g,
                 jax.random.normal(next(keys), (n_i, r, heads, kd),
                                   jnp.float32)))),
             jax.random.uniform(next(keys), (n_i, r, heads), jnp.float32))
    forms = {name: jax.jit(lambda s, *a, name=name: kda_chunk(
        s, *a, sub, impl=name), donate_argnums=0) for name in ("pallas", "xla")}
    outs = {name: fn(state + 0.0, *tiles) for name, fn in forms.items()}
    for name, got, want in zip(("kda_chunk_state", "kda_chunk_y"),
                               outs["pallas"], outs["xla"]):
        close(name, got, want, tol=1e-5)
    chunk_ms = median_ms(forms, outs, tiles)

    from deepspeed_tpu.ops.pallas.selscan import selscan_decode, selscan_tile

    rows_n, n, ch, t, n_i, r = selscan
    keys = iter(jax.random.split(jax.random.PRNGKey(SEED + 5), 10))
    state = jax.random.normal(next(keys), (rows_n, n, ch), jnp.float32)
    a = -jnp.broadcast_to(jnp.arange(1.0, n + 1)[:, None], (n, ch))

    def steps(key, *lead):
        return jnp.exp(jax.random.uniform(key, (*lead, ch), jnp.float32,
                                          np.log(1e-3), np.log(0.1)))

    where = jnp.asarray(np.random.default_rng(SEED).permutation(rows_n)[:t],
                        jnp.int32)
    step = (where, jnp.arange(t) % 7 == 3, steps(next(keys), t),
            jax.random.normal(next(keys), (t, ch), jnp.bfloat16), a,
            jax.random.normal(next(keys), (t, n), jnp.float32),
            jax.random.normal(next(keys), (t, n), jnp.float32))
    forms = {name: jax.jit(lambda s, *x, name=name: selscan_decode(
        s, *x, impl=name), donate_argnums=0) for name in ("pallas", "xla")}
    outs = {name: fn(state + 0.0, *step) for name, fn in forms.items()}
    for name, got, want in zip(("selscan_decode_state", "selscan_decode_y"),
                               outs["pallas"], outs["xla"]):
        close(name, got, want, tol=1e-5)
    selscan_decode_ms = median_ms(forms, outs, step)
    slot, other, spare = (int(x) for x in where[:3])
    tiles = (jnp.asarray([slot] * (n_i - 1) + [other], jnp.int32),
             jnp.asarray([spare] * (n_i - 2) + [slot, other], jnp.int32),
             jnp.arange(n_i) == n_i - 1,                       # fresh
             (jnp.arange(n_i) > 0) & (jnp.arange(n_i) < n_i - 1),
             jnp.arange(n_i) >= n_i - 2,                       # write
             steps(next(keys), n_i, r).at[-1, r // 2:].set(0.0),
             jax.random.normal(next(keys), (n_i, r, ch), jnp.bfloat16), a,
             jax.random.normal(next(keys), (n_i, r, n), jnp.float32),
             jax.random.normal(next(keys), (n_i, r, n), jnp.float32))
    forms = {name: jax.jit(lambda s, *x, name=name: selscan_tile(
        s, *x, impl=name), donate_argnums=0) for name in ("pallas", "xla")}
    outs = {name: fn(state + 0.0, *tiles) for name, fn in forms.items()}
    for name, got, want in zip(("selscan_tile_state", "selscan_tile_y"),
                               outs["pallas"], outs["xla"]):
        close(name, got, want, tol=1e-5)
    selscan_tile_ms = median_ms(forms, outs, tiles)
    del state, outs

    rows_n, hq, hkv, d, window, context, blk = swa
    mb = context // blk
    keys = iter(jax.random.split(jax.random.PRNGKey(SEED + 4), 4))
    pool_shape = (rows_n * mb + 1, blk, hkv * d)

    def peaked(key, rows):
        """Queries whose scores over unit keys spread 3 wide: a few keys hold
        a row's weight, so one block too many or too few at the window's edge
        moves whole heads and not a mean's 128th part."""
        return (3.0 * jax.random.normal(key, (rows, hq, d), jnp.float32)
                ).astype(jnp.bfloat16)

    pools = (jax.random.normal(next(keys), pool_shape, jnp.bfloat16),
             jax.random.normal(next(keys), pool_shape, jnp.bfloat16))
    table = jnp.asarray(1 + np.random.default_rng(SEED).permutation(
        rows_n * mb).reshape(rows_n, mb), jnp.int32)
    # decode rows: contexts from inside the window to the table's last row;
    # tiles: the last under the window, the first past it, the table's last,
    # and a sequence's first, half valid
    tile_pos0 = jnp.asarray([window - tile, window, context - tile, 0], jnp.int32)
    tile_rows = (tile_pos0[:, None] + jnp.arange(tile)).reshape(-1)
    valid = (jnp.arange(4 * tile) < 3 * tile + tile // 2)[:, None, None]

    def decode(impl, q, slots, pos):
        return ops.paged_attention(q, *pools, slots, pos, table, impl=impl,
                                   window=window)

    def prefill(impl, q, slots, pos):
        if impl == "xla":   # XLA's form gathers a whole table a ROW: 16 at a time
            out = jax.lax.map(lambda a: decode("xla", *a), tuple(
                x.reshape(-1, 16, *x.shape[1:]) for x in (
                    q, jnp.repeat(slots, tile), tile_rows)))
            return jnp.where(valid, out.reshape(q.shape), 0)
        return jnp.where(valid, ops.ragged_prefill_attention(
            q, *pools, slots, pos, jnp.asarray([tile] * 3 + [tile // 2], jnp.int32),
            table, tile, impl=impl, window=window), 0)

    calls = {
        "swa_decode": (decode, (
            peaked(next(keys), rows_n), jnp.arange(rows_n, dtype=jnp.int32),
            jnp.asarray(np.linspace(window // 2, context - 1, rows_n),
                        jnp.int32))),
        "swa_prefill": (prefill, (
            peaked(next(keys), 4 * tile), jnp.arange(4, dtype=jnp.int32),
            tile_pos0))}
    swa_ms = {}
    for kernel, (op, args) in calls.items():
        forms = {name: jax.jit(partial(op, name)) for name in ("pallas", "xla")}
        outs = {name: fn(*args) for name, fn in forms.items()}
        close(kernel, outs["pallas"], outs["xla"])
        swa_ms[kernel] = {}
        for name, fn in forms.items():
            times = []
            for _ in range(5):
                t1 = time.perf_counter()
                jax.block_until_ready(fn(*args))
                times.append((time.perf_counter() - t1) * 1e3)
            swa_ms[kernel][name] = round(sorted(times)[2], 3)

    seqs, b, hq, hkv, d, context, blk_size = blocks
    mb = -(-(context + b) // blk_size)
    keys = iter(jax.random.split(jax.random.PRNGKey(SEED + 5), 4))
    pool_shape = (seqs * mb + 1, blk_size, hkv * d)
    pools = (jax.random.normal(next(keys), pool_shape, jnp.bfloat16),
             jax.random.normal(next(keys), pool_shape, jnp.bfloat16))
    table = jnp.asarray(1 + np.random.default_rng(SEED).permutation(
        seqs * mb).reshape(seqs, mb), jnp.int32)
    # blocks at p0 around ``context``, multiples of the block length
    p0 = jnp.asarray(np.linspace(context // 2, context, seqs), jnp.int32) // b * b
    rows = (jnp.repeat(jnp.arange(seqs, dtype=jnp.int32), b),
            (p0[:, None] + jnp.arange(b)).reshape(-1))
    q = (3.0 * jax.random.normal(next(keys), (seqs * b, hq, d), jnp.float32)
         ).astype(jnp.bfloat16)
    forms = {
        "pallas": jax.jit(lambda q, sl, po: ops.paged_attention(
            q, *pools, sl, po, table, impl="pallas", block=b)),
        # the same keys a row, as one query each: its block's last position
        "four_walks": jax.jit(lambda q, sl, po: ops.paged_attention(
            q, *pools, sl, po | (b - 1), table, impl="pallas")),
        "xla": jax.jit(lambda q, sl, po: jax.lax.map(
            lambda a: ops.paged_attention(*a[:1], *pools, *a[1:], table,
                                          impl="xla", block=b),
            tuple(x.reshape(-1, 4 * b, *x.shape[1:]) for x in (q, sl, po))
        ).reshape(q.shape))}
    outs = {name: fn(q, *rows) for name, fn in forms.items()}
    close("blk_decode", outs["pallas"], outs["xla"])
    close("blk_decode_as_four_walks", outs["four_walks"], outs["xla"])
    blk_ms = {}
    for name, fn in forms.items():
        times = []
        for _ in range(5):
            t1 = time.perf_counter()
            jax.block_until_ready(fn(q, *rows))
            times.append((time.perf_counter() - t1) * 1e3)
        blk_ms[name] = round(sorted(times)[2], 3)

    return _emit("kernels", seconds=round(time.perf_counter() - t0, 2),
                 blk_decode_ms=blk_ms,
                 geometries=[list(g) for g in geometries],
                 shapes={"seq_len": seq_len, "tile": tile, "block": block},
                 max_rel_err=worst, ssd_chunk_ms=ssd_ms, kda_decode_ms=kda_ms,
                 kda_chunk_ms=chunk_ms, selscan_decode_ms=selscan_decode_ms,
                 selscan_tile_ms=selscan_tile_ms,
                 swa_decode_ms=swa_ms["swa_decode"],
                 swa_prefill_ms=swa_ms["swa_prefill"],
                 memory=_memory())


# ------------------------------------------------------------------- train
def train_config(micro_batch: int, seq_len: int, mesh: dict,
                 zero_stage: int) -> dict:
    return {
        "train_micro_batch_size_per_device": micro_batch,
        "gradient_accumulation_steps": 1,
        "steps_per_print": 0,
        "gradient_clipping": 1.0,
        "sequence_length": seq_len,
        "seed": SEED,
        "bf16": {"enabled": True, "master_weights": True},
        # no warm-up here, and Adam's first steps move every weight by ~lr
        # whatever the gradient: 1e-4 made the 48-layer loss jump at step 4
        # on the chip (11.13, 10.70, 10.40, 11.61), 3e-5 does not
        "optimizer": {"type": "adamw",
                      "params": {"lr": 3e-5, "weight_decay": 0.01}},
        "zero_optimization": {"stage": zero_stage},
        "mesh": mesh,
        "activation_checkpointing": {"enabled": True, "policy": "full"},
    }


def _train_engine(cfg, micro_batch, seq_len, mesh, zero_stage):
    import deepspeed_tpu
    from deepspeed_tpu.comm.topology import reset_topology
    from deepspeed_tpu.models import gpt2

    reset_topology()
    engine, _, _, _ = deepspeed_tpu.initialize(
        model=lambda ctx: gpt2.build(cfg, ctx=ctx),
        config=train_config(micro_batch, seq_len, mesh, zero_stage))
    rng = np.random.default_rng(SEED)
    batch = {"input_ids": rng.integers(
        0, cfg.vocab_size, (engine.train_batch_size, seq_len), dtype=np.int32)}
    return engine, batch


def _step_lowering(engine, batch):
    """The train step as the engine dispatches it, lowered (not compiled)."""
    import jax.numpy as jnp

    return engine._train_batch_jit.lower(
        engine.params, engine.opt_state, engine.scale_state,
        jnp.int32(engine.global_steps), engine._train_rng,
        engine._put_gas_batch(batch))


def _timed_steps(engine, batch, steps: int):
    losses, seconds = [], []
    for _ in range(steps):
        t0 = time.perf_counter()
        losses.append(float(engine.train_batch(batch)))  # the fetch settles
        seconds.append(time.perf_counter() - t0)
    return losses, seconds


def _free(engine) -> None:
    """Tear the engine down and hand its device bytes back (the caller's
    name still points at it, so its arrays are dropped here)."""
    engine.destroy()
    engine.params = engine.opt_state = None
    gc.collect()


def train_phase(cfg, *, full_layers: int, micro_batch: int, seq_len: int,
                steps: int, expect_kernels: bool) -> dict:
    """A few optimizer steps on a fixed seeded batch through
    ``deepspeed_tpu.initialize`` / ``engine.train_batch``, then a checkpoint
    round trip."""
    import jax

    _require(steps >= 3, "train_phase needs at least 3 steps")
    meter = _CompileMeter()
    t_phase = time.perf_counter()
    n_dev = len(jax.devices())
    engine, batch = _train_engine(cfg, micro_batch, seq_len,
                                  {"data": 1, "fsdp": n_dev}, zero_stage=3)
    losses, seconds = _timed_steps(engine, batch, steps)
    _require(all(np.isfinite(x) for x in losses),
             f"train: non-finite loss in {losses}")
    _require(losses[-1] < losses[1],
             f"train: loss did not fall after step 2: {losses}")

    kernels = _step_lowering(engine, batch).as_text().count("tpu_custom_call")
    if expect_kernels:  # flash forward + its two backward kernels
        _require(kernels >= 3, "train: the lowered step holds "
                 f"{kernels} tpu_custom_call(s), expected the flash kernel's 3")

    with tempfile.TemporaryDirectory(prefix="chip_smoke_ckpt_") as ckpt_dir:
        t0 = time.perf_counter()
        engine.save_checkpoint(ckpt_dir)
        save_s = time.perf_counter() - t0
        straight = float(engine.train_batch(batch))
        t0 = time.perf_counter()
        engine.load_checkpoint(ckpt_dir)
        load_s = time.perf_counter() - t0
        resumed = float(engine.train_batch(batch))
    _require(straight == resumed, "train: the step after save->load gave "
             f"loss {resumed!r}, without the round trip {straight!r}")

    mem = _memory()
    num_params = engine.model_spec.num_params
    _free(engine)
    return _emit(
        "train", seconds=round(time.perf_counter() - t_phase, 2),
        **meter.take(),
        shapes=_shapes(cfg, seq_len=seq_len, micro_batch=micro_batch,
                       params=num_params),
        reduced=_reduced(cfg, full_layers),
        config={"zero_stage": 3, "dtype": "bf16 + fp32 masters",
                "optimizer": "adamw", "remat": "full"},
        first_step_seconds=round(seconds[0], 3),
        step_seconds_median=round(float(np.median(seconds[2:])), 4),
        losses=[round(x, 4) for x in losses],
        flash_custom_calls_in_step=kernels,
        checkpoint={"save_seconds": round(save_s, 2),
                    "load_seconds": round(load_s, 2),
                    "loss_after_round_trip": resumed,
                    "loss_without": straight},
        memory=mem)


# ------------------------------------------------------------------- serve
def serve_requests(n: int, prompt_range, new_range, vocab: int) -> list:
    """``n`` seeded requests; every fourth streams over SSE."""
    rng = np.random.default_rng(SEED + 1)
    out = []
    for i in range(n):
        plen = int(rng.integers(prompt_range[0], prompt_range[1] + 1))
        out.append({
            "prompt": [int(t) for t in rng.integers(0, vocab, plen)],
            "max_tokens": int(rng.integers(new_range[0], new_range[1] + 1)),
            "stream": i % 4 == 1,
        })
    return out


def _get(frontend, path: str):
    conn = http.client.HTTPConnection(frontend.host, frontend.port, timeout=60)
    try:
        conn.request("GET", path)
        resp = conn.getresponse()
        return resp.status, resp.read().decode()
    finally:
        conn.close()


def _complete(frontend, body: dict, timeout: float) -> dict:
    """One ``POST /v1/completions``; returns status, tokens and the clock."""
    from deepspeed_tpu.serving import decode_sse

    t0 = time.perf_counter()
    conn = http.client.HTTPConnection(frontend.host, frontend.port,
                                      timeout=timeout)
    try:
        conn.request("POST", "/v1/completions", body=json.dumps(body),
                     headers={"Content-Type": "application/json"})
        resp = conn.getresponse()
        raw = resp.read()
    finally:
        conn.close()
    out = {"status": resp.status, "t0": t0, "t1": time.perf_counter(),
           "tokens": None, "finish": None}
    if resp.status != 200:
        out["error"] = raw[:300].decode(errors="replace")
        return out
    if body["stream"]:
        frames = decode_sse(raw)
        final = frames[-2] if len(frames) >= 2 else {}
        streamed = [f["token"] for f in frames
                    if isinstance(f, dict) and "token" in f]
        out["sse_ok"] = bool(frames and frames[-1] == "[DONE]" and streamed
                             == (final.get("choices") or [{}])[0].get("tokens"))
    else:
        final = json.loads(raw)
    choice = (final.get("choices") or [{}])[0]
    out["tokens"], out["finish"] = choice.get("tokens"), choice.get("finish_reason")
    return out


def _reference_check(cfg, params, served: list) -> dict:
    """Teacher-forced agreement of served tokens with the model's plain
    ``gpt2.forward`` — XLA attention, no cache, float32 weights at "highest"
    matmul precision — and, for scale, of a plain bf16 forward with it."""
    import jax
    import jax.numpy as jnp

    from deepspeed_tpu.models import gpt2

    forward = jax.jit(
        lambda p, ids: gpt2.forward(cfg, p, ids, attn_impl="xla"))
    params32 = jax.tree_util.tree_map(
        lambda x: x.astype(jnp.float32)
        if jnp.issubdtype(x.dtype, jnp.floating) else x, params)
    matches = total = 0
    max_gap = noise = 0.0
    for prompt, tokens in served:
        ids = np.zeros((1, cfg.max_seq_len), np.int32)  # causal: pad is inert
        seq = prompt + tokens
        ids[0, :len(seq)] = seq
        rows = np.arange(len(prompt) - 1, len(seq) - 1)  # row i predicts i+1
        with jax.default_matmul_precision("highest"):
            ref = np.asarray(forward(params32, ids)[0, rows], np.float32)
        low = np.asarray(forward(params, ids)[0, rows], np.float32)
        _require(np.isfinite(ref).all(), "serve: reference logits not finite")
        picked = ref[np.arange(len(rows)), tokens]
        max_gap = max(max_gap, float((ref.max(-1) - picked).max()))
        noise = max(noise, float(np.abs(low - ref).max()))
        matches += int((ref.argmax(-1) == np.asarray(tokens)).sum())
        total += len(tokens)
    rate = matches / total
    _require(max_gap <= LOGIT_GAP_NOISE_FACTOR * noise,
             f"serve: a served token sits {max_gap:.4f} below the reference's "
             f"best logit; bf16 noise on these positions is {noise:.4f}")
    _require(rate >= MATCH_RATE_MIN,
             f"serve: greedy match rate {rate:.3f} < {MATCH_RATE_MIN}")
    return {"requests": len(served), "tokens": total,
            "greedy_match_rate": round(rate, 4),
            "max_logit_gap": round(max_gap, 5),
            "bf16_logit_noise": round(noise, 5),
            "gap_limit": round(LOGIT_GAP_NOISE_FACTOR * noise, 5),
            "match_rate_min": MATCH_RATE_MIN}


def serve_phase(cfg, rcfg, requests: list, *, sample: int,
                expect_kernels: bool, request_timeout: float = 600.0) -> dict:
    """``requests`` over real HTTP against one engine behind the router."""
    import jax
    import jax.numpy as jnp

    from deepspeed_tpu import serving
    from deepspeed_tpu.inference.ragged import RaggedInferenceEngine
    from deepspeed_tpu.models import gpt2

    meter = _CompileMeter()
    t_phase = time.perf_counter()
    # bf16 weights straight from the seed: no float32 copy ever resident
    params = jax.jit(lambda key: jax.tree_util.tree_map(
        lambda x: x.astype(jnp.bfloat16), gpt2.init_params(cfg, key)))(
            jax.random.PRNGKey(SEED))
    engine = RaggedInferenceEngine(
        lambda ctx: gpt2.build(cfg, ctx=ctx), rcfg, dtype=jnp.bfloat16,
        params=params, seed=SEED)
    t0 = time.perf_counter()
    warmed = engine.warmup()
    warmup_s = time.perf_counter() - t0

    pool_tokens = (rcfg.num_blocks - 1) * rcfg.block_size
    frontend, router, loops = serving.build_server(
        [engine], router_cfg=serving.RouterConfig(max_queue_tokens=pool_tokens))
    results: list = [None] * len(requests)
    try:
        gate = threading.Barrier(len(requests))

        def client(i):
            gate.wait()
            results[i] = _complete(frontend, requests[i], request_timeout)

        threads = [threading.Thread(target=client, args=(i,), daemon=True)
                   for i in range(len(requests))]
        t_load = time.perf_counter()
        for t in threads:
            t.start()
        health = _get(frontend, "/healthz")
        for t in threads:
            t.join(timeout=request_timeout + 60)
        load_s = time.perf_counter() - t_load
        _require(not any(t.is_alive() for t in threads),
                 "serve: a client did not finish")
        metrics = _get(frontend, "/metrics")
    finally:
        # drain as .claude/skills/verify/SKILL.md says: stop admitting,
        # let the loops finish what is in flight, then close the listener
        router.begin_drain()
        drained = all([lp.join(timeout=120) for lp in loops])
        frontend.close()

    _require(health[0] == 200 and "status" in json.loads(health[1]),
             f"serve: /healthz answered {health[0]}")
    _require(metrics[0] == 200 and "serving_requests_admitted_total"
             in metrics[1], f"serve: /metrics answered {metrics[0]}")
    _require(drained, "serve: an engine loop did not drain")
    for req, res in zip(requests, results):
        _require(res is not None and res["status"] == 200,
                 f"serve: request failed: {res}")
        _require(res["finish"] == "length"
                 and len(res["tokens"]) == req["max_tokens"],
                 f"serve: wanted {req['max_tokens']} tokens, got {res}")
        _require(res.get("sse_ok", True), "serve: SSE frames disagree with "
                 "the final body")
    in_flight = max(sum(1 for o in results if o["t0"] <= r["t0"] < o["t1"])
                    for r in results)
    _require(in_flight >= min(4, len(requests)),
             f"serve: only {in_flight} requests were in flight together")
    _require(engine.degraded_mode == 0,
             f"serve: engine degraded to mode {engine.degraded_mode}: "
             f"{engine.degraded_reason}")
    _require(engine.step_failures == 0,
             f"serve: {engine.step_failures} step failure(s)")
    _require(not engine.has_work, "serve: engine still has work after drain")
    _require(engine.allocator.free_blocks == rcfg.num_blocks - 1,
             f"serve: {engine.allocator.free_blocks} KV blocks free of "
             f"{rcfg.num_blocks - 1}")

    # which attention ran where: the tiled prefill programs must hold the
    # Pallas kernel; decode is the paged-decode kernel at every table width
    # (ops/attention.paged_attention)
    prefill_programs = {k: fn for k, fn in engine._dev_step_jits.items()
                        if k[2] > 0}
    _require(prefill_programs, "serve: no tiled prefill program was built")
    kernels = _prefill_custom_calls(engine, *next(iter(prefill_programs.items())))
    if expect_kernels:
        _require(kernels >= 1,
                 "serve: the tiled prefill program holds no tpu_custom_call")

    stats = {"dispatches": engine.dispatch_count,
             "tokens_emitted": engine.tokens_emitted,
             "tokens_scheduled": engine.tokens_scheduled,
             "tokens_padded": engine.tokens_padded,
             "preemptions": engine.preemptions,
             "programs": {"dev_step": len(engine._dev_step_jits)},
             "cold_dispatches": engine.program_cold_dispatches}
    mem = _memory()
    engine.cache = None  # the pool's bytes go to the float32 reference
    gc.collect()
    picks = np.random.default_rng(SEED + 2).choice(
        len(requests), size=min(sample, len(requests)), replace=False)
    numerics = _reference_check(
        cfg, engine.params,
        [(requests[i]["prompt"], results[i]["tokens"]) for i in sorted(picks)])

    return _emit(
        "serve", seconds=round(time.perf_counter() - t_phase, 2),
        **meter.take(),
        shapes=_shapes(cfg, kv_pool_tokens=pool_tokens,
                       prompt_tokens=[len(r["prompt"]) for r in requests],
                       max_tokens=[r["max_tokens"] for r in requests]),
        reduced={},
        config={k: getattr(rcfg, k) for k in (
            "max_tokens_per_step", "max_seqs", "block_size", "num_blocks",
            "max_blocks_per_seq", "device_state", "prefill_tile")},
        warmup={"programs": warmed, "seconds": round(warmup_s, 2)},
        load_seconds=round(load_s, 2),
        requests={"n": len(requests),
                  "sse": sum(r["stream"] for r in requests),
                  "max_in_flight": in_flight,
                  "latency_seconds": [round(r["t1"] - r["t0"], 2)
                                      for r in results]},
        engine=stats,
        attention={"prefill": f"pallas tiled ({kernels} tpu_custom_call)",
                   "decode": "pallas paged_decode "
                             "(ops/attention.paged_attention)"},
        numerics=numerics, memory=mem)


def _prefill_custom_calls(engine, key, fn) -> int:
    """``tpu_custom_call`` count in one device-resident tiled step program,
    lowered against the shapes ``_dispatch_step_device`` hands it."""
    import jax
    import jax.numpy as jnp

    t_total, _, nt = key[:3]

    def abstract(tree):
        return jax.tree_util.tree_map(
            lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype), tree)

    cache = jax.eval_shape(lambda: engine._build_cache())
    staged = jax.ShapeDtypeStruct((4 * t_total + 3 * max(nt, 1),), jnp.int32)
    return fn.lower(abstract(engine.params), cache, abstract(engine._dev_state),
                    abstract(engine._bt_dev), staged,
                    abstract(engine._sample_root)).as_text().count(
                        "tpu_custom_call")


# ------------------------------------------------------- four chips, sharded
def _median_step(seconds) -> float:
    return round(float(np.median(seconds[2:] or seconds[-1:])), 4)


def sharded_compare_phase(cfg, *, full_layers: int, micro_batch: int,
                          seq_len: int, steps: int,
                          expect_reduce_scatter: bool) -> dict:
    """One process driving every device jax found (four on the chip host):
    ``mesh {"fsdp": n}`` stage 3 against ``mesh {"data": n}`` stage 0, same
    depth, seed and global batch — the loss curves must agree — and where
    stage 3 put every parameter and optimizer leaf."""
    import jax

    meter = _CompileMeter()
    t_phase = time.perf_counter()
    devices = jax.devices()
    n = len(devices)

    # the comparison: whole state on every device, gradients all-reduced
    engine, batch = _train_engine(cfg, micro_batch, seq_len,
                                  {"data": n, "fsdp": 1}, zero_stage=0)
    data_losses, data_seconds = _timed_steps(engine, batch, steps)
    _free(engine)

    engine, batch3 = _train_engine(cfg, micro_batch, seq_len,
                                   {"data": 1, "fsdp": n}, zero_stage=3)
    _require(np.array_equal(batch["input_ids"], batch3["input_ids"]),
             "sharded: the two placements were given different batches")
    # placement, before any step: shards on n distinct devices, large
    # leaves really split, and no device holding more than its share
    leaves = jax.tree_util.tree_leaves((engine.params, engine.opt_state))
    whole = 0
    for leaf in leaves:
        shards = leaf.addressable_shards
        _require(len({s.device for s in shards}) == n,
                 f"sharded: a leaf of shape {leaf.shape} is on "
                 f"{len({s.device for s in shards})} device(s)")
        if shards[0].data.size == leaf.size:
            whole += 1
            _require(leaf.size < 2**20, "sharded: a leaf of shape "
                     f"{leaf.shape} sits whole on every device")
    in_use = [(d.memory_stats() or {}).get("bytes_in_use") for d in devices]
    if all(b is not None for b in in_use):  # the CPU rehearsal reports none
        even = sum(in_use) / n
        _require(max(abs(b - even) for b in in_use)
                 <= SHARDED_BYTES_SPREAD * even,
                 f"sharded: bytes_in_use per device {in_use} is not within "
                 f"{SHARDED_BYTES_SPREAD:.0%} of even")
    fsdp_losses, fsdp_seconds = _timed_steps(engine, batch3, steps)
    _require(all(np.isfinite(x) for x in data_losses + fsdp_losses),
             f"sharded: non-finite loss: {data_losses} / {fsdp_losses}")
    _require(np.allclose(fsdp_losses, data_losses, rtol=SHARDED_LOSS_RTOL),
             f"sharded: fsdp={n} stage 3 losses {fsdp_losses} leave data={n} "
             f"stage 0 losses {data_losses} by more than {SHARDED_LOSS_RTOL}")
    hlo = _step_lowering(engine, batch3).compile().as_text()
    # mentions, not ops: the TPU compiler writes the sharded gradient sum as
    # "all-reduce-scatter" fusions and collective-permute rings; the CPU
    # compiler of the rehearsal leaves all-reduce + slice
    collectives = {name: hlo.count(name) for name in (
        "all-gather", "reduce-scatter", "all-reduce", "collective-permute",
        "all-to-all")}
    _require(collectives["all-gather"] and collectives[
        "reduce-scatter" if expect_reduce_scatter else "all-reduce"],
             f"sharded: the stage-3 step's HLO holds {collectives}")
    memory = [_memory(d) for d in devices]
    _free(engine)
    return _emit(
        "sharded_compare", seconds=round(time.perf_counter() - t_phase, 2),
        **meter.take(), devices=n, shapes=_shapes(cfg, seq_len=seq_len),
        reduced=_reduced(cfg, full_layers),
        global_batch=int(batch3["input_ids"].shape[0]),
        data_stage0_losses=[round(x, 4) for x in data_losses],
        fsdp_stage3_losses=[round(x, 4) for x in fsdp_losses],
        loss_rtol=SHARDED_LOSS_RTOL,
        data_step_seconds_median=_median_step(data_seconds),
        fsdp_step_seconds_median=_median_step(fsdp_seconds),
        placement={"leaves": len(leaves), "whole_small_leaves": whole,
                   "bytes_in_use_per_device_before_steps": in_use,
                   "collective_mentions_in_step_hlo": collectives},
        memory=memory)


def sharded_full_phase(cfg, *, seq_len: int, steps: int) -> dict:
    """Every layer under ``mesh {"fsdp": n}`` stage 3: more state (12 B/param
    of fp32 masters and Adam moments alone) than one chip holds."""
    import jax

    meter = _CompileMeter()
    t_phase = time.perf_counter()
    devices = jax.devices()
    engine, batch = _train_engine(cfg, 1, seq_len,
                                  {"data": 1, "fsdp": len(devices)},
                                  zero_stage=3)
    num_params = engine.model_spec.num_params
    losses, seconds = _timed_steps(engine, batch, steps)
    _require(all(np.isfinite(x) for x in losses) and losses[-1] < losses[0]
             and losses[-1] < losses[1],
             f"sharded: full-depth loss did not fall: {losses}")
    memory = [_memory(d) for d in devices]
    _free(engine)
    return _emit(
        "sharded_full", seconds=round(time.perf_counter() - t_phase, 2),
        **meter.take(), devices=len(devices),
        shapes=_shapes(cfg, seq_len=seq_len),
        reduced={}, params=num_params,
        state_bytes_fp32_master_adam=12 * num_params,
        global_batch=int(batch["input_ids"].shape[0]),
        losses=[round(x, 4) for x in losses],
        first_step_seconds=round(seconds[0], 3),
        step_seconds_median=_median_step(seconds), memory=memory)


# -------------------------------------------------------------------- main
def main(argv=None) -> int:
    parser = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    parser.add_argument("--chips", type=int, choices=(1, 4), default=1,
                        help="4: only the sharded-training comparison")
    args = parser.parse_args(argv)

    device = device_phase(args.chips)  # exits non-zero off the chip

    from deepspeed_tpu.inference.ragged import RaggedConfig
    from deepspeed_tpu.models import gpt2
    from deepspeed_tpu.utils.compile_cache import enable_compile_cache

    cache_dir = enable_compile_cache()
    total = _CompileMeter()
    t0 = time.perf_counter()
    _emit("device", device=device, compile_cache_dir=cache_dir,
          memory=_memory())
    xl = gpt2.GPT2Config.gpt2_xl()
    try:
        if args.chips == 4:
            sharded_compare_phase(
                dataclasses.replace(xl, num_layers=TRAIN_LAYERS),
                full_layers=xl.num_layers, micro_batch=2, seq_len=SEQ_LEN,
                steps=6, expect_reduce_scatter=True)
            sharded_full_phase(xl, seq_len=SEQ_LEN, steps=4)
        else:
            # GPT-2 XL; Llama-3-8B / Mixtral; Nemotron-3's 16 query heads a
            # KV head on 256-lane rows
            kernels_phase([(xl.num_heads, xl.num_heads, xl.hd), (32, 8, 128),
                           (32, 2, 128)])
            train_phase(
                dataclasses.replace(xl, num_layers=TRAIN_LAYERS),
                full_layers=xl.num_layers, micro_batch=TRAIN_MICRO_BATCH,
                seq_len=SEQ_LEN, steps=TRAIN_STEPS, expect_kernels=True)
            # what a deployment sets on this chip: scheduler state on the
            # device (the default) and prompts through the tiled prefill
            # kernel. A K-step program (sched_steps, removed by PR 43 as the
            # two older K-step modes were by PR 28) held the KV pool about
            # three times over, and with 48 layers and this pool the TPU
            # compiler refused it (21.9 GB of 15.75): decode is one dispatch
            # a step.
            serve_phase(
                xl,
                RaggedConfig(
                    max_tokens_per_step=STEP_TOKENS, max_seqs=MAX_SEQS,
                    block_size=KV_BLOCK, num_blocks=KV_BLOCKS,
                    max_blocks_per_seq=xl.max_seq_len // KV_BLOCK,
                    prefill_tile=PREFILL_TILE),
                serve_requests(10, (64, 896), (32, 128), xl.vocab_size),
                sample=3, expect_kernels=True)
    except SmokeFailure as e:
        print(f"chip_smoke: FAILED: {e}", file=sys.stderr)
        return 1
    _emit("summary", seconds=round(time.perf_counter() - t0, 2),
          compile_cache_dir=cache_dir, **total.take(), memory=_memory(),
          claim=None)
    print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
