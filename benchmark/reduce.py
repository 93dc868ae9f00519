"""From the load generator's records to numbers: window and percentile rules.

Times are seconds from the window's start; the window is ``[0, seconds)``.

Open loop: a request **due** inside the window is attempted. One that got no
200, or a whole answer with fewer tokens than it asked for, or not even a
first token before the run ended, is failed, and its time to first token
counts as ``missing`` (larger than any real one), so that it misses every
tail. One that was still streaming when the run ended (``cut``) is neither
finished nor failed: its first token and its gaps so far count.

Closed loop: requests **sent** inside the window are attempted; throughput
counts every finished request's tokens for the share of its time in the system
that lay inside the window (``window_tokens``).
"""

from __future__ import annotations

import math
import re


def percentile(values, q: float) -> float:
    """Linear-interpolated ``q``-th percentile (numpy's default rule)."""
    xs = sorted(values)
    if not xs:
        raise ValueError("percentile of no values")
    k = (len(xs) - 1) * q / 100.0
    lo, hi = math.floor(k), math.ceil(k)
    return xs[lo] + (xs[hi] - xs[lo]) * (k - lo)


def trimmed_mean(values, percent: float) -> float:
    """Mean of what is left when the smallest and the largest ``percent`` per
    cent of the values are dropped. Unlike a percentile it moves smoothly when
    a little of the distribution moves (a percentile that sits between two
    clusters of values jumps), and unlike the mean a few enormous values (a
    machine that stood still for seconds) do not move it at all."""
    xs = sorted(values)
    if not xs:
        raise ValueError("trimmed mean of no values")
    k = int(len(xs) * percent / 100.0)
    kept = xs[k:len(xs) - k]
    return sum(kept) / len(kept)


def stat(values, how: str) -> float:
    """``values`` reduced as a metric's name says: ``p90`` (a percentile) or
    ``trim5`` (``trimmed_mean`` without 5% at either end)."""
    m = re.fullmatch(r"(p|trim)(\d+)", how)
    if not m:
        raise ValueError(f"unknown statistic {how!r}")
    fn = percentile if m.group(1) == "p" else trimmed_mean
    return fn(values, float(m.group(2)))


LATENCY_NAME = re.compile(r"(ttft|itl)_([a-z]+\d+)_ms")


def latency_metric(name: str, tried, missing_ms: float) -> float | None:
    """The open-loop latency number called ``name`` -- ``ttft_<stat>_ms`` or
    ``itl_<stat>_ms``, ``stat`` as in ``stat`` -- over the attempted requests
    ``tried``; None for another kind of name or where there is nothing to
    reduce."""
    m = LATENCY_NAME.fullmatch(name)
    if not m:
        return None
    values = ttft_ms(tried, missing_ms) if m.group(1) == "ttft" else gaps_ms(tried)
    return stat(values, m.group(2)) if values else None


def ok(rec: dict) -> bool:
    return (rec["status"] == 200 and rec["tokens"] is not None
            and len(rec["tokens"]) == rec["max_tokens"])


def failed(rec: dict) -> bool:
    return not ok(rec) and not rec.get("cut", False)


def attempted(records, seconds: float, open_loop: bool) -> list:
    key = "due" if open_loop else "sent"
    return [r for r in records
            if r.get(key) is not None and 0.0 <= r[key] < seconds]


def ttft_ms(records, missing_ms: float) -> list:
    """Due time -> first token frame, per attempted streaming request."""
    return [(r["first"] - r["due"]) * 1e3
            if not failed(r) and r["first"] is not None else missing_ms
            for r in records]


def missing_ttft_ms(seconds: float, mix: dict) -> float:
    """What a request that never got a first token counts as: longer than
    any real time to first token of the run."""
    return (seconds + mix["grace_seconds"]) * 1e3


def gaps_ms(records) -> list:
    """Gaps between successive token frames, pooled over the requests."""
    out = []
    for r in records:
        f = r["frames"]
        out.extend((b - a) * 1e3 for a, b in zip(f, f[1:]))
    return out


def late_ms(records) -> list:
    """How late the generator sent each request (sent - due)."""
    return [(r["sent"] - r["due"]) * 1e3 for r in records
            if r.get("sent") is not None and r.get("due") is not None]


def window_tokens(records, seconds: float) -> float:
    """Prompt + generated tokens served inside the window: each finished
    request's tokens spread evenly over its time in the system (sent -> end)
    and counted for the part of that time inside ``[0, seconds)``. Whole
    requests that end inside the window would count the same work on average
    (as many straddle the start as the end), but with some tens of long
    requests in a window the straddlers make that count swing by a tenth."""
    total = 0.0
    for r in records:
        if not ok(r) or r["end"] <= r["sent"]:
            continue
        inside = min(r["end"], seconds) - max(r["sent"], 0.0)
        if inside > 0:
            total += ((r["prompt_len"] + len(r["tokens"]))
                      * inside / (r["end"] - r["sent"]))
    return total


def request_ms(records) -> list:
    return [(r["end"] - r["sent"]) * 1e3 for r in records if ok(r)]


def in_flight(records, t: float) -> int:
    """Requests sent and not yet ended at time ``t`` (unanswered: forever)."""
    return sum(1 for r in records if r.get("sent") is not None
               and r["sent"] <= t and (r["end"] is None or r["end"] > t))


def mean_in_flight(records, t0: float, t1: float, step: float = 0.5) -> float:
    """Mean of ``in_flight`` over ``[t0, t1)``, read every ``step`` seconds."""
    n = max(1, int((t1 - t0) / step))
    return sum(in_flight(records, t0 + i * step) for i in range(n)) / n


def slo_share(records, ttft_limit_ms: float, gap_limit_ms: float) -> float:
    """Share of attempted requests that met both latency limits."""
    if not records:
        raise ValueError("slo_share of no requests")
    met = 0
    for r in records:
        if failed(r) or r["first"] is None:
            continue
        f = r["frames"]
        worst_gap = max(((b - a) * 1e3 for a, b in zip(f, f[1:])), default=0.0)
        if (r["first"] - r["due"]) * 1e3 <= ttft_limit_ms and worst_gap <= gap_limit_ms:
            met += 1
    return met / len(records)


def step_roofline(ctx) -> float | None:
    """The least time the chip could take for the window's *required* work
    over the time the device was busy, in per cent (both scaled to the traced
    slice):

        max( 2 x active parameters x tokens scheduled / peak FLOP/s,
             dispatches x weight bytes / peak bytes/s )

    Active parameters are what the architecture needs (top-k experts), not
    what the program spends; weight bytes are what a step must read once
    (``reference/<family>.py``). KV-cache reads and attention FLOPs are left
    out (no per-dispatch context sums exist yet), so the share is a little
    low at long contexts."""
    win = ctx["window"]
    c, trace = win["counters"], win["trace"]
    if not trace or not c.get("dispatch_count"):
        return None
    ref, cfg, peaks = ctx["reference"], ctx["cfg"], ctx["peaks"]
    compute_s = (2.0 * ref.active_params(cfg) * c["tokens_scheduled"]
                 / peaks["bf16_flops_per_s"])
    bytes_s = c["dispatch_count"] * ref.weight_bytes(cfg) / peaks["hbm_bytes_per_s"]
    busy_in_window = trace["busy_s"] / trace["window_s"] * win["seconds"]
    return 100.0 * max(compute_s, bytes_s) / busy_in_window


def kernel_share(ctx, kernel: str) -> float | None:
    """Device time of the events ``kernels/<kernel>.json`` names over device
    busy time in the traced slice, in per cent."""
    trace = ctx["window"]["trace"]
    if not trace or kernel not in trace["kernel_s"]:
        return None
    return 100.0 * trace["kernel_s"][kernel] / trace["busy_s"]
