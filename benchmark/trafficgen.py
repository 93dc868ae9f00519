"""The one traffic generator: a mix's data file + a seed -> a request schedule.

Stdlib only (the load generator child imports this and never imports jax).
Everything is a pure function of ``(mix, seed, stream, index)``: the same seed
gives the same schedule, byte for byte; ``stream`` separates the warm-up slice
(1) from the measured slice (0) of one seed.

Lengths are drawn *stratified*: requests come in blocks of ``STRATUM`` and a
block holds one draw from each of ``STRATUM`` equal-probability slices of the
distribution, in a seeded order. Every block therefore carries nearly the same
amount of work, whatever the seed, and a run's totals repeat.

An open loop's schedule -- when each request is due and how long its prompt
and its answer are -- comes from the mix's own ``pattern_seed`` and not from
the run's seed: it is part of the mix, replayed like a recorded trace, and the
run's seed decides what is said (the token ids) and the weights. The tail of
the time to first token is set by how bursts and long prompts fall together;
with both redrawn every run its 90th percentile over ~290 requests swung by
4-14% between runs of the same code on the chip (PR 22), which no bound a later
PR could be held to survives. Arrival gaps are gamma draws with the mix's
coefficient of variation, scaled so that exactly ``round(rate x seconds)``
requests are due in the window. A closed loop has no schedule: its lengths are
the run's seed's.
"""

from __future__ import annotations

import math
import random
from statistics import NormalDist

STRATUM = 8
_NORMAL = NormalDist()


def _rng(seed: int, stream: int, *more) -> random.Random:
    return random.Random(":".join(str(x) for x in (seed, stream, *more)))


def _quantile(dist: dict, u: float) -> int:
    """The ``u``-quantile of a length distribution, clipped to its range."""
    if dist["dist"] == "lognormal":
        x = math.exp(math.log(dist["median"]) + dist["sigma"] * _NORMAL.inv_cdf(u))
    elif dist["dist"] == "uniform":
        x = dist["min"] + u * (dist["max"] - dist["min"])
    elif dist["dist"] == "fixed":
        x = dist["value"]
    else:
        raise ValueError(f"unknown length distribution {dist['dist']!r}")
    return int(min(max(round(x), dist.get("min", 1)), dist.get("max", round(x))))


def _stratified_u(seed: int, stream, what: str, index: int) -> float:
    block, slot = divmod(index, STRATUM)
    rng = _rng(seed, stream, what, block)
    order = list(range(STRATUM))
    rng.shuffle(order)
    return (order[slot] + rng.random()) / STRATUM


def request(mix: dict, seed: int, stream, index: int) -> dict:
    """Request ``index`` of the stream: its lengths (ids: ``prompt_tokens``)."""
    prompt = _quantile(mix["prompt_tokens"],
                       _stratified_u(seed, stream, "prompt", index))
    out = _quantile(mix["output_tokens"],
                    _stratified_u(seed, stream, "output", index))
    out = max(1, min(out, mix["total_tokens_max"] - prompt))
    return {"i": index, "stream_id": stream, "prompt_len": prompt,
            "max_tokens": out, "stream": bool(mix["stream"])}


def prompt_tokens(seed: int, stream, index: int, length: int,
                  vocab: int) -> list:
    """The token ids of request ``index``: uniform over the vocabulary."""
    return _rng(seed, stream, "ids", index).choices(range(vocab), k=length)


def _arrivals(rng: random.Random, shape: float, n: int, start: float,
              length: float) -> list:
    """``n`` arrival times in ``[start, start + length)``: gamma gaps scaled
    so that the count is exact (one extra gap closes the interval)."""
    gaps = [rng.gammavariate(shape, 1.0) for _ in range(n + 1)]
    scale = length / sum(gaps)
    out, t = [], start
    for g in gaps[:n]:
        t += g * scale
        out.append(t)
    return out


def open_schedule(mix: dict, rate: float, stream: int,
                  seconds: float) -> list:
    """Arrivals at ``rate`` a second: a lead-in of ``lead_seconds`` (its own
    stream, ``"<stream>.lead"``) and then exactly ``round(rate x seconds)``
    requests due inside the window. Each entry is ``request(...)`` plus
    ``due``, seconds from the window's start. Gamma gaps of shape 1/cv^2
    (cv 1 is Poisson)."""
    shape = 1.0 / float(mix["arrivals"]["cv"]) ** 2
    pattern = mix["arrivals"]["pattern_seed"]
    rng = _rng(pattern, stream, "arrivals")
    lead = float(mix["lead_seconds"])
    out = []
    for part, start, length in ((f"{stream}.lead", -lead, lead),
                                (stream, 0.0, float(seconds))):
        n = max(1, round(rate * length))
        for i, due in enumerate(_arrivals(rng, shape, n, start, length)):
            out.append({**request(mix, pattern, part, i), "due": due})
    return out
