"""Set-up as the program put it down: the ``setup.*`` metrics' common reader.

``deepspeed_tpu/telemetry/compile_watch.py`` keeps, in the process that runs
the cell, one record for every program build (program, thread, begin and end
on ``time.perf_counter()``, the seconds jax spent tracing, lowering, in the
backend compile and in the persistent cache's retrieval, and whether the build
asked the cache, hit it, or had its executable written to it) and one for
every start-up phase the engines mark (``utils/tracing.phase``). This module
reads ``telemetry.snapshot()["startup"]`` in the reader's own process and
keeps the records that began before the window: set-up is
``[T_PROCESS, t_window)`` on the same clock, ``T_PROCESS`` being ``t_window -
setup_s``.

**No value rather than a wrong one**: every function returns None where the
program has no such log (the parent of the PR that added it), the watch is
not installed, or the log overflowed its bound before the window opened.

The six metrics that are seconds of the MAIN thread split ``setup_s`` without
overlap: ``setup.trace_s + setup.lower_s + setup.compile_s +
setup.cache_retrieval_s`` (the main thread's builds), ``setup.engine_init_s``
or ``setup.train_init_s`` (the marked phases less the builds inside them),
and ``setup.unattributed_s`` (the rest).

What was read is also written to ``.bench_out/<cell>.startup.json`` (beside
the traces; git ignores it), for whoever wants to see which thread built
which program.
"""

from __future__ import annotations

import json
import os

MAIN_THREAD = "MainThread"
BACKGROUND_THREADS = "ragged-compile"
STEP_PROGRAMS = "jit_ragged_"
ENGINE_PHASES = ("engine/init", "engine/warmup", "server/build")
TRAIN_PHASES = ("train/init",)


def read_snapshot() -> dict | None:
    """``telemetry.snapshot()["startup"]`` of this process, or None."""
    from deepspeed_tpu import telemetry

    return telemetry.snapshot().get("startup")


def log(ctx) -> dict | None:
    """``{"builds", "phases", "setup_s"}`` of the set-up (read once a run)."""
    win = ctx["window"]
    if "setup_log" not in win:
        win["setup_log"] = _read(ctx)
    return win["setup_log"]


def _read(ctx) -> dict | None:
    snap = read_snapshot()
    t_window = ctx["window"].get("t_window")
    setup_s = ctx.get("end_to_end", {}).get("setup_s")
    if not snap or t_window is None or setup_s is None:
        return None  # no log, or a context that says no window
    _keep(ctx, {"cell": ctx["spec"]["name"], "t_window": t_window,
                "setup_s": setup_s, "startup": snap})
    builds = [b for b in snap["builds"] if b["t0"] < t_window]
    if snap["overflowed"] and len(builds) == len(snap["builds"]):
        return None  # the log was full before the window opened
    return {"builds": builds, "setup_s": setup_s,
            "phases": [p for p in snap["phases"] if p["t0"] < t_window]}


def _keep(ctx, record: dict) -> None:
    out = os.path.join(os.path.dirname(ctx["spec"]["base"]), ".bench_out")
    try:
        os.makedirs(out, exist_ok=True)
        with open(os.path.join(out, ctx["spec"]["name"] + ".startup.json"),
                  "w") as f:
            json.dump(record, f)
    except OSError:
        pass


def main_builds(ctx) -> list | None:
    got = log(ctx)
    return got and [b for b in got["builds"] if b["thread"] == MAIN_THREAD]


def main_sum(ctx, field: str, less: str | None = None) -> float | None:
    """Sum of ``field`` (less the field ``less``) over the main thread's
    builds."""
    builds = main_builds(ctx)
    if builds is None:
        return None
    return sum(b[field] - (b[less] if less else 0.0) for b in builds)


def built_s(b: dict) -> float:
    return b["trace_s"] + b["lower_s"] + b["compile_s"]


def phases_rest_s(ctx, names) -> float | None:
    """Seconds of the phases called ``names`` less the builds their own
    thread began inside them."""
    got = log(ctx)
    if got is None:
        return None
    total = 0.0
    for p in got["phases"]:
        if p["name"] not in names:
            continue
        total += p["t1"] - p["t0"] - sum(
            built_s(b) for b in got["builds"]
            if b["thread"] == p["thread"] and p["t0"] <= b["t0"] < p["t1"])
    return total


def cache_hit_share(ctx) -> float | None:
    """Builds that hit an entry the persistent cache held when the process
    started, over the builds whose executable the cache holds or would hold.
    Left out of both: a build that never asked; one that asked, missed and
    was not written (under jax's compile-time threshold: no process ever
    hits it); and a hit on an entry this same process wrote (a program the
    background threads compiled seconds before the foreground asked)."""
    got = log(ctx)
    if got is None:
        return None
    written = [b for b in got["builds"]
               if b["cache"] == "miss" and b["written"]]
    own = {b["program"] for b in written}
    hits = sum(1 for b in got["builds"]
               if b["cache"] == "hit" and b["program"] not in own)
    return hits / (hits + len(written)) if hits + len(written) else None


def program_builds(ctx) -> int | None:
    got = log(ctx)
    if got is None:
        return None
    return sum(1 for b in got["builds"]
               if b["compiled"] and b["program"].startswith(STEP_PROGRAMS))


def background_compile_s(ctx) -> float | None:
    got = log(ctx)
    if got is None:
        return None
    return sum(b["compile_s"] for b in got["builds"]
               if b["thread"].startswith(BACKGROUND_THREADS))


def unattributed_s(ctx) -> float | None:
    """``setup_s`` less everything the other five split of it; never
    negative (a phase on another thread can overlap the main thread's)."""
    got = log(ctx)
    if got is None:
        return None
    seen = (sum(built_s(b) for b in main_builds(ctx))
            + phases_rest_s(ctx, ENGINE_PHASES + TRAIN_PHASES))
    return max(0.0, got["setup_s"] - seen)
