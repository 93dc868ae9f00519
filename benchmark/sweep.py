"""Find an open-loop cell's knee, once, when the cell is defined:

    python benchmark/sweep.py --workload <name> --seed <n> --seconds <s> --rates 2,2.5,3

One set-up, then one full window at each rate of the ladder, lowest first,
each on an idle engine (and for each of ``--seeds``: the traffic's seed; the
weights stay the first seed's). Prints one JSON line a window: attempted,
finished, failed, the mean number in flight over the middle and the last third
of the window (a backlog that grows is a rate past the knee), the tails, the
tokens emitted a second, the engine's counters. The knee is read off the
ladder by whoever defines the cell, and written with the ladder into
``cells/<name>.json``; the cell's fixed rate is 0.8 x knee. PR 22 found that
the rule it was given (in flight at the end <= at one third of the window, two
single readings) does not order a ladder under a fixed burst pattern; what
marked the knee was the step from one decode-batch program to the next
(``itl_p99_ms`` 64 -> 98 ms) and completed tokens falling behind offered ones.
The driver never runs this; it exists so that the next benchmark PR can find
the knee again after an optimisation has moved it.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
# what a ladder shows at each rate: the judged number and the tails that
# mark a knee (names as ``reduce.latency_metric`` reads them)
LADDER_METRICS = ("itl_trim5_ms", "itl_p50_ms", "itl_p99_ms", "ttft_p50_ms",
                  "ttft_p90_ms")
sys.path.insert(0, HERE)
sys.path.insert(0, os.path.dirname(HERE))


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--seed", type=int, default=1)
    p.add_argument("--seconds", type=float, required=True)
    p.add_argument("--rates", required=True)
    p.add_argument("--seeds", default=None)
    args = p.parse_args(argv)

    import cellspec
    import reduce
    import run
    import serve_cell

    spec = cellspec.resolve(args.workload)
    device = run.device_or_exit(spec["chips"])
    from deepspeed_tpu.utils.compile_cache import enable_compile_cache

    enable_compile_cache()
    rig = serve_cell.ServeRig(spec, args.seed, os.path.join(
        os.path.dirname(HERE), ".bench_out", f"{args.workload}.sweep"))
    try:
        seeds = ([int(x) for x in args.seeds.split(",")] if args.seeds
                 else [args.seed])
        windows = [(rate, seed)
                   for rate in sorted(float(r) for r in args.rates.split(","))
                   for seed in seeds]
        for rate, seed in windows:
            secs = args.seconds
            win = rig.window(secs, False, rate, tag=f"rate{rate:g}", seed=seed)
            tried = reduce.attempted(win["records"], secs, True)
            middle = reduce.mean_in_flight(win["records"], secs / 3, 2 * secs / 3)
            last = reduce.mean_in_flight(win["records"], 2 * secs / 3, secs)
            e2e = serve_cell.end_to_end(win, spec["mix"], LADDER_METRICS)
            print(json.dumps({
                "rate": rate, "seed": seed, "attempted": len(tried),
                "finished": sum(1 for r in tried if reduce.ok(r)),
                "failed": e2e["failed"],
                "in_flight_middle": middle, "in_flight_last": last,
                **e2e["metrics"],
                "stalls": serve_cell.frozen(win, spec["mix"]),
                "late_p99_ms": reduce.percentile(reduce.late_ms(tried), 99),
                "emitted_per_s": win["counters"]["tokens_emitted"] / secs,
                "counters": win["counters"], "device": device}), flush=True)
            for _ in range(600):  # the next rate starts on an idle engine
                if not rig.engine.has_work:
                    break
                time.sleep(0.1)
    finally:
        rig.close()
    return 0


if __name__ == "__main__":
    sys.exit(main())
