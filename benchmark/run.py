"""One cell, once, in a new process:

    python benchmark/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

Makes the weights on the device from the seed, warms the cell's own shapes,
measures for ``--seconds``, checks correctness outside the window and prints
the contract's JSON object as the last line of stdout: with ``--trace 0`` the
cell's end-to-end metrics, with ``--trace 1`` its per-layer metrics (read by
``layer_metrics/<name>.py``) and the device's busy seconds from the profiler.
No TPU, or not the chips the cell asks for: exit 2, no result, never the CPU.

Everything about a cell is data under this directory (``cellspec.py``).
"""

from __future__ import annotations

import time

T_PROCESS = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)
sys.path.insert(0, os.path.dirname(HERE))


def device_or_exit(chips: int) -> dict:
    import jax

    devices = jax.devices()
    found = {"platform": devices[0].platform, "kind": devices[0].device_kind,
             "count": len(devices)}
    if found["platform"] != "tpu" or found["count"] != chips:
        print(f"benchmark: the cell needs {chips} TPU chip(s); jax found "
              f"{found}", file=sys.stderr)
        raise SystemExit(2)
    return found


def run_cell(spec: dict, seed: int, seconds: float, trace: bool,
             out_dir: str) -> dict:
    """Dispatch on the mix's kind; returns the cell's raw result."""
    if spec["mix"]["kind"] == "train_job":
        import train_cell

        return train_cell.run(spec, seed, seconds, trace, out_dir)
    import serve_cell

    return serve_cell.run(spec, seed, seconds, trace, out_dir)


def result_line(spec: dict, raw: dict, device: dict, trace: bool,
                peaks: dict) -> dict:
    """The contract's object from a cell's raw result."""
    import cellspec

    win = raw["window"]
    units = {m["name"]: m["unit"] for m in spec["end_to_end"] + spec["per_layer"]}
    end_to_end = dict(raw["metrics"])
    end_to_end["setup_s"] = win["t_window"] - T_PROCESS
    device = {**device, "memory_peak_bytes": win["memory_peak_bytes"]}
    line = {"correct": raw["correct"], "attempted": raw["attempted"],
            "failed": raw["failed"]}
    if not trace:
        values = {k: v for k, v in end_to_end.items() if k in units}
    else:
        ctx = {"window": win, "spec": spec, "peaks": peaks,
               "chips": device["count"], "end_to_end": end_to_end,
               **raw["context"]}
        values = {}
        for name, (entry, read) in cellspec.layer_readers(spec).items():
            if entry["moves"] not in end_to_end:
                continue
            value = read(ctx)
            if value is not None:
                values[name] = value
        reduced = win["trace"]
        device.update(busy_s=reduced["busy_s"], window_s=reduced["window_s"])
        line["breakdown"] = {"device_ops": reduced["top_ops"],
                             "idle_gaps": reduced["idle_gaps"]}
    line["metrics"] = {k: {"value": float(v), "unit": units[k]}
                       for k, v in values.items()}
    line["device"] = device
    return line


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--seed", type=int, required=True)
    p.add_argument("--seconds", type=float, required=True)
    p.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = p.parse_args(argv)

    import cellspec

    spec = cellspec.resolve(args.workload)
    device = device_or_exit(spec["chips"])
    peaks = cellspec.peaks_for(spec, device["kind"])

    from deepspeed_tpu.utils.compile_cache import enable_compile_cache

    cache_dir = enable_compile_cache()
    out_dir = os.path.join(os.path.dirname(HERE), ".bench_out",
                           f"{args.workload}.seed{args.seed}.trace{args.trace}")
    print(json.dumps({"phase": "start", "workload": args.workload,
                      "seed": args.seed, "device": device,
                      "compile_cache_dir": cache_dir}), flush=True)
    raw = run_cell(spec, args.seed, args.seconds, bool(args.trace), out_dir)
    print(json.dumps(result_line(spec, raw, device, bool(args.trace), peaks)),
          flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
