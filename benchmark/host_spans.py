"""The host's spans and the device's program executions of a traced slice, on
the profiler's one clock, and the dispatch -> execution matcher on top.

The program writes ``jax.profiler.TraceAnnotation`` spans while a profiler
session is open (``deepspeed_tpu/utils/tracing.span``): on the thread that
drives the serving engine ``engine/schedule``, ``engine/stage``,
``engine/dispatch`` (arguments: ``program``, ``tokens``, ``pad``,
``kv_tokens``, ``attn_pairs``, ``dec_kv_tokens``), ``engine/readback``,
``loop/inbox``, ``loop/deliver`` and the instants ``request/admit``,
``request/first_token`` (argument ``wait_s``); in training ``train_step``
(``StepTraceAnnotation``), ``train/stage_batch`` and ``train/dispatch``. They
land in the ``/host:CPU`` plane of the same ``.xplane.pb`` whose
``/device:TPU:<n>`` planes ``trace_reduce.py`` reads, one line per thread.

``load_xplane(path, kernel_patterns)`` -> a plain dict (what a fixture under
``tests/fixtures/spans/`` keeps), times in ns:

    {"host": [{"thread", "events": [[name, start, dur, {arg: value}], ...]}],
     "modules": [[name, start, dur], ...]   first chip, line ``XLA Modules``
     "busy": [[start, end], ...]            first chip, union of its operations
     "kernels": {name: [[start, dur], ...]} first chip, operations matching
                                            ``kernels/<name>.json``}

A program that writes no span (a parent commit) gives ``"host": []`` and every
reader here returns None.

A dispatch is matched to its execution **by order**: the device runs programs
in the order the host enqueued them, so the n-th ``engine/dispatch`` span is
the n-th execution of a step program after the slice's first matched pair.
The slice starts mid-stream, so up to ``MAX_LEAD`` leading executions belong
to dispatches from before the slice; the lead is the smallest one under which
every pair is causal (an execution starts after its dispatch's enqueue began)
and the names agree (``jit_<program>``; with unnamed programs the
fingerprint in the module event's name must map to one program key).
Dispatches whose execution fell after the slice's end stay unmatched: when
the profiler stops, one step is executing and the next is already enqueued,
so the slice's last ``PIPELINE_DEPTH`` dispatch spans are not held against
the match where they have no execution. Under ``MATCHED_MIN`` of the others
matched, no reader gives a value.
"""

from __future__ import annotations

import bisect
import glob
import os
import re
import statistics

import cellspec
import trace_reduce

HOST_PLANE = "/host:CPU"
SPAN_PREFIXES = ("engine/", "loop/", "request/", "train/", "train_step")
# the executions dispatch spans are matched to: the serving engine's
# device-resident step programs (named by their key, or ``step_fn`` where a
# build does not name them) and the training step
STEP_PROGRAM = re.compile(r"^(ragged_step_\w+|step_fn|train_batch_fn)$")
DISPATCH_SPANS = ("engine/dispatch", "train/dispatch")
MAX_LEAD = 4
MATCHED_MIN = 0.9
PIPELINE_DEPTH = 2
# an execution may be stamped this much before its dispatch span's start: in
# the slices recorded on the chip the device plane runs ~1 ms ahead of the
# host plane (an idle chip "starts" a step 1.0 ms before the span that
# enqueues it; a readback ends 2.4 ms after the execution it waits for). A
# lead too small pairs a dispatch with the step before it, most of a step
# time earlier: 27-560 ms in the benchmark's cells.
CAUSAL_SLACK_NS = 2e6


def load_xplane(path: str, kernel_patterns: dict | None = None) -> dict:
    from jax.profiler import ProfileData

    compiled = {k: re.compile(v) for k, v in (kernel_patterns or {}).items()}
    out = {"host": [], "modules": [], "busy": [],
           "kernels": {k: [] for k in compiled}}
    device_seen = False
    for plane in ProfileData.from_file(path).planes:
        if plane.name == HOST_PLANE:
            for line in plane.lines:
                events = [[ev.name, float(ev.start_ns), float(ev.duration_ns),
                           dict(ev.stats)]
                          for ev in line.events
                          if ev.name.startswith(SPAN_PREFIXES)]
                if events:
                    out["host"].append({"thread": line.name, "events": events})
        elif trace_reduce.DEVICE_PLANE.match(plane.name) and not device_seen:
            device_seen = True
            for line in plane.lines:
                if line.name == trace_reduce.MODULES_LINE:
                    out["modules"] = [[ev.name, float(ev.start_ns),
                                       float(ev.duration_ns)]
                                      for ev in line.events]
                elif line.name == trace_reduce.OPS_LINE:
                    ops = []
                    for ev in line.events:
                        name, s, d = ev.name, float(ev.start_ns), float(ev.duration_ns)
                        if d <= 0 or trace_reduce.is_container(name):
                            continue
                        ops.append((s, s + d))
                        for k, rx in compiled.items():
                            if rx.search(name):
                                out["kernels"][k].append([s, d])
                    out["busy"] = [list(iv) for iv in trace_reduce.union(ops)]
    return out


def timeline(ctx) -> dict | None:
    """The traced slice of ``ctx["window"]`` (parsed once a run), or None:
    an untraced run, or a program that wrote no span."""
    win = ctx["window"]
    if "host_spans" not in win:
        path = newest_trace(ctx)
        patterns = {k: v["trace_pattern"]
                    for k, v in cellspec.kernels(ctx["spec"]).items()}
        win["host_spans"] = load_xplane(path, patterns) if path else None
    tl = win["host_spans"]
    return tl if tl and tl["host"] and tl["modules"] else None


def newest_trace(ctx) -> str | None:
    """The window's ``.xplane.pb``: under ``window["trace_dir"]`` where the
    cell gives it (serving); the training cell keeps its directory to itself
    (``<run.py's out_dir>/trace_window``), so there the newest traced run of
    this cell under ``.bench_out/`` is taken, which is the one just made."""
    win, spec = ctx["window"], ctx["spec"]
    if win.get("trace_dir"):
        return trace_reduce.newest_xplane(win["trace_dir"])
    if "step_s" not in win or not win.get("trace"):
        return None
    runs = glob.glob(os.path.join(os.path.dirname(spec["base"]), ".bench_out",
                                  spec["name"] + ".seed*.trace1", "trace_window"))
    files = [f for f in map(trace_reduce.newest_xplane, runs) if f]
    return max(files, key=os.path.getmtime) if files else None


# ------------------------------------------------------------------ spans
def spans(tl: dict, name: str) -> list:
    """``[(start_ns, end_ns, args)]`` of every span called ``name``, any
    thread, by start."""
    return sorted(((s, s + d, a) for line in tl["host"]
                   for n, s, d, a in line["events"] if n == name),
                  key=lambda e: e[0])


def mean_wait_ms(ctx, name: str) -> float | None:
    """Mean ``wait_s`` of the instants called ``name`` in the slice, in ms."""
    tl = timeline(ctx)
    waits = [a["wait_s"] for _, _, a in spans(tl, name)] if tl else []
    return 1e3 * sum(waits) / len(waits) if waits else None


def self_seconds(tl: dict, name: str) -> float:
    """Seconds in spans called ``name`` less the spans nested inside them
    (``engine/stage`` of a row update inside the admission's
    ``engine/schedule``), summed over the threads."""
    return sum(sec for line in tl["host"]
               for n, sec in trace_reduce.self_seconds(
                   [[n, s, d, ""] for n, s, d, _ in line["events"]])
               if n == name)


def driver_thread(tl: dict) -> dict | None:
    """The thread that dispatches (the engine loop; in training the caller's)."""
    for line in tl["host"]:
        if any(n in DISPATCH_SPANS for n, _, _, _ in line["events"]):
            return line
    return None


def thread_extent_s(line: dict) -> float:
    return (max(s + d for _, s, d, _ in line["events"])
            - min(s for _, s, _, _ in line["events"])) * 1e-9


def slice_s(tl: dict) -> float:
    """The device's slice: first program's start to the last one's end."""
    return (max(s + d for _, s, d in tl["modules"])
            - min(s for _, s, _ in tl["modules"])) * 1e-9


def idle_intervals(tl: dict) -> list:
    """``[(start_ns, end_ns)]``: the gaps between the chip's operations that
    the host could have filled (``trace_reduce.GAP_FLOOR_S`` and longer)."""
    busy = tl["busy"]
    return [(a_end, b_start) for (_, a_end), (b_start, _) in zip(busy, busy[1:])
            if (b_start - a_end) * 1e-9 >= trace_reduce.GAP_FLOOR_S]


def covered_ns(intervals, cover) -> float:
    """Length of ``intervals`` inside the union of ``cover``."""
    merged = trace_reduce.union(cover)
    ends = [e for _, e in merged]
    return sum(trace_reduce.intersect_len(iv, merged, ends) for iv in intervals)


def span_intervals(tl: dict, names=None) -> list:
    return [(s, s + d) for line in tl["host"] for n, s, d, _ in line["events"]
            if d > 0 and (names is None or n in names)]


# ---------------------------------------------------------------- matcher
def program_of(module_name: str) -> tuple:
    """``jit_ragged_step_d8_t3(123)`` -> ``("ragged_step_d8_t3", "123")``."""
    m = re.match(r"^(.*?)(?:\((\d+)\))?$", module_name)
    return m.group(1).removeprefix("jit_"), m.group(2) or ""


def match(tl: dict) -> dict:
    """``{"dispatches": n, "eligible": n less the spans the slice's end cut
    off, "pairs": [(args, exec_start_ns, exec_dur_ns)]}``: the slice's
    dispatch spans matched to executions by order (module doc)."""
    if "match" in tl:
        return tl["match"]
    disp = sorted(((s, a) for line in tl["host"]
                   for n, s, _, a in line["events"] if n in DISPATCH_SPANS),
                  key=lambda e: e[0])
    execs = sorted((s, d, *program_of(n)) for n, s, d in tl["modules"])
    execs = [e for e in execs if STEP_PROGRAM.match(e[2])]
    best, offered = [], 0
    for lead in range(min(MAX_LEAD, len(execs)) + 1):
        pairs = _pairs_at(disp, execs[lead:])
        if len(pairs) > len(best):
            best, offered = pairs, min(len(disp), len(execs) - lead)
        if len(pairs) == min(len(disp), len(execs) - lead):
            break  # every pair this lead offers is good: the smallest such
    # the dispatches the device's slice ended on (module doc)
    cut_off = min(PIPELINE_DEPTH, len(disp) - offered)
    tl["match"] = {"dispatches": len(disp), "pairs": best,
                   "eligible": len(disp) - cut_off}
    return tl["match"]


def _pairs_at(disp, execs) -> list:
    """The pairs ``zip(disp, execs)`` that are causal and agree by name."""
    seen: dict = {}  # an unnamed program's fingerprint -> the key it ran
    pairs = []
    for (d_start, args), (e_start, e_dur, name, fingerprint) in zip(disp, execs):
        key = args.get("program")
        if e_start + CAUSAL_SLACK_NS < d_start:
            continue
        if key is not None and name.startswith("ragged_step_") and name != key:
            continue
        if key is not None and name == "step_fn" \
                and seen.setdefault(fingerprint, key) != key:
            continue
        pairs.append((args, e_start, e_dur))
    return pairs


def well_matched(tl: dict) -> bool:
    m = match(tl)
    return bool(m["eligible"]) and len(m["pairs"]) >= MATCHED_MIN * m["eligible"]


def matched(tl: dict, keep=lambda nd, nt: True) -> list | None:
    """The matched pairs whose program key ``ragged_step_d<nd>_t<nt>`` passes
    ``keep``; None under ``MATCHED_MIN`` of the slice's dispatches matched."""
    if not well_matched(tl):
        return None
    out = []
    for pair in match(tl)["pairs"]:
        key = re.match(r"ragged_step_d(\d+)_t(\d+)$", pair[0].get("program", ""))
        if key and keep(int(key.group(1)), int(key.group(2))):
            out.append(pair)
    return out


def exec_ms_p50(ctx, keep) -> float | None:
    """Median device duration of the executions matched to dispatches whose
    ``(decode rows, tiles)`` pass ``keep``."""
    tl = timeline(ctx)
    pairs = matched(tl, keep) if tl else None
    return statistics.median(d for _, _, d in pairs) * 1e-6 if pairs else None


# ------------------------------------------------------- model arithmetic
def attention_geometry(ctx) -> dict:
    """Per KV token and per query x key pair, all layers: the bytes a step
    must read (K and V once) and the FLOPs it must spend (QK^T and PV)."""
    cfg = ctx["cfg"]
    heads = cfg.num_heads
    kv_heads = getattr(cfg, "num_kv_heads", None) or heads
    head = cfg.hidden_size // heads
    itemsize = {"bfloat16": 2, "float16": 2, "float32": 4}[
        ctx["spec"]["config"]["serve"]["dtype"]]
    return {"kv_bytes_per_token": 2 * kv_heads * head * itemsize * cfg.num_layers,
            "flops_per_pair": 4 * heads * head * cfg.num_layers}


def step_roofline_kv(ctx) -> float | None:
    """``reduce.step_roofline`` with the attention's required work added,
    over the matched dispatches of the traced slice and the device time of
    their executions:

        max( (2 x active parameters x tokens + attention FLOPs x pairs) / peak FLOP/s,
             (dispatches x weight bytes + KV bytes x kv_tokens) / peak bytes/s )
    """
    tl = timeline(ctx)
    pairs = matched(tl) if tl else None
    if not pairs:
        return None
    ref, cfg, peaks, geo = ctx["reference"], ctx["cfg"], ctx["peaks"], attention_geometry(ctx)
    tokens = sum(a["tokens"] for a, _, _ in pairs)
    compute_s = ((2.0 * ref.active_params(cfg) * tokens
                  + geo["flops_per_pair"] * sum(a["attn_pairs"] for a, _, _ in pairs))
                 / peaks["bf16_flops_per_s"])
    bytes_s = ((len(pairs) * ref.weight_bytes(cfg)
                + geo["kv_bytes_per_token"] * sum(a["kv_tokens"] for a, _, _ in pairs))
               / peaks["hbm_bytes_per_s"])
    device_s = sum(d for _, _, d in pairs) * 1e-9
    return 100.0 * max(compute_s, bytes_s) / device_s


def kernel_roofline(ctx, kernel: str, work) -> float | None:
    """A Pallas attention kernel's roofline share: the least time for the
    attention work of the matched dispatches (``work(args)`` -> ``(kv
    tokens read, query x key pairs)``; the queries' and outputs' own bytes
    are left out, under 1% of the decode kernel's and not the prefill
    kernel's bound) over the kernel's device time inside their executions."""
    tl = timeline(ctx)
    pairs = matched(tl) if tl else None
    events = tl["kernels"].get(kernel) if tl else None
    if not pairs or not events:
        return None
    geo, peaks = attention_geometry(ctx), ctx["peaks"]
    kv = pairs_n = 0
    for args, _, _ in pairs:
        k, p = work(args)
        kv, pairs_n = kv + k, pairs_n + p
    least_s = max(geo["flops_per_pair"] * pairs_n / peaks["bf16_flops_per_s"],
                  geo["kv_bytes_per_token"] * kv / peaks["hbm_bytes_per_s"])
    spans_ = sorted((s, s + d) for _, s, d in pairs)
    starts = [s for s, _ in spans_]
    kernel_s = 0.0
    for s, d in events:
        i = bisect.bisect_right(starts, s) - 1
        if i >= 0 and s < spans_[i][1]:
            kernel_s += d * 1e-9
    return 100.0 * least_s / kernel_s if kernel_s else None


def kernel_share(ctx, *kernels: str) -> float | None:
    """Device time of the named kernels together over busy time, per cent
    (``reduce.kernel_share`` for one); None where the trace names none of
    them (a program without kernel names)."""
    trace = ctx["window"]["trace"]
    if not trace:
        return None
    seconds = sum(trace["kernel_s"].get(k, 0.0) for k in kernels)
    return 100.0 * seconds / trace["busy_s"] if seconds else None


# -------------------------------------------------------------- training
def step_gaps(tl: dict | None) -> list:
    """``[(start_ns, end_ns)]`` between successive executions of the training
    step on the first chip; none where the ``train/dispatch`` spans do not
    match the executions."""
    if not tl or not well_matched(tl):
        return []
    steps = sorted((s, s + d) for n, s, d in tl["modules"]
                   if program_of(n)[0] == "train_batch_fn")
    return [(a_end, b_start) for (_, a_end), (b_start, _) in zip(steps, steps[1:])
            if b_start > a_end]


def _main(argv) -> int:
    """``python host_spans.py <trace dir, .xplane.pb or saved .json.gz> [cut.json.gz
    [seconds [skip seconds]]]``: what the host plane of a trace holds, and
    how the matcher fares on it; optionally save ``seconds`` of it (after
    ``skip``) as a fixture."""
    import gzip
    import json

    base = os.path.dirname(os.path.abspath(__file__))
    patterns = {k: v["trace_pattern"]
                for k, v in cellspec.kernels({"base": base}).items()}
    if argv[1].endswith(".json.gz"):  # a timeline saved earlier
        with gzip.open(argv[1], "rt") as f:
            tl = json.load(f)
    else:
        path = argv[1] if argv[1].endswith(".pb") else trace_reduce.newest_xplane(argv[1])
        tl = load_xplane(path, patterns)
    for line in tl["host"]:
        by: dict = {}
        for n, _, d, _ in line["events"]:
            c = by.setdefault(n, [0, 0.0])
            c[0] += 1
            c[1] += d * 1e-9
        print("thread", line["thread"], {k: (c, round(s, 6)) for k, (c, s) in by.items()})
    names: dict = {}
    for n, _, d in tl["modules"]:
        c = names.setdefault(program_of(n)[0], [0, 0.0])
        c[0] += 1
        c[1] += d * 1e-9
    print("modules", {k: (c, round(s, 6)) for k, (c, s) in names.items()})
    print("kernels", {k: (len(v), round(sum(d for _, d in v) * 1e-9, 6))
                      for k, v in tl["kernels"].items()})
    if tl["host"] and tl["modules"]:
        m = match(tl)
        print("matched", len(m["pairs"]), "of", m["dispatches"],
              "dispatch spans,", m["eligible"], "of them not cut off by the slice's end")
        host0 = min(s for line in tl["host"] for _, s, _, _ in line["events"])
        print("first host span at", host0, "first module at", tl["modules"][0][1])
    tl.pop("match", None)
    if len(argv) > 2:
        seconds = float(argv[3]) if len(argv) > 3 else 0.25
        skip = float(argv[4]) if len(argv) > 4 else 0.0
        t0 = min([s for _, s, _ in tl["modules"]]
                 + [s for line in tl["host"] for _, s, _, _ in line["events"]]) + skip * 1e9
        t1 = t0 + seconds * 1e9

        def cut(rows, start=1, dur=2):
            out = []
            for r in rows:
                if r[start] >= t0 and r[start] + r[dur] <= t1:
                    r = list(r)
                    r[start] -= t0
                    out.append(r)
            return out

        tl["modules"] = cut(tl["modules"])
        for line in tl["host"]:
            line["events"] = cut(line["events"])
        tl["host"] = [line for line in tl["host"] if line["events"]]
        tl["kernels"] = {k: cut(v, 0, 1) for k, v in tl["kernels"].items()}
        # a fixture keeps the busy spans with gaps under a microsecond closed
        # (a tenth of the file; no reader looks at a gap under GAP_FLOOR_S)
        busy: list = []
        for a, b in tl["busy"]:
            if a < t0 or b > t1:
                continue
            if busy and a - t0 - busy[-1][1] < 1e3:
                busy[-1][1] = b - t0
            else:
                busy.append([a - t0, b - t0])
        tl["busy"] = busy
        with gzip.open(argv[2], "wt") as f:
            json.dump(tl, f)
    return 0


if __name__ == "__main__":
    import sys

    sys.exit(_main(sys.argv))
