"""From a profiler trace to device numbers. The yardstick's copy of the parser
and interval-union math of ``telemetry/devprof.py``, reading the profiler's
own ``.xplane.pb`` (``jax.profiler.ProfileData``) instead of a Chrome trace.

Two steps, so the arithmetic can be checked without a chip:

``load_xplane(path)``  -> a plain dict ``{"planes": [{"name", "lines":
    [{"name", "events": [[name, start_ns, dur_ns, category], ...]}]}]}`` with
    only the device planes and the lines read below. ``tests/fixtures/`` keeps
    one such dict, cut from a trace recorded on the chip.
``reduce(trace, window_s, patterns)`` -> busy seconds, collective seconds
    exposed, per-pattern kernel seconds, top operations, idle gaps.

What the TPU's trace looks like (recorded in PR 22, v5 lite): one plane per
chip, ``/device:TPU:<n>``; its line ``XLA Ops`` holds one event per executed
HLO operation of the TensorCore, in sequence; ``XLA Modules`` holds one event
per executed program (``jit_<name>(<fingerprint>)``). An operation's event
name is its whole HLO instruction (``%fusion.163 = bf16[392,4096]{...}
fusion(...)``, kilobytes for a ``while``), and the line nests: a ``while``'s
event spans the events of its body. ``hlo_category`` is empty on this chip.
A Pallas kernel is a ``custom-call`` named after the jaxpr call that holds it
(``%closed_call.23 = bf16[8,32,128] custom-call(...)``): today's names tell a
kernel from XLA's own operations, and not one kernel from another.
"""

from __future__ import annotations

import bisect
import contextlib
import glob
import os
import re

OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")

COLLECTIVE_PREFIXES = (
    "all-reduce", "all-gather", "reduce-scatter", "all-to-all",
    "collective-permute", "collective-broadcast", "send", "recv",
)
# a gap shorter than this between two operations is the device's own
# sequencing, not the host's
GAP_FLOOR_S = 50e-6


@contextlib.contextmanager
def recording(trace_dir: str):
    """Trace what runs inside the ``with`` into ``trace_dir``: device and
    TraceMe events only, no Python call tracing (it slows the host and makes
    a 4 s slice of a busy server hundreds of megabytes)."""
    import jax

    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 1
    jax.profiler.start_trace(trace_dir, profiler_options=opts)
    try:
        yield
    finally:
        jax.profiler.stop_trace()


def reduce_dir(trace_dir: str, kernel_files: dict) -> dict:
    """``reduce`` of the newest trace under ``trace_dir``, with the patterns
    of ``kernels/*.json`` (``cellspec.kernels``)."""
    patterns = {k: v["trace_pattern"] for k, v in kernel_files.items()}
    return reduce(load_xplane(newest_xplane(trace_dir)), None, patterns)


def newest_xplane(trace_dir: str) -> str | None:
    files = glob.glob(os.path.join(trace_dir, "plugins", "profile", "*",
                                   "*.xplane.pb"))
    return max(files, key=os.path.getmtime) if files else None


def load_xplane(path: str) -> dict:
    from jax.profiler import ProfileData

    planes = []
    for plane in ProfileData.from_file(path).planes:
        if not DEVICE_PLANE.match(plane.name):
            continue
        lines = []
        for line in plane.lines:
            if line.name not in (OPS_LINE, MODULES_LINE):
                continue
            # the 4th field is the profiler's hlo_category where a backend
            # fills it; this chip's traces leave it empty, so it is not read
            events = [[ev.name, float(ev.start_ns), float(ev.duration_ns), ""]
                      for ev in line.events]
            lines.append({"name": line.name, "events": events})
        planes.append({"name": plane.name, "lines": lines})
    return {"planes": planes}


def family(name: str) -> str:
    """``%all-gather-start.3 = ...`` -> ``all-gather-start``: the name of the
    instruction without its instance number."""
    name = name.split(" = ", 1)[0].strip().lstrip("%")
    return re.sub(r"\.\d+$", "", name) or "unknown"


def label(name: str) -> str:
    """A short stable name for the breakdown: instruction family, opcode and
    output shape (``fusion fusion bf16[392,4096]``)."""
    lhs, _, rest = name.partition(" = ")
    shape = re.sub(r"\{[^}]*\}", "", rest.split(" ", 1)[0])
    if shape.startswith("("):  # a tuple: its first member, marked
        shape = shape[1:].rstrip(",") + ",.."
    op = re.search(r"\b([a-z][a-z\-]*)\(", rest)
    fam, opcode = family(lhs), op.group(1) if op else ""
    return " ".join(x for x in (fam, "" if opcode == fam else opcode,
                                shape[:40]) if x)


def self_seconds(events) -> list:
    """``[(name, self seconds)]``: each event's duration less the events
    nested inside it (a ``while`` is charged only what its body is not)."""
    out, stack = [], []  # stack of [end, index into out]
    for n, s, d, _ in sorted(events, key=lambda e: (e[1], -e[2])):
        while stack and stack[-1][0] <= s:
            stack.pop()
        if stack:
            out[stack[-1][1]][1] -= d * 1e-9
        out.append([n, d * 1e-9])
        stack.append([s + d, len(out) - 1])
    return [(n, max(0.0, sec)) for n, sec in out]


def is_collective(name: str) -> bool:
    """By the instruction's name: the TPU compiler also writes the sharded
    gradient sum as ``all-reduce-scatter`` fusions."""
    return family(name).lower().startswith(COLLECTIVE_PREFIXES)


CONTAINERS = ("while", "conditional", "call")


def is_container(name: str) -> bool:
    """A ``while``, ``conditional`` or ``call``: its event spans the events
    of its body, so it is neither busy time nor compute of its own."""
    return family(name).startswith(CONTAINERS)


def union(intervals) -> list:
    """Merge overlapping or touching intervals into sorted disjoint spans."""
    out = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            if b > out[-1][1]:
                out[-1][1] = b
        else:
            out.append([a, b])
    return [(a, b) for a, b in out]


def union_len(intervals) -> float:
    return sum(b - a for a, b in union(intervals))


def intersect_len(interval, spans, ends=None) -> float:
    """Length of ``interval`` covered by the disjoint sorted ``spans``
    (``ends``: their end points, where the caller has them already)."""
    a, b = interval
    ends = ends if ends is not None else [e for _, e in spans]
    total = 0.0
    for u0, u1 in spans[bisect.bisect_right(ends, a):]:
        if u0 >= b:
            break
        total += min(b, u1) - max(a, u0)
    return total


def _line(plane: dict, name: str) -> list:
    for line in plane["lines"]:
        if line["name"] == name:
            return line["events"]
    return []


def span_s(trace: dict) -> float:
    """First event's start to last event's end over the device planes."""
    edges = [(s, s + d) for plane in trace["planes"] for line in plane["lines"]
             for _, s, d, _ in line["events"]]
    return (max(b for _, b in edges) - min(a for a, _ in edges)) * 1e-9


def reduce(trace: dict, window_s: float | None = None,
           patterns: dict | None = None) -> dict:
    """Device numbers of one traced slice. Its length ``window_s`` defaults
    to the trace's own span: the host's clock around ``start_trace`` and
    ``stop_trace`` adds the profiler's start-up and collection, ~0.14 s of a
    2.4 s slice (my chip run, PR 22), all of which would read as idle.

    ``patterns`` maps a kernel's name to a regular expression over operation
    events' whole names (``kernels/<name>.json``'s ``trace_pattern``); a
    kernel never nests, so its seconds are plain sums. Seconds are averaged
    over the device planes (the chips used); ``top_ops`` and ``idle_gaps``
    are the first plane's."""
    planes = trace["planes"]
    if not planes:
        raise ValueError("trace holds no device plane")
    if window_s is None:
        window_s = span_s(trace)
    compiled = {k: re.compile(v) for k, v in (patterns or {}).items()}
    busy, exposed, collective = [], [], []
    kernel_s = {k: [] for k in compiled}
    for plane in planes:
        ops = [(n, s * 1e-9, (s + d) * 1e-9)
               for n, s, d, _ in _line(plane, OPS_LINE)
               if d > 0 and not is_container(n)]
        all_iv = [(a, b) for _, a, b in ops]
        coll_iv = [(a, b) for n, a, b in ops if is_collective(n)]
        compute = union([(a, b) for n, a, b in ops if not is_collective(n)])
        busy.append(union_len(all_iv))
        collective.append(sum(b - a for a, b in coll_iv))
        compute_ends = [e for _, e in compute]
        exposed.append(sum((b - a) - intersect_len((a, b), compute, compute_ends)
                           for a, b in union(coll_iv)))
        for k, rx in compiled.items():
            kernel_s[k].append(sum(b - a for n, a, b in ops if rx.search(n)))

    first = planes[0]
    by_label: dict = {}
    for n, sec in self_seconds(_line(first, OPS_LINE)):
        key = label(n)
        by_label[key] = by_label.get(key, 0.0) + sec
    top_ops = sorted(by_label.items(), key=lambda kv: -kv[1])[:10]

    def mean(xs):
        return sum(xs) / len(xs)

    return {
        "devices": len(planes),
        "window_s": window_s,
        "busy_s": mean(busy),
        "collective_s": mean(collective),
        "collective_exposed_s": mean(exposed),
        "kernel_s": {k: mean(v) for k, v in kernel_s.items()},
        "top_ops": [[k, v] for k, v in top_ops],
        "idle_gaps": idle_gaps(first),
    }


def idle_gaps(plane: dict) -> list:
    """The idle time of one chip, by the programs on either side of each gap.

    Without host spans on the profiler's clock (a later ``tracing`` PR) a gap
    can only be named by what the device ran before and after it:
    ``inside <program>`` when both operations belong to one execution of a
    program, else ``<program> -> <program>`` (the host was between two
    dispatches). Returns the ten classes with most idle seconds."""
    ops = sorted((s * 1e-9, (s + d) * 1e-9)
                 for n, s, d, _ in _line(plane, OPS_LINE)
                 if d > 0 and not is_container(n))
    modules = sorted((s * 1e-9, (s + d) * 1e-9, re.sub(r"\(.*\)$", "", n))
                     for n, s, d, _ in _line(plane, MODULES_LINE))

    starts = [a for a, _, _ in modules]

    def module_at(t: float) -> str:
        i = bisect.bisect_right(starts, t) - 1
        return modules[i][2] if i >= 0 and t <= modules[i][1] else "?"

    classes: dict = {}
    busy = union(ops)
    for (_, end), (start, _) in zip(busy, busy[1:]):
        if start - end < GAP_FLOOR_S:
            continue
        # a nanosecond inside the operations on either side: an operation
        # may end on its program's last tick
        before, after = module_at(end - 1e-9), module_at(start + 1e-9)
        i = bisect.bisect_right(starts, end - 1e-9) - 1
        inside = i >= 0 and start + 1e-9 <= modules[i][1]
        key = f"inside {before}" if inside else f"{before} -> {after}"
        classes[key] = classes.get(key, 0.0) + (start - end)
    return [[k, v] for k, v in
            sorted(classes.items(), key=lambda kv: -kv[1])[:10]]


def _main(argv) -> int:
    """``python trace_reduce.py <trace dir or .xplane.pb> [cut.json.gz
    [seconds]]``: what a trace holds (planes, lines, the operations that took
    most time with their categories), looked at by hand before any pattern
    is written against it; optionally save the first ``seconds`` of every
    kept line as a fixture."""
    import gzip
    import json

    if argv[1].endswith(".json.gz"):  # a fixture
        with gzip.open(argv[1], "rt") as f:
            trace = json.load(f)
    else:
        trace = load_xplane(argv[1] if argv[1].endswith(".pb")
                            else newest_xplane(argv[1]))
    for plane in trace["planes"]:
        print("plane", plane["name"])
        for line in plane["lines"]:
            evs = line["events"]
            print("  line", line["name"], len(evs), "events")
            by: dict = {}
            for n, sec in self_seconds(evs):
                key = label(n)
                by[key] = by.get(key, 0.0) + sec
            for n, sec in sorted(by.items(), key=lambda kv: -kv[1])[:40]:
                print(f"    {sec:10.6f} s  {n}")
    if len(argv) > 2:
        seconds = float(argv[3]) if len(argv) > 3 else 0.25
        for plane in trace["planes"]:
            starts = [e[1] for ln in plane["lines"] for e in ln["events"]]
            t0 = min(starts) if starts else 0.0
            for line in plane["lines"]:
                line["events"] = [[n, s - t0, d, c] for n, s, d, c in line["events"]
                                  if s - t0 + d <= seconds * 1e9]
        with gzip.open(argv[2], "wt") as f:
            json.dump(trace, f)
    return 0


if __name__ == "__main__":
    import sys

    sys.exit(_main(sys.argv))
