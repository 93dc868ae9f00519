"""Idle time of the chip (gaps between its operations of
``trace_reduce.GAP_FLOOR_S`` and longer) that falls under no host span, over
the slice: what the instrument cannot give to anything the host was doing."""
import host_spans


def read(ctx):
    tl = host_spans.timeline(ctx)
    if not tl:
        return None
    idle = host_spans.idle_intervals(tl)
    named = host_spans.covered_ns(idle, host_spans.span_intervals(tl))
    return 100.0 * (sum(b - a for a, b in idle) - named) * 1e-9 / host_spans.slice_s(tl)
