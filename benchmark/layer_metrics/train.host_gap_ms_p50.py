"""Idle time of the first chip between two successive executions of the
training step, median over the traced steps."""
import statistics

import host_spans


def read(ctx):
    gaps = host_spans.step_gaps(host_spans.timeline(ctx))
    return statistics.median(b - a for a, b in gaps) * 1e-6 if gaps else None
