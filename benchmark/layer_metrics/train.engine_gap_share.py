"""The part of the idle time between training-step executions that falls
under a ``train_step`` span (inside ``engine.train_batch``: staging the
batch, the dispatch and, where telemetry or a monitor makes the engine fetch
the step's metrics, its own wait for the device) over all of it; the rest is
the caller's (fetching the loss, making the next batch)."""
import host_spans


def read(ctx):
    tl = host_spans.timeline(ctx)
    gaps = host_spans.step_gaps(tl)
    if not gaps:
        return None
    inside = host_spans.covered_ns(gaps, host_spans.span_intervals(tl, ("train_step",)))
    return 100.0 * inside / sum(b - a for a, b in gaps)
