"""Gap between successive token frames, 99th percentile, pooled over the
window's attempted requests: a step of the next larger decode-row program, or
a prefill chunk landing between two decode steps. Not judged: under 1% of the
gaps come from the larger program, so the percentile sits on the edge between
two clusters and spread 7% in the driver's runs of PR 22."""
import reduce


def read(ctx):
    win = ctx["window"]
    tried = reduce.attempted(win["records"], win["seconds"], True)
    return reduce.latency_metric("itl_p99_ms", tried, 0.0)
