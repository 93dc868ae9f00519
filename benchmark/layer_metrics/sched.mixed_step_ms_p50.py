"""Device time of a mixed step (prefill tiles beside decode rows), median
over the traced slice: executions matched by order to ``engine/dispatch``
spans whose program has both."""
import host_spans


def read(ctx):
    return host_spans.exec_ms_p50(ctx, lambda nd, nt: nd > 0 and nt > 0)
