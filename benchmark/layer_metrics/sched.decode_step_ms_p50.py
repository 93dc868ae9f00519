"""Device time of a decode-only step, median over the traced slice: the
``XLA Modules`` duration of the executions matched by order
(``host_spans.match``) to ``engine/dispatch`` spans whose program has decode
rows and no prefill tile."""
import host_spans


def read(ctx):
    return host_spans.exec_ms_p50(ctx, lambda nd, nt: nd > 0 and nt == 0)
