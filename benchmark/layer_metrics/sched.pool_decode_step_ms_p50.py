"""Device time of a decode-only step in a worker-pool (closed-loop) cell,
median over the traced slice: ``sched.decode_step_ms_p50``'s quantity where
the end-to-end metric is ``serve_tokens_per_s``."""
import host_spans


def read(ctx):
    return host_spans.exec_ms_p50(ctx, lambda nd, nt: nd > 0 and nt == 0)
