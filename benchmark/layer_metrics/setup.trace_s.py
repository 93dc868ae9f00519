"""Seconds the main thread spent tracing (jax's ``jaxpr_trace_duration`` of
its outermost builds, the kernels' wrappers and every nested jit inside them)
before the window: Python, the same on a warm cache and a cold one."""
import setup_log


def read(ctx):
    return setup_log.main_sum(ctx, "trace_s")
