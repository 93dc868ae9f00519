"""Seconds the main thread waited in ``backend_compile_duration`` before the
window, less the persistent cache's retrieval: the compiler on a miss, the
cache key and loading the executable on a hit."""
import setup_log


def read(ctx):
    return setup_log.main_sum(ctx, "compile_s", less="retrieval_s")
