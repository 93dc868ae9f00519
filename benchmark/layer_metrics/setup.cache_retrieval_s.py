"""Seconds the main thread spent fetching executables from the persistent
compilation cache before the window (``cache_retrieval_time_sec``)."""
import setup_log


def read(ctx):
    return setup_log.main_sum(ctx, "retrieval_s")
