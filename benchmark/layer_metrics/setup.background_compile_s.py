"""Seconds the engine's ``ragged-compile`` threads spent in the backend
compile before the window: 0 on a warm cache. Another thread's time: it is in
none of the sums that split ``setup_s``."""
import setup_log


def read(ctx):
    return setup_log.background_compile_s(ctx)
