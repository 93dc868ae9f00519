"""``setup_s`` less the main thread's builds and the marked phases: imports
and the backend's start, the weights' execution, the steps the warm-up and the
replay execute, the lead-in's sleep; in the training cell the steps before
the window. What the measurement cannot see yet, never negative."""
import setup_log


def read(ctx):
    return setup_log.unattributed_s(ctx)
