"""Builds of the ragged engine's own programs (``jit_ragged_*``: the step
programs and the row updaters, a build a size) before the window, on any
thread: the background threads of a cold start count beside the foreground."""
import setup_log


def read(ctx):
    return setup_log.program_builds(ctx)
