"""Dispatches in the window that had to build their program
(``program_cold_dispatches``), plus backend compiles jax reported in the
window (``telemetry/compile_watch.py``). Must read 0: anything else is set-up
that leaked into the window, and every number of the run is suspect."""


def read(ctx):
    c = ctx["window"]["counters"]
    return c.get("program_cold_dispatches", 0) + c.get("compiles", 0)
