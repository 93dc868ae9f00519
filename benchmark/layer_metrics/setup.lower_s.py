"""Seconds the main thread spent lowering (``jaxpr_to_mlir_module_duration``:
the jaxpr to MLIR, the Mosaic kernel bodies serialised into it) before the
window: the same on a warm cache and a cold one."""
import setup_log


def read(ctx):
    return setup_log.main_sum(ctx, "lower_s")
