"""Seconds of the serving start-up phases (``engine/init``, ``engine/warmup``,
``server/build``) before the window, less the program builds inside them:
the pool, the slot leaves, the device state, the loops and the listener."""
import setup_log


def read(ctx):
    return setup_log.phases_rest_s(ctx, setup_log.ENGINE_PHASES)
