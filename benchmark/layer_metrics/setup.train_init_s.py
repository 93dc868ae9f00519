"""Seconds of ``train/init`` (the training engine's construction: state
placement and sharding) less the program builds inside it."""
import setup_log


def read(ctx):
    return setup_log.phases_rest_s(ctx, setup_log.TRAIN_PHASES)
