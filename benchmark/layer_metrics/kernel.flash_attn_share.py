"""Device time of the Pallas flash-attention kernels of the training step
(forward, recomputed forward and the two backward kernels together:
``kernels/pallas_custom_call.json``) over device busy time in the traced
slice."""
import reduce


def read(ctx):
    return reduce.kernel_share(ctx, "pallas_custom_call")
