"""Device time of the block decode kernel (``kernels/blk_decode.json``) over
device busy time in the traced slice."""
import host_spans


def read(ctx):
    return host_spans.kernel_share(ctx, "blk_decode")
