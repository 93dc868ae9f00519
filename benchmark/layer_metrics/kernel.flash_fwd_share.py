"""Device time of the Pallas flash-attention forward kernel (forward and its
recomputation; ``kernels/flash_fwd.json``) over device busy time in the
traced slice."""
import host_spans


def read(ctx):
    return host_spans.kernel_share(ctx, "flash_fwd")
