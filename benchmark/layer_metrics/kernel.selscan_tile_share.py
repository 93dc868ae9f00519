"""Device time of the Mamba-1 tile kernel (``kernels/selscan_tile.json``: the
selective scan over a step's prefill tiles) over device busy time in the
traced slice."""
import host_spans


def read(ctx):
    return host_spans.kernel_share(ctx, "selscan_tile")
