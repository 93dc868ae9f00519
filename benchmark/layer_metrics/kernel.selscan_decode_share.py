"""Device time of the Mamba-1 decode kernel (``kernels/selscan_decode.json``)
over device busy time in the traced slice."""
import host_spans


def read(ctx):
    return host_spans.kernel_share(ctx, "selscan_decode")
