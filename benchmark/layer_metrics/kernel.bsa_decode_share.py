"""Device time of the block-sparse decode kernel (``kernels/bsa_decode.json``)
over device busy time in the traced slice."""
import host_spans


def read(ctx):
    return host_spans.kernel_share(ctx, "bsa_decode")
