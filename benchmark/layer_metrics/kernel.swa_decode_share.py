"""Device time of the window layers' decode kernel (``kernels/swa_decode.json``)
over device busy time in the traced slice."""
import host_spans


def read(ctx):
    return host_spans.kernel_share(ctx, "swa_decode")
