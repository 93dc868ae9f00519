"""Device time of the KDA decode kernel (``kernels/kda_decode.json``) over
device busy time in the traced slice."""
import host_spans


def read(ctx):
    return host_spans.kernel_share(ctx, "kda_decode")
