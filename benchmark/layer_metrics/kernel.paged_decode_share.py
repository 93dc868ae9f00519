"""Device time of the Pallas paged-decode attention kernel
(``kernels/paged_decode.json``) over device busy time in the traced slice."""
import host_spans


def read(ctx):
    return host_spans.kernel_share(ctx, "paged_decode")
