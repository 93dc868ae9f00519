"""Device time of the Pallas tiled-prefill attention kernel
(``kernels/tiled_prefill.json``) over device busy time in the traced slice."""
import host_spans


def read(ctx):
    return host_spans.kernel_share(ctx, "tiled_prefill")
