"""Device time of the lightning indexer's scoring kernel
(``kernels/dsa_index.json``) over device busy time in the traced slice."""
import host_spans


def read(ctx):
    return host_spans.kernel_share(ctx, "dsa_index")
