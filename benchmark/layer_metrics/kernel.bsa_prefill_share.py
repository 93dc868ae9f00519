"""Device time of the block-sparse tile kernel (``kernels/bsa_prefill.json``)
over device busy time in the traced slice."""
import host_spans


def read(ctx):
    return host_spans.kernel_share(ctx, "bsa_prefill")
