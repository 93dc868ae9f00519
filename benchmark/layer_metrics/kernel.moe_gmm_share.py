"""Device time of the grouped expert-FFN kernel (``kernels/moe_gmm.json``)
over device busy time in the traced slice."""
import host_spans


def read(ctx):
    return host_spans.kernel_share(ctx, "moe_gmm")
