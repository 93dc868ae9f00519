"""Device time of the block-causal tile kernel (``kernels/blk_prefill.json``)
over device busy time in the traced slice."""
import host_spans


def read(ctx):
    return host_spans.kernel_share(ctx, "blk_prefill")
