"""Device time of the two Pallas flash-attention backward kernels
(``kernels/flash_bwd_dkv.json``, ``kernels/flash_bwd_dq.json``) over device
busy time in the traced slice."""
import host_spans


def read(ctx):
    return host_spans.kernel_share(ctx, "flash_bwd_dkv", "flash_bwd_dq")
