"""Device time of the Mamba-2 decode kernel (``kernels/ssm_decode.json``) over
device busy time in the traced slice of a chat cell, as
``kernel.ssm_decode_share`` has it for a closed pool."""
import host_spans


def read(ctx):
    return host_spans.kernel_share(ctx, "ssm_decode")
