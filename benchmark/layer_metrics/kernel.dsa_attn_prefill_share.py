"""Device time of the sparse MLA prefill kernel
(``kernels/dsa_attn_prefill.json``) over device busy time in the traced slice."""
import host_spans


def read(ctx):
    return host_spans.kernel_share(ctx, "dsa_attn_prefill")
