"""Device time of the sparse MLA decode kernel
(``kernels/dsa_attn_decode.json``) over device busy time in the traced slice;
the gather of the kept rows before it is XLA's and is not in it."""
import host_spans


def read(ctx):
    return host_spans.kernel_share(ctx, "dsa_attn_decode")
