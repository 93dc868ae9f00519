"""Device time of the absorbed MLA prefill kernel
(``kernels/mla_prefill.json``) over device busy time in the traced slice."""
import host_spans


def read(ctx):
    return host_spans.kernel_share(ctx, "mla_prefill")
