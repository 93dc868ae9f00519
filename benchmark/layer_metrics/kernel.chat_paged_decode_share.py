"""Device time of the Pallas paged-decode attention kernel
(``kernels/paged_decode.json``) over device busy time in the traced slice of
a chat cell: since PR 29 every decode row of an unquantized K/V pool runs it,
whatever the table width. A program that gathers instead names no such kernel
and the metric is left out."""
import host_spans


def read(ctx):
    return host_spans.kernel_share(ctx, "paged_decode")
