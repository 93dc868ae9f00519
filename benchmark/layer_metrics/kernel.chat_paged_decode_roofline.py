"""Roofline share of the paged-decode kernel in a chat cell, as
``kernel.paged_decode_roofline`` has it: every decode row reads its context's
K and V once (``dec_kv_tokens``) and spends one query's QK^T and PV on it;
bytes-bound at 4 FLOPs a byte. ``host_spans.kernel_roofline``."""
import host_spans


def read(ctx):
    return host_spans.kernel_roofline(
        ctx, "paged_decode",
        lambda a: (a["dec_kv_tokens"], a["dec_kv_tokens"]))
