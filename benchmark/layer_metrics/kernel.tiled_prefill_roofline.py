"""Roofline share of the tiled-prefill kernel: every prompt chunk reads its
sequence's context once (``kv_tokens`` less the decode rows') and spends its
causal query x key pairs (``attn_pairs`` less the decode rows');
FLOP-bound. ``host_spans.kernel_roofline``."""
import host_spans


def read(ctx):
    return host_spans.kernel_roofline(
        ctx, "tiled_prefill",
        lambda a: (a["kv_tokens"] - a["dec_kv_tokens"],
                   a["attn_pairs"] - a["dec_kv_tokens"]))
