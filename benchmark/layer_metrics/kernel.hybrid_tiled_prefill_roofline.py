"""Roofline share of the tiled-prefill kernel in a model where only some
layers are attention layers: every prompt chunk reads its sequence's K and V
once (``kv_tokens`` less the decode rows') and spends its causal query x key
pairs (``attn_pairs`` less the decode rows') at the reference's
``attn_flops_per_pair`` of the attention layers alone; FLOP-bound.
``latent_spans.kernel_roofline``."""
import latent_spans


def read(ctx):
    return latent_spans.kernel_roofline(
        ctx, "tiled_prefill",
        lambda a: (a["kv_tokens"] - a["dec_kv_tokens"],
                   a["attn_pairs"] - a["dec_kv_tokens"]))
