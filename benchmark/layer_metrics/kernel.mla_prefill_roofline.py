"""Roofline share of the MLA prefill kernel: every prompt chunk reads its
sequence's latent rows once (``kv_tokens`` less the decode rows') and spends
its causal query x key pairs (``attn_pairs`` less the decode rows') at the
absorbed form's 34.8 kFLOP a pair and layer (the reference's
``attn_flops_per_pair``); FLOP-bound. ``latent_spans.kernel_roofline``."""
import latent_spans


def read(ctx):
    return latent_spans.kernel_roofline(
        ctx, "mla_prefill",
        lambda a: (a["kv_tokens"] - a["dec_kv_tokens"],
                   a["attn_pairs"] - a["dec_kv_tokens"]))
