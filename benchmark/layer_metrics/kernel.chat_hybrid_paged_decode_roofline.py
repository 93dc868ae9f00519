"""Roofline share of the paged-decode kernel in a chat cell whose model has
attention in only some of its layers, as ``kernel.hybrid_paged_decode_roofline``
has it: every decode row reads its context's K and V once (``dec_kv_tokens`` x
the reference's ``kv_bytes_per_token``, which counts the attention layers
alone: 4,096 B a token at Granite's one layer of 8 KV heads x 128) and spends
one query's pairs on it; bytes-bound. ``latent_spans.kernel_roofline``
(``kernel.chat_paged_decode_roofline``'s reader multiplies by every layer)."""
import latent_spans


def read(ctx):
    return latent_spans.kernel_roofline(
        ctx, "paged_decode",
        lambda a: (a["dec_kv_tokens"], a["dec_kv_tokens"]))
