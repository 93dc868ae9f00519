"""Roofline share of the paged-decode kernel in a model where only some layers
are attention layers: every decode row reads its context's K and V once
(``dec_kv_tokens`` x the reference's ``kv_bytes_per_token``, which counts the
attention layers alone: 1,024 B a token at one layer of 2 KV heads x 128) and
spends one query's pairs on it; bytes-bound. ``latent_spans.kernel_roofline``
(``kernel.paged_decode_roofline``'s reader multiplies by every layer)."""
import latent_spans


def read(ctx):
    return latent_spans.kernel_roofline(
        ctx, "paged_decode",
        lambda a: (a["dec_kv_tokens"], a["dec_kv_tokens"]))
