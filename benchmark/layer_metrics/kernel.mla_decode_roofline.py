"""Roofline share of the MLA decode kernel: every decode row reads its
context's latent rows once (``dec_kv_tokens`` x the reference's
``kv_bytes_per_token``: 1,152 B a token and layer, key and value in one) and
spends one query's absorbed scores and values on it; bytes-bound at 30 FLOPs a
byte. ``latent_spans.kernel_roofline``."""
import latent_spans


def read(ctx):
    return latent_spans.kernel_roofline(
        ctx, "mla_decode",
        lambda a: (a["dec_kv_tokens"], a["dec_kv_tokens"]))
