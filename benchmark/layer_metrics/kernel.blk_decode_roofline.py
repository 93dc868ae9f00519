"""Roofline share of the block decode kernel: a decoding block reads its
context's K and V ONCE a pass for its ``blk_len`` queries (``dec_kv_tokens``
of the dispatch spans, ``p0 + blk_len`` a sequence, x the reference's
``kv_bytes_per_token``, 14,336 B) and spends ``blk_len`` queries' pairs on it;
bytes-bound. ``blk_spans.kernel_roofline``."""
import blk_spans


def read(ctx):
    return blk_spans.kernel_roofline(ctx, "blk_decode", blk_spans.decode_work)
