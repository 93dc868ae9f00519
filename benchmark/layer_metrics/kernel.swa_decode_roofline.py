"""Roofline share of the window layers' decode kernel: every decode row reads
the K and V of the rows inside its window once (``dec_win_kv_tokens`` of the
dispatch spans, ``min(context, window)`` a row, x the reference's
``window_kv_bytes_per_token``) and spends one query's pairs on them;
bytes-bound. ``swa_spans.kernel_roofline``."""
import swa_spans


def read(ctx):
    return swa_spans.kernel_roofline(
        ctx, "swa_decode",
        lambda a: (a["dec_win_kv_tokens"], a["dec_win_kv_tokens"]))
