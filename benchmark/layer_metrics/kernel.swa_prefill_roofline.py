"""Roofline share of the window layers' tiled-prefill kernel: every prompt
chunk reads the rows inside its sequence's window once (``win_kv_tokens`` less
the decode rows') and spends its query x key pairs inside the window
(``win_attn_pairs`` less the decode rows') at the reference's
``window_attn_flops_per_pair``; FLOP-bound. ``swa_spans.kernel_roofline``."""
import swa_spans


def read(ctx):
    return swa_spans.kernel_roofline(
        ctx, "swa_prefill",
        lambda a: (a["win_kv_tokens"] - a["dec_win_kv_tokens"],
                   a["win_attn_pairs"] - a["dec_win_kv_tokens"]))
