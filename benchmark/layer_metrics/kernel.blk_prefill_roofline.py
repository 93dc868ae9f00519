"""Roofline share of the block-causal tile kernel: a prompt chunk spends its
query x key pairs under the block-causal mask (``attn_pairs`` less the
blocks') at the reference's ``attn_flops_per_pair`` and reads its context once
(``kv_tokens`` less ``dec_kv_tokens``); FLOP-bound.
``blk_spans.kernel_roofline``."""
import blk_spans


def read(ctx):
    return blk_spans.kernel_roofline(ctx, "blk_prefill",
                                     blk_spans.prefill_work)
