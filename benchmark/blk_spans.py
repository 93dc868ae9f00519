"""Readers for a model that generates by diffusion over blocks
(``deepspeed_tpu/models/sdar.py``; ``ModelSpec.block_gen``): its two
attention kernels against the least bytes and pairs, the step against weights
+ K and V once a sequence and PASS, and what the schedule costs in passes.

The program says what its blocks did in the ``engine/dispatch`` span
(``deepspeed_tpu/inference/ragged.py`` ``_block_attr``): ``blk_seqs`` (the
sequences that ran a block of ``blk_len`` rows through the model this step),
``blk_commit_seqs`` (those of them in their commit pass), ``blk_unmasked`` (the
positions the step unmasked), ``blk_len``, ``blk_steps``. The span's other
arguments keep their meaning: ``dec_kv_tokens`` is the context the blocks read,
ONCE a sequence and pass (``p0 + blk_len``), ``kv_tokens`` that plus the tiles'
contexts, ``attn_pairs`` the query x key pairs (``blk_len`` x its context a
block; a tile's under the block-causal mask). The cell's reference module
counts a row and a pair (``kv_bytes_per_token``, ``attn_flops_per_pair``: head
128, which ``host_spans.attention_geometry`` would read as 64). A program that
wrote no such argument (every other family, a parent commit) or no span gives
None.
"""

from __future__ import annotations

import latent_spans

SPAN_KEYS = ("blk_seqs", "blk_commit_seqs", "blk_unmasked", "blk_len")


def block_pairs(ctx):
    """The matched dispatches, if they carry the blocks' arguments."""
    _, pairs = latent_spans._matched(ctx)
    if not pairs or not all(k in a for a, _, _ in pairs for k in SPAN_KEYS):
        return None
    return pairs


def decode_work(a) -> tuple:
    """``(context rows read, query x key pairs)`` of a dispatch's blocks."""
    return a["dec_kv_tokens"], a["blk_len"] * a["dec_kv_tokens"]


def prefill_work(a) -> tuple:
    """The same of its prefill tiles."""
    rows, pairs = decode_work(a)
    return a["kv_tokens"] - rows, a["attn_pairs"] - pairs


def kernel_roofline(ctx, kernel: str, work) -> float | None:
    if block_pairs(ctx) is None:
        return None
    return latent_spans.kernel_roofline(ctx, kernel, work)


def step_roofline_kv(ctx) -> float | None:
    """``latent_spans.step_roofline_kv`` (weights once a dispatch + K and V
    once a sequence and pass + the rows' and the pairs' FLOPs, over the
    matched executions' device time) where the dispatches ran blocks."""
    if block_pairs(ctx) is None:
        return None
    return latent_spans.step_roofline_kv(ctx)


def _ratio(ctx, over: str, under: str) -> float | None:
    pairs = block_pairs(ctx)
    bottom = sum(a[under] for a, _, _ in pairs or ())
    if not bottom:
        return None
    return sum(a[over] for a, _, _ in pairs) / bottom


def passes_per_token(ctx) -> float | None:
    """Passes of a block through the model over the positions unmasked
    (``(T + 1) / B`` with every block committed: 0.75 at ``T = 2``, ``B =
    4``; what folding the commit into the next block's first pass would
    lower). A property of the schedule, not of speed."""
    return _ratio(ctx, "blk_seqs", "blk_unmasked")


def commit_share(ctx) -> float | None:
    """Of the block passes, those that only wrote a finished block's K and
    V, per cent (``1 / (T + 1)``)."""
    share = _ratio(ctx, "blk_commit_seqs", "blk_seqs")
    return None if share is None else 100.0 * share
