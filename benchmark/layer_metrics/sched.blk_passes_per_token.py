"""Passes of a block through the model for every position unmasked
(``blk_seqs`` over ``blk_unmasked`` of the matched ``engine/dispatch`` spans):
``(T + 1) / B``, 0.75 at two denoise passes and a commit a block of four. A
property of the schedule, not of speed. ``blk_spans.passes_per_token``."""
from blk_spans import passes_per_token as read  # noqa: F401
