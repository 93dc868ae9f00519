"""Of the passes of a block through the model, those that only wrote the
finished block's K and V (``blk_commit_seqs`` over ``blk_seqs``), per cent:
``1 / (T + 1)``. ``blk_spans.commit_share``."""
from blk_spans import commit_share as read  # noqa: F401
