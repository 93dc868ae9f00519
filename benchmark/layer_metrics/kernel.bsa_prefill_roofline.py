"""Roofline share of the block-sparse tile kernel: the tiles' KEPT pairs
(``sel_pairs`` less the decode rows') at 16,384 FLOP a pair and layer, and the
rows a tile's last query keeps; the kernel multiplies every causal pair under
the selection's bias, so the share says what the unkept pairs cost.
``bsa_spans.prefill_roofline``."""
from bsa_spans import prefill_roofline as read  # noqa: F401
