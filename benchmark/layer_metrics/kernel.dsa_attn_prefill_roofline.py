"""Roofline share of the sparse MLA prefill kernel: the tiles' KEPT pairs
(``sel_pairs`` less the decode rows') at the absorbed form's 278,528 FLOP a
pair and layer, and ``min(context, index_topk)`` latent rows a tile; the kernel
multiplies every causal pair under a bias, so the share says what the unkept
pairs cost. ``dsa_spans.prefill_roofline``."""
from dsa_spans import prefill_roofline as read  # noqa: F401
