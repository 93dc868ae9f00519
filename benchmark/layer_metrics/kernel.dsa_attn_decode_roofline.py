"""Roofline share of the sparse MLA decode kernel: every decode row's kept
rows (``dec_sel_kv_tokens``: ``min(context, index_topk)`` a row) read once
(1,152 B a row and layer) and multiplied by one query's 128 heads (278,528 FLOP
a row and layer). ``dsa_spans.decode_roofline``."""
from dsa_spans import decode_roofline as read  # noqa: F401
