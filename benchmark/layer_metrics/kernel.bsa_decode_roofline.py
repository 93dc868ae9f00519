"""Roofline share of the block-sparse decode kernel: every decode row's kept
keys (``dec_sel_kv_tokens``: every key under the dense length, at most 64
blocks of 64 past it) read once (1,024 B a key and layer) and multiplied by
one query's 32 heads (16,384 FLOP a key and layer).
``bsa_spans.decode_roofline``."""
from bsa_spans import decode_roofline as read  # noqa: F401
