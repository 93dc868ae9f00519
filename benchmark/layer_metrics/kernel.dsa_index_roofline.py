"""Roofline share of the indexer's scoring kernel: every causal query x key
pair at the reference's ``index_flops_per_pair`` (16,384 a layer) and every
cached index key of the step's sequences once (``index_bytes_per_token``, 256 B
a layer). ``dsa_spans.index_roofline``."""
from dsa_spans import index_roofline as read  # noqa: F401
