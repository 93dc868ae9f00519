"""Query x kept-row pairs over query x context-row pairs of the traced slice's
matched dispatches (``sel_pairs`` over ``attn_pairs`` of ``engine/dispatch``):
what a learned selection of ``index_topk`` rows a query leaves of a dense
model's attention. ``dsa_spans.selected_share``."""
from dsa_spans import selected_share as read  # noqa: F401
