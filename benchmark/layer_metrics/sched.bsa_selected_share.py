"""Query x kept-key pairs over query x context-key pairs of the traced slice's
matched dispatches (``sel_pairs`` over ``attn_pairs`` of ``engine/dispatch``):
what a selection of 64 blocks of 64 keys past 8,192 leaves of a dense model's
attention. ``bsa_spans.selected_share``."""
from bsa_spans import selected_share as read  # noqa: F401
