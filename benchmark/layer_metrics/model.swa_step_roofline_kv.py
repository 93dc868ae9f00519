"""``model.step_roofline_kv`` for a model with window layers beside full ones:
the full layers' K/V bytes x ``kv_tokens`` and pair FLOPs x ``attn_pairs``, the
window layers' x ``win_kv_tokens`` and ``win_attn_pairs``, the weights once a
dispatch (``swa_spans.step_roofline_kv``)."""
from swa_spans import step_roofline_kv as read  # noqa: F401
