"""``model.step_roofline_kv`` for a model that generates by blocks: the
weights once a dispatch, K and V once a sequence and pass (``kv_tokens`` x
the reference's ``kv_bytes_per_token``), the rows' and the pairs' FLOPs, over
the matched executions' device time (``blk_spans.step_roofline_kv``)."""
from blk_spans import step_roofline_kv as read  # noqa: F401
