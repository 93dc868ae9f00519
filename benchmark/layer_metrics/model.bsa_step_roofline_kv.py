"""``model.step_roofline_kv`` for a model that selects its keys by blocks
beside layers with a recurrent state: the kept pairs' attention FLOPs and the
kept rows' bytes, the compressed keys' bytes, the state's read and write and
the recurrence's FLOPs (``bsa_spans.step_roofline_kv``)."""
from bsa_spans import step_roofline_kv as read  # noqa: F401
