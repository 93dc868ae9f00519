"""``model.chat_step_roofline`` with KV-cache reads in the bytes bound and
attention FLOPs in the FLOP bound, over the matched dispatches of the traced
slice: ``host_spans.step_roofline_kv``."""
from host_spans import step_roofline_kv as read  # noqa: F401
