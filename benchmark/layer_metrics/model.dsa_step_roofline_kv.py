"""``model.step_roofline_kv`` for a model that selects the rows it attends
over: the kept pairs' attention FLOPs and the kept rows' bytes, the indexer's
FLOPs over every causal pair and its keys' bytes over the whole context
(``dsa_spans.step_roofline_kv``)."""
from dsa_spans import step_roofline_kv as read  # noqa: F401
