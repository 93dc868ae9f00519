"""``model.step_roofline_kv`` for a model with Mamba layers: the attention
layers' K/V bytes and pair FLOPs from the cell's reference module, and the
recurrent state's read and write (``state_bytes`` of the dispatch spans) added
to the bytes, the recurrence's FLOPs to the compute
(``ssm_spans.step_roofline_kv``)."""
from ssm_spans import step_roofline_kv as read  # noqa: F401
