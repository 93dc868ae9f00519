"""``model.chat_step_roofline_kv`` for a chat cell over a model with Mamba
layers, as ``model.ssm_step_roofline_kv`` has it: the attention layers' K/V
bytes and pair FLOPs from the cell's reference module, the recurrent state's
read and write (``state_bytes`` of the dispatch spans) added to the bytes, the
recurrence's FLOPs to the compute (``ssm_spans.step_roofline_kv``)."""
from ssm_spans import step_roofline_kv as read  # noqa: F401
