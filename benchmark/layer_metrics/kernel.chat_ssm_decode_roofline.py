"""Roofline share of the Mamba-2 decode kernel in a chat cell, as
``kernel.ssm_decode_roofline`` has it: every REAL decode row's state is read
once and written once (``dec_state_bytes`` of the dispatch spans); the padding
rows of the one decode bucket move the scratch slot's state too and are not in
the least time, so under an open loop the share falls with
``sched.state_pad_row_share``. ``ssm_spans.decode_kernel_roofline``."""
from ssm_spans import decode_kernel_roofline as read  # noqa: F401
