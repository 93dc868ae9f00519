"""Roofline share of the Mamba-2 decode kernel: every decode row's state is
read once and written once (``dec_state_bytes`` of the dispatch spans: rows x
the reference's ``state_bytes_per_slot`` x 2); bytes-bound.
``ssm_spans.decode_kernel_roofline``."""
from ssm_spans import decode_kernel_roofline as read  # noqa: F401
