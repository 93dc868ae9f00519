"""Roofline share of the Mamba-1 decode kernel: every decode row's state is
read once and written once (``dec_state_bytes`` of the dispatch spans: rows x
the reference's ``state_bytes_per_slot`` x 2); bytes-bound (the decay is
computed in the kernel, so nothing else of the state's size moves).
``ssm_spans.decode_kernel_roofline`` on the kernel's own name."""
import ssm_spans


def read(ctx):
    return ssm_spans.decode_kernel_roofline(ctx, kernel="selscan_decode")
