"""Readers for a model whose layers carry a recurrent state a slot beside the
paged K/V pool (Mamba-2 layers among attention layers): the step's roofline
with the state's traffic in it, and the state-update kernel's own.

The program says what of the state a step moves in the ``engine/dispatch``
span (``deepspeed_tpu/inference/ragged.py`` ``_state_attr``): ``state_bytes``,
the slot-state bytes the step must read and write (decode rows + distinct
prefilling slots, x the state a slot holds, x 2), ``dec_state_bytes`` the
decode rows' part, ``ssm_prefill_tokens``. The cell's reference module counts
the rest: ``kv_bytes_per_token`` and ``attn_flops_per_pair`` of the attention
layers alone (``latent_spans.geometry``), ``ssm_flops_per_token`` of the
recurrence. A program that wrote no such argument (every other family, a
parent commit), or no span, gives None.
"""

from __future__ import annotations

import bisect

import latent_spans


def _state_pairs(ctx):
    """The matched dispatches, if they carry the state's arguments."""
    _, pairs = latent_spans._matched(ctx)
    if not pairs or not all("state_bytes" in a for a, _, _ in pairs):
        return None
    return pairs


def step_roofline_kv(ctx) -> float | None:
    """``latent_spans.step_roofline_kv`` with the state in it: max((2 x
    active parameters x tokens + pair FLOPs x pairs + recurrence FLOPs x
    tokens) / peak FLOP/s, (dispatches x weight bytes + K/V bytes x
    kv_tokens + state_bytes) / peak bytes/s) over the device time of the
    matched executions, per cent."""
    geo, pairs = latent_spans.geometry(ctx), _state_pairs(ctx)
    ref = ctx["reference"]
    if not geo or not pairs or not hasattr(ref, "ssm_flops_per_token"):
        return None
    cfg, peaks = ctx["cfg"], ctx["peaks"]
    tokens = sum(a["tokens"] for a, _, _ in pairs)
    compute_s = (((2.0 * ref.active_params(cfg) + ref.ssm_flops_per_token(cfg)) * tokens
                  + geo["flops_per_pair"] * sum(a["attn_pairs"] for a, _, _ in pairs))
                 / peaks["bf16_flops_per_s"])
    bytes_s = ((len(pairs) * ref.weight_bytes(cfg)
                + geo["kv_bytes_per_token"] * sum(a["kv_tokens"] for a, _, _ in pairs)
                + sum(a["state_bytes"] for a, _, _ in pairs))
               / peaks["hbm_bytes_per_s"])
    device_s = sum(d for _, _, d in pairs) * 1e-9
    return 100.0 * max(compute_s, bytes_s) / device_s


def decode_kernel_roofline(ctx, kernel: str = "ssm_decode") -> float | None:
    """The least time to move the decode rows' states once each way
    (``dec_state_bytes`` of the matched dispatches over the peak bytes/s; the
    kernel is bytes-bound at 1.25 FLOP a byte) over the kernel's device time
    inside their executions, per cent."""
    pairs = _state_pairs(ctx)
    tl, _ = latent_spans._matched(ctx)
    events = tl["kernels"].get(kernel) if tl else None
    if not pairs or not events:
        return None
    least_s = (sum(a["dec_state_bytes"] for a, _, _ in pairs)
               / ctx["peaks"]["hbm_bytes_per_s"])
    spans = sorted((s, s + d) for _, s, d in pairs)
    starts = [s for s, _ in spans]
    kernel_s = 0.0
    for s, d in events:
        i = bisect.bisect_right(starts, s) - 1
        if i >= 0 and s < spans[i][1]:
            kernel_s += d * 1e-9
    return 100.0 * least_s / kernel_s if kernel_s else None
