"""``host_spans``'s two rooflines for a model whose cache is not K and V heads:
the bytes a context token costs and the FLOPs a query x key pair costs come
from the cell's reference module (``kv_bytes_per_token``,
``attn_flops_per_pair``) instead of ``host_spans.attention_geometry``'s
``num_kv_heads x head``. MLA caches one latent row a token and layer, which is
key and value both: 1,152 B at Moonlight's widths, read once.

The formulas are ``host_spans.step_roofline_kv`` and
``host_spans.kernel_roofline`` otherwise, over the same matched dispatches. A
reference without the two functions, or a program that wrote no span, gives
None.
"""

from __future__ import annotations

import bisect

import host_spans


def geometry(ctx) -> dict | None:
    ref, cfg = ctx["reference"], ctx["cfg"]
    if not hasattr(ref, "kv_bytes_per_token") or not hasattr(ref, "attn_flops_per_pair"):
        return None
    itemsize = {"bfloat16": 2, "float16": 2, "float32": 4}[
        ctx["spec"]["config"]["serve"]["dtype"]]
    return {"kv_bytes_per_token": ref.kv_bytes_per_token(cfg, itemsize),
            "flops_per_pair": ref.attn_flops_per_pair(cfg)}


def _matched(ctx):
    tl = host_spans.timeline(ctx)
    pairs = host_spans.matched(tl) if tl else None
    return (tl, pairs) if pairs else (None, None)


def step_roofline_kv(ctx) -> float | None:
    """max((2 x active parameters x tokens + pair FLOPs x pairs) / peak
    FLOP/s, (dispatches x weight bytes + row bytes x kv_tokens) / peak
    bytes/s) over the device time of the matched executions, per cent."""
    geo = geometry(ctx)
    _, pairs = _matched(ctx)
    if not geo or not pairs:
        return None
    ref, cfg, peaks = ctx["reference"], ctx["cfg"], ctx["peaks"]
    tokens = sum(a["tokens"] for a, _, _ in pairs)
    compute_s = ((2.0 * ref.active_params(cfg) * tokens
                  + geo["flops_per_pair"] * sum(a["attn_pairs"] for a, _, _ in pairs))
                 / peaks["bf16_flops_per_s"])
    bytes_s = ((len(pairs) * ref.weight_bytes(cfg)
                + geo["kv_bytes_per_token"] * sum(a["kv_tokens"] for a, _, _ in pairs))
               / peaks["hbm_bytes_per_s"])
    device_s = sum(d for _, _, d in pairs) * 1e-9
    return 100.0 * max(compute_s, bytes_s) / device_s


def kernel_roofline(ctx, kernel: str, work) -> float | None:
    """The least time for the attention work of the matched dispatches
    (``work(args)`` -> ``(context tokens read, query x key pairs)``) over the
    kernel's device time inside their executions, per cent."""
    geo = geometry(ctx)
    tl, pairs = _matched(ctx)
    events = tl["kernels"].get(kernel) if tl else None
    if not geo or not pairs or not events:
        return None
    peaks = ctx["peaks"]
    kv = pairs_n = 0
    for args, _, _ in pairs:
        k, p = work(args)
        kv, pairs_n = kv + k, pairs_n + p
    least_s = max(geo["flops_per_pair"] * pairs_n / peaks["bf16_flops_per_s"],
                  geo["kv_bytes_per_token"] * kv / peaks["hbm_bytes_per_s"])
    spans = sorted((s, s + d) for _, s, d in pairs)
    starts = [s for s, _ in spans]
    kernel_s = 0.0
    for s, d in events:
        i = bisect.bisect_right(starts, s) - 1
        if i >= 0 and s < spans[i][1]:
            kernel_s += d * 1e-9
    return 100.0 * least_s / kernel_s if kernel_s else None
