"""Roofline share of the Mamba-1 tile kernel: the least time for what any
implementation of the scan must do for the matched dispatches' prompt tokens
(``ssm_prefill_tokens`` of the ``engine/dispatch`` spans: their ``x``, ``dt``,
``B``, ``C`` in and ``y`` out, the reference's ``scan_io_bytes_per_token``;
the prefilling slots' state once each way, ``state_bytes`` less
``dec_state_bytes``; the reference's ``ssm_flops_per_token``, 7 a state
update) over the kernel's device time inside their executions, per cent. The
kernel is bound by the vector unit (an ``exp`` and 7 FLOPs a state update, no
matmul), for which ``peaks.json`` has no peak: the share reads against the
bytes and the MXU's FLOP/s and so reads LOW, as ``kernels/selscan_tile.json``
says. Nothing where the spans carry no ``scan_tiles`` (every other family, a
parent commit) or the trace no such kernel."""
import bisect

import latent_spans
import ssm_spans


def read(ctx):
    pairs = ssm_spans._state_pairs(ctx)
    tl, _ = latent_spans._matched(ctx)
    events = tl["kernels"].get("selscan_tile") if tl else None
    ref = ctx["reference"]
    if (not pairs or not events or not hasattr(ref, "scan_io_bytes_per_token")
            or not any("scan_tiles" in a for a, _, _ in pairs)):
        return None
    cfg, peaks = ctx["cfg"], ctx["peaks"]
    itemsize = {"bfloat16": 2, "float16": 2, "float32": 4}[
        ctx["spec"]["config"]["serve"]["dtype"]]
    tokens = sum(a["ssm_prefill_tokens"] for a, _, _ in pairs)
    state = sum(a["state_bytes"] - a["dec_state_bytes"] for a, _, _ in pairs)
    least_s = max(
        (ref.scan_io_bytes_per_token(cfg, itemsize) * tokens + state)
        / peaks["hbm_bytes_per_s"],
        ref.ssm_flops_per_token(cfg) * tokens / peaks["bf16_flops_per_s"])
    spans = sorted((s, s + d) for _, s, d in pairs)
    starts = [s for s, _ in spans]
    kernel_s = 0.0
    for s, d in events:
        i = bisect.bisect_right(starts, s) - 1
        if i >= 0 and s < spans[i][1]:
            kernel_s += d * 1e-9
    return 100.0 * least_s / kernel_s if kernel_s else None
