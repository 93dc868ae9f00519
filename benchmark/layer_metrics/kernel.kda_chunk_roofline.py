"""Roofline share of the KDA chunk kernel: the least time for what any
implementation of the chunk form must do for the matched dispatches' prompt
tokens over the kernel's device time inside their executions, per cent. The
least time is the larger of (a) bytes: ``ssm_prefill_tokens`` of the
``engine/dispatch`` spans x the reference's ``kda_chunk_io_bytes_per_token``
(``q``, ``k``, ``v``, ``g``, ``beta`` in and ``y`` out, as a layer hands
them) + ``chunk_slots`` (the distinct prefilling slots of a step) x the
reference's ``kda_state_bytes_per_slot`` x 2 (the state once each way), over
the peak bytes/s, and (b) the chunk form's products counted ONCE each
(``kda_chunk_flops_per_tile`` at the engine's tile, x tokens / the tile's
rows) at the MXU's bf16 peak. The kernel multiplies float32 at
``Precision.HIGHEST``, several passes a product, and solves by a chain of
dependent steps: the share reads LOW by construction, as
``kernels/kda_chunk.json`` says. Nothing where the spans carry no
``chunk_slots`` (a family without a chunk form, a parent commit), the
reference no such arithmetic, or the trace no such kernel."""
import bisect

import latent_spans
import ssm_spans


def read(ctx):
    pairs = ssm_spans._state_pairs(ctx)
    tl, _ = latent_spans._matched(ctx)
    events = tl["kernels"].get("kda_chunk") if tl else None
    ref = ctx["reference"]
    if (not pairs or not events or not hasattr(ref, "kda_chunk_flops_per_tile")
            or not all("chunk_slots" in a for a, _, _ in pairs)):
        return None
    cfg, peaks = ctx["cfg"], ctx["peaks"]
    spec = ctx["spec"]
    tile = {**spec["config"]["serve"]["engine"],
            **spec["cell"].get("engine", {})}["prefill_tile"]
    tokens = sum(a["ssm_prefill_tokens"] for a, _, _ in pairs)
    slots = sum(a["chunk_slots"] for a, _, _ in pairs)
    least_s = max(
        (ref.kda_chunk_io_bytes_per_token(cfg) * tokens
         + 2 * ref.kda_state_bytes_per_slot(cfg) * slots)
        / peaks["hbm_bytes_per_s"],
        ref.kda_chunk_flops_per_tile(cfg, tile) * tokens / tile
        / peaks["bf16_flops_per_s"])
    spans = sorted((s, s + d) for _, s, d in pairs)
    starts = [s for s, _ in spans]
    kernel_s = 0.0
    for s, d in events:
        i = bisect.bisect_right(starts, s) - 1
        if i >= 0 and s < spans[i][1]:
            kernel_s += d * 1e-9
    return 100.0 * least_s / kernel_s if kernel_s else None
