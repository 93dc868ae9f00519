"""Readers for a model whose attention reads a learned selection of the
context (DeepSeek sparse attention): the indexer's and the sparse attention's
kernels against the least work any implementation must do, and the step's
roofline with the selected work in it.

The program says what a step selected in the ``engine/dispatch`` span
(``deepspeed_tpu/inference/ragged.py`` ``_pack_step``): ``sel_pairs``, the
query x kept-row pairs (``min(position + 1, index_topk)`` a query token),
``sel_kv_tokens``, the fewest cached rows any implementation must read
(``min(context, index_topk)`` a decode row and a prefill tile), and
``dec_sel_kv_tokens``, the decode rows' part of it (a decode row is one query,
so also its part of the pairs). ``kv_tokens``, ``attn_pairs`` and
``dec_kv_tokens`` keep their meaning and are what the indexer scores. The
cell's reference module counts the rest, all layers: ``kv_bytes_per_token`` and
``attn_flops_per_pair`` of the latent attention, ``index_bytes_per_token`` and
``index_flops_per_pair`` of the indexer. A program that wrote no such argument
(every other family, a parent commit), a reference without the indexer's
arithmetic, or no span, gives None.

The least time of a kernel holds whatever implements it: the kept pairs'
FLOPs and the kept rows' bytes, so that no reading can pass 100% because a
tile's queries share rows or because unkept pairs were multiplied too.
"""

from __future__ import annotations

import bisect

import latent_spans


def _selected(ctx):
    """``(timeline, matched dispatches)`` if they carry the selection's
    arguments, else ``(None, None)``."""
    tl, pairs = latent_spans._matched(ctx)
    if not pairs or not all("sel_pairs" in a for a, _, _ in pairs):
        return None, None
    return tl, pairs


def geometry(ctx) -> dict | None:
    """Bytes a cached token and FLOPs a pair, all layers, of the latent
    attention and of the indexer."""
    geo, ref, cfg = latent_spans.geometry(ctx), ctx["reference"], ctx["cfg"]
    if not geo or not hasattr(ref, "index_flops_per_pair"):
        return None
    itemsize = {"bfloat16": 2, "float16": 2, "float32": 4}[
        ctx["spec"]["config"]["serve"]["dtype"]]
    return {**geo, "index_flops_per_pair": ref.index_flops_per_pair(cfg),
            "index_bytes_per_token": ref.index_bytes_per_token(cfg, itemsize)}


def selected_share(ctx) -> float | None:
    """Kept pairs over causal pairs of the matched dispatches, per cent: what
    the selection left of the attention a dense model would do."""
    _, pairs = _selected(ctx)
    if not pairs:
        return None
    causal = sum(a["attn_pairs"] for a, _, _ in pairs)
    return 100.0 * sum(a["sel_pairs"] for a, _, _ in pairs) / causal if causal else None


def _kernel_seconds(tl, pairs, kernel: str) -> float:
    """Device seconds of ``kernel``'s events inside the matched executions."""
    spans = sorted((s, s + d) for _, s, d in pairs)
    starts = [s for s, _ in spans]
    total = 0.0
    for s, d in tl["kernels"].get(kernel) or ():
        i = bisect.bisect_right(starts, s) - 1
        if i >= 0 and s < spans[i][1]:
            total += d * 1e-9
    return total


def _roofline(ctx, kernel: str, work) -> float | None:
    """``work(args, geo) -> (FLOPs, bytes)`` of one dispatch; the larger of
    the two least times over the kernel's device time, per cent."""
    geo = geometry(ctx)
    tl, pairs = _selected(ctx)
    if not geo or not pairs:
        return None
    kernel_s = _kernel_seconds(tl, pairs, kernel)
    if not kernel_s:
        return None
    flops = nbytes = 0
    for args, _, _ in pairs:
        f, b = work(args, geo)
        flops, nbytes = flops + f, nbytes + b
    peaks = ctx["peaks"]
    least_s = max(flops / peaks["bf16_flops_per_s"],
                  nbytes / peaks["hbm_bytes_per_s"])
    return 100.0 * least_s / kernel_s


def index_roofline(ctx) -> float | None:
    """The indexer scores every causal pair and reads every cached index key
    of the step's sequences once."""
    return _roofline(ctx, "dsa_index", lambda a, g: (
        g["index_flops_per_pair"] * a["attn_pairs"],
        g["index_bytes_per_token"] * a["kv_tokens"]))


def prefill_roofline(ctx) -> float | None:
    """The tiles' kept pairs and the rows a tile must read."""
    return _roofline(ctx, "dsa_attn_prefill", lambda a, g: (
        g["flops_per_pair"] * (a["sel_pairs"] - a["dec_sel_kv_tokens"]),
        g["kv_bytes_per_token"] * (a["sel_kv_tokens"] - a["dec_sel_kv_tokens"])))


def decode_roofline(ctx) -> float | None:
    """The decode rows' kept rows, read once and multiplied by one query."""
    return _roofline(ctx, "dsa_attn_decode", lambda a, g: (
        g["flops_per_pair"] * a["dec_sel_kv_tokens"],
        g["kv_bytes_per_token"] * a["dec_sel_kv_tokens"]))


def step_roofline_kv(ctx) -> float | None:
    """``latent_spans.step_roofline_kv`` with the selected work: max((2 x
    active parameters x tokens + attention FLOPs x kept pairs + indexer FLOPs
    x causal pairs) / peak FLOP/s, (dispatches x weight bytes + latent bytes x
    sel_kv_tokens + index bytes x kv_tokens) / peak bytes/s) over the device
    time of the matched executions, per cent."""
    geo = geometry(ctx)
    _, pairs = _selected(ctx)
    if not geo or not pairs:
        return None
    ref, cfg, peaks = ctx["reference"], ctx["cfg"], ctx["peaks"]

    def total(key):
        return sum(a[key] for a, _, _ in pairs)

    compute_s = ((2.0 * ref.active_params(cfg) * total("tokens")
                  + geo["flops_per_pair"] * total("sel_pairs")
                  + geo["index_flops_per_pair"] * total("attn_pairs"))
                 / peaks["bf16_flops_per_s"])
    bytes_s = ((len(pairs) * ref.weight_bytes(cfg)
                + geo["kv_bytes_per_token"] * total("sel_kv_tokens")
                + geo["index_bytes_per_token"] * total("kv_tokens"))
               / peaks["hbm_bytes_per_s"])
    device_s = sum(d for _, _, d in pairs) * 1e-9
    return 100.0 * max(compute_s, bytes_s) / device_s
