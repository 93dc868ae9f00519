"""Readers for a model whose attention layers select their keys by BLOCKS
past a dense length (InfLLM-V2, ``deepspeed_tpu/models/minicpm_sala.py``)
beside layers with a recurrent state a slot: the two block-sparse kernels
against the least work any implementation must do, and the step's roofline
with the selected work AND the state's traffic in it.

The program says what a step selected in the ``engine/dispatch`` span
(``deepspeed_tpu/inference/ragged.py`` ``_pack_step``), counted by the
family's own rule (``ModelSpec.index_blocks``): ``sel_pairs``, the query x
kept-key pairs (every key up to the dense length, the kept blocks' past it),
``sel_kv_tokens``, the fewest cached rows any implementation must read (what a
decode row keeps; what a tile's last query keeps), ``dec_sel_kv_tokens`` the
decode rows' part (one query a row, so also its part of the pairs),
``sel_queries``, the queries past the dense length, ``cmp_kv_tokens``, the
compressed keys their scores must read (once a decode row and a tile), and the
state family's ``state_bytes``. The cell's reference module counts the rest,
the sparse layers': ``kv_bytes_per_token``, ``attn_flops_per_pair``,
``cmp_bytes_per_key``. A program that wrote no such argument (every other
family, a parent commit), a reference without the arithmetic, or no span,
gives None.

The least time of a kernel holds whatever implements it: the kept pairs' FLOPs
and the kept rows' bytes, so that no reading can pass 100% because a tile's
queries share rows or because unkept pairs were multiplied too.
"""

from __future__ import annotations

import dsa_spans
import latent_spans


def _selected(ctx):
    """``(timeline, matched dispatches)`` if they carry the block
    selection's arguments, else ``(None, None)``."""
    tl, pairs = latent_spans._matched(ctx)
    if not pairs or not all("sel_queries" in a for a, _, _ in pairs):
        return None, None
    return tl, pairs


def geometry(ctx) -> dict | None:
    geo, ref, cfg = latent_spans.geometry(ctx), ctx["reference"], ctx["cfg"]
    if not geo or not hasattr(ref, "cmp_bytes_per_key"):
        return None
    itemsize = {"bfloat16": 2, "float16": 2, "float32": 4}[
        ctx["spec"]["config"]["serve"]["dtype"]]
    return {**geo, "cmp_bytes_per_key": ref.cmp_bytes_per_key(cfg, itemsize)}


def selected_share(ctx) -> float | None:
    """Kept pairs over causal pairs of the matched dispatches, per cent."""
    _, pairs = _selected(ctx)
    if not pairs:
        return None
    causal = sum(a["attn_pairs"] for a, _, _ in pairs)
    return 100.0 * sum(a["sel_pairs"] for a, _, _ in pairs) / causal \
        if causal else None


def _roofline(ctx, kernel: str, work) -> float | None:
    """``work(args, geo) -> (FLOPs, bytes)`` of one dispatch; the larger of
    the two least times over the kernel's device time, per cent."""
    geo = geometry(ctx)
    tl, pairs = _selected(ctx)
    if not geo or not pairs:
        return None
    kernel_s = dsa_spans._kernel_seconds(tl, pairs, kernel)
    if not kernel_s:
        return None
    flops = nbytes = 0
    for args, _, _ in pairs:
        f, b = work(args, geo)
        flops, nbytes = flops + f, nbytes + b
    peaks = ctx["peaks"]
    return 100.0 * max(flops / peaks["bf16_flops_per_s"],
                       nbytes / peaks["hbm_bytes_per_s"]) / kernel_s


def prefill_roofline(ctx) -> float | None:
    """The tiles' kept pairs and the rows a tile's last query keeps."""
    return _roofline(ctx, "bsa_prefill", lambda a, g: (
        g["flops_per_pair"] * (a["sel_pairs"] - a["dec_sel_kv_tokens"]),
        g["kv_bytes_per_token"] * (a["sel_kv_tokens"]
                                   - a["dec_sel_kv_tokens"])))


def decode_roofline(ctx) -> float | None:
    """The decode rows' kept keys, read once and multiplied by one query."""
    return _roofline(ctx, "bsa_decode", lambda a, g: (
        g["flops_per_pair"] * a["dec_sel_kv_tokens"],
        g["kv_bytes_per_token"] * a["dec_sel_kv_tokens"]))


def step_roofline_kv(ctx) -> float | None:
    """``ssm_spans.step_roofline_kv`` with the selected work of
    ``dsa_spans.step_roofline_kv`` in it: max((2 x active parameters x tokens
    + recurrence FLOPs x tokens + pair FLOPs x kept pairs) / peak FLOP/s,
    (dispatches x weight bytes + K/V bytes x sel_kv_tokens + compressed-key
    bytes x cmp_kv_tokens + state_bytes) / peak bytes/s) over the device time
    of the matched executions, per cent."""
    geo = geometry(ctx)
    _, pairs = _selected(ctx)
    ref = ctx["reference"]
    if not geo or not pairs or not hasattr(ref, "ssm_flops_per_token") \
            or not all("state_bytes" in a for a, _, _ in pairs):
        return None
    cfg, peaks = ctx["cfg"], ctx["peaks"]

    def total(key):
        return sum(a[key] for a, _, _ in pairs)

    compute_s = (((2.0 * ref.active_params(cfg) + ref.ssm_flops_per_token(cfg))
                  * total("tokens")
                  + geo["flops_per_pair"] * total("sel_pairs"))
                 / peaks["bf16_flops_per_s"])
    bytes_s = ((len(pairs) * ref.weight_bytes(cfg)
                + geo["kv_bytes_per_token"] * total("sel_kv_tokens")
                + geo["cmp_bytes_per_key"] * total("cmp_kv_tokens")
                + total("state_bytes")) / peaks["hbm_bytes_per_s"])
    device_s = sum(d for _, _, d in pairs) * 1e-9
    return 100.0 * max(compute_s, bytes_s) / device_s
