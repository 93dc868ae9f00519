"""Readers for a model that keeps sliding-window layers beside full ones, each
kind in a pool of its own (``deepspeed_tpu/models/paged.py``, *Sliding
leaves*): the window layers' kernels against the window's bytes and pairs, the
step against weights + BOTH pools' least traffic, and what the sliding pool
saves.

The program says what its window layers read in the ``engine/dispatch`` span
(``deepspeed_tpu/inference/ragged.py`` ``_window_attr``): ``win_kv_tokens``
(``min(context, window)`` a scheduled sequence), ``dec_win_kv_tokens`` (the
decode rows' part), ``win_attn_pairs`` (``min(position + 1, window)`` a query),
``full_blocks_busy`` / ``win_blocks_busy`` (the pools' held blocks); beside
them ``kv_tokens`` / ``dec_kv_tokens`` / ``attn_pairs`` are what the full
layers read. The cell's reference module counts a row and a pair by layer
kind: ``kv_bytes_per_token`` / ``attn_flops_per_pair`` (full layers),
``window_kv_bytes_per_token`` / ``window_attn_flops_per_pair`` (window
layers). A program that wrote no such argument (every other family, a parent
commit), a reference without the window's functions, or no span, gives None.
"""

from __future__ import annotations

import types

import latent_spans

SPAN_KEYS = ("win_kv_tokens", "dec_win_kv_tokens", "win_attn_pairs")


def _window_view(ctx) -> dict | None:
    """``ctx`` with a reference whose row and pair are the WINDOW layers', so
    ``latent_spans``' formulas count them."""
    ref = ctx["reference"]
    if not hasattr(ref, "window_kv_bytes_per_token") \
            or not hasattr(ref, "window_attn_flops_per_pair"):
        return None
    view = types.SimpleNamespace(
        kv_bytes_per_token=ref.window_kv_bytes_per_token,
        attn_flops_per_pair=ref.window_attn_flops_per_pair)
    return {**ctx, "reference": view}


def _window_pairs(ctx):
    """The matched dispatches, if they carry the window's arguments."""
    _, pairs = latent_spans._matched(ctx)
    if not pairs or not all(k in a for a, _, _ in pairs for k in SPAN_KEYS):
        return None
    return pairs


def kernel_roofline(ctx, kernel: str, work) -> float | None:
    """``latent_spans.kernel_roofline`` with the window layers' row bytes and
    pair FLOPs: ``work(args)`` -> ``(window rows read, pairs inside the
    window)`` of a matched dispatch."""
    view = _window_view(ctx)
    if view is None or _window_pairs(ctx) is None:
        return None
    return latent_spans.kernel_roofline(view, kernel, work)


def step_roofline_kv(ctx) -> float | None:
    """max((2 x active parameters x tokens + full pair FLOPs x attn_pairs +
    window pair FLOPs x win_attn_pairs) / peak FLOP/s, (dispatches x weight
    bytes + full row bytes x kv_tokens + window row bytes x win_kv_tokens) /
    peak bytes/s) over the device time of the matched executions, per cent."""
    geo, view, pairs = (latent_spans.geometry(ctx), _window_view(ctx),
                        _window_pairs(ctx))
    if not geo or view is None or not pairs:
        return None
    win = latent_spans.geometry(view)
    ref, cfg, peaks = ctx["reference"], ctx["cfg"], ctx["peaks"]

    def total(key):
        return sum(a[key] for a, _, _ in pairs)

    compute_s = ((2.0 * ref.active_params(cfg) * total("tokens")
                  + geo["flops_per_pair"] * total("attn_pairs")
                  + win["flops_per_pair"] * total("win_attn_pairs"))
                 / peaks["bf16_flops_per_s"])
    bytes_s = ((len(pairs) * ref.weight_bytes(cfg)
                + geo["kv_bytes_per_token"] * total("kv_tokens")
                + win["kv_bytes_per_token"] * total("win_kv_tokens"))
               / peaks["hbm_bytes_per_s"])
    device_s = sum(d for _, _, d in pairs) * 1e-9
    return 100.0 * max(compute_s, bytes_s) / device_s


def window_held_share(ctx) -> float | None:
    """Blocks the sliding pool holds over the blocks ONE table would hold for
    the same sequences (the full pool's: a block of either is one block of a
    sequence's positions), summed over the matched dispatches, per cent: what
    the slide leaves of the window layers' cache."""
    _, pairs = latent_spans._matched(ctx)
    rows = [a for a, _, _ in pairs or ()
            if "win_blocks_busy" in a and "full_blocks_busy" in a]
    full = sum(a["full_blocks_busy"] for a in rows)
    if not full:
        return None
    return 100.0 * sum(a["win_blocks_busy"] for a in rows) / full
