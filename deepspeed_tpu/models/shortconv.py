"""The gated short-convolution mixer (LFM2's ``conv`` layers, ``lfm2_moe``):
the projections' split, the two gates around a causal depthwise convolution of
``K`` taps, the slot leaf and the seeded draws. A family's layer is its own
(what it norms and adds to the residual); the mixer takes the normed rows and
gives its output.

``[B | C | u] = h W_in`` (``D`` each, in that order, no bias); ``z = B * u``;
``c_t = sum_k w_k * z_{t - K + 1 + k}`` (``w`` [K, D] a depthwise causal
filter, ``z`` zero before the sequence's first token, no bias and NO
activation); ``out = (C * c) W_out``. What a sequence carries from token to
token is the last ``K - 1`` rows of ``z`` and nothing else: no matrix state,
no decay, no chunk form and no scan.

**Beside ``models/mamba2.py`` / ``models/mamba1.py``.** Shared: the taps'
arithmetic (``mamba2.causal_conv``, here with no bias and ``act=None``), the
window leaf and a step's rows through it (``paged.decode_windows`` /
``paged.tile_windows``), and how a step's tiles address a slot leaf (``cont``
/ ``fresh`` / ``write``, the scratch slot: ``mamba2.tile_rows``). Not shared:
everything else of those mixers. There the convolution stands in FRONT of a
recurrence; here it, between its two gates, IS the mixer.

``cfg`` is the family's config; read here: ``hidden_size`` and
``conv_kernel``.

**Serving** (``models/paged.py``, *Slot leaves*, *Window leaves*): the slot
leaves are a window leaf alone, ``conv`` ``[L_conv, S, (K - 1) x r, D / r]``
(LFM2-8B-A1B: 2,048 lanes fold over a whole bfloat16 tile, ``[.., 32, 128]``,
8 KB a slot and layer). A decode row reads its slot's window, moves it on by
its own ``z`` and writes it back; a prefill tile starts from its slot's window
(from zeros at position 0, from the tile before it of the same slot in the
same step) and leaves the last ``K - 1`` valid rows of ``z`` behind. One
gather and one scatter of the step's rows a layer, nothing of the leaf's size.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from deepspeed_tpu.models.mamba2 import causal_conv, tile_rows

LOGICAL_AXES = {
    "w_in": ("layers", "embed", None),
    "conv_w": ("layers", None, None),
    "w_out": ("layers", None, "embed"),
}


def init_mixer(cfg, layers: int, keys, std: float, out_std) -> dict:
    """The mixers' weights of ``layers`` layers, stacked, float32, drawn from
    ``keys`` (an iterator) in the order of the result: ``W_in`` at ``std``,
    the filter uniform in +-1/sqrt(K) (as ``mamba2.init_mixer`` draws its
    convolution), ``W_out`` at ``out_std``."""
    d, k = cfg.hidden_size, cfg.conv_kernel
    return {
        "w_in": jax.random.normal(next(keys), (layers, d, 3 * d),
                                  jnp.float32) * std,
        "conv_w": jax.random.uniform(next(keys), (layers, k, d), jnp.float32,
                                     -1.0, 1.0) * k ** -0.5,
        "w_out": jax.random.normal(next(keys), (layers, d, d),
                                   jnp.float32) * out_std,
    }


def mixer_param_count(cfg) -> int:
    """One mixer's parameters: ``W_in``, the filter, ``W_out``."""
    d = cfg.hidden_size
    return d * 3 * d + cfg.conv_kernel * d + d * d


def init_slot_leaves(cfg, layers: int, num_slots: int, dtype) -> dict:
    """The slot leaves of ``layers`` convolution layers (``models/paged.py``):
    ``conv``, the ``K - 1`` carried rows of ``z`` as a window leaf
    (``paged.init_window_leaf``), and nothing else. The last slot is the
    scratch slot."""
    from deepspeed_tpu.models.paged import init_window_leaf

    return {"conv": init_window_leaf(layers, num_slots, cfg.conv_kernel - 1,
                                     cfg.hidden_size, dtype)}


def split(cfg, h, lp):
    """``h`` [..., D] (normed) -> ``z = B * u`` (what the filter reads) and
    the output gate ``C``, each [..., D]."""
    d = cfg.hidden_size
    bcu = h @ lp["w_in"].astype(h.dtype)
    return bcu[..., :d] * bcu[..., 2 * d:], bcu[..., d:2 * d]


def sequence(cfg, lp, h):
    """The mixer over one whole sequence ``h`` [S, D] from an empty window,
    for the plain forward pass."""
    z, gate = split(cfg, h, lp)
    win = jnp.concatenate(
        [jnp.zeros((cfg.conv_kernel - 1, z.shape[1]), z.dtype), z])
    c = causal_conv(cfg, win, lp["conv_w"], None, h.shape[0], act=None)
    return (gate * c) @ lp["w_out"].astype(h.dtype)


def ragged(cfg, h, lp, state, slot0, scratch, slots, positions,
           prefill_tiles):
    """The mixer over a flat ragged token batch ``h`` [T, D] (normed) ->
    ``(its output [T, D], the slot leaves)``: ``state`` the slot leaves,
    layers and slots merged; this layer's slot ``s`` is row ``slot0 + s``;
    ``scratch`` the scratch slot. ``mamba2.ragged``'s rules for a step's
    rows, with nothing behind the convolution."""
    from deepspeed_tpu.models.paged import (
        decode_windows,
        tile_windows,
        window_fold,
    )

    with jax.named_scope("shortconv"):
        conv = state["conv"]
        z, gate = split(cfg, h, lp)
        t = h.shape[0]
        n_dec = t if prefill_tiles is None else prefill_tiles[0]
        cs = []
        if n_dec:
            real = slots[:n_dec] != scratch
            fresh = real & (positions[:n_dec] == 0)
            win, conv = decode_windows(conv, slots[:n_dec] + slot0, z[:n_dec],
                                       fresh, real)
            cs.append(causal_conv(cfg, win, window_fold(conv, lp["conv_w"]),
                                  None, 1, act=None).reshape(n_dec, -1))
        if t > n_dec:
            _, ts, tp, tv, r = prefill_tiles
            n_i = ts.shape[0]
            rows, rows_w, fresh, cont, write = tile_rows(ts, tp, slot0, scratch)
            win, conv = tile_windows(conv, rows, rows_w,
                                     z[n_dec:].reshape(n_i, r, -1), cont,
                                     fresh, write, tv)
            cs.append(causal_conv(cfg, win, lp["conv_w"], None, r,
                                  act=None).reshape(n_i * r, -1))
        c = cs[0] if len(cs) == 1 else jnp.concatenate(cs)
        return (gate * c) @ lp["w_out"].astype(h.dtype), {"conv": conv}
