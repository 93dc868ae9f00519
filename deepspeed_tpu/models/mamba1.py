"""The Mamba-1 mixer (the selective scan of ``jamba``'s Mamba layers): the
projections' split, the causal convolution, the rank-``R`` bottleneck that
makes ``dt`` and the three inner norms, the recurrence, the gate, the slot
leaves and the seeded draws. A family's layer is its own (what it norms and
adds to the residual); the mixer takes the normed rows and gives its output.

``[x | z] = h W_in`` (``d_inner`` each); ``x <- silu(causal depthwise
conv_K(x) + b)`` (over ``x`` alone: ``z`` is the gate and never convolved);
``[dt_r | B | C] = x W_x^T`` (``R`` | ``N`` | ``N``; ``W_x`` kept ``[R + 2 N,
d_inner]``), each through an RMSNorm of its own; ``dt = softplus(dt_r W_dt +
b_dt)`` [d_inner], ``A = -exp(A_log)`` [N, d_inner]; ``S_t[n, c] = exp(dt_t[c]
A[n, c]) S_{t-1}[n, c] + B_t[n] dt_t[c] x_t[c]``, ``y_t[c] = sum_n S_t[n, c]
C_t[n] + D[c] x_t[c]``; ``out = (y silu(z)) W_out``. What a sequence carries from token to token is ``S``
(float32) and the last ``K - 1`` rows of ``x`` before the convolution.

**Beside ``models/mamba2.py``.** Shared: the causal convolution
(``mamba2.causal_conv``), the slot leaves' layout rule (the state size first,
the channels on the lanes; the convolution's rows a window leaf), and
``ragged``'s rules for a step's rows (decode rows then tiles, ``cont`` /
``fresh`` / ``write``: ``mamba2.tile_rows``; ``dt = 0`` past a tile's valid
rows, the scratch slot). Not shared: the decay here is ``exp(dt[c] A[n, c])``, a value for
every channel and state index, so no chunk form exists (``mamba2.ssd_tiles``
factors a head's scalar decay out of the state) and a tile's rows go through
the recurrence in order (``ops/pallas/selscan.py``); ``B`` and ``C`` come
AFTER the convolution through ``W_x`` (Mamba-2 convolves them), ``dt`` a
channel through the bottleneck (Mamba-2's is a head's, straight off ``W_in``),
and there is no gated norm before ``W_out``.

``cfg`` is the family's config; read here: ``hidden_size``, ``d_inner``,
``ssm_state_size``, ``dt_rank``, ``conv_kernel``, ``rms_norm_eps`` and, by
the draws, ``time_step_min`` / ``time_step_max`` / ``time_step_floor``.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax

from deepspeed_tpu.models.llama import rmsnorm
from deepspeed_tpu.models.mamba2 import causal_conv, tile_rows

LOGICAL_AXES = {
    "w_in": ("layers", "embed", None),
    "conv_w": ("layers", None, None),
    "conv_b": ("layers", None),
    "w_x": ("layers", None, None),
    "dt_norm": ("layers", None),
    "b_norm": ("layers", None),
    "c_norm": ("layers", None),
    "w_dt": ("layers", None, None),
    "dt_bias": ("layers", None),
    "a_log": ("layers", None, None),
    "d_skip": ("layers", None),
    "w_out": ("layers", None, "embed"),
}


def init_mixer(cfg, layers: int, keys, std: float, out_std) -> dict:
    """The mixers' weights of ``layers`` layers, stacked, float32, drawn from
    ``keys`` (an iterator) in the order of the result. As Mamba-1 initialises
    them: ``A[n, c] = n + 1`` (``A_log`` its log, kept ``[N, d_inner]`` as the
    state lies), ``dt`` log-uniform over [time_step_min, time_step_max] a
    channel through ``b_dt``'s inverse softplus with ``W_dt`` uniform in
    +-R^-0.5, ``D`` = 1 + N(0, 0.1), the inner norms' weights 1; the
    convolution uniform in +-1/sqrt(K) with bias N(0, 0.1), as
    ``mamba2.init_mixer``."""
    d, di, n, r = cfg.hidden_size, cfg.d_inner, cfg.ssm_state_size, cfg.dt_rank
    k_conv = cfg.conv_kernel

    def norm(key, *shape, s=std):
        return jax.random.normal(key, shape, jnp.float32) * s

    w_in = norm(next(keys), layers, d, 2 * di)
    conv_w = jax.random.uniform(next(keys), (layers, k_conv, di), jnp.float32,
                                -1.0, 1.0) * k_conv ** -0.5
    conv_b = norm(next(keys), layers, di, s=0.1)
    w_x = norm(next(keys), layers, r + 2 * n, di)
    w_dt = jax.random.uniform(next(keys), (layers, r, di), jnp.float32,
                              -1.0, 1.0) * r ** -0.5
    dt0 = jnp.exp(jax.random.uniform(next(keys), (layers, di), jnp.float32)
                  * (jnp.log(cfg.time_step_max) - jnp.log(cfg.time_step_min))
                  + jnp.log(cfg.time_step_min))
    dt0 = jnp.maximum(dt0, cfg.time_step_floor)
    return {
        "w_in": w_in,
        "conv_w": conv_w,
        "conv_b": conv_b,
        "w_x": w_x,
        "dt_norm": jnp.ones((layers, r), jnp.float32),
        "b_norm": jnp.ones((layers, n), jnp.float32),
        "c_norm": jnp.ones((layers, n), jnp.float32),
        "w_dt": w_dt,
        "dt_bias": dt0 + jnp.log(-jnp.expm1(-dt0)),
        "a_log": jnp.broadcast_to(
            jnp.log(jnp.arange(1, n + 1, dtype=jnp.float32))[None, :, None],
            (layers, n, di)),
        "d_skip": 1.0 + norm(next(keys), layers, di, s=0.1),
        "w_out": norm(next(keys), layers, di, d, s=out_std),
    }


def mixer_param_count(cfg) -> int:
    """One mixer's parameters: ``W_in``, the convolution and its bias,
    ``W_x``, the three inner norms, ``W_dt`` and its bias, ``A_log``, ``D``,
    ``W_out``."""
    d, di, n, r = cfg.hidden_size, cfg.d_inner, cfg.ssm_state_size, cfg.dt_rank
    return (d * 2 * di + (cfg.conv_kernel + 1) * di + di * (r + 2 * n)
            + r + 2 * n + r * di + di + n * di + di + di * d)


def init_slot_leaves(cfg, layers: int, num_slots: int, dtype) -> dict:
    """The slot leaves of ``layers`` Mamba layers (``models/paged.py``):
    ``ssm`` ``[layers, num_slots, N, d_inner]`` float32 and ``conv``, the
    convolution's ``K - 1`` carried rows of ``x`` as a window leaf
    (``paged.init_window_leaf``). The last slot is the scratch slot."""
    from deepspeed_tpu.models.paged import init_window_leaf

    return {
        "ssm": jnp.zeros((layers, num_slots, cfg.ssm_state_size, cfg.d_inner),
                         jnp.float32),
        "conv": init_window_leaf(layers, num_slots, cfg.conv_kernel - 1,
                                 cfg.d_inner, dtype),
    }


def split(cfg, h, lp):
    """``h`` [..., D] (normed) -> ``x`` (before the convolution), ``z``, each
    [..., d_inner]."""
    xz = h @ lp["w_in"].astype(h.dtype)
    return xz[..., :cfg.d_inner], xz[..., cfg.d_inner:]


def selection(cfg, xc, lp):
    """``xc`` [..., d_inner] (after the convolution) -> ``dt`` [..., d_inner]
    float32 (after the bias and the softplus), ``B`` and ``C`` [..., N]: the
    bottleneck's three parts, each through its RMSNorm."""
    r, n, eps = cfg.dt_rank, cfg.ssm_state_size, cfg.rms_norm_eps
    # W_x lies [R + 2N, d_inner], as published (a Linear's [out, in]): 192
    # outputs are a lane tile and a half, and stored [d_inner, 192] a step
    # program copies every run's stack transposed (PERF.md section 6, PR 53)
    proj = jnp.einsum("...c,oc->...o", xc, lp["w_x"].astype(xc.dtype))
    dt_r = rmsnorm(proj[..., :r], lp["dt_norm"], eps)
    b = rmsnorm(proj[..., r:r + n], lp["b_norm"], eps)
    c = rmsnorm(proj[..., r + n:], lp["c_norm"], eps)
    dt = jax.nn.softplus((dt_r @ lp["w_dt"].astype(xc.dtype)).astype(jnp.float32)
                         + lp["dt_bias"].astype(jnp.float32))
    return dt, b, c


def mixer_out(cfg, y, xc, z, lp):
    """``y`` [..., d_inner] float32 (the state's part) -> the layer's output
    [..., D]: the skip ``D x``, the gate ``silu(z)``, ``W_out``."""
    f32 = jnp.float32
    y = y + lp["d_skip"].astype(f32) * xc.astype(f32)
    y = (y * jax.nn.silu(z.astype(f32))).astype(z.dtype)
    return y @ lp["w_out"].astype(z.dtype)


def sequence(cfg, lp, h):
    """The mixer over one whole sequence ``h`` [S, D] from an empty state,
    for the plain forward pass: the recurrence token by token."""
    f32 = jnp.float32
    s, k = h.shape[0], cfg.conv_kernel
    x, z = split(cfg, h, lp)
    win = jnp.concatenate([jnp.zeros((k - 1, x.shape[1]), x.dtype), x])
    xc = causal_conv(cfg, win, lp["conv_w"], lp["conv_b"], s)
    dt, b, c = selection(cfg, xc, lp)
    a = -jnp.exp(lp["a_log"].astype(f32))

    def token(state, xs):
        dt_t, x_t, b_t, c_t = xs
        state = jnp.exp(dt_t * a) * state + b_t[:, None] * (dt_t * x_t)
        return state, jnp.sum(state * c_t[:, None], axis=0)

    _, y = lax.scan(token, jnp.zeros(a.shape, f32),
                    (dt, xc.astype(f32), b.astype(f32), c.astype(f32)))
    return mixer_out(cfg, y, xc, z, lp)


def ragged(cfg, h, lp, state, slot0, scratch, slots, positions,
           prefill_tiles):
    """The mixer over a flat ragged token batch ``h`` [T, D] (normed) ->
    ``(its output [T, D], the slot leaves)``: ``state`` the slot leaves,
    layers and slots merged; this layer's slot ``s`` is row ``slot0 + s``;
    ``scratch`` the scratch slot. ``mamba2.ragged``'s rules, the recurrence
    through ``ops/pallas/selscan.py``."""
    from deepspeed_tpu.models.paged import (
        decode_windows,
        tile_windows,
        window_fold,
    )
    from deepspeed_tpu.ops.pallas.selscan import selscan_decode, selscan_tile

    f32 = jnp.float32
    ssm, conv = state["ssm"], state["conv"]
    x, z = split(cfg, h, lp)
    a = -jnp.exp(lp["a_log"].astype(f32))
    t = h.shape[0]
    n_dec = t if prefill_tiles is None else prefill_tiles[0]
    ys, xs = [], []
    if n_dec:
        real = slots[:n_dec] != scratch
        fresh = real & (positions[:n_dec] == 0)
        rows = slots[:n_dec] + slot0
        win, conv = decode_windows(conv, rows, x[:n_dec], fresh, real)
        xd = causal_conv(cfg, win, window_fold(conv, lp["conv_w"]),
                         window_fold(conv, lp["conv_b"]), 1).reshape(n_dec, -1)
        dtd, bd, cd = selection(cfg, xd, lp)
        # a padding row names the scratch slot: decay 1, feed 0
        dtd = jnp.where(real[:, None], dtd, 0.0)
        ssm, y = selscan_decode(ssm, rows, fresh, dtd, xd, a, bd, cd)
        ys.append(y)
        xs.append(xd)
    if t > n_dec:
        _, ts, tp, tv, r = prefill_tiles
        n_i = ts.shape[0]
        rows, rows_w, fresh, cont, write = tile_rows(ts, tp, slot0, scratch)
        win, conv = tile_windows(conv, rows, rows_w,
                                 x[n_dec:].reshape(n_i, r, -1), cont, fresh,
                                 write, tv)
        xt = causal_conv(cfg, win, lp["conv_w"], lp["conv_b"], r)
        dtt, bt, ct = selection(cfg, xt, lp)
        valid = jnp.arange(r)[None, :] < tv[:, None]
        dtt = jnp.where(valid[..., None], dtt, 0.0)
        ssm, y = selscan_tile(ssm, rows, rows_w, fresh, cont, write, dtt, xt,
                              a, bt, ct)
        ys.append(y.reshape(n_i * r, -1))
        xs.append(xt.reshape(n_i * r, -1))
    y = ys[0] if len(ys) == 1 else jnp.concatenate(ys)
    xc = xs[0] if len(xs) == 1 else jnp.concatenate(xs)
    return mixer_out(cfg, y, xc, z, lp), {"ssm": ssm, "conv": conv}
