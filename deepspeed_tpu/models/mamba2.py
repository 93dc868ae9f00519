"""The Mamba-2 mixer, shared by the families that have one (``nemotron_h``'s
``M`` layers, ``granite_hybrid``'s ``mamba`` layers): the projections' split,
the causal convolution, the chunked (SSD) form a prefill tile runs, the decode
row's state update (``ops/pallas/ssm.py``), the gated norm, the slot leaves and
the seeded draws. A family's layer is its own (what it norms, what it adds to
the residual and how it is scaled); the mixer takes the normed rows and gives
its output.

``[z | xBC | dt] = h W_in`` (widths ``d_inner`` | ``d_inner + 2 G N`` | ``H``);
``xBC <- silu(causal depthwise conv_K(xBC) + b)``; split ``x`` [H, P], ``B``,
``C`` [G, N] (``H / G`` heads share a group's; ``G`` = 1: one ``B`` and one
``C`` for all the heads); ``dt = softplus(dt + dt_bias)``, ``A = -exp(A_log)``
a head; ``S_t = exp(dt_t A) S_{t-1} + dt_t x_t (x) B_t``, ``y_t = S_t C_t + D
x_t``; ``y <- RMSNorm_grouped(y silu(z))`` (``G`` groups, its own weight; one
group: over all ``d_inner`` lanes); ``out = y W_out``. What a sequence carries
from token to token is ``S`` (float32) and the last ``K - 1`` rows of ``xBC``,
whatever its length.

**Beside ``models/mamba1.py``** (``jamba``'s Mamba-1 mixer). It shares
``causal_conv``, the slot leaves' layout rule (``init_slot_leaves``: the state
size first, the lanes last, the convolution's rows a window leaf) and
``ragged``'s rules for a step's rows (decode rows then tiles, ``cont`` /
``fresh`` / ``write``: ``tile_rows``; ``dt = 0`` past a tile's valid rows, the
scratch slot). It cannot share ``ssd_tiles`` (a head's scalar decay factors out of a chunk,
Mamba-1's decay a channel and state index does not: its tiles are a scan,
``ops/pallas/selscan.py``), ``split`` / ``xbc_split`` (Mamba-1 convolves ``x``
alone and makes ``dt``, ``B``, ``C`` after the convolution, through a
bottleneck and three norms), ``mixer_out`` (no gated norm there) nor
``ssm_decode`` (one decay a lane, handed in).

``cfg`` is the family's config; read here: ``d_inner``, ``conv_width``,
``conv_kernel``, ``n_groups``, ``ssm_state_size``, ``mamba_num_heads``,
``mamba_head_dim``, ``chunk_size``, ``rms_norm_eps`` and, by the draws,
``time_step_min`` / ``time_step_max`` / ``time_step_floor``.

**Serving** (``models/paged.py``, *Slot leaves*, *Window leaves*): the state
lies in slot leaves, ``ssm`` ``[L_mamba, S, N, H x P]`` float32 (the state size
first, a head's ``P`` values side by side on the lanes: ``ops/pallas/ssm.py``
says why) and ``conv``, the convolution's last ``K - 1`` input rows as a window
leaf. A ragged step is decode rows, then prefill tiles. A decode row is one
update of its slot's state (``ssm_decode``). A tile is one chunk of the chunked
form: matmuls inside the chunk, the state carried from tile to tile of a slot
in order, the first from the slot's state; ``dt = 0`` on a tile's rows past its
valid ones, so that they neither decay nor feed the state. A row or tile at
position 0 starts from zeros whatever the slot held.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax

LOGICAL_AXES = {
    "w_in": ("layers", "embed", None),
    "conv_w": ("layers", None, None),
    "conv_b": ("layers", None),
    "dt_bias": ("layers", None),
    "a_log": ("layers", None),
    "d_skip": ("layers", None),
    "ssm_norm": ("layers", None),
    "w_out": ("layers", None, "embed"),
}


def init_mixer(cfg, layers: int, key_dt, keys, std: float, out_std) -> dict:
    """The mixers' weights of ``layers`` layers, stacked, float32: ``key_dt``
    draws ``dt``, ``keys`` (an iterator) the five draws that follow, in the
    order of the result. ``dt`` is spread log-uniformly over [time_step_min,
    time_step_max] a head (``dt_bias`` its inverse softplus), ``A`` over [1,
    16], as Mamba-2 initialises them, ``D`` = 1 + N(0, 0.1), the convolution
    uniform in +-1/sqrt(K) with bias N(0, 0.1): decay, gate and bias all matter
    from the first token."""
    d, di, cw = cfg.hidden_size, cfg.d_inner, cfg.conv_width
    h, k_conv = cfg.mamba_num_heads, cfg.conv_kernel

    def norm(key, *shape, s=std):
        return jax.random.normal(key, shape, jnp.float32) * s

    dt0 = jnp.exp(jax.random.uniform(key_dt, (layers, h), jnp.float32)
                  * (jnp.log(cfg.time_step_max) - jnp.log(cfg.time_step_min))
                  + jnp.log(cfg.time_step_min))
    dt0 = jnp.maximum(dt0, cfg.time_step_floor)
    return {
        "w_in": norm(next(keys), layers, d, di + cw + h),
        "conv_w": jax.random.uniform(next(keys), (layers, k_conv, cw),
                                     jnp.float32, -1.0, 1.0) * k_conv ** -0.5,
        "conv_b": norm(next(keys), layers, cw, s=0.1),
        "dt_bias": dt0 + jnp.log(-jnp.expm1(-dt0)),
        "a_log": jnp.log(jax.random.uniform(next(keys), (layers, h),
                                            jnp.float32, 1.0, 16.0)),
        "d_skip": 1.0 + norm(next(keys), layers, h, s=0.1),
        "ssm_norm": jnp.ones((layers, di), jnp.float32),
        "w_out": norm(next(keys), layers, di, d, s=out_std),
    }


def mixer_param_count(cfg) -> int:
    """One mixer's parameters: ``W_in``, the convolution and its bias,
    ``dt_bias``, ``A_log``, ``D``, the gated norm's weight, ``W_out``."""
    d = cfg.hidden_size
    return (d * (cfg.d_inner + cfg.conv_width + cfg.mamba_num_heads)
            + (cfg.conv_kernel + 1) * cfg.conv_width
            + 3 * cfg.mamba_num_heads + cfg.d_inner + cfg.d_inner * d)


def init_slot_leaves(cfg, layers: int, num_slots: int, dtype) -> dict:
    """The slot leaves of ``layers`` Mamba layers (``models/paged.py``):
    ``ssm`` ``[layers, num_slots, N, H x P]`` float32 and ``conv``, the
    convolution's ``K - 1`` carried rows as a window leaf
    (``paged.init_window_leaf``). The last slot is the scratch slot."""
    from deepspeed_tpu.models.paged import init_window_leaf

    return {
        "ssm": jnp.zeros((layers, num_slots, cfg.ssm_state_size, cfg.d_inner),
                         jnp.float32),
        "conv": init_window_leaf(layers, num_slots, cfg.conv_kernel - 1,
                                 cfg.conv_width, dtype),
    }


def split(cfg, h, lp):
    """``h`` [..., D] (normed) -> ``z`` [..., d_inner], ``xBC`` [..., conv
    width] (before the convolution), ``dt`` [..., H] float32 (after the
    bias and the softplus).

    ONE product, and its three column parts handed out as arrays of their
    own, tied by ``lax.optimization_barrier`` so that the compiler cannot
    look through them to the product. ``xBC`` and ``dt`` are read at once;
    ``z`` only at the layer's very end (``mixer_out``), after the decode
    rows' kernel, the tiles' chunk form and every slot write. As plain slices
    of ``zxbcdt``, keeping ``z`` meant keeping the whole result (15 MB at
    Granite's 448 rows x 16,768) in the chip's near memory across the layer:
    the compiler evicted it and, rather than fetch it back, made the product
    a SECOND time for the gate (``%fusion.669.remat = bf16[448,16768]``:
    0.1609 s of a 4.0 s slice of ``granite-4.0-h-small-d10-ep2.chat-open``,
    0.0858 s of the Nemotron cell's; ledger, PR 53). Tied, what lives across
    the layer is ``z`` alone and every column is made once:
    ``tests/unit/test_compile_tpu.py
    test_mamba2_step_makes_the_in_projection_once`` holds that on the
    compiled programs at the cells' sizes. The tie is no arithmetic (the
    parts are the product's columns bit for bit, the gradients through them
    too) and one path for every program, decode-only ones included.

    NOT two products over column slices of ``W_in`` (``z`` made where it is
    read): with two readers the compiler stops reading the layer's weight
    where the stack keeps it, slices the layer's whole ``W_in`` (137 MB) out
    of the stack into a buffer of its own every step and rematerialises
    THAT (compiled for a described v5e, PR 54; the fault
    ``test_step_program_relays_out_no_projection_weight`` names)."""
    di, cw = cfg.d_inner, cfg.conv_width
    zxbcdt = h @ lp["w_in"].astype(h.dtype)
    z, xbc, dt = lax.optimization_barrier(
        (zxbcdt[..., :di], zxbcdt[..., di:di + cw], zxbcdt[..., di + cw:]))
    dt = jax.nn.softplus(dt.astype(jnp.float32)
                         + lp["dt_bias"].astype(jnp.float32))
    return z, xbc, dt


def causal_conv(cfg, win, w, b, rows: int, act=jax.nn.silu):
    """Causal depthwise convolution and ``act`` (silu; None: none, the gated
    short convolution of ``models/shortconv.py``): ``win`` [..., rows + K - 1,
    C] (the ``K - 1`` rows before the first, then the rows), ``w`` [K, C] and
    ``b`` [C] (None: no bias) -> [..., rows, C] in ``win``'s dtype, float32
    inside. The channels may be folded over two axes in all three, as a
    window leaf keeps them (``paged.window_fold``): the result's are too."""
    w = w.astype(jnp.float32)
    axis = win.ndim - w.ndim
    acc = None if b is None else b.astype(jnp.float32)
    for k in range(cfg.conv_kernel):
        tap = lax.slice_in_dim(win, k, k + rows, axis=axis).astype(
            jnp.float32) * w[k]
        acc = tap if acc is None else acc + tap
    return (acc if act is None else act(acc)).astype(win.dtype)


def xbc_split(cfg, xc):
    """``xc`` [..., C] -> ``x`` [..., H, P], ``B`` and ``C`` [..., G, N]."""
    di, gn = cfg.d_inner, cfg.n_groups * cfg.ssm_state_size
    lead = xc.shape[:-1]
    return (xc[..., :di].reshape(*lead, cfg.mamba_num_heads, cfg.mamba_head_dim),
            xc[..., di:di + gn].reshape(*lead, cfg.n_groups, cfg.ssm_state_size),
            xc[..., di + gn:].reshape(*lead, cfg.n_groups, cfg.ssm_state_size))


def mixer_out(cfg, y, x, z, lp):
    """``y`` [..., H x P] float32 (the state's part) -> the layer's output
    [..., D]: the skip ``D x``, the gate ``silu(z)``, the grouped RMSNorm,
    ``W_out``."""
    f32 = jnp.float32
    lead = y.shape[:-1]
    y = y.reshape(*lead, cfg.mamba_num_heads, cfg.mamba_head_dim) \
        + lp["d_skip"].astype(f32)[:, None] * x.astype(f32)
    y = y.reshape(*lead, cfg.d_inner) * jax.nn.silu(z.astype(f32))
    g = y.reshape(*lead, cfg.n_groups, cfg.d_inner // cfg.n_groups)
    g = g * lax.rsqrt(jnp.mean(g * g, axis=-1, keepdims=True) + cfg.rms_norm_eps)
    y = g.reshape(*lead, cfg.d_inner).astype(z.dtype) * lp["ssm_norm"].astype(z.dtype)
    return y @ lp["w_out"].astype(z.dtype)


def ssd_tiles(cfg, x, dt, a, b, c, s0, cont):
    """The chunked (SSD) form over ``I`` tiles of ``R`` rows, one chunk each:
    ``x`` [I, R, H, P], ``dt`` [I, R, H] float32 (0 on rows that must
    neither decay nor feed the state), ``a`` [H] (negative), ``b`` / ``c``
    [I, R, G, N], ``s0`` [I, N, H x P] float32 (the state each tile would
    start from were it its slot's first of the step), ``cont`` [I] bool
    (tile ``i`` goes on where tile ``i - 1`` ended) -> ``(y [I, R, H x P]
    float32, s [I, N, H x P] float32)``: the recurrence's outputs and each
    tile's final state. bfloat16 (the inputs' dtype) operands to the
    matmuls, float32 decay and accumulation."""
    f32 = jnp.float32
    n_i, r, h, p = x.shape
    g, n = b.shape[2:]
    hg, q = h // g, h // g * p
    acum = jnp.cumsum(dt * a.astype(f32), axis=1)                 # [I, R, H]
    # inside the chunk: row t reads row s <= t, decayed from s to t
    diff = acum[:, :, None] - acum[:, None]                       # [I, t, s, H]
    causal = jnp.tril(jnp.ones((r, r), bool))[None, :, :, None]
    decay = jnp.exp(jnp.where(causal, diff, -jnp.inf))
    scores = jnp.einsum("itgn,isgn->itsg", c, b, preferred_element_type=f32)
    m = (jnp.repeat(scores, hg, axis=3) * decay * dt[:, None]).astype(x.dtype)
    y = jnp.einsum("itsh,ishp->ithp", m, x, preferred_element_type=f32)
    # each tile's own contribution to the state at its end
    to_end = jnp.exp(acum[:, -1:] - acum)                         # [I, R, H]
    xw = (x.astype(f32) * (dt * to_end)[..., None]).astype(x.dtype)
    # a group at a time, on lane slices of the state's own layout ([N, H x
    # P], the group's lanes side by side): one einsum over a group axis
    # makes XLA re-lay the whole slot leaf out to suit it, every step
    xw = xw.reshape(n_i, r, g, q)
    ds = jnp.concatenate(
        [jnp.einsum("isn,isq->inq", b[:, :, j], xw[:, :, j],
                    preferred_element_type=f32) for j in range(g)], axis=2)
    total = jnp.repeat(jnp.exp(acum[:, -1]), p, axis=1)[:, None]  # [I, 1, HP]
    # the carry from tile to tile: in order, tiny beside the matmuls
    before, after = [], []
    for i in range(n_i):
        prev = s0[i] if i == 0 else jnp.where(cont[i], after[-1], s0[i])
        before.append(prev)
        after.append(prev * total[i] + ds[i])
    before = jnp.stack(before)
    before = before.astype(x.dtype)
    y_state = jnp.concatenate(
        [jnp.einsum("itn,inq->itq", c[:, :, j], before[:, :, j * q:(j + 1) * q],
                    preferred_element_type=f32) for j in range(g)], axis=2)
    y = (y.reshape(n_i, r, h * p)
         + y_state * jnp.repeat(jnp.exp(acum), p, axis=2))
    return y, jnp.stack(after)


def sequence(cfg, lp, h):
    """The Mamba mixer over one whole sequence ``h`` [S, D] from an empty
    state, for the plain forward pass: a scan over chunks of the form the
    serving tiles run."""
    s, r, k = h.shape[0], cfg.chunk_size, cfg.conv_kernel
    z, xbc, dt = split(cfg, h, lp)
    win = jnp.concatenate([jnp.zeros((k - 1, xbc.shape[1]), xbc.dtype), xbc])
    x, b, c = xbc_split(cfg, causal_conv(cfg, win, lp["conv_w"], lp["conv_b"], s))
    pad = -s % r
    a = -jnp.exp(lp["a_log"].astype(jnp.float32))

    def chunks(t):
        return jnp.pad(t, ((0, pad),) + ((0, 0),) * (t.ndim - 1)).reshape(
            (-1, r) + t.shape[1:])

    def chunk(state, xs):
        y, state = ssd_tiles(cfg, *(t[None] for t in xs[:2]), a,
                             *(t[None] for t in xs[2:]), state[None],
                             jnp.zeros((1,), bool))
        return state[0], y[0]

    state = jnp.zeros((cfg.ssm_state_size, cfg.d_inner), jnp.float32)
    _, y = lax.scan(chunk, state, tuple(map(chunks, (x, dt, b, c))))
    return mixer_out(cfg, y.reshape(-1, cfg.d_inner)[:s], x, z, lp)



def tile_rows(ts, tp, slot0, scratch):
    """A step's prefill tiles (``ts`` their slots, ``tp`` their first
    positions) as a slot family's mixer addresses them: ``(rows, rows_w,
    fresh, cont, write)``, where a tile's state lies, where the state it ends
    with goes, whether it starts from zeros (position 0), goes on where the
    tile before ended, writes."""
    real = ts != scratch
    rows = ts + slot0
    fresh = tp == 0
    # tile i goes on where tile i - 1 of the same slot ended
    cont = jnp.concatenate([jnp.zeros((1,), bool),
                            (ts[1:] == ts[:-1]) & real[1:]])
    write = real & ~jnp.concatenate([cont[1:], jnp.zeros((1,), bool)])
    # a tile that is not its slot's last of the step, and a padding tile,
    # write the scratch slot, and write it zeros
    rows_w = jnp.where(write, rows, slot0 + scratch)
    return rows, rows_w, fresh, cont, write


def ragged(cfg, h, lp, state, slot0, scratch, slots, positions,
           prefill_tiles):
    """The mixer over a flat ragged token batch ``h`` [T, D] (normed) ->
    ``(its output [T, D], the slot leaves)``: ``state`` the slot leaves,
    layers and slots merged; this layer's slot ``s`` is row ``slot0 + s``;
    ``scratch`` the scratch slot."""
    from deepspeed_tpu.models.paged import (
        decode_windows,
        tile_windows,
        window_fold,
    )
    from deepspeed_tpu.ops.pallas.ssm import ssm_decode

    f32 = jnp.float32
    ssm, conv = state["ssm"], state["conv"]
    p = cfg.mamba_head_dim
    z, xbc, dt = split(cfg, h, lp)
    a = -jnp.exp(lp["a_log"].astype(f32))
    t = h.shape[0]
    n_dec = t if prefill_tiles is None else prefill_tiles[0]
    ys, xs = [], []
    if n_dec:
        real = slots[:n_dec] != scratch
        fresh = real & (positions[:n_dec] == 0)
        rows = slots[:n_dec] + slot0
        # the window's arithmetic runs on the channels as the leaf folds
        # them: the rows come and go as whole tiles, the step's new rows and
        # the weights are what is folded, its results what is unfolded
        win, conv = decode_windows(conv, rows, xbc[:n_dec], fresh, real)
        xd, bd, cd = xbc_split(cfg, causal_conv(
            cfg, win, window_fold(conv, lp["conv_w"]),
            window_fold(conv, lp["conv_b"]), 1).reshape(n_dec, -1))
        dtd = jnp.where(real[:, None], dt[:n_dec], 0.0)
        # position 0: from zeros
        da = jnp.where(fresh[:, None], 0.0, jnp.exp(dtd * a))
        ssm, y = ssm_decode(
            ssm, rows, jnp.repeat(da, p, axis=1),
            (dtd[..., None] * xd.astype(f32)).reshape(n_dec, -1),
            bd.astype(f32).transpose(0, 2, 1), cd.astype(f32).transpose(0, 2, 1))
        ys.append(y)
        xs.append(xd)
    if t > n_dec:
        _, ts, tp, tv, r = prefill_tiles
        n_i = ts.shape[0]
        rows, rows_w, fresh, cont, write = tile_rows(ts, tp, slot0, scratch)
        win, conv = tile_windows(conv, rows, rows_w,
                                 xbc[n_dec:].reshape(n_i, r, -1), cont, fresh,
                                 write, tv)
        xt, bt, ct = xbc_split(cfg, causal_conv(cfg, win, lp["conv_w"],
                                           lp["conv_b"], r))
        valid = jnp.arange(r)[None, :] < tv[:, None]
        dtt = jnp.where(valid[..., None], dt[n_dec:].reshape(n_i, r, -1), 0.0)
        # a tile's state is read and written as ONE row of the leaf, a
        # dynamic slice each: handed a gather of whole rows, XLA re-lays the
        # entire leaf out in four lane-quarters first (2.6 GB a step at
        # Nemotron-3's sizes, on the compiled program)
        s_old = jnp.stack([lax.dynamic_index_in_dim(ssm, rows[i], 0, False)
                           for i in range(n_i)])
        y, s_new = ssd_tiles(cfg, xt, dtt, a, bt, ct,
                             jnp.where(fresh[:, None, None], 0.0, s_old), cont)
        s_new = jnp.where(write[:, None, None], s_new, 0.0)
        for i in range(n_i):
            ssm = lax.dynamic_update_index_in_dim(ssm, s_new[i], rows_w[i], 0)
        ys.append(y.reshape(n_i * r, -1))
        xs.append(xt.reshape((n_i * r,) + xt.shape[2:]))
    y = ys[0] if len(ys) == 1 else jnp.concatenate(ys)
    xh = xs[0] if len(xs) == 1 else jnp.concatenate(xs)
    return mixer_out(cfg, y, xh, z, lp), {"ssm": ssm, "conv": conv}
