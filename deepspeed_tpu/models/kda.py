"""The Kimi Delta Attention (KDA) mixer, shared by the families that have one
(``kimi_linear``'s ``D`` / ``K`` layers, ``solar_open2``'s ``K`` layers): the
projections, the three causal convolutions as one, the L2-normalised heads,
the chunk form a prefill tile runs and the decode row's delta-rule update
(``ops/pallas/kda.py``), the head norm and the output gate, the slot leaves
and the seeded draws. A family's layer is its own (what it norms, what it
adds to the residual, what follows the mixer); the mixer takes the normed
rows and gives its output.

``H`` heads, ``K = V`` a head (``kda_head_dim``), ``P = H x K``, a
convolution of ``conv_kernel`` taps::

    q = silu(conv(h W_q));  k = silu(conv(h W_k));  v = silu(conv(h W_v))        # causal depthwise, no bias
    q, k -> [T, H, K], each L2-normalised over K;  q *= K^-0.5;   v -> [T, H, V]
    g    = -exp(A_log[head]) * softplus((h W_fa) W_fb + dt_bias)   -> [T, H, K]  (<= 0: a log-decay A CHANNEL)
    beta = kda_beta_scale * sigmoid(h W_b)                         -> [T, H]
    per head, S in R^{K x V}, float32, S_0 = 0 at position 0:
        S  <- diag(exp(g_t)) S                  # decay each of the K rows by its own factor
        u   = beta_t * (v_t - S^T k_t)          # the delta rule: what the decayed state does not yet say about k_t
        S  <- S + k_t u^T
        o_t = S^T q_t
    out = (RMSNorm_head(o) * sigmoid((h W_ga) W_gb)) W_o           # norm over a head's V, weight [V]

**``kda_beta_scale`` is the only arithmetic that differs between the users**:
1.0 (``kimi_linear``: ``beta`` in (0, 1), the state's eigenvalue along ``k_t``
``1 - beta`` in (0, 1)) or 2.0 (``solar_open2``, ``kda_allow_neg_eigval``:
``beta`` in (0, 2), the eigenvalue in (-1, 1); ``|1 - beta| < 1`` still, so
the update along a unit ``k_t`` contracts and the state stays bounded). At 1.0
no multiply is traced: ``kimi_linear``'s step programs print the jaxprs they
printed before the mixer had two users
(``tests/unit/fixtures/step_jaxprs_pr48.json``).

``cfg`` is the family's config; read here: ``hidden_size``, ``kda_heads``,
``kda_head_dim``, ``kda_width``, ``conv_kernel``, ``chunk_size``,
``sub_chunk``, ``rms_norm_eps``, ``kda_beta_scale``.

**The chunk form** (``ops/pallas/kda.kda_tiles``; that module's doc has the
algebra). A tile of ``R`` rows runs the recurrence as matmuls, in sub-chunks
of ``sub_chunk`` (16) rows, float32 and ``Precision.HIGHEST`` throughout.
Rows of a tile past its valid ones have ``g = 0`` and ``beta = 0``: they
neither decay nor feed the state.

**Serving** (``models/paged.py``, *Slot leaves*, *Window leaves*): the state
lies in slot leaves, ``kda`` ``[L_kda, S, K, H x V]`` float32 (the key
channels on the sublanes, a head's values side by side on the lanes:
``ops/pallas/kda.py``) and ``conv``, the last ``kernel - 1`` rows of the three
convolutions' inputs ``[q | k | v]``, oldest first, as a window leaf. A decode
row is one delta-rule update of its slot's state (``kda_decode``), a prefill
tile one chunk, the state carried from tile to tile of a slot in order: on
the chip ``kda_chunk``, the chunk form as one kernel that reads the tiles'
rows and the slots' states where they lie; off it ``kda_tiles`` between a
slice and an update a tile (``kda_chunk_xla``). A row or tile at position 0
starts from zeros whatever the slot held. How a step's tiles address the
leaf (``cont`` / ``fresh`` / ``write``, the scratch slot) is every slot
family's rule, ``mamba2.tile_rows``.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax

STATE_KIND = "kda"
# the seeded gates: ``dt`` log-uniform over this range a channel (``draw``)
DT_RANGE = (0.001, 0.1)

LOGICAL_AXES = {"w_qkv": ("embed", None), "w_fa": ("embed", None),
                "w_b": ("embed", None), "w_ga": ("embed", None),
                "wo": ("heads", "embed")}


def mixer_shapes(cfg) -> dict:
    """``{name: (shape, init)}`` of one mixer; ``init`` a std, ``"out"`` (an
    output projection's), ``"ones"`` or one of ``draw``'s names."""
    d = cfg.hidden_size
    h, kd, p = cfg.kda_heads, cfg.kda_head_dim, cfg.kda_width
    return {"w_qkv": ((d, 3 * p), 0.02),
            "conv_w": ((cfg.conv_kernel, 3 * p), "conv"),
            "w_fa": ((d, kd), 0.02), "w_fb": ((kd, p), 0.02),
            "dt_bias": ((p,), "dt"), "a_log": ((h,), "a"),
            "w_b": ((d, h), 0.02),
            "w_ga": ((d, kd), 0.02), "w_gb": ((kd, p), 0.02),
            "o_norm": ((kd,), "ones"), "wo": ((p, d), "out")}


def draw(cfg, key, shape, init: str):
    """The mixer's own seeded draws, float32: ``"conv"`` uniform in +-1 /
    sqrt(kernel), ``"a"`` ``A_log = log U(1, 16)`` a head, ``"dt"``
    ``dt_bias`` the inverse softplus of a log-uniform draw over ``DT_RANGE``
    a channel (so a channel's decay a token lies in ~[0.2, 0.999]): decay and
    ``beta`` matter from the first token. Every other ``init`` (a std,
    ``"out"``, ``"ones"``) is the family's to draw."""
    if init == "conv":
        return jax.random.uniform(key, shape, jnp.float32, -1.0,
                                  1.0) * cfg.conv_kernel ** -0.5
    if init == "a":
        return jnp.log(jax.random.uniform(key, shape, jnp.float32, 1.0, 16.0))
    if init == "dt":
        lo, hi = (jnp.log(t) for t in DT_RANGE)
        dt = jnp.exp(jax.random.uniform(key, shape, jnp.float32)
                     * (hi - lo) + lo)
        return dt + jnp.log(-jnp.expm1(-dt))
    raise ValueError(f"kda: {init!r} is no draw of the mixer's own")


def init_slot_leaves(cfg, layers: int, num_slots: int, dtype) -> dict:
    """The slot leaves of ``layers`` KDA layers (``models/paged.py``):
    ``kda`` ``[layers, num_slots, K, H x V]`` float32 and ``conv``, the
    convolutions' ``kernel - 1`` carried rows of ``3 P`` channels, oldest
    first, as a window leaf (``paged.init_window_leaf``: ``[layers,
    num_slots, (kernel - 1) x r, 3 P / r]``). The last slot is the scratch
    slot."""
    from deepspeed_tpu.models.paged import init_window_leaf

    return {
        "kda": jnp.zeros((layers, num_slots, cfg.kda_head_dim, cfg.kda_width),
                         jnp.float32),
        "conv": init_window_leaf(layers, num_slots, cfg.conv_kernel - 1,
                                 3 * cfg.kda_width, dtype),
    }


def inputs(cfg, h, lp):
    """``h`` [..., D] (normed) -> ``qkv`` [..., 3 P] (before the
    convolutions), the log-decay ``g`` [..., H, K] float32 (<= 0), ``beta``
    [..., H] float32 and the output gate's logits [..., P]."""
    f32 = jnp.float32
    lead = h.shape[:-1]
    heads, kd = cfg.kda_heads, cfg.kda_head_dim
    dtype = h.dtype
    f = (h @ lp["w_fa"].astype(dtype)) @ lp["w_fb"].astype(dtype)
    dt = jax.nn.softplus(f.astype(f32) + lp["dt_bias"].astype(f32))
    g = -jnp.exp(lp["a_log"].astype(f32))[:, None] * dt.reshape(*lead, heads, kd)
    beta = jax.nn.sigmoid((h @ lp["w_b"].astype(dtype)).astype(f32))
    if cfg.kda_beta_scale != 1.0:
        beta = beta * cfg.kda_beta_scale
    gate = (h @ lp["w_ga"].astype(dtype)) @ lp["w_gb"].astype(dtype)
    return h @ lp["w_qkv"].astype(dtype), g, beta, gate


def conv(cfg, win, w, rows: int):
    """The three causal depthwise convolutions as one over ``[q | k | v]``,
    and silu: ``win`` [..., rows + kernel - 1, 3 P] (the ``kernel - 1`` rows
    before the first, then the rows) and ``w`` [kernel, 3 P] -> [..., rows,
    3 P] float32. The channels may be folded over two axes in both, as a
    window leaf keeps them (``paged.window_fold``): the result's are too."""
    w = w.astype(jnp.float32)
    axis = win.ndim - w.ndim
    acc = 0.0
    for j in range(cfg.conv_kernel):
        acc = acc + lax.slice_in_dim(win, j, j + rows, axis=axis).astype(
            jnp.float32) * w[j]
    return jax.nn.silu(acc)


def qkv_split(cfg, xc):
    """``xc`` [..., 3 P] float32 (after convolution and silu) -> ``q``, ``k``
    [..., H, K] (each L2-normalised over ``K``; ``q`` times ``K^-0.5``) and
    ``v`` [..., H, V], float32."""
    lead = xc.shape[:-1]
    heads, kd = cfg.kda_heads, cfg.kda_head_dim
    q, k, v = (xc[..., j * cfg.kda_width:(j + 1) * cfg.kda_width].reshape(
        *lead, heads, kd) for j in range(3))

    def unit(x):
        return x * lax.rsqrt(jnp.sum(x * x, axis=-1, keepdims=True) + 1e-6)

    return unit(q) * kd ** -0.5, unit(k), v


def mixer_out(cfg, o, gate, lp):
    """``o`` [..., H x V] float32 (the state's reading) -> the layer's output
    [..., D]: RMSNorm over each head's ``V`` (its weight ``[V]``), the sigmoid
    gate, ``W_o``."""
    f32 = jnp.float32
    lead = o.shape[:-1]
    o = o.reshape(*lead, cfg.kda_heads, cfg.kda_head_dim)
    o = o * lax.rsqrt(jnp.mean(o * o, axis=-1, keepdims=True) + cfg.rms_norm_eps)
    o = (o * lp["o_norm"].astype(f32)).reshape(*lead, cfg.kda_width)
    o = (o * jax.nn.sigmoid(gate.astype(f32))).astype(gate.dtype)
    return o @ lp["wo"].astype(gate.dtype)


def sequence(cfg, lp, h):
    """The KDA mixer over one whole sequence ``h`` [S, D] from an empty
    state, for the plain forward pass: a scan over chunks of the form the
    serving tiles run."""
    from deepspeed_tpu.ops.pallas.kda import kda_tiles

    s, r, kc = h.shape[0], cfg.chunk_size, cfg.conv_kernel
    qkv, g, beta, gate = inputs(cfg, h, lp)
    win = jnp.concatenate([jnp.zeros((kc - 1, qkv.shape[1]), qkv.dtype), qkv])
    q, k, v = qkv_split(cfg, conv(cfg, win, lp["conv_w"], s))
    pad = -s % r

    def chunks(t):
        return jnp.pad(t, ((0, pad),) + ((0, 0),) * (t.ndim - 1)).reshape(
            (-1, r) + t.shape[1:])

    def chunk(state, xs):
        y, state = kda_tiles(*(t[None] for t in xs), state[None],
                             jnp.zeros((1,), bool), cfg.sub_chunk)
        return state[0], y[0]

    state = jnp.zeros((cfg.kda_head_dim, cfg.kda_width), jnp.float32)
    _, y = lax.scan(chunk, state, tuple(map(chunks, (q, k, v, g, beta))))
    return mixer_out(cfg, y.reshape(-1, cfg.kda_width)[:s], gate, lp)


def ragged(cfg, h, lp, state, slot0, scratch, slots, positions,
           prefill_tiles):
    """The KDA mixer over the normed rows ``h`` [T, D] of a flat ragged token
    batch: ``state`` the slot leaves, layers and slots merged; this layer's
    slot ``s`` is row ``slot0 + s``; ``scratch`` the scratch slot. Returns
    ``(out [T, D], state)``."""
    from deepspeed_tpu.models.mamba2 import tile_rows
    from deepspeed_tpu.models.paged import (
        decode_windows,
        tile_windows,
        window_fold,
    )
    from deepspeed_tpu.ops.pallas.kda import kda_chunk, kda_decode

    kda, cw = state["kda"], state["conv"]
    vd = cfg.kda_head_dim

    qkv, g, beta, gate = inputs(cfg, h, lp)
    t = h.shape[0]
    n_dec = t if prefill_tiles is None else prefill_tiles[0]
    ys = []
    if n_dec:
        real = slots[:n_dec] != scratch
        fresh = real & (positions[:n_dec] == 0)
        rows = slots[:n_dec] + slot0
        # the window's arithmetic runs on the channels as the leaf folds
        # them: the rows come and go as whole tiles, the 128 new rows and
        # the weights are what is folded, the 128 results what is unfolded
        win, cw = decode_windows(cw, rows, qkv[:n_dec], fresh, real)
        qd, kd, vv = qkv_split(cfg, conv(
            cfg, win, window_fold(cw, lp["conv_w"]), 1).reshape(n_dec, -1))
        # a padding row neither decays nor feeds; position 0 starts from zeros
        a = jnp.where(fresh[:, None, None], 0.0, jnp.exp(
            jnp.where(real[:, None, None], g[:n_dec], 0.0)))
        bd = jnp.where(real[:, None], beta[:n_dec], 0.0)
        kda, y = kda_decode(
            kda, rows, *(x.transpose(0, 2, 1) for x in (a, kd, qd)),
            vv.reshape(n_dec, -1), jnp.repeat(bd, vd, axis=1))
        ys.append(y)
    if t > n_dec:
        _, ts, tp, tv, r = prefill_tiles
        sub = min(cfg.sub_chunk, r)
        if r % sub:
            raise ValueError(f"kda: a prefill tile of {r} rows is no "
                             f"multiple of the sub-chunk ({sub})")
        n_i = ts.shape[0]
        rows, rows_w, fresh, cont, write = tile_rows(ts, tp, slot0, scratch)
        win, cw = tile_windows(cw, rows, rows_w,
                               qkv[n_dec:].reshape(n_i, r, -1), cont, fresh,
                               write, tv)
        qt, kt, vt = qkv_split(cfg, conv(cfg, win, lp["conv_w"], r))
        valid = (jnp.arange(r)[None, :] < tv[:, None])[..., None]
        gt = jnp.where(valid[..., None],
                       g[n_dec:].reshape((n_i, r) + g.shape[1:]), 0.0)
        bt = jnp.where(valid, beta[n_dec:].reshape(n_i, r, -1), 0.0)
        # a head's channels are a lane block of the rows as they lie: the
        # chunk form reads them, and the slots' states in the leaf, in place
        kda, y = kda_chunk(
            kda, rows, rows_w, fresh, cont, write,
            *(x.reshape(n_i, r, -1) for x in (qt, kt, gt, vt)), bt, sub)
        ys.append(y.reshape(n_i * r, -1))
    y = ys[0] if len(ys) == 1 else jnp.concatenate(ys)
    return mixer_out(cfg, y, gate, lp), {"kda": kda, "conv": cw}
