"""MiniCPM-SALA as openbmb/MiniCPM-SALA configures it (``model_type:
minicpm_sala``), plain: the forward pass in straight ``jax.numpy``.

No kernels, no cache, no chunked form, nothing imported from the program; the
queries of a sparse layer go ``Q_BLOCK`` rows at a time (a ``[32, Q_BLOCK,
32768]`` float32 score block is 0.5 GB at 128 rows) and a Lightning layer is
its recurrence, token by token.

With ``L = depth_layers`` (the PUBLISHED depth, 32) and ``r = scale_depth /
sqrt(L)``:

- ``x = scale_emb E[id]``.
- a layer: ``x += r Mixer(RMSNorm(x))``, then ``x += r W_d(silu(h W_g) * h
  W_u)``, ``h = RMSNorm(x)`` (eps ``rms_norm_eps``); ``mixer_types`` says which
  mixer.
- ``logits = RMSNorm(x) / (hidden_size / dim_model_base) W_head``.
- ``lightning-attn``: ``q, k, v = h W_q, h W_k, h W_v`` as ``lightning_nh``
  heads of ``lightning_head_dim``; RMSNorm over a head's lanes on q and k, each
  with its weight; RoPE (theta ``rope_theta``, the whole head, the halves
  paired) on both; ``S_t = exp(-s) S_{t-1} + k_t^T v_t``, ``o_t = (q_t /
  sqrt(d)) S_t``, ``s`` the layer's row of ``lightning_decay`` (a head's
  constant), the state float32; RMSNorm over each head's ``o``; ``out = (o *
  sigmoid(h W_z)) W_o``.
- ``minicpm4`` (InfLLM-V2): ``q`` ``num_heads`` heads, ``k, v``
  ``num_kv_heads`` heads, RMSNorm a head on q and k, no positions. A query at
  ``t`` of K/V head ``g`` (its ``rep`` query heads):

  - ``t + 1 <= dense_len``: every key ``s <= t``.
  - else: compressed keys ``K_g[j] = mean k_g[S j : S j + K]`` (``K``
    ``kernel_size``, ``S`` ``kernel_stride``), visible once ``S j + K - 1 <=
    t``; ``P_g[t, j] = sum_{h in g} softmax_j(q_h[t] . K_g[j] / sqrt(d))``
    over the visible ``j``, float32; block ``b`` (``block_size`` ``B`` tokens)
    scores ``max P_g[t, j]`` over the visible ``j`` whose kernel overlaps it
    (``S j + K > B b`` and ``S j < B b + B``; -inf with none); blocks ``b <
    init_blocks`` and ``b_t - window_size / B < b <= b_t`` (``b_t = t // B``)
    score +inf; the query keeps the ``topk`` best blocks ``<= b_t``, the
    lowest first among equals (a stable sort), and every key ``s <= t`` inside
    them.

  Softmax attention over the kept keys in float32; the heads' output times
  ``sigmoid(h W_z)``, then ``W_o``.

Departures from the published code, each for a stated reason: the switch at
``dense_len`` is by the query's position (the published code switches by a
call's length, which is no property of a row); stage one's softmax is exact
(the published CUDA code approximates its normaliser from a coarser pooling:
an implementation's saving); ``mup_denominator`` enters no equation.

Also the arithmetic of the model that metrics divide by.
"""

from __future__ import annotations

from itertools import groupby

import jax
import jax.numpy as jnp

Q_BLOCK = 128
SPARSE, LIGHTNING = "minicpm4", "lightning-attn"


def _rms(x, w, eps):
    return x * jax.lax.rsqrt((x * x).mean(-1, keepdims=True) + eps) * w


def _rope(x, theta):
    """``x`` [S, H, d] at positions 0 .. S - 1."""
    half = x.shape[-1] // 2
    freqs = 1.0 / theta ** (jnp.arange(half, dtype=jnp.float32) / half)
    ang = jnp.arange(x.shape[0], dtype=jnp.float32)[:, None] * freqs
    cos, sin = jnp.cos(ang)[:, None], jnp.sin(ang)[:, None]
    x1 = x[..., :half].astype(jnp.float32)
    x2 = x[..., half:].astype(jnp.float32)
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin],
                           -1).astype(x.dtype)


def recurrence(q, k, v, lam):
    """``q, k, v`` [S, H, d], ``lam`` [H] -> ``o`` [S, H, d] float32: ``S_t =
    lam S_{t-1} + k_t^T v_t``, ``o_t = q_t S_t``, token by token from zeros,
    the state float32."""
    nh, d = q.shape[1:]

    def token(state, xs):
        q_t, k_t, v_t = (a.astype(jnp.float32) for a in xs)
        state = lam[:, None, None] * state + k_t[:, :, None] * v_t[:, None, :]
        return state, jnp.einsum("hn,hnp->hp", q_t, state)

    return jax.lax.scan(token, jnp.zeros((nh, d, d), jnp.float32),
                        (q, k, v))[1]


def _lightning(cfg, h, lp, decay, dtype):
    s = h.shape[0]
    nh, d = cfg.lightning_nh, cfg.lightning_head_dim
    eps = cfg.rms_norm_eps
    q = _rms((h @ lp["wq"].astype(dtype)).reshape(s, nh, d),
             lp["q_norm"].astype(dtype), eps)
    k = _rms((h @ lp["wk"].astype(dtype)).reshape(s, nh, d),
             lp["k_norm"].astype(dtype), eps)
    v = (h @ lp["wv"].astype(dtype)).reshape(s, nh, d)
    q = _rope(q, cfg.rope_theta) * jnp.asarray(d ** -0.5, dtype)
    k = _rope(k, cfg.rope_theta)
    o = recurrence(q, k, v, jnp.exp(-decay.astype(jnp.float32)))
    o = _rms(o.astype(dtype), lp["o_norm"].astype(dtype), eps)
    gate = jax.nn.sigmoid(h @ lp["w_z"].astype(dtype))
    return (o.reshape(s, nh * d) * gate) @ lp["wo"].astype(dtype)


def compressed_keys(cfg, k):
    """``k`` [S, Hkv, d] -> [J, Hkv, d], ``J = ceil(S / stride)``: key ``j``
    the mean of rows ``stride j .. stride j + kernel - 1`` (zeros past the
    sequence; such a key is visible to no query)."""
    n = -(-k.shape[0] // cfg.kernel_stride)
    k = jnp.concatenate([k, jnp.zeros((cfg.kernel_size,) + k.shape[1:],
                                      k.dtype)])
    idx = (jnp.arange(n)[:, None] * cfg.kernel_stride
           + jnp.arange(cfg.kernel_size))
    return k[idx].astype(jnp.float32).mean(1).astype(k.dtype)


def kept_blocks(cfg, q, ck, t, n_blocks):
    """``q`` [Q, Hkv, rep, d] the queries at positions ``t`` [Q], ``ck`` [J,
    Hkv, d] -> [Q, Hkv, n_blocks] bool: the blocks each query's groups
    keep."""
    bsz, ksz, stride = cfg.block_size, cfg.kernel_size, cfg.kernel_stride
    f32 = jnp.float32
    j = jnp.arange(ck.shape[0])
    visible = stride * j[None, :] + ksz - 1 <= t[:, None]           # [Q, J]
    s = jnp.einsum("tgqd,jgd->tgqj", q.astype(f32), ck.astype(f32)
                   ) * q.shape[-1] ** -0.5
    s = jnp.where(visible[:, None, None], s, -jnp.inf)
    m = s.max(-1, keepdims=True)
    e = jnp.where(visible[:, None, None],
                  jnp.exp(s - jnp.where(jnp.isfinite(m), m, 0.0)), 0.0)
    p = (e / jnp.maximum(e.sum(-1, keepdims=True), 1e-30)).sum(2)  # [Q, Hkv, J]
    b = jnp.arange(n_blocks)
    overlaps = (stride * j[None, :] + ksz > bsz * b[:, None]) \
        & (stride * j[None, :] < bsz * (b[:, None] + 1))            # [NB, J]
    score = jnp.where(overlaps[None, None] & visible[:, None, None],
                      p[:, :, None, :], -jnp.inf).max(-1)       # [Q, Hkv, NB]
    own = (t // bsz)[:, None, None]
    forced = (b < cfg.init_blocks) | (
        (b <= own) & (b > own - cfg.window_size // bsz))
    score = jnp.where(forced, jnp.inf, score)
    score = jnp.where(b <= own, score, -jnp.inf)
    order = jnp.argsort(-score, axis=-1, stable=True)
    rank = jnp.argsort(order, axis=-1, stable=True)
    keep = (rank < cfg.topk) & (b <= own)
    dense = (t + 1 <= cfg.dense_len)[:, None, None]
    return jnp.where(dense, b <= own, keep)


def _sparse(cfg, h, lp, dtype):
    s = h.shape[0]
    hq, hkv, d = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    rep, eps = hq // hkv, cfg.rms_norm_eps
    q = _rms((h @ lp["wq"].astype(dtype)).reshape(s, hkv, rep, d),
             lp["q_norm"].astype(dtype), eps)
    k = _rms((h @ lp["wk"].astype(dtype)).reshape(s, hkv, d),
             lp["k_norm"].astype(dtype), eps)
    v = (h @ lp["wv"].astype(dtype)).reshape(s, hkv, d)
    ck = compressed_keys(cfg, k)
    n_blocks = -(-s // cfg.block_size)
    kpos = jnp.arange(s)
    qb_rows = min(Q_BLOCK, s)

    def block(q0):
        qb = jax.lax.dynamic_slice_in_dim(q, q0, qb_rows, axis=0)
        t = q0 + jnp.arange(qb_rows)
        keep = kept_blocks(cfg, qb, ck, t, n_blocks)            # [Q, Hkv, NB]
        seen = jnp.repeat(keep, cfg.block_size, axis=-1)[..., :s] \
            & (kpos[None, :] <= t[:, None])[:, None]
        scores = jnp.einsum("tgqd,sgd->tgqs", qb, k) * jnp.asarray(
            d ** -0.5, dtype)
        p = jax.nn.softmax(jnp.where(seen[:, :, None], scores.astype(
            jnp.float32), -jnp.inf), axis=-1).astype(dtype)
        return jnp.einsum("tgqs,sgd->tgqd", p, v)

    o = jax.lax.map(block, jnp.arange(0, s, qb_rows))
    gate = jax.nn.sigmoid(h @ lp["w_z"].astype(dtype))
    return (o.reshape(s, hq * d) * gate) @ lp["wo"].astype(dtype)


def _layer(cfg, kind, x, lp, decay, dtype):
    r = jnp.asarray(cfg.scale_depth / cfg.depth_layers ** 0.5, dtype)
    h = _rms(x, lp["norm"].astype(dtype), cfg.rms_norm_eps)
    mix = _sparse(cfg, h, lp["mix"], dtype) if kind == SPARSE else \
        _lightning(cfg, h, lp["mix"], decay, dtype)
    x = x + mix * r
    h = _rms(x, lp["ffn_norm"].astype(dtype), cfg.rms_norm_eps)
    f = lp["ffn"]
    y = (jax.nn.silu(h @ f["w_gate"].astype(dtype))
         * (h @ f["w_up"].astype(dtype))) @ f["w_down"].astype(dtype)
    return x + y * r


def forward(cfg, params, ids, dtype=jnp.float32):
    """``ids`` [S] (S a multiple of ``Q_BLOCK``, or under it) -> logits [S,
    vocab rows held]."""
    x = params["embed"][ids].astype(dtype) * jnp.asarray(cfg.scale_emb, dtype)
    table = jnp.asarray(cfg.lightning_decay, jnp.float32)
    first = 0
    for (kind, group), stack in zip(groupby(cfg.mixer_types), params["runs"]):
        n = len(list(group))
        x, _ = jax.lax.scan(
            lambda x, xs, kind=kind: (
                _layer(cfg, kind, x, xs[0], xs[1], dtype), None),
            x, (stack, table[first:first + n]))
        first += n
    x = _rms(x, params["final_norm"].astype(dtype), cfg.rms_norm_eps)
    x = x / jnp.asarray(cfg.hidden_size / cfg.dim_model_base, dtype)
    return x @ params["lm_head"].astype(dtype)


# ------------------------------------------------------- model arithmetic
def _count(cfg, kind: str) -> int:
    return list(cfg.mixer_types).count(kind)


def layer_params(cfg, kind: str) -> int:
    """One layer's parameters: ``W_q``, ``W_z``, ``W_o`` at the query heads'
    width, ``W_k``, ``W_v`` at the key heads', the head norms (q, k; a
    Lightning layer's output norm too), the gated MLP, the two norms."""
    d = cfg.hidden_size
    if kind == SPARSE:
        wide = cfg.num_heads * cfg.head_dim
        narrow = cfg.num_kv_heads * cfg.head_dim
        norms = 2 * cfg.head_dim
    else:
        wide = narrow = cfg.lightning_nh * cfg.lightning_head_dim
        norms = 3 * cfg.lightning_head_dim
    return (3 * d * wide + 2 * d * narrow + norms
            + 3 * d * cfg.intermediate_size + 2 * d)


def num_params(cfg) -> int:
    """The layers, the embedding and the untied head's held rows, the final
    norm."""
    return (sum(layer_params(cfg, kind) for kind in cfg.mixer_types)
            + 2 * cfg.vocab_size * cfg.hidden_size + cfg.hidden_size)


def active_params(cfg) -> int:
    """Parameters a token's forward pass multiplies by: all but the embedding
    (a lookup)."""
    return num_params(cfg) - cfg.vocab_size * cfg.hidden_size


def weight_bytes(cfg, bytes_per_param: int = 2) -> int:
    """Bytes a step must read: every layer and the head (the embedding's rows
    are a gather of the step's tokens)."""
    return active_params(cfg) * bytes_per_param


def kv_bytes_per_token(cfg, bytes_per_value: int = 2) -> int:
    """Bytes of K and V a step must read per KEPT context token, the sparse
    layers (1,024 B a layer as published)."""
    return (2 * cfg.num_kv_heads * cfg.head_dim * bytes_per_value
            * _count(cfg, SPARSE))


def attn_flops_per_pair(cfg) -> int:
    """FLOPs of one query x kept-key pair (QK^T and PV), the sparse layers
    (16,384 a layer)."""
    return 4 * cfg.num_heads * cfg.head_dim * _count(cfg, SPARSE)


def cmp_bytes_per_key(cfg, bytes_per_value: int = 2) -> int:
    """Bytes of one compressed key, the sparse layers."""
    return cfg.num_kv_heads * cfg.head_dim * bytes_per_value * _count(cfg, SPARSE)


def cmp_flops_per_pair(cfg) -> int:
    """FLOPs of one query x compressed-key score, the sparse layers."""
    return 2 * cfg.num_heads * cfg.head_dim * _count(cfg, SPARSE)


def state_bytes_per_slot(cfg, bytes_per_value: int = 2) -> int:
    """Bytes of recurrent state one sequence holds, the Lightning layers: a
    head's ``[d, d]`` float32 (2 MB a layer as published)."""
    del bytes_per_value
    return (4 * cfg.lightning_nh * cfg.lightning_head_dim ** 2
            * _count(cfg, LIGHTNING))


def ssm_flops_per_token(cfg) -> int:
    """FLOPs of the recurrence as written, a token, the Lightning layers:
    decay and feed the state (3 an element), read it (2)."""
    return (5 * cfg.lightning_nh * cfg.lightning_head_dim ** 2
            * _count(cfg, LIGHTNING))
