"""Solar-Open2 as Solar-Open2-250B configures it, plain: the forward pass in
straight ``jax.numpy``.

No kernels, no cache, no chunk form, nothing imported from the program.
Pre-norm RMSNorm (eps ``rms_norm_eps``), ``x += mixer(norm(x))``, ``x +=
moe(norm(x))``, a final RMSNorm and an untied head. A layer's letter in
``cfg.layer_pattern`` names its mixer: ``G`` gated grouped-query attention
without positions, ``K`` KDA; every layer ends in the expert layer.

- ``K``, KDA: ``[q | k | v] = silu(causal depthwise conv_4(h W_qkv))`` (three
  convolutions side by side, no bias); ``q``, ``k`` [H, K] L2-normalised over
  ``K`` (``x rsqrt(sum x^2 + 1e-6)``), ``q`` times ``K^-0.5``; ``g = -exp(A_log)
  softplus((h W_fa) W_fb + dt_bias)`` [H, K], a log-decay a CHANNEL; ``beta =
  2 sigmoid(h W_b)`` [H] with ``kda_allow_neg_eigval`` (``sigmoid`` alone
  without). The recurrence as a ``lax.scan`` over the TOKENS, a head's ``S``
  [K, V] from zeros: ``S <- exp(g_t)[:, None] S``; ``u = beta_t (v_t - S^T
  k_t)``; ``S <- S + k_t u^T``; ``o_t = S^T q_t``. ``out = (RMSNorm_head(o)
  sigmoid((h W_ga) W_gb)) W_o``, the norm over each head's ``V`` with a
  weight ``[V]``.
- ``G``: ``q = h W_q`` [64 heads of 128], ``k = h W_k``, ``v = h W_v`` [8
  heads], a K/V head shared by 8 query heads; causal softmax in float32 of ``q
  . k head_dim^-0.5``, NO rotation; ``out = (o sigmoid(h W_g)) W_o`` with
  ``use_gqa_gate`` (``o W_o`` without). Computed a block of ``Q_BLOCK`` query
  rows at a time, so that 8,192 x 8,192 scores fit.
- Experts: ``s = sigmoid(h_f32 W_r)`` over ALL the routed experts; the
  ``top_k`` largest of ``s + e_score_correction_bias``; weights ``s`` there,
  divided by their sum + 1e-20 (``norm_topk_prob``), times
  ``routed_scaling_factor``; ``y = sum_picks w_e SwiGLU_e(h) +
  SwiGLU_shared(h)``.

One rank's share: the parameter tree holds experts ``expert_rank x held ..`` of
the routed ones (and a share of the vocabulary's rows, which the tree's shapes
already are). The router's picks of experts that are not here add nothing,
here as in the program: the other ranks' parts.

The weights lie as the program's do: ``params["lead"]`` (a list of layers),
``params["period"]`` (one tree a position of the repeated period, leaves
stacked over the repeats: a ``scan`` here too, so that the period compiles
once) and ``params["tail"]``. Experts run one at a time, attention in blocks
of query rows, the head in blocks of columns.

Also the arithmetic of the model that metrics divide by, the chunk kernel's
(``kda_chunk_flops_per_tile``, ``kda_chunk_io_bytes_per_token``) included.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

Q_BLOCK = 512
HEAD_BLOCK = 4096


def _rms(x, w, eps):
    return x * jax.lax.rsqrt((x * x).mean(-1, keepdims=True) + eps) * w


def _swiglu(h, wg, wu, wd, dtype):
    return (jax.nn.silu(h @ wg.astype(dtype)) * (h @ wu.astype(dtype))
            ) @ wd.astype(dtype)


def _kda(cfg, h, lp, dtype):
    s = h.shape[0]
    heads, kd, kc = cfg.kda_heads, cfg.kda_head_dim, cfg.conv_kernel
    p = heads * kd
    qkv = h @ lp["w_qkv"].astype(dtype)
    # causal depthwise convolution: row t sees rows t - kernel + 1 .. t
    padded = jnp.concatenate([jnp.zeros((kc - 1, 3 * p), dtype), qkv])
    qkv = jax.nn.silu(sum(padded[j:j + s] * lp["conv_w"][j].astype(dtype)
                          for j in range(kc)))
    q, k, v = (qkv[:, j * p:(j + 1) * p].reshape(s, heads, kd) for j in range(3))

    def unit(x):
        return x * jax.lax.rsqrt((x * x).sum(-1, keepdims=True)
                                 + jnp.asarray(1e-6, dtype))

    q, k = unit(q) * jnp.asarray(kd ** -0.5, dtype), unit(k)
    f = (h @ lp["w_fa"].astype(dtype)) @ lp["w_fb"].astype(dtype)
    g = (-jnp.exp(lp["a_log"].astype(dtype))[:, None]
         * jax.nn.softplus(f + lp["dt_bias"].astype(dtype)).reshape(s, heads, kd))
    beta = jax.nn.sigmoid(h @ lp["w_b"].astype(dtype))              # [S, H]
    if cfg.kda_allow_neg_eigval:
        beta = beta * jnp.asarray(2.0, dtype)

    def token(state, xs):                                           # [H, K, V]
        q_t, k_t, v_t, g_t, b_t = xs
        state = jnp.exp(g_t)[:, :, None] * state
        u = b_t[:, None] * (v_t - jnp.einsum("hkv,hk->hv", state, k_t))
        state = state + k_t[:, :, None] * u[:, None, :]
        return state, jnp.einsum("hkv,hk->hv", state, q_t)

    _, o = jax.lax.scan(token, jnp.zeros((heads, kd, kd), dtype),
                        (q, k, v, g, beta))
    o = (o * jax.lax.rsqrt((o * o).mean(-1, keepdims=True) + cfg.rms_norm_eps)
         * lp["o_norm"].astype(dtype)).reshape(s, p)
    gate = jax.nn.sigmoid((h @ lp["w_ga"].astype(dtype)) @ lp["w_gb"].astype(dtype))
    return (o * gate) @ lp["wo"].astype(dtype)


def _gqa(cfg, h, lp, dtype):
    s = h.shape[0]
    heads, kv, hd = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    q = (h @ lp["wq"].astype(dtype)).reshape(s, heads, hd)
    k = jnp.repeat((h @ lp["wk"].astype(dtype)).reshape(s, kv, hd),
                   heads // kv, axis=1)
    v = jnp.repeat((h @ lp["wv"].astype(dtype)).reshape(s, kv, hd),
                   heads // kv, axis=1)
    kpos = jnp.arange(s)
    qb_rows = min(Q_BLOCK, s)

    def block(q0):
        qb = jax.lax.dynamic_slice_in_dim(q, q0, qb_rows, axis=0)
        scores = jnp.einsum("qhd,khd->hqk", qb, k) * jnp.asarray(
            hd ** -0.5, dtype)
        ok = kpos[None, :] <= (q0 + jnp.arange(qb_rows))[:, None]
        p = jax.nn.softmax(jnp.where(ok[None], scores, -jnp.inf).astype(
            jnp.float32), axis=-1).astype(dtype)
        return jnp.einsum("hqk,khd->qhd", p, v)

    o = jax.lax.map(block, jnp.arange(0, s, qb_rows)).reshape(s, heads * hd)
    if cfg.use_gqa_gate:
        o = o * jax.nn.sigmoid(h @ lp["w_g"].astype(dtype))
    return o @ lp["wo"].astype(dtype)


def _moe(cfg, h, lp, dtype):
    scores = jax.nn.sigmoid(h.astype(jnp.float32)
                            @ lp["router"].astype(jnp.float32))
    _, top_i = jax.lax.top_k(
        scores + lp["router_bias"].astype(jnp.float32), cfg.top_k)
    top_w = jnp.take_along_axis(scores, top_i, axis=-1)
    if cfg.norm_topk_prob:
        top_w = top_w / (top_w.sum(-1, keepdims=True) + 1e-20)
    top_w = top_w * cfg.routed_scaling_factor
    # combine[t, e]: the weight where routed expert e is among t's picks
    combine = (jax.nn.one_hot(top_i, cfg.num_experts, dtype=jnp.float32)
               * top_w[..., None]).sum(1)
    held = lp["w_up"].shape[0]
    first = cfg.expert_rank * held

    def expert(acc, we):
        wg, wu, wd, c = we
        return acc + _swiglu(h, wg, wu, wd, dtype) * c[:, None].astype(dtype), None

    routed, _ = jax.lax.scan(
        expert, jnp.zeros_like(h),
        (lp["w_gate"], lp["w_up"], lp["w_down"],
         combine[:, first:first + held].T))
    return routed + _swiglu(h, lp["ws_gate"], lp["ws_up"], lp["ws_down"], dtype)


def _layer(cfg, kind, x, lp, dtype):
    h = _rms(x, lp["attn_norm"].astype(dtype), cfg.rms_norm_eps)
    x = x + (_kda if kind == "K" else _gqa)(cfg, h, lp["mix"], dtype)
    h = _rms(x, lp["mlp_norm"].astype(dtype), cfg.rms_norm_eps)
    return x + _moe(cfg, h, lp["ffn"], dtype)


def forward(cfg, params, ids, dtype=jnp.float32):
    """``ids`` [S] (S a multiple of ``Q_BLOCK``, or under it) -> logits [S,
    vocab rows held]."""
    pattern = cfg.layer_pattern
    n_lead, n_tail = len(params["lead"]), len(params["tail"])
    per = len(params["period"])
    lead, tail = pattern[:n_lead], pattern[len(pattern) - n_tail:]
    period = pattern[n_lead:n_lead + per]
    x = params["embed"][ids].astype(dtype)
    for kind, lp in zip(lead, params["lead"]):
        x = _layer(cfg, kind, x, lp, dtype)

    def one_period(x, lps):
        for kind, lp in zip(period, lps):
            x = _layer(cfg, kind, x, lp, dtype)
        return x, None

    x, _ = jax.lax.scan(one_period, x, tuple(params["period"]))
    for kind, lp in zip(tail, params["tail"]):
        x = _layer(cfg, kind, x, lp, dtype)
    x = _rms(x, params["final_norm"].astype(dtype), cfg.rms_norm_eps)
    return _head(x, params["lm_head"], dtype)


def _head(x, w, dtype):
    """``x @ w`` in ``dtype``, ``HEAD_BLOCK`` columns at a time."""
    vocab = w.shape[1]
    if vocab % HEAD_BLOCK:
        return x @ w.astype(dtype)

    def block(i, out):
        wb = jax.lax.dynamic_slice_in_dim(w, i * HEAD_BLOCK, HEAD_BLOCK, axis=1)
        return jax.lax.dynamic_update_slice_in_dim(
            out, x @ wb.astype(dtype), i * HEAD_BLOCK, axis=1)

    return jax.lax.fori_loop(0, vocab // HEAD_BLOCK, block,
                             jnp.zeros((x.shape[0], vocab), dtype))


# ------------------------------------------------------- model arithmetic
def _held(cfg) -> int:
    return cfg.num_experts if cfg.experts_held is None else cfg.experts_held


def _count(cfg, kind: str) -> int:
    return cfg.layer_pattern.count(kind)


def kda_params(cfg) -> int:
    """One KDA mixer: ``W_q/k/v``, ``W_o``, the two low-rank pairs (the
    decay's and the output gate's), ``W_b``, the three convolutions,
    ``A_log``, ``dt_bias``, the head norm."""
    d, kd, h = cfg.hidden_size, cfg.kda_head_dim, cfg.kda_heads
    p = h * kd
    return (4 * d * p + 2 * (d * kd + kd * p) + d * h
            + cfg.conv_kernel * 3 * p + h + p + kd)


def gqa_params(cfg) -> int:
    """One gated grouped-query mixer: ``W_q``, ``W_o`` and (with
    ``use_gqa_gate``) ``W_g`` at the query heads' width, ``W_k``, ``W_v`` at
    the K/V heads'."""
    d, hd = cfg.hidden_size, cfg.head_dim
    return (d * hd * cfg.num_heads * (3 if cfg.use_gqa_gate else 2)
            + 2 * d * hd * cfg.num_kv_heads)


def expert_params(cfg) -> int:
    return 3 * cfg.hidden_size * cfg.moe_intermediate_size


def _layer_params(cfg, kind: str, experts: float) -> float:
    """One layer's parameters with ``experts`` routed experts counted: the
    mixer, the two norms, the shared expert(s), the router, its selection
    bias and the routed experts."""
    d = cfg.hidden_size
    return ((kda_params(cfg) if kind == "K" else gqa_params(cfg)) + 2 * d
            + d * cfg.num_experts + cfg.num_experts
            + (experts + cfg.num_shared_experts) * expert_params(cfg))


def num_params(cfg) -> int:
    """Parameters that live on this rank: its share of the routed experts
    and of the vocabulary, everything else of every layer."""
    d = cfg.hidden_size
    return int(2 * cfg.vocab_size * d + d + sum(
        _layer_params(cfg, kind, _held(cfg)) for kind in cfg.layer_pattern))


def active_params(cfg) -> float:
    """Parameters a token's forward pass multiplies by HERE: everything
    outside the routed experts, and of them the ``top_k x held /
    num_experts`` a token picks on this rank on average; the embedding is a
    lookup."""
    d = cfg.hidden_size
    return (cfg.vocab_size * d + d + sum(
        _layer_params(cfg, kind, cfg.top_k * _held(cfg) / cfg.num_experts)
        for kind in cfg.layer_pattern))


def weight_bytes(cfg, bytes_per_param: int = 2) -> int:
    """Bytes a decode step must read: every held expert's weights, whatever
    the routing of a batch of more than a few tokens; the embedding is a
    lookup."""
    return (num_params(cfg) - cfg.vocab_size * cfg.hidden_size) * bytes_per_param


def kv_bytes_per_token(cfg, bytes_per_value: int = 2) -> int:
    """Bytes of cache a step must read per context token: K and V of the
    K/V heads in each ``G`` layer; the KDA layers have no cache that grows."""
    return (2 * cfg.num_kv_heads * cfg.head_dim * bytes_per_value
            * _count(cfg, "G"))


def attn_flops_per_pair(cfg) -> int:
    """FLOPs of one query x key pair (scores and values, every query head),
    the ``G`` layers alone."""
    return 4 * cfg.num_heads * cfg.head_dim * _count(cfg, "G")


def state_bytes_per_slot(cfg, bytes_per_value: int = 2) -> int:
    """Bytes of recurrent state one sequence holds, all KDA layers: ``S``
    [H, K, V] in float32 and the last ``kernel - 1`` rows of the three
    convolutions' inputs; whatever its length."""
    p = cfg.kda_heads * cfg.kda_head_dim
    return _count(cfg, "K") * (
        4 * cfg.kda_heads * cfg.kda_head_dim ** 2
        + (cfg.conv_kernel - 1) * 3 * p * bytes_per_value)


def ssm_flops_per_token(cfg) -> int:
    """FLOPs of the recurrence as written, a token, all KDA layers: decay
    the state (1 an element), read it for the delta (2), feed it (2), read
    it for the output (2)."""
    return 7 * cfg.kda_heads * cfg.kda_head_dim ** 2 * _count(cfg, "K")


def kda_state_bytes_per_slot(cfg) -> int:
    """Bytes of ``S`` alone one sequence holds, all KDA layers: what the
    chunk form reads and writes once a prefilling slot (the convolutions'
    rows go through the window leaf, outside it)."""
    return 4 * cfg.kda_heads * cfg.kda_head_dim ** 2 * _count(cfg, "K")


def kda_chunk_flops_per_tile(cfg, rows: int) -> int:
    """FLOPs of the chunk form's products over ONE tile of ``rows`` rows, all
    KDA layers, each product counted once (an implementation in float32 at
    ``Precision.HIGHEST`` makes several passes of each: they are not
    counted), a head: the tile's rows against the state it starts from, ``[K
    e^G | Q e^G] S0`` (2 x 2 R K V); the pairwise products that make ``A``
    and ``B``, ``K K^T`` and ``Q K^T`` under their decays (2 x 2 R R K, the
    causal half not taken off: a blocked form computes the blocks whole);
    ``A``'s and ``B``'s products with ``U`` in the substitution and the
    readings (2 x 2 R R V); the state's update ``K_end^T U`` (2 R K V)."""
    h, k = cfg.kda_heads, cfg.kda_head_dim
    return h * (6 * rows * k * k + 8 * rows * rows * k) * _count(cfg, "K")


def kda_chunk_io_bytes_per_token(cfg) -> int:
    """Bytes a prompt token moves through the chunk form, all KDA layers, as
    a layer hands them to it: ``q``, ``k``, ``v`` and the log-decay ``g`` in
    (float32, ``[H x K]`` each: the convolution and the norms leave
    float32), ``beta`` in (float32, ``[H]``), the reading ``y`` out (float32,
    ``[H x V]``)."""
    p = cfg.kda_heads * cfg.kda_head_dim
    return 4 * (5 * p + cfg.kda_heads) * _count(cfg, "K")
