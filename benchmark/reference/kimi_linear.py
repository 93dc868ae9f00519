"""Kimi-Linear as Kimi-Linear-48B-A3B-Instruct configures it, plain: the
published forward pass in straight ``jax.numpy``.

No kernels, no cache, no chunk form, no absorbed attention, nothing imported
from the program. Pre-norm RMSNorm (eps ``rms_norm_eps``), ``x += mixer(norm
(x))``, ``x += ffn(norm(x))``, a final RMSNorm and an untied head. A layer's
letter in ``cfg.layer_pattern`` names its two parts: ``D`` / ``K`` a KDA mixer,
``M`` / ``A`` MLA; ``D`` / ``A`` a dense SwiGLU, ``K`` / ``M`` the expert layer.

- KDA: ``[q | k | v] = silu(causal depthwise conv_4(h W_qkv))`` (three
  convolutions side by side, no bias); ``q``, ``k`` [H, K] L2-normalised over
  ``K`` (``x rsqrt(sum x^2 + 1e-6)``), ``q`` times ``K^-0.5``; ``g = -exp(A_log)
  softplus((h W_fa) W_fb + dt_bias)`` [H, K], a log-decay a CHANNEL; ``beta =
  sigmoid(h W_b)`` [H]. The recurrence as a ``lax.scan`` over the TOKENS, a
  head's ``S`` [K, V] from zeros: ``S <- exp(g_t)[:, None] S``; ``u = beta_t
  (v_t - S^T k_t)``; ``S <- S + k_t u^T``; ``o_t = S^T q_t``. ``out =
  (RMSNorm_head(o) sigmoid((h W_ga) W_gb)) W_o``, the norm over each head's
  ``V`` with a weight ``[V]``.
- MLA, not absorbed, and NOT rotated (``mla_use_nope``): ``q = h W_q`` a head;
  ``a = h W_kva``; ``c = RMSNorm(a[:, :lat])``; ``k_pe = a[:, lat:]``, one head
  shared by all, as projected; ``kv = c W_kvb`` a head into ``k_nope`` and
  ``v``; causal softmax in float32 of ``[q_nope, q_pe] . [k_nope, k_pe] (nope
  + rope)^-0.5``. With ``mla_use_nope`` off the two ``pe`` parts are rotated
  (half-split, ``rope_theta``).
- Experts: ``s = sigmoid(h_f32 W_r)`` over ALL the routed experts; the
  ``top_k`` largest of ``s + e_score_correction_bias``; weights ``s`` there,
  divided by their sum + 1e-20, times ``routed_scaling_factor``; ``y = sum_picks
  w_e SwiGLU_e(h) + SwiGLU_shared(h)``.

One rank's share: the parameter tree holds experts ``expert_rank x held ..`` of
the routed ones (and a share of the vocabulary's rows, which the tree's shapes
already are). The router's picks of experts that are not here add nothing,
here as in the program: the other ranks' parts.

The weights lie as the program's do: ``params["lead"]`` (a list of layers),
``params["period"]`` (one tree a position of the repeated period, leaves
stacked over the repeats: a ``scan`` here too, so that the period compiles
once) and ``params["tail"]``. Experts run one at a time, attention in blocks
of query rows, the head in blocks of columns.

Also the arithmetic of the model that metrics divide by.
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
    heads, kd, p, kc = (cfg.kda_heads, cfg.kda_head_dim, cfg.kda_width,
                        cfg.conv_kernel)
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


def _rope(x, positions, theta):
    """``x`` [S, H, D], rotated over ``D`` (half-split)."""
    half = x.shape[-1] // 2
    freqs = 1.0 / (theta ** (jnp.arange(half, dtype=jnp.float32) / half))
    ang = positions[:, None].astype(jnp.float32) * freqs
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :half].astype(jnp.float32), x[..., half:].astype(jnp.float32)
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin],
                           axis=-1).astype(x.dtype)


def _mla(cfg, h, lp, dtype):
    s = h.shape[0]
    heads, lat = cfg.num_heads, cfg.kv_lora_rank
    nope, rope, vd = cfg.qk_nope_head_dim, cfg.qk_rope_head_dim, cfg.v_head_dim
    q = (h @ lp["wq"].astype(dtype)).reshape(s, heads, nope + rope)
    a = h @ lp["wkv_a"].astype(dtype)
    c = _rms(a[:, :lat], lp["kv_norm"].astype(dtype), cfg.rms_norm_eps)
    k_pe = a[:, None, lat:]                                         # [S, 1, rope]
    if not cfg.mla_use_nope:
        pos = jnp.arange(s)
        k_pe = _rope(k_pe, pos, cfg.rope_theta)
        q = jnp.concatenate([q[..., :nope],
                             _rope(q[..., nope:], pos, cfg.rope_theta)], -1)
    kv = (c @ lp["wkv_b"].astype(dtype)).reshape(s, heads, nope + vd)
    k = jnp.concatenate([kv[..., :nope],
                         jnp.broadcast_to(k_pe, (s, heads, rope))], axis=-1)
    v = kv[..., nope:]
    kpos = jnp.arange(s)
    qb_rows = min(Q_BLOCK, s)

    def block(q0):
        qb = jax.lax.dynamic_slice_in_dim(q, q0, qb_rows, axis=0)
        scores = jnp.einsum("qhd,khd->hqk", qb, k) * jnp.asarray(
            (nope + rope) ** -0.5, dtype)
        ok = kpos[None, :] <= (q0 + jnp.arange(qb_rows))[:, None]
        p = jax.nn.softmax(jnp.where(ok[None], scores, -jnp.inf).astype(
            jnp.float32), axis=-1).astype(dtype)
        return jnp.einsum("hqk,khd->qhd", p, v)

    o = jax.lax.map(block, jnp.arange(0, s, qb_rows))
    return o.reshape(s, heads * vd) @ lp["wo"].astype(dtype)


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
    x = x + (_kda if kind in "DK" else _mla)(cfg, h, lp["mix"], dtype)
    h = _rms(x, lp["mlp_norm"].astype(dtype), cfg.rms_norm_eps)
    ffn = lp["ffn"]
    if kind in "DA":
        return x + _swiglu(h, ffn["w_gate"], ffn["w_up"], ffn["w_down"], dtype)
    return x + _moe(cfg, h, ffn, dtype)


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


def _count(cfg, kinds: str) -> int:
    return sum(cfg.layer_pattern.count(c) for c in kinds)


def kda_params(cfg) -> int:
    """One KDA mixer: ``W_q/k/v``, ``W_o``, the two low-rank pairs (the
    decay's and the output gate's), ``W_b``, the three convolutions,
    ``A_log``, ``dt_bias``, the head norm."""
    d, p, kd, h = (cfg.hidden_size, cfg.kda_width, cfg.kda_head_dim,
                   cfg.kda_heads)
    return (4 * d * p + 2 * (d * kd + kd * p) + d * h
            + cfg.conv_kernel * 3 * p + h + p + kd)


def mla_params(cfg) -> int:
    d, h, lat = cfg.hidden_size, cfg.num_heads, cfg.kv_lora_rank
    return (d * h * (cfg.qk_nope_head_dim + cfg.qk_rope_head_dim)
            + d * (lat + cfg.qk_rope_head_dim) + lat
            + lat * h * (cfg.qk_nope_head_dim + cfg.v_head_dim)
            + h * cfg.v_head_dim * d)


def expert_params(cfg) -> int:
    return 3 * cfg.hidden_size * cfg.moe_intermediate_size


def _layer_params(cfg, kind: str, experts: float) -> float:
    """One layer's parameters with ``experts`` routed experts counted: the
    mixer, the two norms, then the dense FFN or the shared expert(s), the
    router, its selection bias and the routed experts."""
    d = cfg.hidden_size
    n = (kda_params(cfg) if kind in "DK" else mla_params(cfg)) + 2 * d
    if kind in "DA":
        return n + 3 * d * cfg.intermediate_size
    return (n + d * cfg.num_experts + cfg.num_experts
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
    """Bytes of cache a step must read per context token: ONE latent row of
    ``kv_lora_rank + qk_rope_head_dim`` values (key and value both) in each
    MLA layer; the KDA layers have no cache that grows."""
    return ((cfg.kv_lora_rank + cfg.qk_rope_head_dim) * bytes_per_value
            * _count(cfg, "MA"))


def attn_flops_per_pair(cfg) -> int:
    """FLOPs of one query x key pair in the absorbed form the program runs
    (scores over ``lat + rope`` lanes, values over ``lat`` lanes a head), the
    MLA layers alone."""
    return (2 * cfg.num_heads * (2 * cfg.kv_lora_rank + cfg.qk_rope_head_dim)
            * _count(cfg, "MA"))


def state_bytes_per_slot(cfg, bytes_per_value: int = 2) -> int:
    """Bytes of recurrent state one sequence holds, all KDA layers: ``S``
    [H, K, V] in float32 and the last ``kernel - 1`` rows of the three
    convolutions' inputs; whatever its length."""
    return _count(cfg, "DK") * (
        4 * cfg.kda_heads * cfg.kda_head_dim ** 2
        + (cfg.conv_kernel - 1) * 3 * cfg.kda_width * bytes_per_value)


def ssm_flops_per_token(cfg) -> int:
    """FLOPs of the recurrence as written, a token, all KDA layers: decay
    the state (1 an element), read it for the delta (2), feed it (2), read
    it for the output (2)."""
    return 7 * cfg.kda_heads * cfg.kda_head_dim ** 2 * _count(cfg, "DK")
