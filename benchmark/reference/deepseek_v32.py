"""DeepSeek-V3.2-Exp, plain: the forward pass in straight ``jax.numpy``.

No kernels, no cache, no absorbed attention, nothing imported from the
program. Per layer (pre-norm RMSNorm, a residual after each half), dense
layers too (``h`` the normed input):

- Query: ``cq = RMSNorm(h W_qa)``; ``q = cq W_qb`` split per head into
  ``q_nope`` and ``q_rope``. Latent row: ``a = h W_kva``; ``c =
  RMSNorm(a[:, :lat])``; ``k_rope`` = the rest, ONE head shared by all; ``kv =
  c W_kvb`` split per head into ``k_nope`` and ``v`` (MLA not absorbed).
- Indexer: ``qI = cq W_Iq`` per index head, ``kI = LayerNorm(h W_Ik)`` (eps
  1e-6, weight and bias), rotary positions on the first ``qk_rope_head_dim``
  lanes of both; ``wI = h W_Iw * HI^-0.5 * DI^-0.5``; ``I[t, s] = sum_j wI[t, j]
  relu(qI[t, j] . kI[s])``, float32, a dense ``[S, S]`` a layer (in blocks of
  query rows).
- Selection: for query ``t`` the ``index_topk`` positions ``s <= t`` with the
  largest ``I[t, s]`` (among equals the lowest position first, ``lax.top_k``'s
  rule), all of them while ``t < index_topk``; a mask.
- Attention: softmax in float32 over the kept ``s`` of ``[q_nope, q_rope] .
  [k_nope, k_rope] * (nope + rope)^-0.5 * mscale^2``, ``mscale = 0.1
  mscale_all_dim ln(factor) + 1``; ``x += (P v) W_o``.
- Rotary positions are YaRN's (``rope_scaling``: factor, beta_fast, beta_slow,
  the original length), half-split rotation.
- Layers ``0 .. first_k_dense - 1``: a SwiGLU of ``intermediate_size``. The
  others: ``s = sigmoid(x_f32 W_r)``; ``s' = s + e_score_correction_bias``; a
  group's score is the sum of its two largest ``s'``, the ``topk_group`` best of
  ``n_group`` groups stay, the ``top_k`` largest ``s'`` among their experts are
  picked; their weights are ``s`` (no bias) divided by their sum + 1e-20,
  times ``routed_scaling_factor``. ``y = sum`` over the picks HELD HERE
  (experts ``expert_rank * held .. + held - 1`` of ``num_experts``) of ``w_i
  SwiGLU_i(x)``, plus the shared SwiGLU; what the absent experts would add is
  the other ranks' part and is left out, in the program and here alike.

Departures from the published model: it rotates ``qI`` and ``kI`` by a Hadamard
matrix (orthogonal: no score changes) and keeps ``kI`` in FP8; it rotates
interleaved lane pairs after a permutation of the projections' columns (with
seeded weights the half-split rotation is the same model up to that
permutation); the multi-token-prediction module is outside this forward pass.

Memory: layers run one at a time, experts one at a time and a matrix is
converted to ``dtype`` where it is used, attention and the indexer run
``Q_BLOCK`` query rows at a time over all heads. A sequence is padded INSIDE
to a multiple of ``PAD_TO`` (causal: the padding is inert), so that the check's
sequences of several lengths compile once a ``dtype``.

Also the arithmetic of the model that metrics divide by.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

Q_BLOCK = 128
PAD_TO = 8192


def _rms(x, w, eps):
    return x * jax.lax.rsqrt((x * x).mean(-1, keepdims=True) + eps) * w


def _layer_norm(x, w, b, eps=1e-6):
    x = x - x.mean(-1, keepdims=True)
    return x * jax.lax.rsqrt((x * x).mean(-1, keepdims=True) + eps) * w + b


def _frequencies(cfg, half):
    freqs = 1.0 / (cfg.rope_theta ** (jnp.arange(half, dtype=jnp.float32) / half))
    if cfg.rope_scaling is None:
        return freqs
    r = dict(cfg.rope_scaling)
    factor, orig = r["factor"], r["original_max_position_embeddings"]

    def lane(turns):
        return half * math.log(orig / (turns * 2 * math.pi)) / math.log(cfg.rope_theta)

    low = max(math.floor(lane(r["beta_fast"])), 0)
    high = min(math.ceil(lane(r["beta_slow"])), half - 1)
    ramp = jnp.clip((jnp.arange(half, dtype=jnp.float32) - low)
                    / max(high - low, 1e-3), 0.0, 1.0)
    return freqs / factor * ramp + freqs * (1.0 - ramp)


def _mscale(cfg) -> float:
    if cfg.rope_scaling is None:
        return 1.0
    r = dict(cfg.rope_scaling)
    return 0.1 * r.get("mscale_all_dim", 0) * math.log(r["factor"]) + 1.0


def _rope(cfg, x, positions):
    """``x`` [S, H, D], rotated over ``D`` (half-split)."""
    half = x.shape[-1] // 2
    ang = positions[:, None].astype(jnp.float32) * _frequencies(cfg, half)
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :half].astype(jnp.float32), x[..., half:].astype(jnp.float32)
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin],
                           axis=-1).astype(x.dtype)


def _swiglu(h, wg, wu, wd, dtype):
    return (jax.nn.silu(h @ wg.astype(dtype)) * (h @ wu.astype(dtype))
            ) @ wd.astype(dtype)


def _kept(index, keep):
    """``[Q, S]`` bool: a row's ``keep`` largest entries, among equals the
    lowest position first (what ``lax.top_k`` returns; written as a sort, the
    ``keep``-th largest value as a threshold and a running count of the
    entries equal to it: the chip's compiler takes 2.9 s for this against 8.0
    for ``lax.top_k`` and a scatter at ``[128, 8192]``, and the check
    compiles it four times)."""
    kth = jnp.sort(index, axis=-1)[:, index.shape[-1] - keep]
    above = index > kth[:, None]
    equal = index == kth[:, None]
    room = keep - above.sum(-1)
    return above | (equal & (jnp.cumsum(equal, axis=-1) <= room[:, None]))


def _sparse_attention(cfg, q, k, v, qi, ki, wi, dtype):
    """``Q_BLOCK`` query rows at a time: the indexer's scores against every
    key, the top ``index_topk`` as a mask, attention under it. q, k [S, H,
    Dk]; v [S, H, Dv]; qi [S, HI, DI]; ki [S, DI]; wi [S, HI] float32."""
    s, heads, _ = q.shape
    keep = min(cfg.index_topk, s)
    kpos = jnp.arange(s)
    scale = (cfg.qk_nope_head_dim + cfg.qk_rope_head_dim) ** -0.5 * _mscale(cfg) ** 2

    def block(q0):
        def rows(a):
            return jax.lax.dynamic_slice_in_dim(a, q0, Q_BLOCK, axis=0)

        causal = kpos[None, :] <= (q0 + jnp.arange(Q_BLOCK))[:, None]
        index = jnp.einsum("qhd,kd->qhk", rows(qi), ki).astype(jnp.float32)
        index = (jnp.maximum(index, 0.0) * rows(wi)[:, :, None]).sum(1)
        kept = _kept(jnp.where(causal, index, -jnp.inf), keep) & causal
        scores = jnp.einsum("qhd,khd->hqk", rows(q), k) * jnp.asarray(scale, dtype)
        scores = jnp.where(kept[None], scores, -jnp.inf)
        p = jax.nn.softmax(scores.astype(jnp.float32), axis=-1).astype(dtype)
        return jnp.einsum("hqk,khd->qhd", p, v)

    out = jax.lax.map(block, jnp.arange(0, s, Q_BLOCK))
    return out.reshape(s, heads, v.shape[-1])


def _attention(cfg, h, lp, pos, dtype):
    s = h.shape[0]
    heads, lat = cfg.num_heads, cfg.kv_lora_rank
    nope, rope, vd = cfg.qk_nope_head_dim, cfg.qk_rope_head_dim, cfg.v_head_dim
    cq = _rms(h @ lp["wq_a"].astype(dtype), lp["q_norm"].astype(dtype),
              cfg.rms_norm_eps)
    q = (cq @ lp["wq_b"].astype(dtype)).reshape(s, heads, nope + rope)
    a = h @ lp["wkv_a"].astype(dtype)
    c = _rms(a[:, :lat], lp["kv_norm"].astype(dtype), cfg.rms_norm_eps)
    k_rope = _rope(cfg, a[:, None, lat:], pos)                    # [S, 1, rope]
    kv = (c @ lp["wkv_b"].astype(dtype)).reshape(s, heads, nope + vd)
    q = jnp.concatenate([q[..., :nope], _rope(cfg, q[..., nope:], pos)], axis=-1)
    k = jnp.concatenate([kv[..., :nope],
                         jnp.broadcast_to(k_rope, (s, heads, rope))], axis=-1)

    hi, di = cfg.index_n_heads, cfg.index_head_dim
    qi = (cq @ lp["wi_q"].astype(dtype)).reshape(s, hi, di)
    ki = _layer_norm((h @ lp["wi_k"].astype(dtype)).astype(jnp.float32),
                     lp["wi_k_norm"].astype(jnp.float32),
                     lp["wi_k_bias"].astype(jnp.float32)).astype(dtype)
    qi = jnp.concatenate([_rope(cfg, qi[..., :rope], pos), qi[..., rope:]], -1)
    ki = jnp.concatenate([_rope(cfg, ki[:, None, :rope], pos)[:, 0],
                          ki[:, rope:]], -1)
    wi = (h @ lp["wi_w"].astype(dtype)).astype(jnp.float32) * (hi ** -0.5 * di ** -0.5)

    o = _sparse_attention(cfg, q, k, kv[..., nope:], qi, ki, wi, dtype)
    return o.reshape(s, heads * vd) @ lp["wo"].astype(dtype)


def _held(cfg) -> int:
    return cfg.num_experts if cfg.experts_held is None else cfg.experts_held


def _moe(cfg, h, lp, dtype):
    scores = jax.nn.sigmoid(h.astype(jnp.float32)
                            @ lp["router"].astype(jnp.float32))
    picking = scores + lp["router_bias"].astype(jnp.float32)
    t, e = picking.shape
    if cfg.n_group > 1:
        groups = picking.reshape(t, cfg.n_group, e // cfg.n_group)
        group_score = jax.lax.top_k(groups, 2)[0].sum(-1)
        _, best = jax.lax.top_k(group_score, cfg.topk_group)
        stays = jax.nn.one_hot(best, cfg.n_group, dtype=jnp.float32).sum(1) > 0
        picking = jnp.where(jnp.repeat(stays, e // cfg.n_group, axis=1),
                            picking, -jnp.inf)
    _, top_i = jax.lax.top_k(picking, cfg.top_k)
    top_w = jnp.take_along_axis(scores, top_i, axis=-1)
    if cfg.norm_topk_prob:
        top_w = top_w / (top_w.sum(-1, keepdims=True) + 1e-20)
    top_w = top_w * cfg.routed_scaling_factor
    # combine[t, e]: the weight where e is among t's picks, else 0
    combine = (jax.nn.one_hot(top_i, cfg.num_experts, dtype=jnp.float32)
               * top_w[..., None]).sum(1)
    first = cfg.expert_rank * _held(cfg)
    combine = combine[:, first:first + _held(cfg)]     # the experts held here

    def expert(acc, we):
        wg, wu, wd, c = we
        return acc + _swiglu(h, wg, wu, wd, dtype) * c[:, None].astype(dtype), None

    out, _ = jax.lax.scan(expert, jnp.zeros_like(h),
                          (lp["w_gate"], lp["w_up"], lp["w_down"], combine.T))
    return out + _swiglu(h, lp["ws_gate"], lp["ws_up"], lp["ws_down"], dtype)


def forward(cfg, params, ids, dtype=jnp.float32):
    """``ids`` [S] -> logits [S, vocab]; inside, ``S`` is padded to a
    multiple of ``PAD_TO`` (and so of ``Q_BLOCK``)."""
    n = ids.shape[0]
    ids = jnp.pad(ids, (0, -n % PAD_TO))
    pos = jnp.arange(ids.shape[0])
    x = params["embed"][ids].astype(dtype)

    def layer(x, lp):
        h = _rms(x, lp["attn_norm"].astype(dtype), cfg.rms_norm_eps)
        x = x + _attention(cfg, h, lp, pos, dtype)
        h = _rms(x, lp["mlp_norm"].astype(dtype), cfg.rms_norm_eps)
        if "router" in lp:
            return x + _moe(cfg, h, lp, dtype), None
        return x + _swiglu(h, lp["w_gate"], lp["w_up"], lp["w_down"], dtype), None

    x, _ = jax.lax.scan(layer, x, params["dense"])
    x, _ = jax.lax.scan(layer, x, params["layers"])
    x = _rms(x, params["final_norm"].astype(dtype), cfg.rms_norm_eps)
    return (x @ params["lm_head"].astype(dtype))[:n]


# ------------------------------------------------------- model arithmetic
def _attention_params(cfg) -> int:
    d, h, r = cfg.hidden_size, cfg.num_heads, cfg.q_lora_rank
    row = cfg.kv_lora_rank + cfg.qk_rope_head_dim
    mla = (d * r + r + r * h * (cfg.qk_nope_head_dim + cfg.qk_rope_head_dim)
           + d * row + cfg.kv_lora_rank
           + cfg.kv_lora_rank * h * (cfg.qk_nope_head_dim + cfg.v_head_dim)
           + h * cfg.v_head_dim * d + 2 * d)
    hi, di = cfg.index_n_heads, cfg.index_head_dim
    return mla + r * hi * di + d * di + 2 * di + d * hi


def _dense_layer_params(cfg) -> int:
    return _attention_params(cfg) + 3 * cfg.hidden_size * cfg.intermediate_size


def _moe_layer_params(cfg, experts) -> int:
    """An expert layer with ``experts`` routed experts counted: the router
    over all the published experts and its selection bias, the routed and
    the shared experts."""
    d, fm = cfg.hidden_size, cfg.moe_intermediate_size
    return (_attention_params(cfg) + d * cfg.num_experts + cfg.num_experts
            + 3 * d * fm * (experts + cfg.num_shared_experts))


def _layers(cfg) -> tuple:
    return cfg.first_k_dense, cfg.num_layers - cfg.first_k_dense


def num_params(cfg) -> int:
    """Parameters that live here: the held experts, not all the routed."""
    dense, moe = _layers(cfg)
    d = cfg.hidden_size
    return (2 * cfg.vocab_size * d + d + dense * _dense_layer_params(cfg)
            + moe * _moe_layer_params(cfg, _held(cfg)))


def active_params(cfg) -> float:
    """Parameters a token's forward pass multiplies by HERE: of its ``top_k``
    picks the expected ``top_k x held / num_experts`` land on a held expert
    (0.5 at 8 picks, 16 of 256); the shared expert, attention, the indexer's
    projections, router, head; the embedding is a lookup."""
    dense, moe = _layers(cfg)
    picks = cfg.top_k * _held(cfg) / cfg.num_experts
    return (cfg.vocab_size * cfg.hidden_size + dense * _dense_layer_params(cfg)
            + moe * _moe_layer_params(cfg, picks))


def weight_bytes(cfg, bytes_per_param: int = 2) -> int:
    """Bytes a step must read: every held expert's weights, whatever the
    routing of a batch of more than a few tokens; the embedding is a lookup."""
    return (num_params(cfg) - cfg.vocab_size * cfg.hidden_size) * bytes_per_param


def kv_bytes_per_token(cfg, bytes_per_value: int = 2) -> int:
    """Bytes of the latent cache per context token, all layers: ONE row of
    ``kv_lora_rank + qk_rope_head_dim`` values a layer, key and value both."""
    return ((cfg.kv_lora_rank + cfg.qk_rope_head_dim) * bytes_per_value
            * cfg.num_layers)


def attn_flops_per_pair(cfg) -> int:
    """FLOPs of one query x kept-key pair, all layers, absorbed (what the
    program runs): scores over ``lat + rope`` lanes and values over ``lat``
    lanes a head."""
    return (2 * cfg.num_heads * (2 * cfg.kv_lora_rank + cfg.qk_rope_head_dim)
            * cfg.num_layers)


def index_bytes_per_token(cfg, bytes_per_value: int = 2) -> int:
    """Bytes of the indexer's cache per context token, all layers."""
    return cfg.index_head_dim * bytes_per_value * cfg.num_layers


def index_flops_per_pair(cfg) -> int:
    """FLOPs of the indexer's score of one query x key pair, all layers."""
    return 2 * cfg.index_n_heads * cfg.index_head_dim * cfg.num_layers


def index_topk(cfg) -> int:
    return cfg.index_topk
