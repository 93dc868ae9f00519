"""DeepSeek-V3 as Moonlight-16B-A3B configures it, plain: the published
forward pass in straight ``jax.numpy``.

No kernels, no cache, no absorbed attention, nothing imported from the
program. Per layer (pre-norm RMSNorm, a residual after each half):

- MLA, not absorbed: ``q = x W_q`` split per head into ``q_nope`` and
  ``q_rope``; ``a = x W_kva``; ``c = RMSNorm(a[:, :lat])``; ``k_rope`` = the
  rest, ONE head shared by all; rotary positions on ``q_rope`` and ``k_rope``
  (half-split rotation, ``rope_theta``); ``kv = c W_kvb`` split per head into
  ``k_nope`` and ``v``; causal softmax in float32 of ``[q_nope, q_rope] .
  [k_nope, k_rope] * (nope + rope)^-0.5``; ``x += (P v) W_o``. No
  ``rope_scaling`` in the source, so no YaRN factor on the scale.
- Layers ``0 .. first_k_dense - 1``: a SwiGLU of width ``intermediate_size``.
- The other layers: ``s = sigmoid(x_f32 W_r)``; the ``top_k`` experts with the
  largest ``s + e_score_correction_bias``; their weights are ``s`` (without the
  bias) divided by their sum + 1e-20, times ``routed_scaling_factor``; ``y =
  sum_i w_i SwiGLU_i(x)`` plus one SwiGLU of width ``num_shared_experts x
  moe_intermediate_size`` (the shared experts).

The one departure: the published code rotates interleaved lane pairs after a
permutation of the projections' columns; with seeded weights the half-split
rotation is the same model up to that permutation.

Memory: layers run one at a time and experts one at a time (a ``scan`` over
the expert axis converts one expert's three matrices to ``dtype`` inside its
body), so a float32 reference of a 9.7 GB bf16 model needs well under 1 GB of
weights at once. An expert computes every token and the combine weight is 0
where the router did not pick it: the same sum as routing. Attention runs in
blocks of query rows.

Also the arithmetic of the model that metrics divide by.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

Q_BLOCK = 512
HEAD_BLOCK = 16384


def _rms(x, w, eps):
    return x * jax.lax.rsqrt((x * x).mean(-1, keepdims=True) + eps) * w


def _rope(x, positions, theta):
    """``x`` [S, H, D], rotated over ``D`` (half-split)."""
    half = x.shape[-1] // 2
    freqs = 1.0 / (theta ** (jnp.arange(half, dtype=jnp.float32) / half))
    ang = positions[:, None].astype(jnp.float32) * freqs
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :half].astype(jnp.float32), x[..., half:].astype(jnp.float32)
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin],
                           axis=-1).astype(x.dtype)


def _swiglu(h, wg, wu, wd, dtype):
    return (jax.nn.silu(h @ wg.astype(dtype)) * (h @ wu.astype(dtype))
            ) @ wd.astype(dtype)


def _attention(q, k, v, scale, dtype):
    """Causal attention, ``Q_BLOCK`` query rows at a time. q, k [S, H, Dk],
    v [S, H, Dv]."""
    s, heads, _ = q.shape
    kpos = jnp.arange(s)

    def block(q0):
        qb = jax.lax.dynamic_slice_in_dim(q, q0, Q_BLOCK, axis=0)
        scores = jnp.einsum("qhd,khd->hqk", qb, k) * jnp.asarray(scale, dtype)
        ok = kpos[None, :] <= (q0 + jnp.arange(Q_BLOCK))[:, None]
        scores = jnp.where(ok[None], scores, -jnp.inf)
        p = jax.nn.softmax(scores.astype(jnp.float32), axis=-1).astype(dtype)
        return jnp.einsum("hqk,khd->qhd", p, v)

    out = jax.lax.map(block, jnp.arange(0, s, Q_BLOCK))
    return out.reshape(s, heads, v.shape[-1])


def _mla(cfg, h, lp, pos, dtype):
    s = h.shape[0]
    heads, lat = cfg.num_heads, cfg.kv_lora_rank
    nope, rope, vd = cfg.qk_nope_head_dim, cfg.qk_rope_head_dim, cfg.v_head_dim
    q = (h @ lp["wq"].astype(dtype)).reshape(s, heads, nope + rope)
    a = h @ lp["wkv_a"].astype(dtype)
    c = _rms(a[:, :lat], lp["kv_norm"].astype(dtype), cfg.rms_norm_eps)
    k_rope = _rope(a[:, None, lat:], pos, cfg.rope_theta)        # [S, 1, rope]
    q_rope = _rope(q[..., nope:], pos, cfg.rope_theta)
    kv = (c @ lp["wkv_b"].astype(dtype)).reshape(s, heads, nope + vd)
    q = jnp.concatenate([q[..., :nope], q_rope], axis=-1)
    k = jnp.concatenate([kv[..., :nope],
                         jnp.broadcast_to(k_rope, (s, heads, rope))], axis=-1)
    o = _attention(q, k, kv[..., nope:], (nope + rope) ** -0.5, dtype)
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
    # combine[t, e]: the weight where e is among t's picks, else 0
    combine = (jax.nn.one_hot(top_i, cfg.num_experts, dtype=jnp.float32)
               * top_w[..., None]).sum(1)

    def expert(acc, we):
        wg, wu, wd, c = we
        return acc + _swiglu(h, wg, wu, wd, dtype) * c[:, None].astype(dtype), None

    out, _ = jax.lax.scan(expert, jnp.zeros_like(h),
                          (lp["w_gate"], lp["w_up"], lp["w_down"], combine.T))
    return out + _swiglu(h, lp["ws_gate"], lp["ws_up"], lp["ws_down"], dtype)


def forward(cfg, params, ids, dtype=jnp.float32):
    """``ids`` [S] (S a multiple of ``Q_BLOCK``) -> logits [S, vocab]."""
    pos = jnp.arange(ids.shape[0])
    x = params["embed"][ids].astype(dtype)

    def layer(x, lp):
        h = _rms(x, lp["attn_norm"].astype(dtype), cfg.rms_norm_eps)
        x = x + _mla(cfg, h, lp, pos, dtype)
        h = _rms(x, lp["mlp_norm"].astype(dtype), cfg.rms_norm_eps)
        if "router" in lp:
            return x + _moe(cfg, h, lp, dtype), None
        return x + _swiglu(h, lp["w_gate"], lp["w_up"], lp["w_down"], dtype), None

    x, _ = jax.lax.scan(layer, x, params["dense"])
    x, _ = jax.lax.scan(layer, x, params["layers"])
    x = _rms(x, params["final_norm"].astype(dtype), cfg.rms_norm_eps)
    return _head(x, params["lm_head"], dtype)


def _head(x, w, dtype):
    """``x @ w`` in ``dtype``, ``HEAD_BLOCK`` columns of the vocabulary at a
    time: at 163,840 columns the head converted to float32 whole is 1.3 GB
    beside 2.7 GB of logits."""
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
def _attention_params(cfg) -> int:
    d, h = cfg.hidden_size, cfg.num_heads
    row = cfg.kv_lora_rank + cfg.qk_rope_head_dim
    return (d * h * (cfg.qk_nope_head_dim + cfg.qk_rope_head_dim)   # W_q
            + d * row + cfg.kv_lora_rank                            # W_kva, its norm
            + cfg.kv_lora_rank * h * (cfg.qk_nope_head_dim + cfg.v_head_dim)
            + h * cfg.v_head_dim * d + 2 * d)                       # W_o, two norms


def _dense_layer_params(cfg) -> int:
    return _attention_params(cfg) + 3 * cfg.hidden_size * cfg.intermediate_size


def _moe_layer_params(cfg, experts: int) -> int:
    """An expert layer with ``experts`` routed experts counted: the router
    and its selection bias, the routed and the shared experts."""
    d, fm = cfg.hidden_size, cfg.moe_intermediate_size
    return (_attention_params(cfg) + d * cfg.num_experts + cfg.num_experts
            + 3 * d * fm * (experts + cfg.num_shared_experts))


def _layers(cfg) -> tuple:
    return cfg.first_k_dense, cfg.num_layers - cfg.first_k_dense


def num_params(cfg) -> int:
    dense, moe = _layers(cfg)
    d = cfg.hidden_size
    return (2 * cfg.vocab_size * d + d + dense * _dense_layer_params(cfg)
            + moe * _moe_layer_params(cfg, cfg.num_experts))


def active_params(cfg) -> int:
    """Parameters a token's forward pass multiplies by: ``top_k`` routed
    experts a layer (what the architecture requires, not what an all-experts
    einsum spends), the shared experts, attention, router, head; the
    embedding is a lookup."""
    dense, moe = _layers(cfg)
    return (cfg.vocab_size * cfg.hidden_size + dense * _dense_layer_params(cfg)
            + moe * _moe_layer_params(cfg, cfg.top_k))


def train_flops_per_token(cfg, seq_len: int) -> float:
    return (6.0 * active_params(cfg)
            + 6.0 * cfg.num_layers * cfg.num_heads * seq_len / 2.0
            * (cfg.qk_nope_head_dim + cfg.qk_rope_head_dim + cfg.v_head_dim))


def weight_bytes(cfg, bytes_per_param: int = 2) -> int:
    """Bytes a decode step must read: every expert's weights, whatever the
    routing of a batch of more than a few tokens; the embedding is a lookup."""
    return (num_params(cfg) - cfg.vocab_size * cfg.hidden_size) * bytes_per_param


def kv_bytes_per_token(cfg, bytes_per_value: int = 2) -> int:
    """Bytes of cache a step must read per context token, all layers: ONE
    row of ``kv_lora_rank + qk_rope_head_dim`` values a layer (the latent is
    key and value both), read once."""
    return ((cfg.kv_lora_rank + cfg.qk_rope_head_dim) * bytes_per_value
            * cfg.num_layers)


def attn_flops_per_pair(cfg, absorbed: bool = True) -> int:
    """FLOPs of one query x key pair, all layers. Absorbed (what the program
    runs, decode and prefill): scores over ``lat + rope`` lanes and values
    over ``lat`` lanes a head. Decompressed: scores over ``nope + rope`` and
    values over ``v`` lanes a head (``kv_b_proj`` over the context apart)."""
    if absorbed:
        lanes = 2 * cfg.kv_lora_rank + cfg.qk_rope_head_dim
    else:
        lanes = cfg.qk_nope_head_dim + cfg.qk_rope_head_dim + cfg.v_head_dim
    return 2 * cfg.num_heads * lanes * cfg.num_layers
