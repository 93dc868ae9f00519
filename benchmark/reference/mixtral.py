"""Mixtral, plain: the published forward pass in straight ``jax.numpy``.

No kernels, no cache, no batching tricks, nothing imported from the program
(in particular not ``_moe_infer``). RMSNorm, rotary positions (half-split
rotation, ``rope_theta``), grouped-query causal attention, and in every layer
the sparse expert FFN **as published**: softmax over the router's logits, the
top ``num_experts_per_tok`` experts, their weights renormalised to sum to 1,
each a SwiGLU (``w_down(silu(w_gate x) * w_up x)``).

Memory: layers run one at a time and experts one at a time (a ``scan`` over
the expert axis converts one expert's three matrices to ``dtype`` inside its
body), so a float32 reference of a 9 GB bf16 model needs < 1 GB of weights at
once. An expert computes every token and the combine weight is 0 where the
router did not pick it: the same sum as routing, at no extra memory. Attention
runs in blocks of query rows, so an 8K context never makes an 8K x 8K x heads
score tensor.

Also the arithmetic of the model that metrics divide by.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

Q_BLOCK = 512


def _rms(x, w, eps):
    return x * jax.lax.rsqrt((x * x).mean(-1, keepdims=True) + eps) * w


def _rope(x, positions, theta):
    half = x.shape[-1] // 2
    freqs = 1.0 / (theta ** (jnp.arange(half, dtype=jnp.float32) / half))
    ang = positions[:, None].astype(jnp.float32) * freqs
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :half].astype(jnp.float32), x[..., half:].astype(jnp.float32)
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin],
                           axis=-1).astype(x.dtype)


def _attention(q, k, v, dtype):
    """Causal GQA, ``Q_BLOCK`` query rows at a time. q [S,Hq,D], k/v [S,Hkv,D]."""
    s, hq, d = q.shape
    rep = hq // k.shape[1]
    k, v = jnp.repeat(k, rep, axis=1), jnp.repeat(v, rep, axis=1)
    kpos = jnp.arange(s)

    def block(q0):
        qb = jax.lax.dynamic_slice_in_dim(q, q0, Q_BLOCK, axis=0)
        scores = jnp.einsum("qhd,khd->hqk", qb, k) / jnp.sqrt(jnp.asarray(d, dtype))
        ok = kpos[None, :] <= (q0 + jnp.arange(Q_BLOCK))[:, None]
        scores = jnp.where(ok[None], scores, -jnp.inf)
        p = jax.nn.softmax(scores.astype(jnp.float32), axis=-1).astype(dtype)
        return jnp.einsum("hqk,khd->qhd", p, v)

    out = jax.lax.map(block, jnp.arange(0, s, Q_BLOCK))
    return out.reshape(s, hq, d)


def _moe(cfg, h, lp, dtype):
    probs = jax.nn.softmax(h.astype(jnp.float32)
                           @ lp["router"].astype(jnp.float32), axis=-1)
    top_w, top_i = jax.lax.top_k(probs, cfg.top_k)
    top_w = top_w / top_w.sum(-1, keepdims=True)
    # combine[t, e]: the renormalised weight where e is among t's top k, else 0
    combine = (jax.nn.one_hot(top_i, cfg.num_experts, dtype=jnp.float32)
               * top_w[..., None]).sum(1)

    def expert(acc, we):
        wg, wu, wd, c = we
        y = (jax.nn.silu(h @ wg.astype(dtype)) * (h @ wu.astype(dtype))) @ wd.astype(dtype)
        return acc + y * c[:, None].astype(dtype), None

    out, _ = jax.lax.scan(expert, jnp.zeros_like(h),
                          (lp["w_gate"], lp["w_up"], lp["w_down"], combine.T))
    return out


def forward(cfg, params, ids, dtype=jnp.float32):
    """``ids`` [S] (S a multiple of ``Q_BLOCK``) -> logits [S, vocab]."""
    s = ids.shape[0]
    hq, hkv = cfg.num_heads, cfg.num_kv_heads
    hd = cfg.head_dim if cfg.head_dim is not None else cfg.hidden_size // hq
    pos = jnp.arange(s)
    x = params["embed"][ids].astype(dtype)

    def layer(x, lp):
        h = _rms(x, lp["attn_norm"].astype(dtype), cfg.rms_norm_eps)
        q = (h @ lp["wq"].astype(dtype)).reshape(s, hq, hd)
        k = (h @ lp["wk"].astype(dtype)).reshape(s, hkv, hd)
        v = (h @ lp["wv"].astype(dtype)).reshape(s, hkv, hd)
        q, k = _rope(q, pos, cfg.rope_theta), _rope(k, pos, cfg.rope_theta)
        o = _attention(q, k, v, dtype).reshape(s, hq * hd)
        x = x + o @ lp["wo"].astype(dtype)
        h = _rms(x, lp["mlp_norm"].astype(dtype), cfg.rms_norm_eps)
        return x + _moe(cfg, h, lp, dtype), None

    x, _ = jax.lax.scan(layer, x, params["layers"])
    x = _rms(x, params["final_norm"].astype(dtype), cfg.rms_norm_eps)
    return x @ params["lm_head"].astype(dtype)


def _layer_params(cfg, experts: int) -> int:
    d, f = cfg.hidden_size, cfg.intermediate_size
    hd = cfg.head_dim if cfg.head_dim is not None else d // cfg.num_heads
    return (d * hd * (cfg.num_heads * 2 + cfg.num_kv_heads * 2)
            + d * cfg.num_experts + 3 * experts * d * f + 2 * d)


def num_params(cfg) -> int:
    d = cfg.hidden_size
    return (cfg.vocab_size * d * 2 + d
            + cfg.num_layers * _layer_params(cfg, cfg.num_experts))


def active_params(cfg) -> int:
    """Parameters a token's forward pass multiplies by: ``top_k`` experts a
    layer (what the architecture requires, not what an all-experts einsum
    spends), attention, router, head; the embedding is a lookup."""
    d = cfg.hidden_size
    return cfg.vocab_size * d + cfg.num_layers * _layer_params(cfg, cfg.top_k)


def train_flops_per_token(cfg, seq_len: int) -> float:
    return (6.0 * active_params(cfg)
            + 12.0 * cfg.num_layers * cfg.hidden_size * seq_len / 2.0)


def weight_bytes(cfg, bytes_per_param: int = 2) -> int:
    """Bytes a decode step must read: every expert's weights, whatever the
    routing of a batch of more than a few tokens; the embedding is a lookup."""
    return (num_params(cfg) - cfg.vocab_size * cfg.hidden_size) * bytes_per_param
