"""SmallThinker (PowerInfer SmallThinker-21BA3B-Instruct), plain: the published
forward pass in straight ``jax.numpy``.

No kernels, no cache, no batching, nothing imported from the program. Layer
``l`` on its input ``x`` [S, D]:

    r   = x W_r                      router logits, float32, from the layer's
                                     INPUT: before the norm, before attention
    h   = rmsnorm(x, g_attn)
    q, k, v = h W_q, h W_k, h W_v    28 query heads over 4 KV heads of 128
    rope_layout[l] == 1:  q, k rotated (theta, all lanes, half-split pairs);
                    == 0:  as projected (NoPE)
    allowed(i, j) = j <= i                       sliding_window_layout[l] == 0
                  = i - W < j <= i               == 1 (W keys, i's own among them)
    x1  = x + softmax(q k / sqrt(128)) v W_o
    h2  = rmsnorm(x1, g_ffn)
    top = the 6 largest of r;  w = softmax over those 6 logits
    out = x1 + sum_e w_e (relu(h2 W_gate,e) * (h2 W_up,e)) W_down,e

One rank's share of an expert-parallel deployment: the weights hold
``cfg.held`` of the ``cfg.num_experts`` routed experts (``cfg.held_range``
says which); the router scores all of them and the weights are normalised
over all the picks; a pick of an expert that is not held adds nothing (the
other ranks' parts, which the deployment adds up).

The weights are the program's tree: ``params["lead"]`` (layers before the
period), ``params["period"]`` (one tree a position of the period, leaves
``[repeats, ...]``); ``_plan`` below finds the same split from the config's
two lists. The period is scanned over its repeats.

Memory: experts one at a time, attention ``Q_BLOCK`` query rows at a time
over all keys under the mask, so an 8K context never makes an 8K x 8K x heads
score array (7.5 GB in float32).

Also the arithmetic of the model that metrics divide by, split by layer kind:
the full layers' K and V grow with the context, the window layers' stop at
``W`` rows.
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


def _attention(q, k, v, window, dtype):
    """Causal GQA, ``Q_BLOCK`` query rows at a time; with ``window`` a query
    at ``i`` sees keys ``i - window < j <= i``. q [S,Hq,D], k/v [S,Hkv,D]."""
    s, hq, d = q.shape
    rep = hq // k.shape[1]
    k, v = jnp.repeat(k, rep, axis=1), jnp.repeat(v, rep, axis=1)
    kpos = jnp.arange(s)

    def block(q0):
        qb = jax.lax.dynamic_slice_in_dim(q, q0, Q_BLOCK, axis=0)
        scores = jnp.einsum("qhd,khd->hqk", qb, k) / jnp.sqrt(jnp.asarray(d, dtype))
        qpos = (q0 + jnp.arange(Q_BLOCK))[:, None]
        ok = kpos[None, :] <= qpos
        if window is not None:
            ok = ok & (qpos - kpos[None, :] < window)
        scores = jnp.where(ok[None], scores, -jnp.inf)
        p = jax.nn.softmax(scores.astype(jnp.float32), axis=-1).astype(dtype)
        return jnp.einsum("hqk,khd->qhd", p, v)

    out = jax.lax.map(block, jnp.arange(0, s, Q_BLOCK))
    return out.reshape(s, hq, d)


def _moe(cfg, x, h, lp, dtype):
    """The experts on ``h``, routed on ``x``; the held share only."""
    logits = x.astype(jnp.float32) @ lp["router"].astype(jnp.float32)
    top_l, top_i = jax.lax.top_k(logits, cfg.top_k)
    top_w = jax.nn.softmax(top_l, axis=-1)
    # combine[t, e]: the weight where routed expert e is among t's picks
    combine = (jax.nn.one_hot(top_i, cfg.num_experts, dtype=jnp.float32)
               * top_w[..., None]).sum(1)
    first = 0 if cfg.held_range is None else cfg.held_range[0]
    combine = jax.lax.dynamic_slice_in_dim(combine, first, cfg.held, axis=1)

    def expert(acc, we):
        wg, wu, wd, c = we
        y = (jax.nn.relu(h @ wg.astype(dtype)) * (h @ wu.astype(dtype))) \
            @ wd.astype(dtype)
        return acc + y * c[:, None].astype(dtype), None

    out, _ = jax.lax.scan(expert, jnp.zeros_like(h),
                          (lp["w_gate"], lp["w_up"], lp["w_down"], combine.T))
    return out


def _layer(cfg, kind, x, lp, pos, dtype):
    """One layer; ``kind`` ``(windowed, rotated)``."""
    windowed, rotated = kind
    s = x.shape[0]
    hq, hkv, hd = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    h = _rms(x, lp["attn_norm"].astype(dtype), cfg.rms_norm_eps)
    q = (h @ lp["wq"].astype(dtype)).reshape(s, hq, hd)
    k = (h @ lp["wk"].astype(dtype)).reshape(s, hkv, hd)
    v = (h @ lp["wv"].astype(dtype)).reshape(s, hkv, hd)
    if rotated:
        q, k = _rope(q, pos, cfg.rope_theta), _rope(k, pos, cfg.rope_theta)
    o = _attention(q, k, v, cfg.sliding_window if windowed else None, dtype)
    x1 = x + o.reshape(s, hq * hd) @ lp["wo"].astype(dtype)
    h2 = _rms(x1, lp["mlp_norm"].astype(dtype), cfg.rms_norm_eps)
    return x1 + _moe(cfg, x, h2, lp, dtype)


def _plan(cfg):
    """``(lead, period, repeats)`` of the layers' kinds ``(windowed,
    rotated)``: the fewest leading layers and the shortest period the order
    is ``lead + period x repeats`` of (``repeats`` >= 2): the split the
    program's weights lie in."""
    kinds = list(zip(cfg.sliding_window_layout, cfg.rope_layout))
    n = len(kinds)
    best = None
    for lead in range(n):
        for period in range(1, (n - lead) // 2 + 1):
            rest = kinds[lead:]
            if len(rest) % period == 0 \
                    and rest == rest[:period] * (len(rest) // period) \
                    and (best is None or lead + period < best[0] + best[1]):
                best = (lead, period)
    lead, period = best
    return kinds[:lead], kinds[lead:lead + period], (n - lead) // period


def forward(cfg, params, ids, dtype=jnp.float32):
    """``ids`` [S] -> logits [S, vocab] (padded inside to whole ``Q_BLOCK``s:
    causal, so the padding is inert)."""
    s = ids.shape[0]
    pad = -s % Q_BLOCK
    ids = jnp.pad(ids, (0, pad))
    pos = jnp.arange(s + pad)
    lead, period, _ = _plan(cfg)
    x = params["embed"][ids].astype(dtype)
    for kind, lp in zip(lead, params["lead"]):
        x = _layer(cfg, kind, x, lp, pos, dtype)

    def repeat(x, trees):
        for kind, lp in zip(period, trees):
            x = _layer(cfg, kind, x, lp, pos, dtype)
        return x, None

    x, _ = jax.lax.scan(repeat, x, tuple(params["period"]))
    x = _rms(x, params["final_norm"].astype(dtype), cfg.rms_norm_eps)
    return (x @ params["lm_head"].astype(dtype))[:s]


# ------------------------------------------------------------- arithmetic
def attention_params(cfg) -> int:
    return cfg.hidden_size * cfg.head_dim * (2 * cfg.num_heads
                                             + 2 * cfg.num_kv_heads)


def expert_params(cfg) -> int:
    return 3 * cfg.hidden_size * cfg.moe_intermediate_size


def _layer_params(cfg, experts: float) -> float:
    """Attention + router + the two norms + ``experts`` experts."""
    d = cfg.hidden_size
    return (attention_params(cfg) + d * cfg.num_experts + 2 * d
            + experts * expert_params(cfg))


def num_params(cfg) -> int:
    """Parameters that live here: the held experts of every layer."""
    d = cfg.hidden_size
    return int(2 * cfg.vocab_size * d + d
               + cfg.num_layers * _layer_params(cfg, cfg.held))


def active_params(cfg) -> float:
    """Parameters a token's forward pass multiplies by HERE: of its ``top_k``
    picks ``top_k x held / num_experts`` land on a held expert (uniform
    routing); attention, router, head; the embedding is a lookup."""
    return (cfg.vocab_size * cfg.hidden_size + cfg.num_layers * _layer_params(
        cfg, cfg.top_k * cfg.held / cfg.num_experts))


def train_flops_per_token(cfg, seq_len: int) -> float:
    full, win = full_layers(cfg), window_layers(cfg)
    keys = full * seq_len / 2.0 + win * min(seq_len / 2.0, cfg.sliding_window)
    return 6.0 * active_params(cfg) + 12.0 * cfg.num_heads * cfg.head_dim * keys


def weight_bytes(cfg, bytes_per_param: int = 2) -> int:
    """Bytes a step must read: every held expert's weights, whatever the
    routing of a batch of more than a few tokens; the embedding is a lookup."""
    return (num_params(cfg) - cfg.vocab_size * cfg.hidden_size) * bytes_per_param


def full_layers(cfg) -> int:
    return sum(1 for w in cfg.sliding_window_layout if not w)


def window_layers(cfg) -> int:
    return sum(1 for w in cfg.sliding_window_layout if w)


def _kv_row_bytes(cfg, itemsize: int) -> int:
    return 2 * cfg.num_kv_heads * cfg.head_dim * itemsize


def kv_bytes_per_token(cfg, itemsize: int = 2) -> int:
    """K and V a cached token costs in the FULL layers (13 of 52: the part
    that grows with the context; what ``kv_tokens`` / ``dec_kv_tokens`` of
    ``engine/dispatch`` multiply)."""
    return full_layers(cfg) * _kv_row_bytes(cfg, itemsize)


def window_kv_bytes_per_token(cfg, itemsize: int = 2) -> int:
    """K and V a token INSIDE THE WINDOW costs in the window layers (what
    ``win_kv_tokens`` / ``dec_win_kv_tokens`` multiply)."""
    return window_layers(cfg) * _kv_row_bytes(cfg, itemsize)


def _pair_flops(cfg) -> int:
    return 4 * cfg.num_heads * cfg.head_dim      # q.k and p.v, a layer


def attn_flops_per_pair(cfg) -> int:
    """FLOPs of one query x key pair over the full layers (``attn_pairs``)."""
    return full_layers(cfg) * _pair_flops(cfg)


def window_attn_flops_per_pair(cfg) -> int:
    """The same over the window layers (``win_attn_pairs``)."""
    return window_layers(cfg) * _pair_flops(cfg)
