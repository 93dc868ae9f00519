"""Nemotron-H as NVIDIA-Nemotron-3-Super-120B-A12B configures it, plain: the
published forward pass in straight ``jax.numpy``.

No kernels, no cache, no chunked form, nothing imported from the program.
Every layer is ``x += mixer(RMSNorm(x))`` (eps ``rms_norm_eps``), in the order
of ``hybrid_override_pattern``; a final RMSNorm and an untied head follow.

- ``M``, Mamba-2: ``[z | xBC | dt] = h W_in`` (``d_inner`` | ``d_inner + 2 G N``
  | ``H`` wide); ``xBC = silu(causal depthwise conv_K(xBC) + b)``; ``x`` [H,
  P], ``B``, ``C`` [G, N] (``H / G`` heads to a group); ``dt = softplus(dt +
  dt_bias)``, ``A = -exp(A_log)``; the recurrence as a ``lax.scan`` over the
  TOKENS, ``S_t = exp(dt_t A) S_{t-1} + dt_t x_t (x) B_t`` (``S`` [H, P, N]),
  ``y_t = S_t C_t + D x_t``; ``y = RMSNorm(y silu(z))`` in ``G`` groups, its own
  weight; ``out = y W_out``.
- ``*``, attention: grouped-query, causal softmax in float32 of ``q . k
  head_dim^-0.5``, NO positional embedding, no bias.
- ``E``, LatentMoE: ``s = sigmoid(h_f32 W_g)`` over ALL the routed experts; the
  ``top_k`` largest of ``s + e_score_correction_bias``; weights ``s`` there,
  divided by their sum + 1e-20, times ``routed_scaling_factor``; ``u = h
  W_lat_in``; ``y = (sum_picks w_e relu(u W1_e)**2 W2_e) W_lat_out + relu(h
  Ws1)**2 Ws2``.

One rank's share: the parameter tree holds experts ``expert_rank x held ..``
of the routed ones (and a share of the vocabulary's rows, which the tree's
shapes already are). The router's picks of experts that are not here add
nothing, here as in the program: the other ranks' parts.

Memory: the experts run one at a time (a ``scan`` over the held experts
converts one expert's two matrices to ``dtype`` inside its body): an ``[S,
held, ffn]`` float32 intermediate is 5.6 GB at 4,096 x 128 x 2,688 and never
exists. A stretch of the pattern that repeats (``EMEMEMEMEM``) runs as a
``scan`` over its repeats, each layer's weights indexed out of the stacks
inside the body, so the program compiles one ``E`` and one ``M``, not five.

Also the arithmetic of the model that metrics divide by.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

Q_BLOCK = 512
HEAD_BLOCK = 16384
STACK = {"M": "mamba", "E": "moe", "*": "attn"}


def _rms(x, w, eps):
    return x * jax.lax.rsqrt((x * x).mean(-1, keepdims=True) + eps) * w


def _relu2(h, w1, w2, dtype):
    return jnp.square(jax.nn.relu(h @ w1.astype(dtype))) @ w2.astype(dtype)


def _attention(cfg, h, lp, dtype):
    s = h.shape[0]
    heads, kv, hd = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    q = (h @ lp["wq"].astype(dtype)).reshape(s, heads, hd)
    k = jnp.repeat((h @ lp["wk"].astype(dtype)).reshape(s, kv, hd),
                   heads // kv, axis=1)
    v = jnp.repeat((h @ lp["wv"].astype(dtype)).reshape(s, kv, hd),
                   heads // kv, axis=1)
    kpos = jnp.arange(s)

    def block(q0):
        qb = jax.lax.dynamic_slice_in_dim(q, q0, min(Q_BLOCK, s), axis=0)
        scores = jnp.einsum("qhd,khd->hqk", qb, k) * jnp.asarray(hd ** -0.5, dtype)
        ok = kpos[None, :] <= (q0 + jnp.arange(qb.shape[0]))[:, None]
        p = jax.nn.softmax(jnp.where(ok[None], scores, -jnp.inf).astype(
            jnp.float32), axis=-1).astype(dtype)
        return jnp.einsum("hqk,khd->qhd", p, v)

    o = jax.lax.map(block, jnp.arange(0, s, min(Q_BLOCK, s)))
    return o.reshape(s, heads * hd) @ lp["wo"].astype(dtype)


def _mamba(cfg, h, lp, dtype):
    s = h.shape[0]
    heads, p = cfg.mamba_num_heads, cfg.mamba_head_dim
    g, n, k = cfg.n_groups, cfg.ssm_state_size, cfg.conv_kernel
    di = heads * p
    cw = di + 2 * g * n
    zxbcdt = h @ lp["w_in"].astype(dtype)
    z, xbc, dt = zxbcdt[:, :di], zxbcdt[:, di:di + cw], zxbcdt[:, di + cw:]
    # causal depthwise convolution: row t sees rows t-K+1 .. t
    padded = jnp.concatenate([jnp.zeros((k - 1, cw), dtype), xbc])
    conv = lp["conv_b"].astype(dtype) + sum(
        padded[j:j + s] * lp["conv_w"][j].astype(dtype) for j in range(k))
    xbc = jax.nn.silu(conv)
    x = xbc[:, :di].reshape(s, heads, p)
    b = jnp.repeat(xbc[:, di:di + g * n].reshape(s, g, n), heads // g, axis=1)
    c = jnp.repeat(xbc[:, di + g * n:].reshape(s, g, n), heads // g, axis=1)
    dt = jax.nn.softplus(dt + lp["dt_bias"].astype(dtype))          # [S, H]
    a = -jnp.exp(lp["a_log"].astype(dtype))                         # [H]

    def token(state, xs):
        x_t, b_t, c_t, dt_t = xs
        state = (jnp.exp(dt_t * a)[:, None, None] * state
                 + (dt_t[:, None] * x_t)[:, :, None] * b_t[:, None, :])
        return state, jnp.einsum("hpn,hn->hp", state, c_t)

    _, y = jax.lax.scan(token, jnp.zeros((heads, p, n), dtype), (x, b, c, dt))
    y = (y + lp["d_skip"].astype(dtype)[:, None] * x).reshape(s, di)
    y = (y * jax.nn.silu(z)).reshape(s, g, di // g)
    y = (y * jax.lax.rsqrt((y * y).mean(-1, keepdims=True) + cfg.rms_norm_eps)
         ).reshape(s, di) * lp["ssm_norm"].astype(dtype)
    return y @ lp["w_out"].astype(dtype)


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
    u = h @ lp["w_lat_in"].astype(dtype)

    def expert(acc, we):
        w1, w2, c = we
        return acc + _relu2(u, w1, w2, dtype) * c[:, None].astype(dtype), None

    routed, _ = jax.lax.scan(
        expert, jnp.zeros_like(u),
        (lp["w_up"], lp["w_down"], combine[:, first:first + held].T))
    return (routed @ lp["w_lat_out"].astype(dtype)
            + _relu2(h, lp["ws_up"], lp["ws_down"], dtype))


MIXERS = {"M": _mamba, "E": _moe, "*": _attention}


def _segments(pattern: str) -> list:
    """``[(unit, repeats)]``: the pattern as stretches of a repeated unit of
    1-4 layers, greedily the longest stretch at each place."""
    i, out = 0, []
    while i < len(pattern):
        best = (pattern[i], 1)
        for u in range(1, 5):
            unit, k = pattern[i:i + u], 1
            while pattern[i + k * u:i + (k + 1) * u] == unit:
                k += 1
            if k >= 2 and k * u > len(best[0]) * best[1]:
                best = (unit, k)
        out.append(best)
        i += len(best[0]) * best[1]
    return out


def forward(cfg, params, ids, dtype=jnp.float32):
    """``ids`` [S] (S a multiple of ``Q_BLOCK``, or under it) -> logits [S,
    vocab rows held]."""
    x = params["embed"][ids].astype(dtype)
    seen = {kind: 0 for kind in STACK}

    def layer(x, kind, index):
        lp = jax.tree_util.tree_map(lambda a: a[index], params[STACK[kind]])
        h = _rms(x, lp["norm"].astype(dtype), cfg.rms_norm_eps)
        return x + MIXERS[kind](cfg, h, lp, dtype)

    for unit, repeats in _segments(cfg.hybrid_override_pattern):
        base = dict(seen)

        def unit_layers(x, r, unit=unit, base=base):
            at = dict(base)
            for kind in unit:
                x = layer(x, kind, at[kind] + r * unit.count(kind))
                at[kind] += 1
            return x, None

        if repeats == 1:
            x, _ = unit_layers(x, 0)
        else:
            x, _ = jax.lax.scan(unit_layers, x, jnp.arange(repeats))
        for kind in unit:
            seen[kind] += repeats
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
    return cfg.hybrid_override_pattern.count(kind)


def _d_inner(cfg) -> int:
    return cfg.mamba_num_heads * cfg.mamba_head_dim


def _conv_width(cfg) -> int:
    return _d_inner(cfg) + 2 * cfg.n_groups * cfg.ssm_state_size


def _layer_params(cfg, kind: str, experts: float) -> float:
    """One layer's parameters with ``experts`` routed experts counted."""
    d = cfg.hidden_size
    if kind == "M":
        di, cw, h = _d_inner(cfg), _conv_width(cfg), cfg.mamba_num_heads
        return (d + d * (di + cw + h)            # norm, W_in
                + (cfg.conv_kernel + 1) * cw     # convolution and its bias
                + 3 * h + di + di * d)           # dt_bias, A_log, D; norm; W_out
    if kind == "*":
        return d + 2 * d * cfg.head_dim * (cfg.num_heads + cfg.num_kv_heads)
    lat = cfg.moe_latent_size
    return (d + d * cfg.num_experts + cfg.num_experts   # norm, router, its bias
            + 2 * d * lat                               # into the latent and out
            + 2 * experts * lat * cfg.moe_intermediate_size
            + 2 * d * cfg.moe_shared_expert_intermediate_size)


def num_params(cfg) -> int:
    """Parameters that live on this rank: its share of the routed experts
    and of the vocabulary, everything else of every layer."""
    d = cfg.hidden_size
    return int(2 * cfg.vocab_size * d + d + sum(
        _layer_params(cfg, kind, _held(cfg))
        for kind in cfg.hybrid_override_pattern))


def active_params(cfg) -> float:
    """Parameters a token's forward pass multiplies by HERE: everything
    outside the routed experts, and of them the ``top_k x held /
    num_experts`` a token picks on this rank on average; the embedding is a
    lookup."""
    d = cfg.hidden_size
    return (cfg.vocab_size * d + d + sum(
        _layer_params(cfg, kind, cfg.top_k * _held(cfg) / cfg.num_experts)
        for kind in cfg.hybrid_override_pattern))


def weight_bytes(cfg, bytes_per_param: int = 2) -> int:
    """Bytes a decode step must read: every held expert's weights, whatever
    the routing of a batch of more than a few tokens; the embedding is a
    lookup."""
    return (num_params(cfg) - cfg.vocab_size * cfg.hidden_size) * bytes_per_param


def kv_bytes_per_token(cfg, bytes_per_value: int = 2) -> int:
    """Bytes of K and V a step must read per context token: the attention
    layers alone have a cache that grows."""
    return 2 * cfg.num_kv_heads * cfg.head_dim * bytes_per_value * _count(cfg, "*")


def attn_flops_per_pair(cfg) -> int:
    """FLOPs of one query x key pair (QK^T and PV), the attention layers."""
    return 4 * cfg.num_heads * cfg.head_dim * _count(cfg, "*")


def state_bytes_per_slot(cfg, bytes_per_value: int = 2) -> int:
    """Bytes of recurrent state one sequence holds, all Mamba layers: ``S``
    in float32 and the last ``K - 1`` rows of ``xBC``; whatever its length."""
    return _count(cfg, "M") * (
        4 * cfg.ssm_state_size * _d_inner(cfg)
        + (cfg.conv_kernel - 1) * _conv_width(cfg) * bytes_per_value)


def ssm_flops_per_token(cfg) -> int:
    """FLOPs of the recurrence as written, a token, all Mamba layers: decay
    and feed the state (3 an element), read it (2)."""
    return 5 * cfg.ssm_state_size * _d_inner(cfg) * _count(cfg, "M")
