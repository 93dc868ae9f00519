"""Granite-4.0-H as ibm-granite/granite-4.0-h-small configures it
(``modeling_granitemoehybrid.py``), plain: the published forward pass in
straight ``jax.numpy``.

No kernels, no cache, no chunked form, nothing imported from the program.

- ``h = E[ids] * embedding_multiplier``.
- Every layer: ``h += residual_multiplier * Mixer(RMSNorm(h))``, then ``h +=
  residual_multiplier * (MoE(x) + Shared(x))``, ``x = RMSNorm(h)`` (eps
  ``rms_norm_eps``); ``layer_types`` says which mixer.
- ``attention``: grouped-query, no bias, NO positional embedding; causal
  softmax in float32 of ``q . k * attention_multiplier`` (1/128 as published,
  not ``head_dim ** -0.5``).
- ``mamba``, Mamba-2 at ``G = n_groups`` groups (1 as published): ``[z | xBC |
  dt] = h W_in`` (``d_inner`` | ``d_inner + 2 G N`` | ``H`` wide); ``xBC =
  silu(causal depthwise conv_K(xBC) + b)``; ``x`` [H, P], ``B``, ``C`` [G, N];
  ``dt = softplus(dt + dt_bias)``, ``A = -exp(A_log)``; the recurrence as a
  ``lax.scan`` over the TOKENS, ``S_t = exp(dt_t A) S_{t-1} + dt_t x_t (x)
  B_t`` (``S`` [H, P, N]), ``y_t = S_t C_t + D x_t``; ``y = RMSNorm(y
  silu(z))`` in ``G`` groups (one: all ``d_inner`` lanes), its own weight;
  ``out = y W_out``.
- experts: ``l = x_f32 W_r`` over ALL the routed experts; the ``top_k``
  largest logits; weights the softmax over those ``top_k`` logits; ``sum_picks
  w_e (silu(x W_gate,e) * (x W_up,e)) W_down,e``; beside it the shared MLP of
  the same form.
- ``logits = RMSNorm(h) E^T / logits_scaling`` (the head is the embedding).

Departures from the published code, each for a stated reason:

- ``input_linear`` (``[2 x width, hidden]`` an expert, ``[gate; up]``) is two
  matrices ``w_gate`` / ``w_up`` here, stored ``[hidden, width]``: the same
  products, the tree the program's ``init_params`` makes.
- No ``time_step_limit`` clamp of ``dt`` (the published default is (0, inf):
  no clamp).
- The published chunked scan (``mamba_chunk_size`` 256) is the recurrence
  above blocked for a GPU; token by token is its definition.
- One rank's share: the parameter tree holds experts ``expert_rank x held ..``
  of the routed ones (and a share of the vocabulary's rows, which the tree's
  shapes already are). The router's picks of experts that are not here add
  nothing, here as in the program: the other ranks' parts.

Memory and compile time: the experts run one at a time (a ``scan`` over the
held experts), and a run of layers of one kind (``params["runs"]``: a run a
stack) is a ``scan`` over its stack, so the program compiles one layer a run.

Also the arithmetic of the model that metrics divide by.
"""

from __future__ import annotations

from itertools import groupby

import jax
import jax.numpy as jnp

Q_BLOCK = 512
HEAD_BLOCK = 12544   # 50,176 = 4 x 12,544 rows of the table at a time


def _rms(x, w, eps):
    return x * jax.lax.rsqrt((x * x).mean(-1, keepdims=True) + eps) * w


def _gated(h, w_gate, w_up, w_down, dtype):
    return (jax.nn.silu(h @ w_gate.astype(dtype)) * (h @ w_up.astype(dtype))
            ) @ w_down.astype(dtype)


def _attention(cfg, h, lp, dtype):
    s = h.shape[0]
    heads, kv = cfg.num_heads, cfg.num_kv_heads
    hd = cfg.hidden_size // heads
    q = (h @ lp["wq"].astype(dtype)).reshape(s, heads, hd)
    k = jnp.repeat((h @ lp["wk"].astype(dtype)).reshape(s, kv, hd),
                   heads // kv, axis=1)
    v = jnp.repeat((h @ lp["wv"].astype(dtype)).reshape(s, kv, hd),
                   heads // kv, axis=1)
    kpos = jnp.arange(s)

    def block(q0):
        qb = jax.lax.dynamic_slice_in_dim(q, q0, min(Q_BLOCK, s), axis=0)
        scores = jnp.einsum("qhd,khd->hqk", qb, k) * jnp.asarray(
            cfg.attention_multiplier, dtype)
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


def _routed(cfg, h, lp, dtype):
    """The held experts' part of the routed sum, ``[S, D]``."""
    logits = h.astype(jnp.float32) @ lp["router"].astype(jnp.float32)
    top_l, top_i = jax.lax.top_k(logits, cfg.top_k)
    top_w = jax.nn.softmax(top_l, axis=-1)
    # combine[t, e]: the weight where routed expert e is among t's picks
    combine = (jax.nn.one_hot(top_i, cfg.num_experts, dtype=jnp.float32)
               * top_w[..., None]).sum(1)
    held = lp["w_up"].shape[0]
    first = cfg.expert_rank * held

    def expert(acc, we):
        w_gate, w_up, w_down, c = we
        return acc + _gated(h, w_gate, w_up, w_down, dtype) \
            * c[:, None].astype(dtype), None

    routed, _ = jax.lax.scan(
        expert, jnp.zeros_like(h),
        (lp["w_gate"], lp["w_up"], lp["w_down"],
         combine[:, first:first + held].T))
    return routed


def _ffn(cfg, h, lp, dtype):
    """The expert block on normed rows: routed experts + the shared MLP."""
    return _routed(cfg, h, lp, dtype) + _gated(
        h, lp["ws_gate"], lp["ws_up"], lp["ws_down"], dtype)


MIXERS = {"mamba": _mamba, "attention": _attention}


def _layer(cfg, kind, x, lp, dtype):
    r = jnp.asarray(cfg.residual_multiplier, dtype)
    h = _rms(x, lp["norm"].astype(dtype), cfg.rms_norm_eps)
    x = x + MIXERS[kind](cfg, h, lp["mix"], dtype) * r
    h = _rms(x, lp["ffn_norm"].astype(dtype), cfg.rms_norm_eps)
    return x + _ffn(cfg, h, lp["ffn"], dtype) * r


def forward(cfg, params, ids, dtype=jnp.float32):
    """``ids`` [S] (S a multiple of ``Q_BLOCK``, or under it) -> logits [S,
    vocab rows held]."""
    x = params["embed"][ids].astype(dtype) * jnp.asarray(
        cfg.embedding_multiplier, dtype)
    kinds = [kind for kind, _ in groupby(cfg.layer_types)]
    for kind, stack in zip(kinds, params["runs"]):
        x, _ = jax.lax.scan(
            lambda x, lp, kind=kind: (_layer(cfg, kind, x, lp, dtype), None),
            x, stack)
    x = _rms(x, params["final_norm"].astype(dtype), cfg.rms_norm_eps)
    return _head(x, params["embed"], dtype) / jnp.asarray(
        cfg.logits_scaling, dtype)


def _head(x, table, dtype):
    """``x @ table.T`` in ``dtype``, ``HEAD_BLOCK`` rows of the table at a
    time."""
    vocab = table.shape[0]
    if vocab % HEAD_BLOCK:
        return x @ table.astype(dtype).T

    def block(i, out):
        rows = jax.lax.dynamic_slice_in_dim(table, i * HEAD_BLOCK, HEAD_BLOCK)
        return jax.lax.dynamic_update_slice_in_dim(
            out, x @ rows.astype(dtype).T, i * HEAD_BLOCK, axis=1)

    return jax.lax.fori_loop(0, vocab // HEAD_BLOCK, block,
                             jnp.zeros((x.shape[0], vocab), dtype))


# ------------------------------------------------------- model arithmetic
def _held(cfg) -> int:
    return cfg.num_experts if cfg.experts_held is None else cfg.experts_held


def _count(cfg, kind: str) -> int:
    return list(cfg.layer_types).count(kind)


def _d_inner(cfg) -> int:
    return cfg.mamba_num_heads * cfg.mamba_head_dim


def _conv_width(cfg) -> int:
    return _d_inner(cfg) + 2 * cfg.n_groups * cfg.ssm_state_size


def _head_dim(cfg) -> int:
    return cfg.hidden_size // cfg.num_heads


def _layer_params(cfg, kind: str, experts: float) -> float:
    """One layer's parameters with ``experts`` routed experts counted."""
    d = cfg.hidden_size
    if kind == "mamba":
        di, cw, h = _d_inner(cfg), _conv_width(cfg), cfg.mamba_num_heads
        mixer = (d * (di + cw + h)                # W_in
                 + (cfg.conv_kernel + 1) * cw     # convolution and its bias
                 + 3 * h + di + di * d)           # dt_bias, A_log, D; norm; W_out
    else:
        mixer = 2 * d * _head_dim(cfg) * (cfg.num_heads + cfg.num_kv_heads)
    return (2 * d + mixer                         # the two norms, the mixer
            + d * cfg.num_experts                 # the router
            + 3 * experts * d * cfg.intermediate_size
            + 3 * d * cfg.shared_intermediate_size)


def num_params(cfg) -> int:
    """Parameters that live on this rank: its share of the routed experts and
    of the vocabulary (once: the head is the table), everything else of every
    layer."""
    d = cfg.hidden_size
    return int(cfg.vocab_size * d + d + sum(
        _layer_params(cfg, kind, _held(cfg)) for kind in cfg.layer_types))


def active_params(cfg) -> float:
    """Parameters a token's forward pass multiplies by HERE: everything
    outside the routed experts, of them the ``top_k x held / num_experts`` a
    token picks on this rank on average, and the table once, as the head (the
    embedding is a lookup)."""
    d = cfg.hidden_size
    return (cfg.vocab_size * d + d + sum(
        _layer_params(cfg, kind, cfg.top_k * _held(cfg) / cfg.num_experts)
        for kind in cfg.layer_types))


def weight_bytes(cfg, bytes_per_param: int = 2) -> int:
    """Bytes a decode step must read: every held expert's weights, whatever
    the routing of a batch of more than a few tokens, and the table once, as
    the head."""
    return num_params(cfg) * bytes_per_param


def kv_bytes_per_token(cfg, bytes_per_value: int = 2) -> int:
    """Bytes of K and V a step must read per context token: the attention
    layers alone have a cache that grows."""
    return (2 * cfg.num_kv_heads * _head_dim(cfg) * bytes_per_value
            * _count(cfg, "attention"))


def attn_flops_per_pair(cfg) -> int:
    """FLOPs of one query x key pair (QK^T and PV), the attention layers."""
    return 4 * cfg.num_heads * _head_dim(cfg) * _count(cfg, "attention")


def state_bytes_per_slot(cfg, bytes_per_value: int = 2) -> int:
    """Bytes of recurrent state one sequence holds, all Mamba layers: ``S``
    in float32 and the last ``K - 1`` rows of ``xBC``; whatever its length."""
    return _count(cfg, "mamba") * (
        4 * cfg.ssm_state_size * _d_inner(cfg)
        + (cfg.conv_kernel - 1) * _conv_width(cfg) * bytes_per_value)


def ssm_flops_per_token(cfg) -> int:
    """FLOPs of the recurrence as written, a token, all Mamba layers: decay
    and feed the state (3 an element), read it (2)."""
    return 5 * cfg.ssm_state_size * _d_inner(cfg) * _count(cfg, "mamba")
