"""Jamba as ai21labs/AI21-Jamba2-3B configures it (``modeling_jamba.py``),
plain: the published forward pass in straight ``jax.numpy``.

No kernels, no cache, no batching, nothing imported from the program.

- ``h = E[ids]``.
- Every layer: ``h += Mixer(RMSNorm(h))``, then ``h += W_down(silu(u W_gate)
  * (u W_up))``, ``u = RMSNorm(h)`` (eps ``rms_norm_eps``). Layer ``i`` is
  attention iff ``i mod attn_layer_period == attn_layer_offset``, else Mamba;
  every FFN is the dense gated MLP (``num_experts`` 1).
- attention: ``num_heads`` query heads on ``num_kv_heads`` K/V heads (20 on
  1), no bias, NO positional term; causal softmax in float32 of ``q . k /
  sqrt(head_dim)``.
- Mamba (Mamba-1): ``[x | z] = h W_in``; ``x = silu(causal depthwise
  conv_K(x) + b)``; ``[dt_r | B | C] = x W_x`` (``dt_rank`` | ``N`` | ``N``),
  each through its own RMSNorm; ``dt = softplus(dt_r W_dt + b_dt)``, ``A =
  -exp(A_log)``; the recurrence as a ``lax.scan`` over the TOKENS, ``S_t =
  exp(dt_t (x) A) S_{t-1} + B_t (x) (dt_t x_t)`` (``S`` [N, d_inner]), ``y_t
  = S_t^T C_t + D x_t``; ``out = (y silu(z)) W_out``.
- ``logits = RMSNorm(h) E^T`` (the head is the embedding).

Departures from the published code, each for a stated reason:

- ``A_log`` is kept ``[N, d_inner]``, the transpose of the published
  ``[d_inner, N]``: the tree the program's ``init_params`` makes, the same
  numbers. ``W_x`` is ``[dt_rank + 2 N, d_inner]`` as published (a Linear's
  ``[out, in]``); every other projection is stored ``[in, out]``.
- The published fused scan (``use_mamba_kernels``) is the recurrence above
  blocked for a GPU; token by token is its definition.

Memory: a run of layers of one kind (``params["runs"]``: a run a stack) is a
``scan`` over its stack, and a layer's weights are cast where they are used,
so beside the served tree (6.06 GB in bfloat16) one layer's float32 copy lives
at a time, never the model's (12 GB).

Also the arithmetic of the model that metrics divide by.
"""

from __future__ import annotations

from itertools import groupby

import jax
import jax.numpy as jnp

Q_BLOCK = 512
HEAD_BLOCK = 16384   # 65,536 = 4 x 16,384 rows of the table at a time


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
            hd ** -0.5, dtype)
        ok = kpos[None, :] <= (q0 + jnp.arange(qb.shape[0]))[:, None]
        p = jax.nn.softmax(jnp.where(ok[None], scores, -jnp.inf).astype(
            jnp.float32), axis=-1).astype(dtype)
        return jnp.einsum("hqk,khd->qhd", p, v)

    o = jax.lax.map(block, jnp.arange(0, s, min(Q_BLOCK, s)))
    return o.reshape(s, heads * hd) @ lp["wo"].astype(dtype)


def _mamba(cfg, h, lp, dtype):
    s = h.shape[0]
    di, n, r, k = _d_inner(cfg), cfg.ssm_state_size, cfg.dt_rank, cfg.conv_kernel
    eps = cfg.rms_norm_eps
    xz = h @ lp["w_in"].astype(dtype)
    x, z = xz[:, :di], xz[:, di:]
    # causal depthwise convolution over x alone: row t sees rows t-K+1 .. t
    padded = jnp.concatenate([jnp.zeros((k - 1, di), dtype), x])
    x = jax.nn.silu(lp["conv_b"].astype(dtype) + sum(
        padded[j:j + s] * lp["conv_w"][j].astype(dtype) for j in range(k)))
    proj = x @ lp["w_x"].astype(dtype).T
    dt_r = _rms(proj[:, :r], lp["dt_norm"].astype(dtype), eps)
    b = _rms(proj[:, r:r + n], lp["b_norm"].astype(dtype), eps)
    c = _rms(proj[:, r + n:], lp["c_norm"].astype(dtype), eps)
    dt = jax.nn.softplus(dt_r @ lp["w_dt"].astype(dtype)
                         + lp["dt_bias"].astype(dtype))             # [S, di]
    a = -jnp.exp(lp["a_log"].astype(dtype))                         # [N, di]

    def token(state, xs):
        x_t, b_t, c_t, dt_t = xs
        state = jnp.exp(dt_t * a) * state + b_t[:, None] * (dt_t * x_t)
        return state, c_t @ state

    _, y = jax.lax.scan(token, jnp.zeros((n, di), dtype), (x, b, c, dt))
    y = (y + lp["d_skip"].astype(dtype) * x) * jax.nn.silu(z)
    return y @ lp["w_out"].astype(dtype)


MIXERS = {"mamba": _mamba, "attention": _attention}


def layer_types(cfg) -> list:
    """The published rule: layer ``i`` is attention iff ``i mod
    attn_layer_period == attn_layer_offset``."""
    return ["attention" if i % cfg.attn_layer_period == cfg.attn_layer_offset
            else "mamba" for i in range(cfg.num_layers)]


def _layer(cfg, kind, x, lp, dtype):
    h = _rms(x, lp["norm"].astype(dtype), cfg.rms_norm_eps)
    x = x + MIXERS[kind](cfg, h, lp["mix"], dtype)
    h = _rms(x, lp["ffn_norm"].astype(dtype), cfg.rms_norm_eps)
    ffn = lp["ffn"]
    return x + _gated(h, ffn["w_gate"], ffn["w_up"], ffn["w_down"], dtype)


def forward(cfg, params, ids, dtype=jnp.float32):
    """``ids`` [S] (S a multiple of ``Q_BLOCK``, or under it) -> logits [S,
    vocab]."""
    x = params["embed"][ids].astype(dtype)
    kinds = [kind for kind, _ in groupby(layer_types(cfg))]
    for kind, stack in zip(kinds, params["runs"]):
        x, _ = jax.lax.scan(
            lambda x, lp, kind=kind: (_layer(cfg, kind, x, lp, dtype), None),
            x, stack)
    x = _rms(x, params["final_norm"].astype(dtype), cfg.rms_norm_eps)
    return _head(x, params["embed"], dtype)


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
def _count(cfg, kind: str) -> int:
    return layer_types(cfg).count(kind)


def _d_inner(cfg) -> int:
    return cfg.expand * cfg.hidden_size


def _head_dim(cfg) -> int:
    return cfg.hidden_size // cfg.num_heads


def mixer_params(cfg, kind: str) -> dict:
    """One mixer's parameters, term by term."""
    d = cfg.hidden_size
    if kind == "attention":
        hd = _head_dim(cfg)
        return {"q": d * cfg.num_heads * hd, "k": d * cfg.num_kv_heads * hd,
                "v": d * cfg.num_kv_heads * hd, "o": cfg.num_heads * hd * d}
    di, n, r = _d_inner(cfg), cfg.ssm_state_size, cfg.dt_rank
    return {"in_proj": d * 2 * di, "conv": (cfg.conv_kernel + 1) * di,
            "x_proj": di * (r + 2 * n), "dt_proj": r * di + di,
            "a_log": n * di, "d": di, "inner_norms": r + 2 * n,
            "out_proj": di * d}


def layer_params(cfg, kind: str) -> int:
    """One layer: the mixer, the dense gated MLP, the two norms."""
    d = cfg.hidden_size
    return (sum(mixer_params(cfg, kind).values())
            + 3 * d * cfg.intermediate_size + 2 * d)


def num_params(cfg) -> int:
    """Every parameter; the table once (the head is the table)."""
    d = cfg.hidden_size
    return cfg.vocab_size * d + d + sum(
        layer_params(cfg, kind) for kind in layer_types(cfg))


def active_params(cfg) -> int:
    """Parameters a token's forward pass multiplies by: all of them (dense),
    the table once, as the head (the embedding is a lookup)."""
    return num_params(cfg)


def weight_bytes(cfg, bytes_per_param: int = 2) -> int:
    """Bytes a decode step must read: every weight, the table once."""
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
    in float32 and the last ``K - 1`` rows of ``x``; whatever its length."""
    di = _d_inner(cfg)
    return _count(cfg, "mamba") * (
        4 * cfg.ssm_state_size * di
        + (cfg.conv_kernel - 1) * di * bytes_per_value)


def ssm_flops_per_token(cfg) -> int:
    """FLOPs of the recurrence as written, a token, all Mamba layers, 7 a
    state update: ``dt A`` (1), the decay times the state (1), ``B (dt x)``
    (1, and ``dt x`` itself, a channel's, counted with it: 1), the add (1),
    the reading ``S C`` (2). The ``exp`` is counted beside
    (``ssm_exps_per_token``)."""
    return 7 * cfg.ssm_state_size * _d_inner(cfg) * _count(cfg, "mamba")


def ssm_exps_per_token(cfg) -> int:
    """``exp`` evaluations of the recurrence a token, all Mamba layers: one a
    state update (the decay of every channel and state index)."""
    return cfg.ssm_state_size * _d_inner(cfg) * _count(cfg, "mamba")


def scan_io_bytes_per_token(cfg, bytes_per_value: int = 2) -> int:
    """Bytes the scan must move a prompt token, all Mamba layers, beside its
    slot's state: ``x`` and ``dt`` [d_inner] and ``B``, ``C`` [N] in, ``y``
    [d_inner] out, at the activations' width."""
    return ((3 * _d_inner(cfg) + 2 * cfg.ssm_state_size) * bytes_per_value
            * _count(cfg, "mamba"))
