"""LFM2-MoE as LiquidAI/LFM2-8B-A1B configures it (``model_type: lfm2_moe``),
plain: the published forward pass in straight ``jax.numpy``.

No kernels, no cache, no batching, nothing imported from the program. ``x``
the residual stream, ``d`` its width, RMSNorm with a weight and eps
``rms_norm_eps`` everywhere.

- ``x = E[ids]``.
- Layer ``i``: ``x += Op_i(RMSNorm(x; norm))``, then ``x += FFN_i(RMSNorm(x;
  ffn_norm))``.
- ``Op`` = gated short convolution (``layer_types[i] == "conv"``): ``[B | C |
  u] = h W_in`` (``d`` each, in that order, no bias); ``z = B * u``; ``c_t =
  sum_{k=0..K-1} w_k * z_{t-K+1+k}`` (``w`` [K, d], ``K`` = ``conv_kernel`` =
  3, ``z`` zero before the first token, no bias, NO activation); ``Op = (C *
  c) W_out``.
- ``Op`` = attention (``"full_attention"``): ``num_heads`` query heads on
  ``num_kv_heads`` K/V heads (32 on 8) of ``d / num_heads`` lanes (64), no
  bias; RMSNorm over each head's lanes on q and on k, then RoPE
  (``rope_theta``, all the lanes, halves rotated); causal softmax in float32
  of ``q . k / sqrt(head_dim)``; ``W_o``.
- ``FFN``, ``i < num_dense_layers``: ``W_down(silu(h W_gate) * (h W_up))``.
- ``FFN``, the rest: ``s = sigmoid(h_f32 W_r)``; the ``top_k`` experts with
  the largest ``s + b`` (``b`` the selection bias: it picks and never
  weighs); their weights are ``s`` divided by their sum + 1e-6
  (``norm_topk_prob``), times ``routed_scaling_factor``; ``FFN = sum_picked
  w_e SwiGLU_e(h)``. No shared expert.
- ``logits = RMSNorm(x; final_norm) E^T`` (the head is the embedding).

Departures from the published code, each for a stated reason:

- The published convolution is a ``Conv1d`` over ``[d, 1, K]`` with left
  padding; the filter here is ``[K, d]``, tap ``k`` of channel ``c`` at ``w[k,
  c]``: the same numbers, the tree the program's ``init_params`` makes.
- Every projection is stored ``[in, out]``.

Memory: a run of layers of one kind (``params["runs"]``: a run a stack) is a
``scan`` over its stack and the experts a ``scan`` over the expert axis (an
expert computes every token and its combine weight is 0 where the router did
not pick it: the same sum as routing), weights cast where they are used, so
beside the served tree (7.86 GB in bfloat16) one expert's or one dense layer's
float32 copy lives at a time.

Also the arithmetic of the model that metrics divide by.
"""

from __future__ import annotations

from itertools import groupby

import jax
import jax.numpy as jnp

Q_BLOCK = 512
HEAD_BLOCK = 16384   # 65,536 = 4 x 16,384 rows of the table at a time
ROUTER_EPS = 1e-6


def _rms(x, w, eps):
    return x * jax.lax.rsqrt((x * x).mean(-1, keepdims=True) + eps) * w


def _rope(x, theta):
    """``x`` [S, H, D] at positions 0 .. S - 1, rotated over ``D`` by
    halves."""
    half = x.shape[-1] // 2
    freqs = 1.0 / (theta ** (jnp.arange(half, dtype=jnp.float32) / half))
    ang = jnp.arange(x.shape[0], dtype=jnp.float32)[:, None] * freqs
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :half].astype(jnp.float32), x[..., half:].astype(jnp.float32)
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin],
                           axis=-1).astype(x.dtype)


def _swiglu(h, wg, wu, wd, dtype):
    return (jax.nn.silu(h @ wg.astype(dtype)) * (h @ wu.astype(dtype))
            ) @ wd.astype(dtype)


def _attention(cfg, h, lp, dtype):
    s = h.shape[0]
    heads, kv = cfg.num_heads, cfg.num_kv_heads
    hd, eps = _head_dim(cfg), cfg.rms_norm_eps
    q = (h @ lp["wq"].astype(dtype)).reshape(s, heads, hd)
    k = (h @ lp["wk"].astype(dtype)).reshape(s, kv, hd)
    v = (h @ lp["wv"].astype(dtype)).reshape(s, kv, hd)
    q = _rope(_rms(q, lp["q_norm"].astype(dtype), eps), cfg.rope_theta)
    k = _rope(_rms(k, lp["k_norm"].astype(dtype), eps), cfg.rope_theta)
    k, v = (jnp.repeat(a, heads // kv, axis=1) for a in (k, v))
    kpos = jnp.arange(s)
    blk = min(Q_BLOCK, s)

    def block(q0):
        qb = jax.lax.dynamic_slice_in_dim(q, q0, blk, axis=0)
        scores = jnp.einsum("qhd,khd->hqk", qb, k) * jnp.asarray(
            hd ** -0.5, dtype)
        ok = kpos[None, :] <= (q0 + jnp.arange(blk))[:, None]
        p = jax.nn.softmax(jnp.where(ok[None], scores, -jnp.inf).astype(
            jnp.float32), axis=-1).astype(dtype)
        return jnp.einsum("hqk,khd->qhd", p, v)

    o = jax.lax.map(block, jnp.arange(0, s, blk))
    return o.reshape(s, heads * hd) @ lp["wo"].astype(dtype)


def _conv(cfg, h, lp, dtype):
    s, d, k = h.shape[0], cfg.hidden_size, cfg.conv_kernel
    bcu = h @ lp["w_in"].astype(dtype)
    gate_in, gate_out, u = bcu[:, :d], bcu[:, d:2 * d], bcu[:, 2 * d:]
    # causal depthwise filter over z = B * u: row t sees rows t-K+1 .. t
    z = jnp.concatenate([jnp.zeros((k - 1, d), dtype), gate_in * u])
    c = sum(z[j:j + s] * lp["conv_w"][j].astype(dtype) for j in range(k))
    return (gate_out * c) @ lp["w_out"].astype(dtype)


OPERATORS = {"conv": _conv, "full_attention": _attention}


def router_picks(cfg, h, ffn):
    """``(weights [S, top_k] float32, experts [S, top_k])`` of the normed rows
    ``h``: the sigmoid scores, the picks by score + bias, the weights the
    scores alone, normalised over the picks."""
    scores = jax.nn.sigmoid(h.astype(jnp.float32)
                            @ ffn["router"].astype(jnp.float32))
    picking = scores
    if cfg.use_expert_bias:
        picking = scores + ffn["router_bias"].astype(jnp.float32)
    _, top_i = jax.lax.top_k(picking, cfg.top_k)
    top_w = jnp.take_along_axis(scores, top_i, axis=-1)
    if cfg.norm_topk_prob:
        top_w = top_w / (top_w.sum(-1, keepdims=True) + ROUTER_EPS)
    return top_w * cfg.routed_scaling_factor, top_i


def _moe(cfg, h, ffn, dtype):
    top_w, top_i = router_picks(cfg, h, ffn)
    # combine[t, e]: the weight where e is among t's picks, else 0
    combine = (jax.nn.one_hot(top_i, cfg.num_experts, dtype=jnp.float32)
               * top_w[..., None]).sum(1)

    def expert(acc, we):
        wg, wu, wd, c = we
        return acc + _swiglu(h, wg, wu, wd, dtype) * c[:, None].astype(dtype), None

    out, _ = jax.lax.scan(expert, jnp.zeros_like(h),
                          (ffn["w_gate"], ffn["w_up"], ffn["w_down"], combine.T))
    return out


def kinds(cfg) -> list:
    """Each layer's ``(operator, ffn)``: the published ``layer_types`` and
    ``"dense"`` for the first ``num_dense_layers`` layers, ``"moe"`` after."""
    return [(op, "dense" if i < cfg.num_dense_layers else "moe")
            for i, op in enumerate(cfg.layer_types)]


def _layer(cfg, kind, x, lp, dtype):
    op, ffn_kind = kind
    h = _rms(x, lp["norm"].astype(dtype), cfg.rms_norm_eps)
    x = x + OPERATORS[op](cfg, h, lp["mix"], dtype)
    h = _rms(x, lp["ffn_norm"].astype(dtype), cfg.rms_norm_eps)
    ffn = lp["ffn"]
    if ffn_kind == "dense":
        return x + _swiglu(h, ffn["w_gate"], ffn["w_up"], ffn["w_down"], dtype)
    return x + _moe(cfg, h, ffn, dtype)


def forward(cfg, params, ids, dtype=jnp.float32):
    """``ids`` [S] (S a multiple of ``Q_BLOCK``, or under it) -> logits [S,
    vocab]."""
    x = params["embed"][ids].astype(dtype)
    runs = [kind for kind, _ in groupby(kinds(cfg))]
    for kind, stack in zip(runs, params["runs"]):
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
def _count(cfg, operator: str) -> int:
    return list(cfg.layer_types).count(operator)


def _head_dim(cfg) -> int:
    return cfg.hidden_size // cfg.num_heads


def _moe_layers(cfg) -> int:
    return cfg.num_layers - cfg.num_dense_layers


def mixer_params(cfg, operator: str) -> dict:
    """One operator's parameters, term by term."""
    d = cfg.hidden_size
    if operator == "full_attention":
        hd = _head_dim(cfg)
        return {"q": d * cfg.num_heads * hd, "k": d * cfg.num_kv_heads * hd,
                "v": d * cfg.num_kv_heads * hd, "o": cfg.num_heads * hd * d,
                "q_norm": hd, "k_norm": hd}
    return {"in_proj": d * 3 * d, "conv": cfg.conv_kernel * d,
            "out_proj": d * d}


def ffn_params(cfg, ffn: str, experts: int) -> int:
    """One FFN: the dense gated MLP, or the router, its selection bias and
    ``experts`` routed experts."""
    d = cfg.hidden_size
    if ffn == "dense":
        return 3 * d * cfg.intermediate_size
    return (d * cfg.num_experts + cfg.num_experts
            + experts * 3 * d * cfg.moe_intermediate_size)


def _params(cfg, experts: int) -> int:
    d = cfg.hidden_size
    return cfg.vocab_size * d + d + sum(
        sum(mixer_params(cfg, op).values()) + ffn_params(cfg, ffn, experts)
        + 2 * d for op, ffn in kinds(cfg))


def num_params(cfg) -> int:
    """Every parameter; the table once (the head is the table)."""
    return _params(cfg, cfg.num_experts)


def active_params(cfg) -> int:
    """Parameters a token's forward pass multiplies by: ``top_k`` routed
    experts a layer (what the architecture requires, not what an all-experts
    einsum spends), the operators, the routers, the table once, as the head
    (the embedding is a lookup)."""
    return _params(cfg, cfg.top_k)


def weight_bytes(cfg, bytes_per_param: int = 2) -> int:
    """Bytes a step must read: every expert's weights, whatever the routing of
    a batch of more than a few tokens, the table once (as the head)."""
    return num_params(cfg) * bytes_per_param


def kv_bytes_per_token(cfg, bytes_per_value: int = 2) -> int:
    """Bytes of K and V a step must read per context token: the attention
    layers alone have a cache that grows."""
    return (2 * cfg.num_kv_heads * _head_dim(cfg) * bytes_per_value
            * _count(cfg, "full_attention"))


def attn_flops_per_pair(cfg) -> int:
    """FLOPs of one query x key pair (QK^T and PV), the attention layers."""
    return 4 * cfg.num_heads * _head_dim(cfg) * _count(cfg, "full_attention")


def state_bytes_per_slot(cfg, bytes_per_value: int = 2) -> int:
    """Bytes of carried state one sequence holds, all convolution layers: the
    last ``K - 1`` rows of ``z``, whatever its length."""
    return (_count(cfg, "conv") * (cfg.conv_kernel - 1) * cfg.hidden_size
            * bytes_per_value)


def ssm_flops_per_token(cfg) -> int:
    """FLOPs of the convolution as written, a token, all convolution layers:
    the two gates (1 a lane each) and ``K`` taps (a multiply and an add each,
    the first tap's add none)."""
    return ((2 + 2 * cfg.conv_kernel - 1) * cfg.hidden_size
            * _count(cfg, "conv"))


def held_expert_slots(cfg) -> int:
    """Expert weight sets the deployment holds: every expert of every expert
    layer."""
    return _moe_layers(cfg) * cfg.num_experts
