"""LongCat-Flash (the language model of LongCat-Flash-Omni), plain: the
published forward pass in straight ``jax.numpy``.

No kernels, no cache, no absorbed attention, nothing imported from the
program. Every layer is a DOUBLE layer; ``a`` in {0, 1} indexes its two
sublayers, each with its own weights and norms (``params["layers"]["sub"][a]``
in the program's parameter tree, every leaf stacked ``[L, ...]``). Pre-norm
RMSNorm everywhere::

    MLA_a(h, positions):
      cq  = RMSNorm(h W_qa[a]) * sqrt(d / r)          mla_scale_q_lora
      q   = cq W_qb[a] -> [S, H, nope + rope];  q_rope = RoPE(q[..., nope:])
      kva = h W_kva[a] -> [S, lat + rope]
      c   = RMSNorm(kva[:, :lat]) * sqrt(d / lat)     mla_scale_kv_lora
      k_rope = RoPE(kva[:, lat:])                     one head for all H, not scaled
      kv  = c W_kvb[a] -> [S, H, nope + v]; k = [kv[..., :nope], k_rope]; v = kv[..., nope:]
      o   = causal_softmax(q . k * (nope + rope)^-0.5) v;  o W_o[a]

    Router(h), float32:
      s    = softmax(h W_r)         over E + Z outputs (E routed, Z zero-compute)
      pick = top_k(s + e_score_correction_bias, k)    the bias selects, never weighs
      w    = s[pick] * routed_scaling_factor          NOT renormalised
    ScMoE(h) = sum over picks i:  w_i * SwiGLU_i(h)  if pick_i < E
                                  w_i * h            if pick_i >= E  (identity)

    Layer(x):
      x1 = x  + MLA_0(RMSNorm_in0(x));   h1 = RMSNorm_post0(x1)
      m  = ScMoE(h1)                                  computed here ...
      x2 = x1 + SwiGLU_dense0(h1)
      x3 = x2 + MLA_1(RMSNorm_in1(x2))
      x4 = x3 + SwiGLU_dense1(RMSNorm_post1(x3)) + m  ... added here
    Model: embed -> Layer x num_layers -> RMSNorm -> lm_head (untied)

One rank's share, as the program is given it: the tree holds ``held`` of the
``num_experts`` routed experts, those from ``expert_rank x held`` on; the
router scores all ``E + Z`` outputs; a pick of a routed expert that is not
held adds nothing (another rank's part); the identity picks are computed here
for every token.

Departures from the published code (the configuration file lists them under
``assumed``): half-split rotation in place of interleaved pairs; the two
``mla_scale_*`` factors multiply after the latent norms; no router bias term,
``norm_topk_prob`` false.

Memory: layers run one at a time and experts one at a time (a ``scan`` over
the held experts converts one expert's three matrices to ``dtype`` inside its
body); attention runs ``Q_BLOCK`` query rows at a time. A sequence is padded
INSIDE to a multiple of ``PAD_TO`` (causal: the padding is inert), which the
harness's own padding (1,024) already is.

Also the arithmetic of the model that metrics divide by.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

Q_BLOCK = 512
PAD_TO = 1024


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
    """One sublayer's attention (``lp``: its tree) on the normed ``h`` [S, d]."""
    s, d = h.shape
    heads, lat, r = cfg.num_heads, cfg.kv_lora_rank, cfg.q_lora_rank
    nope, rope, vd = cfg.qk_nope_head_dim, cfg.qk_rope_head_dim, cfg.v_head_dim
    cq = _rms(h @ lp["wq_a"].astype(dtype), lp["q_norm"].astype(dtype),
              cfg.rms_norm_eps)
    if cfg.mla_scale_q_lora:
        cq = cq * jnp.asarray((d / r) ** 0.5, dtype)
    q = (cq @ lp["wq_b"].astype(dtype)).reshape(s, heads, nope + rope)
    kva = h @ lp["wkv_a"].astype(dtype)
    c = _rms(kva[:, :lat], lp["kv_norm"].astype(dtype), cfg.rms_norm_eps)
    if cfg.mla_scale_kv_lora:
        c = c * jnp.asarray((d / lat) ** 0.5, dtype)
    k_rope = _rope(kva[:, None, lat:], pos, cfg.rope_theta)      # [S, 1, rope]
    q_rope = _rope(q[..., nope:], pos, cfg.rope_theta)
    kv = (c @ lp["wkv_b"].astype(dtype)).reshape(s, heads, nope + vd)
    q = jnp.concatenate([q[..., :nope], q_rope], axis=-1)
    k = jnp.concatenate([kv[..., :nope],
                         jnp.broadcast_to(k_rope, (s, heads, rope))], axis=-1)
    o = _attention(q, k, kv[..., nope:], (nope + rope) ** -0.5, dtype)
    return o.reshape(s, heads * vd) @ lp["wo"].astype(dtype)


def route(cfg, h, lp):
    """The router on ``h`` [S, d], float32 -> (weights [S, k], picks [S, k]
    over the ``E + Z`` outputs)."""
    scores = jax.nn.softmax(h.astype(jnp.float32)
                            @ lp["router"].astype(jnp.float32), axis=-1)
    _, picks = jax.lax.top_k(
        scores + lp["router_bias"].astype(jnp.float32), cfg.top_k)
    weights = jnp.take_along_axis(scores, picks, axis=-1)
    return weights * cfg.routed_scaling_factor, picks


def _scmoe(cfg, h, lp, dtype):
    """The expert branch on ``h`` [S, d]: the held experts one at a time, and
    the identity experts as ``w * h``."""
    weights, picks = route(cfg, h, lp)
    held = lp["w_up"].shape[0]
    local = picks - cfg.expert_rank * held
    # combine[s, j]: the weight where held expert j is among s's picks, else 0
    combine = (jax.nn.one_hot(jnp.where((local >= 0) & (picks < cfg.num_experts),
                                        local, -1), held, dtype=jnp.float32)
               * weights[..., None]).sum(1)
    identity = jnp.where(picks >= cfg.num_experts, weights, 0.0).sum(-1)

    def expert(acc, we):
        wg, wu, wd, c = we
        return acc + _swiglu(h, wg, wu, wd, dtype) * c[:, None].astype(dtype), None

    out, _ = jax.lax.scan(expert, jnp.zeros_like(h),
                          (lp["w_gate"], lp["w_up"], lp["w_down"], combine.T))
    return out + identity[:, None].astype(dtype) * h


def forward(cfg, params, ids, dtype=jnp.float32):
    """``ids`` [S] -> logits [S, vocab]; inside, ``S`` is padded to a
    multiple of ``PAD_TO`` (and so of ``Q_BLOCK``)."""
    return _run(cfg, params, ids, dtype)[0]


def router_picks(cfg, params, ids, dtype=jnp.float32):
    """``ids`` [S] -> the router's picks of every layer, [L, S, k] over the
    ``E + Z`` outputs, in the forward pass ``forward`` computes."""
    return _run(cfg, params, ids, dtype)[1]


def _run(cfg, params, ids, dtype):
    n = ids.shape[0]
    ids = jnp.pad(ids, (0, -n % PAD_TO))
    pos = jnp.arange(ids.shape[0])
    x = params["embed"][ids].astype(dtype)
    eps = cfg.rms_norm_eps

    def dense(h, sub):
        return _swiglu(h, sub["wd_gate"], sub["wd_up"], sub["wd_down"], dtype)

    def layer(x, lp):
        s0, s1 = lp["sub"]
        x1 = x + _mla(cfg, _rms(x, s0["attn_norm"].astype(dtype), eps), s0,
                      pos, dtype)
        h1 = _rms(x1, s0["mlp_norm"].astype(dtype), eps)
        m = _scmoe(cfg, h1, lp, dtype)
        picks = route(cfg, h1, lp)[1]
        x2 = x1 + dense(h1, s0)
        x3 = x2 + _mla(cfg, _rms(x2, s1["attn_norm"].astype(dtype), eps), s1,
                       pos, dtype)
        h3 = _rms(x3, s1["mlp_norm"].astype(dtype), eps)
        return x3 + dense(h3, s1) + m, picks

    x, picks = jax.lax.scan(layer, x, params["layers"])
    x = _rms(x, params["final_norm"].astype(dtype), eps)
    return (x @ params["lm_head"].astype(dtype))[:n], picks[:, :n]


# ------------------------------------------------------- model arithmetic
def _held(cfg) -> int:
    return cfg.num_experts if cfg.experts_held is None else cfg.experts_held


def held_expert_slots(cfg) -> int:
    """The held experts of all layers: what a step's picks of held experts
    spread over (``sched.moe_held_rows_per_expert``)."""
    return _held(cfg) * cfg.num_layers


def _outputs(cfg) -> int:
    return cfg.num_experts + cfg.zero_expert_num


def _sublayer_params(cfg) -> int:
    """One MLA sublayer, its two norms, the dense FFN after it."""
    d, h, r, lat = (cfg.hidden_size, cfg.num_heads, cfg.q_lora_rank,
                    cfg.kv_lora_rank)
    return (d * r + r + r * h * (cfg.qk_nope_head_dim + cfg.qk_rope_head_dim)
            + d * (lat + cfg.qk_rope_head_dim) + lat
            + lat * h * (cfg.qk_nope_head_dim + cfg.v_head_dim)
            + h * cfg.v_head_dim * d + 2 * d
            + 3 * d * cfg.ffn_hidden_size)


def _layer_params(cfg, experts) -> float:
    """A double layer with ``experts`` routed experts counted: two sublayers,
    the router over all ``E + Z`` outputs and its selection bias, the
    experts (a zero-compute expert has no parameter)."""
    return (2 * _sublayer_params(cfg) + cfg.hidden_size * _outputs(cfg)
            + _outputs(cfg)
            + 3 * cfg.hidden_size * cfg.expert_ffn_hidden_size * experts)


def num_params(cfg) -> int:
    """Parameters that live here: the held experts, not all the routed."""
    d = cfg.hidden_size
    return (2 * cfg.vocab_size * d + d
            + cfg.num_layers * _layer_params(cfg, _held(cfg)))


def active_params(cfg) -> float:
    """Parameters a token's forward pass multiplies by HERE: of its ``top_k``
    picks the expected ``top_k x held / (E + Z)`` land on a held expert (0.25
    at 12 picks, 16 of 768 outputs; a zero-compute pick multiplies by
    nothing); both sublayers' attention and dense FFNs, router, head; the
    embedding is a lookup."""
    picks = cfg.top_k * _held(cfg) / _outputs(cfg)
    return (cfg.vocab_size * cfg.hidden_size
            + cfg.num_layers * _layer_params(cfg, picks))


def weight_bytes(cfg, bytes_per_param: int = 2) -> int:
    """Bytes a step must read: every held expert's weights, whatever the
    routing of a batch of more than a few tokens; the embedding is a lookup."""
    return (num_params(cfg) - cfg.vocab_size * cfg.hidden_size) * bytes_per_param


def kv_bytes_per_token(cfg, bytes_per_value: int = 2) -> int:
    """Bytes of the latent cache per context token, all layers: TWO rows of
    ``kv_lora_rank + qk_rope_head_dim`` values a layer, one a sublayer (8 x
    1,152 B at 4 layers in bf16), each key and value both, read once."""
    return ((cfg.kv_lora_rank + cfg.qk_rope_head_dim) * bytes_per_value
            * 2 * cfg.num_layers)


def attn_flops_per_pair(cfg) -> int:
    """FLOPs of one query x key pair, all layers (two attentions each),
    absorbed (what the program runs): scores over ``lat + rope`` lanes and
    values over ``lat`` lanes a head."""
    return (2 * cfg.num_heads * (2 * cfg.kv_lora_rank + cfg.qk_rope_head_dim)
            * 2 * cfg.num_layers)


def train_flops_per_token(cfg, seq_len: int) -> float:
    return (6.0 * active_params(cfg)
            + 6.0 * 2 * cfg.num_layers * cfg.num_heads * seq_len / 2.0
            * (cfg.qk_nope_head_dim + cfg.qk_rope_head_dim + cfg.v_head_dim))
