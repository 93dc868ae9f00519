"""DeepSeek-V3-family causal LM (``model_type: deepseek_v3``; Moonlight-16B-A3B
configures the subset its defaults give): multi-head latent attention (MLA),
leading dense SwiGLU layers, then layers of sigmoid-routed experts beside
shared ones. ``models/deepseek_v32.py`` builds DeepSeek-V3.2's sparse attention
on this module.

Layer equations (pre-norm RMSNorm, a residual after each half):

- **MLA.** ``q = x W_q -> [T, H, nope + rope]`` (with ``q_lora_rank``: ``q =
  RMSNorm(x W_qa) W_qb``, a low-rank query), RoPE on the ``rope`` lanes.
  ``a = x W_kva -> [T, lat + rope]``; ``c = RMSNorm(a[:, :lat])``; ``k_rope =
  RoPE(a[:, lat:])``, one head shared by all ``H``. ``kv = c W_kvb -> [T, H,
  nope + v]``, ``k = [kv[..., :nope], k_rope]``, ``v = kv[..., nope:]``;
  causal softmax of ``q . k * (nope + rope)^-0.5``. ``forward`` (training,
  evaluation) computes exactly that on ``xla_attention``. ``rope_scaling``
  (YaRN) stretches the rotation's frequencies
  (``ops/attention.rope_frequencies``) and multiplies the softmax scale by
  ``mscale**2``, ``mscale = 0.1 mscale_all_dim ln(factor) + 1``. With
  ``mla_use_nope`` nothing is rotated: the ``rope`` lanes of ``q`` and of
  the cached row stay as projected (``models/kimi_linear.py``, whose other
  layers carry the position).
- **Serving caches the row ``[c, k_rope]``** (``lat + rope`` lanes a token and
  layer, 1,152 B in bf16 at Moonlight's 512 + 64, against 8,192 B for 16 K
  and V heads of 128) and decodes **absorbed**: ``q_lat[h] = q_nope[h]
  W_kvb_k[h]^T``, score ``= (q_lat . c + q_rope . k_rope) * scale``, ``o_lat =
  P c``, ``o[h] = o_lat[h] W_kvb_v[h]``: attention over the cached rows
  themselves (``models/paged.latent_pool_attention``), prefill chunks too.
- **Router.** ``s = sigmoid(x_f32 W_r)``; the ``top_k`` largest of ``s +
  e_score_correction_bias`` are picked (with ``n_group > 1``: among the
  experts of a token's ``topk_group`` best groups, ``experts._in_best_groups``);
  the weights are ``s`` there (without the bias), divided by their sum, times
  ``routed_scaling_factor``. ``y = sum_i w_i SwiGLU_i(x) + SwiGLU_shared(x)``;
  the routed sum goes through ``models/experts.routed_experts``, the function
  Mixtral serves through.
- **One rank's share.** ``experts_held`` of the ``num_experts`` routed experts
  live here, those of rank ``expert_rank`` (the names ``nemotron_h`` uses): the
  router scores and picks over all of them, the layer computes the part its
  own experts give (``routed_experts``' ``held``), and nothing stands in for
  the exchange with the other ranks.

One departure from the published code: it rotates interleaved lane pairs
after a permutation of the projections' columns; ``ops/attention.apply_rope``
rotates half-split lanes, which with seeded weights is the same model up to
that permutation. A quantized latent pool is not implemented and raises.
"""

from __future__ import annotations

import math
from dataclasses import dataclass
from functools import partial

import jax
import jax.numpy as jnp
from jax import lax

from deepspeed_tpu.models.api import ModelSpec, ShardCtx, causal_lm_loss
from deepspeed_tpu.models.experts import (
    expert_form,
    expert_stacks,
    routed_experts,
    routed_experts_einsum,
    swiglu,
)
from deepspeed_tpu.models.llama import rmsnorm
from deepspeed_tpu.ops.attention import apply_rope, xla_attention


@dataclass(frozen=True)
class DeepseekConfig:
    vocab_size: int = 163840
    hidden_size: int = 2048
    intermediate_size: int = 11264        # the dense layers' FFN
    moe_intermediate_size: int = 1408     # one expert's FFN
    num_layers: int = 27
    num_heads: int = 16
    kv_lora_rank: int = 512
    q_lora_rank: int | None = None
    # multiply the normed low-rank query / the normed latent by
    # sqrt(hidden_size / rank) (``models/longcat_flash.py`` sets both)
    mla_scale_q_lora: bool = False
    mla_scale_kv_lora: bool = False
    # no rotation: the ``rope`` lanes of the query and of the cached row stay
    # as projected (``models/kimi_linear.py`` sets it: its other layers carry
    # the position)
    mla_use_nope: bool = False
    qk_nope_head_dim: int = 128
    qk_rope_head_dim: int = 64
    v_head_dim: int = 128
    num_experts: int = 64
    num_shared_experts: int = 2
    top_k: int = 6
    first_k_dense: int = 1
    scoring_func: str = "sigmoid"
    norm_topk_prob: bool = True
    routed_scaling_factor: float = 2.446
    n_group: int = 1
    topk_group: int = 1
    experts_held: int | None = None      # of num_experts, those that live here
    expert_rank: int = 0                 # ... experts rank * held onwards
    rope_theta: float = 50000.0
    # the published ``rope_scaling`` group (YaRN), whole; None: plain RoPE
    rope_scaling: dict | None = None
    rms_norm_eps: float = 1e-5
    max_seq_len: int = 8192

    def __post_init__(self):
        if not 0 <= self.first_k_dense < self.num_layers:
            raise ValueError("deepseek: first_k_dense must leave at least "
                             "one expert layer")
        if self.num_experts % self.n_group or not \
                1 <= self.topk_group <= self.n_group:
            raise ValueError("deepseek: n_group must divide num_experts and "
                             "topk_group lie in 1 .. n_group")
        if self.num_experts % self.held or not \
                0 <= self.expert_rank < self.num_experts // self.held:
            raise ValueError("deepseek: experts_held must divide num_experts "
                             "and expert_rank name one of the shares")
        if self.mla_scale_q_lora and self.q_lora_rank is None:
            raise ValueError("deepseek: mla_scale_q_lora scales the low-rank "
                             "query (q_lora_rank)")
        if self.rope_scaling is not None:
            if self.rope_scaling.get("type") != "yarn":
                raise NotImplementedError(
                    "deepseek: rope_scaling of type "
                    f"{self.rope_scaling.get('type')!r} is not implemented "
                    "(yarn is)")
            # a dict would make the config unhashable
            object.__setattr__(self, "rope_scaling",
                               tuple(sorted(self.rope_scaling.items())))

    @property
    def held(self) -> int:
        return self.num_experts if self.experts_held is None else self.experts_held

    @property
    def held_share(self):
        """``routed_experts``' ``held``; None where every expert lives here."""
        if self.held == self.num_experts:
            return None
        return (self.expert_rank * self.held, self.num_experts)

    @property
    def route_groups(self):
        """``routed_experts``' ``groups``; None for one group."""
        return None if self.n_group == 1 else (self.n_group, self.topk_group)

    @property
    def yarn(self):
        """``ops/attention.rope_frequencies``' stretch; None: plain RoPE."""
        if self.rope_scaling is None:
            return None
        r = dict(self.rope_scaling)
        return (float(r["factor"]), float(r.get("beta_fast", 32)),
                float(r.get("beta_slow", 1)),
                int(r["original_max_position_embeddings"]))

    @property
    def qk_head_dim(self) -> int:
        return self.qk_nope_head_dim + self.qk_rope_head_dim

    @property
    def row_lanes(self) -> int:
        """Lanes of a cached row: the latent, the shared roped key, and
        zeros up to whole 128-lane tiles (640 for 512 + 64; why:
        ``ops/pallas/mla_attention.py``)."""
        return -(-(self.kv_lora_rank + self.qk_rope_head_dim) // 128) * 128

    @property
    def softmax_scale(self) -> float:
        scale = self.qk_head_dim ** -0.5
        if self.rope_scaling is None:
            return scale
        r = dict(self.rope_scaling)
        mscale = 0.1 * r.get("mscale_all_dim", 0) * math.log(r["factor"]) + 1.0
        return scale * mscale * mscale

    @property
    def num_moe_layers(self) -> int:
        return self.num_layers - self.first_k_dense

    @staticmethod
    def tiny(vocab_size: int = 256) -> "DeepseekConfig":
        return DeepseekConfig(
            vocab_size=vocab_size, hidden_size=64, intermediate_size=96,
            moe_intermediate_size=48, num_layers=3, num_heads=2,
            kv_lora_rank=32, qk_nope_head_dim=16, qk_rope_head_dim=16,
            v_head_dim=16, num_experts=8, num_shared_experts=1, top_k=3,
            first_k_dense=1, max_seq_len=128)


def init_params(cfg: DeepseekConfig, rng) -> dict:
    d, h = cfg.hidden_size, cfg.num_heads
    e, held, fm = cfg.num_experts, cfg.held, cfg.moe_intermediate_size
    fs = cfg.num_shared_experts * fm
    k = iter(jax.random.split(rng, 24))
    std = 0.02
    out_std = std / jnp.sqrt(2.0 * cfg.num_layers)

    def norm(key, *shape, s=std):
        return jax.random.normal(key, shape, jnp.float32) * s

    def query(n):
        if cfg.q_lora_rank is None:
            return {"wq": norm(next(k), n, d, h * cfg.qk_head_dim)}
        return {"wq_a": norm(next(k), n, d, cfg.q_lora_rank),
                "q_norm": jnp.ones((n, cfg.q_lora_rank), jnp.float32),
                "wq_b": norm(next(k), n, cfg.q_lora_rank, h * cfg.qk_head_dim)}

    def attention(n):
        return {
            "attn_norm": jnp.ones((n, d), jnp.float32),
            **query(n),
            "wkv_a": norm(next(k), n, d,
                          cfg.kv_lora_rank + cfg.qk_rope_head_dim),
            "kv_norm": jnp.ones((n, cfg.kv_lora_rank), jnp.float32),
            "wkv_b": norm(next(k), n, cfg.kv_lora_rank,
                          h * (cfg.qk_nope_head_dim + cfg.v_head_dim)),
            "wo": norm(next(k), n, h * cfg.v_head_dim, d, s=out_std),
            "mlp_norm": jnp.ones((n, d), jnp.float32),
        }

    nd, nm = cfg.first_k_dense, cfg.num_moe_layers
    return {
        "embed": norm(next(k), cfg.vocab_size, d),
        "dense": {
            **attention(nd),
            "w_gate": norm(next(k), nd, d, cfg.intermediate_size),
            "w_up": norm(next(k), nd, d, cfg.intermediate_size),
            "w_down": norm(next(k), nd, cfg.intermediate_size, d, s=out_std),
        },
        "layers": {
            **attention(nm),
            "router": norm(next(k), nm, d, e),
            # drawn small and non-zero, so that selection (with the bias)
            # and weighting (without it) differ
            "router_bias": norm(next(k), nm, e, s=0.01),
            "w_gate": norm(next(k), nm, held, d, fm),
            "w_up": norm(next(k), nm, held, d, fm),
            "w_down": norm(next(k), nm, held, fm, d, s=out_std),
            "ws_gate": norm(next(k), nm, d, fs),
            "ws_up": norm(next(k), nm, d, fs),
            "ws_down": norm(next(k), nm, fs, d, s=out_std),
        },
        "final_norm": jnp.ones((d,), jnp.float32),
        "lm_head": norm(next(k), d, cfg.vocab_size),
    }


def param_logical_axes(cfg: DeepseekConfig) -> dict:
    """The logical axes of ``init_params``' tree, leaf for leaf."""
    query = ({"wq": ("layers", "embed", "heads")} if cfg.q_lora_rank is None
             else {"wq_a": ("layers", "embed", None),
                   "q_norm": ("layers", None),
                   "wq_b": ("layers", None, "heads")})
    attention = {
        "attn_norm": ("layers", "embed"),
        **query,
        "wkv_a": ("layers", "embed", None),
        "kv_norm": ("layers", None),
        "wkv_b": ("layers", None, "heads"),
        "wo": ("layers", "heads", "embed"),
        "mlp_norm": ("layers", "embed"),
    }
    return {
        "embed": ("vocab", "embed"),
        "dense": {
            **attention,
            "w_gate": ("layers", "embed", "ffn"),
            "w_up": ("layers", "embed", "ffn"),
            "w_down": ("layers", "ffn", "embed"),
        },
        "layers": {
            **attention,
            "router": ("layers", "embed", None),
            "router_bias": ("layers", None),
            "w_gate": ("layers", "experts", "embed", "ffn"),
            "w_up": ("layers", "experts", "embed", "ffn"),
            "w_down": ("layers", "experts", "ffn", "embed"),
            "ws_gate": ("layers", "embed", "ffn"),
            "ws_up": ("layers", "embed", "ffn"),
            "ws_down": ("layers", "ffn", "embed"),
        },
        "final_norm": ("embed",),
        "lm_head": ("embed", "vocab"),
    }


def _ffn(cfg: DeepseekConfig, h, lp, experts, **stacked):
    """The FFN half of a layer on flat tokens ``h`` [T, D]: a dense layer's
    SwiGLU, or the routed experts (``experts``: the serving rule with a
    scan's ``stacked``, or its einsum form by name) plus the shared ones."""
    if "router" not in lp:
        return swiglu(h, lp["w_gate"], lp["w_up"], lp["w_down"])
    routed = experts(
        h, lp["router"], lp["w_gate"], lp["w_up"], lp["w_down"], cfg.top_k,
        **stacked, scoring=cfg.scoring_func, bias=lp["router_bias"],
        renormalize=cfg.norm_topk_prob, scale=cfg.routed_scaling_factor,
        eps=1e-20, held=cfg.held_share, groups=cfg.route_groups)
    return routed + swiglu(h, lp["ws_gate"], lp["ws_up"], lp["ws_down"])


def _rope(cfg: DeepseekConfig, q, k, positions):
    """``apply_rope`` on flat tokens, ``q`` [T, H, R] and ``k`` [T, R] (one
    head), at the config's frequencies."""
    q, k = apply_rope(q[None], k[None, :, None], positions[None],
                      cfg.rope_theta, cfg.yarn)
    return q[0], k[0, :, 0]


def _query_latent(cfg: DeepseekConfig, h, lp):
    """The normed low-rank query ``cq`` [T, q_lora_rank] (``q_lora_rank``
    set), times ``sqrt(hidden / rank)`` under ``mla_scale_q_lora``."""
    cq = rmsnorm(h @ lp["wq_a"], lp["q_norm"], cfg.rms_norm_eps)
    if cfg.mla_scale_q_lora:
        cq = cq * (cfg.hidden_size / cfg.q_lora_rank) ** 0.5
    return cq


def _mla_inputs(cfg: DeepseekConfig, h, lp, positions, cq=None):
    """``h`` [T, D] (normed) -> ``q_nope`` [T, H, nope], roped ``q_rope``
    [T, H, rope], the normed latent ``c`` [T, lat] (times ``sqrt(hidden /
    lat)`` under ``mla_scale_kv_lora``: what is cached and what ``W_kvb``
    multiplies), roped ``k_rope`` [T, rope] (both ``rope`` parts as
    projected, unrotated, under ``mla_use_nope``). ``cq``:
    ``_query_latent``'s, where the caller has it. The query goes to its heads
    through the paged contract's ``rows_to_heads`` (the plain pass shares
    these lines and so its pin, which changes no value there either)."""
    from deepspeed_tpu.models.paged import rows_to_heads

    lat, nope = cfg.kv_lora_rank, cfg.qk_nope_head_dim
    if cfg.q_lora_rank is None:
        q = rows_to_heads(h, lp["wq"], cfg.num_heads)
    else:
        q = rows_to_heads(_query_latent(cfg, h, lp) if cq is None else cq,
                          lp["wq_b"], cfg.num_heads)
    a = h @ lp["wkv_a"]
    c = rmsnorm(a[:, :lat], lp["kv_norm"], cfg.rms_norm_eps)
    if cfg.mla_scale_kv_lora:
        c = c * (cfg.hidden_size / lat) ** 0.5
    q_rope, k_rope = q[..., nope:], a[:, lat:]
    if not cfg.mla_use_nope:
        q_rope, k_rope = _rope(cfg, q_rope, k_rope, positions)
    return q[..., :nope], q_rope, c, k_rope


def _wkv_b(cfg: DeepseekConfig, lp):
    """``kv_b_proj`` as ``[lat, H, nope + v]``: the key half and the value
    half of every head."""
    w = lp["wkv_b"].reshape(cfg.kv_lora_rank, cfg.num_heads,
                            cfg.qk_nope_head_dim + cfg.v_head_dim)
    return w[..., :cfg.qk_nope_head_dim], w[..., cfg.qk_nope_head_dim:]


def _plain_attention(cfg: DeepseekConfig, h, lp, positions, select=None):
    """MLA as published on the normed ``h`` [B, S, D] (per-head keys and
    values from the latent, not absorbed), through ``W_o`` -> [B, S, D].
    ``select``: ``_layer``'s."""
    b, s, d = h.shape
    heads = cfg.num_heads
    h = h.reshape(b * s, d)
    cq = None if cfg.q_lora_rank is None else _query_latent(cfg, h, lp)
    q_nope, q_rope, c, k_rope = _mla_inputs(cfg, h, lp, positions.reshape(-1),
                                            cq)
    wk, wv = _wkv_b(cfg, lp)
    k_nope = jnp.einsum("tl,lhn->thn", c, wk)
    v = jnp.einsum("tl,lhv->thv", c, wv)
    q = jnp.concatenate([q_nope, q_rope], axis=-1)
    k = jnp.concatenate(
        [k_nope, jnp.broadcast_to(k_rope[:, None], (b * s, heads,
                                                     cfg.qk_rope_head_dim))],
        axis=-1)
    bias = None if select is None else select(h, cq, lp, positions)
    o = xla_attention(q.reshape(b, s, heads, -1), k.reshape(b, s, heads, -1),
                      v.reshape(b, s, heads, -1), causal=True, bias=bias,
                      scale=cfg.softmax_scale)
    return o.reshape(b, s, heads * cfg.v_head_dim) @ lp["wo"]


def _layer(cfg: DeepseekConfig, ctx: ShardCtx, x, lp, positions, select=None):
    """One layer of the plain forward pass, ``x`` [B, S, D].
    ``select(h, cq, lp, positions) -> [B, 1, S, S]`` additive bias: a family
    whose attention reads some of the context only (``deepseek_v32``)."""
    lp = ctx.layer_weights(lp, x.dtype)
    b, s, d = x.shape
    x = x + _plain_attention(
        cfg, rmsnorm(x, lp["attn_norm"], cfg.rms_norm_eps), lp, positions,
        select)
    h = rmsnorm(x, lp["mlp_norm"], cfg.rms_norm_eps)
    # differentiated, and maybe under ``ctx``'s mesh: the einsum form
    x = x + _ffn(cfg, h.reshape(b * s, d), lp,
                 routed_experts_einsum).reshape(b, s, d)
    return ctx.constrain(x, "batch", "seq", "embed_act")


def _dense_layers(cfg: DeepseekConfig, params) -> list:
    """The leading dense layers' weights, one tree a layer."""
    return [jax.tree_util.tree_map(lambda a: a[i], params["dense"])
            for i in range(cfg.first_k_dense)]


def _lm_head(head, x):
    from deepspeed_tpu.ops.quantizer import maybe_dequantize

    return x @ maybe_dequantize(head, x.dtype).astype(x.dtype)


def forward(cfg: DeepseekConfig, params, input_ids, ctx: ShardCtx | None = None,
            remat: bool = False, remat_policy=None, select=None):
    """``[B, S]`` token ids -> ``[B, S, V]`` logits, dropless (every expert
    computes every token; the router's weights combine). ``select``:
    ``_layer``'s."""
    ctx = ctx or ShardCtx()
    b, s = input_ids.shape
    x = ctx.embed_lookup(params["embed"], input_ids, "batch", "seq", "embed_act")
    positions = jnp.broadcast_to(jnp.arange(s)[None, :], (b, s))
    layer = partial(_layer, cfg, ctx, select=select)
    if remat:
        layer = jax.checkpoint(layer, policy=remat_policy)
    for lp in _dense_layers(cfg, params):
        x = layer(x, lp, positions)
    x, _ = lax.scan(lambda x, lp: (layer(x, lp, positions), None), x,
                    params["layers"])
    x = rmsnorm(x, params["final_norm"], cfg.rms_norm_eps)
    head = ctx.whole_weight(params["lm_head"], "lm_head")  # stage 3: gathered
    return ctx.constrain(_lm_head(head, x), "batch", "seq", "vocab_act")


# ------------------------------------------------------------------ inference
def init_paged_cache(cfg: DeepseekConfig, num_blocks: int, block_size: int,
                     dtype=jnp.bfloat16, codec=None,
                     num_slots=None) -> dict:
    """The latent pool of the ragged engine, ``{"kv": [L, num_blocks,
    block_size, row_lanes]}``: ONE row a token and layer, ``[c, k_rope,
    zeros]``, in the paged contract's storage form (``models/paged.py``);
    ``L`` counts the dense layers too."""
    del num_slots  # this family keeps no state a slot (models/paged.py)
    if codec is not None:
        raise NotImplementedError(
            "deepseek: a quantized latent pool is not implemented (a row's "
            "latent and its roped key want scales of their own)")
    return {"kv": jnp.zeros((cfg.num_layers, num_blocks, block_size,
                             cfg.row_lanes), dtype)}


def _pool_attention(cfg: DeepseekConfig, h, lp, pool, positions, slots,
                    block_tables, prefill_tiles=None, sparse=None):
    """MLA over the latent pool on the normed ``h`` [T, D] of a flat ragged
    token batch, through ``W_o`` -> ([T, D], pool): the step's rows ``[c,
    k_rope, zeros]`` are scattered into the pool's ``"kv"`` leaf through
    ``block_tables`` (the table of ONE block layer), then absorbed attention
    reads the cached rows through the same table. ``sparse``:
    ``_ragged_layer``'s."""
    from deepspeed_tpu.models.paged import (
        latent_pool_attention,
        latent_queries,
        write_rows_paged,
    )

    t_tokens = h.shape[0]
    cq = None if cfg.q_lora_rank is None else _query_latent(cfg, h, lp)
    q_nope, q_rope, c, k_rope = _mla_inputs(cfg, h, lp, positions, cq)
    pad = cfg.row_lanes - cfg.kv_lora_rank - cfg.qk_rope_head_dim
    kv = write_rows_paged(
        pool["kv"],
        jnp.concatenate([c, k_rope, jnp.zeros((t_tokens, pad), c.dtype)], -1),
        slots, positions, block_tables, prefill_tiles)
    wk, wv = _wkv_b(cfg, lp)
    # both absorbed products are batched over heads, so their rows lie
    # head-major: the tile rows go to the kernel and come back that way
    q = latent_queries(q_nope, wk, q_rope, cfg.row_lanes, prefill_tiles)
    if sparse is None:
        pool = {"kv": kv}
        o_lat = latent_pool_attention(
            *q, kv, slots, positions, block_tables, cfg.kv_lora_rank,
            cfg.softmax_scale, prefill_tiles)
    else:
        o_lat, pool = sparse(h, cq, lp, q, {**pool, "kv": kv})
    o = jnp.einsum("htl,lhv->thv", o_lat.astype(h.dtype), wv)
    return o.reshape(t_tokens, -1) @ lp["wo"], pool


def _ragged_layer(cfg: DeepseekConfig, x, lp, pool, positions, slots,
                  block_tables, prefill_tiles=None, stacks=None, sparse=None):
    """One layer over a flat ragged token batch [T, D]: ``_pool_attention``,
    then the FFN half. ``sparse(h, cq, lp, q, pool) -> (o_lat, pool)`` takes
    the attention's place for a family that reads some of the cached rows
    only and keeps further block leaves (``deepseek_v32``): ``q`` the pair
    ``paged.latent_queries`` gives, ``o_lat`` [H, T, lat]; ``pool`` has this
    step's latent rows in it."""
    from deepspeed_tpu.ops.quantizer import dequantize_layer

    lp = dequantize_layer(lp, x.dtype)
    o, pool = _pool_attention(
        cfg, rmsnorm(x, lp["attn_norm"], cfg.rms_norm_eps), lp, pool,
        positions, slots, block_tables, prefill_tiles, sparse)
    x = x + o

    h = rmsnorm(x, lp["mlp_norm"], cfg.rms_norm_eps)
    stacked = (*stacks, lp["first_expert"]) if "first_expert" in lp else None
    return x + _ffn(cfg, h, lp, routed_experts, stacked=stacked), pool


def ragged_forward(cfg: DeepseekConfig, params, tokens, slots, positions,
                   block_tables, cache, prefill_tiles=None, sparse=None):
    """Flat ragged step: [T] mixed tokens -> ([T, V] logits, cache). The
    stack is not homogeneous: the dense layers run before the scan at layers
    ``0 .. first_k_dense - 1`` of the pool, the expert layers scan after
    them (``models/paged.scan_layers_paged``). ``sparse(layer_tables)`` makes
    ``_ragged_layer``'s ``sparse`` for a layer's block table."""
    from deepspeed_tpu.models.paged import scan_layers_paged

    layers, stacks = expert_stacks(params["layers"])

    def layer(x, lp, pool, layer_tables):
        return _ragged_layer(
            cfg, x, lp, pool, positions, slots, layer_tables,
            prefill_tiles=prefill_tiles, stacks=stacks,
            sparse=None if sparse is None else sparse(layer_tables))

    x = params["embed"][tokens].astype(cache["kv"].dtype)
    x, cache = scan_layers_paged(
        layer, x, layers, cache, block_tables,
        lead=[(layer, lp) for lp in _dense_layers(cfg, params)])
    x = rmsnorm(x, params["final_norm"], cfg.rms_norm_eps)
    return _lm_head(params["lm_head"], x), cache


def _attention_params(cfg: DeepseekConfig) -> int:
    d, h = cfg.hidden_size, cfg.num_heads
    r = cfg.q_lora_rank
    query = (d * h * cfg.qk_head_dim if r is None
             else d * r + r + r * h * cfg.qk_head_dim)
    return (query
            + d * (cfg.kv_lora_rank + cfg.qk_rope_head_dim) + cfg.kv_lora_rank
            + cfg.kv_lora_rank * h * (cfg.qk_nope_head_dim + cfg.v_head_dim)
            + h * cfg.v_head_dim * d + 2 * d)


def _dense_layer_params(cfg: DeepseekConfig) -> int:
    return _attention_params(cfg) + 3 * cfg.hidden_size * cfg.intermediate_size


def _moe_layer_params(cfg: DeepseekConfig, experts: int) -> int:
    d, fm = cfg.hidden_size, cfg.moe_intermediate_size
    return (_attention_params(cfg) + d * cfg.num_experts + cfg.num_experts
            + 3 * d * fm * (experts + cfg.num_shared_experts))


def num_params(cfg: DeepseekConfig) -> int:
    """Parameters that live here: the held experts, not all the routed."""
    d = cfg.hidden_size
    return (2 * cfg.vocab_size * d + d
            + cfg.first_k_dense * _dense_layer_params(cfg)
            + cfg.num_moe_layers * _moe_layer_params(cfg, cfg.held))


def flops_per_token(cfg: DeepseekConfig, seq_len: int) -> float:
    """Active-parameter training FLOPs: ``top_k`` of the routed experts (of
    them, the held share), the shared ones, attention over ``seq_len``
    (scores at ``nope + rope``, values at ``v`` lanes a head)."""
    active = (cfg.vocab_size * cfg.hidden_size
              + cfg.first_k_dense * _dense_layer_params(cfg)
              + cfg.num_moe_layers * _moe_layer_params(
                  cfg, cfg.top_k * cfg.held / cfg.num_experts))
    attn = (6.0 * cfg.num_layers * cfg.num_heads
            * (cfg.qk_head_dim + cfg.v_head_dim) * seq_len / 2.0)
    return 6.0 * active + attn


def build(cfg: DeepseekConfig, ctx: ShardCtx | None = None,
          remat: bool | None = None, remat_policy=None) -> ModelSpec:
    ctx = ctx or ShardCtx()
    remat = ctx.remat if remat is None else remat
    remat_policy = remat_policy if remat_policy is not None else ctx.remat_policy
    fwd = partial(forward, cfg, ctx=ctx, remat=remat, remat_policy=remat_policy)

    def loss_fn(params, batch, rng=None):
        del rng  # dropless routing draws nothing
        return causal_lm_loss(fwd(params, batch["input_ids"]),
                              batch["input_ids"], batch.get("labels"))

    return ModelSpec(
        name="deepseek",
        config=cfg,
        init_fn=partial(init_params, cfg),
        loss_fn=loss_fn,
        forward_fn=fwd,
        param_logical_axes=param_logical_axes(cfg),
        logical_dim_units={"heads": cfg.num_heads, "experts": cfg.held},
        num_params=num_params(cfg),
        flops_per_token=partial(flops_per_token, cfg),
        init_paged_cache_fn=partial(init_paged_cache, cfg),
        ragged_forward_fn=partial(ragged_forward, cfg),
        supports_prefill_tiles=True,
        moe_form=partial(expert_form, num_experts=cfg.num_experts,
                         top_k=cfg.top_k),
    )
