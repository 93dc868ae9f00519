"""LongCat-Flash causal LM (the language model of ``LongCat-Flash-Omni``;
``family: "longcat_flash"``): every layer is a DOUBLE layer, two MLA sublayers
and two dense FFNs around a shortcut-connected expert branch whose router picks
among the routed experts AND a run of zero-compute (identity) experts.

The layer, as the published ``config.json`` gives it (``d`` hidden 6144, ``H``
64 heads, ``nope`` 128, ``rope`` 64, ``v`` 128, ``lat = kv_lora_rank`` 512, ``r
= q_lora_rank`` 1536, dense FFN 12288, expert FFN 2048, ``E`` 512 routed + ``Z``
256 zero-compute experts, ``k = moe_topk`` 12, ``routed_scaling_factor`` 6,
``rms_norm_eps`` 1e-5, ``rope_theta`` 1e7 and no ``rope_scaling``, 28 layers =
56 attentions). Pre-norm RMSNorm everywhere; ``a`` in {0, 1} indexes a layer's
two sublayers, each with its own weights and norms (``params["layers"]["sub"]``
is the list of the two sublayers' trees, every leaf stacked ``[L, ...]``)::

    MLA_a(h, positions):                            # models/deepseek.py's MLA
      cq  = RMSNorm(h W_qa[a]) * sqrt(d / r)        # mla_scale_q_lora
      q   = cq W_qb[a] -> [T, H, nope + rope];  q_rope = RoPE(q[..., nope:])
      kva = h W_kva[a] -> [T, lat + rope]
      c   = RMSNorm(kva[:, :lat]) * sqrt(d / lat)   # mla_scale_kv_lora
      k_rope = RoPE(kva[:, lat:])                   # one head for all H, NOT scaled
      kv  = c W_kvb[a] -> [T, H, nope + v]; k = [kv[..., :nope], k_rope]; v = kv[..., nope:]
      o   = causal_softmax(q . k * (nope + rope)^-0.5) v;  return o W_o[a]

    Router(h):                                      # float32
      s    = softmax(h W_r)            over E + Z outputs, no linear bias
      pick = top_k(s + e_score_correction_bias, k)  # the bias selects, never weighs
      w    = s[pick] * routed_scaling_factor        # NOT renormalised over the picks
    ScMoE(h) = sum over picks i:  w_i * SwiGLU_i(h)  if pick_i < E   (width 2048)
                                  w_i * h            if pick_i >= E  (identity)
               # no shared expert

    Layer(x):
      x1 = x  + MLA_0(RMSNorm_in0(x))
      h1 = RMSNorm_post0(x1)
      m  = ScMoE(h1)                                # the shortcut: computed here ...
      x2 = x1 + SwiGLU_dense0(h1)
      x3 = x2 + MLA_1(RMSNorm_in1(x2))
      x4 = x3 + SwiGLU_dense1(RMSNorm_post1(x3)) + m   # ... added here
    Model: embed -> Layer x num_layers -> RMSNorm -> lm_head (untied)

``_double_layer`` is that wiring, once, for the plain ``forward`` and for the
ragged step. **One MLA implementation**: the sublayers are ``models/deepseek``'s
helpers (``_plain_attention``, ``_pool_attention``) under this config, the two
``mla_scale_*`` factors behind fields that ``deepseek``'s own configs leave off.
The expert branch is ``models/experts.routed_experts`` with ``zero_experts``.

**Serving** caches the row ``[c, k_rope, zeros]`` (``row_lanes``, 640) for EACH
sublayer and decodes absorbed, as ``deepseek`` does; the scaled ``c`` is what is
cached, so the absorbed form needs nothing else. The latent pool is ONE leaf,
``{"kv": [2 x num_layers, NB, BS, row_lanes]}``: layer ``i`` owns block layers
``2 i`` and ``2 i + 1`` (``models/paged.scan_layers_paged(block_layers=2)``),
and the expert branch's output crosses from the first sublayer to the end of
the second inside the layer function. On one chip the shortcut has nothing to
hide (its reason is the expert-parallel exchange, which one chip does not run):
the step is one program and XLA orders the branch where it likes.

**One rank's share.** ``experts_held`` of the ``num_experts`` routed experts
live here (``expert_rank``'s); the router scores all ``E + Z`` outputs; the
zero-compute picks cost no weight and no exchange, so every rank computes them
for its own tokens, once.

**What a step hands back.** ``ragged_forward(row_counts=True)`` also returns
``[3, T]`` int32 (``STEP_COUNTERS``): a token's picks (``k`` a layer), those of
zero-compute experts and those of held experts, summed over the layers; the
engine sums them over a step's real rows (``ModelSpec.step_counters``).

Departures from the published code (each under ``assumed`` in the benchmark's
configuration): half-split rotation in place of interleaved pairs (the same
model up to a permutation of seeded weights, as ``deepseek``); the Omni release's
audio / vision encoders and codec decoder lie outside the text forward pass. A
quantized latent pool is not implemented and raises.
"""

from __future__ import annotations

from dataclasses import dataclass
from functools import partial

import jax
import jax.numpy as jnp
from jax import lax

from deepspeed_tpu.models.api import ModelSpec, ShardCtx, causal_lm_loss
from deepspeed_tpu.models.deepseek import (
    _lm_head,
    _plain_attention,
    _pool_attention,
)
from deepspeed_tpu.models.experts import (
    expert_form,
    expert_stacks,
    routed_experts,
    routed_experts_einsum,
    swiglu,
)
from deepspeed_tpu.models.llama import rmsnorm

# the counts a step program hands back (``ModelSpec.step_counters``)
STEP_COUNTERS = ("moe_picks", "moe_zero_picks", "moe_held_picks")
# ONE decode bucket at the benchmark's 128 slots: 7 step programs (``d128_t0..
# t3``, ``d0_t1/t2/t4``), not 27. A step reads 10 GB of weights whatever its rows,
# and a padding decode row walks one block of each sublayer's pool (~1 us). At
# 16 the ladder's 19 programs put a cold run whose window is measured twice at
# 354 s of the benchmark's 360 (PERF.md section 6, PR 38)
DECODE_BUCKET_MIN = 128


@dataclass(frozen=True)
class LongcatFlashConfig:
    vocab_size: int = 131072
    hidden_size: int = 6144
    ffn_hidden_size: int = 12288          # each of a layer's two dense FFNs
    expert_ffn_hidden_size: int = 2048    # one expert's FFN
    num_layers: int = 28                  # double layers: 2 x as many attentions
    num_heads: int = 64
    kv_lora_rank: int = 512
    q_lora_rank: int = 1536
    qk_nope_head_dim: int = 128
    qk_rope_head_dim: int = 64
    v_head_dim: int = 128
    mla_scale_q_lora: bool = True
    mla_scale_kv_lora: bool = True
    num_experts: int = 512                # routed (real) experts
    zero_expert_num: int = 256            # router outputs past them
    zero_expert_type: str = "identity"
    top_k: int = 12
    routed_scaling_factor: float = 6.0
    experts_held: int | None = None       # of num_experts, those that live here
    expert_rank: int = 0                  # ... experts rank * held onwards
    rope_theta: float = 1e7
    rms_norm_eps: float = 1e-5
    max_seq_len: int = 131072

    def __post_init__(self):
        if self.zero_expert_num and self.zero_expert_type != "identity":
            raise NotImplementedError(
                f"longcat_flash: zero experts of type "
                f"{self.zero_expert_type!r} are not implemented (identity is)")
        if self.num_experts % self.held or not \
                0 <= self.expert_rank < self.num_experts // self.held:
            raise ValueError("longcat_flash: experts_held must divide "
                             "num_experts and expert_rank name one of the "
                             "shares")
        if not 1 <= self.top_k <= self.num_experts + self.zero_expert_num:
            raise ValueError("longcat_flash: top_k must lie in 1 .. "
                             "num_experts + zero_expert_num")

    @property
    def held(self) -> int:
        return self.num_experts if self.experts_held is None else self.experts_held

    @property
    def held_share(self):
        """``routed_experts``' ``held``; None where every expert lives here."""
        if self.held == self.num_experts:
            return None
        return (self.expert_rank * self.held, self.num_experts)

    # ---- what ``models/deepseek``'s MLA helpers read off a config
    yarn = None                           # plain RoPE: no ``rope_scaling``
    mla_use_nope = False                  # ... and every sublayer rotates

    @property
    def qk_head_dim(self) -> int:
        return self.qk_nope_head_dim + self.qk_rope_head_dim

    @property
    def row_lanes(self) -> int:
        """Lanes of a cached row (``DeepseekConfig.row_lanes``)."""
        return -(-(self.kv_lora_rank + self.qk_rope_head_dim) // 128) * 128

    @property
    def softmax_scale(self) -> float:
        return self.qk_head_dim ** -0.5

    @staticmethod
    def tiny(vocab_size: int = 256) -> "LongcatFlashConfig":
        """2 double layers, 2 heads, a 24-wide low-rank query; 8 routed + 4
        zero-compute experts, top-3."""
        return LongcatFlashConfig(
            vocab_size=vocab_size, hidden_size=64, ffn_hidden_size=96,
            expert_ffn_hidden_size=48, num_layers=2, num_heads=2,
            kv_lora_rank=32, q_lora_rank=24, qk_nope_head_dim=16,
            qk_rope_head_dim=16, v_head_dim=16, num_experts=8,
            zero_expert_num=4, top_k=3, rope_theta=10000.0, max_seq_len=128)


def init_params(cfg: LongcatFlashConfig, rng) -> dict:
    """Seeded weights: std 0.02 (output projections 0.02 / sqrt(2 x
    attentions)). ``e_score_correction_bias ~ N(0, 0.002)``: at the published
    widths a token's 12th-largest softmax score of 768 is ~0.011 (its largest
    ~0.06, the median 0.0004), so ``deepseek``'s 0.01 would make 8 of the 12
    picks the same outputs for most tokens (31% of the picks those of
    ``top_k(s)``); 0.002 changes 13% of the picks and fixes none (the busiest
    output is picked by 5% of the tokens, uniform would be 1.6%). The draws
    come from the device's own generator (``nemotron_h.init_params`` says
    why)."""
    d, h, n = cfg.hidden_size, cfg.num_heads, cfg.num_layers
    r, lat = cfg.q_lora_rank, cfg.kv_lora_rank
    ff, fe = cfg.ffn_hidden_size, cfg.expert_ffn_hidden_size
    outputs = cfg.num_experts + cfg.zero_expert_num
    rng = jax.random.wrap_key_data(jax.random.bits(rng, (4,), jnp.uint32),
                                   impl="rbg")
    k = iter(jax.random.split(rng, 24))
    std = 0.02
    out_std = std / jnp.sqrt(4.0 * n)

    def norm(key, *shape, s=std):
        return jax.random.normal(key, shape, jnp.float32) * s

    def sublayer():
        """One MLA sublayer, its two norms and the dense FFN after it. A tree
        a sublayer, every leaf ``[L, ...]``: the layer scan's slice of a
        matrix then has ONE consumer and fuses into its product (one leaf
        ``[L, 2, ...]`` for both is copied out of the stack every layer and
        step, 1.1 GB a layer at the published widths: PERF.md 6, PR 38)."""
        return {
            "attn_norm": jnp.ones((n, d), jnp.float32),
            "wq_a": norm(next(k), n, d, r),
            "q_norm": jnp.ones((n, r), jnp.float32),
            "wq_b": norm(next(k), n, r, h * cfg.qk_head_dim),
            "wkv_a": norm(next(k), n, d, lat + cfg.qk_rope_head_dim),
            "kv_norm": jnp.ones((n, lat), jnp.float32),
            "wkv_b": norm(next(k), n, lat,
                          h * (cfg.qk_nope_head_dim + cfg.v_head_dim)),
            "wo": norm(next(k), n, h * cfg.v_head_dim, d, s=out_std),
            "mlp_norm": jnp.ones((n, d), jnp.float32),
            "wd_gate": norm(next(k), n, d, ff),
            "wd_up": norm(next(k), n, d, ff),
            "wd_down": norm(next(k), n, ff, d, s=out_std),
        }

    return {
        "embed": norm(next(k), cfg.vocab_size, d),
        "layers": {
            "sub": [sublayer(), sublayer()],
            "router": norm(next(k), n, d, outputs),
            "router_bias": norm(next(k), n, outputs, s=0.002),
            "w_gate": norm(next(k), n, cfg.held, d, fe),
            "w_up": norm(next(k), n, cfg.held, d, fe),
            "w_down": norm(next(k), n, cfg.held, fe, d, s=out_std),
        },
        "final_norm": jnp.ones((d,), jnp.float32),
        "lm_head": norm(next(k), d, cfg.vocab_size),
    }


def param_logical_axes(cfg: LongcatFlashConfig) -> dict:
    """The logical axes of ``init_params``' tree, leaf for leaf."""
    del cfg
    sublayer = {
        "attn_norm": ("layers", "embed"),
        "wq_a": ("layers", "embed", None),
        "q_norm": ("layers", None),
        "wq_b": ("layers", None, "heads"),
        "wkv_a": ("layers", "embed", None),
        "kv_norm": ("layers", None),
        "wkv_b": ("layers", None, "heads"),
        "wo": ("layers", "heads", "embed"),
        "mlp_norm": ("layers", "embed"),
        "wd_gate": ("layers", "embed", "ffn"),
        "wd_up": ("layers", "embed", "ffn"),
        "wd_down": ("layers", "ffn", "embed"),
    }
    return {
        "embed": ("vocab", "embed"),
        "layers": {
            "sub": [dict(sublayer), dict(sublayer)],
            "router": ("layers", "embed", None),
            "router_bias": ("layers", None),
            "w_gate": ("layers", "experts", "embed", "ffn"),
            "w_up": ("layers", "experts", "embed", "ffn"),
            "w_down": ("layers", "experts", "ffn", "embed"),
        },
        "final_norm": ("embed",),
        "lm_head": ("embed", "vocab"),
    }


def _experts(cfg: LongcatFlashConfig, h, lp, experts, **how):
    """``ScMoE`` on flat tokens ``h`` [T, D] through ``experts`` (the serving
    rule with its ``stacked`` / ``count_picks``, or its einsum form)."""
    return experts(
        h, lp["router"], lp["w_gate"], lp["w_up"], lp["w_down"], cfg.top_k,
        scoring="softmax", bias=lp["router_bias"], renormalize=False,
        scale=cfg.routed_scaling_factor, held=cfg.held_share,
        zero_experts=cfg.zero_expert_num, **how)


def _layer_weights(lp: dict, prepare) -> dict:
    """``prepare`` (a flat dict's just-in-time weight preparation) on the
    layer's own leaves and on each sublayer's tree."""
    return {**prepare({k: v for k, v in lp.items() if k != "sub"}),
            "sub": [prepare(sub) for sub in lp["sub"]]}


def _double_layer(cfg: LongcatFlashConfig, x, lp, attend, experts):
    """The module doc's ``Layer``: ``attend(a, h, sub) -> MLA_a(h)`` with
    ``sub`` sublayer ``a``'s weights, ``experts(h) -> (ScMoE(h), picks)``;
    returns ``(x4, picks)``."""
    eps = cfg.rms_norm_eps
    s0, s1 = lp["sub"]
    x = x + attend(0, rmsnorm(x, s0["attn_norm"], eps), s0)
    h = rmsnorm(x, s0["mlp_norm"], eps)
    m, picks = experts(h)                 # the shortcut: computed here ...
    x = x + swiglu(h, s0["wd_gate"], s0["wd_up"], s0["wd_down"])
    x = x + attend(1, rmsnorm(x, s1["attn_norm"], eps), s1)
    h = rmsnorm(x, s1["mlp_norm"], eps)
    x = x + swiglu(h, s1["wd_gate"], s1["wd_up"], s1["wd_down"]) + m  # ... added here
    return x, picks


def forward(cfg: LongcatFlashConfig, params, input_ids,
            ctx: ShardCtx | None = None, remat: bool = False,
            remat_policy=None):
    """``[B, S]`` token ids -> ``[B, S, V]`` logits: MLA as published (not
    absorbed), the expert branch in its einsum form (differentiable)."""
    ctx = ctx or ShardCtx()
    b, s = input_ids.shape
    x = ctx.embed_lookup(params["embed"], input_ids, "batch", "seq", "embed_act")
    positions = jnp.broadcast_to(jnp.arange(s)[None, :], (b, s))

    def layer(x, lp):
        lp = _layer_weights(lp, partial(ctx.layer_weights, dtype=x.dtype))
        x, _ = _double_layer(
            cfg, x, lp,
            lambda a, h, sub: _plain_attention(cfg, h, sub, positions),
            lambda h: (_experts(cfg, h.reshape(b * s, -1), lp,
                                routed_experts_einsum).reshape(h.shape), None))
        return ctx.constrain(x, "batch", "seq", "embed_act")

    if remat:
        layer = jax.checkpoint(layer, policy=remat_policy)
    x, _ = lax.scan(lambda x, lp: (layer(x, lp), None), x, params["layers"])
    x = rmsnorm(x, params["final_norm"], cfg.rms_norm_eps)
    head = ctx.whole_weight(params["lm_head"], "lm_head")
    return ctx.constrain(_lm_head(head, x), "batch", "seq", "vocab_act")


# ------------------------------------------------------------------ inference
def init_paged_cache(cfg: LongcatFlashConfig, num_blocks: int, block_size: int,
                     dtype=jnp.bfloat16, codec=None, num_slots=None) -> dict:
    """The latent pool of the ragged engine, ``{"kv": [2 x num_layers,
    num_blocks, block_size, row_lanes]}``: TWO block layers a model layer, one
    row ``[c, k_rope, zeros]`` a token and sublayer (``models/paged.py``)."""
    del num_slots  # this family keeps no state a slot (models/paged.py)
    if codec is not None:
        raise NotImplementedError(
            "longcat_flash: a quantized latent pool is not implemented (a "
            "row's latent and its roped key want scales of their own)")
    return {"kv": jnp.zeros((2 * cfg.num_layers, num_blocks, block_size,
                             cfg.row_lanes), dtype)}


def ragged_forward(cfg: LongcatFlashConfig, params, tokens, slots, positions,
                   block_tables, cache, prefill_tiles=None,
                   row_counts: bool = False):
    """Flat ragged step: [T] mixed tokens -> ([T, V] logits, cache), and with
    ``row_counts`` the ``STEP_COUNTERS`` a token, ``[3, T]`` int32. A layer
    scatters and reads two block layers of the pool, one a sublayer."""
    from deepspeed_tpu.models.paged import scan_layers_paged
    from deepspeed_tpu.ops.quantizer import dequantize_layer

    layers, stacks = expert_stacks(params["layers"])
    t_tokens = tokens.shape[0]

    def layer(carry, lp, pool, tables):
        x, counts = carry
        lp = _layer_weights(lp, partial(dequantize_layer, dtype=x.dtype))
        stacked = (*stacks, lp["first_expert"]) if "first_expert" in lp else None

        def attend(a, h, sub):
            nonlocal pool
            o, pool = _pool_attention(cfg, h, sub, pool, positions, slots,
                                      tables[a], prefill_tiles)
            return o

        x, picks = _double_layer(
            cfg, x, lp, attend,
            lambda h: _experts(cfg, h, lp, routed_experts, stacked=stacked,
                               count_picks=True))
        return (x, counts + picks), pool

    # the counts ride the scan's carry either way; a program that does not
    # return them (``row_counts`` False) drops them as dead code
    x = params["embed"][tokens].astype(cache["kv"].dtype)
    (x, counts), cache = scan_layers_paged(
        layer, (x, jnp.zeros((t_tokens, 2), jnp.int32)), layers, cache,
        block_tables, block_layers=2)
    x = rmsnorm(x, params["final_norm"], cfg.rms_norm_eps)
    logits = _lm_head(params["lm_head"], x)
    if not row_counts:
        return logits, cache
    picks = jnp.full((t_tokens,), cfg.top_k * cfg.num_layers, jnp.int32)
    return logits, cache, jnp.stack([picks, counts[:, 0], counts[:, 1]])


def _sublayer_params(cfg: LongcatFlashConfig) -> int:
    """One MLA sublayer with its two norms and the dense FFN after it."""
    d, h, r, lat = (cfg.hidden_size, cfg.num_heads, cfg.q_lora_rank,
                    cfg.kv_lora_rank)
    return (d * r + r + r * h * cfg.qk_head_dim
            + d * (lat + cfg.qk_rope_head_dim) + lat
            + lat * h * (cfg.qk_nope_head_dim + cfg.v_head_dim)
            + h * cfg.v_head_dim * d + 2 * d
            + 3 * d * cfg.ffn_hidden_size)


def _layer_params(cfg: LongcatFlashConfig, experts: float) -> float:
    """A double layer with ``experts`` routed experts counted."""
    outputs = cfg.num_experts + cfg.zero_expert_num
    return (2 * _sublayer_params(cfg) + cfg.hidden_size * outputs + outputs
            + 3 * cfg.hidden_size * cfg.expert_ffn_hidden_size * experts)


def num_params(cfg: LongcatFlashConfig) -> int:
    """Parameters that live here: the held experts, not all the routed."""
    d = cfg.hidden_size
    return 2 * cfg.vocab_size * d + d + cfg.num_layers * _layer_params(cfg, cfg.held)


def flops_per_token(cfg: LongcatFlashConfig, seq_len: int) -> float:
    """Active-parameter training FLOPs: of a token's ``top_k`` picks the
    expected share that lands on a held expert (``held`` of ``num_experts +
    zero_expert_num`` outputs; a zero-compute pick multiplies nothing), and
    TWO attentions a layer over ``seq_len``."""
    outputs = cfg.num_experts + cfg.zero_expert_num
    active = (cfg.vocab_size * cfg.hidden_size + cfg.num_layers
              * _layer_params(cfg, cfg.top_k * cfg.held / outputs))
    attn = (6.0 * 2 * cfg.num_layers * cfg.num_heads
            * (cfg.qk_head_dim + cfg.v_head_dim) * seq_len / 2.0)
    return 6.0 * active + attn


def build(cfg: LongcatFlashConfig, ctx: ShardCtx | None = None,
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
        name="longcat_flash",
        config=cfg,
        init_fn=partial(init_params, cfg),
        loss_fn=loss_fn,
        forward_fn=fwd,
        param_logical_axes=param_logical_axes(cfg),
        logical_dim_units={"heads": cfg.num_heads, "experts": cfg.held},
        num_params=int(num_params(cfg)),
        flops_per_token=partial(flops_per_token, cfg),
        init_paged_cache_fn=partial(init_paged_cache, cfg),
        ragged_forward_fn=partial(ragged_forward, cfg),
        supports_prefill_tiles=True,
        moe_form=partial(expert_form, num_experts=cfg.num_experts,
                         top_k=cfg.top_k),
        decode_bucket_min=DECODE_BUCKET_MIN,
        step_counters=STEP_COUNTERS,
    )
