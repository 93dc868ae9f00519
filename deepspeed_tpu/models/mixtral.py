"""Mixtral-family sparse-MoE causal LM (``benchmark/configs/mixtral-8x7b-d3.json``:
Mixtral-8x7B's widths).

Llama backbone (RMSNorm / RoPE / GQA) with a top-k routed SwiGLU expert FFN in
every layer (reference analog: ``deepspeed/moe/layer.py MoE`` wrapping an HF
model; v2 inference ``model_implementations/mixtral``). Expert weights are
stacked ``[L, E, ...]`` so the expert GEMMs batch on the MXU and the expert dim
shards over the ``expert`` mesh axis.
"""

from __future__ import annotations

from dataclasses import dataclass
from functools import partial

import jax
import jax.numpy as jnp
from jax import lax

from deepspeed_tpu.config.config import MoEConfig
from deepspeed_tpu.models.api import ModelSpec, ShardCtx, causal_lm_loss
from deepspeed_tpu.models.experts import (
    expert_form,
    expert_stacks,
    routed_experts,
    routed_experts_einsum,
)
from deepspeed_tpu.models.llama import rmsnorm
from deepspeed_tpu.ops.attention import apply_rope
from deepspeed_tpu.parallel.moe import moe_ffn


@dataclass(frozen=True)
class MixtralConfig:
    vocab_size: int = 32000
    hidden_size: int = 4096
    intermediate_size: int = 14336
    num_layers: int = 32
    num_heads: int = 32
    num_kv_heads: int = 8
    num_experts: int = 8
    top_k: int = 2
    head_dim: int | None = None
    rope_theta: float = 1000000.0
    rms_norm_eps: float = 1e-5
    max_seq_len: int = 4096
    capacity_factor: float = 2.0
    aux_loss_coef: float = 0.01

    @property
    def hd(self) -> int:
        return self.head_dim if self.head_dim is not None else self.hidden_size // self.num_heads

    def moe_config(self) -> MoEConfig:
        return MoEConfig(enabled=True, num_experts=self.num_experts, top_k=self.top_k,
                         capacity_factor=self.capacity_factor,
                         aux_loss_coef=self.aux_loss_coef)

    @staticmethod
    def mixtral_8x7b() -> "MixtralConfig":
        return MixtralConfig()

    @staticmethod
    def tiny(vocab_size: int = 256) -> "MixtralConfig":
        return MixtralConfig(vocab_size=vocab_size, hidden_size=64, intermediate_size=96,
                             num_layers=2, num_heads=4, num_kv_heads=2, num_experts=4,
                             top_k=2, max_seq_len=128)


def init_params(cfg: MixtralConfig, rng) -> dict:
    d, f, hd = cfg.hidden_size, cfg.intermediate_size, cfg.hd
    hq, hkv, nl, e = cfg.num_heads, cfg.num_kv_heads, cfg.num_layers, cfg.num_experts
    k = iter(jax.random.split(rng, 16))
    std = 0.02
    out_std = std / jnp.sqrt(2.0 * nl)

    def norm(key, *shape, s=std):
        return jax.random.normal(key, shape, jnp.float32) * s

    return {
        "embed": norm(next(k), cfg.vocab_size, d),
        "layers": {
            "attn_norm": jnp.ones((nl, d), jnp.float32),
            "wq": norm(next(k), nl, d, hq * hd),
            "wk": norm(next(k), nl, d, hkv * hd),
            "wv": norm(next(k), nl, d, hkv * hd),
            "wo": norm(next(k), nl, hq * hd, d, s=out_std),
            "mlp_norm": jnp.ones((nl, d), jnp.float32),
            "router": norm(next(k), nl, d, e),
            "w_gate": norm(next(k), nl, e, d, f),
            "w_up": norm(next(k), nl, e, d, f),
            "w_down": norm(next(k), nl, e, f, d, s=out_std),
        },
        "final_norm": jnp.ones((d,), jnp.float32),
        "lm_head": norm(next(k), d, cfg.vocab_size),
    }


PARAM_LOGICAL_AXES = {
    "embed": ("vocab", "embed"),
    "layers": {
        "attn_norm": ("layers", "embed"),
        "wq": ("layers", "embed", "heads"),
        "wk": ("layers", "embed", "kv_heads"),
        "wv": ("layers", "embed", "kv_heads"),
        "wo": ("layers", "heads", "embed"),
        "mlp_norm": ("layers", "embed"),
        "router": ("layers", "embed", None),
        "w_gate": ("layers", "experts", "embed", "ffn"),
        "w_up": ("layers", "experts", "embed", "ffn"),
        "w_down": ("layers", "experts", "ffn", "embed"),
    },
    "final_norm": ("embed",),
    "lm_head": ("embed", "vocab"),
}


def _layer(cfg: MixtralConfig, moe_cfg: MoEConfig, ctx: ShardCtx, attn_impl: str,
           train: bool, x, lp, positions, rng):
    lp = ctx.layer_weights(lp, x.dtype)  # WOQ dequant + qwZ gather hooks
    b, s, d = x.shape
    hq, hkv, hd = cfg.num_heads, cfg.num_kv_heads, cfg.hd

    h = rmsnorm(x, lp["attn_norm"], cfg.rms_norm_eps)
    q = (h @ lp["wq"]).reshape(b, s, hq, hd)
    kk = (h @ lp["wk"]).reshape(b, s, hkv, hd)
    vv = (h @ lp["wv"]).reshape(b, s, hkv, hd)
    q = ctx.constrain(q, "batch", "seq", "heads_act", None)
    q, kk = apply_rope(q, kk, positions, cfg.rope_theta)
    o = ctx.attention(q, kk, vv, causal=True, impl=attn_impl)
    x = x + o.reshape(b, s, hq * hd) @ lp["wo"]

    h = rmsnorm(x, lp["mlp_norm"], cfg.rms_norm_eps)
    y, aux = moe_ffn(h, lp["router"], lp["w_gate"], lp["w_up"], lp["w_down"],
                     moe_cfg, train=train, rng=rng, ctx=ctx)
    x = x + y
    return ctx.constrain(x, "batch", "seq", "embed_act"), aux


def forward(cfg: MixtralConfig, params, input_ids, ctx: ShardCtx | None = None,
            attn_impl: str = "auto", train: bool = True, rng=None,
            remat: bool = False, remat_policy=None, return_aux: bool = False):
    ctx = ctx or ShardCtx()
    moe_cfg = cfg.moe_config()
    b, s = input_ids.shape
    x = ctx.embed_lookup(params["embed"], input_ids, "batch", "seq", "embed_act")
    positions = jnp.broadcast_to(jnp.arange(s)[None, :], (b, s))
    rng = rng if rng is not None else jax.random.PRNGKey(0)

    layer = partial(_layer, cfg, moe_cfg, ctx, attn_impl, train)
    if remat:
        layer = jax.checkpoint(layer, policy=remat_policy)

    def body(carry, lp_idx):
        x, aux_sum = carry
        lp, idx = lp_idx
        x, aux = layer(x, lp, positions, jax.random.fold_in(rng, idx))
        return (x, aux_sum + aux), None

    (x, aux_sum), _ = lax.scan(
        body, (x, jnp.float32(0.0)),
        (params["layers"], jnp.arange(cfg.num_layers)),
    )
    x = rmsnorm(x, params["final_norm"], cfg.rms_norm_eps)
    from deepspeed_tpu.ops.quantizer import maybe_dequantize

    head = ctx.whole_weight(params["lm_head"], "lm_head")  # stage 3: gathered
    logits = x @ maybe_dequantize(head, x.dtype).astype(x.dtype)
    logits = ctx.constrain(logits, "batch", "seq", "vocab_act")
    if return_aux:
        return logits, aux_sum / cfg.num_layers
    return logits


# ------------------------------------------------------------------ inference
def init_cache(cfg: MixtralConfig, batch: int, max_len: int,
               dtype=jnp.bfloat16) -> dict:
    """Dense fixed-shape KV cache [L, B, max_len, Hkv, Dh] (v1 engine)."""
    shape = (cfg.num_layers, batch, max_len, cfg.num_kv_heads, cfg.hd)
    return {"k": jnp.zeros(shape, dtype), "v": jnp.zeros(shape, dtype)}


def _cached_layer(cfg: MixtralConfig, x, lp, k_cache, v_cache, start_pos,
                  max_len: int):
    from deepspeed_tpu.models.paged import append_kv_and_attend
    from deepspeed_tpu.ops.quantizer import dequantize_layer

    lp = dequantize_layer(lp, x.dtype)
    b, t, d = x.shape
    hq, hkv, hd = cfg.num_heads, cfg.num_kv_heads, cfg.hd

    h = rmsnorm(x, lp["attn_norm"], cfg.rms_norm_eps)
    q = (h @ lp["wq"]).reshape(b, t, hq, hd)
    kk = (h @ lp["wk"]).reshape(b, t, hkv, hd)
    vv = (h @ lp["wv"]).reshape(b, t, hkv, hd)
    positions = start_pos + jnp.broadcast_to(jnp.arange(t)[None, :], (b, t))
    q, kk = apply_rope(q, kk, positions, cfg.rope_theta)
    o, k_cache, v_cache = append_kv_and_attend(
        q, kk, vv, k_cache, v_cache, start_pos, max_len)
    x = x + o.reshape(b, t, hq * hd) @ lp["wo"]

    h = rmsnorm(x, lp["mlp_norm"], cfg.rms_norm_eps)
    # may run under ``InferenceEngine``'s tensor-parallel mesh
    y = routed_experts_einsum(h.reshape(b * t, d), lp["router"],
                              lp["w_gate"], lp["w_up"], lp["w_down"],
                              cfg.top_k)
    return x + y.reshape(b, t, d), k_cache, v_cache


def decode_forward(cfg: MixtralConfig, params, tokens, cache, start_pos,
                   ctx: ShardCtx | None = None):
    """[B, T] new tokens + cache -> ([B, T, V] logits, cache); prefill
    (T = prompt) and incremental decode (T = 1) share the program."""
    del ctx
    max_len = cache["k"].shape[2]
    x = params["embed"][tokens].astype(cache["k"].dtype)

    def body(x, lp_kv):
        lp, kc, vc = lp_kv
        x, kc, vc = _cached_layer(cfg, x, lp, kc, vc, start_pos, max_len)
        return x, (kc, vc)

    x, (new_k, new_v) = lax.scan(body, x,
                                 (params["layers"], cache["k"], cache["v"]))
    x = rmsnorm(x, params["final_norm"], cfg.rms_norm_eps)
    from deepspeed_tpu.ops.quantizer import maybe_dequantize

    logits = x @ maybe_dequantize(params["lm_head"], x.dtype).astype(x.dtype)
    return logits, {"k": new_k, "v": new_v}


def init_paged_cache(cfg: MixtralConfig, num_blocks: int, block_size: int,
                     dtype=jnp.bfloat16, codec=None,
                     num_slots=None) -> dict:
    """Blocked KV pool of the ragged engine in the paged contract's storage
    form, ``[L, num_blocks, block_size, Hkv*Dh]`` (``models/paged.py``): a
    step program addresses it through block tables and never holds an array
    the size of a layer's slice. ``codec``: a ``kvquant.KVQCodec`` builds the
    low-bit pool at storage precision."""
    del num_slots  # this family keeps no state a slot (models/paged.py)
    from deepspeed_tpu.models.paged import init_paged_pool

    return init_paged_pool(cfg.num_layers, num_blocks, block_size,
                           cfg.num_kv_heads, cfg.hd, dtype, codec)


def _ragged_layer(cfg: MixtralConfig, x, lp, kc, vc, positions, slots,
                  block_tables, prefill_tiles=None, stacks=None,
                  qk_norm: bool = False, block: int | None = None):
    """One decoder layer over a flat ragged token batch [T, D]: paged
    attention identical to the Llama ragged layer, MoE FFN routed per token
    (decode tokens route through the SAME per-token top-k machinery as
    prefill-chunk tokens — MoE over a paged cache is a routing problem only
    in the FFN, which is position-free).

    ``cfg`` is any config with this layer's fields (``sdar`` shares the
    layer). ``qk_norm`` (static): an RMSNorm over each head's ``q`` and ``k``
    (gains ``lp["q_norm"]`` / ``lp["k_norm"]`` ``[head_dim]``) before the
    rotation. ``block`` (static): the block-causal mask of a model that
    generates by blocks (``models/paged.py``, *Blocks of rows*)."""
    from deepspeed_tpu.models.paged import (
        ragged_pool_attention,
        rows_to_heads,
        write_kv_paged,
    )
    from deepspeed_tpu.ops.quantizer import dequantize_layer

    lp = dequantize_layer(lp, x.dtype)
    t_tokens, d = x.shape
    hq, hkv, hd = cfg.num_heads, cfg.num_kv_heads, cfg.hd

    h = rmsnorm(x, lp["attn_norm"], cfg.rms_norm_eps)
    q = rows_to_heads(h, lp["wq"], hq)
    kk = rows_to_heads(h, lp["wk"], hkv)
    vv = rows_to_heads(h, lp["wv"], hkv)
    if qk_norm:
        q = rmsnorm(q, lp["q_norm"], cfg.rms_norm_eps)
        kk = rmsnorm(kk, lp["k_norm"], cfg.rms_norm_eps)
    q, kk = apply_rope(q[None], kk[None], positions[None], cfg.rope_theta)
    q, kk = q[0], kk[0]

    kc, vc = write_kv_paged(kc, vc, kk, vv, slots, positions, block_tables,
                            prefill_tiles)
    more = {} if block is None else {"block": block}
    o = ragged_pool_attention(q, kc, vc, slots, positions, block_tables,
                              prefill_tiles, **more).astype(x.dtype)
    x = x + o.reshape(t_tokens, hq * hd) @ lp["wo"]

    h = rmsnorm(x, lp["mlp_norm"], cfg.rms_norm_eps)
    x = x + routed_experts(
        h, lp["router"], lp["w_gate"], lp["w_up"], lp["w_down"], cfg.top_k,
        stacked=stacks and (*stacks, lp["first_expert"]))
    return x, kc, vc


def ragged_forward(cfg: MixtralConfig, params, tokens, slots, positions,
                   block_tables, cache, prefill_tiles=None):
    """Flat ragged step: [T] mixed tokens -> ([T, V] logits, cache) — the
    MoE member of the continuous-batching engine (reference
    ``inference/v2/model_implementations/mixtral``)."""
    from deepspeed_tpu.models.paged import scan_layers_paged

    x = params["embed"][tokens].astype(cache["k"].dtype)
    layers, stacks = expert_stacks(params["layers"])

    def layer(x, lp, pool, layer_tables):
        x, kc, vc = _ragged_layer(
            cfg, x, lp, pool["k"], pool["v"], positions, slots, layer_tables,
            prefill_tiles=prefill_tiles, stacks=stacks)
        return x, {"k": kc, "v": vc}

    x, cache = scan_layers_paged(layer, x, layers, cache, block_tables)
    x = rmsnorm(x, params["final_norm"], cfg.rms_norm_eps)
    from deepspeed_tpu.ops.quantizer import maybe_dequantize

    logits = x @ maybe_dequantize(params["lm_head"], x.dtype).astype(x.dtype)
    return logits, cache


def num_params(cfg: MixtralConfig) -> int:
    d, f, hd, e = cfg.hidden_size, cfg.intermediate_size, cfg.hd, cfg.num_experts
    per_layer = (d * hd * (cfg.num_heads * 2 + cfg.num_kv_heads * 2) + d * e
                 + 3 * e * d * f + 2 * d)
    return cfg.vocab_size * d * 2 + cfg.num_layers * per_layer + d


def flops_per_token(cfg: MixtralConfig, seq_len: int) -> float:
    """Active-param flops: attention + top_k of E experts."""
    d, f, hd = cfg.hidden_size, cfg.intermediate_size, cfg.hd
    active_per_layer = (d * hd * (cfg.num_heads * 2 + cfg.num_kv_heads * 2)
                        + cfg.top_k * 3 * d * f + d * cfg.num_experts)
    active = cfg.vocab_size * d * 2 + cfg.num_layers * active_per_layer
    return 6.0 * active + 12.0 * cfg.num_layers * d * seq_len / 2.0


def build(cfg: MixtralConfig, ctx: ShardCtx | None = None, attn_impl: str = "auto",
          remat: bool | None = None, remat_policy=None) -> ModelSpec:
    ctx = ctx or ShardCtx()
    remat = ctx.remat if remat is None else remat
    remat_policy = remat_policy if remat_policy is not None else ctx.remat_policy
    fwd = partial(forward, cfg, ctx=ctx, attn_impl=attn_impl,
                  remat=remat, remat_policy=remat_policy, train=False)

    def loss_fn(params, batch, rng=None):
        logits, aux = forward(cfg, params, batch["input_ids"], ctx=ctx,
                              attn_impl=attn_impl, train=True, rng=rng,
                              remat=remat, remat_policy=remat_policy, return_aux=True)
        lm = causal_lm_loss(logits, batch["input_ids"], batch.get("labels"))
        return lm + cfg.aux_loss_coef * aux

    return ModelSpec(
        name="mixtral",
        config=cfg,
        init_fn=partial(init_params, cfg),
        loss_fn=loss_fn,
        forward_fn=fwd,
        param_logical_axes=PARAM_LOGICAL_AXES,
        logical_dim_units={"heads": cfg.num_heads, "kv_heads": cfg.num_kv_heads,
                           "experts": cfg.num_experts},
        num_params=num_params(cfg),
        flops_per_token=partial(flops_per_token, cfg),
        init_cache_fn=partial(init_cache, cfg),
        decode_fn=partial(decode_forward, cfg, ctx=ctx),
        init_paged_cache_fn=partial(init_paged_cache, cfg),
        ragged_forward_fn=partial(ragged_forward, cfg),
        supports_prefill_tiles=True,
        moe_form=partial(expert_form, num_experts=cfg.num_experts,
                         top_k=cfg.top_k),
    )
