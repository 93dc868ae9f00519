"""GPT-2 causal LM — the model of the training cell and of ``chip_smoke.py``
(``benchmark/configs/gpt2-xl.json``: GPT-2 XL at its published widths).

LayerNorm(+bias), learned positional embeddings, GELU MLP, tied LM head —
matching HF ``GPT2LMHeadModel`` semantics. Same functional stacked-scan design
as ``models/llama.py``.
"""

from __future__ import annotations

from dataclasses import dataclass
from functools import partial

import jax
import jax.numpy as jnp
from jax import lax

from deepspeed_tpu.models.api import ModelSpec, ShardCtx, causal_lm_loss


@dataclass(frozen=True)
class GPT2Config:
    vocab_size: int = 50257
    hidden_size: int = 768
    num_layers: int = 12
    num_heads: int = 12
    max_seq_len: int = 1024
    layer_norm_eps: float = 1e-5

    @property
    def ffn(self) -> int:
        return 4 * self.hidden_size

    @property
    def hd(self) -> int:
        return self.hidden_size // self.num_heads

    @staticmethod
    def gpt2_125m() -> "GPT2Config":
        return GPT2Config()

    @staticmethod
    def gpt2_xl() -> "GPT2Config":
        """GPT-2 XL, 1.56B (openai-community/gpt2-xl config.json: n_embd
        1600, n_head 25, n_layer 48, n_positions 1024, vocab 50257)."""
        return GPT2Config(hidden_size=1600, num_layers=48, num_heads=25)

    @staticmethod
    def tiny(vocab_size: int = 256) -> "GPT2Config":
        return GPT2Config(vocab_size=vocab_size, hidden_size=64, num_layers=2,
                          num_heads=4, max_seq_len=128)


def init_params(cfg: GPT2Config, rng) -> dict:
    d, f, nl = cfg.hidden_size, cfg.ffn, cfg.num_layers
    k = iter(jax.random.split(rng, 16))
    std = 0.02
    out_std = std / jnp.sqrt(2.0 * nl)

    def norm(key, *shape, s=std):
        return jax.random.normal(key, shape, jnp.float32) * s

    return {
        "wte": norm(next(k), cfg.vocab_size, d),
        "wpe": norm(next(k), cfg.max_seq_len, d, s=0.01),
        "layers": {
            "ln1_g": jnp.ones((nl, d)), "ln1_b": jnp.zeros((nl, d)),
            "wq": norm(next(k), nl, d, d), "bq": jnp.zeros((nl, d)),
            "wk": norm(next(k), nl, d, d), "bk": jnp.zeros((nl, d)),
            "wv": norm(next(k), nl, d, d), "bv": jnp.zeros((nl, d)),
            "wo": norm(next(k), nl, d, d, s=out_std), "bo": jnp.zeros((nl, d)),
            "ln2_g": jnp.ones((nl, d)), "ln2_b": jnp.zeros((nl, d)),
            "w_in": norm(next(k), nl, d, f), "b_in": jnp.zeros((nl, f)),
            "w_out": norm(next(k), nl, f, d, s=out_std), "b_out": jnp.zeros((nl, d)),
        },
        "lnf_g": jnp.ones((d,)), "lnf_b": jnp.zeros((d,)),
    }


PARAM_LOGICAL_AXES = {
    "wte": ("vocab", "embed"),
    "wpe": (None, "embed"),
    "layers": {
        "ln1_g": ("layers", "embed"), "ln1_b": ("layers", "embed"),
        "wq": ("layers", "embed", "heads"), "bq": ("layers", "heads"),
        "wk": ("layers", "embed", "heads"), "bk": ("layers", "heads"),
        "wv": ("layers", "embed", "heads"), "bv": ("layers", "heads"),
        "wo": ("layers", "heads", "embed"), "bo": ("layers", "embed"),
        "ln2_g": ("layers", "embed"), "ln2_b": ("layers", "embed"),
        "w_in": ("layers", "embed", "ffn"), "b_in": ("layers", "ffn"),
        "w_out": ("layers", "ffn", "embed"), "b_out": ("layers", "embed"),
    },
    "lnf_g": ("embed",), "lnf_b": ("embed",),
}


def layernorm(x, g, b, eps):
    xf = x.astype(jnp.float32)
    mu = xf.mean(axis=-1, keepdims=True)
    var = ((xf - mu) ** 2).mean(axis=-1, keepdims=True)
    return (((xf - mu) * lax.rsqrt(var + eps)).astype(x.dtype) * g.astype(x.dtype)
            + b.astype(x.dtype))


def _block(cfg: GPT2Config, ctx: ShardCtx, attn_impl: str, x, lp):
    # WOQ dequant, then under ZeRO stage 3 the layer's weights are gathered
    # HERE, whole, before any of them multiplies. Left to the partitioner,
    # h @ wq with wq sharded on its contraction dimension became a ring of
    # four K = 400 partial products, and h @ w_in four 1600-column pieces
    # each written into the [4, 1024, 6400] result by a bare
    # dynamic-update-slice: 8.7% of the four-chip step (ledger PR 31,
    # gpt2-xl.train-zero3-x4, breakdown.device_ops)
    lp = ctx.layer_weights(lp, x.dtype)
    b, s, d = x.shape
    h = layernorm(x, lp["ln1_g"], lp["ln1_b"], cfg.layer_norm_eps)
    q = (h @ lp["wq"] + lp["bq"]).reshape(b, s, cfg.num_heads, cfg.hd)
    kk = (h @ lp["wk"] + lp["bk"]).reshape(b, s, cfg.num_heads, cfg.hd)
    vv = (h @ lp["wv"] + lp["bv"]).reshape(b, s, cfg.num_heads, cfg.hd)
    q = ctx.constrain(q, "batch", "seq", "heads_act", None)
    o = ctx.attention(q, kk, vv, causal=True, impl=attn_impl).reshape(b, s, d)
    x = x + o @ lp["wo"] + lp["bo"]
    h = layernorm(x, lp["ln2_g"], lp["ln2_b"], cfg.layer_norm_eps)
    h = jax.nn.gelu(h @ lp["w_in"] + lp["b_in"], approximate=True)
    h = ctx.constrain(h, "batch", "seq", "ffn_act")
    x = x + h @ lp["w_out"] + lp["b_out"]
    return ctx.constrain(x, "batch", "seq", "embed_act")


def forward(cfg: GPT2Config, params, input_ids, ctx: ShardCtx | None = None,
            attn_impl: str = "auto", remat: bool = False, remat_policy=None,
            pld_theta=None, pld_rng=None, ltd_keep: int = 0, ltd_rng=None):
    ctx = ctx or ShardCtx()
    b, s = input_ids.shape
    # stage 3: the tied table is gathered once, for the lookup and the head
    wte = ctx.whole_weight(params["wte"], "wte")
    wpe = ctx.whole_weight(params["wpe"], "wpe")
    x = wte[input_ids] + wpe[:s][None, :, :]
    x = ctx.constrain(x, "batch", "seq", "embed_act")

    layer = partial(_block, cfg, ctx, attn_impl)
    if remat:
        layer = jax.checkpoint(layer, policy=remat_policy)
    x = ctx.layer_stack(layer, params["layers"], x,
                        pld_theta=pld_theta, pld_rng=pld_rng,
                        ltd_keep=ltd_keep, ltd_rng=ltd_rng)
    x = layernorm(x, params["lnf_g"], params["lnf_b"], cfg.layer_norm_eps)
    logits = x @ wte.T.astype(x.dtype)  # tied head
    return ctx.constrain(logits, "batch", "seq", "vocab_act")


# ------------------------------------------------------------------ inference
def init_cache(cfg: GPT2Config, batch: int, max_len: int,
               dtype=jnp.bfloat16) -> dict:
    """Dense fixed-shape KV cache [L, B, max_len, H, Dh] (v1 engine)."""
    shape = (cfg.num_layers, batch, max_len, cfg.num_heads, cfg.hd)
    return {"k": jnp.zeros(shape, dtype), "v": jnp.zeros(shape, dtype)}


def _cached_block(cfg: GPT2Config, x, lp, k_cache, v_cache, start_pos,
                  max_len: int):
    from deepspeed_tpu.models.paged import append_kv_and_attend
    from deepspeed_tpu.ops.quantizer import dequantize_layer

    lp = dequantize_layer(lp, x.dtype)
    b, t, d = x.shape
    h = layernorm(x, lp["ln1_g"], lp["ln1_b"], cfg.layer_norm_eps)
    q = (h @ lp["wq"] + lp["bq"]).reshape(b, t, cfg.num_heads, cfg.hd)
    kk = (h @ lp["wk"] + lp["bk"]).reshape(b, t, cfg.num_heads, cfg.hd)
    vv = (h @ lp["wv"] + lp["bv"]).reshape(b, t, cfg.num_heads, cfg.hd)
    o, k_cache, v_cache = append_kv_and_attend(
        q, kk, vv, k_cache, v_cache, start_pos, max_len)
    x = x + o.reshape(b, t, d) @ lp["wo"] + lp["bo"]
    h = layernorm(x, lp["ln2_g"], lp["ln2_b"], cfg.layer_norm_eps)
    h = jax.nn.gelu(h @ lp["w_in"] + lp["b_in"], approximate=True)
    return x + h @ lp["w_out"] + lp["b_out"], k_cache, v_cache


def decode_forward(cfg: GPT2Config, params, tokens, cache, start_pos,
                   ctx: ShardCtx | None = None):
    """[B, T] new tokens + cache -> ([B, T, V] logits, cache)."""
    del ctx
    max_len = cache["k"].shape[2]
    b, t = tokens.shape
    pos = start_pos + jnp.arange(t)
    x = (params["wte"][tokens] + params["wpe"][pos][None]).astype(
        cache["k"].dtype)

    def body(x, lp_kv):
        lp, kc, vc = lp_kv
        x, kc, vc = _cached_block(cfg, x, lp, kc, vc, start_pos, max_len)
        return x, (kc, vc)

    x, (new_k, new_v) = lax.scan(body, x,
                                 (params["layers"], cache["k"], cache["v"]))
    x = layernorm(x, params["lnf_g"], params["lnf_b"], cfg.layer_norm_eps)
    from deepspeed_tpu.ops.quantizer import maybe_dequantize

    logits = x @ maybe_dequantize(params["wte"], x.dtype).astype(x.dtype).T
    return logits, {"k": new_k, "v": new_v}


def init_paged_cache(cfg: GPT2Config, num_blocks: int, block_size: int,
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
                           cfg.num_heads, cfg.hd, dtype, codec)


def _ragged_block(cfg: GPT2Config, x, lp, kc, vc, positions, slots,
                  block_tables, prefill_tiles=None):
    from deepspeed_tpu.models.paged import (
        ragged_pool_attention,
        rows_to_heads,
        write_kv_paged,
    )
    from deepspeed_tpu.ops.quantizer import dequantize_layer

    lp = dequantize_layer(lp, x.dtype)
    t_tokens, d = x.shape
    h = layernorm(x, lp["ln1_g"], lp["ln1_b"], cfg.layer_norm_eps)
    q = rows_to_heads(h, lp["wq"], cfg.num_heads, lp["bq"])
    kk = rows_to_heads(h, lp["wk"], cfg.num_heads, lp["bk"])
    vv = rows_to_heads(h, lp["wv"], cfg.num_heads, lp["bv"])
    kc, vc = write_kv_paged(kc, vc, kk, vv, slots, positions, block_tables,
                            prefill_tiles)
    o = ragged_pool_attention(q, kc, vc, slots, positions, block_tables,
                              prefill_tiles).astype(x.dtype)
    x = x + o.reshape(t_tokens, d) @ lp["wo"] + lp["bo"]
    h = layernorm(x, lp["ln2_g"], lp["ln2_b"], cfg.layer_norm_eps)
    h = jax.nn.gelu(h @ lp["w_in"] + lp["b_in"], approximate=True)
    return x + h @ lp["w_out"] + lp["b_out"], kc, vc


def ragged_forward(cfg: GPT2Config, params, tokens, slots, positions,
                   block_tables, cache, prefill_tiles=None):
    """Flat ragged step: [T] mixed tokens -> ([T, V] logits, cache).
    Learned positional embeddings ride the per-token ``positions`` the
    ragged layout already carries."""
    from deepspeed_tpu.models.paged import scan_layers_paged

    x = (params["wte"][tokens] + params["wpe"][positions]).astype(
        cache["k"].dtype)

    def layer(x, lp, pool, layer_tables):
        x, kc, vc = _ragged_block(
            cfg, x, lp, pool["k"], pool["v"], positions, slots, layer_tables,
            prefill_tiles=prefill_tiles)
        return x, {"k": kc, "v": vc}

    x, cache = scan_layers_paged(layer, x, params["layers"], cache,
                                 block_tables)
    x = layernorm(x, params["lnf_g"], params["lnf_b"], cfg.layer_norm_eps)
    from deepspeed_tpu.ops.quantizer import maybe_dequantize

    logits = x @ maybe_dequantize(params["wte"], x.dtype).astype(x.dtype).T
    return logits, cache


def num_params(cfg: GPT2Config) -> int:
    d, f = cfg.hidden_size, cfg.ffn
    per_layer = 4 * d * d + 4 * d + 2 * d * f + d + f + 4 * d
    return cfg.vocab_size * d + cfg.max_seq_len * d + cfg.num_layers * per_layer + 2 * d


def flops_per_token(cfg: GPT2Config, seq_len: int) -> float:
    return 6.0 * num_params(cfg) + 12.0 * cfg.num_layers * cfg.hidden_size * seq_len / 2.0


def build(cfg: GPT2Config, ctx: ShardCtx | None = None, attn_impl: str = "auto",
          remat: bool | None = None, remat_policy=None) -> ModelSpec:
    ctx = ctx or ShardCtx()
    remat = ctx.remat if remat is None else remat
    remat_policy = remat_policy if remat_policy is not None else ctx.remat_policy
    fwd = partial(forward, cfg, ctx=ctx, attn_impl=attn_impl,
                  remat=remat, remat_policy=remat_policy)

    def loss_fn(params, batch, rng=None, ltd_keep: int = 0):
        pld = batch.get("pld_theta")
        if pld is not None and rng is None:
            raise ValueError("progressive layer drop needs the loss rng")
        if ltd_keep and rng is None:
            raise ValueError("random_ltd needs the loss rng")
        logits = fwd(params, batch["input_ids"], pld_theta=pld, pld_rng=rng,
                     ltd_keep=ltd_keep,
                     ltd_rng=(jax.random.fold_in(rng, 0x17D)
                              if ltd_keep else None))
        return causal_lm_loss(logits, batch["input_ids"], batch.get("labels"))

    return ModelSpec(
        name="gpt2",
        config=cfg,
        init_fn=partial(init_params, cfg),
        loss_fn=loss_fn,
        forward_fn=fwd,
        param_logical_axes=PARAM_LOGICAL_AXES,
        logical_dim_units={"heads": cfg.num_heads},
        num_params=num_params(cfg),
        flops_per_token=partial(flops_per_token, cfg),
        supports_pld=True,
        supports_random_ltd=True,
        woq_skip=("wte", "wpe"),
        init_cache_fn=partial(init_cache, cfg),
        decode_fn=partial(decode_forward, cfg, ctx=ctx),
        init_paged_cache_fn=partial(init_paged_cache, cfg),
        ragged_forward_fn=partial(ragged_forward, cfg),
        supports_prefill_tiles=True,
    )
