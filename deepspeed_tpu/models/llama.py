"""Llama-family causal LM (Llama 2/3 architecture), TPU-first.

Its RMSNorm / RoPE / GQA backbone is what the MoE families
(``models/mixtral.py`` and the ones after it) build on.
Functional design: parameters are a pytree with a *stacked* leading layer dim,
the decoder runs as one ``lax.scan`` over that stack — one compiled layer body
regardless of depth (fast compiles, natural pipeline partitioning, uniform
remat). The reference has no model zoo for training; its inference engine ships
per-arch implementations (``inference/v2/model_implementations/llama_v2``);
this module is the training+inference source of truth for the family.

Architecture: RMSNorm, SwiGLU MLP, RoPE, grouped-query attention, optional
tied embeddings — matching HF ``LlamaForCausalLM`` semantics.
"""

from __future__ import annotations

from dataclasses import dataclass
from functools import partial

import jax
import jax.numpy as jnp
from jax import lax

from deepspeed_tpu.models.api import ModelSpec, ShardCtx, causal_lm_loss, count_params
from deepspeed_tpu.ops.attention import apply_rope


@dataclass(frozen=True)
class LlamaConfig:
    vocab_size: int = 32000
    hidden_size: int = 4096
    intermediate_size: int = 11008
    num_layers: int = 32
    num_heads: int = 32
    num_kv_heads: int = 32
    head_dim: int | None = None
    rope_theta: float = 10000.0
    rms_norm_eps: float = 1e-5
    max_seq_len: int = 4096
    tie_embeddings: bool = False

    @property
    def hd(self) -> int:
        return self.head_dim if self.head_dim is not None else self.hidden_size // self.num_heads

    @staticmethod
    def llama3_8b() -> "LlamaConfig":
        return LlamaConfig(vocab_size=128256, hidden_size=4096, intermediate_size=14336,
                           num_layers=32, num_heads=32, num_kv_heads=8, rope_theta=500000.0,
                           max_seq_len=8192)

    @staticmethod
    def tiny(vocab_size: int = 256) -> "LlamaConfig":
        return LlamaConfig(vocab_size=vocab_size, hidden_size=64, intermediate_size=128,
                           num_layers=2, num_heads=4, num_kv_heads=2, max_seq_len=128)


def init_params(cfg: LlamaConfig, rng) -> dict:
    """fp32 master weights; scaled init on residual-out projections."""
    d, f, hd = cfg.hidden_size, cfg.intermediate_size, cfg.hd
    hq, hkv, nl = cfg.num_heads, cfg.num_kv_heads, cfg.num_layers
    k = iter(jax.random.split(rng, 16))
    std = 0.02
    out_std = std / jnp.sqrt(2.0 * nl)

    def norm(key, *shape, s=std):
        return jax.random.normal(key, shape, jnp.float32) * s

    params = {
        "embed": norm(next(k), cfg.vocab_size, d),
        "layers": {
            "attn_norm": jnp.ones((nl, d), jnp.float32),
            "wq": norm(next(k), nl, d, hq * hd),
            "wk": norm(next(k), nl, d, hkv * hd),
            "wv": norm(next(k), nl, d, hkv * hd),
            "wo": norm(next(k), nl, hq * hd, d, s=out_std),
            "mlp_norm": jnp.ones((nl, d), jnp.float32),
            "w_gate": norm(next(k), nl, d, f),
            "w_up": norm(next(k), nl, d, f),
            "w_down": norm(next(k), nl, f, d, s=out_std),
        },
        "final_norm": jnp.ones((d,), jnp.float32),
    }
    if not cfg.tie_embeddings:
        params["lm_head"] = norm(next(k), d, cfg.vocab_size)
    return params


PARAM_LOGICAL_AXES = {
    "embed": ("vocab", "embed"),
    "layers": {
        "attn_norm": ("layers", "embed"),
        "wq": ("layers", "embed", "heads"),
        "wk": ("layers", "embed", "kv_heads"),
        "wv": ("layers", "embed", "kv_heads"),
        "wo": ("layers", "heads", "embed"),
        "mlp_norm": ("layers", "embed"),
        "w_gate": ("layers", "embed", "ffn"),
        "w_up": ("layers", "embed", "ffn"),
        "w_down": ("layers", "ffn", "embed"),
    },
    "final_norm": ("embed",),
    "lm_head": ("embed", "vocab"),
}


def rmsnorm(x: jnp.ndarray, w: jnp.ndarray, eps: float) -> jnp.ndarray:
    xf = x.astype(jnp.float32)
    var = jnp.mean(xf * xf, axis=-1, keepdims=True)
    return (xf * lax.rsqrt(var + eps)).astype(x.dtype) * w.astype(x.dtype)


from deepspeed_tpu.ops.quantizer import dequantize_layer as _dq_layer  # noqa: E402


def _decoder_layer(cfg: LlamaConfig, ctx: ShardCtx, attn_impl: str,
                   x: jnp.ndarray, lp: dict, positions: jnp.ndarray | None = None) -> jnp.ndarray:
    lp = ctx.layer_weights(lp, x.dtype)
    b, s, d = x.shape
    hq, hkv, hd = cfg.num_heads, cfg.num_kv_heads, cfg.hd
    if positions is None:
        positions = jnp.broadcast_to(jnp.arange(s)[None, :], (b, s))

    h = rmsnorm(x, lp["attn_norm"], cfg.rms_norm_eps)
    q = (h @ lp["wq"]).reshape(b, s, hq, hd)
    kk = (h @ lp["wk"]).reshape(b, s, hkv, hd)
    vv = (h @ lp["wv"]).reshape(b, s, hkv, hd)
    q = ctx.constrain(q, "batch", "seq", "heads_act", None)
    kk = ctx.constrain(kk, "batch", "seq", "heads_act", None)
    q, kk = apply_rope(q, kk, positions, cfg.rope_theta)
    o = ctx.attention(q, kk, vv, causal=True, impl=attn_impl)
    x = x + o.reshape(b, s, hq * hd) @ lp["wo"]
    x = ctx.constrain(x, "batch", "seq", "embed_act")

    if ctx.mlp_tile_size:
        from deepspeed_tpu.parallel.sequence_tiling import tiled_mlp

        def mlp_fn(xs):
            hs = rmsnorm(xs, lp["mlp_norm"], cfg.rms_norm_eps)
            gate = ctx.constrain(jax.nn.silu(hs @ lp["w_gate"]),
                                 "batch", "seq", "ffn_act")
            up = ctx.constrain(hs @ lp["w_up"], "batch", "seq", "ffn_act")
            return (gate * up) @ lp["w_down"]

        x = x + tiled_mlp(mlp_fn, x, ctx.mlp_tile_size)
    else:
        h = rmsnorm(x, lp["mlp_norm"], cfg.rms_norm_eps)
        gate = jax.nn.silu(h @ lp["w_gate"])
        up = h @ lp["w_up"]
        gate = ctx.constrain(gate, "batch", "seq", "ffn_act")
        x = x + (gate * up) @ lp["w_down"]
    return ctx.constrain(x, "batch", "seq", "embed_act")


def hidden_states(cfg: LlamaConfig, params: dict, input_ids: jnp.ndarray,
                  ctx: ShardCtx | None = None, attn_impl: str = "auto",
                  remat_policy=None, remat: bool = False,
                  pld_theta=None, pld_rng=None, ltd_keep: int = 0,
                  ltd_rng=None) -> jnp.ndarray:
    """[B, S] int tokens -> [B, S, D] final (post-norm) hidden states."""
    ctx = ctx or ShardCtx()
    x = ctx.embed_lookup(params["embed"], input_ids, "batch", "seq", "embed_act")

    layer = partial(_decoder_layer, cfg, ctx, attn_impl)
    if remat:
        layer = jax.checkpoint(layer, policy=remat_policy)

    x = ctx.layer_stack(layer, params["layers"], x,
                        pld_theta=pld_theta, pld_rng=pld_rng,
                        ltd_keep=ltd_keep, ltd_rng=ltd_rng)
    return rmsnorm(x, params["final_norm"], cfg.rms_norm_eps)


def lm_head(cfg: LlamaConfig, params: dict,
            ctx: ShardCtx | None = None) -> jnp.ndarray:
    """The head's matrix [D, V]; with a training ``ctx`` under ZeRO stage 3,
    gathered over fsdp before it multiplies."""
    ctx = ctx or ShardCtx()
    if cfg.tie_embeddings:
        return ctx.whole_weight(params["embed"], "embed").T
    from deepspeed_tpu.ops.quantizer import maybe_dequantize

    return maybe_dequantize(
        ctx.whole_weight(params["lm_head"], "lm_head"), jnp.float32)


def forward(cfg: LlamaConfig, params: dict, input_ids: jnp.ndarray,
            ctx: ShardCtx | None = None, attn_impl: str = "auto",
            remat_policy=None, remat: bool = False,
            pld_theta=None, pld_rng=None) -> jnp.ndarray:
    """[B, S] int tokens -> [B, S, V] logits. Decoder is a scan over the layer stack."""
    ctx = ctx or ShardCtx()
    x = hidden_states(cfg, params, input_ids, ctx=ctx, attn_impl=attn_impl,
                      remat_policy=remat_policy, remat=remat,
                      pld_theta=pld_theta, pld_rng=pld_rng)
    logits = x @ lm_head(cfg, params, ctx).astype(x.dtype)
    return ctx.constrain(logits, "batch", "seq", "vocab_act")


# ------------------------------------------------------------------ inference
def init_cache(cfg: LlamaConfig, batch: int, max_len: int, dtype=jnp.bfloat16) -> dict:
    """Per-layer KV cache, stacked [L, B, max_len, Hkv, Dh] — the dense
    fixed-shape cache of the v1-style engine (the TPU analog of the reference
    inference KV workspace)."""
    shape = (cfg.num_layers, batch, max_len, cfg.num_kv_heads, cfg.hd)
    return {"k": jnp.zeros(shape, dtype), "v": jnp.zeros(shape, dtype)}


def _cached_layer(cfg: LlamaConfig, ctx: ShardCtx, x, lp, k_cache, v_cache,
                  start_pos, max_len: int):
    """Decode/prefill layer: append new KV at ``start_pos``, attend over the
    cache prefix with absolute-position causal masking."""
    from deepspeed_tpu.models.paged import append_kv_and_attend

    lp = _dq_layer(lp, x.dtype)
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
    x = x + (jax.nn.silu(h @ lp["w_gate"]) * (h @ lp["w_up"])) @ lp["w_down"]
    return x, k_cache, v_cache


def decode_forward(cfg: LlamaConfig, params, tokens, cache, start_pos,
                   ctx: ShardCtx | None = None):
    """[B, T] new tokens + cache -> ([B, T, V] logits, updated cache).

    Works for both prefill (T = prompt length, start_pos = 0) and incremental
    decode (T = 1). Scans over the stacked layers, carrying x and threading the
    per-layer cache through scan xs/ys.
    """
    ctx = ctx or ShardCtx()
    max_len = cache["k"].shape[2]
    # plain per-row gather: decode looks up a handful of tokens per step, so
    # embed_lookup's table replication (a training-scale fix for the gather
    # resharding remat) would all-gather the whole table every step
    x = params["embed"][tokens].astype(cache["k"].dtype)

    def body(x, lp_kv):
        lp, kc, vc = lp_kv
        x, kc, vc = _cached_layer(cfg, ctx, x, lp, kc, vc, start_pos, max_len)
        return x, (kc, vc)

    x, (new_k, new_v) = lax.scan(body, x, (params["layers"], cache["k"], cache["v"]))
    x = rmsnorm(x, params["final_norm"], cfg.rms_norm_eps)
    head = lm_head(cfg, params)
    logits = x @ head.astype(x.dtype)
    return logits, {"k": new_k, "v": new_v}


def init_paged_cache(cfg: LlamaConfig, num_blocks: int, block_size: int,
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


def _ragged_layer(cfg: LlamaConfig, x, lp, kc, vc, positions, slots,
                  block_tables, prefill_tiles=None):
    """One decoder layer over a flat ragged token batch.

    ``x`` [T, D] mixes prefill-chunk tokens and decode tokens from different
    sequences (SplitFuse layout, reference ``inference/v2/ragged``). New KV is
    scattered into the block pool *before* attention, so intra-chunk causal
    attention falls out of the position mask with no special casing.

    ``prefill_tiles``: optional ``(n_dec, tile_slot, tile_pos0, tile_valid,
    tile)`` — tokens [0, n_dec) are decodes (per-token kernel), the rest are
    tile-aligned prefill chunks (tiled kernel: one KV-block fetch per tile).
    """
    from deepspeed_tpu.models.paged import (
        ragged_pool_attention,
        rows_to_heads,
        write_kv_paged,
    )

    lp = _dq_layer(lp, x.dtype)
    t_tokens, d = x.shape
    hq, hkv, hd = cfg.num_heads, cfg.num_kv_heads, cfg.hd

    h = rmsnorm(x, lp["attn_norm"], cfg.rms_norm_eps)
    q = rows_to_heads(h, lp["wq"], hq)
    kk = rows_to_heads(h, lp["wk"], hkv)
    vv = rows_to_heads(h, lp["wv"], hkv)
    q, kk = apply_rope(q[None], kk[None], positions[None], cfg.rope_theta)
    q, kk = q[0], kk[0]

    kc, vc = write_kv_paged(kc, vc, kk, vv, slots, positions, block_tables,
                            prefill_tiles)
    o = ragged_pool_attention(q, kc, vc, slots, positions, block_tables,
                              prefill_tiles).astype(x.dtype)
    x = x + o.reshape(t_tokens, hq * hd) @ lp["wo"]

    h = rmsnorm(x, lp["mlp_norm"], cfg.rms_norm_eps)
    x = x + (jax.nn.silu(h @ lp["w_gate"]) * (h @ lp["w_up"])) @ lp["w_down"]
    return x, kc, vc


def ragged_forward(cfg: LlamaConfig, params, tokens, slots, positions,
                   block_tables, cache, prefill_tiles=None):
    """Flat ragged step: ``[T]`` mixed tokens -> (``[T, V]`` logits, cache).

    Each token carries (slot, absolute position); ``block_tables``
    [max_seqs+1, max_blocks] maps slots to KV pool blocks (row ``max_seqs`` is
    the all-scratch padding row). One static-shape XLA program serves any mix
    of prefill chunks and decodes (reference ``inference/v2/engine_v2.py:30``
    ``put()`` + ``ragged_ops`` kernels). ``prefill_tiles``: see
    ``_ragged_layer`` (tiled-prefill fast path).
    """
    # plain gather (see decode_forward's note: replication is a training fix)
    from deepspeed_tpu.models.paged import scan_layers_paged

    x = params["embed"][tokens].astype(cache["k"].dtype)

    def layer(x, lp, pool, layer_tables):
        x, kc, vc = _ragged_layer(
            cfg, x, lp, pool["k"], pool["v"], positions, slots, layer_tables,
            prefill_tiles=prefill_tiles)
        return x, {"k": kc, "v": vc}

    x, cache = scan_layers_paged(layer, x, params["layers"], cache,
                                 block_tables)
    x = rmsnorm(x, params["final_norm"], cfg.rms_norm_eps)
    head = lm_head(cfg, params)
    logits = x @ head.astype(x.dtype)
    return logits, cache


# ------------------------------------------------------------------ pipeline
def pipeline_parts(cfg: LlamaConfig, ctx: ShardCtx | None = None,
                   attn_impl: str = "auto"):
    """Stage decomposition for the 1F1B schedule
    (``parallel/pipeline_1f1b.py``): embedding on stage 0, the scanned layer
    block per stage, final-norm + head + loss on the last stage (reference
    ``PipelineModule`` places loss_fn on the last stage).

    Returns ``(stage0_fn, block_fn, last_fn, split_fn, merge_fn)``.
    """
    ctx = ctx or ShardCtx()

    def split_fn(params):
        extras = {k: v for k, v in params.items() if k != "layers"}
        return params["layers"], extras

    def merge_fn(layer_grads, extras_grads):
        return {**extras_grads, "layers": layer_grads}

    def stage0_fn(extras, mb):
        return ctx.embed_lookup(extras["embed"], mb["input_ids"],
                                "batch", "seq", "embed_act")

    def block_fn(layer_slice, extras, x):
        del extras
        layer = partial(_decoder_layer, cfg, ctx, attn_impl)
        return lax.scan(lambda c, lp: (layer(c, lp), None), x, layer_slice)[0]

    def last_fn(extras, y, mb):
        x = rmsnorm(y, extras["final_norm"], cfg.rms_norm_eps)
        head = (extras["embed"].T if cfg.tie_embeddings
                else extras["lm_head"]).astype(x.dtype)
        return causal_lm_loss(x @ head, mb["input_ids"], mb.get("labels"))

    return stage0_fn, block_fn, last_fn, split_fn, merge_fn


def num_params(cfg: LlamaConfig) -> int:
    d, f, hd = cfg.hidden_size, cfg.intermediate_size, cfg.hd
    per_layer = d * hd * (cfg.num_heads * 2 + cfg.num_kv_heads * 2) + 3 * d * f + 2 * d
    total = cfg.vocab_size * d + cfg.num_layers * per_layer + d
    if not cfg.tie_embeddings:
        total += d * cfg.vocab_size
    return total


def flops_per_token(cfg: LlamaConfig, seq_len: int) -> float:
    """Training FLOPs/token (PaLM convention): 6*N matmul + 6*L*D*S causal attention
    (12*L*D*S non-causal, halved)."""
    return 6.0 * num_params(cfg) + 12.0 * cfg.num_layers * cfg.hidden_size * seq_len / 2.0


def build(cfg: LlamaConfig, ctx: ShardCtx | None = None, attn_impl: str = "auto",
          remat: bool | None = None, remat_policy=None) -> ModelSpec:
    ctx = ctx or ShardCtx()
    remat = ctx.remat if remat is None else remat
    remat_policy = remat_policy if remat_policy is not None else ctx.remat_policy
    fwd = partial(forward, cfg, ctx=ctx, attn_impl=attn_impl,
                  remat=remat, remat_policy=remat_policy)

    def loss_fn(params, batch, rng=None, ltd_keep: int = 0):
        # progressive layer drop: the engine injects a traced theta into the
        # batch (runtime/progressive_layer_drop.py); rng drives the drops.
        # ltd_keep (STATIC): random layerwise token dropping — the engine
        # passes the bucketed schedule value and compiles per bucket.
        pld = batch.get("pld_theta")
        if pld is not None and rng is None:
            raise ValueError("progressive layer drop needs the loss rng")
        if ltd_keep and rng is None:
            raise ValueError("random_ltd needs the loss rng")
        ltd_rng = (jax.random.fold_in(rng, 0x17D) if ltd_keep else None)
        if ctx.loss_tile_size or ltd_keep:
            from deepspeed_tpu.parallel.sequence_tiling import tiled_causal_lm_loss

            x = hidden_states(cfg, params, batch["input_ids"], ctx=ctx,
                              attn_impl=attn_impl, remat=remat,
                              remat_policy=remat_policy,
                              pld_theta=pld, pld_rng=rng,
                              ltd_keep=ltd_keep, ltd_rng=ltd_rng)
            if ctx.loss_tile_size:
                return tiled_causal_lm_loss(
                    x, lm_head(cfg, params, ctx), batch["input_ids"],
                    batch.get("labels"), tile_size=ctx.loss_tile_size,
                )
            logits = x @ lm_head(cfg, params, ctx).astype(x.dtype)
            return causal_lm_loss(logits, batch["input_ids"],
                                  batch.get("labels"))
        logits = fwd(params, batch["input_ids"], pld_theta=pld, pld_rng=rng)
        return causal_lm_loss(logits, batch["input_ids"], batch.get("labels"))

    axes = dict(PARAM_LOGICAL_AXES)
    if cfg.tie_embeddings:
        axes = {k: v for k, v in axes.items() if k != "lm_head"}
    return ModelSpec(
        name="llama",
        config=cfg,
        init_fn=partial(init_params, cfg),
        loss_fn=loss_fn,
        forward_fn=fwd,
        param_logical_axes=axes,
        logical_dim_units={"heads": cfg.num_heads, "kv_heads": cfg.num_kv_heads},
        num_params=num_params(cfg),
        flops_per_token=partial(flops_per_token, cfg),
        init_cache_fn=partial(init_cache, cfg),
        decode_fn=partial(decode_forward, cfg, ctx=ctx),
        init_paged_cache_fn=partial(init_paged_cache, cfg),
        ragged_forward_fn=partial(ragged_forward, cfg),
        supports_prefill_tiles=True,
        pipeline_parts=pipeline_parts(cfg, ctx=ctx, attn_impl=attn_impl),
        # MPMD staging: untied models split cleanly (embed grads live on the
        # first stage, head grads on the last); a tied table would need its
        # gradient reduced across both end stages, which the activation
        # transport does not carry — None tells PipeEngine to refuse.
        pipeline_extras_owner=(None if cfg.tie_embeddings else {
            "embed": "first", "final_norm": "last", "lm_head": "last"}),
        supports_pld=True,
        supports_random_ltd=True,
    )
