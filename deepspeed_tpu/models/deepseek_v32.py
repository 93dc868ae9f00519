"""DeepSeek-V3.2 (``model_type: deepseek_v32``): ``models/deepseek.py``'s
layers (MLA with a low-rank query, YaRN, group-limited sigmoid routing, a held
share of the experts) with DeepSeek sparse attention: a lightning indexer picks,
for every query, the ``index_topk`` cached positions its attention reads.

Every layer, the dense ones too (``h`` the normed input, ``cq`` the normed
low-rank query the MLA query also comes from):

- **Indexer.** ``qI = cq W_Iq -> [T, HI, DI]`` and ``kI = LayerNorm(h W_Ik)``
  ``[T, DI]``, RoPE (the model's frequencies) on the first ``qk_rope_head_dim``
  lanes of both; ``wI = h W_Iw * HI^-0.5 * DI^-0.5`` ``[T, HI]``;
  ``I[t, s] = sum_j wI[t, j] relu(qI[t, j] . kI[s])`` for ``s <= t``.
- **Selection.** ``S_t``: the ``index_topk`` positions ``s <= t`` with the
  largest ``I[t, s]``, the lowest position first among equals; all of them while
  ``t < index_topk``. ``select_mask`` finds the k-th largest score by a search
  over the scores' bit patterns (32 compare-and-count passes, then 13 over the
  positions for the tie rule): the exact top-k, and no sort.
- **Attention.** MLA's softmax over ``s in S_t`` only.

Serving keeps TWO rows a token and layer, in two block leaves behind one block
table (``models/paged.py``): the latent row ``"kv"`` as ``deepseek`` has it and
the index key ``"idx"`` (``DI`` lanes). A step scatters both, scores its
queries against every cached index key of their own sequences
(``ops/pallas/dsa_attention.dsa_index_scores``), selects, and attends over the
kept rows alone: a prefill tile through ``dsa_prefill_attention`` with the
selection as a bias; a decode row in one of two forms, by the width of the step
program's block table (``decode_form``): up to ``WALK_MAX_TABLE_TOKENS`` it
walks its own blocks with the selection as a mask on the scores
(``mla_attention.mla_decode_attention(keep=...)``, the walk ``deepseek``'s
decode rows take, reading the whole context at the pool's bandwidth); past that
it attends over a gather of its ``min(context, index_topk)`` kept rows
(``_gather_kept`` + ``dsa_decode_attention``). Off the chip the same steps run
as plain XLA (the ``*_xla`` forms below, which the kernels' tests compare with).

Departures from the published model (each also under ``assumed`` in the
benchmark's configuration): the published indexer rotates ``qI`` and ``kI`` by a
Hadamard matrix and keeps ``kI`` in FP8 with scales; the rotation is orthogonal
and changes no score, so it is left out, and the index keys are cached in the
engine's dtype. The multi-token-prediction module is outside the forward pass.
A quantized pool is refused (``init_paged_cache``).
"""

from __future__ import annotations

from dataclasses import dataclass
from functools import partial

import jax
import jax.numpy as jnp
from jax import lax

from deepspeed_tpu.models import deepseek
from deepspeed_tpu.models.api import ModelSpec, ShardCtx, causal_lm_loss
from deepspeed_tpu.models.deepseek import DeepseekConfig, _rope
from deepspeed_tpu.models.experts import expert_form

# a step program beside each tile count for 0 and for max_seqs decode rows:
# a padding decode row costs this model a walk of one block
DECODE_BUCKET_MIN = 16
_INT_MIN = -2 ** 31
_NEG_INF = -1e30
# The widest block table, in tokens, under which a decode row WALKS its whole
# context under the selection's mask; past it the kept rows are gathered. The
# walk reads every cached row at the pool's bandwidth (41 us a 1K tokens of 16
# rows' context); the gather reads index_topk rows a row at XLA's ~40 GB/s
# behind an index that is a compare-and-count over [rows, index_topk, table],
# so it grows with the table too. One layer on the chip, 16 rows of 128 heads,
# walk / gather us at tables of 4K, 8K, 16K, 32K tokens three quarters full:
# 200 / 835, 268 / 929, 524 / 1,130, 984 / 1,614; full: 346 / 929 at 8K, 660
# / 1,131 at 16K, 1,312 / 1,619 at 32K (PERF.md section 6, PR 37). The walk
# won at every width timed, so this is the widest one timed; the two lines
# would meet near 64K.
WALK_MAX_TABLE_TOKENS = 32768


def decode_form(table_tokens: int) -> str:
    """``"walk"`` | ``"gather"``: how the decode rows of a step program whose
    block table is ``table_tokens`` wide read the latent pool (module doc).
    The table's width is static in a step program, so the rule costs a step
    nothing; the engine writes its answer on ``engine/dispatch``."""
    return "walk" if table_tokens <= WALK_MAX_TABLE_TOKENS else "gather"


@dataclass(frozen=True)
class DeepseekV32Config(DeepseekConfig):
    index_n_heads: int = 64
    index_head_dim: int = 128
    index_topk: int = 2048
    layer_norm_eps: float = 1e-6          # the index key's LayerNorm

    def __post_init__(self):
        super().__post_init__()
        if self.q_lora_rank is None:
            raise ValueError("deepseek_v32: the indexer's queries come from "
                             "the low-rank query (q_lora_rank)")
        if self.index_head_dim < self.qk_rope_head_dim or self.index_topk < 1:
            raise ValueError("deepseek_v32: index_head_dim must hold the "
                             "roped lanes and index_topk be positive")

    @staticmethod
    def tiny(vocab_size: int = 256) -> "DeepseekV32Config":
        """3 layers (1 dense), 2 heads, a 24-wide low-rank query; 8 experts
        in 4 groups of which 2 stay, top-3; 2 index heads of 24 lanes keeping
        8 positions a query."""
        return DeepseekV32Config(
            vocab_size=vocab_size, hidden_size=64, intermediate_size=96,
            moe_intermediate_size=48, num_layers=3, num_heads=2,
            kv_lora_rank=32, q_lora_rank=24, qk_nope_head_dim=16,
            qk_rope_head_dim=16, v_head_dim=16, num_experts=8,
            num_shared_experts=1, top_k=3, first_k_dense=1, n_group=4,
            topk_group=2, rope_theta=10000.0, rms_norm_eps=1e-6,
            rope_scaling={"type": "yarn", "factor": 4.0, "beta_fast": 32,
                          "beta_slow": 1, "mscale": 1.0, "mscale_all_dim": 1.0,
                          "original_max_position_embeddings": 16},
            index_n_heads=2, index_head_dim=24, index_topk=8, max_seq_len=128)


def init_params(cfg: DeepseekV32Config, rng) -> dict:
    """``deepseek.init_params`` plus the indexer's weights in every layer. The
    draws come from the device's own generator (``nemotron_h.init_params``
    says why)."""
    rng = jax.random.wrap_key_data(jax.random.bits(rng, (4,), jnp.uint32),
                                   impl="rbg")
    params = deepseek.init_params(cfg, rng)
    d, hi, di = cfg.hidden_size, cfg.index_n_heads, cfg.index_head_dim
    k = iter(jax.random.split(jax.random.fold_in(rng, 1), 6))

    def indexer(n):
        def norm(*shape):
            return jax.random.normal(next(k), (n,) + shape, jnp.float32) * 0.02

        return {"wi_q": norm(cfg.q_lora_rank, hi * di), "wi_k": norm(d, di),
                "wi_k_norm": jnp.ones((n, di), jnp.float32),
                "wi_k_bias": jnp.zeros((n, di), jnp.float32),
                "wi_w": norm(d, hi)}

    return {**params,
            "dense": {**params["dense"], **indexer(cfg.first_k_dense)},
            "layers": {**params["layers"], **indexer(cfg.num_moe_layers)}}


_INDEXER_AXES = {
    "wi_q": ("layers", None, None),
    "wi_k": ("layers", "embed", None),
    "wi_k_norm": ("layers", None),
    "wi_k_bias": ("layers", None),
    "wi_w": ("layers", "embed", None),
}


def param_logical_axes(cfg: "DeepseekV32Config") -> dict:
    axes = deepseek.param_logical_axes(cfg)
    return {**axes, "dense": {**axes["dense"], **_INDEXER_AXES},
            "layers": {**axes["layers"], **_INDEXER_AXES}}


# ------------------------------------------------------ indexer and selection
def _indexer_inputs(cfg: DeepseekV32Config, h, cq, lp, positions):
    """``h`` [T, D] (normed), ``cq`` [T, q_lora_rank] -> the roped index
    queries [T, HI, DI], the roped index key [T, DI], the heads' weights
    [T, HI] float32 with both scale factors in."""
    from deepspeed_tpu.models.paged import rows_to_heads

    hi, di, rope = cfg.index_n_heads, cfg.index_head_dim, cfg.qk_rope_head_dim
    q = rows_to_heads(cq, lp["wi_q"], hi)
    k = (h @ lp["wi_k"]).astype(jnp.float32)
    k = k - jnp.mean(k, axis=-1, keepdims=True)
    k = k * lax.rsqrt(jnp.mean(k * k, axis=-1, keepdims=True)
                      + cfg.layer_norm_eps)
    k = (k * lp["wi_k_norm"].astype(jnp.float32)
         + lp["wi_k_bias"].astype(jnp.float32)).astype(h.dtype)
    q_rope, k_rope = _rope(cfg, q[..., :rope], k[:, :rope], positions)
    w = (h @ lp["wi_w"]).astype(jnp.float32) * (hi ** -0.5 * di ** -0.5)
    return (jnp.concatenate([q_rope, q[..., rope:]], axis=-1),
            jnp.concatenate([k_rope, k[:, rope:]], axis=-1), w)


def _order_keys(scores):
    """float32 -> int32 with the same order (``-0.0`` equal to ``0.0``)."""
    bits = lax.bitcast_convert_type(scores.astype(jnp.float32), jnp.int32)
    return jnp.where(bits < 0, jnp.where(bits == _INT_MIN, 0,
                                         bits ^ 0x7FFFFFFF), bits)


def select_mask(scores, positions, k: int):
    """``[R, S]`` bool: for row ``r`` the ``k`` entries ``s <= positions[r]``
    with the largest ``scores[r, s]``, the lowest ``s`` first among equals;
    every ``s <= positions[r]`` where there are no more than ``k``.

    The k-th largest value comes from a search over the scores' bit patterns,
    most significant bit first: a bit stays set if at least ``k`` entries are
    as large as the pattern so far (32 passes of compare and count over the
    row, whatever ``k``); then the last position that still fits among the
    entries equal to it, the same way over the position's bits. ``lax.top_k``
    at 2,048 of 8,192 is a sort on the chip."""
    r, s = scores.shape
    pos = jnp.arange(s, dtype=jnp.int32)[None, :]
    causal = pos <= positions[:, None]
    keys = jnp.where(causal, _order_keys(scores), _INT_MIN)

    def count(hit):
        return jnp.sum(hit, axis=-1, dtype=jnp.int32)

    def value_bit(i, prefix):
        # ``prefix`` in offset binary (the order of unsigned integers);
        # ``^ _INT_MIN`` takes it to the signed order ``keys`` has
        cand = prefix | lax.shift_left(jnp.int32(1), 31 - i)
        enough = count(keys >= (cand ^ _INT_MIN)[:, None]) >= k
        return jnp.where(enough, cand, prefix)

    kth = lax.fori_loop(0, 32, value_bit, jnp.zeros((r,), jnp.int32)) ^ _INT_MIN
    above = keys > kth[:, None]
    equal = keys == kth[:, None]
    room = k - count(above)                    # >= 1: how many equals fit
    bits = max(1, (s - 1).bit_length())

    def position_bit(i, last):
        # the largest position with fewer than ``room`` equals up to it
        cand = last + lax.shift_left(jnp.int32(1), bits - 1 - i)
        fewer = count(equal & (pos <= cand[:, None])) < room
        return jnp.where(fewer, cand, last)

    last = lax.fori_loop(0, bits, position_bit,
                         jnp.full((r,), -1, jnp.int32)) + 1
    return causal & (above | (equal & (pos <= last[:, None])))


def index_scores_xla(q, w, pool, slots, positions, block_tables):
    """``dsa_attention.dsa_index_scores`` as plain XLA: a gather of every
    row's whole table of index keys. [T, S] float32."""
    t = q.shape[0]
    keys = pool[block_tables[slots]].reshape(t, -1, pool.shape[-1])
    s = jnp.einsum("thd,tsd->ths", q.astype(jnp.float32),
                   keys.astype(jnp.float32))
    return jnp.sum(jnp.maximum(s, 0.0) * w[:, :, None], axis=1)


def _gather_kept(mask, k: int, pool, slots, block_tables):
    """The rows of ``pool`` [blocks, BS, W] that ``mask`` [T, S] keeps (at
    most ``k`` a row), in position order -> ``([T, k, W], n_kept [T])``;
    entries past ``n_kept`` repeat the sequence's first row. The only read of
    the latent pool a decode row makes: ``min(context, k)`` rows.

    The j-th kept position is the number of positions with fewer than ``j +
    1`` kept rows up to them: a compare-and-count over ``[T, k, S]`` that the
    compiler fuses into the sum (a scatter of the positions to their ranks
    is serial on the chip: 0.86 ms a layer at 16 rows, PERF.md 6, PR 33)."""
    bs = pool.shape[1]
    upto = jnp.cumsum(mask, axis=-1, dtype=jnp.int32)              # [T, S]
    n_kept = upto[:, -1]
    j = jnp.arange(k, dtype=jnp.int32)
    idx = jnp.sum(upto[:, None, :] <= j[None, :, None], axis=-1,
                  dtype=jnp.int32)                                  # [T, k]
    idx = jnp.where(j[None, :] < n_kept[:, None], idx, 0)
    blk = jnp.take_along_axis(block_tables[slots], idx // bs, axis=1)
    # rows of the pool as ONE axis: a plain row gather
    flat = pool.reshape((-1,) + pool.shape[2:])
    return flat[blk * bs + idx % bs], n_kept


def decode_attention_xla(q, rows, n_kept, lat: int, scale: float):
    """``dsa_attention.dsa_decode_attention`` as plain XLA."""
    rows = rows.astype(jnp.float32)
    s = jnp.einsum("thw,tkw->thk", q.astype(jnp.float32) * scale, rows)
    kept = jnp.arange(rows.shape[1])[None, :] < n_kept[:, None]
    p = jax.nn.softmax(jnp.where(kept[:, None, :], s, _NEG_INF), axis=-1)
    return jnp.einsum("thk,tkl->thl", p, rows[..., :lat]).astype(q.dtype)


def prefill_attention_xla(q_lat, q_rope, pool, bias, slots, block_tables,
                          scale: float):
    """``dsa_attention.dsa_prefill_attention`` as plain XLA, a row at a time
    over a gather of its whole table (``slots`` [T]: every row's own), on
    the kernel's head-major rows in their two parts: ``q_lat`` [H, T, lat],
    ``q_rope`` [H, T, W - lat] -> [H, T, lat]."""
    q = jnp.concatenate([q_lat, q_rope], axis=-1).astype(jnp.float32)
    ctx = pool[block_tables[slots]].reshape(q.shape[1], -1, pool.shape[-1]
                                            ).astype(jnp.float32)
    s = jnp.einsum("htw,tcw->htc", q * scale, ctx)
    p = jax.nn.softmax(s + bias[None], axis=-1)
    return jnp.einsum("htc,tcl->htl", p, ctx[..., :q_lat.shape[-1]]
                      ).astype(q_lat.dtype)


def sparse_pool_attention(cfg: DeepseekV32Config, q_dec, q_tiles, q_idx, w_idx,
                          pool_kv, pool_idx, slots, positions, block_tables,
                          prefill_tiles=None, impl: str = "auto"):
    """Absorbed MLA attention of a flat ragged batch over the rows its
    indexer keeps: ``q_dec`` [n_dec, H, W] and ``q_tiles`` (the pair [H, T -
    n_dec, lat], [H, T - n_dec, W - lat]) as ``paged.latent_queries`` gives
    them, ``q_idx`` [T, HI, DI] and
    ``w_idx`` [T, HI] the indexer's, the two pools with this step's rows in
    them -> [H, T, lat] (``paged.head_major``)."""
    from deepspeed_tpu.models.paged import head_major
    from deepspeed_tpu.ops.attention import _on_tpu
    from deepspeed_tpu.ops.pallas import dsa_attention as dsa
    from deepspeed_tpu.ops.pallas.mla_attention import mla_decode_attention

    if impl == "auto":
        impl = "pallas" if _on_tpu() else "xla"
    lat, scale, k = cfg.kv_lora_rank, cfg.softmax_scale, cfg.index_topk
    if impl == "pallas":
        scores = dsa.dsa_index_scores(q_idx, w_idx, pool_idx, slots, positions,
                                      block_tables, prefill_tiles)
    else:
        scores = index_scores_xla(q_idx, w_idx, pool_idx, slots, positions,
                                  block_tables)
    mask = select_mask(scores, positions, k)
    n_dec = 0 if q_dec is None else q_dec.shape[0]
    o_dec = o_tiles = None
    if n_dec and decode_form(mask.shape[1]) == "walk":
        if impl == "pallas":
            o_dec = mla_decode_attention(
                q_dec, pool_kv, slots[:n_dec], positions[:n_dec],
                block_tables, lat, scale, keep=mask[:n_dec])
        else:
            q = jnp.swapaxes(q_dec, 0, 1)
            o_dec = jnp.swapaxes(prefill_attention_xla(
                q[..., :lat], q[..., lat:], pool_kv,
                jnp.where(mask[:n_dec], 0.0, _NEG_INF), slots[:n_dec],
                block_tables, scale), 0, 1)
    elif n_dec:
        rows, n_kept = _gather_kept(mask[:n_dec], min(k, mask.shape[1]),
                                    pool_kv, slots[:n_dec], block_tables)
        attend = (dsa.dsa_decode_attention if impl == "pallas"
                  else decode_attention_xla)
        o_dec = attend(q_dec, rows, n_kept, lat, scale)
    if q_tiles is not None:
        bias = jnp.where(mask[n_dec:], 0.0, _NEG_INF)
        if impl == "pallas":
            _, ts, tp, tv, ct = prefill_tiles
            o_tiles = dsa.dsa_prefill_attention(
                *q_tiles, pool_kv, bias, ts, tp, tv, block_tables, ct, scale)
        else:
            o_tiles = prefill_attention_xla(
                *q_tiles, pool_kv, bias, slots[n_dec:], block_tables, scale)
    return head_major(o_dec, o_tiles)


# -------------------------------------------------------------------- forward
def _select_bias(cfg: DeepseekV32Config, h, cq, lp, positions):
    """``deepseek._layer``'s ``select``: the dense ``[B, 1, S, S]`` bias of
    the plain forward pass (0 on a kept pair, -1e30 elsewhere)."""
    b, s = positions.shape
    q, k, w = _indexer_inputs(cfg, h, cq, lp, positions.reshape(-1))
    q = q.reshape(b, s, *q.shape[1:]).astype(jnp.float32)
    k = k.reshape(b, s, -1).astype(jnp.float32)
    scores = jnp.sum(jnp.maximum(jnp.einsum("bqhd,bkd->bqhk", q, k), 0.0)
                     * w.reshape(b, s, -1, 1), axis=2)           # [B, S, S]
    mask = select_mask(scores.reshape(b * s, s), positions.reshape(-1),
                       cfg.index_topk)
    return jnp.where(mask, 0.0, _NEG_INF).reshape(b, 1, s, s)


def forward(cfg: DeepseekV32Config, params, input_ids,
            ctx: ShardCtx | None = None, remat: bool = False,
            remat_policy=None):
    """``deepseek.forward`` with every layer's attention over the positions
    its indexer keeps (a dense ``[S, S]`` of index scores a layer)."""
    return deepseek.forward(cfg, params, input_ids, ctx, remat, remat_policy,
                            select=partial(_select_bias, cfg))


# ------------------------------------------------------------------ inference
def init_paged_cache(cfg: DeepseekV32Config, num_blocks: int, block_size: int,
                     dtype=jnp.bfloat16, codec=None, num_slots=None) -> dict:
    """``deepseek.init_paged_cache``'s latent pool and, beside it under the
    same block ids, the index keys: ``{"kv": [L, NB, BS, row_lanes], "idx":
    [L, NB, BS, index_head_dim]}``."""
    pool = deepseek.init_paged_cache(cfg, num_blocks, block_size, dtype,
                                     codec, num_slots)
    return {**pool, "idx": jnp.zeros(
        (cfg.num_layers, num_blocks, block_size, cfg.index_head_dim), dtype)}


def ragged_forward(cfg: DeepseekV32Config, params, tokens, slots, positions,
                   block_tables, cache, prefill_tiles=None):
    """``deepseek.ragged_forward`` with both rows scattered and the attention
    sparse (module doc)."""
    from deepspeed_tpu.models.paged import write_rows_paged

    def sparse(layer_tables):
        def attend(h, cq, lp, q, pool):
            q_idx, k_idx, w_idx = _indexer_inputs(cfg, h, cq, lp, positions)
            idx = write_rows_paged(pool["idx"], k_idx, slots, positions,
                                   layer_tables, prefill_tiles)
            o_lat = sparse_pool_attention(
                cfg, *q, q_idx, w_idx, pool["kv"], idx, slots, positions,
                layer_tables, prefill_tiles)
            return o_lat, {"kv": pool["kv"], "idx": idx}

        return attend

    return deepseek.ragged_forward(cfg, params, tokens, slots, positions,
                                   block_tables, cache, prefill_tiles,
                                   sparse=sparse)


def _indexer_params(cfg: DeepseekV32Config) -> int:
    hi, di = cfg.index_n_heads, cfg.index_head_dim
    return (cfg.q_lora_rank * hi * di + cfg.hidden_size * (di + hi) + 2 * di)


def num_params(cfg: DeepseekV32Config) -> int:
    return deepseek.num_params(cfg) + cfg.num_layers * _indexer_params(cfg)


def flops_per_token(cfg: DeepseekV32Config, seq_len: int) -> float:
    """``deepseek.flops_per_token`` with the indexer's projections and its
    scores over ``seq_len`` added, the attention over the kept positions."""
    kept = min(seq_len, 2 * cfg.index_topk)   # deepseek counts seq_len / 2
    hi, di = cfg.index_n_heads, cfg.index_head_dim
    return (deepseek.flops_per_token(cfg, kept)
            + 6.0 * cfg.num_layers * (_indexer_params(cfg)
                                      + hi * di * seq_len / 2.0))


def build(cfg: DeepseekV32Config, ctx: ShardCtx | None = None,
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
        name="deepseek_v32",
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
        decode_bucket_min=DECODE_BUCKET_MIN,
        index_topk=cfg.index_topk,
        sparse_decode_form=decode_form,
    )
