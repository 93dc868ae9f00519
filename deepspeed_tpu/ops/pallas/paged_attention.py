"""Pallas paged attention over the blocked KV pool: flash-decode + tiled prefill.

Role parity with the reference's ragged kernels
(``inference/v2/kernels/ragged_ops/`` blocked flash attention +
``ragged/csrc`` blocked-KV layout): each ragged token reads its sequence's
KV directly from the block pool through the block table — no gather of the
full padded context (the XLA fallback in ``models/llama.ragged_forward``
materializes ``[T, max_blocks*block, H, D]``; this kernel streams one block
at a time through VMEM with online-softmax accumulation).

Mechanism: ``PrefetchScalarGridSpec`` — the block table and slot/position
vectors are scalar-prefetch operands, so the KV BlockSpec index map resolves
``pool_block = block_tables[slots[t], j]`` *before* the kernel body runs and
the DMA fetches exactly that block (the TPU paged-attention idiom). Blocks
past the token's position are predicated off with ``pl.when``.

The pool has the paged contract's storage form (``models/paged.py``):
``[blocks, BS, Hkv*D]``, a token row one lane-dense vector of all KV heads.
Both kernels fetch ``(1, BS, Hkv*D)`` blocks and split the heads inside VMEM
(``_split_heads``), so the pool is never re-laid-out around them.

Inference-only (no VJP): the ragged engine never differentiates through
decode.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from deepspeed_tpu.ops.pallas.flash_attention import interpret_mode

_NEG_INF = -1e30


def _split_heads(ref, hkv: int, d: int):
    """One pool block ``ref[0]`` ``[BS, Hkv*D]`` as float32 ``[Hkv, BS, D]``:
    a static lane slice per head (a reshape of the lane dimension is refused
    by Mosaic at D = 64; the slices compile at 64 and 128)."""
    return jnp.stack([ref[0, :, g * d:(g + 1) * d].astype(jnp.float32)
                      for g in range(hkv)])


def _kernel(slots_ref, pos_ref, bt_ref, q_ref, k_ref, v_ref, o_ref,
            acc, m_sc, l_sc, *, bs: int, hkv: int, rep: int, scale: float):
    t = pl.program_id(0)
    j = pl.program_id(1)
    nj = pl.num_programs(1)
    pos = pos_ref[t]

    @pl.when(j == 0)
    def _init():
        acc[:] = jnp.zeros_like(acc)
        m_sc[:] = jnp.full_like(m_sc, _NEG_INF)
        l_sc[:] = jnp.zeros_like(l_sc)

    @pl.when(j * bs <= pos)
    def _compute():
        q = q_ref[0].astype(jnp.float32) * scale          # [Hq, D]
        hq, d = q.shape
        k = _split_heads(k_ref, hkv, d)                   # [Hkv, BS, D]
        v = _split_heads(v_ref, hkv, d)
        qg = q.reshape(hkv, rep, d)
        # scores[g, r, k] over this block's keys
        s = jax.lax.dot_general(
            qg, k, (((2,), (2,)), ((0,), (0,))),          # contract D, batch g
        )                                                 # [Hkv, rep, BS]
        kpos = j * bs + jax.lax.broadcasted_iota(jnp.int32, (1, 1, bs), 2)
        s = jnp.where(kpos <= pos, s, _NEG_INF)
        s = s.reshape(hq, bs)
        m_blk = jnp.max(s, axis=-1, keepdims=True)        # [Hq, 1]
        m_prev = m_sc[:, :1]
        m_new = jnp.maximum(m_prev, m_blk)
        p = jnp.exp(s - m_new)                            # [Hq, BS]
        corr = jnp.exp(m_prev - m_new)                    # [Hq, 1]
        l_sc[:, :1] = l_sc[:, :1] * corr + jnp.sum(p, -1, keepdims=True)
        m_sc[:, :1] = m_new
        pg = p.reshape(hkv, rep, bs)
        pv = jax.lax.dot_general(
            pg, v, (((2,), (1,)), ((0,), (0,))),          # [Hkv, rep, D]
        ).reshape(hq, d)
        acc[:] = acc[:] * corr + pv

    @pl.when(j == nj - 1)
    def _finish():
        o_ref[0] = (acc[:] / jnp.maximum(l_sc[:, :1], 1e-30)).astype(o_ref.dtype)


def paged_decode_attention(q, k_pool, v_pool, slots, positions, block_tables,
                           scale: float | None = None,
                           interpret: bool | None = None):
    """[T, Hq, D] ragged tokens -> [T, Hq, D] attention outputs.

    ``k_pool``/``v_pool``: [blocks, BS, Hkv*D]; ``block_tables``:
    [max_seqs+1, MB] mapping (slot, block-ordinal) -> pool block id. Exact
    vs the dense-gather path (same position masking).
    """
    t_tokens, hq, d = q.shape
    _, bs, hd = k_pool.shape
    hkv = hd // d
    mb = block_tables.shape[1]
    rep = hq // hkv
    scale = scale if scale is not None else 1.0 / (d ** 0.5)

    # Past the token's last valid block (j > pos // bs) the index map clamps
    # to that last block: the pipeline sees an unchanged block id, skips the
    # DMA, and the body's `pl.when` predicate skips the compute — so decode
    # bandwidth scales with the actual context, not the table width, and
    # nothing is ever read through freed/stale block_tables entries.
    def _kv_map(t, j, sl, po, bt):
        return (bt[sl[t], jnp.minimum(j, po[t] // bs)], 0, 0)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(t_tokens, mb),
        in_specs=[
            pl.BlockSpec((1, hq, d), lambda t, j, sl, po, bt: (t, 0, 0)),
            pl.BlockSpec((1, bs, hd), _kv_map),
            pl.BlockSpec((1, bs, hd), _kv_map),
        ],
        out_specs=pl.BlockSpec((1, hq, d), lambda t, j, sl, po, bt: (t, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((hq, d), jnp.float32),
            pltpu.VMEM((hq, 128), jnp.float32),
            pltpu.VMEM((hq, 128), jnp.float32),
        ],
    )
    kernel = functools.partial(_kernel, bs=bs, hkv=hkv, rep=rep, scale=scale)
    return pl.pallas_call(
        kernel,
        out_shape=jax.ShapeDtypeStruct((t_tokens, hq, d), q.dtype),
        grid_spec=grid_spec,
        interpret=interpret_mode(interpret),
        name="paged_decode",
    )(slots.astype(jnp.int32), positions.astype(jnp.int32),
      block_tables.astype(jnp.int32), q, k_pool, v_pool)


# --------------------------------------------------------------- tiled prefill
def _prefill_kernel(ts_ref, tp_ref, tv_ref, bt_ref, q_ref, k_ref, v_ref, o_ref,
                    acc, m_sc, l_sc, *, bs: int, ct: int, hkv: int, rep: int,
                    scale: float):
    c = pl.program_id(0)   # query tile
    j = pl.program_id(1)   # kv block ordinal
    nj = pl.num_programs(1)
    pos0 = tp_ref[c]
    valid = tv_ref[c]
    max_pos = pos0 + valid - 1

    @pl.when(j == 0)
    def _init():
        acc[:] = jnp.zeros_like(acc)
        m_sc[:] = jnp.full_like(m_sc, _NEG_INF)
        l_sc[:] = jnp.zeros_like(l_sc)

    @pl.when(jnp.logical_and(valid > 0, j * bs <= max_pos))
    def _compute():
        q = q_ref[...].astype(jnp.float32) * scale        # [CT, Hq, D]
        d = q.shape[2]
        k = _split_heads(k_ref, hkv, d)                   # [Hkv, BS, D]
        v = _split_heads(v_ref, hkv, d)
        # GQA layout: [Hkv, CT*rep, D]; row r -> query token i = r // rep
        qg = q.reshape(ct, hkv, rep, d).transpose(1, 0, 2, 3).reshape(
            hkv, ct * rep, d)
        s = jax.lax.dot_general(
            qg, k, (((2,), (2,)), ((0,), (0,))),
        )                                                 # [Hkv, CT*rep, BS]
        qi = jax.lax.broadcasted_iota(jnp.int32, (1, ct * rep, 1), 1) // rep
        qpos = pos0 + qi
        kpos = j * bs + jax.lax.broadcasted_iota(jnp.int32, (1, 1, bs), 2)
        mask = jnp.logical_and(kpos <= qpos, qi < valid)
        s = jnp.where(mask, s, _NEG_INF)
        m_blk = jnp.max(s, axis=-1, keepdims=True)        # [Hkv, CT*rep, 1]
        m_prev = m_sc[:, :, :1]
        m_new = jnp.maximum(m_prev, m_blk)
        p = jnp.exp(s - m_new)
        # fully-masked rows (pad queries / no visible keys in this block)
        # produce exp(-inf - -inf); zero them rather than poison l
        p = jnp.where(m_new > _NEG_INF / 2, p, 0.0)
        corr = jnp.exp(m_prev - m_new)
        l_sc[:, :, :1] = l_sc[:, :, :1] * corr + jnp.sum(p, -1, keepdims=True)
        m_sc[:, :, :1] = m_new
        pv = jax.lax.dot_general(
            p, v, (((2,), (1,)), ((0,), (0,))),
        )                                                 # [Hkv, CT*rep, D]
        acc[:] = acc[:] * corr + pv

    @pl.when(j == nj - 1)
    def _finish():
        d = acc.shape[2]
        out = acc[:] / jnp.maximum(l_sc[:, :, :1], 1e-30)
        o_ref[...] = out.reshape(hkv, ct, rep, d).transpose(1, 0, 2, 3).reshape(
            ct, hkv * rep, d).astype(o_ref.dtype)


# Scoped VMEM a Mosaic kernel may use by default (v5e), and what one query
# row of one head costs the prefill kernel in it: three float32 scratches
# (acc, m, l; D and the m/l column pad to 128 lanes), the double-buffered
# bf16 q and o blocks, and about three float32 temporaries (q, scores, p).
# Checked against the compiler: 128 rows x 32 heads x 128 is refused at
# 16.2-16.4 MiB, 128 x 25 x 64 is accepted (tests/unit/test_compile_tpu.py).
_VMEM_SCOPED_BYTES = 16 * 2**20
_PREFILL_BYTES_PER_LANE = 3 * 4 + 2 * 2 * 2 + 3 * 4


def prefill_kernel_tile(tile: int, hq: int, d: int) -> int:
    """Largest power-of-two split of the scheduler's ``tile`` whose working
    set fits scoped VMEM with an eighth to spare (the K/V blocks and the
    compiler's own stack share the limit)."""
    row = hq * max(d, 128) * _PREFILL_BYTES_PER_LANE
    ct = tile
    while ct > 8 and ct % 2 == 0 and ct * row > _VMEM_SCOPED_BYTES * 7 // 8:
        ct //= 2
    return ct


def split_tiles(tile_slot, tile_pos0, tile_valid, tile: int, ct: int):
    """The scheduler's tiles of ``tile`` rows as consecutive sub-tiles of
    ``ct``: a sub-tile is itself a tile of the same sequence."""
    if ct == tile:
        return tile_slot, tile_pos0, tile_valid
    sub0 = jnp.arange(tile // ct, dtype=jnp.int32) * ct
    return (jnp.repeat(tile_slot, tile // ct),
            (tile_pos0[:, None] + sub0).reshape(-1),
            jnp.clip(tile_valid[:, None] - sub0, 0, ct).reshape(-1))


def ragged_prefill_attention(q, k_pool, v_pool, tile_slot, tile_pos0,
                             tile_valid, block_tables, tile: int,
                             scale: float | None = None,
                             interpret: bool | None = None):
    """Tiled prefill attention: [NT*CT, Hq, D] tile-aligned prefill tokens ->
    outputs, one KV-block DMA shared by the whole CT-token tile (the
    SplitFuse blocked flash attention, reference
    ``inference/v2/kernels/ragged_ops`` — vs the decode kernel above, which
    fetches per TOKEN and is O(context) DMA per token).

    Scheduler contract (``inference/ragged.py``): each tile's tokens belong
    to ONE sequence at consecutive positions ``pos0..pos0+valid-1``; rows
    past ``valid`` are padding. ``tile_valid == 0`` marks an all-pad tile.

    Where a whole scheduler tile does not fit scoped VMEM (32 heads x 128),
    each tile runs as consecutive sub-tiles: a sub-tile is itself a tile of
    the same sequence, so the kernel and its masking are unchanged and only
    the KV block is fetched once per sub-tile instead of once per tile.
    """
    t_tokens, hq, d = q.shape
    _, bs, hd = k_pool.shape
    hkv = hd // d
    mb = block_tables.shape[1]
    rep = hq // hkv
    ct = prefill_kernel_tile(tile, hq, d)
    tile_slot, tile_pos0, tile_valid = split_tiles(
        tile_slot, tile_pos0, tile_valid, tile, ct)
    n_tiles = t_tokens // ct
    scale = scale if scale is not None else 1.0 / (d ** 0.5)

    # clamp past the tile's last needed block: unchanged id -> no new DMA
    def _kv_map(c, j, ts, tp, tv, bt):
        last = jnp.maximum(tp[c] + tv[c] - 1, 0) // bs
        return (bt[ts[c], jnp.minimum(j, last)], 0, 0)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=4,
        grid=(n_tiles, mb),
        in_specs=[
            pl.BlockSpec((ct, hq, d), lambda c, j, ts, tp, tv, bt: (c, 0, 0)),
            pl.BlockSpec((1, bs, hd), _kv_map),
            pl.BlockSpec((1, bs, hd), _kv_map),
        ],
        out_specs=pl.BlockSpec((ct, hq, d),
                               lambda c, j, ts, tp, tv, bt: (c, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((hkv, ct * rep, d), jnp.float32),
            pltpu.VMEM((hkv, ct * rep, 128), jnp.float32),
            pltpu.VMEM((hkv, ct * rep, 128), jnp.float32),
        ],
    )
    kernel = functools.partial(_prefill_kernel, bs=bs, ct=ct, hkv=hkv,
                               rep=rep, scale=scale)
    return pl.pallas_call(
        kernel,
        out_shape=jax.ShapeDtypeStruct((t_tokens, hq, d), q.dtype),
        grid_spec=grid_spec,
        interpret=interpret_mode(interpret),
        name="tiled_prefill",
    )(tile_slot.astype(jnp.int32), tile_pos0.astype(jnp.int32),
      tile_valid.astype(jnp.int32), block_tables.astype(jnp.int32),
      q, k_pool, v_pool)
