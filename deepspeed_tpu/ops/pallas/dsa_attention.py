"""Pallas kernels of learned sparse attention over paged pools (DeepSeek
sparse attention): the lightning indexer's scores over a paged cache of index
keys, and absorbed MLA attention over the rows a selection kept.

A layer caches two rows a token: the latent row of ``mla_attention.py``
(``[c, k_rope, zeros]``, ``W`` lanes) and the indexer's key (``DI`` lanes), in
two pools behind ONE block table. A query token ``t`` scores every cached
position ``s <= t`` of its own sequence,

    I[t, s] = sum_j w[t, j] * relu(qI[t, j] . kI[s])        (j: index heads)

keeps the ``index_topk`` best (``models/deepseek_v32.select_mask``: plain XLA,
a threshold search) and attends over those rows of the latent pool alone.

``dsa_index_scores`` writes ``I`` as ``[T, S]`` float32, ``S`` the table's
width in tokens: a sum over the heads, so no ``[T, S]`` a head exists anywhere.
Decode rows (one query a sequence) and prefill tiles (``CT`` queries of one
sequence) are two bodies under one name: a decode row's 64 heads are the rows
of ONE ``[HI, DI] x [DI, BS]`` product and are summed down the sublanes; a
tile's queries lie head-major, ``[HI, CT, DI]``, one ``[CT, DI] x [DI, BS]``
product a head accumulated into ``[CT, BS]`` with the head's weight a lane
broadcast of one column of ``w`` (no relayout of either operand). Entries past
a query's position are whatever the block held: the selection masks by
position.

``dsa_prefill_attention`` is ``mla_prefill_attention`` (head-major rows in
two parts in, head-major out, a tile's rows ordered (head, query)) with the
selection as an additive bias ``[T, S]`` (0 on a kept pair, -1e30 elsewhere:
causal mask, padding and selection in one; a query's row of it meets the
scores seen as ``[H, CT, keys]``, one broadcast over the leading axis): a
tile's queries keep different rows, so the tile still walks every block up to
its last position and a block is skipped
only past it. With the context at a few times ``index_topk`` that costs under
twice the kept pairs' FLOPs and reads a block once a tile; a gather a query
would read ``CT`` times the rows.

``dsa_decode_attention`` attends over rows ALREADY gathered, ``[T, K, W]``
(``K = index_topk``; an XLA gather of ``min(context, K)`` rows a decode row,
the only rows of the latent pool a decode row reads): all ``H`` heads share
the gathered rows, so one row's work is ``[H, W] x [W, K]`` and ``[H, K] x
[K, lat]`` on 2.6 MB, at the chip's ridge. It is the decode rows' form past
``deepseek_v32.WALK_MAX_TABLE_TOKENS`` of table only; under it they walk their
own blocks under the selection's mask (``mla_attention.mla_decode_attention``
with ``keep``, which then goes by this kernel's name, ``dsa_attn_decode``).

Inference-only (no VJP).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from deepspeed_tpu.ops.pallas.flash_attention import interpret_mode
from deepspeed_tpu.ops.pallas.mla_attention import (
    _NEG_INF,
    _scores,
    mla_prefill_kernel_tile,
    tile_rows,
)
from deepspeed_tpu.ops.pallas.paged_attention import split_tiles


# ------------------------------------------------------------- index scores
def _index_decode_kernel(slots_ref, pos_ref, bt_ref, q_ref, w_ref, k_ref,
                         o_ref, *, bs: int):
    t = pl.program_id(0)
    j = pl.program_id(1)

    @pl.when(j * bs <= pos_ref[t])
    def _compute():
        s = _scores(q_ref[0], k_ref[0])                   # [HI, BS]
        o_ref[0] = jnp.sum(jnp.maximum(s, 0.0) * w_ref[0], axis=0,
                           keepdims=True)

    @pl.when(j * bs > pos_ref[t])
    def _skip():
        o_ref[0] = jnp.zeros_like(o_ref[0])


def _index_tile_kernel(ts_ref, tp_ref, tv_ref, bt_ref, q_ref, w_ref, k_ref,
                       o_ref, *, bs: int, heads: int):
    c = pl.program_id(0)
    j = pl.program_id(1)
    live = jnp.logical_and(tv_ref[c] > 0,
                           j * bs <= tp_ref[c] + tv_ref[c] - 1)

    @pl.when(live)
    def _compute():
        blk = k_ref[0]                                    # [BS, DI]
        w = w_ref[...]                                    # [CT, HI]
        acc = jnp.zeros(o_ref.shape, jnp.float32)
        for h in range(heads):
            s = _scores(q_ref[h], blk)                    # [CT, BS]
            acc = acc + jnp.maximum(s, 0.0) * w[:, h:h + 1]
        o_ref[...] = acc

    @pl.when(jnp.logical_not(live))
    def _skip():
        o_ref[...] = jnp.zeros_like(o_ref)


def dsa_index_scores(q, w, pool, slots, positions, block_tables,
                     prefill_tiles=None, interpret: bool | None = None):
    """The indexer's scores of a flat ragged batch: ``q`` [T, HI, DI] (roped),
    ``w`` [T, HI] float32 (the heads' weights, scale factors in), ``pool``
    [blocks, BS, DI] the paged index keys -> [T, S] float32, ``S =
    block_tables.shape[1] * BS``; row ``t`` holds ``I[t, s]`` for the
    positions ``s`` of its own sequence up to its block, zeros past it.
    ``prefill_tiles`` as ``models/paged._decode_then_tiles`` has them: the
    first ``n_dec`` rows are decode rows, tiles follow."""
    t_tokens, heads, di = q.shape
    _, bs, _ = pool.shape
    mb = block_tables.shape[1]
    bt = block_tables.astype(jnp.int32)
    n_dec = t_tokens if prefill_tiles is None else prefill_tiles[0]
    parts = []
    if n_dec:
        def _k_map(t, j, sl, po, bt):
            return (bt[sl[t], jnp.minimum(j, po[t] // bs)], 0, 0)

        grid_spec = pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(n_dec, mb),
            in_specs=[
                pl.BlockSpec((1, heads, di), lambda t, j, *_: (t, 0, 0)),
                pl.BlockSpec((1, heads, 1), lambda t, j, *_: (t, 0, 0)),
                pl.BlockSpec((1, bs, di), _k_map),
            ],
            out_specs=pl.BlockSpec((1, 1, bs), lambda t, j, *_: (t, 0, j)),
        )
        out = pl.pallas_call(
            functools.partial(_index_decode_kernel, bs=bs),
            out_shape=jax.ShapeDtypeStruct((n_dec, 1, mb * bs), jnp.float32),
            grid_spec=grid_spec,
            interpret=interpret_mode(interpret),
            name="dsa_index",
        )(slots[:n_dec].astype(jnp.int32), positions[:n_dec].astype(jnp.int32),
          bt, q[:n_dec].astype(pool.dtype),
          w[:n_dec, :, None].astype(jnp.float32), pool)
        parts.append(out[:, 0])
    if t_tokens > n_dec:
        _, ts, tp, tv, ct = prefill_tiles
        n_tiles = (t_tokens - n_dec) // ct

        def _k_map(c, j, ts, tp, tv, bt):
            last = jnp.maximum(tp[c] + tv[c] - 1, 0) // bs
            return (bt[ts[c], jnp.minimum(j, last)], 0, 0)

        grid_spec = pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=4,
            grid=(n_tiles, mb),
            in_specs=[
                pl.BlockSpec((heads, ct, di), lambda c, j, *_: (0, c, 0)),
                pl.BlockSpec((ct, heads), lambda c, j, *_: (c, 0)),
                pl.BlockSpec((1, bs, di), _k_map),
            ],
            out_specs=pl.BlockSpec((ct, bs), lambda c, j, *_: (c, j)),
        )
        out = pl.pallas_call(
            functools.partial(_index_tile_kernel, bs=bs, heads=heads),
            out_shape=jax.ShapeDtypeStruct((t_tokens - n_dec, mb * bs),
                                           jnp.float32),
            grid_spec=grid_spec,
            interpret=interpret_mode(interpret),
            name="dsa_index",
        )(ts.astype(jnp.int32), tp.astype(jnp.int32), tv.astype(jnp.int32),
          bt, jnp.swapaxes(q[n_dec:], 0, 1).astype(pool.dtype),
          w[n_dec:].astype(jnp.float32), pool)
        parts.append(out)
    return parts[0] if len(parts) == 1 else jnp.concatenate(parts, axis=0)


# ------------------------------------------------- attention over the kept rows
def _decode_kernel(n_ref, q_ref, rows_ref, o_ref, *, lat: int, scale: float):
    t = pl.program_id(0)
    rows = rows_ref[0]                                    # [K, W]
    s = _scores(q_ref[0].astype(rows.dtype), rows) * scale    # [H, K]
    kept = jax.lax.broadcasted_iota(jnp.int32, (1, rows.shape[0]), 1) < n_ref[t]
    s = jnp.where(kept, s, _NEG_INF)
    # n >= 1: a row keeps its own position at least, so the maximum is real
    p = jnp.exp(s - jnp.max(s, axis=-1, keepdims=True))
    o = jnp.dot(p.astype(rows.dtype), rows[:, :lat],
                preferred_element_type=jnp.float32)
    o_ref[0] = (o / jnp.sum(p, axis=-1, keepdims=True)).astype(o_ref.dtype)


def dsa_decode_attention(q, rows, n_kept, lat: int, scale: float,
                         interpret: bool | None = None):
    """Absorbed MLA attention of decode rows over their gathered rows: ``q``
    [T, H, W], ``rows`` [T, K, W] (the kept rows of the latent pool, the
    first ``n_kept[t]`` of them real) -> [T, H, lat]."""
    t_tokens, h, width = q.shape
    k = rows.shape[1]
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(t_tokens,),
        in_specs=[
            pl.BlockSpec((1, h, width), lambda t, n: (t, 0, 0)),
            pl.BlockSpec((1, k, width), lambda t, n: (t, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, h, lat), lambda t, n: (t, 0, 0)),
    )
    return pl.pallas_call(
        functools.partial(_decode_kernel, lat=lat, scale=scale),
        out_shape=jax.ShapeDtypeStruct((t_tokens, h, lat), q.dtype),
        grid_spec=grid_spec,
        interpret=interpret_mode(interpret),
        name="dsa_attn_decode",
    )(n_kept.astype(jnp.int32), q, rows)


# the cached blocks one grid step of the prefill kernel takes: the softmax's
# two reductions a row and the accumulator's rescale are paid once a step,
# so one step over 4 x 128 keys costs the vector units a fraction of four
# steps over 128 (one layer on the chip, PERF.md section 6, PR 33)
PREFILL_BLOCKS_A_STEP = 4
_PREFILL_VMEM_BYTES = 48 * 2 ** 20


def _prefill_kernel(ts_ref, tp_ref, tv_ref, bt_ref, q_lat_ref, q_rope_ref,
                    *refs, bs: int, scale: float, group: int):
    kv_refs, (bias_ref, o_ref, q_sc, acc, m_sc, l_sc) = (refs[:group],
                                                         refs[group:])
    c = pl.program_id(0)   # query tile
    j = pl.program_id(1)   # ordinal of a group of kv blocks
    nj = pl.num_programs(1)
    heads, ct, lat = q_lat_ref.shape
    valid = tv_ref[c]
    max_pos = tp_ref[c] + valid - 1

    @pl.when(j == 0)
    def _init():
        tile_rows(q_lat_ref, q_rope_ref, q_sc, scale)
        acc[:] = jnp.zeros_like(acc)
        m_sc[:] = jnp.full_like(m_sc, _NEG_INF)
        l_sc[:] = jnp.zeros_like(l_sc)

    @pl.when(jnp.logical_and(valid > 0, j * group * bs <= max_pos))
    def _compute():
        blk = kv_refs[0][0] if group == 1 else jnp.concatenate(
            [r[0] for r in kv_refs], axis=0)              # [G*BS, W]
        s = _scores(q_sc[...], blk)                       # [H*CT, G*BS]
        # rows lie (head, query): a query's bias row meets its H heads as
        # ONE broadcast over the leading axis of [H, CT, keys]
        s = (s.reshape(heads, ct, -1) + bias_ref[...]).reshape(s.shape)
        m_prev = m_sc[:, :1]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
        # a row that has kept nothing yet sums garbage of size <= 1 a key
        # under m_new ~ -1e30; its first kept key wipes it (corr = 0), and
        # every row keeps a key before the walk ends
        p = jnp.exp(s - m_new)
        corr = jnp.exp(m_prev - m_new)
        l_sc[:, :1] = l_sc[:, :1] * corr + jnp.sum(p, -1, keepdims=True)
        m_sc[:, :1] = m_new
        acc[:] = acc[:] * corr + jnp.dot(
            p.astype(blk.dtype), blk[:, :lat],
            preferred_element_type=jnp.float32)

    @pl.when(j == nj - 1)
    def _finish():
        o = acc[:] / jnp.maximum(l_sc[:, :1], 1e-30)
        o_ref[...] = o.reshape(heads, ct, lat).astype(o_ref.dtype)


def dsa_prefill_attention(q_lat, q_rope, pool, bias, tile_slot, tile_pos0,
                          tile_valid, block_tables, tile: int, scale: float,
                          interpret: bool | None = None,
                          group: int = PREFILL_BLOCKS_A_STEP):
    """``mla_attention.mla_prefill_attention`` (``q_lat`` [H, NT*CT, lat] and
    ``q_rope`` [H, NT*CT, W - lat] head-major -> [H, NT*CT, lat]) with the
    pairs to keep given as ``bias`` [NT*CT, S] float32 (0 kept, -1e30 not;
    ``S`` the table's width in tokens): the same scheduler contract and
    sub-tiling, and a walk of ``group`` blocks a grid step (the pool is
    ``group`` operands of the call, one block of each a step; past a tile's
    last position a step repeats its last block under a bias of -1e30).
    ``scale`` meets the queries where the kernel lays a tile's rows out, once
    a tile, rounded to their dtype."""
    h, t_tokens, lat = q_lat.shape
    _, bs, width = pool.shape
    mb = block_tables.shape[1]
    while mb % group:
        group //= 2
    ct = mla_prefill_kernel_tile(tile, h, lat, width, bs)
    tile_slot, tile_pos0, tile_valid = split_tiles(
        tile_slot, tile_pos0, tile_valid, tile, ct)
    n_tiles = t_tokens // ct

    def _last(c, tp, tv):
        return jnp.maximum(tp[c] + tv[c] - 1, 0) // bs

    # clamp past the tile's last needed block: unchanged id -> no new DMA
    def _kv_map(i):
        def index(c, j, ts, tp, tv, bt):
            return (bt[ts[c], jnp.minimum(j * group + i, _last(c, tp, tv))],
                    0, 0)
        return index

    def _bias_map(c, j, ts, tp, tv, bt):
        return (c, jnp.minimum(j, _last(c, tp, tv) // group))

    def _q_map(c, j, ts, tp, tv, bt):
        return (0, c, 0)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=4,
        grid=(n_tiles, mb // group),
        in_specs=[
            pl.BlockSpec((h, ct, lat), _q_map),
            pl.BlockSpec((h, ct, width - lat), _q_map),
            *[pl.BlockSpec((1, bs, width), _kv_map(i)) for i in range(group)],
            pl.BlockSpec((ct, group * bs), _bias_map),
        ],
        out_specs=pl.BlockSpec((h, ct, lat), _q_map),
        scratch_shapes=[
            pltpu.VMEM((h * ct, width), pool.dtype),
            pltpu.VMEM((h * ct, lat), jnp.float32),
            pltpu.VMEM((h * ct, 128), jnp.float32),
            pltpu.VMEM((h * ct, 128), jnp.float32),
        ],
    )
    kernel = functools.partial(
        _prefill_kernel, bs=bs, group=group,
        # as the product ``q * scale`` rounds it outside a kernel
        scale=float(np.asarray(scale, q_lat.dtype)))
    return pl.pallas_call(
        kernel,
        out_shape=jax.ShapeDtypeStruct((h, t_tokens, lat), q_lat.dtype),
        grid_spec=grid_spec,
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=_PREFILL_VMEM_BYTES),
        interpret=interpret_mode(interpret),
        name="dsa_attn_prefill",
    )(tile_slot.astype(jnp.int32), tile_pos0.astype(jnp.int32),
      tile_valid.astype(jnp.int32), block_tables.astype(jnp.int32), q_lat,
      q_rope, *[pool] * group, bias.astype(jnp.float32))
