"""Pallas attention over a latent (MLA) paged pool: absorbed decode and
absorbed tiled prefill.

Multi-head latent attention (DeepSeek-V2/V3) caches ONE row a token and
layer: the normed latent ``c`` (``kv_lora_rank`` lanes) followed by the roped
key all heads share (``qk_rope_head_dim`` lanes). With the key half of
``kv_b_proj`` multiplied into the query (``q_lat``) and the value half applied
to the result, attention runs on the cached rows directly: to these kernels it
is ``H`` query heads of ``lat + rope`` lanes over ONE KV head whose values are
lanes ``0 .. lat-1`` of the same row. No per-head K or V of the context is
ever made, and a block is fetched once for both uses.

The pool has the paged contract's storage form (``models/paged.py``):
``[blocks, BS, W]`` with ``W`` = ``lat + rope`` rounded up to whole 128-lane
tiles, the lanes past ``lat + rope`` zero (``q`` is padded alike, so they add
nothing to a score). At Moonlight's 512 + 64 that is 640: the device lays a
576-lane row out as 640 whether it is one array or ``c`` and ``k_rope`` apart
(64 lanes pad to 128), and a DMA written by hand cannot take 576 lanes of 640
(Mosaic: "slice shape along dimension 2 must be aligned to tiling (128)"), so
the padding is the pool's own: one array, one DMA a block, one scatter a token.

``mla_decode`` walks each decode row's OWN blocks, ``decode_step_blocks`` of
them a step (four at Moonlight's 164 KB a block: the one rule of both decode
kernels, ``paged_attention.py``). The rows' steps are laid end to end as
``paged_decode`` lays them (``decode_steps``); the grid is the rows, and a
step's blocks ``0 .. pos // BS`` are copied HBM -> VMEM by hand, all of a
step's copies started together and those of the next ``_DECODE_AHEAD`` steps
in flight while this one is computed, whichever rows they belong to. One
online-softmax update a step: ``[H, W] x [W, k BS]``, one max / exp / sum,
``[H, k BS] x [k BS, lat]``, one rescale. Block operands fetched by the
pipeline, as ``paged_decode`` has them, measured 22-30% slower here (PERF.md
section 6, PR 34): the pipeline keeps ONE step in flight (it has two buffers
an operand and takes no third), and one 0.66 MB step does not cover a copy's
latency; by hand it is two. A grid of rows x table width would spend a grid
step on every table entry: at 128 rows x 32 entries 4,096 steps a layer for
~1,100 blocks of real context.

``mla_prefill`` takes a step's tile-aligned prefill rows HEAD-MAJOR and in
their two parts, ``q_lat`` ``[H, T, lat]`` (the absorbed product's result
itself) and ``q_rope`` ``[H, T, W - lat]``, and gives ``[H, T, lat]``: the
absorbed products on either side of it are batched over heads and lie that
way in memory, so the rows cross HBM once each way (``models/paged.py``,
*Rows to heads*). A grid tile's blocks are ``(H, CT, lat)`` and ``(H, CT, W -
lat)``; inside, a tile's rows are ordered (head, query), row ``h * CT + i``
head ``h`` of query ``i``: the rows ``[q_lat, q_rope, zeros]`` of ONE ``[H*CT,
W] x [W, BS]`` product a block, joined and laid out in VMEM once a tile
(``tile_rows``: at 128 heads ``CT`` is 8, half a bfloat16 tile's rows), a
query's position ``row mod CT``.

A family that selects the rows a query attends over (``deepseek_v32``) hands
the same walk its decode rows' selection, ``keep`` [T, S]: one row of it is
resident a grid step, a step's lanes one sublane of it, and a dropped
position's score goes where a position past ``pos`` goes. Nothing else
differs, the name apart (``dsa_attn_decode``); without ``keep`` the call
traces to the program it was before there was one.

Inference-only (no VJP).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from deepspeed_tpu.ops.pallas.flash_attention import interpret_mode
from deepspeed_tpu.ops.pallas.paged_attention import (
    _VMEM_SCOPED_BYTES,
    decode_step_blocks,
    decode_steps,
    split_tiles,
)

_NEG_INF = -1e30


def _scores(q, blk):
    """``q`` [R, W] x ``blk`` [BS, W] -> float32 [R, BS]."""
    return jax.lax.dot_general(q, blk, (((1,), (1,)), ((), ())),
                               preferred_element_type=jnp.float32)


# Steps whose copies are in flight beside the one being computed (so one
# buffer more): with one ahead a copy's latency shows between steps, 367 us
# a layer at the reason-pool cell's shape against 307 with two and 298 with
# three (PERF.md section 6, PR 34). Three buffers of four 164 KB blocks are
# 2 MB of the 16 MiB of scoped VMEM.
_DECODE_AHEAD = 2


def _decode_kernel(ends_ref, row_ref, chunk_ref, slots_ref, pos_ref, bt_ref,
                   q_ref, pool_ref, *refs, bs: int, nb: int, lat: int,
                   scale: float):
    # with a selection its row rides in before the output: [1, S / CH, CH]
    keep_ref = refs[0] if len(refs) == 4 else None
    o_ref, buf, sem = refs[-3:]
    t = pl.program_id(0)
    n_rows = pl.num_programs(0)
    n_buf = buf.shape[0]                                  # _DECODE_AHEAD + 1
    ch = nb * bs
    pos = pos_ref[t]

    # Nothing here is negative, so lax.div / lax.rem and not // and %: the
    # flooring forms are a dozen scalar operations each, and the kernel is
    # lowered again for every step program (half of its lowering time).
    def copies(s, act: str):
        """Start, or wait for, step ``s``'s copies into buffer ``s % n_buf``
        (returned): the blocks of its row's context alone, so every block
        moves once."""
        row, c = row_ref[s], chunk_ref[s]
        slot = jax.lax.rem(s, n_buf)

        def one(i, _):
            getattr(pltpu.make_async_copy(
                pool_ref.at[bt_ref[slots_ref[row], c * nb + i]],
                buf.at[slot, pl.ds(pl.multiple_of(i * bs, bs), bs)],
                sem.at[slot, i]), act)()
            return _

        jax.lax.fori_loop(
            0, jnp.minimum(jax.lax.div(pos_ref[row], bs) + 1 - c * nb, nb),
            one, 0)
        return slot

    def start(s, _=None):                                 # also a loop body
        @pl.when(s < ends_ref[n_rows - 1])
        def _in_range():
            copies(s, "start")

    def wait(s):
        return buf[copies(s, "wait")]                     # [CH, W]

    @pl.when(t == 0)
    def _first():
        jax.lax.fori_loop(0, _DECODE_AHEAD, start, None)

    q = q_ref[0]                                          # [H, W]
    h = q.shape[0]
    last_chunk = jax.lax.div(pos, ch)
    s_row = ends_ref[t] - last_chunk - 1                  # the row's step 0

    def chunk(c, carry, tail: bool = False):
        m_prev, l_prev, acc = carry
        start(s_row + c + _DECODE_AHEAD)
        blk = wait(s_row + c)
        s = _scores(q.astype(blk.dtype), blk) * scale
        v = blk[:, :lat]
        if keep_ref is not None:
            # the selection is causal already, so it is the tail's mask too.
            # A chunk may keep nothing, the first included: until a row's
            # first kept key its maximum stays _NEG_INF, every p is 1 and l
            # and acc sum finite garbage, which that key's corr = 0 wipes;
            # a selection keeps a key at or before pos, so one comes.
            s = jnp.where(keep_ref[0, pl.ds(c, 1), :] > 0, s, _NEG_INF)
        if tail:
            # Only a row's last chunk has keys past pos. There the buffer
            # holds what the pool had, an earlier step's blocks or nothing
            # yet: a masked score's p is 0, and 0 times a value that is not
            # finite is not 0, so those values go too.
            if keep_ref is None:
                kpos = c * ch + jax.lax.broadcasted_iota(jnp.int32, (1, ch), 1)
                s = jnp.where(kpos <= pos, s, _NEG_INF)
            vpos = c * ch + jax.lax.broadcasted_iota(jnp.int32, (ch, 1), 0)
            v = jnp.where(vpos <= pos, v, jnp.zeros((), v.dtype))
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
        p = jnp.exp(s - m_new)                            # [H, CH]
        corr = jnp.exp(m_prev - m_new)
        l_new = l_prev * corr + jnp.sum(p, axis=-1, keepdims=True)
        pv = jnp.dot(p.astype(v.dtype), v,
                     preferred_element_type=jnp.float32)  # [H, lat]
        return m_new, l_new, acc * corr + pv

    # without a selection position 0 is never masked, so the running maximum
    # is real from the first chunk on and no row of p is all zeros
    carry = jax.lax.fori_loop(
        0, last_chunk, chunk,
        (jnp.full((h, 1), _NEG_INF, jnp.float32),
         jnp.zeros((h, 1), jnp.float32), jnp.zeros((h, lat), jnp.float32)))
    _, l, acc = chunk(last_chunk, carry, tail=True)
    o_ref[0] = (acc / l).astype(o_ref.dtype)


def mla_decode_attention(q, pool, slots, positions, block_tables, lat: int,
                         scale: float, keep=None,
                         interpret: bool | None = None):
    """Absorbed MLA decode: ``q`` [T, H, W] (``q_lat``, the roped ``q_rope``,
    zeros) over ``pool`` [blocks, BS, W] through ``block_tables[slots]`` ->
    [T, H, lat] (``P c``; the caller applies the
    value half of ``kv_b_proj``). Each row reads blocks ``0 .. pos // BS``
    of its sequence, once.

    ``keep`` [T, S] bool (``S`` the table's width in tokens), for a family
    that selects the cached rows a query attends over: the softmax runs over
    row ``t``'s positions ``keep[t]`` alone, which lie at or before its own
    and are one at least. The walk is the same (a dropped position's score
    goes where a position past ``pos`` goes); the call is then
    ``dsa_attn_decode``, that family's sparse decode attention."""
    return _mla_decode(
        q, pool, slots.astype(jnp.int32), positions.astype(jnp.int32),
        block_tables.astype(jnp.int32), keep, lat=lat, scale=float(scale),
        interpret=interpret_mode(interpret))


# ONE jitted function: the step programs of one row count share its trace
@functools.partial(jax.jit, static_argnames=("lat", "scale", "interpret"))
def _mla_decode(q, pool, slots, positions, block_tables, keep=None, *,
                lat: int, scale: float, interpret: bool):
    t_tokens, h, width = q.shape
    _, bs, _ = pool.shape
    nb = decode_step_blocks(bs, width, pool.dtype.itemsize, arrays=1)
    ch = nb * bs
    n_chunks = -(-block_tables.shape[1] // nb)
    ends, step_row, step_chunk = decode_steps(positions, ch,
                                              t_tokens * n_chunks)

    def _row_map(t, *prefetched):
        return (t, 0, 0)

    selection, selection_specs = (), []
    if keep is not None:
        # a row of the selection resident a grid step, a chunk's lanes one
        # sublane of it: 32 KB at a table of 8,192 tokens
        keep = jnp.pad(keep.astype(jnp.float32),
                       ((0, 0), (0, n_chunks * ch - keep.shape[1])))
        selection = (keep.reshape(t_tokens, n_chunks, ch),)
        selection_specs = [pl.BlockSpec((1, n_chunks, ch), _row_map)]

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=6,
        grid=(t_tokens,),
        in_specs=[
            pl.BlockSpec((1, h, width), _row_map),
            pl.BlockSpec(memory_space=pl.ANY),
            *selection_specs,
        ],
        out_specs=pl.BlockSpec((1, h, lat), _row_map),
        scratch_shapes=[
            pltpu.VMEM((_DECODE_AHEAD + 1, ch, width), pool.dtype),
            pltpu.SemaphoreType.DMA((_DECODE_AHEAD + 1, nb)),
        ],
    )
    kernel = functools.partial(_decode_kernel, bs=bs, nb=nb, lat=lat,
                               scale=scale)
    return pl.pallas_call(
        kernel,
        out_shape=jax.ShapeDtypeStruct((t_tokens, h, lat), q.dtype),
        grid_spec=grid_spec,
        # the next rows' steps are in flight across grid steps
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret,
        name="mla_decode" if keep is None else "dsa_attn_decode",
    )(ends, step_row, step_chunk, slots, positions, block_tables, q, pool,
      *selection)


# --------------------------------------------------------------- tiled prefill
def tile_rows(q_lat_ref, q_rope_ref, q_sc, scale=None):
    """A tile's two query blocks, ``[H, CT, lat]`` and ``[H, CT, W - lat]``,
    into ``q_sc`` [H * CT, W]: the rows of one product, row ``h * CT + i``
    head ``h`` of the tile's query ``i``, ``[q_lat, q_rope, zeros]`` (times
    ``scale``, rounded once as a product outside the kernel would be).
    Through float32, whose eight-row tiles make the merge of the two leading
    axes no move at any ``CT`` the tile chooser gives; packed to the
    scratch's dtype after it. Once a tile."""
    lat = q_lat_ref.shape[-1]
    for ref, lanes in ((q_lat_ref, slice(0, lat)),
                       (q_rope_ref, slice(lat, None))):
        h, ct, n = ref.shape
        q = ref[...].astype(jnp.float32)
        if scale is not None:
            q = q * scale
        q_sc[:, lanes] = q.reshape(h * ct, n).astype(q_sc.dtype)


def _prefill_kernel(ts_ref, tp_ref, tv_ref, bt_ref, q_lat_ref, q_rope_ref,
                    kv_ref, o_ref, q_sc, acc, m_sc, l_sc, *, bs: int,
                    scale: float):
    c = pl.program_id(0)   # query tile
    j = pl.program_id(1)   # kv block ordinal
    nj = pl.num_programs(1)
    heads, ct, lat = q_lat_ref.shape
    pos0 = tp_ref[c]
    valid = tv_ref[c]
    max_pos = pos0 + valid - 1

    @pl.when(j == 0)
    def _init():
        tile_rows(q_lat_ref, q_rope_ref, q_sc)
        acc[:] = jnp.zeros_like(acc)
        m_sc[:] = jnp.full_like(m_sc, _NEG_INF)
        l_sc[:] = jnp.zeros_like(l_sc)

    @pl.when(jnp.logical_and(valid > 0, j * bs <= max_pos))
    def _compute():
        blk = kv_ref[0]                                   # [BS, W]
        q = q_sc[...]                                     # [H*CT, W]
        rows = q.shape[0]
        s = _scores(q, blk) * scale
        # rows lie (head, query): a row's query is its index within a head
        qi = jax.lax.rem(
            jax.lax.broadcasted_iota(jnp.int32, (rows, 1), 0), ct)
        kpos = j * bs + jax.lax.broadcasted_iota(jnp.int32, (1, bs), 1)
        mask = jnp.logical_and(kpos <= pos0 + qi, qi < valid)
        s = jnp.where(mask, s, _NEG_INF)
        m_prev = m_sc[:, :1]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
        p = jnp.exp(s - m_new)
        # fully-masked rows (pad queries) give exp(-inf - -inf): zero them
        p = jnp.where(m_new > _NEG_INF / 2, p, 0.0)
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


# What one query row of one head costs the prefill kernel in scoped VMEM
# (16 MiB on v5e, ``paged_attention._VMEM_SCOPED_BYTES``), in bytes: the
# float32 accumulator (lat lanes) and m, l (128 lanes each), the
# double-buffered q (W lanes) and o blocks in bf16, the tile's rows as the
# products take them (W lanes, bf16) and about three float32 [rows, BS]
# temporaries (scores, p, the mask's select).
def _prefill_row_bytes(lat: int, width: int, bs: int) -> int:
    return ((lat + 2 * 128) * 4 + 2 * 2 * (width + lat) + 2 * width
            + 3 * 4 * bs)


def mla_prefill_kernel_tile(tile: int, heads: int, lat: int, width: int,
                            bs: int) -> int:
    """Largest power-of-two split of the scheduler's ``tile`` whose working
    set fits scoped VMEM with an eighth to spare (as
    ``paged_attention.prefill_kernel_tile``)."""
    row = heads * _prefill_row_bytes(lat, width, bs)
    ct = tile
    while ct > 8 and ct % 2 == 0 and ct * row > _VMEM_SCOPED_BYTES * 7 // 8:
        ct //= 2
    return ct


def mla_prefill_attention(q_lat, q_rope, pool, tile_slot, tile_pos0,
                          tile_valid, block_tables, tile: int, scale: float,
                          interpret: bool | None = None):
    """Absorbed MLA prefill over the latent pool: the tile-aligned prefill
    tokens' queries HEAD-MAJOR and in their two parts (module doc), ``q_lat``
    [H, NT*CT, lat] and ``q_rope`` [H, NT*CT, W - lat] (the roped lanes,
    zeros) -> [H, NT*CT, lat]. Same scheduler contract and sub-tiling as
    ``paged_attention.ragged_prefill_attention``; a tile's ``H * CT`` query
    rows share each fetched block, so the matmuls are ``[H*CT, W] x [W, BS]``
    and ``[H*CT, BS] x [BS, lat]``.

    Absorbed, a query-key pair costs ``2 H (2 lat + rope)`` FLOPs (34.8 k at
    Moonlight's widths) against ``2 H (nope + rope + v)`` (10.2 k) on
    decompressed keys and values; the decompressed form also needs
    ``kv_b_proj`` over the whole context once a chunk (4.2 MFLOP a context
    token) and a per-head K and V of it in memory. At the chunks the
    scheduler makes (<= 4 tiles a step) the two cost about the same and
    both are a few per cent of the step's expert einsum; the absorbed form
    keeps one read path over the cached rows and materialises nothing.
    """
    h, t_tokens, lat = q_lat.shape
    _, bs, width = pool.shape
    mb = block_tables.shape[1]
    ct = mla_prefill_kernel_tile(tile, h, lat, width, bs)
    tile_slot, tile_pos0, tile_valid = split_tiles(
        tile_slot, tile_pos0, tile_valid, tile, ct)
    n_tiles = t_tokens // ct

    # clamp past the tile's last needed block: unchanged id -> no new DMA
    def _kv_map(c, j, ts, tp, tv, bt):
        last = jnp.maximum(tp[c] + tv[c] - 1, 0) // bs
        return (bt[ts[c], jnp.minimum(j, last)], 0, 0)

    def _q_map(c, j, ts, tp, tv, bt):
        return (0, c, 0)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=4,
        grid=(n_tiles, mb),
        in_specs=[
            pl.BlockSpec((h, ct, lat), _q_map),
            pl.BlockSpec((h, ct, width - lat), _q_map),
            pl.BlockSpec((1, bs, width), _kv_map),
        ],
        out_specs=pl.BlockSpec((h, ct, lat), _q_map),
        scratch_shapes=[
            pltpu.VMEM((h * ct, width), pool.dtype),
            pltpu.VMEM((h * ct, lat), jnp.float32),
            pltpu.VMEM((h * ct, 128), jnp.float32),
            pltpu.VMEM((h * ct, 128), jnp.float32),
        ],
    )
    kernel = functools.partial(_prefill_kernel, bs=bs, scale=scale)
    return pl.pallas_call(
        kernel,
        out_shape=jax.ShapeDtypeStruct((h, t_tokens, lat), q_lat.dtype),
        grid_spec=grid_spec,
        interpret=interpret_mode(interpret),
        name="mla_prefill",
    )(tile_slot.astype(jnp.int32), tile_pos0.astype(jnp.int32),
      tile_valid.astype(jnp.int32), block_tables.astype(jnp.int32), q_lat,
      q_rope, pool)
