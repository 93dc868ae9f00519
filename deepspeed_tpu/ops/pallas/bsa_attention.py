"""Pallas kernels of block-sparse attention over the paged K/V pool (InfLLM-V2,
``models/minicpm_sala.py``): a query keeps some BLOCKS of ``B`` keys, each K/V
head's group of query heads its own, and attends over the keys ``s <= t``
inside them.

``bsa_decode`` follows a decode row's KEPT blocks and nothing else. The row's
selection is a block table a K/V head: the pool ``[pages, BS, Hkv*D]`` is seen
in blocks of ``B`` rows (``paged.sub_blocks``: a bitcast), a (row, K/V head)
pair is one walk of ``paged_attention.decode_steps`` over ITS list of kept
blocks, in position order, and a block of the pool comes in as that head's
``D`` lanes only (the index map's lane block is the head). The list ends with
the query's own block (the local blocks are forced), every block before it lies
wholly before the query, so the walk's own causal mask, ``key ordinal < n_keys``
with ``n_keys = (kept blocks - 1) x B + t % B + 1``, is the selection's: no
mask operand. A row under the dense length lists every block up to its own;
the grid is the rows' steps laid end to end, a traced value, so a row past the
dense length takes ``ceil(topk / blocks a step)`` steps whatever the table's
width or its context (``tests/unit/test_minicpm_sala.py`` holds the step
program to it). A step multiplies the group's ``rep`` queries against ``nb x B``
keys of ONE head: the body of ``paged_attention._block_decode_kernel`` at one
K/V head.

``bsa_prefill`` is ``paged_attention``'s tile kernel (``tiled_prefill``: a tile
walks every block up to its last position, q by K/V head, strips of every
head's group a turn) with the selection as an additive bias a K/V head,
``[tiles, Hkv, CT, keys]`` (0 on a key of a kept block, -1e30 elsewhere; the
causal edge inside a block stays the kernel's own compare): a tile's 128
queries keep different blocks, and 128 x 2 selections of 64 cover most of a
context's blocks between them, so the walk skips nothing and the share of its
products that were kept is what ``kernel.bsa_prefill_roofline`` reads.

Inference-only (no VJP).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from deepspeed_tpu.ops.pallas.flash_attention import _lanes, interpret_mode
from deepspeed_tpu.ops.pallas.paged_attention import (
    _PREFILL_VMEM_BYTES,
    _strip_rows,
    decode_step_blocks,
    decode_steps,
    prefill_kernel_tile,
    prefill_step_blocks,
    split_tiles,
)

_NEG_INF = -1e30


# --------------------------------------------------------------------- decode
def _decode_kernel(row_ref, chunk_ref, nk_ref, ids_ref, q_ref, *refs, bs: int,
                   nb: int, scale: float):
    del ids_ref  # the index maps' alone
    k_refs, v_refs = refs[:nb], refs[nb:2 * nb]
    o_ref, acc, m_sc, l_sc = refs[2 * nb:]
    s_id = pl.program_id(0)
    c = chunk_ref[s_id]
    last = nk_ref[row_ref[s_id]] - 1      # the row's last key's ordinal
    ch = nb * bs

    @pl.when(c == 0)
    def _init():
        acc[:] = jnp.zeros_like(acc)
        m_sc[:] = jnp.full_like(m_sc, _NEG_INF)
        l_sc[:] = jnp.zeros_like(l_sc)

    def blocks(block_refs):                                # [CH, D]
        parts = [r[0] for r in block_refs]
        return parts[0] if nb == 1 else jnp.concatenate(parts, axis=0)

    k, v = blocks(k_refs), blocks(v_refs)
    s = jax.lax.dot_general(
        q_ref[0].astype(k.dtype), k, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32) * scale        # [rep, CH]
    # the list's first key (position 0: block 0 is kept) is never masked, so
    # a row's running maximum is real from its first chunk on
    seen = c * ch + jax.lax.broadcasted_iota(jnp.int32, (1, ch), 1) <= last
    s = jnp.where(seen, s, _NEG_INF)
    m_prev = m_sc[:]                                       # [rep, 128]
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
    p = jnp.exp(s - _lanes(m_new, ch))
    corr = jnp.exp(m_prev - m_new)
    l_sc[:] = l_sc[:] * corr + jnp.sum(p, axis=-1, keepdims=True)
    m_sc[:] = m_new
    acc[:] = acc[:] * _lanes(corr, acc.shape[-1]) + jnp.dot(
        p.astype(v.dtype), v, preferred_element_type=jnp.float32)

    @pl.when(c == last // ch)
    def _finish():
        o_ref[0] = (acc[:] / _lanes(l_sc[:], acc.shape[-1])).astype(o_ref.dtype)


def decode_grid_steps(lists: int, block: int, d: int, itemsize: int,
                      width: int):
    """``(nb, n_steps)``: the blocks a grid step of ``bsa_decode`` takes (a
    block here is ``block`` keys of ONE head's ``d`` lanes: eight a step at
    64 x 128 bfloat16) and the most steps ``lists`` lists of ``width`` blocks
    can take."""
    nb = min(decode_step_blocks(block, d, itemsize), width)
    return nb, lists * -(-width // nb)


def bsa_decode_attention(q, k_pool, v_pool, ids, n_keys, scale: float,
                         interpret: bool | None = None):
    """``q`` [T, Hkv, rep, D], the decode rows' queries by K/V head;
    ``k_pool`` / ``v_pool`` [blocks, B, Hkv*D], the pool seen in the
    selection's blocks; ``ids`` [T, Hkv, W] int32, each row's and head's kept
    blocks in position order (entries past the list unread), ``n_keys`` [T,
    Hkv] the keys it sees in them (>= 1; the last block cut at the row's
    position) -> [T, Hkv, rep, D]."""
    return _bsa_decode(q, k_pool, v_pool, ids.astype(jnp.int32),
                       n_keys.astype(jnp.int32), scale=float(scale),
                       interpret=interpret_mode(interpret))


@functools.partial(jax.jit, static_argnames=("scale", "interpret"))
def _bsa_decode(q, k_pool, v_pool, ids, n_keys, *, scale: float,
                interpret: bool):
    t, hkv, rep, d = q.shape
    bs = k_pool.shape[1]
    width = ids.shape[-1]
    rows = t * hkv                      # a (row, K/V head) pair is one walk
    nb, n_steps = decode_grid_steps(rows, bs, d, k_pool.dtype.itemsize, width)
    n_keys = n_keys.reshape(rows)
    ends, step_row, step_chunk = decode_steps(n_keys - 1, nb * bs, n_steps + 1)
    # the block of operand i of step s: past the list's last block the
    # operand's block of a step ago, or the last (``_block_decode``'s rule)
    j = step_chunk[:, None] * nb + jnp.arange(nb, dtype=jnp.int32)
    last = ((n_keys - 1) // bs)[step_row][:, None]
    j = jnp.where(j <= last, j, jnp.where(j >= nb, j - nb, last))
    step_ids = jnp.take_along_axis(ids.reshape(rows, width)[step_row], j,
                                   axis=1).reshape(-1)

    def _row_map(s, row, chunk, nk, sid):
        return (row[s], 0, 0)

    def _kv_map(i):
        # the row's K/V head is the lane block
        return lambda s, row, chunk, nk, sid: (sid[s * nb + i], 0,
                                               row[s] % hkv)

    kv_specs = [pl.BlockSpec((1, bs, d), _kv_map(i)) for i in range(nb)]
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=4,
        grid=(ends[-1],),
        in_specs=[pl.BlockSpec((1, rep, d), _row_map)] + kv_specs + kv_specs,
        out_specs=pl.BlockSpec((1, rep, d), _row_map),
        scratch_shapes=[
            pltpu.VMEM((rep, d), jnp.float32),
            pltpu.VMEM((rep, 128), jnp.float32),
            pltpu.VMEM((rep, 128), jnp.float32),
        ],
    )
    out = pl.pallas_call(
        functools.partial(_decode_kernel, bs=bs, nb=nb, scale=scale),
        out_shape=jax.ShapeDtypeStruct((rows, rep, d), q.dtype),
        grid_spec=grid_spec,
        interpret=interpret,
        name="bsa_decode",
    )(step_row, step_chunk, n_keys, step_ids, q.reshape(rows, rep, d),
      *([k_pool] * nb), *([v_pool] * nb))
    return out.reshape(t, hkv, rep, d)


# -------------------------------------------------------------------- prefill
def _prefill_kernel(ts_ref, tp_ref, tv_ref, bt_ref, q_ref, b_ref, *refs,
                    bs: int, nb: int, ct: int, hkv: int, rep: int, d: int,
                    strip: int, scale: float):
    k_refs, v_refs = refs[:nb], refs[nb:2 * nb]
    o_ref, acc, m_sc, l_sc = refs[2 * nb:]
    c = pl.program_id(0)   # query tile
    j = pl.program_id(1)   # step of nb kv blocks
    pos0 = tp_ref[c]
    valid = tv_ref[c]
    max_pos = pos0 + valid - 1
    ch = nb * bs
    k_lo = j * ch
    rows = rep * ct
    log2e_scale = scale * 1.4426950408889634

    @pl.when(j == 0)
    def _init():
        acc[:] = jnp.zeros_like(acc)
        m_sc[:] = jnp.full_like(m_sc, _NEG_INF)
        l_sc[:] = jnp.zeros_like(l_sc)

    def heads(block_refs):
        parts = [r[0] for r in block_refs]
        x = parts[0] if nb == 1 else jnp.concatenate(parts, axis=0)
        return jnp.stack([x[:, g * d:(g + 1) * d] for g in range(hkv)])

    @pl.when(jnp.logical_and(valid > 0, k_lo <= max_pos))
    def _step():
        k, v = heads(k_refs), heads(v_refs)                # [Hkv, CH, D]
        # row r of a head's group is query token r % ct and a strip is whole
        # runs of ct rows: ONE bias a step serves every strip, a K/V head its
        # own; the causal edge is the kernel's compare, as in ``tiled_prefill``
        tok = jax.lax.broadcasted_iota(jnp.int32, (strip, ch), 0) % ct
        seen = (pos0 - k_lo + tok
                - jax.lax.broadcasted_iota(jnp.int32, (strip, ch), 1)) >= 0
        kept = b_ref[0].astype(jnp.float32)                # [Hkv, CT, CH]
        if strip > ct:
            kept = jnp.concatenate([kept] * (strip // ct), axis=1)
        bias = jnp.where(seen[None], kept, _NEG_INF)       # [Hkv, strip, CH]

        def strip_of_every_head(i, _):
            at = pl.ds(pl.multiple_of(i * strip, strip), strip)
            q = q_ref[0, :, at, :]                         # [Hkv, strip, D]
            s = jax.lax.dot_general(
                q.astype(k.dtype), k, (((2,), (2,)), ((0,), (0,))),
                preferred_element_type=jnp.float32) + bias
            # a real query keeps block 0 (forced, or every block under the
            # dense length), so its maximum is real from the first step on;
            # a padding row gathers p = 1 over masked keys, finite
            m_prev = m_sc[:, at, :]
            m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
            p = jnp.exp2((s - _lanes(m_new, ch)) * log2e_scale)
            corr = jnp.exp2((m_prev - m_new) * log2e_scale)
            l_sc[:, at, :] = l_sc[:, at, :] * corr + jnp.sum(
                p, -1, keepdims=True)
            pv = jax.lax.dot_general(
                p.astype(v.dtype), v, (((2,), (1,)), ((0,), (0,))),
                preferred_element_type=jnp.float32)
            acc[:, at, :] = acc[:, at, :] * _lanes(corr, d) + pv
            m_sc[:, at, :] = m_new

        jax.lax.fori_loop(0, rows // strip, strip_of_every_head, None,
                          unroll=True)

    @pl.when(j == pl.num_programs(1) - 1)
    def _finish():
        l = jnp.maximum(_lanes(l_sc[:], d), 1e-30)
        o_ref[0] = (acc[:] / l).astype(o_ref.dtype)


def bsa_prefill_attention(q, k_pool, v_pool, keep, tile_slot, tile_pos0,
                          tile_valid, block_tables, tile: int, scale: float,
                          interpret: bool | None = None):
    """``q`` [NT*CT, Hq, D] tile-aligned prefill rows (``paged_attention.
    ragged_prefill_attention``'s contract), ``keep`` [NT*CT, Hkv, NB] bool:
    row ``r``'s K/V head ``g`` keeps the ``B = table keys / NB`` keys of block
    ``b`` (those ``<=`` its own position) -> [NT*CT, Hq, D]. A real row keeps
    block 0 and its own."""
    _, hq, d = q.shape
    _, bs, hd = k_pool.shape
    nb = prefill_step_blocks(bs, hd, k_pool.dtype.itemsize)
    ct = prefill_kernel_tile(tile, hq, hd // d, d, q.dtype.itemsize, nb * bs)
    return _bsa_prefill(
        q, k_pool, v_pool, keep, tile_slot.astype(jnp.int32),
        tile_pos0.astype(jnp.int32), tile_valid.astype(jnp.int32),
        block_tables.astype(jnp.int32), tile=tile, ct=ct, nb=nb,
        scale=float(scale), interpret=interpret_mode(interpret))


@functools.partial(jax.jit, static_argnames=(
    "tile", "ct", "nb", "scale", "interpret"))
def _bsa_prefill(q, k_pool, v_pool, keep, tile_slot, tile_pos0, tile_valid,
                 block_tables, *, tile: int, ct: int, nb: int, scale: float,
                 interpret: bool):
    t_tokens, hq, d = q.shape
    _, bs, hd = k_pool.shape
    hkv = hd // d
    rep = hq // hkv
    mb = block_tables.shape[1]
    keys = mb * bs
    tile_slot, tile_pos0, tile_valid = split_tiles(
        tile_slot, tile_pos0, tile_valid, tile, ct)
    n_tiles = t_tokens // ct
    ch = nb * bs
    n_steps = -(-mb // nb)

    def _kv_map(i):
        def index(c, j, ts, tp, tv, bt):
            last = jnp.maximum(tp[c] + tv[c] - 1, 0) // bs
            return (bt[ts[c], jnp.minimum(j * nb + i, last)], 0, 0)
        return index

    def _tile_map(c, j, ts, tp, tv, bt):
        return (c, 0, 0, 0)

    def _bias_map(c, j, ts, tp, tv, bt):
        # past the tile's last needed step: the block it has (no new DMA)
        return (c, 0, 0, jnp.minimum(
            j, jnp.maximum(tp[c] + tv[c] - 1, 0) // ch))

    # 0 on a key of a kept block, -1e30 elsewhere, in the pool's dtype: made
    # here from the blocks' flags, one fused write beside the kernel
    # (the small flags are turned to the kernel's order first: the bias is
    # written once, where the kernel reads it)
    keep = keep.reshape(n_tiles, ct, hkv, -1).transpose(0, 2, 1, 3)
    bias = jnp.where(jnp.repeat(keep, keys // keep.shape[-1], axis=-1), 0.0,
                     _NEG_INF).astype(k_pool.dtype)
    if keys < n_steps * ch:
        bias = jnp.pad(bias, ((0, 0),) * 3 + ((0, n_steps * ch - keys),),
                       constant_values=_NEG_INF)
    q_groups = q.reshape(n_tiles, ct, hkv, rep, d).transpose(
        0, 2, 3, 1, 4).reshape(n_tiles, hkv, rep * ct, d)
    kv_specs = [pl.BlockSpec((1, bs, hd), _kv_map(i)) for i in range(nb)]
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=4,
        grid=(n_tiles, n_steps),
        in_specs=[pl.BlockSpec((1, hkv, rep * ct, d), _tile_map),
                  pl.BlockSpec((1, hkv, ct, ch), _bias_map)]
        + kv_specs + kv_specs,
        out_specs=pl.BlockSpec((1, hkv, rep * ct, d), _tile_map),
        scratch_shapes=[
            pltpu.VMEM((hkv, rep * ct, d), jnp.float32),
            pltpu.VMEM((hkv, rep * ct, 128), jnp.float32),
            pltpu.VMEM((hkv, rep * ct, 128), jnp.float32),
        ],
    )
    kernel = functools.partial(
        _prefill_kernel, bs=bs, nb=nb, ct=ct, hkv=hkv, rep=rep, d=d,
        strip=_strip_rows(hkv, rep, ct), scale=scale)
    out = pl.pallas_call(
        kernel,
        out_shape=jax.ShapeDtypeStruct((n_tiles, hkv, rep * ct, d), q.dtype),
        grid_spec=grid_spec,
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=_PREFILL_VMEM_BYTES),
        interpret=interpret,
        name="bsa_prefill",
    )(tile_slot, tile_pos0, tile_valid, block_tables, q_groups, bias,
      *([k_pool] * nb), *([v_pool] * nb))
    return out.reshape(n_tiles, hkv, rep, ct, d).transpose(
        0, 3, 1, 2, 4).reshape(t_tokens, hq, d)
