"""Pallas paged attention over the blocked KV pool: flash-decode + tiled prefill.

Role parity with the reference's ragged kernels
(``inference/v2/kernels/ragged_ops/`` blocked flash attention +
``ragged/csrc`` blocked-KV layout): each ragged token reads its sequence's
KV directly from the block pool through the block table — no gather of the
full padded context (the XLA fallback in ``models/llama.ragged_forward``
materializes ``[T, max_blocks*block, H, D]``; this kernel streams one block
at a time through VMEM with online-softmax accumulation).

Mechanism: ``PrefetchScalarGridSpec`` — the block table and slot/position
vectors are scalar-prefetch operands, so a KV BlockSpec's index map resolves
``pool_block = block_tables[slot, j]`` *before* the kernel body runs and the
DMA fetches exactly that block (the TPU paged-attention idiom). A DMA written
by hand cannot (Mosaic refuses to slice GPT-2 XL's 1600-lane rows out of the
pool: "slice shape along dimension 2 must be aligned to tiling (128)"), so
both kernels leave the fetching to the pipeline.

The pool has the paged contract's storage form (``models/paged.py``):
``[blocks, BS, Hkv*D]``, a token row one lane-dense vector of all KV heads,
and is never re-laid-out around the kernels.

``paged_decode`` walks each decode row's OWN context. Its grid is not rows x
table width but the rows' steps laid end to end: a row at position ``pos``
takes ``pos // CH + 1`` steps of ``CH`` tokens (``decode_step_blocks`` whole
pool blocks, each an operand of its own), the grid's length is their sum, a
traced value, and two prefetched vectors say which row and which chunk a
step works on. So no grid step is spent on a table entry past a row's
context and none is read: past the row's last block an operand's index stays
what it was a step before. Inside a step the heads are not split either: the
query rides in as ``[Hq, Hkv*D]``, each head's ``D`` values in its KV head's
lanes and zeros elsewhere, so one bf16 matmul against the chunk gives all
heads' scores, one more ``P x chunk``, and the output is that product's
diagonal segments.

The tile kernel (``tiled_prefill``) has a grid of tiles x steps, a step
``prefill_step_blocks`` whole pool blocks (512 keys as four 128-token
blocks, 256 as eight of GPT-2 XL's), each an operand of its own as in the
decode kernel: operand ``i`` of step ``j`` is block ``first + j * nb + i`` of
the tile's sequence, ``first`` the tile's first needed block, clamped to its
last needed one (an unchanged id is no new DMA; a repeat's keys lie past
every query's position and are masked by it). q comes laid out by KV head,
``[tiles, Hkv, rep * CT, D]``: an XLA transpose beside the projection that
made it, once a layer, and the output goes back the same way, so a KV
head's group of query heads is ONE matrix of ``rep * CT`` rows and nothing
is re-laid out inside a step (the step this replaced repacked q's sublanes
in every one of its 33 steps a tile: 60% of its bundles were stores). A step
walks those rows in turns, a strip of EVERY KV head's group a turn (one
batched product of ``Hkv`` matrices of ``strip`` rows against the step's
keys, their heads' K and V static lane slices of the blocks in VMEM); the
turns are one ``fori_loop`` body, traced once and unrolled by the compiler.
The products take the pool's dtype and accumulate in float32 (``p`` in the
pool's dtype for ``P x V``, the decode kernel's arithmetic; float32 operands
cost the MXU no second pass and were 1.5% faster, bf16 ones halve q's and
the blocks' VMEM), the scale is applied to the float32 scores inside the
exponent, and a row's running maximum and sum lie in every lane of a
``[rows, 128]`` scratch (``flash_attention._lanes``), so one cross-lane
maximum, one sum and one rescale of the accumulator are paid a row and
STEP. The mask (iotas, compares) is made once a step and shared by every
turn and head, which pay one select a score. Measured and not kept (PERF.md
section 6, PR 45): a second body without the mask for the steps no edge
crosses (2.7% of the kernel's time for twice the program), scores a strip
ahead of the exponentials before them (nothing), the turns as a real loop
(21-24% slower). ``window=None`` traces the same step without the window's
compare.

A sliding-window layer (``window``: a query at ``i`` attends over keys ``i -
window < j <= i``) walks less: a decode row's steps are the chunks from
``max(0, pos - window + 1) // CH`` to ``pos // CH``, at most ``window / CH +
1`` whatever its context, a tile's grid the ``window``-and-a-tile's worth of
blocks from its first needed one (NOT from a multiple of the blocks a step:
the sliding pool's table names no block before it), and both mask ``j <= i -
window`` inside the first. Both kernels then go by another name (``swa_decode``,
``swa_prefill``), so a trace tells a model's window layers from its full
ones. The decode kernel under ``window=None`` traces what it always did.

A model that generates by blocks (``block``: ``B`` positions denoised
together, ``ModelSpec.block_gen``) attends block-causally: a query at ``i``
sees keys ``j <= (i | (B - 1))``, its own block whole. A tile and a pool block
are multiples of ``B``, so the tile kernel's grid is the causal one and only
its mask's query position moves to its block's last. A decoding block's ``B``
queries all read the same context, ``p0 + B`` keys with no mask among them,
so the block is ONE row of the decode walk at position ``p0 + B - 1``: a
sequence's chunks are fetched once a pass, not once a query, in as many grid
steps as one row of that context takes. That row has a kernel body of its own
(``_block_decode``): q comes laid out by KV head, ``[rows, Hkv, B * rep, D]``
(the tile kernel's form: an XLA transpose beside the projection that made it,
and back), and a step is one batched product of ``Hkv`` matrices each way, a
KV head's ``B * rep`` queries against that head's ``D`` lanes of the chunk
only; the accumulator is ``[Hkv, B * rep, D]`` and the finish a divide and a
store. The two needs conflict, so it is a path and not a parameter of the
shared one: the ``[Hq, Hkv*D]`` form serves a row of one query best (1 to 8
query heads a KV head, ``D`` 64 in one model: 77-86% of its roofline) and a
block's 32 queries a KV head worst (``(Hkv - 1) / Hkv`` of its products are
products with zeros, every one popped as partial sums over ``Hkv`` lane
parts and added by the vector unit, and the outputs rotated out of the
accumulator's diagonal segments: 1,410 + 453 bundles where the split issues
639 + 93). The block path also hands the steps' pool block ids in as one
more prefetched vector, made by XLA beside ``decode_steps``, where the shared
path's eight index maps work them out (748 bundles a grid step, 302 so;
PERF.md section 6, PR 59). What chooses is ``block``, the layer's kind. Both
kernels then go by another name again (``blk_decode``, ``blk_prefill``);
``block=None`` traces what it always did.

Inference-only (no VJP): the ragged engine never differentiates through
decode.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from deepspeed_tpu.ops.pallas.flash_attention import _lanes, interpret_mode

_NEG_INF = -1e30


# Bytes of K (and as many of V) one step of a decode kernel takes, as whole
# pool blocks: a step costs ~0.2 us beside its DMA and its matmuls, which
# both go by the byte, so the blocks a step takes follow from their size:
# four of GPT-2 XL's 32-token blocks (128 tokens), two of Mixtral's
# 128-token blocks (256). Twice as much costs the chat shapes ~10% (a row's
# last step is mostly masked), half as much the long-document ones 17%
# (sweep on the chip, PERF.md section 6, PR 29). A latent pool's ONE array is
# a token's key and its value, so a step takes both shares from it: four of
# Moonlight's 164 KB blocks (two cost 18% more, eight the same within 1%
# with twice the masked tail: PERF.md section 6, PR 34). Eight blocks at
# most: each is a copy of its own, with a buffer and a DMA in flight.
DECODE_STEP_BYTES = 512 * 1024
_DECODE_STEP_BLOCKS_MAX = 8


def decode_step_blocks(bs: int, lanes: int, itemsize: int,
                       arrays: int = 2) -> int:
    """Pool blocks one step of a decode kernel takes from each of the
    ``arrays`` a token's key and value are read from: the power of two that
    its share of ``2 * DECODE_STEP_BYTES`` holds, 1 to 8."""
    want = max(1, 2 * DECODE_STEP_BYTES // (arrays * bs * lanes * itemsize))
    return min(1 << (want.bit_length() - 1), _DECODE_STEP_BLOCKS_MAX)


def decode_steps(positions, ch: int, n_steps: int, window: int | None = None):
    """The decode rows' chunks of ``ch`` tokens laid end to end, ``pos // ch
    + 1`` of them a row: ``(ends, step_row, step_chunk)``, step ``s`` works
    on chunk ``step_chunk[s]`` of row ``step_row[s]``, row ``t``'s steps are
    ``ends[t - 1] .. ends[t] - 1``, and the ``n_steps`` entries past
    ``ends[-1]`` stay on the last row's last chunk. (Made again in every
    layer of a step: 0.4 us of 20, sweep of PR 29.) With a ``window`` a
    row's chunks start at the one that holds ``pos - window + 1``: at most
    ``window // ch + 1`` steps a row."""
    n_chunks = positions // ch + 1
    if window is not None:
        first = jnp.maximum(positions - window + 1, 0) // ch
        n_chunks = n_chunks - first
    ends = jnp.cumsum(n_chunks)
    steps = jnp.arange(n_steps, dtype=jnp.int32)
    step_row = jnp.minimum(
        jnp.sum(steps[:, None] >= ends[None, :], axis=1, dtype=jnp.int32),
        positions.shape[0] - 1)
    step_chunk = jnp.minimum(steps - (ends - n_chunks)[step_row],
                             n_chunks[step_row] - 1)
    if window is not None:
        step_chunk = step_chunk + first[step_row]
    return ends, step_row, step_chunk


def _decode_kernel(row_ref, chunk_ref, slots_ref, pos_ref, bt_ref, q_ref,
                   *refs, bs: int, nb: int, hkv: int, rep: int, d: int,
                   scale: float, window: int | None = None):
    k_refs, v_refs = refs[:nb], refs[nb:2 * nb]
    o_ref, acc, m_sc, l_sc = refs[2 * nb:]
    s_id = pl.program_id(0)
    t = row_ref[s_id]
    c = chunk_ref[s_id]
    pos = pos_ref[t]
    ch = nb * bs
    # a row's first chunk: the one that holds the oldest key in its window
    c0 = 0 if window is None else jnp.maximum(pos - window + 1, 0) // ch

    @pl.when(c == c0)
    def _init():
        acc[:] = jnp.zeros_like(acc)
        m_sc[:] = jnp.full_like(m_sc, _NEG_INF)
        l_sc[:] = jnp.zeros_like(l_sc)

    def blocks(block_refs):                               # [CH, Hkv*D]
        parts = [r[0] for r in block_refs]
        return parts[0] if nb == 1 else jnp.concatenate(parts, axis=0)

    q = q_ref[0]                                          # [Hq, Hkv*D]
    k = blocks(k_refs)
    s = jax.lax.dot_general(q.astype(k.dtype), k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32) * scale
    kpos = c * ch + jax.lax.broadcasted_iota(jnp.int32, (1, ch), 1)
    seen = kpos <= pos
    if window is not None:
        seen = jnp.logical_and(seen, kpos > pos - window)
    s = jnp.where(seen, s, _NEG_INF)                      # [Hq, CH]
    # position 0 (the window's oldest key) is never masked, so the running
    # maximum is real from a row's first chunk on and no row of p is all zeros
    m_prev = m_sc[:, :1]
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
    p = jnp.exp(s - m_new)
    corr = jnp.exp(m_prev - m_new)
    l_sc[:, :1] = l_sc[:, :1] * corr + jnp.sum(p, axis=-1, keepdims=True)
    m_sc[:, :1] = m_new
    v = blocks(v_refs)
    acc[:] = acc[:] * corr + jnp.dot(p.astype(v.dtype), v,
                                     preferred_element_type=jnp.float32)

    @pl.when(c == pos // ch)
    def _finish():
        hq, hd = acc.shape
        # row r*Hkv + g holds head (g, r) in lanes g*D .. (g+1)*D - 1
        mine = (jax.lax.broadcasted_iota(jnp.int32, (hq, hd), 1) // d
                == jax.lax.broadcasted_iota(jnp.int32, (hq, hd), 0) % hkv)
        o = jnp.where(mine, acc[:] / l_sc[:, :1], 0.0)
        for r in range(rep):
            o_ref[0, r:r + 1, :] = jnp.sum(
                o[r * hkv:(r + 1) * hkv], axis=0, keepdims=True
            ).astype(o_ref.dtype)


def _kernel_name(base: str, window: int | None, block: int | None) -> str:
    """The name a kernel goes by in a trace: ``paged_decode`` /
    ``tiled_prefill``, ``swa_*`` under a window, ``blk_*`` under a block."""
    if window is not None and block is not None:
        raise NotImplementedError("a window and a block in one layer")
    if block is not None:
        return "blk_" + base.split("_")[-1]
    return base if window is None else "swa_" + base.split("_")[-1]


def paged_decode_attention(q, k_pool, v_pool, slots, positions, block_tables,
                           scale: float | None = None,
                           interpret: bool | None = None,
                           window: int | None = None,
                           block: int | None = None):
    """[T, Hq, D] ragged tokens -> [T, Hq, D] attention outputs.

    ``k_pool``/``v_pool``: [blocks, BS, Hkv*D]; ``block_tables``:
    [max_seqs+1, MB] mapping (slot, block-ordinal) -> pool block id. Exact
    vs the dense-gather path (same position masking). Each row reads blocks
    ``0 .. pos // BS`` of its sequence, once, whatever ``MB`` is; with a
    ``window`` (static) the blocks that hold positions ``pos - window + 1 ..
    pos``, and no table entry before them need name a block of the row's.

    With a ``block`` (static, ``B``) a row is a decoding BLOCK: ``q`` is
    ``[rows, B, Hq, D]``, ``positions`` the blocks' first positions ``p0``
    (multiples of ``B``), and every query of a row reads keys ``0 .. p0 + B -
    1``, the block's own among them, unmasked: the row walks its context
    once, at position ``p0 + B - 1``. -> ``[rows, B, Hq, D]``. The kernel
    (``_block_decode``) takes the queries by KV head, ``[rows, Hkv, B * rep,
    D]``, and multiplies a head's ``B * rep`` against that head's lanes of a
    chunk only: a body of its own, because the single-query body's ``[Hq,
    Hkv*D]`` form, right for a few query heads a KV head, spends a block's
    time on products with zeros and on taking the heads apart again (the
    module's docstring). Where ``D`` is no multiple of 128 a head's lanes are
    no whole tile and the block keeps that form, ``B x Hq`` heads of one row
    (no cell has such a block model).
    """
    scale = scale if scale is not None else 1.0 / (q.shape[-1] ** 0.5)
    if block is None:
        return _paged_decode(
            q, k_pool, v_pool, slots.astype(jnp.int32),
            positions.astype(jnp.int32), block_tables.astype(jnp.int32),
            scale=float(scale), interpret=interpret_mode(interpret),
            window=window)
    rows, b, hq, d = q.shape
    hkv = k_pool.shape[-1] // d
    rep = hq // hkv
    name = _kernel_name("paged_decode", window, block)
    statics = dict(scale=float(scale), interpret=interpret_mode(interpret))
    # head (g, r) of query b becomes row b * rep + r of KV head g's group: a
    # KV head's group is the B queries' groups one under the other
    by_kv_head = q.reshape(rows, b, hkv, rep, d).transpose(0, 2, 1, 3, 4)
    operands = (k_pool, v_pool, slots.astype(jnp.int32),
                positions.astype(jnp.int32) + (b - 1),
                block_tables.astype(jnp.int32))
    if d % 128:
        # a head's lanes are no whole tile: the wide form, the B x Hq heads
        # of a block as one row's (no cell has such a block model)
        out = _paged_decode(by_kv_head.reshape(rows, b * hq, d), *operands,
                            name=name, **statics)
    else:
        out = _block_decode(by_kv_head.reshape(rows, hkv, b * rep, d),
                            *operands, name=name, **statics)
    return out.reshape(rows, hkv, b, rep, d).transpose(
        0, 2, 1, 3, 4).reshape(rows, b, hq, d)


# ONE jitted function: the step programs of one row count (d4_t0 .. d4_t3)
# share its trace
@functools.partial(jax.jit, static_argnames=("scale", "interpret", "window",
                                             "name"))
def _paged_decode(q, k_pool, v_pool, slots, positions, block_tables, *,
                  scale: float, interpret: bool, window: int | None = None,
                  name: str | None = None):
    t_tokens, hq, d = q.shape
    _, bs, hd = k_pool.shape
    hkv = hd // d
    rep = hq // hkv
    nb = decode_step_blocks(bs, hd, k_pool.dtype.itemsize)

    # The grid is the rows' chunks laid end to end (``decode_steps``). The
    # pipeline reads the index maps one step ahead, so the vectors have one
    # entry more than there can be steps; the entries past the last stay on
    # the last row's last chunk, whose blocks are then in place already.
    ends, step_row, step_chunk = decode_steps(
        positions, nb * bs, t_tokens * -(-block_tables.shape[1] // nb) + 1,
        window)

    # The heads stay where the pool has them, side by side in a row's lanes:
    # query head (g, r) becomes row r*Hkv + g of a [Hq, Hkv*D] matrix that is
    # zero outside lanes g*D .. (g+1)*D - 1, so ONE matmul against a chunk
    # [CH, Hkv*D] gives every head's scores, and the diagonal segments of
    # P x chunk every head's output. ``spread`` copies a head's D values into
    # every KV head's lanes (a matmul whose every sum has one term, so exact:
    # XLA re-lays a [.., Hkv, D] -> [.., Hkv*D] reshape out at D = 64) and
    # ``mine`` keeps a row's own.
    lane = jnp.arange(hd)[None, :]
    spread = (lane % d == jnp.arange(d)[:, None]).astype(q.dtype)
    mine = lane // d == jnp.arange(hq)[:, None] % hkv
    q_rows = q.reshape(t_tokens, hkv, rep, d).transpose(0, 2, 1, 3).reshape(
        t_tokens, hq, d)
    q_wide = jnp.where(mine, jnp.einsum(
        "thd,de->the", q_rows, spread,
        precision=jax.lax.Precision.HIGHEST), 0).astype(q.dtype)

    def _row_map(s, row, chunk, sl, po, bt):
        return (row[s], 0, 0)

    def _kv_map(i):
        # block i of the step's chunk. Past the row's last block the entry
        # is never read: the index stays what this operand had a step ago
        # (no new DMA; the keys there are masked), or the row's last block
        def index(s, row, chunk, sl, po, bt):
            t, j = row[s], chunk[s] * nb + i
            last = po[t] // bs
            j = jnp.where(j <= last, j, jnp.where(j >= nb, j - nb, last))
            return (bt[sl[t], j], 0, 0)
        return index

    kv_specs = [pl.BlockSpec((1, bs, hd), _kv_map(i)) for i in range(nb)]
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=5,
        grid=(ends[-1],),
        in_specs=[pl.BlockSpec((1, hq, hd), _row_map)] + kv_specs + kv_specs,
        out_specs=pl.BlockSpec((1, rep, hd), _row_map),
        scratch_shapes=[
            pltpu.VMEM((hq, hd), jnp.float32),
            pltpu.VMEM((hq, 128), jnp.float32),
            pltpu.VMEM((hq, 128), jnp.float32),
        ],
    )
    kernel = functools.partial(_decode_kernel, bs=bs, nb=nb, hkv=hkv, rep=rep,
                               d=d, scale=scale, window=window)
    out = pl.pallas_call(
        kernel,
        out_shape=jax.ShapeDtypeStruct((t_tokens, rep, hd), q.dtype),
        grid_spec=grid_spec,
        interpret=interpret,
        name=name or _kernel_name("paged_decode", window, None),
    )(step_row, step_chunk, slots, positions, block_tables, q_wide,
      *([k_pool] * nb), *([v_pool] * nb))
    return out.reshape(t_tokens, rep, hkv, d).transpose(0, 2, 1, 3).reshape(
        t_tokens, hq, d)


# --------------------------------------------------------------- block decode
def _block_decode_kernel(row_ref, chunk_ref, pos_ref, ids_ref, q_ref, *refs,
                         bs: int, nb: int, hkv: int, d: int, scale: float):
    del ids_ref  # the index maps' alone
    k_refs, v_refs = refs[:nb], refs[nb:2 * nb]
    o_ref, acc, m_sc, l_sc = refs[2 * nb:]
    s_id = pl.program_id(0)
    c = chunk_ref[s_id]
    pos = pos_ref[row_ref[s_id]]
    ch = nb * bs

    @pl.when(c == 0)
    def _init():
        acc[:] = jnp.zeros_like(acc)
        m_sc[:] = jnp.full_like(m_sc, _NEG_INF)
        l_sc[:] = jnp.zeros_like(l_sc)

    def heads(block_refs):                                 # [Hkv, CH, D]
        # a head's keys are a static lane slice of the blocks in VMEM
        return jnp.stack([
            jnp.concatenate([r[0, :, g * d:(g + 1) * d] for r in block_refs],
                            axis=0) for g in range(hkv)])

    # ONE batched product of Hkv matrices each way: a KV head's B * rep
    # queries against that head's D lanes of the chunk only
    k, v = heads(k_refs), heads(v_refs)
    s = jax.lax.dot_general(
        q_ref[0].astype(k.dtype), k, (((2,), (2,)), ((0,), (0,))),
        preferred_element_type=jnp.float32) * scale        # [Hkv, B*rep, CH]
    # position 0 is never masked, so a row's running maximum is real from
    # its first chunk on and no row of p is all zeros
    seen = c * ch + jax.lax.broadcasted_iota(jnp.int32, (1, 1, ch), 2) <= pos
    s = jnp.where(seen, s, _NEG_INF)
    m_prev = m_sc[:]                                       # [Hkv, B*rep, 128]
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
    p = jnp.exp(s - _lanes(m_new, ch))
    corr = jnp.exp(m_prev - m_new)
    l_sc[:] = l_sc[:] * corr + jnp.sum(p, axis=-1, keepdims=True)
    m_sc[:] = m_new
    acc[:] = acc[:] * _lanes(corr, d) + jax.lax.dot_general(
        p.astype(v.dtype), v, (((2,), (1,)), ((0,), (0,))),
        preferred_element_type=jnp.float32)

    @pl.when(c == pos // ch)
    def _finish():
        o_ref[0] = (acc[:] / _lanes(l_sc[:], d)).astype(o_ref.dtype)


# ONE jitted function, as ``_paged_decode``
@functools.partial(jax.jit, static_argnames=("scale", "interpret", "name"))
def _block_decode(q, k_pool, v_pool, slots, positions, block_tables, *,
                  scale: float, interpret: bool, name: str):
    """``q`` [rows, Hkv, B*rep, D], a decoding block's queries by KV head, at
    ``positions`` (the blocks' LAST) -> as much. ``_paged_decode``'s walk
    (``decode_steps``, ``decode_step_blocks``, the same block of the pool in
    the same operand of the same step) under a body of its own."""
    rows, hkv, group, d = q.shape
    _, bs, hd = k_pool.shape
    nb = decode_step_blocks(bs, hd, k_pool.dtype.itemsize)
    n_steps = rows * -(-block_tables.shape[1] // nb) + 1
    ends, step_row, step_chunk = decode_steps(positions, nb * bs, n_steps)

    # The pool block of operand i of step s, made here and not in eight index
    # maps: ``_paged_decode._kv_map``'s rule (past the row's last block the
    # operand's block of a step ago, or the row's last)
    j = step_chunk[:, None] * nb + jnp.arange(nb, dtype=jnp.int32)
    last = (positions // bs)[step_row][:, None]
    j = jnp.where(j <= last, j, jnp.where(j >= nb, j - nb, last))
    step_ids = block_tables[slots[step_row][:, None], j].reshape(-1)

    def _row_map(s, row, chunk, po, ids):
        return (row[s], 0, 0, 0)

    def _kv_map(i):
        return lambda s, row, chunk, po, ids: (ids[s * nb + i], 0, 0)

    kv_specs = [pl.BlockSpec((1, bs, hd), _kv_map(i)) for i in range(nb)]
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=4,
        grid=(ends[-1],),
        in_specs=[pl.BlockSpec((1, hkv, group, d), _row_map)]
        + kv_specs + kv_specs,
        out_specs=pl.BlockSpec((1, hkv, group, d), _row_map),
        scratch_shapes=[
            pltpu.VMEM((hkv, group, d), jnp.float32),
            pltpu.VMEM((hkv, group, 128), jnp.float32),
            pltpu.VMEM((hkv, group, 128), jnp.float32),
        ],
    )
    kernel = functools.partial(_block_decode_kernel, bs=bs, nb=nb, hkv=hkv,
                               d=d, scale=scale)
    return pl.pallas_call(
        kernel,
        out_shape=jax.ShapeDtypeStruct(q.shape, q.dtype),
        grid_spec=grid_spec,
        interpret=interpret,
        name=name,
    )(step_row, step_chunk, positions, step_ids, q,
      *([k_pool] * nb), *([v_pool] * nb))


# --------------------------------------------------------------- tiled prefill
# Keys one grid step of the tile kernel takes, as whole pool blocks, each an
# operand of its own: a row's maximum, its sum and the rescale of its
# accumulator are paid once a step, so a step wants many keys: four of
# SmallThinker's, Mixtral's and Nemotron-3's 128-token blocks, eight of
# GPT-2 XL's 32-token blocks (never more: each is a copy of its own, with a
# buffer and a DMA in flight), and no more bytes of K (and as many of V) than
# the kernel's VMEM has room for twice beside a tile's q, output and
# float32 scratches.
PREFILL_STEP_KEYS = 512
PREFILL_STEP_BYTES = 2**20
# Query rows of ALL KV heads one turn of a step's loop takes (a strip of
# every head's group, one batched product): whole runs of a tile's rows, as
# many as stay under this.
_PREFILL_TURN_ROWS = 1024


def _strip_rows(hkv: int, rep: int, ct: int) -> int:
    """Rows of one KV head's group (``rep`` x ``ct``: query head by query
    head, a tile's ``ct`` rows each) that a turn of the step's loop takes:
    ``ct`` times the largest divisor of ``rep`` that keeps all heads' strips
    together under ``_PREFILL_TURN_ROWS`` rows, at least one run."""
    fit = [n for n in range(1, rep + 1)
           if rep % n == 0 and hkv * n * ct <= _PREFILL_TURN_ROWS]
    return ct * max(fit, default=1)


def prefill_step_blocks(bs: int, lanes: int, itemsize: int) -> int:
    """Pool blocks one grid step of the tile kernel takes from K (and as
    many from V): the power of two, 1 to 8, that holds ``PREFILL_STEP_KEYS``
    tokens or ``PREFILL_STEP_BYTES``, whichever is fewer blocks."""
    want = max(1, min(PREFILL_STEP_KEYS // bs,
                      PREFILL_STEP_BYTES // (bs * lanes * itemsize)))
    return min(1 << (want.bit_length() - 1), _DECODE_STEP_BLOCKS_MAX)


def _prefill_kernel(ts_ref, tp_ref, tv_ref, bt_ref, q_ref, *refs, bs: int,
                    nb: int, ct: int, hkv: int, rep: int, d: int, strip: int,
                    scale: float, window: int | None = None,
                    block: int | None = None):
    k_refs, v_refs = refs[:nb], refs[nb:2 * nb]
    o_ref, acc, m_sc, l_sc = refs[2 * nb:]
    c = pl.program_id(0)   # query tile
    j = pl.program_id(1)   # step of nb kv blocks
    pos0 = tp_ref[c]
    valid = tv_ref[c]
    max_pos = pos0 + valid - 1
    ch = nb * bs
    # the grid's block 0 is the tile's first needed block
    first = 0 if window is None else jnp.maximum(pos0 - window + 1, 0) // bs
    k_lo = (first + j * nb) * bs
    rows = rep * ct
    # p = exp(scale * (s - m)) on the raw float32 products: one multiply
    log2e_scale = scale * 1.4426950408889634

    @pl.when(j == 0)
    def _init():
        acc[:] = jnp.zeros_like(acc)
        m_sc[:] = jnp.full_like(m_sc, _NEG_INF)
        l_sc[:] = jnp.zeros_like(l_sc)

    def heads(block_refs):
        # the step's blocks, then static lane slices of them (Mosaic refuses
        # a reshape of the lanes at D = 64)
        parts = [r[0] for r in block_refs]
        x = parts[0] if nb == 1 else jnp.concatenate(parts, axis=0)
        return jnp.stack([x[:, g * d:(g + 1) * d] for g in range(hkv)])

    @pl.when(jnp.logical_and(valid > 0, k_lo <= max_pos))
    def _step():
        k, v = heads(k_refs), heads(v_refs)                # [Hkv, CH, D]
        # Row r of a head's group is query token r % ct and a strip is whole
        # runs of ct rows, so ONE mask a step serves every strip and head; a
        # clamped repeat's keys lie past every real query's position.
        tok = jax.lax.broadcasted_iota(jnp.int32, (strip, ch), 0) % ct
        if block is not None:
            # a query sees its whole block (pos0 is a multiple of it)
            tok = tok | (block - 1)
        ahead = (pos0 - k_lo + tok
                 - jax.lax.broadcasted_iota(jnp.int32, (strip, ch), 1))
        seen = ahead >= 0                                   # kpos <= qpos
        if window is not None:
            seen = jnp.logical_and(seen, ahead < window)

        def strip_of_every_head(i, _):
            at = pl.ds(pl.multiple_of(i * strip, strip), strip)
            q = q_ref[0, :, at, :]                         # [Hkv, strip, D]
            s = jax.lax.dot_general(
                q.astype(k.dtype), k, (((2,), (2,)), ((0,), (0,))),
                preferred_element_type=jnp.float32)        # [Hkv, strip, CH]
            s = jnp.where(seen[None], s, _NEG_INF)
            # A row that has met none of its keys yet (the window's edge
            # lies in a later block; a padding row) keeps m = -1e30 and
            # gathers p = 1 over masked keys; its first real key's corr =
            # exp(-1e30 - m) = 0 wipes that, and every real row meets its
            # own position.
            m_prev = m_sc[:, at, :]                         # [Hkv, strip, 128]
            m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
            p = jnp.exp2((s - _lanes(m_new, ch)) * log2e_scale)
            corr = jnp.exp2((m_prev - m_new) * log2e_scale)
            l_sc[:, at, :] = l_sc[:, at, :] * corr + jnp.sum(
                p, -1, keepdims=True)
            pv = jax.lax.dot_general(
                p.astype(v.dtype), v, (((2,), (1,)), ((0,), (0,))),
                preferred_element_type=jnp.float32)        # [Hkv, strip, D]
            acc[:, at, :] = acc[:, at, :] * _lanes(corr, d) + pv
            m_sc[:, at, :] = m_new

        # traced once, laid out end to end: as a loop the turns run one
        # after another, 21-24% slower (PERF.md section 6, PR 45)
        jax.lax.fori_loop(0, rows // strip, strip_of_every_head, None,
                          unroll=True)

    @pl.when(j == pl.num_programs(1) - 1)
    def _finish():
        l = jnp.maximum(_lanes(l_sc[:], d), 1e-30)
        o_ref[0] = (acc[:] / l).astype(o_ref.dtype)


# Scoped VMEM a Mosaic kernel may use by default (v5e), and what the tile
# kernel asks for instead (of 128 MiB; the default refuses a whole 128-row
# tile of 32 heads x 128 beside 512 keys, and 28 heads in a program of one
# tile). What the kernel holds in it: for one query row of one head three
# float32 scratches (acc, m, l; D pads to 128 lanes) and the double-buffered
# q and o blocks in the operands' dtype; a step's K and V blocks,
# double-buffered; and up to four float32 values of a turn's scores (all
# heads' strips x the step's keys; the compiler's own totals at the cells'
# shapes: 13.7-18.6 MiB; tests/unit/test_compile_tpu.py).
_VMEM_SCOPED_BYTES = 16 * 2**20
_PREFILL_VMEM_BYTES = 48 * 2**20
_STRIP_TEMPORARIES = 4


def prefill_kernel_tile(tile: int, hq: int, hkv: int, d: int, itemsize: int,
                        step_keys: int) -> int:
    """Largest power-of-two split of the scheduler's ``tile`` whose working
    set fits the kernel's VMEM with an eighth to spare, at ``step_keys``
    keys a grid step."""
    row = hq * max(d, 128) * (3 * 4 + 2 * 2 * itemsize)

    def fits(ct):
        turn = hkv * _strip_rows(hkv, hq // hkv, ct)
        return ct * row + step_keys * (
            4 * hkv * d * itemsize + _STRIP_TEMPORARIES * 4 * turn
        ) <= _PREFILL_VMEM_BYTES * 7 // 8

    ct = tile
    while ct > 8 and ct % 2 == 0 and not fits(ct):
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
                             interpret: bool | None = None,
                             window: int | None = None,
                             block: int | None = None):
    """Tiled prefill attention: [NT*CT, Hq, D] tile-aligned prefill tokens ->
    outputs, one KV-block DMA shared by the whole CT-token tile (the
    SplitFuse blocked flash attention, reference
    ``inference/v2/kernels/ragged_ops`` — vs the decode kernel above, which
    fetches per TOKEN and is O(context) DMA per token).

    Scheduler contract (``inference/ragged.py``): each tile's tokens belong
    to ONE sequence at consecutive positions ``pos0..pos0+valid-1``; rows
    past ``valid`` are padding and their outputs unspecified (finite).
    ``tile_valid == 0`` marks an all-pad tile.

    Where a whole scheduler tile does not fit the kernel's VMEM (128 heads x
    128; every cell's geometry fits), each tile runs as consecutive
    sub-tiles: a sub-tile is itself a tile of the same sequence, so the
    kernel and its masking are unchanged and only the KV blocks are fetched
    once per sub-tile instead of once per tile.

    With a ``window`` (static) the grid's second axis is not the table's
    width but the blocks a window and a tile can span, from the tile's first
    needed block; no entry before it need name a block of the sequence's.

    With a ``block`` (static, ``B``; a divisor of the tile and of a pool
    block, every ``tile_pos0`` and ``tile_valid`` a multiple of it) the mask
    is block-causal: a query at ``i`` sees keys ``j <= (i | (B - 1))``.
    """
    _, hq, d = q.shape
    _, bs, hd = k_pool.shape
    nb = prefill_step_blocks(bs, hd, k_pool.dtype.itemsize)
    ct = prefill_kernel_tile(tile, hq, hd // d, d, q.dtype.itemsize, nb * bs)
    return _tiled_prefill(
        q, k_pool, v_pool, tile_slot.astype(jnp.int32),
        tile_pos0.astype(jnp.int32), tile_valid.astype(jnp.int32),
        block_tables.astype(jnp.int32), tile=tile, ct=ct, nb=nb,
        scale=float(scale if scale is not None else 1.0 / (d ** 0.5)),
        interpret=interpret_mode(interpret), window=window, block=block)


# ONE jitted function: a period's window layers, and the step programs of one
# tile count (d4_t3 .. d16_t3), share its trace
@functools.partial(jax.jit, static_argnames=(
    "tile", "ct", "nb", "scale", "interpret", "window", "block"))
def _tiled_prefill(q, k_pool, v_pool, tile_slot, tile_pos0, tile_valid,
                   block_tables, *, tile: int, ct: int, nb: int, scale: float,
                   interpret: bool, window: int | None,
                   block: int | None = None):
    t_tokens, hq, d = q.shape
    _, bs, hd = k_pool.shape
    hkv = hd // d
    mb = block_tables.shape[1]
    rep = hq // hkv
    tile_slot, tile_pos0, tile_valid = split_tiles(
        tile_slot, tile_pos0, tile_valid, tile, ct)
    n_tiles = t_tokens // ct

    # block i of step j, counted from the tile's first needed block (NOT from
    # a multiple of nb: the sliding pool's table names no block before it);
    # past the tile's last needed block clamped to it: unchanged id -> no new
    # DMA, and its keys are masked by their position
    def _kv_map(i):
        def index(c, j, ts, tp, tv, bt):
            last = jnp.maximum(tp[c] + tv[c] - 1, 0) // bs
            blk = j * nb + i
            if window is not None:
                blk = blk + jnp.maximum(tp[c] - window + 1, 0) // bs
            return (bt[ts[c], jnp.minimum(blk, last)], 0, 0)
        return index

    if window is not None:
        # keys pos0 - window + 1 .. pos0 + ct - 1, wherever pos0 lies in a block
        mb = min(mb, (window + ct - 2) // bs + 2)

    def _tile_map(c, j, ts, tp, tv, bt):
        return (c, 0, 0, 0)

    # a KV head's group of query heads as ONE matrix of rep * CT rows (row r
    # is head r // CT of the group, token r % CT): laid out here, beside the
    # projection that made q, once a layer, not in every grid step
    q_groups = q.reshape(n_tiles, ct, hkv, rep, d).transpose(
        0, 2, 3, 1, 4).reshape(n_tiles, hkv, rep * ct, d)
    kv_specs = [pl.BlockSpec((1, bs, hd), _kv_map(i)) for i in range(nb)]
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=4,
        grid=(n_tiles, -(-mb // nb)),
        in_specs=[pl.BlockSpec((1, hkv, rep * ct, d), _tile_map)]
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
        strip=_strip_rows(hkv, rep, ct), scale=scale, window=window,
        **({} if block is None else {"block": block}))
    out = pl.pallas_call(
        kernel,
        out_shape=jax.ShapeDtypeStruct((n_tiles, hkv, rep * ct, d), q.dtype),
        grid_spec=grid_spec,
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=_PREFILL_VMEM_BYTES),
        interpret=interpret,
        name=_kernel_name("tiled_prefill", window, block),
    )(tile_slot, tile_pos0, tile_valid, block_tables, q_groups,
      *([k_pool] * nb), *([v_pool] * nb))
    return out.reshape(n_tiles, hkv, rep, ct, d).transpose(
        0, 3, 1, 2, 4).reshape(t_tokens, hq, d)
