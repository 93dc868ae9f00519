"""Pallas TPU flash attention (forward kernel + custom VJP).

Role parity with the reference's fused attention kernels
(``csrc/transformer/inference/csrc/softmax.cu``, v2 ``ragged_ops`` blocked
flash attention) — re-built as a Pallas kernel for the MXU: a program holds
one block of Q rows and one K/V block in VMEM, K/V blocks stream through the
sequential innermost grid dim with the classic online-softmax accumulation,
so the [Sq, Sk] score matrix never materializes in HBM.

**What is skipped.** Inside a program the kernels walk the two blocks in
sub-blocks (``sub`` rows; ``ops.attention.flash_blocks`` is the rule) and
stop at the diagonal. Forward and dQ take one strip of ``sub`` q rows at a
time against the K/V rows it meets and no further (``kv_walk``); dK / dV take
one strip of K/V rows against the q rows from its diagonal sub-block down
(``q_walk``). Sub-blocks strictly under the diagonal take no mask, only those
the diagonal crosses pay for the iotas, the compare and the select; those
above it are neither sliced into a product nor exponentiated. The walk is
static: a program's place against the diagonal (``_block_walks``) picks one
of a few bodies traced with their slices known, so it works at every length
from two sub-blocks up, also where one block is the whole sequence (the
training cell's 1,024 tokens: a grid of ``(B, H, 1, 1)``, where a skip of
whole blocks never fires). Whole blocks above the diagonal run no body, and
their index maps name a block the pipeline already holds, so they fetch
nothing. With 256-row sub-blocks 10 of 16 pairs of a 1,024-token square are
multiplied (``ops.attention.flash_pair_share``); ``causal=False`` takes the
same loops over every sub-block with no mask.

**Measured** (one layer's three calls on a v5e chip, us; my chip runs, PR 39;
PERF.md section 6 has the table): ``[4, 25, 1024, 64]`` 364 / 651 / 458
forward / dK dV / dQ on the whole square, 226 / 435 / 306 walking 256-row
sub-blocks; ``[1, 32, 2048, 128]`` 421 / 674 / 465 against 278 / 447 / 345.
Three things beside the skip carry that. The forward keeps a row's running
maximum and sum in every lane of a ``[rows, 128]`` scratch (``_lanes``). It
writes a strip's scores one strip ahead of the exponentials before them, so
the MXU has work while a strip waits for its rows' maximum. And dK / dV
multiply the scores transposed (K rows down, q rows across), so dV and dK are
plain products of ``p`` and ``ds`` with no transpose through the MXU. The
compiled kernels issue matmuls in 81 / 97 / 98% of their bundles: what is left
at float32 operands and a 64-wide head is the MXU's own time.

Layouts: q/k/v [B, S, H, D] (GQA supported: the K/V block index maps divide the
head index, so KV heads are never replicated in memory). The backward pass is
two Pallas kernels (dk/dv accumulated over q blocks; dq accumulated over kv
blocks) from the saved lse — the [Sq, Sk] score matrix never materializes in
either direction. Set ``DSTPU_FLASH_XLA_BWD=1`` to fall back to the XLA
recompute backward.
"""

from __future__ import annotations

import functools
import math
import os

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_NEG_INF = -1e30


def _clamp_div(x: int, t: int, n: int) -> int:
    """``clamp(floor(x / t), 0, n)``."""
    return min(max(x // t, 0), n)


def kv_walk(q0: int, tq: int, tk: int, nk: int, causal: bool):
    """The sub-blocks ``[n * tk, + tk)``, ``n < nk``, of a K/V block that the
    q rows ``[q0, q0 + tq)`` meet (``q0`` counted from the block's first
    row): ``(full, end)``. Sub-blocks ``[0, full)`` lie under the diagonal
    and take no mask, ``[full, end)`` are crossed by it, the rest hold no
    causal pair and are not touched. Without a mask every sub-block is a
    full one."""
    if not causal:
        return nk, nk
    return (_clamp_div(q0 + 1, tk, nk), _clamp_div(q0 + tq - 1 + tk, tk, nk))


def q_walk(k0: int, tk: int, q_base: int, tq: int, nq: int, causal: bool):
    """``kv_walk`` with the roles exchanged, for dK / dV: of the sub-blocks
    ``[q_base + n * tq, + tq)``, ``n < nq``, of a q block the K/V rows
    ``[k0, k0 + tk)`` meet none before ``start``; ``[start, full)`` are
    crossed by the diagonal and ``[full, nq)`` take no mask:
    ``(start, full)``."""
    if not causal:
        return 0, 0
    return (_clamp_div(k0 - q_base, tq, nq),
            _clamp_div(k0 + tk - 1 - q_base + tq - 1, tq, nq))


def _block_walks(walk, rels):
    """What a program does follows from ONE number, its q block's first row
    counted from its K/V block's (``rel = i * block_q - j * block_k``): under
    the diagonal every sub-block is a full one, across it the walk is a
    triangle, above it nothing runs. ``walk(rel)`` gives a program's
    sub-block bounds (a tuple of ``kv_walk`` / ``q_walk`` pairs, or None
    where nothing runs); this groups the grid's ``rel`` values by them:
    ``[(rel_lo, rel_hi, walks)]``, all static, so each group's body is
    traced with its slices and trip counts known (a sequence of one block is
    one group: no branch). A walk the diagonal crosses is a group of its own
    ``rel``: its mask counts positions from there."""
    groups = []
    for rel in rels:
        walks = walk(rel)
        if walks is None:
            continue
        if groups and groups[-1][2] == walks and not _crossed(walks):
            groups[-1] = (groups[-1][0], rel, walks)
        else:
            groups.append((rel, rel, walks))
    return groups


def _crossed(walks) -> bool:
    return any(a != b for a, b in walks)


def _for_each_walk(rel, groups, branch: bool, body):
    """Run ``body(rel_lo, walks)`` of the group the program's ``rel`` falls
    in; with no ``branch`` every program is in the one group there is."""
    if not branch:
        return body(groups[0][0], groups[0][2])
    for lo, hi, walks in groups:
        pl.when((rel >= lo) & (rel <= hi))(
            functools.partial(body, lo, walks))


def _causal_mask(s, q0: int, k0: int, q_axis: int = 0):
    """Scores ``s`` of q rows from ``q0`` (along ``q_axis``) against K rows
    from ``k0`` with the pairs above the diagonal at -1e30: only sub-blocks
    the diagonal crosses pay for the two iotas, the compare and the select."""
    q_pos = q0 + jax.lax.broadcasted_iota(jnp.int32, s.shape, q_axis)
    k_pos = k0 + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1 - q_axis)
    return jnp.where(q_pos >= k_pos, s, _NEG_INF)


def _mask_columns(s, q0: int, full: int):
    """``s`` [rows from q0, K rows from 0]: its columns from ``full`` on are
    the sub-blocks the diagonal crosses."""
    if full == s.shape[1]:
        return s
    crossed = _causal_mask(s[:, full:], q0, full)
    return crossed if not full else jnp.concatenate([s[:, :full], crossed], 1)


def _lanes(x, n: int):
    """``x`` [.., rows, 128], a row's one value in every lane, as [.., rows,
    n]. The softmax statistics live in that form: as [rows, 1] every use of
    them costs a lane broadcast on the XLU, which the forward walk then waits
    for (my chip runs, PR 39: 439 us a call against 264, strip after strip)."""
    lanes = x.shape[-1]
    if n <= lanes:
        return x[..., :n]
    if n % lanes:
        return jnp.broadcast_to(x[..., :1], x.shape[:-1] + (n,))
    return jnp.tile(x, (1,) * (x.ndim - 1) + (n // lanes,))


def _dot(a, b, contract):
    return jax.lax.dot_general(a, b, (contract, ((), ())),
                               preferred_element_type=jnp.float32)


_NT = ((1,), (1,))   # a @ b.T
_NN = ((1,), (0,))   # a @ b


def _fwd_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref, acc, m_sc, l_sc,
                *, scale: float, sub_q: int, sub_k: int, block_q: int,
                block_k: int, groups, branch: bool):
    i = pl.program_id(2)  # q block
    j = pl.program_id(3)  # kv block (sequential innermost)

    @pl.when(j == 0)
    def _init():
        acc[:] = jnp.zeros_like(acc)
        m_sc[:] = jnp.full_like(m_sc, _NEG_INF)
        l_sc[:] = jnp.zeros_like(l_sc)

    def scores(rel, qi, full, end):
        """A strip of sub_q rows against the K/V rows it meets: its scores
        and its rows' running maximum, before and after them."""
        rows = pl.ds(qi * sub_q, sub_q)
        q = q_ref[0, 0, rows, :].astype(jnp.float32) * scale      # [tq, d]
        k = k_ref[0, 0, pl.ds(0, end * sub_k), :].astype(jnp.float32)
        s = _mask_columns(_dot(q, k, _NT), rel + qi * sub_q,
                          full * sub_k)                           # [tq, n]
        m_prev = m_sc[rows, :]                                    # [tq, 128]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
        return rows, end, s, m_prev, m_new

    def accumulate(rows, end, s, m_prev, m_new):
        p = jnp.exp(s - _lanes(m_new, s.shape[1]))
        corr = jnp.exp(m_prev - m_new)
        l_sc[rows, :] = (l_sc[rows, :] * corr
                         + jnp.sum(p, axis=-1, keepdims=True))
        v = v_ref[0, 0, pl.ds(0, end * sub_k), :].astype(jnp.float32)
        pv = _dot(p, v, _NN)
        acc[rows, :] = acc[rows, :] * _lanes(corr, pv.shape[1]) + pv
        m_sc[rows, :] = m_new

    def body(rel, walks):
        # One strip at a time, one ahead: a strip's exponentials wait for its
        # rows' maximum (a reduction across lanes), so the next strip's
        # scores are written before them and the MXU has work meanwhile. The
        # compiler's scheduler keeps to the order it is given: 264 us a call
        # strip after strip, 226 so (my chip runs, PR 39).
        ahead = None
        for qi, (full, end) in enumerate(walks):
            if not end:
                continue
            strip = scores(rel, qi, full, end)
            if ahead is not None:
                accumulate(*ahead)
            ahead = strip
        accumulate(*ahead)

    _for_each_walk(i * block_q - j * block_k, groups, branch, body)

    @pl.when(j == pl.num_programs(3) - 1)
    def _finish():
        safe_l = jnp.maximum(l_sc[:, 0:1], 1e-30)
        o_ref[0, 0] = (acc[:] / safe_l).astype(o_ref.dtype)
        lse_ref[0, 0] = m_sc[:, 0:1] + jnp.log(safe_l)


def _geometry(q, k, block_q, block_k, sub):
    """Blocks, sub-blocks and the grid's two sequence extents as the kernels
    take them."""
    sq, skv = q.shape[1], k.shape[1]
    block_q, block_k = min(block_q, sq), min(block_k, skv)
    return dict(block_q=block_q, block_k=block_k,
                sub_q=min(sub, block_q), sub_k=min(sub, block_k),
                grid_q=sq // block_q, grid_k=skv // block_k)


def _kernel_walks(geo, causal: bool, by_q: bool):
    """Static arguments of a kernel: sub-block and block sizes and its
    programs' walks, a q strip's ``kv_walk`` a program (forward, dQ:
    ``by_q``) or a K/V strip's ``q_walk`` (dK / dV)."""
    tq, tk = geo["sub_q"], geo["sub_k"]
    nq, nk = geo["block_q"] // tq, geo["block_k"] // tk

    def walk(rel):
        if by_q:
            walks = tuple(kv_walk(rel + n * tq, tq, tk, nk, causal)
                          for n in range(nq))
            return walks if any(end for _, end in walks) else None
        walks = tuple(q_walk(n * tk, tk, rel, tq, nq, causal)
                      for n in range(nk))
        return walks if any(start < nq for start, _ in walks) else None

    rels = sorted({i * geo["block_q"] - j * geo["block_k"]
                   for i in range(geo["grid_q"]) for j in range(geo["grid_k"])})
    groups = _block_walks(walk, rels)
    return dict(sub_q=tq, sub_k=tk, block_q=geo["block_q"],
                block_k=geo["block_k"], groups=groups,
                branch=(groups[0][0], groups[0][1]) != (rels[0], rels[-1]))


def _kv_index(n_rep: int, causal: bool, block_q: int, block_k: int):
    """Index map of a K/V block on a grid ``(b, h, i, j)``: a step above the
    diagonal names the last block its q block needs, which the pipeline then
    holds already, so a skipped step fetches nothing."""
    def index(b_, h, i, j):
        if causal:
            j = jnp.minimum(j, ((i + 1) * block_q - 1) // block_k)
        return b_, h // n_rep, j, 0
    return index


def _flash_fwd(q, k, v, scale, causal, block_q, block_k, sub, interpret):
    b, sq, hq, d = q.shape
    skv, hkv = k.shape[1], k.shape[2]
    n_rep = hq // hkv
    geo = _geometry(q, k, block_q, block_k, sub)
    block_q, block_k = geo["block_q"], geo["block_k"]

    qt = q.transpose(0, 2, 1, 3)  # [B, H, S, D]
    kt = k.transpose(0, 2, 1, 3)
    vt = v.transpose(0, 2, 1, 3)

    kv_spec = pl.BlockSpec((1, 1, block_k, d),
                           _kv_index(n_rep, causal, block_q, block_k))
    out, lse = pl.pallas_call(
        functools.partial(_fwd_kernel, scale=scale,
                          **_kernel_walks(geo, causal, by_q=True)),
        out_shape=(
            jax.ShapeDtypeStruct((b, hq, sq, d), q.dtype),
            jax.ShapeDtypeStruct((b, hq, sq, 1), jnp.float32),
        ),
        grid=(b, hq, geo["grid_q"], geo["grid_k"]),
        in_specs=[
            pl.BlockSpec((1, 1, block_q, d), lambda b_, h, i, j: (b_, h, i, 0)),
            kv_spec, kv_spec,
        ],
        out_specs=(
            pl.BlockSpec((1, 1, block_q, d), lambda b_, h, i, j: (b_, h, i, 0)),
            pl.BlockSpec((1, 1, block_q, 1), lambda b_, h, i, j: (b_, h, i, 0)),
        ),
        scratch_shapes=[
            _scratch((block_q, d)),
            _scratch((block_q, 128)),
            _scratch((block_q, 128)),
        ],
        interpret=interpret,
        name="flash_fwd",
    )(qt, kt, vt)
    return out.transpose(0, 2, 1, 3), lse[..., 0]


def _scratch(shape):
    return pltpu.VMEM(shape, jnp.float32)


def interpret_mode(interpret: bool | None) -> bool:
    """Interpret mode is for a caller that chose the CPU (tests,
    rehearsals); on the chip the kernel compiles or the error surfaces."""
    return jax.default_backend() != "tpu" if interpret is None else interpret


def supported(q, k, block_q, block_k, sub) -> bool:
    """Shapes the kernel takes; dispatchers ask BEFORE the call."""
    b, sq, hq, d = q.shape
    skv, hkv = k.shape[1], k.shape[2]
    if hq % hkv:
        return False
    geo = _geometry(q, k, block_q, block_k, sub)
    if sq % geo["block_q"] or skv % geo["block_k"]:
        return False
    if geo["block_q"] % geo["sub_q"] or geo["block_k"] % geo["sub_k"]:
        return False
    if d % 8:
        return False
    return True


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7, 8))
def flash_attention(q, k, v, causal: bool = True, scale: float | None = None,
                    block_q: int = 256, block_k: int = 512, sub: int = 256,
                    interpret: bool | None = None):
    """Drop-in for ``ops.attention.xla_attention`` on TPU shapes. A program
    holds ``block_q`` query rows and one ``block_k`` K/V block in VMEM and
    walks them in sub-blocks of ``sub`` rows (``ops.attention.flash_blocks``
    is the rule for all three). ``interpret=None`` interprets off the chip
    and compiles on it; a compile test for a described device passes
    ``False``."""
    return _fa_fwd(q, k, v, causal, scale, block_q, block_k, sub, interpret)[0]


def _fa_fwd(q, k, v, causal, scale, block_q, block_k, sub, interpret):
    scale = scale if scale is not None else 1.0 / math.sqrt(q.shape[-1])
    if not supported(q, k, block_q, block_k, sub):
        raise NotImplementedError("flash_attention: unsupported shape")
    out, lse = _flash_fwd(q, k, v, scale, causal, block_q, block_k, sub,
                          interpret_mode(interpret))
    return out, (q, k, v, out, lse)


def _dkdv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, dk_ref, dv_ref,
                 dk_acc, dv_acc, *, scale: float, sub_q: int, sub_k: int,
                 block_q: int, block_k: int, groups, branch: bool):
    j = pl.program_id(2)  # kv block
    i = pl.program_id(3)  # q block (sequential innermost)

    @pl.when(i == 0)
    def _init():
        dk_acc[:] = jnp.zeros_like(dk_acc)
        dv_acc[:] = jnp.zeros_like(dv_acc)

    def body(rel, walks):
        # one strip of sub_k K/V rows at a time, against the q rows from its
        # diagonal sub-block down
        for kj, (start, full) in enumerate(walks):
            n = (block_q // sub_q - start) * sub_q
            if not n:
                continue
            cols = pl.ds(kj * sub_k, sub_k)
            rows = pl.ds(start * sub_q, n)
            k = k_ref[0, 0, cols, :].astype(jnp.float32)      # [tk, d]
            v = v_ref[0, 0, cols, :].astype(jnp.float32)
            q = q_ref[0, 0, rows, :].astype(jnp.float32)      # [n, d]
            do = do_ref[0, 0, rows, :].astype(jnp.float32)
            # the scores transposed, K rows down and q rows across: dV and
            # dK are then plain products of them, no transpose of p or ds
            s = _dot(k, q, _NT) * scale                        # [tk, n]
            crossed = (full - start) * sub_q
            if crossed:
                left = _causal_mask(s[:, :crossed], rel + start * sub_q,
                                    kj * sub_k, q_axis=1)
                s = left if crossed == n else jnp.concatenate(
                    [left, s[:, crossed:]], 1)
            p = jnp.exp(s - lse_ref[0, 0, :, rows])            # lse: [1, n]
            dv_acc[cols, :] = dv_acc[cols, :] + _dot(p, do, _NN)
            ds = p * (_dot(v, do, _NT) - delta_ref[0, 0, :, rows]) * scale
            dk_acc[cols, :] = dk_acc[cols, :] + _dot(ds, q, _NN)

    _for_each_walk(i * block_q - j * block_k, groups, branch, body)

    @pl.when(i == pl.num_programs(3) - 1)
    def _finish():
        dk_ref[0, 0] = dk_acc[:].astype(dk_ref.dtype)
        dv_ref[0, 0] = dv_acc[:].astype(dv_ref.dtype)


def _dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, dq_ref, dq_acc,
               *, scale: float, sub_q: int, sub_k: int, block_q: int,
               block_k: int, groups, branch: bool):
    i = pl.program_id(2)  # q block
    j = pl.program_id(3)  # kv block (sequential innermost)

    @pl.when(j == 0)
    def _init():
        dq_acc[:] = jnp.zeros_like(dq_acc)

    def body(rel, walks):
        for qi, (full, end) in enumerate(walks):
            if not end:
                continue
            rows = pl.ds(qi * sub_q, sub_q)
            cols = pl.ds(0, end * sub_k)
            q = q_ref[0, 0, rows, :].astype(jnp.float32)
            do = do_ref[0, 0, rows, :].astype(jnp.float32)
            k = k_ref[0, 0, cols, :].astype(jnp.float32)
            v = v_ref[0, 0, cols, :].astype(jnp.float32)
            s = _mask_columns(_dot(q, k, _NT) * scale, rel + qi * sub_q,
                              full * sub_k)
            p = jnp.exp(s - lse_ref[0, 0, rows, :])
            ds = p * (_dot(do, v, _NT) - delta_ref[0, 0, rows, :]) * scale
            dq_acc[rows, :] = dq_acc[rows, :] + _dot(ds, k, _NN)

    _for_each_walk(i * block_q - j * block_k, groups, branch, body)

    @pl.when(j == pl.num_programs(3) - 1)
    def _finish():
        dq_ref[0, 0] = dq_acc[:].astype(dq_ref.dtype)


def _flash_bwd_pallas(q, k, v, out, lse, do, scale, causal, block_q, block_k,
                      sub, interpret):
    b, sq, hq, d = q.shape
    skv, hkv = k.shape[1], k.shape[2]
    n_rep = hq // hkv
    geo = _geometry(q, k, block_q, block_k, sub)
    block_q, block_k = geo["block_q"], geo["block_k"]
    grid_q, grid_k = geo["grid_q"], geo["grid_k"]

    delta = jnp.sum(do.astype(jnp.float32) * out.astype(jnp.float32), axis=-1)
    delta = delta.transpose(0, 2, 1)                          # [B, Hq, Sq]
    # dQ takes a q row's lse and delta down a column, dK / dV across a row
    lse_col, delta_col = lse[..., None], delta[..., None]     # [B, Hq, Sq, 1]
    lse_row, delta_row = lse[:, :, None], delta[:, :, None]   # [B, Hq, 1, Sq]

    qt = q.transpose(0, 2, 1, 3)
    kt = k.transpose(0, 2, 1, 3)
    vt = v.transpose(0, 2, 1, 3)
    dot = do.transpose(0, 2, 1, 3)

    def q_block(j, i):
        # dK / dV walk the q blocks: one before the first a K/V block meets
        # names that first one, so a skipped step fetches nothing
        return jnp.maximum(i, (j * block_k) // block_q) if causal else i

    kv_index = _kv_index(n_rep, causal, block_q, block_k)
    q_spec_i = pl.BlockSpec((1, 1, block_q, d), lambda b_, h, i, j: (b_, h, i, 0))
    q_spec_j = pl.BlockSpec((1, 1, block_q, d),
                            lambda b_, h, j, i: (b_, h, q_block(j, i), 0))
    kv_spec_i = pl.BlockSpec((1, 1, block_k, d), kv_index)
    kv_spec_j = pl.BlockSpec((1, 1, block_k, d), lambda b_, h, j, i: (b_, h // n_rep, j, 0))
    row_spec_i = pl.BlockSpec((1, 1, block_q, 1), lambda b_, h, i, j: (b_, h, i, 0))
    row_spec_j = pl.BlockSpec((1, 1, 1, block_q),
                              lambda b_, h, j, i: (b_, h, 0, q_block(j, i)))

    # dk/dv: one [B, Hq, Skv, D] buffer per q-head group, reduced below for GQA
    dk_h, dv_h = pl.pallas_call(
        functools.partial(_dkdv_kernel, scale=scale,
                          **_kernel_walks(geo, causal, by_q=False)),
        out_shape=(
            jax.ShapeDtypeStruct((b, hq, skv, d), jnp.float32),
            jax.ShapeDtypeStruct((b, hq, skv, d), jnp.float32),
        ),
        grid=(b, hq, grid_k, grid_q),
        in_specs=[q_spec_j, kv_spec_j, kv_spec_j, q_spec_j, row_spec_j, row_spec_j],
        out_specs=(
            pl.BlockSpec((1, 1, block_k, d), lambda b_, h, j, i: (b_, h, j, 0)),
            pl.BlockSpec((1, 1, block_k, d), lambda b_, h, j, i: (b_, h, j, 0)),
        ),
        scratch_shapes=[_scratch((block_k, d)), _scratch((block_k, d))],
        interpret=interpret,
        name="flash_bwd_dkv",
    )(qt, kt, vt, dot, lse_row, delta_row)

    dq = pl.pallas_call(
        functools.partial(_dq_kernel, scale=scale,
                          **_kernel_walks(geo, causal, by_q=True)),
        out_shape=jax.ShapeDtypeStruct((b, hq, sq, d), q.dtype),
        grid=(b, hq, grid_q, grid_k),
        in_specs=[q_spec_i, kv_spec_i, kv_spec_i, q_spec_i, row_spec_i, row_spec_i],
        out_specs=pl.BlockSpec((1, 1, block_q, d), lambda b_, h, i, j: (b_, h, i, 0)),
        scratch_shapes=[_scratch((block_q, d))],
        interpret=interpret,
        name="flash_bwd_dq",
    )(qt, kt, vt, dot, lse_col, delta_col)

    dq = dq.transpose(0, 2, 1, 3)
    dk = dk_h.transpose(0, 2, 1, 3)
    dv = dv_h.transpose(0, 2, 1, 3)
    if n_rep > 1:
        dk = dk.reshape(b, skv, hkv, n_rep, d).sum(axis=3)
        dv = dv.reshape(b, skv, hkv, n_rep, d).sum(axis=3)
    return dq.astype(q.dtype), dk.astype(k.dtype), dv.astype(v.dtype)


def _fa_bwd_xla(causal, scale, res, do):
    """Standard flash backward algebra from saved lse (XLA; fp32)."""
    q, k, v, out, lse = res
    scale = scale if scale is not None else 1.0 / math.sqrt(q.shape[-1])
    hq, hkv = q.shape[2], k.shape[2]
    n_rep = hq // hkv
    from deepspeed_tpu.ops.attention import repeat_kv

    kf = repeat_kv(k, n_rep).astype(jnp.float32)
    vf = repeat_kv(v, n_rep).astype(jnp.float32)
    qf = q.astype(jnp.float32)
    dof = do.astype(jnp.float32)
    of = out.astype(jnp.float32)

    s = jnp.einsum("bqhd,bkhd->bhqk", qf, kf) * scale
    if causal:
        sq, sk = q.shape[1], k.shape[1]
        mask = (jnp.arange(sq)[:, None] + (sk - sq)) >= jnp.arange(sk)[None, :]
        s = jnp.where(mask[None, None], s, _NEG_INF)
    p = jnp.exp(s - lse[:, :, :, None])                       # [B,H,Sq,Sk]
    dv = jnp.einsum("bhqk,bqhd->bkhd", p, dof)
    dp = jnp.einsum("bqhd,bkhd->bhqk", dof, vf)
    delta = jnp.sum(dof * of, axis=-1).transpose(0, 2, 1)     # [B,H,Sq]
    ds = p * (dp - delta[..., None]) * scale
    dq = jnp.einsum("bhqk,bkhd->bqhd", ds, kf).astype(q.dtype)
    dk_full = jnp.einsum("bhqk,bqhd->bkhd", ds, qf)
    if n_rep > 1:
        bsz, sk_, _, dh = dk_full.shape
        dk_full = dk_full.reshape(bsz, sk_, hkv, n_rep, dh).sum(axis=3)
        dv = dv.reshape(bsz, sk_, hkv, n_rep, dh).sum(axis=3)
    return dq, dk_full.astype(k.dtype), dv.astype(v.dtype)


def _fa_bwd(causal, scale, block_q, block_k, sub, interpret, res, do):
    if os.environ.get("DSTPU_FLASH_XLA_BWD"):
        return _fa_bwd_xla(causal, scale, res, do)
    q, k, v, out, lse = res
    scale = scale if scale is not None else 1.0 / math.sqrt(q.shape[-1])
    return _flash_bwd_pallas(q, k, v, out, lse, do, scale, causal, block_q,
                             block_k, sub, interpret_mode(interpret))


flash_attention.defvjp(_fa_fwd, _fa_bwd)
