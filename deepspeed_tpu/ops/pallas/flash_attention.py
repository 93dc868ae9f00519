"""Pallas TPU flash attention (forward kernel + custom VJP).

Role parity with the reference's fused attention kernels
(``csrc/transformer/inference/csrc/softmax.cu``, v2 ``ragged_ops`` blocked
flash attention) — re-built as a Pallas kernel for the MXU: Q blocks stream
from VMEM, KV blocks stream through the sequential innermost grid dim with the
classic online-softmax accumulation, so the [Sq, Sk] score matrix never
materializes in HBM. Causal upper-triangle blocks are skipped with predicated
execution (``pl.when``), halving the work.

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


def _fwd_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref, acc, m_sc, l_sc,
                *, scale: float, causal: bool, block_q: int, block_k: int):
    i = pl.program_id(2)  # q block
    j = pl.program_id(3)  # kv block (sequential innermost)
    nj = pl.num_programs(3)

    @pl.when(j == 0)
    def _init():
        acc[:] = jnp.zeros_like(acc)
        m_sc[:] = jnp.full_like(m_sc, _NEG_INF)
        l_sc[:] = jnp.zeros_like(l_sc)

    # skip blocks strictly above the diagonal (q ends before kv starts)
    run = True
    if causal:
        run = (i + 1) * block_q - 1 >= j * block_k

    @pl.when(run)
    def _compute():
        q = q_ref[0, 0].astype(jnp.float32) * scale          # [bq, d]
        k = k_ref[0, 0].astype(jnp.float32)                  # [bk, d]
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32)  # [bq, bk]
        if causal:
            q_pos = i * block_q + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 0)
            k_pos = j * block_k + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 1)
            s = jnp.where(q_pos >= k_pos, s, _NEG_INF)

        m_prev = m_sc[:, 0:1]                                 # [bq, 1]
        m_blk = jnp.max(s, axis=-1, keepdims=True)
        m_new = jnp.maximum(m_prev, m_blk)
        p = jnp.exp(s - m_new)                                # [bq, bk]
        corr = jnp.exp(m_prev - m_new)                        # [bq, 1]
        l_new = l_sc[:, 0:1] * corr + jnp.sum(p, axis=-1, keepdims=True)
        pv = jax.lax.dot_general(p, v_ref[0, 0].astype(jnp.float32),
                                 (((1,), (0,)), ((), ())),
                                 preferred_element_type=jnp.float32)  # [bq, d]
        acc[:] = acc[:] * corr + pv
        m_sc[:] = jnp.broadcast_to(m_new, m_sc.shape)
        l_sc[:] = jnp.broadcast_to(l_new, l_sc.shape)

    @pl.when(j == nj - 1)
    def _finish():
        l = l_sc[:, 0:1]
        safe_l = jnp.maximum(l, 1e-30)
        o_ref[0, 0] = (acc[:] / safe_l).astype(o_ref.dtype)
        lse_ref[0, 0] = m_sc[:, 0:1] + jnp.log(safe_l)


def _flash_fwd(q, k, v, scale, causal, block_q, block_k, interpret):
    b, sq, hq, d = q.shape
    skv, hkv = k.shape[1], k.shape[2]
    n_rep = hq // hkv
    block_q = min(block_q, sq)
    block_k = min(block_k, skv)

    qt = q.transpose(0, 2, 1, 3)  # [B, H, S, D]
    kt = k.transpose(0, 2, 1, 3)
    vt = v.transpose(0, 2, 1, 3)

    grid = (b, hq, sq // block_q, skv // block_k)
    kernel = functools.partial(_fwd_kernel, scale=scale, causal=causal,
                               block_q=block_q, block_k=block_k)
    out, lse = pl.pallas_call(
        kernel,
        out_shape=(
            jax.ShapeDtypeStruct((b, hq, sq, d), q.dtype),
            jax.ShapeDtypeStruct((b, hq, sq, 1), jnp.float32),
        ),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1, block_q, d), lambda b_, h, i, j: (b_, h, i, 0)),
            pl.BlockSpec((1, 1, block_k, d), lambda b_, h, i, j: (b_, h // n_rep, j, 0)),
            pl.BlockSpec((1, 1, block_k, d), lambda b_, h, i, j: (b_, h // n_rep, j, 0)),
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


def supported(q, k, block_q, block_k) -> bool:
    """Shapes the kernel takes; dispatchers ask BEFORE the call."""
    b, sq, hq, d = q.shape
    skv, hkv = k.shape[1], k.shape[2]
    if hq % hkv:
        return False
    if sq % min(block_q, sq) or skv % min(block_k, skv):
        return False
    if d % 8:
        return False
    return True


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7))
def flash_attention(q, k, v, causal: bool = True, scale: float | None = None,
                    block_q: int = 256, block_k: int = 512,
                    interpret: bool | None = None):
    """Drop-in for ``ops.attention.xla_attention`` on TPU shapes.
    ``interpret=None`` interprets off the chip and compiles on it; a compile
    test for a described device passes ``False``."""
    return _fa_fwd(q, k, v, causal, scale, block_q, block_k, interpret)[0]


def _fa_fwd(q, k, v, causal, scale, block_q, block_k, interpret):
    scale = scale if scale is not None else 1.0 / math.sqrt(q.shape[-1])
    if not supported(q, k, block_q, block_k):
        raise NotImplementedError("flash_attention: unsupported shape")
    out, lse = _flash_fwd(q, k, v, scale, causal, block_q, block_k,
                          interpret_mode(interpret))
    return out, (q, k, v, out, lse)


def _dkdv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, dk_ref, dv_ref,
                 dk_acc, dv_acc, *, scale: float, causal: bool,
                 block_q: int, block_k: int):
    j = pl.program_id(2)  # kv block
    i = pl.program_id(3)  # q block (sequential innermost)
    ni = pl.num_programs(3)

    @pl.when(i == 0)
    def _init():
        dk_acc[:] = jnp.zeros_like(dk_acc)
        dv_acc[:] = jnp.zeros_like(dv_acc)

    run = True
    if causal:
        run = (i + 1) * block_q - 1 >= j * block_k

    @pl.when(run)
    def _compute():
        q = q_ref[0, 0].astype(jnp.float32)                  # [bq, d]
        k = k_ref[0, 0].astype(jnp.float32)                  # [bk, d]
        v = v_ref[0, 0].astype(jnp.float32)
        do = do_ref[0, 0].astype(jnp.float32)                # [bq, d]
        lse = lse_ref[0, 0]                                  # [bq, 1]
        delta = delta_ref[0, 0]                              # [bq, 1]

        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        if causal:
            q_pos = i * block_q + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 0)
            k_pos = j * block_k + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 1)
            s = jnp.where(q_pos >= k_pos, s, _NEG_INF)
        p = jnp.exp(s - lse)                                  # [bq, bk]
        dv_acc[:] = dv_acc[:] + jax.lax.dot_general(
            p, do, (((0,), (0,)), ((), ())), preferred_element_type=jnp.float32)
        dp = jax.lax.dot_general(do, v, (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)  # [bq, bk]
        ds = p * (dp - delta) * scale
        dk_acc[:] = dk_acc[:] + jax.lax.dot_general(
            ds, q, (((0,), (0,)), ((), ())), preferred_element_type=jnp.float32)

    @pl.when(i == ni - 1)
    def _finish():
        dk_ref[0, 0] = dk_acc[:].astype(dk_ref.dtype)
        dv_ref[0, 0] = dv_acc[:].astype(dv_ref.dtype)


def _dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, dq_ref, dq_acc,
               *, scale: float, causal: bool, block_q: int, block_k: int):
    i = pl.program_id(2)  # q block
    j = pl.program_id(3)  # kv block (sequential innermost)
    nj = pl.num_programs(3)

    @pl.when(j == 0)
    def _init():
        dq_acc[:] = jnp.zeros_like(dq_acc)

    run = True
    if causal:
        run = (i + 1) * block_q - 1 >= j * block_k

    @pl.when(run)
    def _compute():
        q = q_ref[0, 0].astype(jnp.float32)
        k = k_ref[0, 0].astype(jnp.float32)
        v = v_ref[0, 0].astype(jnp.float32)
        do = do_ref[0, 0].astype(jnp.float32)
        lse = lse_ref[0, 0]
        delta = delta_ref[0, 0]

        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        if causal:
            q_pos = i * block_q + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 0)
            k_pos = j * block_k + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 1)
            s = jnp.where(q_pos >= k_pos, s, _NEG_INF)
        p = jnp.exp(s - lse)
        dp = jax.lax.dot_general(do, v, (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        ds = p * (dp - delta) * scale
        dq_acc[:] = dq_acc[:] + jax.lax.dot_general(
            ds, k, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)

    @pl.when(j == nj - 1)
    def _finish():
        dq_ref[0, 0] = dq_acc[:].astype(dq_ref.dtype)


def _flash_bwd_pallas(q, k, v, out, lse, do, scale, causal, block_q, block_k,
                      interpret):
    b, sq, hq, d = q.shape
    skv, hkv = k.shape[1], k.shape[2]
    n_rep = hq // hkv
    block_q = min(block_q, sq)
    block_k = min(block_k, skv)

    delta = jnp.sum(do.astype(jnp.float32) * out.astype(jnp.float32), axis=-1)
    delta = delta.transpose(0, 2, 1)[..., None]               # [B, Hq, Sq, 1]
    lse4 = lse[..., None]                                     # [B, Hq, Sq, 1]

    qt = q.transpose(0, 2, 1, 3)
    kt = k.transpose(0, 2, 1, 3)
    vt = v.transpose(0, 2, 1, 3)
    dot = do.transpose(0, 2, 1, 3)

    q_spec_i = pl.BlockSpec((1, 1, block_q, d), lambda b_, h, i, j: (b_, h, i, 0))
    q_spec_j = pl.BlockSpec((1, 1, block_q, d), lambda b_, h, j, i: (b_, h, i, 0))
    kv_spec_i = pl.BlockSpec((1, 1, block_k, d), lambda b_, h, i, j: (b_, h // n_rep, j, 0))
    kv_spec_j = pl.BlockSpec((1, 1, block_k, d), lambda b_, h, j, i: (b_, h // n_rep, j, 0))
    row_spec_i = pl.BlockSpec((1, 1, block_q, 1), lambda b_, h, i, j: (b_, h, i, 0))
    row_spec_j = pl.BlockSpec((1, 1, block_q, 1), lambda b_, h, j, i: (b_, h, i, 0))

    # dk/dv: one [B, Hq, Skv, D] buffer per q-head group, reduced below for GQA
    dk_h, dv_h = pl.pallas_call(
        functools.partial(_dkdv_kernel, scale=scale, causal=causal,
                          block_q=block_q, block_k=block_k),
        out_shape=(
            jax.ShapeDtypeStruct((b, hq, skv, d), jnp.float32),
            jax.ShapeDtypeStruct((b, hq, skv, d), jnp.float32),
        ),
        grid=(b, hq, skv // block_k, sq // block_q),
        in_specs=[q_spec_j, kv_spec_j, kv_spec_j, q_spec_j, row_spec_j, row_spec_j],
        out_specs=(
            pl.BlockSpec((1, 1, block_k, d), lambda b_, h, j, i: (b_, h, j, 0)),
            pl.BlockSpec((1, 1, block_k, d), lambda b_, h, j, i: (b_, h, j, 0)),
        ),
        scratch_shapes=[_scratch((block_k, d)), _scratch((block_k, d))],
        interpret=interpret,
        name="flash_bwd_dkv",
    )(qt, kt, vt, dot, lse4, delta)

    dq = pl.pallas_call(
        functools.partial(_dq_kernel, scale=scale, causal=causal,
                          block_q=block_q, block_k=block_k),
        out_shape=jax.ShapeDtypeStruct((b, hq, sq, d), q.dtype),
        grid=(b, hq, sq // block_q, skv // block_k),
        in_specs=[q_spec_i, kv_spec_i, kv_spec_i, q_spec_i, row_spec_i, row_spec_i],
        out_specs=pl.BlockSpec((1, 1, block_q, d), lambda b_, h, i, j: (b_, h, i, 0)),
        scratch_shapes=[_scratch((block_q, d))],
        interpret=interpret,
        name="flash_bwd_dq",
    )(qt, kt, vt, dot, lse4, delta)

    dq = dq.transpose(0, 2, 1, 3)
    dk = dk_h.transpose(0, 2, 1, 3)
    dv = dv_h.transpose(0, 2, 1, 3)
    if n_rep > 1:
        dk = dk.reshape(b, skv, hkv, n_rep, d).sum(axis=3)
        dv = dv.reshape(b, skv, hkv, n_rep, d).sum(axis=3)
    return dq.astype(q.dtype), dk.astype(k.dtype), dv.astype(v.dtype)


def _fa_bwd_xla(causal, scale, block_q, block_k, res, do):
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


def _fa_bwd(causal, scale, block_q, block_k, interpret, res, do):
    if os.environ.get("DSTPU_FLASH_XLA_BWD"):
        return _fa_bwd_xla(causal, scale, block_q, block_k, res, do)
    q, k, v, out, lse = res
    scale = scale if scale is not None else 1.0 / math.sqrt(q.shape[-1])
    return _flash_bwd_pallas(q, k, v, out, lse, do, scale, causal, block_q,
                             block_k, interpret_mode(interpret))


flash_attention.defvjp(_fa_fwd, _fa_bwd)
