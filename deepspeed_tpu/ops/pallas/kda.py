"""Pallas decode step of a Kimi Delta Attention (KDA) layer: one delta-rule
update a row, the row's state read from HBM once and written back once, in
place.

A sequence's state is ``S`` ``[K, H x V]`` float32: a head's ``K`` key
channels on the sublanes, its ``V`` values side by side on the lanes, the
heads side by side (the layout ``ops/pallas/ssm.py`` argues for; at
Kimi-Linear's 128 x 32 x 128 a row is 2 MB). One row's step, a head ``h``
(``a = exp(g)`` the decay A CHANNEL, ``k``, ``q`` [K] and ``v`` [V] the head's
key, query and value, ``b`` its ``beta``):

    D[k, v]  = a[k] S[k, v]                    # decay each key channel by its own factor
    u[v]     = b (v[v] - sum_k D[k, v] k[k])   # the delta rule reads the DECAYED state first
    S'[k, v] = D[k, v] + k[k] u[v]
    y[v]     = sum_k S'[k, v] q[k]

This is not ``ssm_decode`` with other numbers: Mamba-2 decays a head by one
scalar and feeds the state an outer product that does not depend on it; here
the decay is a column a head and the fed row ``u`` is read off the state it
is about to be added to, so the two contractions over ``K`` (sublane sums)
bracket the write. The per-channel operands come ``[K, H]`` a row (channels
on the sublanes, as the state has them), and a head's column is spread over
its 128 lanes by a masked lane sum, as ``ssm_decode`` spreads a group's ``B``.

The states of all layers and slots lie in ONE array ``[rows, K, H x V]``
(``models/paged.py``: the slot leaves, layers and slots merged); ``rows[r]``
is where row ``r``'s state lies. The grid is the step's rows and the output
aliases the input, so the step's traffic is its rows' states once each way.

Padding rows all name the scratch slot with ``a = 1`` and ``b = 0``: ``u`` is
then 0 and the row is written back as it was read, whatever order the
pipeline takes them in. A row at position 0 starts from zeros whatever its
slot held: the caller hands it ``a = 0``.

XLA's form of the same step (``kda_decode_xla``: gather, update, scatter) is
what runs off the chip and what ``chip_smoke.py`` times the kernel against
(PERF.md section 6, PR 40).

``state_rows_read`` / ``state_rows_write`` move a prefill tile's state out of
that array and back, one row a grid step, the write in place. They compute
nothing; they are kernels because a kernel's operand has ONE layout. A step
program with prefill tiles and no decode row holds no ``kda_decode``, and left
to itself XLA then lays the WHOLE array out to suit the chunk form's matmuls
(key channels on the lanes): 2.7 GB copied in and copied back, every step, at
the benchmark's 10 layers x 129 slots (the compiled program, PR 40; PR 31 met
the same re-layout under a gather). Off the chip they are a dynamic slice
and a dynamic-update-slice a row.

Inference-only (no VJP).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from deepspeed_tpu.ops.pallas.flash_attention import interpret_mode

# a row's state in and out, double-buffered, is 8 MB at 128 x 4,096 float32
_VMEM_LIMIT_BYTES = 48 * 2**20


def _kernel(rows_ref, s_ref, a_ref, k_ref, q_ref, v_ref, b_ref, o_ref, y_ref,
            *, heads: int):
    del rows_ref  # the state's index maps read it
    vdim = s_ref.shape[2] // heads
    head_of_lane = jax.lax.broadcasted_iota(jnp.int32, a_ref.shape[1:], 1)

    def head(h, carry):
        # a loop, not ``heads`` copies of its body: the kernel is compiled
        # again in every step program that has decode rows
        lanes = pl.ds(pl.multiple_of(h * vdim, vdim), vdim)
        mine = head_of_lane == h

        def column(ref):  # [K, H] -> head h's [K, 1]
            return jnp.sum(jnp.where(mine, ref[0], 0.0), axis=1, keepdims=True)

        kcol = column(k_ref)
        decayed = s_ref[0, :, lanes] * column(a_ref)
        u = b_ref[0, :, lanes] * (
            v_ref[0, :, lanes] - jnp.sum(decayed * kcol, axis=0, keepdims=True))
        new = decayed + kcol * u
        o_ref[0, :, lanes] = new
        y_ref[0, :, lanes] = jnp.sum(new * column(q_ref), axis=0, keepdims=True)
        return carry

    jax.lax.fori_loop(0, heads, head, 0)


def kda_decode(state, rows, a, k, q, v, beta, impl: str = "auto",
               interpret: bool | None = None):
    """``state`` [R, K, HV] float32, ``rows`` [T] int32 (distinct, but for
    rows whose ``a`` is 1 and ``beta`` 0), ``a`` / ``k`` / ``q`` [T, K, H]
    (a row's decay, key and query, channels first), ``v`` / ``beta`` [T, HV]
    (``beta`` spread over its head's lanes) -> ``(state, y [T, HV])``: the
    module doc's step on ``state[rows]``, in place. ``impl``: ``"pallas"``,
    ``"xla"``, or ``"auto"`` (the kernel on the chip, XLA's form off it)."""
    if not _on_chip(impl):
        return kda_decode_xla(state, rows, a, k, q, v, beta)
    return _kda_decode(state, rows.astype(jnp.int32), a, k, q, v, beta,
                       interpret=interpret_mode(interpret))


@functools.partial(jax.jit, static_argnames=("interpret",))
def _kda_decode(state, rows, a, k, q, v, beta, *, interpret: bool):
    """ONE jitted function: every step program of an engine takes the kernel
    as first traced."""
    _, kdim, hv = state.shape
    t, _, heads = a.shape
    f32 = jnp.float32
    row = lambda r, rows: (r, 0, 0)  # noqa: E731
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(t,),
        in_specs=[
            pl.BlockSpec((1, kdim, hv), lambda r, rows: (rows[r], 0, 0)),
            pl.BlockSpec((1, kdim, heads), row),
            pl.BlockSpec((1, kdim, heads), row),
            pl.BlockSpec((1, kdim, heads), row),
            pl.BlockSpec((1, 1, hv), row),
            pl.BlockSpec((1, 1, hv), row),
        ],
        out_specs=[
            pl.BlockSpec((1, kdim, hv), lambda r, rows: (rows[r], 0, 0)),
            pl.BlockSpec((1, 1, hv), row),
        ],
    )
    state, y = pl.pallas_call(
        functools.partial(_kernel, heads=heads),
        out_shape=[jax.ShapeDtypeStruct(state.shape, f32),
                   jax.ShapeDtypeStruct((t, 1, hv), f32)],
        grid_spec=grid_spec,
        # operand 0 is the prefetched ``rows``: the state is operand 1
        input_output_aliases={1: 0},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=_VMEM_LIMIT_BYTES),
        interpret=interpret,
        name="kda_decode",
    )(rows, state, a.astype(f32), k.astype(f32), q.astype(f32),
      v.astype(f32)[:, None], beta.astype(f32)[:, None])
    return state, y[:, 0]


def kda_decode_xla(state, rows, a, k, q, v, beta):
    """The same step as XLA writes it: gather the rows' states, update,
    scatter. What runs off the chip, and the kernel's yardstick (tests,
    ``chip_smoke.py``)."""
    f32 = jnp.float32
    t, kdim, heads = a.shape

    def heads_last(x):  # [T, HV] -> [T, 1, H, V]
        return x.astype(f32).reshape(t, 1, heads, -1)

    def column(x):      # [T, K, H] -> [T, K, H, 1]
        return x.astype(f32)[..., None]

    decayed = state[rows].reshape(t, kdim, heads, -1) * column(a)
    u = heads_last(beta) * (
        heads_last(v) - jnp.sum(decayed * column(k), axis=1, keepdims=True))
    new = decayed + column(k) * u
    y = jnp.sum(new * column(q), axis=1)
    return (state.at[rows].set(new.reshape(t, kdim, -1)),
            y.reshape(t, -1))


def _copy_kernel(rows_ref, *refs):
    del rows_ref  # the index maps read it
    src_ref, dst_ref = refs[-2:]
    dst_ref[...] = src_ref[...]


def _on_chip(impl: str) -> bool:
    if impl not in ("auto", "pallas", "xla"):
        raise ValueError(f"kda: impl {impl!r} (auto, pallas, xla)")
    return impl == "pallas" or (impl == "auto"
                                and jax.default_backend() == "tpu")


def state_rows_read(state, rows, impl: str = "auto",
                    interpret: bool | None = None):
    """``state`` [R, K, HV], ``rows`` [I] int32 -> ``state[rows]`` [I, K, HV],
    a row a grid step (module doc)."""
    if not _on_chip(impl):
        return jnp.stack([jax.lax.dynamic_index_in_dim(state, rows[i], 0, False)
                          for i in range(rows.shape[0])])
    _, kdim, hv = state.shape
    n = rows.shape[0]
    return pl.pallas_call(
        _copy_kernel,
        out_shape=jax.ShapeDtypeStruct((n, kdim, hv), state.dtype),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1, grid=(n,),
            in_specs=[pl.BlockSpec((1, kdim, hv),
                                   lambda i, rows: (rows[i], 0, 0))],
            out_specs=pl.BlockSpec((1, kdim, hv), lambda i, rows: (i, 0, 0))),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=_VMEM_LIMIT_BYTES),
        interpret=interpret_mode(interpret),
        name="kda_state_read",
    )(rows.astype(jnp.int32), state)


def state_rows_write(state, rows, new, impl: str = "auto",
                     interpret: bool | None = None):
    """``state`` [R, K, HV] with ``state[rows[i]] = new[i]`` in order (a row
    named twice keeps the later), in place: the output aliases ``state`` and
    no other row is touched."""
    if not _on_chip(impl):
        for i in range(rows.shape[0]):
            state = jax.lax.dynamic_update_index_in_dim(state, new[i],
                                                        rows[i], 0)
        return state
    _, kdim, hv = state.shape
    n = rows.shape[0]
    return pl.pallas_call(
        _copy_kernel,
        out_shape=jax.ShapeDtypeStruct(state.shape, state.dtype),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1, grid=(n,),
            in_specs=[pl.BlockSpec(memory_space=pl.ANY),
                      pl.BlockSpec((1, kdim, hv), lambda i, rows: (i, 0, 0))],
            out_specs=pl.BlockSpec((1, kdim, hv),
                                   lambda i, rows: (rows[i], 0, 0))),
        # operand 0 is the prefetched ``rows``: the state is operand 1
        input_output_aliases={1: 0},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=_VMEM_LIMIT_BYTES),
        interpret=interpret_mode(interpret),
        name="kda_state_write",
    )(rows.astype(jnp.int32), state, new.astype(state.dtype))
