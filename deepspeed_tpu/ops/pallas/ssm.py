"""Pallas decode step of a Mamba-2 layer: one state update a row, the row's
state read from HBM once and written back once, in place.

A sequence's state is ``S`` ``[N, H x P]`` float32 (``N`` the state size on
the sublanes, a head's ``P`` values side by side on the lanes, the heads of a
group side by side: ``G`` groups of ``Q = H x P / G`` lanes). One row's step
is

    S'[n, q] = dA[q] S[n, q] + B[n, g(q)] dtx[q]        y[q] = sum_n S'[n, q] C[n, g(q)]

with ``dA = exp(dt A)`` and ``dtx = dt x`` a head, spread over the head's
lanes by the caller. ``G`` is read off ``bt`` (``[T, N, G]``) and may be 1
(``granite_hybrid``: one ``B`` and one ``C`` for all the heads): the kernel's
loop over the groups is then one trip over all ``H x P`` lanes, a ``[N, 1]``
column broadcast over 8,192 of them where Nemotron-3's eight groups take 1,024
each (``tests/unit/test_compile_tpu.py`` compiles both for the chip,
``tests/unit/test_granite_hybrid.py`` holds one group to the XLA form). The states of all layers and slots lie in ONE array
``[rows, N, H x P]`` (``models/paged.py``: the slot leaves, layers and slots
merged); ``rows[r]`` is where row ``r``'s state lies. The grid is the step's
rows: the pipeline brings a row's whole state (4 MB at Nemotron-3's 128 x
8,192) while the row before is computed and the one before that leaves, and
the output aliases the input, so the step's traffic is its rows' states once
each way and nothing of the array's own size is ever made.

XLA's form of the same step (``ssm_decode_xla``: gather, update, scatter)
makes the gathered rows and the updated rows as arrays of their own and so
moves every byte twice more; ``chip_smoke.py`` compares the two, PERF.md
section 6 (PR 31) has the times.

Three recurrences keep their state this way (the state size or key channels on
the sublanes, what is read out side by side on the lanes, all layers and slots
in one array, a row a grid step, in place): Mamba-2 here, one decay a lane
handed in by the caller; Kimi Delta Attention in ``ops/pallas/kda.py``, a
decay a key channel and the delta rule; Mamba-1 in ``ops/pallas/selscan.py``,
a decay a channel AND state index, ``exp(dt (x) A)``, which that kernel
computes itself from the row's ``dt`` and the layer's resident ``A`` (as
``da`` here it would be a second array the state's size through HBM) and
whose prefill tiles are a scan and not a chunk of matmuls.

Padding rows all name the scratch slot. Their ``dA`` is 1 and their ``dtx`` 0
(the caller masks ``dt``), so whatever order the pipeline reads and writes
the scratch row in, it stays what it was.

Inference-only (no VJP).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from deepspeed_tpu.ops.pallas.flash_attention import interpret_mode

# a row's state in and out, double-buffered, is 16 MB at 128 x 8,192 float32
_VMEM_LIMIT_BYTES = 64 * 2**20


def _kernel(rows_ref, s_ref, da_ref, dtx_ref, bt_ref, ct_ref, o_ref, y_ref, *,
            groups: int):
    del rows_ref  # the state's index maps read it
    q = s_ref.shape[2] // groups
    group_of_lane = jax.lax.broadcasted_iota(jnp.int32, bt_ref.shape[1:], 1)

    def group(g, carry):
        # a loop, not ``groups`` copies of its body: the kernel is compiled
        # again in every step program that has decode rows
        lanes = pl.ds(pl.multiple_of(g * q, q), q)
        mine = group_of_lane == g
        b = jnp.sum(jnp.where(mine, bt_ref[0], 0.0), axis=1, keepdims=True)
        c = jnp.sum(jnp.where(mine, ct_ref[0], 0.0), axis=1, keepdims=True)
        new = s_ref[0, :, lanes] * da_ref[0, :, lanes] + b * dtx_ref[0, :, lanes]
        o_ref[0, :, lanes] = new
        y_ref[0, :, lanes] = jnp.sum(new * c, axis=0, keepdims=True)
        return carry

    jax.lax.fori_loop(0, groups, group, 0)


def ssm_decode(state, rows, da, dtx, bt, ct, interpret: bool | None = None):
    """``state`` [R, N, HP] float32, ``rows`` [T] int32 (distinct, but for
    rows whose ``da`` is 1 and ``dtx`` 0), ``da`` / ``dtx`` [T, HP], ``bt`` /
    ``ct`` [T, N, G] (a row's ``B`` and ``C``, state size first) ->
    ``(state, y [T, HP])``: the module doc's step on ``state[rows]``, in
    place. ONE jitted function: every step program of an engine takes the
    kernel as first traced."""
    return _ssm_decode(state, rows.astype(jnp.int32), da, dtx, bt, ct,
                       interpret=interpret_mode(interpret))


@functools.partial(jax.jit, static_argnames=("interpret",))
def _ssm_decode(state, rows, da, dtx, bt, ct, *, interpret: bool):
    _, n, hp = state.shape
    t, _, groups = bt.shape
    f32 = jnp.float32
    row = lambda r, rows: (r, 0, 0)  # noqa: E731
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(t,),
        in_specs=[
            pl.BlockSpec((1, n, hp), lambda r, rows: (rows[r], 0, 0)),
            pl.BlockSpec((1, 1, hp), row),
            pl.BlockSpec((1, 1, hp), row),
            pl.BlockSpec((1, n, groups), row),
            pl.BlockSpec((1, n, groups), row),
        ],
        out_specs=[
            pl.BlockSpec((1, n, hp), lambda r, rows: (rows[r], 0, 0)),
            pl.BlockSpec((1, 1, hp), row),
        ],
    )
    state, y = pl.pallas_call(
        functools.partial(_kernel, groups=groups),
        out_shape=[jax.ShapeDtypeStruct(state.shape, f32),
                   jax.ShapeDtypeStruct((t, 1, hp), f32)],
        grid_spec=grid_spec,
        # operand 0 is the prefetched ``rows``: the state is operand 1
        input_output_aliases={1: 0},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=_VMEM_LIMIT_BYTES),
        interpret=interpret,
        name="ssm_decode",
    )(rows, state, da.astype(f32)[:, None], dtx.astype(f32)[:, None],
      bt.astype(f32), ct.astype(f32))
    return state, y[:, 0]


def ssm_decode_xla(state, rows, da, dtx, bt, ct):
    """The same step as XLA writes it: gather the rows' states, update,
    scatter. The kernel's yardstick (tests, ``chip_smoke.py``)."""
    groups = bt.shape[2]
    f32 = jnp.float32

    def lanes(a):  # [T, N, G] -> [T, N, HP]
        return jnp.repeat(a.astype(f32), state.shape[2] // groups, axis=2)

    new = (state[rows] * da.astype(f32)[:, None]
           + lanes(bt) * dtx.astype(f32)[:, None])
    return state.at[rows].set(new), jnp.sum(new * lanes(ct), axis=1)
