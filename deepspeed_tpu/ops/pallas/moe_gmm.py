"""Pallas grouped expert FFN: each expert's weights over that expert's own
rows only, read from HBM once a layer. The expert is gated (three matrices,
``silu(x w_gate) * (x w_up)`` into ``w_down``: Mixtral, DeepSeek-V3; or with
``gate_act="relu"`` ``relu(x w_gate) * (x w_up)``: SmallThinker) or
ungated (two, ``relu(x w_up)**2`` into ``w_down``: Nemotron-H's latent
experts; one weight operand fewer, the same walk).

The rows (the ``T x top_k`` picks of a serving step) arrive grouped by expert:
expert ``e`` owns rows ``row0[e] .. row0[e] + n_e`` of ``x``, ``row0`` a
multiple of ``ROW_ALIGN``. The grid is ``(experts, ffn tiles)``. Per step the
pipeline brings ONE tile of the expert's three weights (``w_gate[e, :, j]``,
``w_up[e, :, j]``, ``w_down[e, j, :]``) and the kernel runs the expert's rows
through it, ``tm`` rows a pass: ``silu(x w_gate) * (x w_up)`` in float32, the
product with ``w_down`` accumulated in float32 over the ffn tiles. So a weight
byte crosses HBM once whatever the number of rows, which is what bounds the
layer at serving row counts (~100 rows an expert at Mixtral's geometry, ~50 at
Moonlight's: far under the MXU's ridge point); a grid over row tiles would
fetch a weight tile again for every row tile that touches the expert.

The rows live in HBM and are copied by hand, an expert's ``ceil(n_e / tm)``
passes at a time: the next expert's rows are in flight while this one is
computed, and an expert's results leave while the next one is computed. A pass
always moves ``tm`` rows, so the last pass of an expert reads and writes past
the expert's end, into the rows of the experts after it (and, for the last
one, into ``tm`` spare rows the caller appends). What is read there is
ignored. What is written there is overwritten: an expert's results are only
sent once the expert before it has landed, and every later expert writes its
own rows later.

Inference-only (no VJP).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from deepspeed_tpu.ops.pallas.flash_attention import interpret_mode

# an expert's first row is a multiple of this: whole sublane tiles of a
# bfloat16 (16 rows) and of a float32 (8 rows) array, so a pass is an aligned
# slice of both ``x`` and the result
ROW_ALIGN = 16
# the three weight tiles of a grid step, double-buffered by the pipeline, may
# take this much VMEM (of 128 MiB on v5e): 512 of Mixtral's 14336 ffn columns
# (25 MB), all 1408 of Moonlight's (35 MB)
_WEIGHT_TILE_BYTES = 36 * 2**20
_VMEM_LIMIT_BYTES = 100 * 2**20


def ffn_tile(d: int, f: int, itemsize: int, matrices: int = 3) -> int:
    """The ffn columns a grid step takes: all of them where they fit
    ``_WEIGHT_TILE_BYTES``, else the widest divisor of ``f`` that does and is
    whole 128-lane tiles. ``matrices``: an expert's weight matrices (3 gated,
    2 ungated)."""
    def fits(tf):
        return matrices * 2 * d * tf * itemsize <= _WEIGHT_TILE_BYTES

    if fits(f):
        return f
    tiles = [tf for tf in range(128, f, 128) if f % tf == 0 and fits(tf)]
    if not tiles:
        raise ValueError(f"no ffn tile of [{d}, {f}] weights fits VMEM")
    return tiles[-1]


def _kernel(first_ref, row0_ref, passes_ref, x_hbm, *refs, tm: int,
            gated: bool, gate_act: str = "silu"):
    del first_ref  # the weights' index maps read it
    wg_ref = refs[0] if gated else None
    wu_ref, wd_ref, o_hbm, xbuf, acc, in_sem, out_sem = refs[gated:]
    e, j = pl.program_id(0), pl.program_id(1)
    n_e, n_j = pl.num_programs(0), pl.num_programs(1)
    slot = e % 2
    n = passes_ref[e]

    def rows(ex, c):
        return pl.ds(pl.multiple_of(row0_ref[ex] + c * tm, ROW_ALIGN), tm)

    def rows_in(ex, c, sl):
        return pltpu.make_async_copy(x_hbm.at[rows(ex, c)], xbuf.at[sl, c],
                                     in_sem.at[sl])

    def rows_out(ex, c, sl):
        return pltpu.make_async_copy(acc.at[sl, c], o_hbm.at[rows(ex, c)],
                                     out_sem.at[0])

    def each(count, fn):
        def body(c, carry):
            fn(c)
            return carry

        jax.lax.fori_loop(0, count, body, 0)

    @pl.when(j == 0)
    def _rows():
        @pl.when(e == 0)
        def _first():
            each(n, lambda c: rows_in(0, c, 0).start())

        each(n, lambda c: rows_in(e, c, slot).wait())

        @pl.when(e + 1 < n_e)
        def _next():
            each(passes_ref[e + 1],
                 lambda c: rows_in(e + 1, c, 1 - slot).start())

    def one_pass(c, first):
        x = xbuf[slot, c]
        if gated:
            g = jnp.dot(x, wg_ref[0], preferred_element_type=jnp.float32)
            u = jnp.dot(x, wu_ref[0], preferred_element_type=jnp.float32)
            a = (jax.nn.relu(g) if gate_act == "relu" else jax.nn.silu(g)) * u
        else:
            a = jnp.square(jax.nn.relu(
                jnp.dot(x, wu_ref[0], preferred_element_type=jnp.float32)))
        y = jnp.dot(a.astype(x.dtype), wd_ref[0],
                    preferred_element_type=jnp.float32)
        acc[slot, c] = y if first else acc[slot, c] + y

    @pl.when(j == 0)
    def _set():
        each(n, lambda c: one_pass(c, True))

    @pl.when(j > 0)
    def _add():
        each(n, lambda c: one_pass(c, False))

    @pl.when(j == n_j - 1)
    def _results():
        # in order (module doc): the expert before has landed before this
        # one's results leave
        @pl.when(e > 0)
        def _before():
            each(passes_ref[e - 1],
                 lambda c: rows_out(e - 1, c, 1 - slot).wait())

        each(n, lambda c: rows_out(e, c, slot).start())

        @pl.when(e == n_e - 1)
        def _last():
            each(n, lambda c: rows_out(e, c, slot).wait())


def grouped_swiglu(x, w_gate, w_up, w_down, row0, counts, tm: int,
                   max_rows: int, first_expert=0,
                   interpret: bool | None = None, gate_act: str = "silu"):
    """``x`` [R, D], rows grouped by expert (``row0`` [E] the experts' first
    rows, multiples of ``ROW_ALIGN``; ``counts`` [E] their rows; no expert
    has more than ``max_rows``; ``R`` at least the last expert's end rounded
    up to ``ROW_ALIGN`` plus ``tm``) through ``w_gate`` / ``w_up`` [N, D, F]
    and ``w_down`` [N, F, D] -> float32 [R, D]: row ``r`` of expert ``e`` is
    ``(silu(x[r] w_gate[e]) * (x[r] w_up[e])) w_down[e]``; the rows between
    the experts hold nothing meant. ``w_gate`` None is the ungated expert of
    two matrices, ``relu(x[r] w_up[e])**2 w_down[e]``: the same walk with one
    weight operand fewer. ``gate_act="relu"`` (static) gates by ``relu`` in
    ``silu``'s place.

    The ``E`` experts are ``first_expert .. first_expert + E - 1`` of the
    ``N`` the weights hold: a layer scan hands the kernel every layer's
    experts as they lie in HBM (``[L x E, ...]``) and the layer's offset,
    because a scan's slice of them would be copied to become a kernel's
    operand (the layer's whole expert weights, every layer of every step).

    The call is ONE jitted function, so a step program that calls it with
    shapes another program has used takes the kernel as traced then: tracing
    it costs ~0.4 s on a serving host, an engine has a step program for every
    row count, and its set-up traces them all."""
    return _grouped_swiglu(
        x, w_gate, w_up, w_down, row0, counts,
        jnp.asarray(first_expert, jnp.int32).reshape(1), tm=tm,
        max_rows=max_rows, interpret=interpret_mode(interpret),
        gate_act=gate_act)


@functools.partial(jax.jit, static_argnames=("tm", "max_rows", "interpret",
                                             "gate_act"))
def _grouped_swiglu(x, w_gate, w_up, w_down, row0, counts, first_expert, *,
                    tm: int, max_rows: int, interpret: bool,
                    gate_act: str = "silu"):
    if gate_act not in ("silu", "relu"):
        raise ValueError(f"unknown expert gate activation {gate_act!r}")
    gated = w_gate is not None
    _, d, f = w_up.shape
    n_e = row0.shape[0]
    tf = ffn_tile(d, f, x.dtype.itemsize, 3 if gated else 2)
    passes = pl.cdiv(max_rows, tm)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(n_e, f // tf),
        in_specs=[
            pl.BlockSpec(memory_space=pl.ANY),
            *[pl.BlockSpec((1, d, tf),
                           lambda e, j, e0, r0, n: (e0[0] + e, 0, j))] * (1 + gated),
            pl.BlockSpec((1, tf, d), lambda e, j, e0, r0, n: (e0[0] + e, j, 0)),
        ],
        out_specs=pl.BlockSpec(memory_space=pl.ANY),
        scratch_shapes=[
            pltpu.VMEM((2, passes, tm, d), x.dtype),
            pltpu.VMEM((2, passes, tm, d), jnp.float32),
            pltpu.SemaphoreType.DMA((2,)),
            pltpu.SemaphoreType.DMA((1,)),
        ],
    )
    return pl.pallas_call(
        functools.partial(_kernel, tm=tm, gated=gated, gate_act=gate_act),
        out_shape=jax.ShapeDtypeStruct(x.shape, jnp.float32),
        grid_spec=grid_spec,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"),
            vmem_limit_bytes=_VMEM_LIMIT_BYTES),
        interpret=interpret,
        name="moe_gmm",
    )(first_expert, row0.astype(jnp.int32),
      ((counts + tm - 1) // tm).astype(jnp.int32), x,
      *((w_gate,) if gated else ()), w_up, w_down)
