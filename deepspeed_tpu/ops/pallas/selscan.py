"""Pallas kernels of a Mamba-1 layer's selective scan: the decode row's state
update (``selscan_decode``) and the scan over a prefill tile's rows with the
state resident (``selscan_tile``).

A sequence's state is ``S`` ``[N, C]`` float32 (``N`` the state size on the
sublanes, the ``C = d_inner`` channels on the lanes: ``ops/pallas/ssm.py``'s
layout rule, one channel a lane where Mamba-2 has a head's ``P`` values side
by side). One token's step is

    S'[n, c] = exp(dt[c] A[n, c]) S[n, c] + B[n] dt[c] x[c]      y[c] = sum_n S'[n, c] C[n]

The decay is a value for every channel AND state index. Mamba-2's is one
scalar a head, so its decay over a chunk factors out of the state and a chunk
is matmuls (``models/mamba2.ssd_tiles``); KDA's is one a key channel, which
factors too. This one does not: the sum over a chunk's rows of ``exp(sum of dt
A)`` weighs every ``(n, c)`` differently, so a tile's rows go through the
recurrence one after the other, and what a kernel can do is keep the state
where the arithmetic is.

**Decode** (``selscan_decode``). The states of all layers and slots lie in ONE
array ``[rows, N, C]`` (``models/paged.py``: slot leaves, layers and slots
merged); ``rows[r]`` is where row ``r``'s lies. The grid is the step's rows:
a row's whole state comes from HBM once (327 KB at 16 x 5,120), is decayed by
``exp(dt (x) A)`` COMPUTED HERE from the row's ``dt`` [C] and the layer's ``A``
[N, C] (resident: its index map is constant), fed, read against ``C`` and
written back in place (the output aliases the input). Handed the decay as
``ssm_decode`` is, a row would bring 82 K floats of it through HBM beside a
state of the same size. A ``fresh`` row (position 0) starts from zeros
whatever its slot held. Padding rows name the scratch slot with ``dt = 0``:
decay 1, feed 0, so whatever order the pipeline reads and writes the scratch
row in, it stays what it was.

**Tiles** (``selscan_tile``). A step's ``I`` prefill tiles of ``R`` rows each.
The grid is channel blocks (outer) x tiles (inner, in order): a block of the
state ``[N, cb]`` is a few registers' worth, carried through the tile's ``R``
rows as a loop's value and from tile to tile of a slot in VMEM. Tile ``i``
starts from what tile ``i - 1`` ended with if it ``cont``inues that tile's
slot, from zeros if it is ``fresh`` (position 0), else from the state at
``rows[i]``; it writes what it ends with to ``rows_w[i]`` where ``write[i]``,
else zeros (a tile that is not its slot's last of the step and a padding tile
name the scratch slot there): ``models/mamba2.ragged``'s rules. ``dt = 0`` on
a tile's rows past its valid ones, so they neither decay nor feed the state.
The kernel moves a tile's ``x``, ``dt``, ``B``, ``C`` in and ``y`` out and its
slot's state once each way; its time is the vector unit's (one ``exp`` and
seven FLOPs a state update, ``R x N x C`` updates a tile), not the MXU's and
not HBM's.

Each has an XLA form (``selscan_decode_xla``: gather, update, scatter;
``selscan_tile_xla``: a ``lax.scan`` over a tile's rows between dynamic slices
of its state), which is what runs off the chip and what the tests and
``chip_smoke.py`` hold the kernels to.

Inference-only (no VJP).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from deepspeed_tpu.ops.pallas.flash_attention import interpret_mode

# a tile's dt, x and y blocks, double-buffered, pass the default 16 MB
_VMEM_LIMIT_BYTES = 64 * 2**20
# the channels of one grid step of ``selscan_tile``: [16, 1280] float32 is 20
# registers of state carried through the rows' loop
_TILE_LANES = 1280
_ROWS_UNROLLED = 16


def _on_chip(impl: str) -> bool:
    if impl not in ("auto", "pallas", "xla"):
        raise ValueError(f"selscan: impl {impl!r} (auto, pallas, xla)")
    return impl == "pallas" or (impl == "auto"
                                and jax.default_backend() == "tpu")


# ------------------------------------------------------------------ decode
def _decode_kernel(rows_ref, fresh_ref, s_ref, a_ref, dt_ref, x_ref, bc_ref,
                   o_ref, y_ref):
    del rows_ref  # the state's index maps read it
    r = pl.program_id(0)
    n = a_ref.shape[0]
    dt = dt_ref[0]                                               # [1, C]
    # the row's B and C come side by side on the lanes ([1, 2N]: handed in
    # as columns, [T, N, 1] each, they are padded to whole tiles in HBM, 128
    # times their size); the diagonal of their broadcast is the column
    bc = jnp.broadcast_to(bc_ref[0], (2 * n, 2 * n))
    eye = (jax.lax.broadcasted_iota(jnp.int32, bc.shape, 0)
           == jax.lax.broadcasted_iota(jnp.int32, bc.shape, 1))
    col = jnp.sum(jnp.where(eye, bc, 0.0), axis=1, keepdims=True)  # [2N, 1]
    s = jnp.where(fresh_ref[r] > 0, 0.0, s_ref[0])               # [N, C]
    new = jnp.exp(dt * a_ref[...]) * s + col[:n] * (dt * x_ref[0])
    o_ref[0] = new
    y_ref[0] = jnp.sum(new * col[n:], axis=0, keepdims=True)


def selscan_decode(state, rows, fresh, dt, x, a, b, c, impl: str = "auto",
                   interpret: bool | None = None):
    """``state`` [R, N, C] float32, ``rows`` [T] int32 (distinct, but for rows
    whose ``dt`` is 0), ``fresh`` [T] bool (the row starts from zeros),
    ``dt`` [T, C] float32 (after the softplus; 0 on padding rows), ``x`` [T,
    C], ``a`` [N, C] float32 (negative), ``b`` / ``c`` [T, N] -> ``(state, y
    [T, C] float32)``: the module doc's step on ``state[rows]``, in place.
    ``impl``: ``auto`` (the kernel on the chip, the XLA form off it),
    ``pallas``, ``xla``."""
    if not _on_chip(impl):
        return selscan_decode_xla(state, rows, fresh, dt, x, a, b, c)
    return _selscan_decode(state, rows.astype(jnp.int32),
                           fresh.astype(jnp.int32), dt, x, a, b, c,
                           interpret=interpret_mode(interpret))


@functools.partial(jax.jit, static_argnames=("interpret",))
def _selscan_decode(state, rows, fresh, dt, x, a, b, c, *, interpret: bool):
    """ONE jitted function: every step program of an engine takes the kernel
    as first traced."""
    _, n, ch = state.shape
    t = dt.shape[0]
    f32 = jnp.float32
    row = lambda r, *_: (r, 0, 0)  # noqa: E731
    where = lambda r, rows, *_: (rows[r], 0, 0)  # noqa: E731
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(t,),
        in_specs=[
            pl.BlockSpec((1, n, ch), where),
            pl.BlockSpec((n, ch), lambda r, *_: (0, 0)),
            pl.BlockSpec((1, 1, ch), row),
            pl.BlockSpec((1, 1, ch), row),
            pl.BlockSpec((1, 1, 2 * n), row),
        ],
        out_specs=[
            pl.BlockSpec((1, n, ch), where),
            pl.BlockSpec((1, 1, ch), row),
        ],
    )
    state, y = pl.pallas_call(
        _decode_kernel,
        out_shape=[jax.ShapeDtypeStruct(state.shape, f32),
                   jax.ShapeDtypeStruct((t, 1, ch), f32)],
        grid_spec=grid_spec,
        # operands 0 and 1 are prefetched: the state is operand 2
        input_output_aliases={2: 0},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=_VMEM_LIMIT_BYTES),
        interpret=interpret,
        name="selscan_decode",
    )(rows, fresh, state, a.astype(f32), dt.astype(f32)[:, None],
      x.astype(f32)[:, None],
      jnp.concatenate([b, c], axis=1).astype(f32)[:, None])
    return state, y[:, 0]


def selscan_decode_xla(state, rows, fresh, dt, x, a, b, c):
    """The same step as XLA writes it: gather the rows' states, update,
    scatter. What runs off the chip, and the kernel's yardstick."""
    f32 = jnp.float32
    dt, x = dt.astype(f32)[:, None], x.astype(f32)[:, None]      # [T, 1, C]
    s = jnp.where(fresh[:, None, None], 0.0, state[rows])
    new = jnp.exp(dt * a.astype(f32)) * s + b.astype(f32)[..., None] * (dt * x)
    return (state.at[rows].set(new),
            jnp.sum(new * c.astype(f32)[..., None], axis=1))


# ------------------------------------------------------------------- tiles
def _tile_kernel(rows_ref, rows_w_ref, fresh_ref, cont_ref, write_ref, s_ref,
                 a_ref, dt_ref, x_ref, bc_ref, o_ref, y_ref, carry_ref):
    del rows_ref, rows_w_ref  # the state's index maps read them
    f32 = jnp.float32
    i = pl.program_id(1)
    r = dt_ref.shape[1]
    n = a_ref.shape[0]
    a = a_ref[...]
    bc = bc_ref[0]                                               # [2N, R]
    lane = jax.lax.broadcasted_iota(jnp.int32, bc.shape, 1)
    s0 = jnp.where(cont_ref[i] > 0, carry_ref[...],
                   jnp.where(fresh_ref[i] > 0, 0.0, s_ref[0]))

    def row(t, x_t, s):
        dt = dt_ref[0, pl.ds(t, 1), :]                           # [1, cb]
        # the row's B and C as columns on the sublanes: its lane of [2N, R]
        col = jnp.sum(jnp.where(lane == t, bc, 0.0), axis=1, keepdims=True)
        s = jnp.exp(dt * a) * s + col[:n] * (dt * x_t)
        y_ref[0, pl.ds(t, 1), :] = jnp.sum(s * col[n:], axis=0, keepdims=True)
        return s

    def rows(g, s):
        # x comes a packed tile's rows at a time (bfloat16: 16), a row of
        # which no dynamic index may name
        at = pl.multiple_of(g * _ROWS_UNROLLED, _ROWS_UNROLLED)
        x = x_ref[0, pl.ds(at, _ROWS_UNROLLED), :].astype(f32)
        for j in range(_ROWS_UNROLLED):
            s = row(at + j, x[j:j + 1], s)
        return s

    s = s0
    if r >= _ROWS_UNROLLED:
        s = jax.lax.fori_loop(0, r // _ROWS_UNROLLED, rows, s)
    for t in range(r - r % _ROWS_UNROLLED, r):
        s = row(t, x_ref[0, t:t + 1, :].astype(f32), s)
    carry_ref[...] = s
    o_ref[0] = jnp.where(write_ref[i] > 0, s, 0.0)


def _lane_block(ch: int) -> int:
    """The channels a grid step takes: the largest divisor of ``ch`` that is
    whole lane tiles and at most ``_TILE_LANES``; all of a width that has
    none."""
    for cb in range(min(_TILE_LANES, ch) // 128 * 128, 0, -128):
        if ch % cb == 0:
            return cb
    return ch


def selscan_tile(state, rows, rows_w, fresh, cont, write, dt, x, a, b, c,
                 impl: str = "auto", interpret: bool | None = None):
    """The scan over a step's ``I`` prefill tiles of ``R`` rows, the state
    read from and written to ``state`` [rows, N, C] float32 in place (module
    doc): ``dt`` [I, R, C] float32 (0 on rows that must neither decay nor
    feed), ``x`` [I, R, C], ``a`` [N, C] float32 (negative), ``b`` / ``c``
    [I, R, N], and a tile: ``rows`` [I] where its slot's state lies,
    ``rows_w`` [I] where the state it ends with goes, ``fresh`` (it starts
    from zeros whatever the row holds), ``cont`` (it goes on where tile ``i -
    1`` ended; never tile 0), ``write`` (else zeros are written) -> ``(state,
    y [I, R, C] float32)``. ``impl`` as ``selscan_decode``'s."""
    if not _on_chip(impl):
        return selscan_tile_xla(state, rows, rows_w, fresh, cont, write, dt,
                                x, a, b, c)
    i32 = jnp.int32
    return _selscan_tile(state, rows.astype(i32), rows_w.astype(i32),
                         fresh.astype(i32), cont.astype(i32),
                         write.astype(i32), dt, x, a, b, c,
                         interpret=interpret_mode(interpret))


@functools.partial(jax.jit, static_argnames=("interpret",))
def _selscan_tile(state, rows, rows_w, fresh, cont, write, dt, x, a, b, c, *,
                  interpret: bool):
    """ONE jitted function, as ``_selscan_decode``."""
    _, n, ch = state.shape
    n_i, r, _ = dt.shape
    cb = _lane_block(ch)
    f32 = jnp.float32
    # a tile's B and C, the rows on the lanes: [I, 2N, R]
    bc = jnp.concatenate([b, c], axis=2).astype(f32).transpose(0, 2, 1)

    def tile(j, i, *_):
        return (i, 0, j)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=5,
        # the tiles are the inner, sequential axis: a channel block's state
        # goes from tile to tile of a slot in ``carry_ref``
        grid=(ch // cb, n_i),
        in_specs=[
            pl.BlockSpec((1, n, cb), lambda j, i, rows, *_: (rows[i], 0, j)),
            pl.BlockSpec((n, cb), lambda j, i, *_: (0, j)),
            pl.BlockSpec((1, r, cb), tile),
            pl.BlockSpec((1, r, cb), tile),
            pl.BlockSpec((1, 2 * n, r), lambda j, i, *_: (i, 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, n, cb),
                         lambda j, i, rows, rows_w, *_: (rows_w[i], 0, j)),
            pl.BlockSpec((1, r, cb), tile),
        ],
        scratch_shapes=[pltpu.VMEM((n, cb), f32)],               # carry
    )
    return pl.pallas_call(
        _tile_kernel,
        out_shape=[jax.ShapeDtypeStruct(state.shape, f32),
                   jax.ShapeDtypeStruct((n_i, r, ch), f32)],
        grid_spec=grid_spec,
        # operands 0-4 are prefetched: the state is operand 5
        input_output_aliases={5: 0},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"),
            vmem_limit_bytes=_VMEM_LIMIT_BYTES),
        interpret=interpret,
        name="selscan_tile",
    )(rows, rows_w, fresh, cont, write, state, a.astype(f32), dt.astype(f32),
      x, bc)


def selscan_tile_xla(state, rows, rows_w, fresh, cont, write, dt, x, a, b, c):
    """The same tiles as XLA writes them: a dynamic slice a tile's state, a
    ``lax.scan`` over its rows (the state through HBM every row), a
    dynamic-update-slice a tile. What runs off the chip, and the kernel's
    yardstick."""
    f32 = jnp.float32
    a = a.astype(f32)

    def row(s, xs):
        dt_t, x_t, b_t, c_t = xs                                 # [C] [C] [N] [N]
        s = jnp.exp(dt_t * a) * s + b_t[:, None] * (dt_t * x_t)
        return s, jnp.sum(s * c_t[:, None], axis=0)

    ys, prev = [], None
    for i in range(dt.shape[0]):
        held = jnp.where(fresh[i], 0.0,
                         jax.lax.dynamic_index_in_dim(state, rows[i], 0, False))
        s0 = held if prev is None else jnp.where(cont[i], prev, held)
        prev, y = jax.lax.scan(row, s0, (dt[i].astype(f32), x[i].astype(f32),
                                         b[i].astype(f32), c[i].astype(f32)))
        ys.append(y)
        state = jax.lax.dynamic_update_index_in_dim(
            state, jnp.where(write[i], prev, 0.0), rows_w[i], 0)
    return state, jnp.stack(ys)
