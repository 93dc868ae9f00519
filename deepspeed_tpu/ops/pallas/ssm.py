"""Pallas kernels of a Mamba-2 layer's state: the decode step (one state
update a row, the row's state read from HBM once and written back once, in
place) and, for a group a head, the chunk form over a step's prefill tiles.

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

**The chunk form, for a group a head only** (``ssd_chunk``). A step's prefill
tiles run the same recurrence a tile of ``R`` rows at a time as four products
a head (``mamba2.ssd_tiles`` is the form in XLA, for any number of groups):
with ``acum`` the cumulative log-decay ``cumsum(dt a)`` of a tile's rows,

    y  = ((C B^T) * exp(acum_t - acum_s) [s <= t] * dt_s) x  +  (C S) * exp(acum_t)
    S' = S exp(acum_R) + B^T (x * dt * exp(acum_R - acum))

bfloat16 (the inputs' dtype) operands, float32 decay and accumulation. Where
every head has a ``B`` and a ``C`` of its own (``G = H``: MiniCPM-SALA's
Lightning attention, 32 heads of ``N = P = 128``) a head's ``B``, ``C`` and
``x`` are each ONE whole lane tile of the rows as the projections leave them
(``[T, H x 128]``, row-major), so a ``BlockSpec`` cuts them out for nothing
and the head's ``[128, 128]`` of the state is a lane slice of the leaf: the
kernel's grid is (heads / ``hb``, tiles), the tiles the inner, sequential
axis, a head's state read from the leaf at its slot's first tile of the step,
carried in VMEM from tile to tile, written where the slot's last tile says.
XLA's form of that case cut a head's ``b`` / ``c`` / ``xw`` out of ``[I, R,
H, 128]`` 32 times a tile and layer, each a ``[I, R, 1, 128]`` copy padded
16-fold (21% of the MiniCPM-SALA cell's device time, ledger PR 60). The cut
is free ONLY there: Nemotron-3 shares a group's ``B`` and ``C`` over 16 heads
of 64 lanes and Granite one over all, another block structure, and their
chunk form is 1.5% of a slice and less; they keep ``mamba2.ssd_tiles``, and
``ssd_chunk`` refuses their shapes by name.

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


# the heads ``ssd_chunk`` takes a grid step: the most of these the count
# divides. The heads are independent; more of them a step spread the ~0.35 us
# a grid step costs, and written out one after another (not a loop) the
# compiler overlaps them: 187 bundles a head and tile at eight against 355 in
# a loop (compiled for a described v5e, PR 61)
_HEADS_A_STEP = (8, 4, 2, 1)


def _on_chip(impl: str) -> bool:
    if impl not in ("auto", "pallas", "xla"):
        raise ValueError(f"ssm: impl {impl!r} (auto, pallas, xla)")
    return impl == "pallas" or (impl == "auto"
                                and jax.default_backend() == "tpu")


def _chunk_kernel(rows_ref, rows_w_ref, fresh_ref, cont_ref, write_ref,
                  s_ref, x_ref, b_ref, c_ref, col_ref, row_ref, o_ref, y_ref,
                  carry_ref, *, heads: int):
    del rows_ref, rows_w_ref  # the state's index maps read them
    f32 = jnp.float32
    i = pl.program_id(1)
    r, dtype = x_ref.shape[1], x_ref.dtype
    n = s_ref.shape[1]
    p = s_ref.shape[2] // heads
    all_heads = row_ref.shape[1] // 2
    mm = functools.partial(jax.lax.dot_general, preferred_element_type=f32)
    iota = functools.partial(jax.lax.broadcasted_iota, jnp.int32)
    causal = iota((r, r), 1) <= iota((r, r), 0)          # [t, s]: s <= t
    lane = iota(col_ref.shape[1:], 1)

    for j in range(heads):
        h = pl.program_id(0) * heads + j
        lp, ln = slice(j * p, (j + 1) * p), slice(j * n, (j + 1) * n)
        prev = jnp.where(cont_ref[i] > 0, carry_ref[:, lp],
                         jnp.where(fresh_ref[i] > 0, 0.0, s_ref[0, :, lp]))
        # the head's cumulative log-decay and dt, a column over the tile's
        # rows (``t``) and a row over them (``s``)
        acum_t = jnp.sum(jnp.where(lane == h, col_ref[0], 0.0), axis=1,
                         keepdims=True)
        dt_t = jnp.sum(jnp.where(lane == all_heads + h, col_ref[0], 0.0),
                       axis=1, keepdims=True)
        acum_s = row_ref[0, pl.ds(h, 1), :]
        dt_s = row_ref[0, pl.ds(all_heads + h, 1), :]
        end = acum_t[r - 1:]
        x, b, c = x_ref[0, :, lp], b_ref[0, :, ln], c_ref[0, :, ln]
        # inside the tile: row t reads row s <= t, decayed from s to t
        decay = jnp.exp(jnp.where(causal, acum_t - acum_s, -jnp.inf))
        scores = mm(c, b, (((1,), (1,)), ((), ())))                  # c b^T
        m = (scores * decay * dt_s).astype(dtype)
        # the tile's own contribution to the state at its end
        xw = (x.astype(f32) * (dt_t * jnp.exp(end - acum_t))).astype(dtype)
        ds = mm(b, xw, (((0,), (0,)), ((), ())))                     # b^T xw
        y_ref[0, :, lp] = (
            mm(m, x, (((1,), (0,)), ((), ())))
            + mm(c, prev.astype(dtype), (((1,), (0,)), ((), ())))
            * jnp.exp(acum_t))
        carry_ref[:, lp] = prev * jnp.exp(end) + ds
    o_ref[0] = jnp.where(write_ref[i] > 0, carry_ref[...], 0.0)


def ssd_chunk(state, rows, rows_w, fresh, cont, write, x, dt, a, b, c,
              impl: str = "auto", interpret: bool | None = None):
    """The chunk form (module doc) over a step's ``I`` prefill tiles of ``R``
    rows at a group a head, the state read from and written to ``state``
    [rows, N, H x P] float32 in place: ``x`` [I, R, H x P], ``b`` / ``c`` [I,
    R, H x N] as the projections leave them (row-major, a head's lanes side by
    side), ``dt`` [I, R, H] float32 (0 on rows that must neither decay nor
    feed), ``a`` [H] (negative), and a tile (``mamba2.tile_rows``): ``rows``
    [I] where its slot's state lies, ``rows_w`` [I] where the state it ends
    with goes, ``fresh`` (it starts from zeros whatever the row holds),
    ``cont`` (it goes on where tile ``i - 1`` ended; never tile 0), ``write``
    (else zeros are written: a tile that is not its slot's last of the step,
    and a padding tile, name the scratch slot in ``rows_w``) -> ``(state, y
    [I, R, H x P] float32)``. The operands of the four products in ``x``'s
    dtype, float32 decay and accumulation. ``impl``: ``"auto"`` the kernel on
    the chip and ``ssd_chunk_xla`` off it, or ``"pallas"`` / ``"xla"``."""
    heads = dt.shape[2]
    n, hp = state.shape[1:]
    if b.shape[2] != heads * n or c.shape != b.shape:
        raise ValueError(
            f"ssd_chunk: B {b.shape} and C {c.shape} for {heads} heads of "
            f"state size {n}: a B and a C a head (a group a head, [I, R, "
            f"{heads * n}]) is what is here; groups shared by several heads "
            "run mamba2.ssd_tiles")
    if not _on_chip(impl):
        return ssd_chunk_xla(state, rows, rows_w, fresh, cont, write, x, dt,
                             a, b, c)
    if n % 128 or (hp // heads) % 128:
        raise ValueError(
            f"ssd_chunk: a state [{n}, {hp}] of {heads} heads: the kernel "
            "cuts a head out as whole lane tiles (a state size and a head "
            "size that are multiples of 128); other sizes run ssd_chunk_xla")
    i32 = jnp.int32
    return _ssd_chunk(state, rows.astype(i32), rows_w.astype(i32),
                      fresh.astype(i32), cont.astype(i32), write.astype(i32),
                      x, dt, a, b, c, interpret=interpret_mode(interpret))


@functools.partial(jax.jit, static_argnames=("interpret",))
def _ssd_chunk(state, rows, rows_w, fresh, cont, write, x, dt, a, b, c, *,
               interpret: bool):
    """ONE jitted function, as ``_ssm_decode``."""
    _, n, hp = state.shape
    n_i, r, heads = dt.shape
    p = hp // heads
    f32 = jnp.float32
    hb = next(k for k in _HEADS_A_STEP if heads % k == 0)
    # a head's cumulative log-decay and its dt, [I, R, 2 H] (49 KB at three
    # tiles of 32 heads): made here, handed in rows-first (a head's column)
    # and heads-first (a head's row)
    dt = dt.astype(f32)
    col = jnp.concatenate([jnp.cumsum(dt * a.astype(f32), axis=1), dt], axis=2)

    def tile(h, i, *_):
        return (i, 0, h)

    def whole(h, i, *_):
        return (i, 0, 0)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=5,
        # the tiles are the inner, sequential axis: a head's state goes from
        # tile to tile of a slot in ``carry_ref``
        grid=(heads // hb, n_i),
        in_specs=[
            pl.BlockSpec((1, n, hb * p),
                         lambda h, i, rows, *_: (rows[i], 0, h)),
            pl.BlockSpec((1, r, hb * p), tile),
            pl.BlockSpec((1, r, hb * n), tile),
            pl.BlockSpec((1, r, hb * n), tile),
            pl.BlockSpec((1, r, 2 * heads), whole),
            pl.BlockSpec((1, 2 * heads, r), whole),
        ],
        out_specs=[
            pl.BlockSpec((1, n, hb * p),
                         lambda h, i, rows, rows_w, *_: (rows_w[i], 0, h)),
            pl.BlockSpec((1, r, hb * p), tile),
        ],
        scratch_shapes=[pltpu.VMEM((n, hb * p), f32)],             # carry
    )
    return pl.pallas_call(
        functools.partial(_chunk_kernel, heads=hb),
        out_shape=[jax.ShapeDtypeStruct(state.shape, f32),
                   jax.ShapeDtypeStruct((n_i, r, hp), f32)],
        grid_spec=grid_spec,
        # operands 0-4 are prefetched: the state is operand 5
        input_output_aliases={5: 0},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary")),
        interpret=interpret,
        name="ssd_chunk",
    )(rows, rows_w, fresh, cont, write, state, x, b.astype(x.dtype),
      c.astype(x.dtype), col, col.transpose(0, 2, 1))


def ssd_chunk_xla(state, rows, rows_w, fresh, cont, write, x, dt, a, b, c):
    """The same tiles as XLA writes them: a dynamic slice a tile's state, the
    chunk form as ``mamba2.ssd_tiles`` rounds it (at a group a head its two
    loops over the groups are one product over a head axis each), a
    dynamic-update-slice a tile. What runs off the chip, and the kernel's
    yardstick."""
    f32 = jnp.float32
    n_i, r, heads = dt.shape
    n = state.shape[1]
    dt = dt.astype(f32)
    xh, bh, ch = (t.reshape(n_i, r, heads, -1) for t in (x, b, c))
    ein = functools.partial(jnp.einsum, preferred_element_type=f32)
    acum = jnp.cumsum(dt * a.astype(f32), axis=1)                 # [I, R, H]
    causal = jnp.tril(jnp.ones((r, r), bool))[None, :, :, None]
    decay = jnp.exp(jnp.where(causal, acum[:, :, None] - acum[:, None],
                              -jnp.inf))                          # [I, t, s, H]
    m = (ein("ithn,ishn->itsh", ch, bh) * decay * dt[:, None]).astype(x.dtype)
    y = ein("itsh,ishp->ithp", m, xh)
    xw = (xh.astype(f32)
          * (dt * jnp.exp(acum[:, -1:] - acum))[..., None]).astype(x.dtype)
    ds = ein("ishn,ishp->inhp", bh, xw)
    total = jnp.exp(acum[:, -1])[:, None, :, None]                # [I,1,H,1]
    s0 = jnp.stack([jax.lax.dynamic_index_in_dim(state, rows[i], 0, False)
                    for i in range(n_i)]).reshape(n_i, n, heads, -1)
    # the carry from tile to tile: in order, tiny beside the products
    before, after = [], []
    for i in range(n_i):
        prev = jnp.where(fresh[i], 0.0, s0[i])
        if i:
            prev = jnp.where(cont[i], after[-1], prev)
        before.append(prev)
        after.append(prev * total[i] + ds[i])
    y = y + (ein("ithn,inhp->ithp", ch, jnp.stack(before).astype(x.dtype))
             * jnp.exp(acum)[..., None])
    for i in range(n_i):
        state = jax.lax.dynamic_update_index_in_dim(
            state, jnp.where(write[i], after[i], 0.0).reshape(n, -1),
            rows_w[i], 0)
    return state, y.reshape(n_i, r, -1)
