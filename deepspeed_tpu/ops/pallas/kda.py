"""Pallas kernels of a Kimi Delta Attention (KDA) layer: the decode step, one
delta-rule update a row (``kda_decode``), and the chunk form over a step's
prefill tiles (``kda_chunk``); each reads a row's or tile's state from HBM
once and writes it back once, in place.

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

``kda_chunk`` runs the recurrence's CHUNK form (below) over a step's prefill
tiles, four heads and a tile a grid step where four divide the head count
(two where two do, else one), the tile axis the inner, sequential one. ``q`` /
``k`` / ``g`` / ``v`` come as the model has them, ``[tiles, R, H x K]``: a
head is a block of 128 lanes, and so it is of the state leaf, so nothing is
transposed on the way in or out. By scalar prefetch a tile names the leaf row
its slot's state lies in, the row the state it ends with goes to, and whether
it starts from zeros (position 0), goes on where the tile before it ended (the
state stays in VMEM scratch between the tiles of a slot) and writes (a tile
that is not its slot's last of the step, and a padding tile, write the scratch
slot zeros). The output aliases the leaf: tile ``i + 1``'s row is fetched
while tile ``i`` still computes, which is safe because a continued tile
ignores what it fetched and only the scratch slot's row is written twice.

Inside, the chunk form is run a SUB-CHUNK at a time (16 rows), the state
carried from one to the next (``kda_tiles`` with ``R`` = 16 between them), so
that a turn of the loop costs what is new to its 16 rows (PERF.md section 6,
PR 58: until then a turn rebuilt, split and pushed two ``[R, 128]`` operands
of the whole tile for 32 streamed rows, two thirds of the kernel's schedule).
Once a tile: a row's cumulative log-decay INSIDE its sub-chunk (``loc`` <= 0)
as sums of picked rows on the MXU, and a head's column of ``beta``. Then a
turn, with ``S`` the state at the sub-chunk's first row and ``tot`` its last
row's ``loc``:

    read = [beta K e^loc; Q e^loc] S            # [32, K] x [K, V]
    (I + A) U = beta V - read_k,  Y = read_q + B U
    S' = diag(e^tot) S + (K e^(tot - loc))^T U  # [K, 16] x [16, V]

``A`` / ``B`` are the sub-chunk's pairwise block, ``[d, c, K]`` with the later
row ``c`` first, summed over the lanes, in two bands of 8 rows of ``d`` (the
pairs of the second band's ``d`` with the first band's ``c`` are never made);
the unit lower triangular solve is forward substitution, a column a step, the
heads of the grid step side by side on the lanes: ONE chain of dependent steps
for all of them. **Every exponent is still a difference with the later row
first, at most 0**: ``loc`` (a row from its sub-chunk's first), ``loc_c -
loc_d`` (``c`` after ``d``), ``tot - loc`` (a row to its sub-chunk's last) and
``tot`` (the whole sub-chunk). The state is only ever MULTIPLIED by ``e^tot``
<= 1; nothing is divided by a decay, so ``exp(-G)`` never appears.

float32 throughout, and every product of two float32 operands is the six
partial products ``Precision.HIGHEST`` makes of their bfloat16 parts (``x =
x1 + x2 + x3`` to the last bit: ``x1 w1 + x1 w2 + x2 w1 + x1 w3 + x2 w2 + x3
w1``, accumulated in float32), written out, because of what the compiler's own
form costs here: it pushes the stationary operand six times as float32
registers (96 pushes for ``S`` a turn). Written out, a part of ``S`` is pushed
ONCE, as 8 packed registers, under all the rows' parts it meets; and the
update, whose contraction is the sub-chunk's 16 rows, lays its six products
SIDE BY SIDE along one pass's contraction (6 x 16 <= 128: ``[k1 k1 k2 k1 k2
k3]^T [u1; u2; u1; u3; u2; u1]``), so the MXU sums them in float32 and ``S'``
comes off in 16 pops, not 96. The keys' parts turn from rows to columns on the
MXU (an identity against them: exact), and the decay's three parts ride the
same product, against three rows of ones, which spreads ``e^tot`` (channels on
the lanes) over the state's lanes as a column (channels on the sublanes) with
no work for the XLU, whose lane sums of the pairwise block are what bounds a
turn now. It is a kernel because XLA's form of the same algebra
(``kda_chunk_xla``: ``kda_tiles`` between a dynamic slice and a
dynamic-update-slice a tile) is ~60 operations a layer around 0.4 ms of
products: transposes to a head-major layout and back, a scan whose carry is
materialised each step (9-11 ms of a 45 ms mixed step at Kimi-Linear's ten
layers, PERF.md section 6, PR 42). And because a kernel's operand has ONE
layout: a step program with tiles and no decode row holds no ``kda_decode``,
and left to itself XLA then lays the WHOLE leaf out to suit the chunk form's
matmuls (key channels on the lanes): 2.7 GB copied in and copied back, every
step, at the benchmark's 10 layers x 129 slots (the compiled program, PR 40).

**The chunk form** (``kda_tiles``). A tile of ``R`` rows runs the recurrence
as matmuls. With ``G_i = sum_{j <= i} g_j`` a channel, ``A[i, j] = beta_i sum_c
k_i[c] k_j[c] exp(G_i[c] - G_j[c])`` (``j < i``) and ``B[i, j]`` the same with
``q_i`` and ``j <= i``: ``(I + A) U = beta (V - (K exp(G)) S_0)``, ``O = (Q
exp(G)) S_0 + B U``, ``S_R = exp(G_R) S_0 + (K exp(G_R - G))^T U``. Every decay
is ``exp`` of a DIFFERENCE of cumulative log-decays with the later row first,
so it is at most 1: ``exp(-G_j)`` alone overflows float32 (a channel's ``g``
reaches -1.6 a token, ``G`` -205 over 128 rows). ``A`` and ``B`` are built in
sub-chunks of ``sub_chunk`` (16) rows: inside a sub-chunk from pairwise
differences (``[C, C, K]``), between sub-chunks as a matmul of rows decayed
from the later sub-chunk's first row and columns decayed up to it (both
factors <= 1); the unit lower triangular system is solved by forward
substitution over the sub-chunks, each sub-chunk's 16 x 16 block inverted by
its finite Neumann series. float32 and ``Precision.HIGHEST`` throughout. Rows
of a tile past its valid ones have ``g = 0`` and ``beta = 0``: they neither
decay nor feed the state.

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
_HIGHEST = jax.lax.Precision.HIGHEST
# the heads ``kda_chunk`` takes a grid step: the most of these the count divides
_HEADS_A_STEP = (4, 2, 1)


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


def _on_chip(impl: str) -> bool:
    if impl not in ("auto", "pallas", "xla"):
        raise ValueError(f"kda: impl {impl!r} (auto, pallas, xla)")
    return impl == "pallas" or (impl == "auto"
                                and jax.default_backend() == "tpu")


@jax.jit
def _parts(x):
    """``x`` float32 -> its three bfloat16 parts, largest first: their sum is
    ``x`` to the last bit (a part is the 8 leading bits of what the parts
    before it left, cut and not rounded, so what is left stays a float32)."""
    out = []
    for _ in range(2):
        top = jax.lax.bitcast_convert_type(
            jax.lax.bitcast_convert_type(x, jnp.uint32)
            & jnp.uint32(0xFFFF0000), jnp.float32)
        out.append(top.astype(jnp.bfloat16))
        x = x - top
    return out + [x.astype(jnp.bfloat16)]


def _sum_rows(picks, x):
    """``picks`` [M, R] bool, ``x`` [R, N] float32 -> ``picks @ x`` as float32
    sums, in three single bfloat16 passes: 0 / 1 is exact in bfloat16 and
    ``x`` is the sum of its three bfloat16 parts."""
    picks = picks.astype(jnp.float32).astype(jnp.bfloat16)
    x1, x2, x3 = (jnp.dot(picks, part, preferred_element_type=jnp.float32)
                  for part in _parts(x))
    return x1 + x2 + x3


def _chunk_kernel(rows_ref, rows_w_ref, fresh_ref, cont_ref, write_ref,
                  s_ref, q_ref, k_ref, g_ref, v_ref, b_ref, o_ref, y_ref,
                  carry_ref, loc_ref, beta_ref, *, sub: int, heads: int):
    del rows_ref, rows_w_ref  # the state's index maps read them
    f32, bf16 = jnp.float32, jnp.bfloat16
    i = pl.program_id(1)
    r = q_ref.shape[1]
    kd = s_ref.shape[1]
    vd = s_ref.shape[2] // heads
    n_sub = r // sub
    mm = functools.partial(jnp.dot, preferred_element_type=f32)
    iota = functools.partial(jax.lax.broadcasted_iota, jnp.int32)
    # the rows of a sub-chunk in bands of 8 where that is half of it: the
    # pairs of a later band's ``d`` with an earlier band's ``c`` are not made
    bands = [(0, sub)] if sub % 16 else [(0, sub // 2), (sub // 2, sub)]
    # the update's operands: six blocks of the sub-chunk's rows side by side,
    # the decay's three parts after them, up to whole lane tiles
    room = -(-3 // sub) * sub
    tall = -(-(6 * sub + room) // 128) * 128
    first = sub * iota((n_sub, sub, r), 0).reshape(r, r)  # of a row's sub-chunk
    inside = (iota((r, r), 1) >= first) & (iota((r, r), 1) <= iota((r, r), 0))

    for j in range(heads):
        # what a head of the grid step needs of the whole tile, once: the
        # state it starts from, its column of beta, and a row's cumulative
        # log-decay inside its sub-chunk (<= 0) as sums of picked rows
        h = pl.program_id(0) * heads + j
        lv = slice(j * vd, (j + 1) * vd)
        carry_ref[:, lv] = jnp.where(
            cont_ref[i] > 0, carry_ref[:, lv],
            jnp.where(fresh_ref[i] > 0, 0.0, s_ref[0, :, lv]))
        mine = iota(b_ref.shape[1:], 1) == h
        beta_ref[:, j * kd:(j + 1) * kd] = jnp.broadcast_to(
            jnp.sum(jnp.where(mine, b_ref[0], 0.0), axis=1, keepdims=True),
            (r, kd))
        loc_ref[:, j * kd:(j + 1) * kd] = _sum_rows(
            inside, g_ref[0, :, j * kd:(j + 1) * kd])

    def over_heads(cols):
        """The heads' [rows, 1] columns, each over its own head's lanes."""
        return jnp.concatenate(
            [jnp.broadcast_to(c, (c.shape[0], vd)) for c in cols], axis=1)

    def pairwise(loc_n, k_n, kb_n, q_n):
        """Inside the sub-chunk: pairwise differences, later row ``c`` first
        (every exponent <= 0), as [d, c, K] a band of ``d``; the sum over K
        leaves pair (c, d) on row c of block d, what multiplies row d of U:
        a band's columns of ``A``, then its columns of ``B``, [C - d0, 1]
        each."""
        out = []
        for d0, d1 in bands:
            shape = (d1 - d0, sub - d0, kd)
            later = iota(shape, 1) - iota(shape, 0)
            kp = k_n[d0:d1][:, None] * jnp.exp(jnp.where(
                later >= 0, loc_n[d0:][None] - loc_n[d0:d1][:, None], -jnp.inf))
            out.append(list(jnp.sum(jnp.concatenate(
                [jnp.where(later > 0, kb_n[d0:][None] * kp, 0.0),
                 q_n[d0:][None] * kp]), axis=-1, keepdims=True)))
        return out

    def against(state, x):
        """``x`` [2C, K] x ``state`` [K, V]: the six products of
        ``Precision.HIGHEST`` written out, so that each part of the state is
        pushed once, under all the rows' parts it meets."""
        x1, x2, x3 = _parts(x)
        s1, s2, s3 = _parts(state)
        by1 = mm(jnp.concatenate([x1, x2, x3]), s1)
        by2 = mm(jnp.concatenate([x1, x2]), s2)
        m = x.shape[0]
        return ((by1[2 * m:] + by2[m:] + mm(x1, s3))
                + (by1[m:2 * m] + by2[:m]) + by1[:m])

    def turned(k_end, decay):
        """``k_end`` [C, K] and ``decay`` [1, K] in parts, their rows turned
        to columns by the MXU (an identity against them: exact) -> [K, tall]:
        the keys' parts as the update's six products meet U's, then the
        decay's three."""
        k1, k2, k3 = _parts(k_end)
        e1, e2, e3 = (t.astype(f32) for t in _parts(decay))
        nth = iota((room, kd), 0)
        eye = (iota((kd, kd), 0) == iota((kd, kd), 1)).astype(f32).astype(bf16)
        return jax.lax.dot_general(
            eye, jnp.concatenate(
                [k1, k1, k2, k1, k2, k3,
                 jnp.where(nth == 0, e1, jnp.where(
                     nth == 1, e2, jnp.where(nth == 2, e3, 0.0))).astype(bf16),
                 jnp.zeros((tall - 6 * sub - room, kd), bf16)]),
            (((1,), (1,)), ((), ())), preferred_element_type=f32).astype(bf16)

    # what a turn does a head is traced once and called once a head (the
    # kernel is traced in every step program's set-up, and a nested ``jit``
    # is inlined when the kernel is lowered)
    @jax.jit
    def before(state, loc_n, k_n, q_n, beta_n):
        """A head's turn as far as it does not wait for U: its pairwise
        block, its rows against the state, its keys turned."""
        kb_n = k_n * beta_n
        tot = loc_n[sub - 1:]
        d_in = jnp.exp(loc_n)
        return (pairwise(loc_n, k_n, kb_n, q_n),
                against(state, jnp.concatenate([kb_n * d_in, q_n * d_in])),
                turned(k_n * jnp.exp(tot - loc_n), jnp.exp(tot)))

    @functools.partial(jax.jit, static_argnames=("cuts", "last"))
    def column(us, ys, u_d, a_d, b_d, *, cuts, last):
        """A step of the forward substitution: row ``d`` of U, ``u_d``, is
        final; column ``d`` of ``A`` and of ``B`` (``a_d`` / ``b_d``, a head's
        [rows, 1] each, from ``d``'s band on) takes it to the rows after it
        (``cuts``: the bands from ``d``'s on; ``last``: ``d`` is its band's
        last row, whose own band has no row after it)."""
        a_d, b_d = over_heads(a_d), over_heads(b_d)
        ys = [y + b_d[c0:c1] * u_d for y, (c0, c1) in zip(ys, cuts)]
        us = [u if last and n == 0 else u - a_d[c0:c1] * u_d
              for n, (u, (c0, c1)) in enumerate(zip(us, cuts))]
        return us, ys

    @jax.jit
    def after(state, keys, u_n):
        """S' = diag(e^tot) S + (K e^(tot - loc))^T U, [K, C] x [C, V]: the
        same six products in ONE pass, side by side along the contraction;
        the decay's three parts against rows of ones spread its column over
        the lanes."""
        u1, u2, u3 = _parts(u_n)
        ones = (iota((tall, vd), 0) // 3 == 2 * sub).astype(f32).astype(bf16)
        return state * mm(keys, ones) + mm(keys, jnp.concatenate(
            [u1, u2, u1, u3, u2, u1, jnp.zeros((tall - 6 * sub, vd), bf16)]))

    # a loop, not ``R / sub`` copies of its body: the kernel is compiled in
    # every step program that has tiles, once a KDA layer body
    def step(n, carry):
        rows = pl.ds(pl.multiple_of(n * sub, sub), sub)
        states = [carry_ref[:, j * vd:(j + 1) * vd] for j in range(heads)]
        pairs, reads, keys = zip(*(
            before(states[j], loc_ref[rows, j * kd:(j + 1) * kd],
                   k_ref[0, rows, j * kd:(j + 1) * kd],
                   q_ref[0, rows, j * kd:(j + 1) * kd],
                   beta_ref[rows, j * kd:(j + 1) * kd])
            for j in range(heads)))
        # (I + A) U = beta V - read_k, Y = read_q + B U by forward
        # substitution, a column a step, the heads side by side on the lanes
        # (ONE chain of dependent steps for all of them): row d of U is final
        # when column d is taken
        read = jnp.concatenate(reads, axis=1)                      # [2C, H V]
        rhs = v_ref[0, rows, :] * over_heads(
            [beta_ref[rows, j * kd:j * kd + 1] for j in range(heads)])
        us = [rhs[d0:d1] - read[d0:d1] for d0, d1 in bands]
        ys = [read[sub + d0:sub + d1] for d0, d1 in bands]
        for at, (d0, d1) in enumerate(bands):
            cuts = tuple((t0 - d0, t1 - d0) for t0, t1 in bands[at:])
            for d in range(d1 - d0):
                us[at:], ys[at:] = column(
                    us[at:], ys[at:], us[at][d:d + 1],
                    [p[at][d] for p in pairs],
                    [p[at][d1 - d0 + d] for p in pairs],
                    cuts=cuts, last=d + 1 == d1 - d0)
        y_ref[0, rows, :] = jnp.concatenate(ys)
        u_n = jnp.concatenate(us)
        for j in range(heads):
            lv = slice(j * vd, (j + 1) * vd)
            carry_ref[:, lv] = after(states[j], keys[j], u_n[:, lv])
        return carry

    jax.lax.fori_loop(0, n_sub, step, 0)
    o_ref[0] = jnp.where(write_ref[i] > 0, carry_ref[...], 0.0)


def kda_chunk(state, rows, rows_w, fresh, cont, write, q, k, g, v, beta,
              sub: int, impl: str = "auto", interpret: bool | None = None):
    """The chunk form of the recurrence over a step's ``I`` prefill tiles of
    ``R`` rows, the state read from and written to ``state`` [rows, K, HV]
    float32 in place (module doc): ``q`` / ``k`` / ``g`` [I, R, H x K]
    (``q`` scaled; ``g`` the log-decay, 0 on rows that must neither decay nor
    feed), ``v`` [I, R, H x V], ``beta`` [I, R, H] (0 on those rows), and a
    tile: ``rows`` [I] where its slot's state lies, ``rows_w`` [I] where the
    state it ends with goes, ``fresh`` (it starts from zeros whatever the row
    holds), ``cont`` (it goes on where tile ``i - 1`` ended; never tile 0),
    ``write`` (else zeros are written: a tile that is not its slot's last of
    the step, and a padding tile, name the scratch slot in ``rows_w``).
    ``sub`` the sub-chunk (divides ``R``) -> ``(state, y [I, R, H x V])``.
    float32, the six products of ``Precision.HIGHEST`` (module doc). ``impl``
    as ``kda_decode``'s."""
    if not _on_chip(impl):
        return kda_chunk_xla(state, rows, rows_w, fresh, cont, write, q, k, g,
                             v, beta, sub)
    i32 = jnp.int32
    return _kda_chunk(state, rows.astype(i32), rows_w.astype(i32),
                      fresh.astype(i32), cont.astype(i32), write.astype(i32),
                      q, k, g, v, beta, sub=sub,
                      interpret=interpret_mode(interpret))


@functools.partial(jax.jit, static_argnames=("sub", "interpret"))
def _kda_chunk(state, rows, rows_w, fresh, cont, write, q, k, g, v, beta, *,
               sub: int, interpret: bool):
    """ONE jitted function, as ``_kda_decode``."""
    _, kd, hv = state.shape
    n_i, r, heads = beta.shape
    vd = hv // heads
    f32 = jnp.float32

    # the heads of a grid step share ONE chain of dependent steps (the
    # forward substitution), side by side on the lanes
    hb = next(n for n in _HEADS_A_STEP if heads % n == 0)

    def tile(h, i, *_):
        return (i, 0, h)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=5,
        # the tiles are the inner, sequential axis: a head's state goes from
        # tile to tile of a slot in ``carry_ref``
        grid=(heads // hb, n_i),
        in_specs=[
            pl.BlockSpec((1, kd, hb * vd),
                         lambda h, i, rows, *_: (rows[i], 0, h)),
            pl.BlockSpec((1, r, hb * kd), tile),
            pl.BlockSpec((1, r, hb * kd), tile),
            pl.BlockSpec((1, r, hb * kd), tile),
            pl.BlockSpec((1, r, hb * vd), tile),
            pl.BlockSpec((1, r, heads), lambda h, i, *_: (i, 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, kd, hb * vd),
                         lambda h, i, rows, rows_w, *_: (rows_w[i], 0, h)),
            pl.BlockSpec((1, r, hb * vd), tile),
        ],
        scratch_shapes=[pltpu.VMEM((kd, hb * vd), f32),             # carry
                        pltpu.VMEM((r, hb * kd), f32),              # loc
                        pltpu.VMEM((r, hb * kd), f32)],            # beta
    )
    return pl.pallas_call(
        functools.partial(_chunk_kernel, sub=sub, heads=hb),
        out_shape=[jax.ShapeDtypeStruct(state.shape, f32),
                   jax.ShapeDtypeStruct((n_i, r, hv), f32)],
        grid_spec=grid_spec,
        # operands 0-4 are prefetched: the state is operand 5
        input_output_aliases={5: 0},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary")),
        interpret=interpret,
        name="kda_chunk",
    )(rows, rows_w, fresh, cont, write, state, q.astype(f32), k.astype(f32),
      g.astype(f32), v.astype(f32), beta.astype(f32))


def _block_inverse(a):
    """``(I + a)^-1`` of strictly lower triangular ``a`` [..., C, C]: ``a`` is
    nilpotent, so the Neumann series ends, ``sum_{n < C} (-a)^n = (I - a)(I +
    a^2)(I + a^4)...``: log2(C) squarings."""
    c = a.shape[-1]
    eye = jnp.eye(c, dtype=a.dtype)
    p = -a
    inv = eye + p
    n = 2
    while n < c:
        p = jnp.matmul(p, p, precision=_HIGHEST)
        inv = jnp.matmul(inv, eye + p, precision=_HIGHEST)
        n *= 2
    return inv


def kda_tiles(q, k, v, g, beta, s0, cont, sub: int):
    """The chunk form over ``I`` tiles of ``R`` rows, one chunk each (module
    doc): ``q`` / ``k`` [I, R, H, K] and ``v`` [I, R, H, V] float32 (``q``
    scaled), ``g`` [I, R, H, K] (the log-decay, 0 on rows that must neither
    decay nor feed the state), ``beta`` [I, R, H] (0 on those rows), ``s0``
    [I, K, H x V] float32 (the state each tile would start from were it its
    slot's first of the step), ``cont`` [I] bool (tile ``i`` goes on where
    tile ``i - 1`` ended), ``sub`` the sub-chunk (divides ``R``) -> ``(y [I,
    R, H x V], s [I, K, H x V])``: the recurrence's readings and each tile's
    final state. float32, ``Precision.HIGHEST``."""
    n_i, r, h, kd = q.shape
    vd = v.shape[-1]
    n = r // sub
    ein = functools.partial(jnp.einsum, precision=_HIGHEST)
    gc = jnp.cumsum(g, axis=1)                                   # [I, R, H, K]
    g5 = gc.reshape(n_i, n, sub, h, kd)
    # a sub-chunk's reference: the cumulative log-decay before its first row
    ref = jnp.concatenate([jnp.zeros_like(g5[:, :1, -1]), g5[:, :-1, -1]], 1)
    local = g5 - ref[:, :, None]                                 # <= 0
    k5, q5 = (t.reshape(n_i, n, sub, h, kd) for t in (k, q))
    # inside a sub-chunk: pairwise differences, later row first
    tri = jnp.tril(jnp.ones((sub, sub), bool))[None, None, :, :, None, None]
    pair = jnp.exp(jnp.where(tri, local[:, :, :, None] - local[:, :, None],
                             -jnp.inf))                          # [I,N,C,C,H,K]
    kk_in = jnp.sum(k5[:, :, :, None] * k5[:, :, None] * pair, axis=-1)
    qk_in = jnp.sum(q5[:, :, :, None] * k5[:, :, None] * pair, axis=-1)
    # between sub-chunks: rows decayed from their sub-chunk's reference,
    # columns (every earlier row of the tile) decayed up to it
    decay_in = jnp.exp(local)
    early = (jnp.arange(r)[None, :] < (jnp.arange(n) * sub)[:, None])
    cols = k[:, None] * jnp.exp(jnp.where(
        early[None, :, :, None, None], ref[:, :, None] - gc[:, None], -jnp.inf))
    kk_off = ein("inchk,inrhk->ihncr", k5 * decay_in, cols)
    qk_off = ein("inchk,inrhk->ihncr", q5 * decay_in, cols)

    def whole(inside, off, strict):
        """[I, H, R, R]: the sub-chunks' own blocks on the diagonal."""
        keep = jnp.tril(jnp.ones((sub, sub), bool), -1 if strict else 0)
        inside = jnp.where(keep[None, None, :, :, None], inside, 0.0)
        eye = jnp.eye(n, dtype=inside.dtype)
        blocks = jnp.einsum("incdh,nm->ihncmd", inside, eye)
        return off.reshape(n_i, h, r, r) + blocks.reshape(n_i, h, r, r)

    beta_h = beta.transpose(0, 2, 1)[..., None]                  # [I, H, R, 1]
    a_mat = whole(kk_in, kk_off, True) * beta_h
    b_mat = whole(qk_in, qk_off, False)
    # (I + A) X = beta [K exp(G) | V]: forward substitution over sub-chunks
    k_dec = k * jnp.exp(gc)
    rhs = jnp.concatenate([k_dec, v], axis=-1).transpose(0, 2, 1, 3) * beta_h
    a_blocks = a_mat.reshape(n_i, h, n, sub, n, sub)
    inv = _block_inverse(jnp.stack(
        [a_blocks[:, :, j, :, j] for j in range(n)], axis=2))    # [I,H,N,C,C]

    # a scan, not ``n`` copies of its body (nor ``I`` of the carry's below):
    # every tiled step program compiles this once a KDA layer it holds. A
    # sub-chunk's row of ``A`` is taken whole: its columns from its own
    # diagonal block on meet rows of ``x`` that are still zero
    def substitute(x, step):
        j, a_rows, rhs_rows, inv_j = step
        mine = rhs_rows - ein("ihcr,ihrx->ihcx", a_rows, x)
        return jax.lax.dynamic_update_slice_in_dim(
            x, ein("ihcd,ihdx->ihcx", inv_j, mine), j * sub, axis=2), None

    def blocks(t):  # [I, H, R, ...] -> [N, I, H, C, ...]
        return jnp.moveaxis(
            t.reshape((n_i, h, n, sub) + t.shape[3:]), 2, 0)

    x, _ = jax.lax.scan(substitute, jnp.zeros_like(rhs),
                    (jnp.arange(n), blocks(a_mat), blocks(rhs),
                     jnp.moveaxis(inv, 2, 0)))
    w, uv = x[..., :kd], x[..., kd:]                             # [I,H,R,K|V]
    q_dec = (q * jnp.exp(gc)).transpose(0, 2, 1, 3)              # [I, H, R, K]
    k_end = (k * jnp.exp(gc[:, -1:] - gc)).transpose(0, 2, 1, 3)
    total = jnp.exp(gc[:, -1]).transpose(0, 2, 1)                # [I, K, H]

    # the carry from tile to tile, in order: U reads the state it feeds
    def tile(after, xs):
        w_i, uv_i, q_i, b_i, k_i, total_i, s0_i, cont_i = xs
        prev = jnp.where(cont_i, after, s0_i)
        u = uv_i - ein("hrk,khv->hrv", w_i, prev)
        y = ein("hrk,khv->rhv", q_i, prev) + ein("hrs,hsv->rhv", b_i, u)
        after = prev * total_i[..., None] + ein("hrk,hrv->khv", k_i, u)
        return after, (y, after)

    s0 = s0.reshape(n_i, kd, h, vd)
    _, (ys, after) = jax.lax.scan(
        tile, jnp.zeros_like(s0[0]),
        (w, uv, q_dec, b_mat, k_end, total, s0,
         cont & (jnp.arange(n_i) > 0)))
    return (ys.reshape(n_i, r, h * vd), after.reshape(n_i, kd, h * vd))


def kda_chunk_xla(state, rows, rows_w, fresh, cont, write, q, k, g, v, beta,
                  sub: int):
    """The same tiles as XLA writes them: a dynamic slice a tile's state,
    ``kda_tiles`` (the chunk form as ~60 operations over all heads and tiles
    at once), a dynamic-update-slice a tile. What runs off the chip, and the
    kernel's yardstick."""
    n_i, r, heads = beta.shape

    def by_head(x):  # [I, R, H x K] -> [I, R, H, K]
        return x.astype(jnp.float32).reshape(n_i, r, heads, -1)

    s0 = jnp.stack([jax.lax.dynamic_index_in_dim(state, rows[i], 0, False)
                    for i in range(n_i)])
    y, s_new = kda_tiles(by_head(q), by_head(k), by_head(v), by_head(g), beta,
                         jnp.where(fresh[:, None, None], 0.0, s0), cont, sub)
    s_new = jnp.where(write[:, None, None], s_new, 0.0)
    for i in range(n_i):
        state = jax.lax.dynamic_update_index_in_dim(state, s_new[i],
                                                    rows_w[i], 0)
    return state, y
