"""Expert FFNs of the inference paths, shared by the MoE families.

``routed_experts`` is the dropless per-token top-k MoE ``mixtral``,
``deepseek`` and ``nemotron_h`` serve through; the families differ in how a
token's combine weights come out of the router's logits (its keyword
arguments), in the expert itself (three matrices, gated by ``silu``, SwiGLU,
or with ``gate_act="relu"`` by ``relu``: ``smallthinker``; or two, ungated
``relu**2``: ``w_gate`` None) and in which of the routed experts the
layer holds (``held``: one rank's share of an expert-parallel deployment). A
pick is of one of three kinds: of an expert the layer holds (computed here), of
a routed expert it does not hold (another rank's part: nothing here), or of a
zero-compute expert (``zero_experts``: the router's outputs past the routed
ones are identity experts, a pick of one adds ``w * h`` and touches no weight;
computed on the token's own chip, so counted ONCE across the ranks). It
has two exact forms of the same computation, and ``expert_form`` picks between
them from the step's shapes. ``routed_experts_einsum`` is the one form that
differentiates and partitions over a mesh, for the callers that need either.
``swiglu`` is the plain gated FFN beside them: a dense layer, shared experts.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax

from deepspeed_tpu.ops.pallas.moe_gmm import ROW_ALIGN, grouped_swiglu

# ``expert_form``'s crossover, in rows of a step. From a sweep of both forms,
# one layer's expert FFN alone on a TPU v5e, at 8 x [4096, 14336] top-2 and
# 64 x [2048, 1408] top-6 (PERF.md section 6, PR 27): at 240 rows the forms
# tie (4.08 / 4.08 ms) or the einsum leads by 2% (1.66 / 1.69 ms), at 256 the
# grouped form leads by 49% and 5%, and the einsum grows by the row from there
GROUPED_MIN_ROWS = 256
# the rows one call of the grouped kernel takes: an expert may be picked by
# all of them, and the kernel holds an expert's rows in VMEM
_GROUPED_MAX_ROWS = 512


def swiglu(h: jnp.ndarray, w_gate, w_up, w_down) -> jnp.ndarray:
    """``w_down(silu(h w_gate) * h w_up)``, weights cast to ``h``'s dtype."""
    dtype = h.dtype
    return (jax.nn.silu(h @ w_gate.astype(dtype)) * (h @ w_up.astype(dtype))
            ) @ w_down.astype(dtype)


def expert_form(rows: int, num_experts: int, top_k: int) -> str:
    """``"grouped"`` or ``"dense"``: the form ``routed_experts`` takes for a
    step of ``rows`` tokens (static under ``jit``: a step program has one).

    The all-experts einsum multiplies every token by every expert and reads
    every expert's weights once. That is ``num_experts / top_k`` times the
    needed FLOPs, and free while the layer is bound by the weight bytes: the
    einsum turns FLOP-bound at the chip's ridge point,
    ``rows > peak FLOP/s / peak bytes/s`` (~240 on a TPU v5e, whatever the
    model; measured there too). Above it the grouped form does the needed
    products only and still reads the weights once; below it the grouped
    form saves nothing (at decode row counts the picks touch every expert)
    and pays for the sort. A router that picks every expert leaves nothing
    to skip at any size.
    """
    grouped = rows >= GROUPED_MIN_ROWS and top_k < num_experts
    return "grouped" if grouped else "dense"


# ``_top_k`` ranks by comparison from this many (experts x picks) on:
# ``lax.top_k`` of 22 among 512 costs a step program 2.5 s of compile where
# its row count is no multiple of 128 (132, 136, ...: most of an engine's
# programs), 6 among 64 and 2 among 8 nothing to speak of (PERF.md 6, PR 31)
_RANKED_TOP_K_MIN = 4096


def _top_k(scores, k: int):
    """``lax.top_k(scores, k)`` over the last axis of ``scores`` [T, E]: the
    same values and indices in the same order, ties to the lower index. A
    large one ranks every score by comparison with every other (``E x E``
    compares a row, fused into the count) and reads the first ``k`` ranks
    off: no sort for the compiler to lay out."""
    e = scores.shape[-1]
    if e * k < _RANKED_TOP_K_MIN:
        return lax.top_k(scores, k)
    i = jnp.arange(e)
    ahead = ((scores[:, None, :] > scores[:, :, None])
             | ((scores[:, None, :] == scores[:, :, None])
                & (i[None, None, :] < i[None, :, None])))
    rank = jnp.sum(ahead, axis=-1, dtype=jnp.int32)               # [T, E]
    hit = rank[:, None, :] == jnp.arange(k)[None, :, None]        # [T, k, E]
    return (jnp.sum(jnp.where(hit, scores[:, None, :], 0.0), axis=-1),
            jnp.sum(jnp.where(hit, i[None, None, :], 0), axis=-1,
                    dtype=jnp.int32))


def _in_best_groups(picking, groups):
    """``picking`` [T, E] with the experts outside a token's best groups at
    ``-inf``. ``groups = (n_group, topk_group)``: the experts are ``n_group``
    runs of neighbours, a group's score is the sum of its two largest
    entries, and the ``topk_group`` best groups stay (DeepSeek-V3's
    group-limited routing: a token's picks lie on few nodes)."""
    n_group, topk_group = groups
    t, e = picking.shape
    group_score = jnp.sum(
        lax.top_k(picking.reshape(t, n_group, e // n_group), 2)[0], axis=-1)
    _, best = lax.top_k(group_score, topk_group)
    stays = jnp.zeros((t, n_group), bool).at[
        jnp.arange(t)[:, None], best].set(True)
    return jnp.where(jnp.repeat(stays, e // n_group, axis=1), picking,
                     -jnp.inf)


def _route(h, router_w, top_k: int, scoring: str, bias, renormalize: bool,
           scale: float, eps: float, groups=None):
    """The router, in float32 -> (combine weights ``[T, top_k]``, picked
    experts ``[T, top_k]``)."""
    logits = h.astype(jnp.float32) @ router_w.astype(jnp.float32)
    if scoring == "softmax":
        scores = jax.nn.softmax(logits, axis=-1)
    elif scoring == "sigmoid":
        scores = jax.nn.sigmoid(logits)
    else:
        raise ValueError(f"unknown router scoring {scoring!r}")
    if bias is None and groups is None:
        topv, topi = _top_k(scores, top_k)
    else:
        picking = scores if bias is None else scores + bias.astype(jnp.float32)
        if groups is not None:
            picking = _in_best_groups(picking, groups)
        _, topi = _top_k(picking, top_k)
        topv = jnp.take_along_axis(scores, topi, axis=-1)
    if renormalize:
        topv = topv / (jnp.sum(topv, axis=-1, keepdims=True) + eps)
    if scale != 1.0:
        topv = topv * scale
    return topv, topi


def _held_picks(topi, held, e: int):
    """The picks as indices into the ``e`` experts the layer holds, ``e`` (one
    past the last) for a pick of an expert it does not hold (another rank's,
    or a zero-compute one: those lie past the ``held[1]`` routed)."""
    if held is None:
        return topi
    local = topi - held[0]
    return jnp.where((local >= 0) & (local < e), local, e)


def _einsum_experts(h, topv, topi, w_gate, w_up, w_down, held=None,
                    gate_act: str = "silu"):
    """Every held expert over every token on the MXU, the router's weights
    (zero for the experts a token did not pick) combining the results; a pick
    of an expert the layer does not hold has no column to land in."""
    t, _ = h.shape
    e = w_up.shape[0]
    w = jnp.zeros((t, e), jnp.float32).at[
        jnp.arange(t)[:, None], _held_picks(topi, held, e)].set(
            topv, **({} if held is None else {"mode": "drop"}))
    dtype = h.dtype
    u = jnp.einsum("td,edf->tef", h, w_up.astype(dtype))
    if w_gate is None:
        a = jnp.square(jax.nn.relu(u))
    else:
        g = jnp.einsum("td,edf->tef", h, w_gate.astype(dtype))
        a = (jax.nn.relu(g) if gate_act == "relu" else jax.nn.silu(g)) * u
    y = jnp.einsum("tef,efd->ted", a, w_down.astype(dtype))
    return jnp.einsum("ted,te->td", y, w.astype(dtype))


# ``_picks_before`` counts in blocks from this many (picks x experts) on: the
# plain cumulative sum compiles in 0.4 s at Moonlight's 3,072 x 64 and in 6.3 s
# at 11,264 x 128 (512 rows x top-22 over 128 held experts), in every step
# program that takes the grouped form (a TPU v5e's compiler, PERF.md 6, PR 31)
_COUNT_BLOCKED_MIN = 2 ** 20
_COUNT_BLOCK = 128


def _picks_before(flat, e: int):
    """``[P, e]`` int32: how many of picks ``0 .. p`` are of expert ``j``
    (``flat`` [P], a pick's expert; ``e`` or more: of none). A cumulative sum
    down the picks; a large one is taken in blocks of ``_COUNT_BLOCK`` picks,
    inside a block as a product with a triangle of ones (exact: ones and
    zeros in bfloat16, whole numbers under 2**24 summed in float32), the
    blocks' totals summed before them, which the compiler takes in a fraction of the time."""
    hits = flat[:, None] == jnp.arange(e)[None, :]
    p = flat.shape[0]
    if p * e < _COUNT_BLOCKED_MIN or p % _COUNT_BLOCK:
        return jnp.cumsum(hits, axis=0, dtype=jnp.int32)
    blocks = hits.reshape(-1, _COUNT_BLOCK, e).astype(jnp.bfloat16)
    inside = jnp.einsum("ts,bse->bte",
                        jnp.tril(jnp.ones((_COUNT_BLOCK,) * 2, jnp.bfloat16)),
                        blocks, preferred_element_type=jnp.float32)
    earlier = jnp.cumsum(inside[:, -1], axis=0) - inside[:, -1]
    return (inside + earlier[:, None]).reshape(p, e).astype(jnp.int32)


# the sorted rows a trip of the held form's two loops takes (``_rows_of`` and
# ``_combine``). From a sweep on a TPU v5e, one layer's expert FFN alone at the
# five held cells' shapes (PERF.md section 6, PR 46), us a layer at 128 / 256
# rows: 202.9 / 206.5 (8 of 64 experts, 2,560 wide), 1,914 / 1,987 (16 of 512,
# 6,144), 801 / 790, 2,186 / 2,186, 2,287 / 2,242 (128 of 512, 1,024): within
# 4% of each other, a trip of either loop costing under 2 us beside its work
_SORTED_BLOCK = 128


def _grouped_experts(h, topv, topi, w_gate, w_up, w_down, first_expert,
                     num_experts, held=None, zero_experts: int = 0,
                     gate_act: str = "silu"):
    """Each pick through its own expert only: the ``T x top_k`` picks are
    sorted by expert (a counting sort: a pick's place is its expert's first
    row plus the picks of that expert before it), every expert's rows go
    through that expert's weights (``ops/pallas/moe_gmm.py``), and the
    results come back to token order for the router's weights. The layer's
    ``num_experts`` experts start at ``first_expert`` of the weights.

    The kernel's row buffer is sized by the SHAPES, for every pick landing on
    an expert of this layer (the router may do that). What is moved around the
    kernel is sized by what the layer holds. ``held`` None, every routed
    expert: every pick has a row, so the rows are gathered and un-sorted
    whole, ``[T x top_k, D]`` either way. With ``held`` (first routed expert
    held, experts routed over) a pick of an expert the layer does not hold
    sorts past the last group and gets no row, as does a pick of one of the
    ``zero_experts`` outputs past the routed ones; the rows that exist end at
    ``row_end``, a value of the step, and only those are gathered
    (``_rows_of``) and combined (``_combine``), a block at a time from the
    sorted side: all of them at any routing, the whole buffer when every
    pick is held."""
    t, d = h.shape
    e, k = num_experts, topi.shape[1]
    if t > _GROUPED_MAX_ROWS:
        pad = -t % _GROUPED_MAX_ROWS
        # the rows that pad the last call (zeros, at weight zero) pick as a
        # real row does, ``top_k`` DIFFERENT experts: with every expert held,
        # experts 0 .. top_k - 1, one row of each, so that no expert of a call
        # gets more rows than the call has (``max_rows``: all the kernel's
        # VMEM holds of an expert; as ``top_k`` picks of expert 0 a row, 384
        # padding rows of top-4 ran its DMA out of bounds on the chip). With
        # ``held`` they pick an expert the layer does not hold (-1) and get
        # no row
        parts = [jnp.pad(a, ((0, pad), (0, 0)), constant_values=c).reshape(
            -1, _GROUPED_MAX_ROWS, a.shape[1])
            for a, c in ((h, 0), (topv, 0), (topi, -1))]
        if held is None:
            parts[2] = jnp.where(parts[2] < 0, jnp.arange(k, dtype=topi.dtype),
                                 parts[2])
        out = lax.map(lambda p: _grouped_experts(
            *p, w_gate, w_up, w_down, first_expert, e, held, zero_experts,
            gate_act), tuple(parts))
        return out.reshape(-1, d)[:t]
    # The kernel's shapes are those of a full call whatever ``t`` is, so
    # that every step program of an engine shares ONE traced kernel
    # (``grouped_swiglu``); the rows past the step's own are never read.
    # A pass of the kernel is 128 rows where an expert's share of a full call
    # is more than 64 (128 at Mixtral's 2 of 8), else 64 (48 at Moonlight's
    # 6 of 64; 8 at 12 of 512 + 256 zero-compute outputs): one pass an expert
    # either way. ``rows``, the buffer's SHAPE, is the worst case: every pick
    # of a full call on an expert of this layer, each expert's rows rounded
    # up to ``ROW_ALIGN``, and a pass past the last. A shape costs nothing:
    # the kernel copies the rows its experts got and no other, and with
    # ``held`` so does everything around it (below).
    outputs = (e if held is None else held[1]) + zero_experts
    tm = 128 if _GROUPED_MAX_ROWS * k > 64 * outputs else 64
    flat = _held_picks(topi, held, e).reshape(-1)
    before = _picks_before(flat, e)
    counts = before[-1]
    aligned = -(-counts // ROW_ALIGN) * ROW_ALIGN
    row0 = jnp.cumsum(aligned) - aligned
    place = row0[flat] + jnp.take_along_axis(before, flat[:, None], 1)[:, 0] - 1
    rows = (-(-(_GROUPED_MAX_ROWS * k + e * (ROW_ALIGN - 1)) // ROW_ALIGN)
            * ROW_ALIGN + tm)
    dtype = h.dtype

    def experts_of(x):
        return grouped_swiglu(
            x, None if w_gate is None else w_gate.astype(dtype),
            w_up.astype(dtype), w_down.astype(dtype), row0, counts, tm,
            max_rows=_GROUPED_MAX_ROWS, first_expert=first_expert,
            gate_act=gate_act)

    if held is None:
        token = jnp.zeros((rows,), jnp.int32).at[place].set(
            jnp.arange(t * k, dtype=jnp.int32) // k)
        y = experts_of(h[token])[place]
        return jnp.einsum("tkd,tk->td", y.reshape(t, k, d), topv).astype(dtype)
    # a sorted row's token (``t``: no pick landed there) and router weight
    place = jnp.where(flat < e, place, rows)
    token = jnp.full((rows,), t, jnp.int32).at[place].set(
        jnp.arange(t * k, dtype=jnp.int32) // k, mode="drop")
    weight = jnp.zeros((rows,), jnp.float32).at[place].set(
        topv.reshape(-1), mode="drop")
    blocks = lax.div(row0[-1] + aligned[-1] + (_SORTED_BLOCK - 1),
                     _SORTED_BLOCK)
    y = experts_of(_rows_of(h, token, blocks, rows))
    return _combine(y, token, weight, blocks, t).astype(dtype)


def _rows_of(h, token, blocks, rows: int):
    """``[rows, D]``: row ``r`` is ``h[token[r]]`` in the first ``blocks``
    blocks of ``_SORTED_BLOCK`` sorted rows (``blocks`` a value of the step:
    the loop lowers to a ``while``), written in place into a buffer that is
    never initialised: what lies past them is nothing meant, as the rows
    between the experts are, and the kernel copies none of it."""
    t, d = h.shape

    def gather(b, x):
        r0 = b * _SORTED_BLOCK
        # a last block past the buffer's end starts early, in both alike
        tok = lax.dynamic_slice(token, (r0,), (_SORTED_BLOCK,))
        return lax.dynamic_update_slice(x, h[jnp.minimum(tok, t - 1)],
                                        (r0, 0))

    return lax.fori_loop(0, blocks, gather, lax.empty((rows, d), h.dtype))


def _combine(y, token, weight, blocks, t: int):
    """float32 ``[t, D]``: every sorted row ``r`` of the first ``blocks``
    blocks on which a pick landed adds ``weight[r] * y[r]`` to row
    ``token[r]``: the un-sort and the router's combine in one step, a block
    as one product with the block's one-hot ``[t, block]`` (``HIGHEST``:
    float32 on either side, as the sum it replaces). A row on which no pick
    landed may hold anything, NaN too, and is selected out, not multiplied
    by zero."""
    rows, d = y.shape

    def combine(b, out):
        r0 = b * _SORTED_BLOCK
        tok = lax.dynamic_slice(token, (r0,), (_SORTED_BLOCK,))
        w = lax.dynamic_slice(weight, (r0,), (_SORTED_BLOCK,))
        # a last block past the buffer's end starts early (``dynamic_slice``
        # clamps): its rows before ``r0`` were the block before's
        r = jnp.minimum(r0, rows - _SORTED_BLOCK) + jnp.arange(_SORTED_BLOCK)
        meant = (tok < t) & (r >= r0)
        part = jnp.where(
            meant[:, None],
            w[:, None] * lax.dynamic_slice(y, (r0, 0), (_SORTED_BLOCK, d)),
            0.0)
        onehot = tok[None, :] == jnp.arange(t)[:, None]
        return out + jnp.dot(onehot.astype(jnp.float32), part,
                             precision=lax.Precision.HIGHEST)

    return lax.fori_loop(0, blocks, combine, jnp.zeros((t, d), jnp.float32))


def routed_experts(h: jnp.ndarray, router_w, w_gate, w_up, w_down,
                   top_k: int, *, stacked=None, scoring: str = "softmax",
                   bias=None, renormalize: bool = True, scale: float = 1.0,
                   eps: float = 1e-9, held=None, router_h=None,
                   groups=None, zero_experts: int = 0,
                   count_picks: bool = False,
                   gate_act: str = "silu") -> jnp.ndarray:
    """Dropless per-token top-k MoE for the serving paths (``h`` [T, D]
    flat tokens): exact (no capacity, no drops, every pick computed), bf16
    operands with float32 accumulation in either form.

    Role parity with the reference's ragged MoE serving stack
    (``inference/v2/model_implementations/mixtral/model.py`` +
    ``inference/v2/kernels/ragged_ops`` top-k gating, MoE gather/scatter):
    the CUDA version compacts tokens per expert with gather/scatter kernels.
    Here a step above the chip's ridge point does the same (sort the picks
    by expert, one grouped matmul kernel that reads an expert's weights
    once, un-sort), and a step below it runs the batched [E] einsum, every
    expert over every token: ``expert_form`` has the rule and its reason.
    The grouped kernel is a Mosaic kernel: it has no gradient and GSPMD
    cannot partition it, so this function is for one device's forward pass
    (the ragged engine's step programs).

    The weights are the layer's, ``[E, ...]``. A layer of a scan also gives
    ``stacked``: every layer's weights whole and this layer's place in them,
    ``(w_gate, w_up, w_down, first_expert)`` of ``expert_stacks``. The
    grouped form reads those, because a Mosaic kernel's operand is an array
    in HBM and a scan's slice of the weights would be copied to become one;
    the einsum form reads the slice, which XLA fuses into the einsum (given
    the whole stack it re-lays all of it out, every step, outside the loop).
    Whichever a step program does not read is dead code in it.

    Routing, in float32: scores are ``softmax`` (Mixtral) or ``sigmoid``
    (DeepSeek-V3) of the router's logits; the ``top_k`` experts are picked
    by ``scores + bias`` (``bias`` [E]: the auxiliary-loss-free selection
    bias, which never enters a weight); the picked scores are divided by
    their sum (``renormalize``) and multiplied by ``scale``. The defaults
    are Mixtral's. ``groups = (n_group, topk_group)`` limits the picks to a
    token's best groups of neighbouring experts (``_in_best_groups``).

    The expert: ``w_down(silu(x w_gate) * x w_up)``; with ``gate_act="relu"``
    (static) ``w_down(relu(x w_gate) * x w_up)``; or with ``w_gate`` None
    the ungated ``w_down(relu(x w_up)**2)`` of two matrices (``stacked``
    then has None in ``w_gate``'s place).

    One rank's share: ``held = (first, routed)`` says that the weights are
    experts ``first .. first + E - 1`` of the ``routed`` the router scores.
    The router still scores and picks over all of them and the weights are
    normalised over all the picks, as published; the layer computes the part
    its own experts give, and what the absent ones would add is left out
    (the other ranks' parts, which the deployment adds up). ``held`` None:
    the layer holds every routed expert.

    ``router_h``: what the router scores where that is not what the experts
    compute on (experts in a latent space, routed on the full hidden state;
    a router that reads the layer's input, before its norm and attention).

    Zero-compute experts: with ``zero_experts = Z`` the router scores ``routed
    + Z`` outputs, and a pick of one of the last ``Z`` is an identity expert:
    it adds its weight times ``h`` and gets no column in the einsum and no
    row in the sort. Every rank computes that part for its own tokens, so a
    deployment adds it up once, not once a rank. ``count_picks`` also returns
    ``[T, 2]`` int32, a token's zero-compute picks and its picks of held
    experts (what a step program hands back for ``engine/dispatch``).
    """
    topv, topi = _route(h if router_h is None else router_h, router_w, top_k,
                        scoring, bias, renormalize, scale, eps, groups)
    e = w_up.shape[0]
    held = _with_zero_experts(router_w, held, e, zero_experts)
    routed = e if held is None else held[1]
    if expert_form(h.shape[0], routed, top_k) == "dense":
        y = _einsum_experts(h, topv, topi, w_gate, w_up, w_down, held,
                            gate_act)
    else:
        y = _grouped_experts(h, topv, topi,
                             *(stacked or (w_gate, w_up, w_down, 0)), e, held,
                             zero_experts, gate_act)
    if zero_experts:
        y = y + _identity_part(h, topv, topi, routed)
    if not count_picks:
        return y
    return y, jnp.stack(
        [jnp.sum(topi >= routed, axis=-1, dtype=jnp.int32),
         jnp.sum(_held_picks(topi, held, e) < e, axis=-1, dtype=jnp.int32)],
        axis=-1)


def _with_zero_experts(router_w, held, e: int, zero_experts: int):
    """``held`` for a router whose last ``zero_experts`` outputs are
    zero-compute experts: a layer that holds every routed expert still has
    picks with no column (``(0, e)``); the router's width is checked."""
    if not zero_experts:
        return held
    held = (0, e) if held is None else held
    if router_w.shape[-1] != held[1] + zero_experts:
        raise ValueError(
            f"the router scores {router_w.shape[-1]} outputs, not {held[1]} "
            f"routed + {zero_experts} zero-compute experts")
    return held


def _identity_part(h, topv, topi, routed: int):
    """What a token's picks of zero-compute experts (``topi >= routed``) add:
    the sum of their weights times ``h``."""
    w = jnp.sum(jnp.where(topi >= routed, topv, 0.0), axis=-1)
    return (w[:, None] * h.astype(jnp.float32)).astype(h.dtype)


def expert_stacks(layers: dict):
    """For a serving scan over the stacked ``layers``: ``(layers, stacks)``,
    ``stacks`` the expert weights whole, ``[L, E, ...]`` as ``[L x E, ...]``
    (the same bytes; None for ``w_gate`` where the experts have none), for
    the scan's body to close over, and ``layers`` with ``first_expert`` [L]
    beside the weights, so that a layer can hand ``routed_experts`` its
    ``stacked``. Weights that are not plain arrays
    (weight-only quantization dequantizes a layer's slice) have no stacks:
    ``(layers, None)``."""
    weights = [layers.get(k) for k in ("w_gate", "w_up", "w_down")]
    if not all(isinstance(w, jax.Array) for w in weights if w is not None):
        return layers, None
    n, e = weights[1].shape[:2]
    first = jnp.arange(n, dtype=jnp.int32) * e
    return ({**layers, "first_expert": first},
            tuple(w if w is None else w.reshape((n * e,) + w.shape[2:])
                  for w in weights))


def routed_experts_einsum(h: jnp.ndarray, router_w, w_gate, w_up, w_down,
                          top_k: int, *, scoring: str = "softmax", bias=None,
                          renormalize: bool = True, scale: float = 1.0,
                          eps: float = 1e-9, held=None, router_h=None,
                          groups=None, zero_experts: int = 0,
                          gate_act: str = "silu") -> jnp.ndarray:
    """``routed_experts`` in its einsum form at every row count: plain XLA,
    so it differentiates (``deepseek``'s training-shaped ``forward`` /
    ``loss_fn``) and partitions over a mesh (``mixtral``'s dense-cache
    inference layer under ``InferenceEngine``'s tensor-parallel mesh)."""
    topv, topi = _route(h if router_h is None else router_h, router_w, top_k,
                        scoring, bias, renormalize, scale, eps, groups)
    held = _with_zero_experts(router_w, held, w_up.shape[0], zero_experts)
    y = _einsum_experts(h, topv, topi, w_gate, w_up, w_down, held,
                        gate_act=gate_act)
    if zero_experts:
        y = y + _identity_part(h, topv, topi, held[1])
    return y
