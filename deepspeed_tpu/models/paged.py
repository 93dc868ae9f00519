"""Shared KV-cache plumbing for the model families' inference paths.

One home for what every family's serving path shares (gpt2, llama, mixtral
on K and V heads; deepseek, deepseek_v32, longcat_flash on a latent row;
nemotron_h, granite_hybrid, jamba, kimi_linear, solar_open2 and lfm2_moe with
a recurrent state a slot beside either;
smallthinker with sliding-window layers in a second pool): the
paged-pool write, the decode/tiled-prefill attention split over the block
pool (reference ``inference/v2/ragged_ops`` layout), the scan over a stack of
one or several kinds of layer, and the dense-cache append+attend used by the
v1-style engines.

**The paged contract** (docs/SERVING.md "The paged KV pool"), stated once:

- *Storage.* ``cache["k"]`` / ``cache["v"]`` are ``[L, NB, BS, Hkv*D]``:
  a block's ``BS`` token rows are contiguous and a row is one lane-dense
  vector of all KV heads (GPT-2 XL 1600 lanes, Mixtral / Llama-3-8B 1024).
  A ``kvquant.QuantizedKV`` pool keeps its payload in that form and its
  scales, one per (row, head), as one lane-dense row a block,
  ``[L, NB, BS*Hkv]``.
- *Block axis.* Inside a step program a layer sees the pool with ``L`` and
  ``NB`` merged (``[L*NB, BS, Hkv*D]``, a bitcast) and addresses it through
  ITS block table, ``block_tables + layer * NB``. It never owns a slice.
- *Block layers.* ``L`` counts BLOCK layers, the rows a token has in a leaf,
  and the family's ``init_paged_cache`` is who counts them: a model layer
  with one attention owns one, a model layer with two attention sublayers
  (``longcat_flash``) owns two neighbours, ``2 i`` and ``2 i + 1``, and is
  handed both tables (``scan_layers_paged(block_layers=2)``). Nothing else
  knows: bytes a cached token, allocation, release, the prefix cache's
  splice, preemption and containment all read the leaves (``a.shape[0]``
  rows a token), never a config's ``num_layers``.
- *Who may index what.* A step program touches the block axis only through
  a block table: ``write_kv_paged`` writes the step's ``T`` rows in place
  (a prefill tile's rows as slices of the one or two blocks a run of ``BS``
  rows lies in, decode rows as one scatter of single rows:
  ``write_rows_paged``), the XLA gather and the two Pallas kernels read the
  blocks the table names. Host-side code between steps (``_gather_blocks``,
  ``_scatter_blocks``, tiers, prefix cache, ``KVHandoff``) indexes blocks
  as ``a[:, ids]`` on ``[L, NB, ...]``.
- A step program must never hold an array the size of a layer's slice of
  the pool (``tests/unit/test_compile_tpu.py`` asserts it on the compiled
  program): its pool traffic is the rows written plus the context read.
- *Slot leaves.* A model whose layers carry a recurrent state (constant
  size, whatever the context: a state-space layer, ``nemotron_h``'s
  Mamba-2, whose state a head decays by one scalar and is fed an outer
  product; a linear-attention layer, ``kimi_linear``'s Kimi Delta Attention,
  whose state a key channel decays by its own factor and is fed by the
  delta rule) keeps it beside the block leaves, K and V heads or a latent
  row alike (``kimi_linear``: ``{"kv": [L_mla, NB, BS, 640], "slots":
  {...}}``; ``solar_open2``, the same KDA state beside ``{"k", "v"}``;
  ``lfm2_moe``, whose gated short convolution carries its last two input
  rows and NOTHING else: its slot leaves are a window leaf alone, below),
  under the cache's ``"slots"`` key: leaves ``[L_s, S, ...]``,
  one row a layer and engine slot, ``S = max_seqs + 1``; the last
  row is the scratch slot padding rows use, as block 0 is for the pool.
  ``block_leaves`` / ``slot_leaves`` tell the two apart. The engine owns
  both in ``engine.cache`` and the family's ``init_paged_cache`` makes both
  (it receives ``num_slots``). Everything above about blocks (bytes a
  token, block operations between steps, tiers, hand-off) sees block
  leaves only; nothing on the host indexes a slot leaf.
- A step program sees a slot leaf with ``L_s`` and ``S`` merged and
  addresses it by ``slots + layer * S``. It reads the rows of the step's
  slots and writes them back in place (donation): it never holds an array
  the size of a layer's slice of the state either. A slot is zeroed INSIDE
  the step: a row or tile at position 0 starts from zeros whatever the
  slot held (a reused slot, a preempted request recomputed), so there is
  no reset program. Padding rows read and write the scratch slot and leave
  it zero.
- *Window leaves.* A causal convolution's carried rows (a slot's last
  ``K - 1`` inputs of ``W`` channels, oldest first: every such family's) are
  a slot leaf whose slot is WHOLE TILES, as the float32 state's is: ``[L_s,
  S, (K - 1) x r, W / r]``, each row's channels folded over the ``r`` rows
  of the dtype's sublane tile (16 bfloat16: ``kimi_linear`` ``[10, 129, 48,
  768]``, ``nemotron_h`` ``[5, 129, 48, 640]``, ``lfm2_moe`` ``[9, 513, 32,
  128]``, the one family with no other slot leaf: ``models/shortconv.py``;
  row ``j`` is rows ``[r j, r j + r)``). With the ``K - 1`` rows themselves on the sublanes (``[..,
  3, 12288]``) the compiler keeps the argument in an axis order of its own,
  copies the whole leaf to a padded layout and back every step and, short
  of memory, compresses and uncompresses it between the layers (9.4% of
  the Kimi-Linear cell's device time, PERF.md section 6, PR 41); as one
  flat row a slot the layers x slots merge is a copy. A width that ``r x
  128`` does not divide keeps ``[L_s, S, K - 1, W]`` (``granite_hybrid``:
  8,448 channels are 66 lane tiles, which no whole bfloat16 tile folds; its
  step programs copy the 30 MB leaf to the compiler's axis order and back,
  PERF.md section 7). A width that HALF the tile's rows fold (``r`` = 8
  bfloat16: ``jamba``'s 5,120 channels are 40 lane tiles, ``[26, 257, 24,
  640]``) takes that fold: a slot is then a tile and a half, padded to two in
  memory, and still enters a step program row-major with nothing of the
  leaf's size copied, where ``[.., 3, 5120]`` was copied whole, twice a
  step (205 MB each way at the Jamba cell's sizes, PERF.md section 6, PR
  53). ``init_window_leaf``
  builds the leaf, ``read_windows`` / ``write_windows`` / ``window_fold``
  read the form off the array, ``decode_windows`` / ``tile_windows`` are a
  step's decode rows and prefill tiles through them: one gather and one
  scatter of the step's rows a layer and nothing else of the leaf's size.
- What a prefix of blocks cannot restore, refuses: the engine raises at
  construction for ``enable_prefix_cache``, ``kv_tier``, ``KVHandoff``
  (all need a state snapshot at a block boundary) and a quantized pool,
  when the cache has slot leaves.
- *Sliding leaves.* A model some of whose attention layers never read a row
  older than ``W`` positions (``smallthinker``: 39 of 52 layers, ``W`` =
  4,096) keeps those layers' K and V in a SECOND group of block leaves,
  under the cache's ``"swa"`` key (``{"k", "v"}`` of ``[L_w, NB_w, BS,
  Hkv*D]``: the same storage form, its own ``L`` and its own ``NB``), with
  a block table and a free list of their own: TWO tables of one width and
  one addressing (``positions // BS``), so a kernel's index maps are the
  full layers'. ``sliding_leaves`` / ``full_leaves`` tell the groups apart;
  ``block_leaves`` is both (bytes a cached token count every row a token
  has). A step program is handed ``(full table, sliding table)`` and a
  layer of kind ``"swa"`` addresses the sliding leaves through the second,
  ``sliding table + layer * NB_w`` (``_scan_periods``). **The slide**: the
  engine returns a sequence's sliding block ``b`` to its free list once
  ``pos - W + 1 > BS b + BS - 1`` for the next query position ``pos``, and
  points the table's entry at the scratch block, so a sequence holds at
  most ``sliding_blocks_per_seq(W, BS)`` = ``W / BS + 1`` sliding blocks
  between steps whatever its length (``NB_w`` = ``max_seqs`` times that,
  and the scratch block: the family's ``init_paged_cache`` sizes the pool by
  the function the engine reserves by, from ``num_slots``) and the kernels of such a
  layer never follow an entry before the window's first block
  (``ops/pallas/paged_attention.py``, ``window``). A model says it has a
  window through ``ModelSpec.sliding_window``. What a prefix of blocks
  cannot restore refuses here too: ``enable_prefix_cache``, ``kv_tier``,
  ``KVHandoff`` (a prefix's sliding blocks are gone by the time it could
  be shared) and a quantized pool raise at construction.
- *Blocks of rows.* A model that generates by diffusion over blocks
  (``sdar``: ``ModelSpec.block_gen``, ``B`` positions denoised together)
  changes no leaf and no addressing, only WHO SEES WHOM: a row at position
  ``i`` attends over keys ``j <= (i | (B - 1))``, every row of its own block
  and of the blocks before it (``ragged_pool_attention(block=B)``; the mask is
  stated here and in ``ops/pallas/paged_attention.py``). A step's decode
  region is then whole blocks, ``B`` consecutive rows a sequence at ``p0 ..
  p0 + B - 1`` with ``p0`` a multiple of ``B``: the step scatters the block's
  K and V as they are in this pass (a position still masked holds the mask
  token's) before its attention reads them, so a denoise pass uses the pool's
  rows ``p0 .. p0 + B - 1`` as its workspace and the COMMIT pass, the block
  finished, leaves there what later blocks read. A pool block and a prefill
  tile are multiples of ``B``, so a prompt's whole blocks are cached by tiles
  under the same mask and no block of rows straddles a pool block.
- *Rows to heads.* A step program reads a layer's weights where the stack
  keeps them and re-lays none of them out. A ragged layer projects its rows
  to heads through ``rows_to_heads``, which pins the product to the layout
  the stored ``[D, heads x head_dim]`` weight gives it: what follows (a
  rotation's halves, a head norm, a kernel's wrapper) reads the product
  head-major, and left to itself the compiler pays for that on the WEIGHT,
  sliced out of its stack and transposed in every layer of every step (32 MB
  of ``wq`` in Mixtral, for a product of 3 MB at 392 rows and 32 KB at 4:
  PERF.md section 6, PR 50), where pinned it copies the rows.
  ``tests/unit/test_compile_tpu.py::test_step_program_relays_out_no_projection_weight``
  holds every family's compiled step to it, and is the check for the next.
  The rule's second clause is for the ROWS of a product batched over heads
  (MLA's absorbed pair, ``thn,lhn`` and ``htl,lhv``): the chip writes and
  reads such a product head-major, ``[H, T, lanes]``, so the rows stay
  head-major into and out of a kernel that shares its keys across heads
  (``latent_queries`` / ``head_major``: a step's TILE rows, most of them,
  reach ``mla_prefill`` / ``dsa_attn_prefill`` as the product wrote them,
  the product's result itself an operand, and come back as the value
  product reads them; the few decode rows turn, their kernels walk a row at
  a time). Token-major, the tile rows cost three re-layouts and a
  concatenation a layer and step (~450 MB of traffic at the sparse cell's
  128 heads, PERF.md section 6, PR 56);
  ``test_latent_tile_rows_cross_memory_once_each_way`` holds the compiled
  steps to it.
"""

from __future__ import annotations

from functools import lru_cache, partial

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax


def init_paged_pool(num_layers: int, num_blocks: int, block_size: int,
                    kv_heads: int, head_dim: int, dtype, codec=None) -> dict:
    """The blocked KV pool in the contract's storage form: ``{"k", "v"}`` of
    ``[L, NB, BS, Hkv*D]`` (block 0 is the scratch block padding tokens write
    into). With a ``kvquant.KVQCodec`` the pool is built at storage
    precision, payload plus one scale per (row, head) (``[L, NB, BS*Hkv]``)."""
    shape = (num_layers, num_blocks, block_size, kv_heads * head_dim)
    if codec is None:
        return {"k": jnp.zeros(shape, dtype), "v": jnp.zeros(shape, dtype)}
    from deepspeed_tpu.ops.kvquant import QuantizedKV

    def pool():
        return QuantizedKV(
            jnp.zeros(shape, codec.storage_dtype),
            jnp.zeros(shape[:2] + (block_size * kv_heads,), codec.scale_dtype),
            codec.name, jnp.dtype(dtype).name)

    return {"k": pool(), "v": pool()}


SLOTS = "slots"  # the cache key of the slot leaves (module doc)
SWA = "swa"      # the cache key of the sliding leaves (module doc)


def slot_leaves(cache):
    """The cache's slot leaves, ``[L_s, S, ...]`` each (None: it has none)."""
    return cache.get(SLOTS) if isinstance(cache, dict) else None


def block_leaves(cache):
    """The cache without its slot leaves: every leaf ``[L, NB, ...]``."""
    if slot_leaves(cache) is None:
        return cache
    return {k: v for k, v in cache.items() if k != SLOTS}


def sliding_leaves(cache):
    """The cache's sliding leaves, ``[L_w, NB_w, ...]`` each (None: it has
    none): the block leaves of the layers that attend over a window."""
    return cache.get(SWA) if isinstance(cache, dict) else None


def sliding_blocks_per_seq(window: int, block_size: int) -> int:
    """The sliding blocks a sequence holds between steps at most: the blocks
    a window can span wherever it starts in one. The one rule by which a
    family sizes its sliding pool (``max_seqs`` times this, and the scratch
    block) and the engine reserves a sequence's share of it."""
    return -(-window // block_size) + 1


def full_leaves(cache):
    """The block leaves of the layers that attend over every row."""
    return {k: v for k, v in block_leaves(cache).items() if k != SWA}


def init_window_leaf(layers: int, slots: int, taps: int, width: int, dtype):
    """The slot leaf of a causal convolution's carried rows (module doc,
    *Window leaves*): a slot's last ``taps`` rows of ``width`` channels,
    oldest first, ``[L_s, S, taps x r, width / r]`` with ``r`` the rows of
    the dtype's sublane tile (8 float32, 16 bfloat16), half of them for a
    width that only ``r / 2 x 128`` divides, or ``r = 1`` (``[L_s, S, taps,
    width]``) for a width that neither does."""
    full = 32 // jnp.dtype(dtype).itemsize
    r = next((r for r in (full, full // 2) if width % (r * 128) == 0), 1)
    return jnp.zeros((layers, slots, taps * r, width // r), dtype)


def window_fold(leaf, x):
    """``x`` [..., W] (rows, or weights a channel) with its channels as the
    window leaf keeps them: ``[..., r, W / r]``."""
    lanes = leaf.shape[-1]
    return x.reshape(x.shape[:-1] + (x.shape[-1] // lanes, lanes))


def read_windows(leaf, rows, width: int):
    """The windows of the merged leaf's ``rows``: ``[n, taps, r, W / r]``
    (``.reshape(n, taps, W)`` is the rows themselves)."""
    lanes = leaf.shape[-1]
    return leaf[rows].reshape(rows.shape[0], -1, width // lanes, lanes)


def write_windows(leaf, rows, win):
    """Scatter ``win`` (``[n, taps, r, W / r]`` or ``[n, taps, W]``) into the
    merged leaf's ``rows``, in place."""
    return leaf.at[rows].set(win.reshape((rows.shape[0],) + leaf.shape[1:]))


def decode_windows(leaf, rows, new, fresh, real):
    """A step's decode rows ``new`` [n, W] behind their slots' windows:
    ``(win [n, taps + 1, r, W / r], leaf)``, the leaf with each window moved
    on by its row. A ``fresh`` row (position 0) starts from zeros whatever
    the slot held; one that is not ``real`` (padding) leaves its slot, the
    scratch slot, as it was."""
    tail = read_windows(leaf, rows, new.shape[-1])
    # the rows are folded as a copy of their own (3 MB at 128 rows of 12,288):
    # handed the fold behind the projection that made them, the compiler
    # transposes the layer's whole projection matrix (56 MB) every step so
    # as to emit the rows folded (``tests/unit/test_compile_tpu.py``)
    new = lax.optimization_barrier(new)
    win = jnp.concatenate([jnp.where(fresh[:, None, None, None], 0, tail),
                           window_fold(leaf, new)[:, None]], axis=1)
    return win, write_windows(
        leaf, rows, jnp.where(real[:, None, None, None], win[:, 1:], tail))


def tile_windows(leaf, rows, rows_w, tiles, cont, fresh, write, valid):
    """A step's prefill ``tiles`` [n_i, R, W] behind their windows: ``(win
    [n_i, taps + R, W], leaf)``. Tile ``i`` goes on where tile ``i - 1``
    ended if it ``cont``inues that tile's slot, from zeros if it is ``fresh``
    (position 0), else from the window of ``rows[i]``. Its first
    ``valid[i]`` rows are valid: where ``write[i]``, the ``taps`` rows before
    the first that is not are the new window of ``rows_w[i]``; elsewhere
    ``rows_w[i]`` is the scratch slot's row (a tile that is not its slot's
    last of the step, a padding tile) and is written zeros."""
    n_i, r, w = tiles.shape
    taps = leaf.shape[-2] * leaf.shape[-1] // w
    carried = jnp.concatenate([jnp.zeros((1, taps, w), tiles.dtype),
                               tiles[:-1, r - taps:]])
    held = read_windows(leaf, rows, w).reshape(n_i, taps, w)
    tail = jnp.where(cont[:, None, None], carried,
                     jnp.where(fresh[:, None, None], 0, held))
    win = jnp.concatenate([tail, tiles], axis=1)
    new_tail = jax.vmap(
        lambda x, v: lax.dynamic_slice_in_dim(x, v, taps, axis=0))(win, valid)
    return win, write_windows(
        leaf, rows_w, jnp.where(write[:, None, None], new_tail, 0))


def stack_plan(pattern: str) -> tuple[str, str, int]:
    """``(lead, period, repeats)`` with ``pattern == lead + period *
    repeats``, ``repeats`` >= 2, and ``len(lead) + len(period)`` (the layers
    a step program compiles) the least: what ``scan_layers_paged`` can run
    as leading layers and a scan over a period. A pattern that is no such
    thing raises."""
    n = len(pattern)
    best = None
    for lead in range(n):
        for period in range(1, (n - lead) // 2 + 1):
            rest = pattern[lead:]
            if len(rest) % period == 0 \
                    and rest == rest[:period] * (len(rest) // period) \
                    and (best is None or lead + period < sum(best[:2])):
                best = (lead, period, len(rest) // period)
    if best is None:
        raise NotImplementedError(
            f"layer pattern {pattern!r} is not leading layers followed by a "
            "repeated period: the layer scan cannot express it")
    lead, period, repeats = best
    return pattern[:lead], pattern[lead:lead + period], repeats


@lru_cache(maxsize=None)
def stack_plan_tail(pattern: str) -> tuple[str, str, int, str]:
    """``(lead, period, repeats, tail)`` with ``pattern == lead + period *
    repeats + tail``, ``stack_plan`` of what comes before the tail, and
    ``lead + period + tail`` (the layers a step program compiles) the least:
    what ``_scan_periods`` runs for a published order that ends off its
    period."""
    best = None
    for t in range(len(pattern)):
        try:
            plan = (*stack_plan(pattern[:len(pattern) - t]),
                    pattern[len(pattern) - t:])
        except NotImplementedError:
            continue
        if best is None or len(plan[0] + plan[1] + plan[3]) \
                < len(best[0] + best[1] + best[3]):
            best = plan
    if best is None:
        raise NotImplementedError(
            f"layer pattern {pattern!r} has no repeated period")
    return best


def scan_layers_paged(layer_fn, x, layers, pool, block_tables, lead=(),
                      block_layers: int = 1, tail=()):
    """Run ``layer_fn(x, lp, pool, layer_tables) -> (x, pool)`` over the
    stacked ``layers``. ``pool`` is the family's paged cache, any pytree of
    ``[L, NB, ...]`` arrays (``{"k", "v"}`` of fp arrays or of
    ``kvquant.QuantizedKV``; the one-leaf latent pool of ``deepseek``). It
    is carried through the scan WHOLE, with ``L`` and ``NB`` merged into one
    block axis; a layer addresses it through ``layer_tables = block_tables +
    layer * NB`` and never gets a slice, so per step and layer the pool
    traffic is the rows the layer scatters plus the blocks its attention
    reads: nothing proportional to ``NB``.

    A stack that is not homogeneous puts its leading layers of another kind
    in ``lead``, ``[(fn, lp), ...]`` with ``fn`` as ``layer_fn``: they run
    before the scan at layers ``0 .. len(lead) - 1`` of the pool and the
    scanned layers follow them, so ``L`` counts every layer.

    A layer with more than one attention owns ``block_layers`` neighbouring
    block layers, one a sublayer: layer ``i`` is handed the tuple of their
    tables, ``block_tables + (i * block_layers + a) * NB``, and what it
    carries from one sublayer to the next (a residual, a branch computed
    after the first and added after the last) stays inside ``layer_fn``.

    A stack of several kinds of layer, some with no blocks and some with a
    state a slot, gives ``layer_fn`` as a period of layers instead of one
    function: ``_scan_periods`` below has that form's arguments (and its
    ``tail``, the layers after the last whole period). A stack whose order is
    runs of one kind and no period (five of one, one of another, four of the
    first) goes through ``scan_runs_paged``.

    Which form a new family of mixed layers takes: this one wherever
    ``stack_plan`` finds a period that repeats, so that a step program
    compiles one body a position of the period whatever the depth
    (``nemotron_h``, ``kimi_linear``, ``smallthinker``). ``scan_runs_paged``
    only where the published order has no such period and ``stack_plan``
    would call most of the stack one layer at a time as ``lead``: on
    ``granite_hybrid``'s ``m m m m m a m m m m`` that form (``m m m m m a``
    called, ``m`` x 4 scanned: seven bodies, not three) compiled 108 s for
    68, and its decode step ran 21.06 ms for 20.15 (PERF.md section 6,
    PR 51: one pair of cold runs on the chip, one seed).
    """
    if not callable(layer_fn):
        return _scan_periods(layer_fn, x, layers, pool, block_tables, lead,
                             tail)
    if tail:
        raise ValueError("a tail follows a scan over periods of layers only")
    leaves = jax.tree_util.tree_leaves(pool)
    n_layers, nb = leaves[0].shape[:2]
    n_lead = len(lead)
    if n_layers % block_layers:
        raise ValueError(f"the cache's leaves hold {n_layers} block layers, "
                         f"no multiple of a layer's {block_layers}")

    def tables(i):
        if block_layers == 1:
            return block_tables + i * nb
        return tuple(block_tables + (i * block_layers + a) * nb
                     for a in range(block_layers))

    pool = jax.tree_util.tree_map(
        lambda a: a.reshape((n_layers * nb,) + a.shape[2:]), pool)
    for i, (fn, lp) in enumerate(lead):
        x, pool = fn(x, lp, pool, tables(i))

    def body(carry, lp_i):
        x, pool = carry
        lp, i = lp_i
        return layer_fn(x, lp, pool, tables(i)), None

    (x, pool), _ = lax.scan(
        body, (x, pool),
        (layers, jnp.arange(n_lead, n_layers // block_layers,
                            dtype=jnp.int32)))
    return x, jax.tree_util.tree_map(
        lambda a: a.reshape((n_layers, nb) + a.shape[1:]), pool)


def _scan_periods(period, x, layers, pool, block_tables, lead, tail=()):
    """``scan_layers_paged`` for a stack of several kinds of layer: the body
    of the scan is a PERIOD of layers, ``period = [(kind, fn), ...]`` with
    ``layers`` a tuple of stacked trees, one a position of the period
    (``[repeats, ...]`` each), ``lead = [(kind, fn, lp), ...]`` before it and
    ``tail``, of the same form, after it (a published order that ends off
    the period: ``kimi_linear``'s 27 layers). ``kind`` says which leaves of the pool count the layer, and so what
    it is handed as its address: ``"block"``, ``fn(x, lp, pool,
    layer_tables)`` as above, its layer among the block leaves' ``L``;
    ``"slot"``, ``fn(x, lp, pool, slot0)`` with ``slot0 = layer * S`` the
    row of its slot 0 in the merged slot leaves; None, ``fn(x, lp, pool,
    None)`` for a layer with no state. Every ``fn`` returns ``(x, pool)``;
    the pool is carried whole, block leaves and slot leaves each merged.

    A cache with sliding leaves (module doc) has a third kind, ``"swa"``: its
    layer among the sliding leaves' ``L_w``, addressed through the SECOND of
    ``block_tables = (full table, sliding table)``."""
    pool, shapes, address, unmerged = _merged_leaves(pool, block_tables)
    sliding = "swa" in shapes
    seen = {"block": 0, "slot": 0, None: 0}
    if sliding:
        seen["swa"] = 0
    for kind, fn, lp in lead:
        x, pool = fn(x, lp, pool, address(kind, seen[kind]))
        seen[kind] += 1
    # position j of the period is layer index[j][r] of its kind in repeat r
    repeats = jax.tree_util.tree_leaves(layers[0])[0].shape[0]
    per = {k: sum(1 for kind, _ in period if kind == k) for k in seen}
    index, at = [], dict(seen)
    for kind, _ in period:
        index.append(at[kind] + per[kind] * jnp.arange(repeats, dtype=jnp.int32))
        at[kind] += 1
    after = {k: seen[k] + per[k] * repeats for k in seen}
    for kind, (n, _) in shapes.items():
        have = after[kind] + sum(1 for k, _, _ in tail if k == kind)
        if have != n:
            raise ValueError(
                f"the cache's {kind} leaves hold {n} layers, the stack has "
                f"{have} of that kind")

    def body(carry, xs):
        x, pool = carry
        for (kind, fn), lp, i in zip(period, *xs):
            x, pool = fn(x, lp, pool, address(kind, i))
        return (x, pool), None

    (x, pool), _ = lax.scan(body, (x, pool), (tuple(layers), tuple(index)))
    for kind, fn, lp in tail:
        x, pool = fn(x, lp, pool, address(kind, after[kind]))
        after[kind] += 1
    return x, unmerged(pool)


def _merged_leaves(pool, block_tables):
    """A cache as a stack's layers carry it: ``(pool, shapes, address,
    unmerged)``. ``pool`` has every leaf's layers merged with its blocks or
    slots into one axis; ``shapes[kind]`` is ``(layers, blocks or slots)`` of
    the leaves that count a layer of ``kind`` (``"block"``, and ``"slot"`` /
    ``"swa"`` where the cache has such leaves); ``address(kind, i)`` is what
    layer ``i`` of its kind is handed (``_scan_periods``); ``unmerged(pool)``
    gives the leaves their two axes back."""
    blocks, slots = full_leaves(pool), slot_leaves(pool)
    sliding = sliding_leaves(pool)
    shapes = {"block": jax.tree_util.tree_leaves(blocks)[0].shape[:2]}
    if slots is not None:
        shapes["slot"] = jax.tree_util.tree_leaves(slots)[0].shape[:2]
    if sliding is not None:
        shapes["swa"] = jax.tree_util.tree_leaves(sliding)[0].shape[:2]
        block_tables, sliding_tables = block_tables

    def address(kind, i):
        if kind == "block":
            return block_tables + i * shapes["block"][1]
        if kind == "swa":
            return sliding_tables + i * shapes["swa"][1]
        return None if kind is None else i * shapes["slot"][1]

    def unmerged(pool):
        out = jax.tree_util.tree_map(
            lambda a: a.reshape(shapes["block"] + a.shape[1:]),
            full_leaves(pool))
        if sliding is not None:
            out = {**out, SWA: jax.tree_util.tree_map(
                lambda a: a.reshape(shapes["swa"] + a.shape[1:]), pool[SWA])}
        if slots is not None:
            out = {**out, SLOTS: jax.tree_util.tree_map(
                lambda a: a.reshape(shapes["slot"] + a.shape[1:]),
                pool[SLOTS])}
        return out

    merged = jax.tree_util.tree_map(
        lambda a: a.reshape((a.shape[0] * a.shape[1],) + a.shape[2:]), pool)
    return merged, shapes, address, unmerged


def scan_runs_paged(runs, x, pool, block_tables):
    """``scan_layers_paged`` for a stack whose order is no lead and period
    but RUNS of one kind of layer (``granite_hybrid``: five Mamba layers, an
    attention layer, four Mamba layers): ``runs = [(kind, fn, layers)]`` in
    the stack's order, ``layers`` the run's weights stacked ``[n, ...]``,
    ``kind`` and ``fn(x, lp, pool, address) -> (x, pool)`` as
    ``_scan_periods`` has them. A run of two layers or more is a scan over
    its own stack, so a step program compiles one body a run and slices no
    run out of a larger stack; a run of one is called on its stack's only
    layer. The pool is carried whole through all of them, merged once."""
    pool, shapes, address, unmerged = _merged_leaves(pool, block_tables)
    seen = dict.fromkeys(shapes, 0)
    for kind, fn, layers in runs:
        n = jax.tree_util.tree_leaves(layers)[0].shape[0]
        first = seen.get(kind, 0)
        if n == 1:
            x, pool = fn(x, jax.tree_util.tree_map(lambda a: a[0], layers),
                         pool, address(kind, first))
        else:
            def body(carry, lp_i, kind=kind, fn=fn):
                lp, i = lp_i
                return fn(carry[0], lp, carry[1], address(kind, i)), None

            (x, pool), _ = lax.scan(
                body, (x, pool),
                (layers, first + jnp.arange(n, dtype=jnp.int32)))
        seen[kind] = first + n
    for kind, (n, _) in shapes.items():
        if seen[kind] != n:
            raise ValueError(
                f"the cache's {kind} leaves hold {n} layers, the stack has "
                f"{seen[kind]} of that kind")
    return x, unmerged(pool)


def tiles_go_as_slices(tile: int, block_size: int) -> bool:
    """Whether ``write_rows_paged`` writes a step's prefill tiles of ``tile``
    rows into a pool of ``block_size``-row blocks as slices (the rule it goes
    by itself; the engine counts a step's rows by it): a tile must be whole
    runs of ``block_size`` rows, or a block whole tiles (a page of several
    tiles, ``minicpm_sala``'s 512-token pages: the pool is then seen in
    blocks of a tile's rows, ``sub_blocks``)."""
    return tile > 0 and (tile % block_size == 0 or block_size % tile == 0)


def sub_blocks(pool, block_tables, rows: int):
    """A pool ``[blocks, BS, lanes]`` and its table seen in blocks of
    ``rows`` rows (a divisor of ``BS``): ``([blocks x BS / rows, rows,
    lanes], [S, MB x BS / rows])``. The pool's is a bitcast (a block's rows
    are contiguous), the table's entry ``j`` is sub-block ``j % (BS / rows)``
    of block ``j // (BS / rows)``."""
    n, bs = pool.shape[:2]
    r = bs // rows
    if r == 1:
        return pool, block_tables
    tables = (block_tables[..., None] * r
              + jnp.arange(r, dtype=block_tables.dtype)).reshape(
                  block_tables.shape[:-1] + (-1,))
    return pool.reshape((n * r, rows) + pool.shape[2:]), tables


def write_rows_paged(pool, rows, slots, positions, block_tables,
                     prefill_tiles=None):
    """Write each ragged token's new cache row into (block, offset) of its
    sequence's pool blocks, in place. ``rows``: [T, ...] (flattened to the
    pool's lanes); ``pool``: [blocks, BS, lanes].

    A run of the step's rows that lies consecutively inside pool blocks goes
    in as slices; only rows that are alone go row by row (a row scatter
    walks its indices one after another, each a read-modify-write of the
    row's whole sublane tile: ~140 ns a row on a v5e where a 1 KB row is
    1.3 ns of HBM time, PERF.md section 6, PR 48). The layer hands over what
    it holds next to its attention, ``prefill_tiles`` = ``(n_dec, tile_slot,
    tile_pos0, tile_valid, tile)``:

    - ``rows[n_dec:]`` are tiles of ``tile`` consecutive positions of one
      sequence each. A tile is ``tile / BS`` runs of ``BS`` rows, and a run
      lies in at most two blocks of its sequence: it is rolled by its start's
      offset in the block and merged into both under row masks, ``where(row
      is the run's and < tile_valid, new, old)``, so rows at or past
      ``tile_valid`` leave the pool's real blocks as the row form leaves
      them, which drops them into the scratch block. At offset 0 the second
      block's mask is empty. One algorithm on an offset it reads
      (``tile_pos0 % BS``): no branch, no second program. A ``tile`` that
      ``BS`` does not divide keeps the row form (``tiles_go_as_slices``),
      unless it divides ``BS``: the pool is then seen in blocks of ``tile``
      rows (``sub_blocks``, a bitcast) and a tile is one run.
    - ``rows[:n_dec]``, and every row with no ``prefill_tiles``: one scatter
      of single rows, ``pool.at[blk, off].set(rows)`` (a step with no row
      past ``n_dec`` traces that scatter and nothing else). So are the decode
      rows of a model that generates by blocks (module doc *Blocks of
      rows*): a scatter whose update is a run's ``[B, lanes]`` the compiler
      turns into a loop of one update an index, 4 us each (380 us where the
      single rows take 53, PR 48).

    Whatever the form, the pool after the write holds the same bits in every
    block but the scratch block (``tests/unit/test_paged_pool_write.py``),
    and the step program holds no other operation of the pool's shape.

    This is the ONE write site of the paged contract, so it is also the
    ONE quantize site: a low-bit pool (``ops/kvquant.QuantizedKV``)
    quantizes each token row at write time — per-row scales keep the
    incremental scatter exact (rewriting a row never re-rounds another). A
    quantized pool keeps the row form for every row (``scatter_rows``; no
    benchmark cell runs one).
    """
    bs = pool.shape[1]
    blk = block_tables[slots, positions // bs]  # [T]
    off = positions % bs
    if getattr(pool, "is_quantized_kv", False):
        return pool.scatter_rows(blk, off, rows)
    rows = rows.reshape(rows.shape[0], -1).astype(pool.dtype)
    n_dec = rows.shape[0]
    if (prefill_tiles is not None and prefill_tiles[0] < n_dec
            and tiles_go_as_slices(prefill_tiles[-1], bs)):
        n_dec, tile_slot, tile_pos0, tile_valid, tile = prefill_tiles
        # a block of several tiles is written as blocks of a tile's rows
        view, tables = sub_blocks(pool, block_tables, min(bs, tile))
        pool = _write_tile_runs(view, rows[n_dec:], tile_slot, tile_pos0,
                                tile_valid, tile, tables).reshape(pool.shape)
    if not n_dec:
        return pool
    return pool.at[blk[:n_dec], off[:n_dec]].set(rows[:n_dec])


@partial(jax.jit, static_argnames="tile")
def _write_tile_runs(pool, rows, tile_slot, tile_pos0, tile_valid, tile: int,
                     block_tables):
    """``write_rows_paged``'s tiles: ``rows`` [nt * tile, lanes] into
    ``pool`` [blocks, BS, lanes], a run of ``BS`` rows at a time, each one
    read-merge-write of the two blocks it may lie in (the updates are a chain
    on the pool, so two tiles of one sequence see each other's rows and the
    pool is updated in place). A function of its own under ``jit``: a layer's
    K and V, and every layer body of a step program, trace it once and lower
    to calls of one function (inline, the window cell's 13 step programs
    took 14 s longer to lower, PERF.md section 6, PR 48)."""
    bs = pool.shape[1]
    runs = np.arange(rows.shape[0] // bs)
    t, j = runs * bs // tile, runs * bs % tile
    pos0 = tile_pos0[t] + j                                          # [runs]
    valid = jnp.minimum(tile_valid[t] - j, bs)
    col, off = pos0 // bs, pos0 % bs
    # a run's first block and the next (none of its rows there at offset 0)
    blocks = block_tables[tile_slot[t][:, None], jnp.minimum(
        col[:, None] + np.arange(2), block_tables.shape[1] - 1)]     # [runs, 2]
    # the run's row that lands on block row i: i - off in the first block,
    # i - off + BS in the next
    src = (np.arange(bs) + np.arange(2)[:, None] * bs) - off[:, None, None]
    mine = (src >= 0) & (src < valid[:, None, None])             # [runs, 2, BS]
    for n in runs:
        # row i of the image holds the run's row i - off (mod BS)
        image = jnp.roll(rows[n * bs:(n + 1) * bs], off[n], axis=0)
        for c in range(2):
            # read before the write is fused: a slice fused into the update
            # reads the donated argument beside it, and the compiler then
            # copies the whole leaf (a layer outside the scan, PR 48)
            held = lax.optimization_barrier(lax.dynamic_index_in_dim(
                pool, blocks[n, c], 0, keepdims=False))
            pool = lax.dynamic_update_index_in_dim(
                pool, jnp.where(mine[n, c][:, None], image, held),
                blocks[n, c], 0)
    return pool


def write_kv_paged(kc, vc, kk, vv, slots, positions, block_tables,
                   prefill_tiles=None):
    """``write_rows_paged`` for a K pool and a V pool. ``kk``/``vv``:
    [T, Hkv, D]; ``kc``/``vc``: [blocks, BS, Hkv*D]."""
    return (write_rows_paged(kc, kk, slots, positions, block_tables,
                             prefill_tiles),
            write_rows_paged(vc, vv, slots, positions, block_tables,
                             prefill_tiles))


# what holds a product to its stored weight's layout (the contract's *Rows to
# heads*); it changes no value, which a test shows by taking it out
_pin = lax.optimization_barrier


def rows_to_heads(h, w, heads: int, bias=None):
    """The rows ``h`` [T, D] times a projection's weight as it is stored,
    ``w`` [D, heads x head_dim] (plus ``bias``), as ``[T, heads, head_dim]``.
    The product is pinned before the reshape, so that the layout its consumer
    wants is paid for on the ``T`` rows and never on the weight."""
    y = h @ w
    if bias is not None:
        y = y + bias
    return _pin(y).reshape(h.shape[0], heads, -1)


def _decode_then_tiles(q, slots, positions, prefill_tiles, decode, prefill):
    """A flat ragged token batch is its decode rows, then tile-aligned
    prefill chunks (``prefill_tiles`` = ``(n_dec, tile_slot, tile_pos0,
    tile_valid, tile)``; None: every row is a decode row): ``decode(q,
    slots, positions)`` over the former, ``prefill(q, tile_slot, tile_pos0,
    tile_valid, tile)`` over the latter."""
    if prefill_tiles is None:
        return decode(q, slots, positions)
    n_dec, ts, tp, tv, ct = prefill_tiles
    parts = []
    if n_dec:
        parts.append(decode(q[:n_dec], slots[:n_dec], positions[:n_dec]))
    if q.shape[0] > n_dec:
        parts.append(prefill(q[n_dec:], ts, tp, tv, ct))
    return parts[0] if len(parts) == 1 else jnp.concatenate(parts, axis=0)


def ragged_pool_attention(q, kc, vc, slots, positions, block_tables,
                          prefill_tiles=None, window: int | None = None,
                          block: int | None = None):
    """Attention over the blocked pool for a flat ragged token batch:
    per-token paged kernel for the decode region, the tiled SplitFuse
    kernel for tile-aligned prefill chunks; with a ``window`` (static) both
    over keys ``pos - window < j <= pos``; with a ``block`` (static, ``B``:
    a model that generates by blocks, module doc *Blocks of rows*) both over
    keys ``j <= (pos | (B - 1))``, the decode region whole blocks of ``B``
    rows a sequence."""
    from deepspeed_tpu.ops.attention import (
        paged_attention,
        ragged_prefill_attention,
    )

    more = {} if block is None else {"block": block}
    return _decode_then_tiles(
        q, slots, positions, prefill_tiles,
        lambda q, sl, po: paged_attention(q, kc, vc, sl, po, block_tables,
                                          window=window, **more),
        lambda q, ts, tp, tv, ct: ragged_prefill_attention(
            q, kc, vc, ts, tp, tv, block_tables, ct, window=window, **more))


def nope_attention_ragged(cfg, h, lp, pool, layer_tables, slots, positions,
                          prefill_tiles, gate=None):
    """A grouped-query attention layer WITHOUT positions over the normed rows
    ``h`` [T, D] of a flat ragged token batch (``granite_hybrid``'s, ``jamba``'s
    and ``solar_open2``'s): ``cfg.num_heads`` query heads (times
    ``cfg.q_scale``) on ``cfg.num_kv_heads`` K/V heads, this layer's rows
    written to ``pool["k"]`` / ``pool["v"]`` through its table and read back by
    the two paged kernels, then ``W_o``. ``gate`` [T, heads x head_dim]
    multiplies the heads' output before ``W_o`` (a gated attention layer's
    sigmoid, ``solar_open2``). Returns ``(out [T, D], pool)``."""
    q = rows_to_heads(h, lp["wq"], cfg.num_heads) * cfg.q_scale
    kk = rows_to_heads(h, lp["wk"], cfg.num_kv_heads)
    vv = rows_to_heads(h, lp["wv"], cfg.num_kv_heads)
    kc, vc = write_kv_paged(pool["k"], pool["v"], kk, vv, slots, positions,
                            layer_tables, prefill_tiles)
    o = ragged_pool_attention(q, kc, vc, slots, positions, layer_tables,
                              prefill_tiles).astype(h.dtype)
    o = o.reshape(h.shape[0], -1)
    if gate is not None:
        o = o * gate
    return o @ lp["wo"], {**pool, "k": kc, "v": vc}


def latent_queries(q_nope, wk, q_rope, width: int, prefill_tiles=None):
    """A flat ragged step's absorbed queries as the latent kernels take them
    (module doc, *Rows to heads*): ``q_nope`` [T, H, nope] times the key half
    of ``kv_b_proj``, ``wk`` [lat, H, nope], beside the roped ``q_rope`` [T,
    H, rope] -> ``(decode rows, tile rows)``, None for a part the step has no
    rows of. The decode rows are ``[n_dec, H, W]`` token-major, rows ``[q_lat,
    q_rope, zeros]``: a decode kernel walks a row at a time, and its few rows
    are a product of their own, turned. The tile rows, most of a step's, are
    HEAD-MAJOR and in their two parts, ``(q_lat [H, T - n_dec, lat], q_rope
    [H, T - n_dec, W - lat])``: the product is batched over heads and writes
    its rows that way, the prefill kernel reads them where it wrote them
    (it joins the parts in VMEM), and the small ``q_rope`` is what turns."""
    t, h, _ = q_nope.shape
    n_dec = t if prefill_tiles is None else prefill_tiles[0]
    pad = width - wk.shape[0] - q_rope.shape[-1]
    q_dec = q_tiles = None
    # two products: a slice of ONE product's result is an array of its own
    # to the compiler, the tile rows copied once more
    if n_dec:
        q_dec = jnp.concatenate(
            [jnp.einsum("thn,lhn->thl", q_nope[:n_dec], wk), q_rope[:n_dec],
             jnp.zeros((n_dec, h, pad), q_rope.dtype)], axis=-1)
    if t > n_dec:
        q_tiles = (jnp.einsum("thn,lhn->htl", q_nope[n_dec:], wk),
                   jnp.concatenate(
                       [jnp.swapaxes(q_rope[n_dec:], 0, 1),
                        jnp.zeros((h, t - n_dec, pad), q_rope.dtype)], -1))
    return q_dec, q_tiles


def head_major(o_dec, o_tiles):
    """``latent_queries``' two parts after attention, ``[n_dec, H, lat]`` and
    ``[H, T - n_dec, lat]``, as ONE ``[H, T, lat]`` for the product batched
    over heads that follows: the tile rows where the kernel wrote them, the
    decode rows turned beside them."""
    parts = [] if o_dec is None else [jnp.swapaxes(o_dec, 0, 1)]
    if o_tiles is not None:
        parts.append(o_tiles)
    return parts[0] if len(parts) == 1 else jnp.concatenate(parts, axis=1)


def latent_pool_attention(q_dec, q_tiles, pool, slots, positions,
                          block_tables, lat: int, scale: float,
                          prefill_tiles=None):
    """Absorbed MLA attention over a latent pool (``[blocks, BS, W]``, a
    row ``[c, k_rope, zeros]``, values = its first ``lat`` lanes) for a flat
    ragged token batch, its queries as ``latent_queries`` gives them ->
    [H, T, lat] (``head_major``)."""
    from deepspeed_tpu.ops.attention import (
        latent_paged_attention,
        latent_prefill_attention,
    )

    o_dec = o_tiles = None
    if q_dec is not None:
        n_dec = q_dec.shape[0]
        o_dec = latent_paged_attention(
            q_dec, pool, slots[:n_dec], positions[:n_dec], block_tables, lat,
            scale)
    if q_tiles is not None:
        _, ts, tp, tv, ct = prefill_tiles
        o_tiles = latent_prefill_attention(
            *q_tiles, pool, ts, tp, tv, block_tables, ct, scale)
    return head_major(o_dec, o_tiles)


def append_kv_and_attend(q, kk, vv, k_cache, v_cache, start_pos, max_len):
    """Dense-cache decode/prefill step: write new KV at ``start_pos``,
    attend over the cache prefix under absolute-position causal masking.
    ``q``/``kk``/``vv``: [B, T, H*, D]; returns (o, k_cache, v_cache)."""
    from deepspeed_tpu.ops.attention import xla_attention

    t = q.shape[1]
    k_cache = lax.dynamic_update_slice(
        k_cache, kk.astype(k_cache.dtype), (0, start_pos, 0, 0))
    v_cache = lax.dynamic_update_slice(
        v_cache, vv.astype(v_cache.dtype), (0, start_pos, 0, 0))
    q_pos = start_pos + jnp.arange(t)[:, None]
    k_pos = jnp.arange(max_len)[None, :]
    bias = jnp.where(k_pos <= q_pos, 0.0, -1e30)[None, None]
    o = xla_attention(q, k_cache, v_cache, causal=False, bias=bias)
    return o, k_cache, v_cache
