"""Shared KV-cache plumbing for the model families' inference paths.

One home for the logic every family (llama, gpt2, mixtral) used to carry
verbatim: the paged-pool KV scatter, the decode/tiled-prefill attention
split over the block pool (reference ``inference/v2/ragged_ops`` layout),
and the dense-cache append+attend used by the v1-style engines. A fix to
the paged contract (e.g. the ``_table_view`` width slicing) lands HERE once
instead of three times.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax


def scan_layers_paged(layer_fn, x, layers, k_pool, v_pool):
    """Run ``layer_fn(x, lp, kc, vc) -> (x, kc, vc)`` over the stacked layers
    with the blocked KV pool CARRIED through the scan: each layer's
    ``[NB, BS, Hkv, D]`` slice is indexed out of the ``[L, ...]`` pool and
    written back in place, so a step program holds the pool once.

    As scan ``xs``/``ys`` the pool was held twice — the stacked input and the
    stacked output are distinct buffers to the compiler, donation or not. At
    GPT-2 XL (48 layers) with a 16K-token pool the TPU compiler put a
    single-step program at 15.5 GiB of the chip's 15.75 that way, and at
    9.3 GiB this way (PERF.md). Works on a fp pool and on a
    ``kvquant.QuantizedKV`` alike: both are pytrees of ``[L, ...]`` arrays.
    """
    n_layers = jax.tree_util.tree_leaves(k_pool)[0].shape[0]

    def take(pool, i):
        return jax.tree_util.tree_map(
            lambda a: lax.dynamic_index_in_dim(a, i, 0, keepdims=False), pool)

    def put(pool, layer, i):
        return jax.tree_util.tree_map(
            lambda a, u: lax.dynamic_update_index_in_dim(a, u, i, 0),
            pool, layer)

    def body(carry, lp_i):
        x, k_all, v_all = carry
        lp, i = lp_i
        x, kc, vc = layer_fn(x, lp, take(k_all, i), take(v_all, i))
        return (x, put(k_all, kc, i), put(v_all, vc, i)), None

    (x, k_pool, v_pool), _ = lax.scan(
        body, (x, k_pool, v_pool),
        (layers, jnp.arange(n_layers, dtype=jnp.int32)))
    return x, k_pool, v_pool


def write_kv_paged(kc, vc, kk, vv, slots, positions, block_tables):
    """Scatter each ragged token's new KV into (block, offset) of its
    sequence's pool blocks. ``kk``/``vv``: [T, Hkv, D].

    This is the ONE write site of the paged contract, so it is also the
    ONE quantize site: a low-bit pool (``inference/kvquant.QuantizedKV``)
    quantizes each token row at write time — per-row scales keep the
    incremental scatter exact (rewriting a row never re-rounds another).
    """
    bs = kc.shape[1]
    blk = block_tables[slots, positions // bs]  # [T]
    off = positions % bs
    if getattr(kc, "is_quantized_kv", False):
        return kc.scatter_rows(blk, off, kk), vc.scatter_rows(blk, off, vv)
    kc = kc.at[blk, off].set(kk.astype(kc.dtype))
    vc = vc.at[blk, off].set(vv.astype(vc.dtype))
    return kc, vc


def ragged_pool_attention(q, kc, vc, slots, positions, block_tables,
                          prefill_tiles=None):
    """Attention over the blocked pool for a flat ragged token batch:
    per-token paged kernel for the decode region, the tiled SplitFuse
    kernel for tile-aligned prefill chunks (``prefill_tiles`` =
    ``(n_dec, tile_slot, tile_pos0, tile_valid, tile)``)."""
    from deepspeed_tpu.ops.attention import (
        paged_attention,
        ragged_prefill_attention,
    )

    t_tokens = q.shape[0]
    if prefill_tiles is None:
        return paged_attention(q, kc, vc, slots, positions, block_tables)
    n_dec, ts, tp, tv, ct = prefill_tiles
    parts = []
    if n_dec:
        parts.append(paged_attention(q[:n_dec], kc, vc, slots[:n_dec],
                                     positions[:n_dec], block_tables))
    if t_tokens > n_dec:
        parts.append(ragged_prefill_attention(
            q[n_dec:], kc, vc, ts, tp, tv, block_tables, ct))
    return parts[0] if len(parts) == 1 else jnp.concatenate(parts, axis=0)


def speculative_lane_layout(cur_tok, draft, pos, live, cap, slots,
                            scratch_slot):
    """Flatten a [T]-row decode batch plus per-row draft proposals into the
    flat verify batch one ragged forward consumes.

    Row ``r`` occupies lanes ``r*(1+D) .. r*(1+D)+D``: lane 0 feeds the
    row's current token at ``pos[r]`` (the plain decode step), lane ``1+i``
    feeds ``draft[r, i]`` at ``pos[r] + 1 + i`` — so one forward scores the
    committed step AND every draft position, and because ``write_kv_paged``
    scatters each lane's KV before attention runs, later lanes attend over
    earlier lanes' keys within the same dispatch. Rejected-draft KV needs no
    rollback: positions are fed strictly monotonically, so a rejected cell
    is always re-scattered by a later dispatch before anything attends to it.

    Lanes of dead rows (``live`` False) and lanes at/past the row's covered
    capacity ``cap[r]`` (first position WITHOUT an allocated block) are
    routed to ``scratch_slot`` at position 0 — their writes land in the
    scratch block and their picks are never surfaced (the emission budget
    clamps first). Returns flat ``(tokens, slots, positions, raw_positions)``
    each [T*(1+D)]; ``raw_positions`` keeps the unrouted positions for
    per-lane sampling-key derivation."""
    t = cur_tok.shape[0]
    d = 0 if draft is None else draft.shape[1]
    lanes = 1 + d
    lane_pos_raw = pos[:, None] + jnp.arange(lanes)[None, :]     # [T, L]
    if d:
        lane_tok = jnp.concatenate([cur_tok[:, None], draft], axis=1)
    else:
        lane_tok = cur_tok[:, None]
    ok = live[:, None] & (lane_pos_raw < cap[:, None])
    lane_slot = jnp.where(ok, slots[:, None], scratch_slot)
    lane_pos = jnp.where(ok, lane_pos_raw, 0)
    return (lane_tok.reshape(-1).astype(jnp.int32),
            lane_slot.reshape(-1).astype(jnp.int32),
            lane_pos.reshape(-1).astype(jnp.int32),
            lane_pos_raw.reshape(-1).astype(jnp.int32))


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
