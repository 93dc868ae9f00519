"""Attention ops with implementation dispatch.

Role parity with the reference's attention kernel stack
(``csrc/transformer/inference`` softmax/rope kernels, v2 ``ragged_ops`` blocked
flash attention) — on TPU the hot path is a Pallas flash-attention kernel
(``ops/pallas/flash_attention.py``); the reference path is a stable-softmax XLA
einsum that the compiler fuses well on the MXU. ``impl="auto"`` picks Pallas on
TPU for supported shapes, XLA otherwise.

Layouts: q [B, Sq, Hq, D], k/v [B, Skv, Hkv, D] (GQA: Hq % Hkv == 0).
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp


def _on_tpu() -> bool:
    return jax.default_backend() == "tpu"


def repeat_kv(k: jnp.ndarray, n_rep: int) -> jnp.ndarray:
    """Expand KV heads for grouped-query attention."""
    if n_rep == 1:
        return k
    b, s, h, d = k.shape
    return jnp.broadcast_to(k[:, :, :, None, :], (b, s, h, n_rep, d)).reshape(b, s, h * n_rep, d)


def xla_attention(
    q: jnp.ndarray,
    k: jnp.ndarray,
    v: jnp.ndarray,
    causal: bool = True,
    bias: jnp.ndarray | None = None,
    scale: float | None = None,
) -> jnp.ndarray:
    """Reference attention: fp32 stable softmax, MXU-friendly einsums."""
    n_rep = q.shape[2] // k.shape[2]
    k = repeat_kv(k, n_rep)
    v = repeat_kv(v, n_rep)
    d = q.shape[-1]
    scale = scale if scale is not None else 1.0 / math.sqrt(d)

    scores = jnp.einsum("bqhd,bkhd->bhqk", q, k, preferred_element_type=jnp.float32)
    scores = scores * scale
    if bias is not None:
        scores = scores + bias.astype(scores.dtype)
    if causal:
        sq, sk = q.shape[1], k.shape[1]
        # offset supports decode (q is a suffix of the kv sequence)
        idx_q = jnp.arange(sq)[:, None] + (sk - sq)
        idx_k = jnp.arange(sk)[None, :]
        mask = idx_q >= idx_k
        scores = jnp.where(mask[None, None], scores, jnp.float32(-1e30))
    weights = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum("bhqk,bkhd->bqhd", weights.astype(v.dtype), v)
    return out


def _fit(n: int, want: int) -> int:
    """``want`` halved down to a divisor of ``n`` (``want`` itself where it
    covers ``n``), so odd lengths (1536, 2560, ...) keep the kernel."""
    while want > 8 and n % min(want, n):
        want //= 2
    return want


# The flash kernels' geometry, measured on v5e (my chip runs, PR 39; PERF.md
# section 6 has the table). A program holds up to FLASH_BLOCK query rows and
# one K/V block of as many in VMEM: at 1,024 a training sequence is one grid
# step a head. Inside it the kernels walk FLASH_SUB-row sub-blocks and stop at
# the diagonal, so 10 of 16 pairs of a 1,024-token square are multiplied. One
# layer's forward (twice: remat) + dK dV + dQ at [4, 25, 1024, 64]: 1,836 us
# on the whole square, 1,177 / 1,193 / 1,420 at sub-blocks of 128 / 256 / 512;
# at [1, 32, 2048, 128] 1,981 against 1,310 / 1,348 / 1,492. 128 and 256 are
# within 3%, at both head widths: 256 is the forward's best (226 us a call
# against 235) and unrolls half as many strips.
FLASH_BLOCK = 1024
FLASH_SUB = 256


def _sub_block(block_q: int, block_k: int) -> int:
    """The walk's sub-block inside blocks of these sizes: FLASH_SUB rows, or
    half of that, where it divides both (a block no longer than it is one
    sub-block); a block neither divides keeps the whole square. The kernels
    slice scores by sub-block along lanes, so a width is whole 128-lane
    tiles."""
    for sub in (FLASH_SUB, FLASH_SUB // 2):
        if not (block_q % min(sub, block_q) or block_k % min(sub, block_k)):
            return sub
    return max(block_q, block_k)


def flash_blocks(q, k, bias=None, impl: str = "auto"):
    """The flash kernel's ``(block_q, block_k, sub)`` where ``attention``
    will run it, None where it takes the XLA path: decided from the backend
    and the shapes, BEFORE any call. ``sub`` is the width of the sub-blocks
    the kernels walk inside a block (``flash_pair_share`` says what that
    leaves of the square); a sequence shorter than two of them is one."""
    from deepspeed_tpu.ops.pallas.flash_attention import supported

    if impl not in ("auto", "pallas", "xla"):
        raise ValueError(f"unknown attention impl {impl!r}")
    if impl == "xla":
        return None
    sq, skv = q.shape[1], k.shape[1]
    bq, bk = _fit(sq, FLASH_BLOCK), _fit(skv, FLASH_BLOCK)
    sub = _sub_block(min(bq, sq), min(bk, skv))
    # dK / dV read lse and delta across a row, one block of q rows wide: the
    # chip's compiler takes whole 128-lane tiles or the whole sequence
    tiled = bq >= sq or bq % 128 == 0
    if impl == "auto" and not (_on_tpu() and bias is None and tiled
                               and supported(q, k, bq, bk, sub)):
        return None
    return bq, bk, sub


# the walk of the flash call traced last: what a step program compiled since
# runs in every layer (a model's attentions share one shape)
_traced_walk: dict = {}


def flash_walk_args() -> dict:
    """Arguments for a step's dispatch span: the sub-block width the flash
    kernels of the program traced last walk (``flash_sub``) and the share of
    the square they multiply (``flash_pair_share``); empty where no flash
    call was traced (the XLA path)."""
    return dict(_traced_walk)


def flash_pair_share(sq: int, skv: int, sub: int, causal: bool = True) -> float:
    """The share of the ``sq x skv`` square's sub-block pairs a flash call
    multiplies: ``causal_pairs / all_pairs`` by the kernels' own walk, 1.0
    without a mask. Static: the walk engages by shape."""
    from deepspeed_tpu.ops.pallas.flash_attention import kv_walk

    tq, tk = min(sub, sq), min(sub, skv)
    nq, nk = sq // tq, skv // tk
    return sum(kv_walk(n * tq, tq, tk, nk, causal)[1]
               for n in range(nq)) / (nq * nk)


def attention(
    q: jnp.ndarray,
    k: jnp.ndarray,
    v: jnp.ndarray,
    causal: bool = True,
    bias: jnp.ndarray | None = None,
    scale: float | None = None,
    impl: str = "auto",
) -> jnp.ndarray:
    """Dispatching attention entry point used by all models.

    ``impl="auto"`` decides kernel-or-XLA from the backend and the shapes
    BEFORE the call (``flash_blocks``); whatever the chosen kernel raises
    propagates — a kernel the chip's compiler refuses must stop the run, not
    reroute it.
    """
    blocks = flash_blocks(q, k, bias, impl)
    if blocks is None:
        return xla_attention(q, k, v, causal=causal, bias=bias, scale=scale)
    from deepspeed_tpu.ops.pallas.flash_attention import flash_attention

    _traced_walk.update(flash_sub=blocks[2], flash_pair_share=round(
        flash_pair_share(q.shape[1], k.shape[1], blocks[2], causal), 4))
    return flash_attention(q, k, v, causal, scale, *blocks)


def paged_attention(q, k_pool, v_pool, slots, positions, block_tables,
                    scale: float | None = None, impl: str = "auto",
                    window: int | None = None, block: int | None = None):
    """Ragged paged-KV attention: [T, Hq, D] tokens over the blocked pool
    ``[blocks, BS, Hkv*D]`` (reference ``inference/v2/kernels/ragged_ops``
    blocked flash attention).

    ``impl="auto"``: on the chip an unquantized pool takes the Pallas kernel
    at every table width, since the kernel walks each row's own context and
    its cost does not follow the table (``ops/pallas/paged_attention.py``;
    one layer, GPT-2 XL's heads, 4 rows x 350 tokens of a 1,024-token table:
    gather 176 us, kernel 20 us; 32 rows 3.35 ms and 0.13; the table in
    PERF.md section 6, PR 29). Off the chip it is the XLA gather of the
    padded context. The choice is made here, from the backend and the pool's
    kind; an error from the chosen kernel propagates.

    ``window`` (static): a sliding-window layer's rows attend over keys
    ``pos - window < j <= pos`` only, in either form.

    ``block`` (static, ``B``): a model that generates by blocks; the rows are
    whole decoding blocks, ``B`` consecutive rows a sequence at positions
    ``p0 .. p0 + B - 1`` (``p0`` a multiple of ``B``), and each attends over
    keys ``j <= (pos | (B - 1))``, its block whole: the kernel walks a
    block's context once, its queries laid out by KV head (``blk_decode``),
    the XLA form moves the mask.

    A quantized pool (``ops/kvquant.QuantizedKV``) always takes the
    XLA path: the gather+dequant fuse into one program there (the fp
    context is a per-dispatch transient). A Pallas kernel that streams
    int8 blocks + scales through VMEM is the TPU drop-in point — it slots
    in at this dispatch without touching callers.
    """
    if getattr(k_pool, "is_quantized_kv", False):
        impl = "xla"
    if impl == "auto":
        impl = "pallas" if _on_tpu() else "xla"
    if impl == "pallas":
        from deepspeed_tpu.ops.pallas.paged_attention import (
            paged_decode_attention,
        )

        if block is None:
            return paged_decode_attention(q, k_pool, v_pool, slots, positions,
                                          block_tables, scale=scale,
                                          window=window)
        return paged_decode_attention(
            q.reshape((-1, block) + q.shape[1:]), k_pool, v_pool,
            slots[::block], positions[::block], block_tables, scale=scale,
            window=window, block=block).reshape(q.shape)
    if impl != "xla":
        raise ValueError(f"unknown paged attention impl {impl!r}")
    if block is not None:
        positions = positions | (block - 1)   # the mask's edge, below
    t_tokens, hq, d = q.shape
    hkv = k_pool.shape[-1] // d
    tables = block_tables[slots]                       # [T, MB]

    def context(pool):
        # heads are split on the GATHERED context (T x context), never on
        # the pool: the pool's rows stay lane-dense [.., BS, Hkv*D]
        ctx = (pool.gather_dequant(tables)
               if getattr(pool, "is_quantized_kv", False) else pool[tables])
        return repeat_kv(ctx.reshape(t_tokens, -1, hkv, d), hq // hkv)

    ctx_k, ctx_v = context(k_pool), context(v_pool)
    scale = scale if scale is not None else 1.0 / jnp.sqrt(jnp.float32(d))
    k_pos = jnp.arange(ctx_k.shape[1])
    seen = k_pos[None, :] <= positions[:, None]
    if window is not None:
        seen = seen & (k_pos[None, :] > positions[:, None] - window)
    bias = jnp.where(seen, 0.0, -1e30)
    scores = (jnp.einsum("thd,tchd->thc", (q * scale).astype(jnp.float32),
                         ctx_k.astype(jnp.float32)) + bias[:, None, :])
    p = jax.nn.softmax(scores, axis=-1)
    return jnp.einsum("thc,tchd->thd", p, ctx_v.astype(jnp.float32)
                      ).astype(q.dtype)


def prefill_step_keys(k_pool) -> int | None:
    """The keys one grid step of the tile kernel takes over ``k_pool``
    ``[.., BS, Hkv*D]`` where ``ragged_prefill_attention`` will run it
    (``prefill_step_blocks`` whole blocks), None where it takes the XLA path
    (a quantized pool, the CPU): decided from the backend and the pool,
    BEFORE any call; a static of a step program with tiles."""
    if getattr(k_pool, "is_quantized_kv", False) or not _on_tpu():
        return None
    from deepspeed_tpu.ops.pallas.paged_attention import prefill_step_blocks

    bs, lanes = k_pool.shape[-2:]
    return bs * prefill_step_blocks(bs, lanes, k_pool.dtype.itemsize)


def ragged_prefill_attention(q, k_pool, v_pool, tile_slot, tile_pos0,
                             tile_valid, block_tables, tile: int,
                             scale: float | None = None, impl: str = "auto",
                             window: int | None = None,
                             block: int | None = None):
    """Tiled prefill attention over the blocked pool: ``q`` holds tile-aligned
    prefill tokens (one sequence per CT-token tile, consecutive positions,
    rows past ``tile_valid`` padding). The Pallas kernel fetches each KV block
    ONCE per tile instead of once per token
    (``ops/pallas/paged_attention.ragged_prefill_attention``); the XLA
    path (quantized pools, the CPU) expands the tile metadata to per-token
    (slot, position) arrays and reuses the padded gather.
    """
    if getattr(k_pool, "is_quantized_kv", False):
        impl = "xla"  # fused gather+dequant (see paged_attention)
    if impl == "auto":
        impl = "xla" if prefill_step_keys(k_pool) is None else "pallas"
    if impl == "pallas":
        from deepspeed_tpu.ops.pallas.paged_attention import (
            ragged_prefill_attention as _pallas_prefill,
        )

        more = {} if block is None else {"block": block}
        return _pallas_prefill(q, k_pool, v_pool, tile_slot, tile_pos0,
                               tile_valid, block_tables, tile, scale=scale,
                               window=window, **more)
    if impl != "xla":
        raise ValueError(f"unknown prefill attention impl {impl!r}")
    slots, positions = _tile_rows(q.shape[0], tile_slot, tile_pos0, tile_valid,
                                  tile, block_tables.shape[0] - 1)
    return paged_attention(q, k_pool, v_pool, slots, positions, block_tables,
                           scale=scale, impl="xla", window=window,
                           block=block)


def _tile_rows(n_rows: int, tile_slot, tile_pos0, tile_valid, tile: int,
               pad_row: int):
    """Per-row ``(slots, positions)`` of ``n_rows`` tile-aligned prefill
    rows; rows past a tile's ``tile_valid`` go to the all-scratch padding
    row at position 0."""
    c = jnp.arange(n_rows) // tile
    i = jnp.arange(n_rows) % tile
    valid = i < tile_valid[c]
    return (jnp.where(valid, tile_slot[c], pad_row).astype(jnp.int32),
            jnp.where(valid, tile_pos0[c] + i, 0).astype(jnp.int32))


def latent_paged_attention(q, pool, slots, positions, block_tables, lat: int,
                           scale: float, impl: str = "auto"):
    """Absorbed MLA attention of ragged rows over a latent pool: ``q``
    [T, H, W] against rows ``[c, k_rope, zeros]`` of ``pool`` [blocks, BS,
    W], values the first ``lat`` lanes of the same rows -> [T, H, lat]. On the chip the Pallas kernel walks each row's own blocks
    (``ops/pallas/mla_attention.mla_decode_attention``); the XLA gather of
    the padded context is the form the CPU runs."""
    if impl == "auto":
        impl = "pallas" if _on_tpu() else "xla"
    if impl == "pallas":
        from deepspeed_tpu.ops.pallas.mla_attention import mla_decode_attention

        return mla_decode_attention(q, pool, slots, positions, block_tables,
                                    lat, scale)
    if impl != "xla":
        raise ValueError(f"unknown latent attention impl {impl!r}")
    t_tokens = q.shape[0]
    ctx = pool[block_tables[slots]].reshape(
        t_tokens, -1, pool.shape[-1]).astype(jnp.float32)    # [T, C, W]
    k_pos = jnp.arange(ctx.shape[1])
    bias = jnp.where(k_pos[None, :] <= positions[:, None], 0.0, -1e30)
    scores = jnp.einsum("thl,tcl->thc", q.astype(jnp.float32) * scale,
                        ctx) + bias[:, None, :]
    p = jax.nn.softmax(scores, axis=-1)
    return jnp.einsum("thc,tcl->thl", p, ctx[..., :lat]).astype(q.dtype)


def latent_prefill_attention(q_lat, q_rope, pool, tile_slot, tile_pos0,
                             tile_valid, block_tables, tile: int, scale: float,
                             impl: str = "auto"):
    """``latent_paged_attention`` for tile-aligned prefill rows (the
    scheduler contract of ``ragged_prefill_attention``), HEAD-MAJOR and in
    two parts as the absorbed products around it have them: ``q_lat`` [H, T,
    lat], ``q_rope`` [H, T, W - lat] -> [H, T, lat]. On the chip the tiled
    kernel, a block fetched once a tile; the XLA form expands the tile
    metadata to per-row (slot, position)."""
    if impl == "auto":
        impl = "pallas" if _on_tpu() else "xla"
    if impl == "pallas":
        from deepspeed_tpu.ops.pallas.mla_attention import mla_prefill_attention

        return mla_prefill_attention(q_lat, q_rope, pool, tile_slot, tile_pos0,
                                     tile_valid, block_tables, tile, scale)
    slots, positions = _tile_rows(q_lat.shape[1], tile_slot, tile_pos0,
                                  tile_valid, tile, block_tables.shape[0] - 1)
    q = jnp.swapaxes(jnp.concatenate([q_lat, q_rope], axis=-1), 0, 1)
    return jnp.swapaxes(latent_paged_attention(
        q, pool, slots, positions, block_tables, q_lat.shape[-1], scale,
        impl=impl), 0, 1)


def rope_frequencies(half: int, theta: float, yarn=None):
    """The ``half`` rotation frequencies of a ``2 * half``-lane head:
    ``theta ** (-i / half)``. ``yarn = (factor, beta_fast, beta_slow,
    original_max)`` stretches them as YaRN does: a lane that turns fewer than
    ``beta_slow`` times over the ``original_max`` positions the model was
    trained on is slowed by ``factor`` (interpolated), one that turns more
    than ``beta_fast`` times is left alone, and the lanes between the two
    ramp linearly from one to the other."""
    freqs = 1.0 / (theta ** (jnp.arange(0, half, dtype=jnp.float32) / half))
    if yarn is None:
        return freqs
    factor, beta_fast, beta_slow, original_max = yarn

    def lane_of(turns):  # the (fractional) lane that turns ``turns`` times
        return (half * math.log(original_max / (turns * 2 * math.pi))
                / math.log(theta))

    low = max(math.floor(lane_of(beta_fast)), 0)
    high = min(math.ceil(lane_of(beta_slow)), half - 1)
    ramp = jnp.clip((jnp.arange(half, dtype=jnp.float32) - low)
                    / max(high - low, 1e-3), 0.0, 1.0)
    return freqs / factor * ramp + freqs * (1.0 - ramp)


@functools.partial(jax.jit, static_argnums=(3, 4))
def apply_rope(q, k, positions, theta: float = 10000.0, yarn=None):
    """Rotary position embedding (reference: ``apply_rotary_pos_emb`` kernels,
    ``csrc/transformer/inference/csrc/apply_rotary_pos_emb.cu``).

    q/k: [B, S, H, D]; positions: [B, S] absolute positions; ``yarn``:
    ``rope_frequencies``' stretch (a tuple: static).
    """
    d = q.shape[-1]
    half = d // 2
    freqs = rope_frequencies(half, theta, yarn)
    angles = positions[..., None].astype(jnp.float32) * freqs  # [B, S, half]
    cos = jnp.cos(angles)[:, :, None, :]  # [B, S, 1, half]
    sin = jnp.sin(angles)[:, :, None, :]

    def rot(x):
        x1, x2 = x[..., :half], x[..., half:]
        xr1 = x1.astype(jnp.float32) * cos - x2.astype(jnp.float32) * sin
        xr2 = x2.astype(jnp.float32) * cos + x1.astype(jnp.float32) * sin
        return jnp.concatenate([xr1, xr2], axis=-1).astype(x.dtype)

    return rot(q), rot(k)
