"""The Pallas kernels of the train and serve paths, compiled for a described
(not attached) TPU v5e at the head geometries of the three model families and
at the block/tile sizes the engines really use. Interpret mode cannot see what
these see: a slice off the tiling, more scoped VMEM than a kernel may take.
Nothing runs, so this says nothing about results or speed."""

import dataclasses
import functools
import math
import os
import re

os.environ.setdefault("TPU_LOG_DIR", "disabled")

import jax
import jax.numpy as jnp
import pytest
from jax.experimental.compilation_cache import compilation_cache

from deepspeed_tpu.ops.attention import flash_blocks, flash_pair_share
from deepspeed_tpu.ops.pallas import dsa_attention as dsa
from deepspeed_tpu.ops.pallas.flash_attention import flash_attention
from deepspeed_tpu.ops.pallas.mla_attention import (
    mla_decode_attention,
    mla_prefill_attention,
    mla_prefill_kernel_tile,
)
from deepspeed_tpu.ops.pallas.moe_gmm import ROW_ALIGN, grouped_swiglu
from deepspeed_tpu.ops.pallas.paged_attention import (
    decode_step_blocks,
    paged_decode_attention,
    prefill_kernel_tile,
    prefill_step_blocks,
    ragged_prefill_attention,
)

# (q heads, kv heads, head size): Llama-3-8B / Mixtral-8x7B, GPT-2 XL,
# GPT-2 medium, Nemotron-3-Super (16 query heads a KV head, 256-lane rows)
GEOMETRIES = [(32, 8, 128), (25, 25, 64), (16, 16, 64), (32, 2, 128)]
SEQ = 1024     # one flash block (ops.attention.flash_blocks), walked in sub-blocks
TILE = 128     # RaggedConfig.prefill_tile of chip_smoke.py
BLOCK = 32     # their KV block_size
MAX_BLOCKS = 8


@pytest.fixture(scope="module")
def v5e():
    try:
        from jax.experimental import topologies

        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler in this installation
        pytest.skip(f"cannot describe a v5e topology: {e}")
    # a compile for a described device is written to the persistent cache
    # but cannot be read back without a chip: keep it out
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield topo.devices
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _flash_fwd(q, k, v):
    return flash_attention(q, k, v, True, None,
                           *flash_blocks(q, k, None, "pallas"), False)


def _flash_bwd(q, k, v):
    return jax.grad(
        lambda *a: _flash_fwd(*a).astype(jnp.float32).sum(), (0, 1, 2))(q, k, v)


def _decode(q, kp, vp, slots, pos, bt):
    return paged_decode_attention(q, kp, vp, slots, pos, bt, interpret=False)


def _prefill(q, kp, vp, ts, tp, tv, bt):
    return ragged_prefill_attention(q, kp, vp, ts, tp, tv, bt, TILE,
                                    interpret=False)


# Moonlight-16B-A3B's latent attention: 16 heads over ONE cached row of 512
# latent + 64 rope lanes, padded to 640; 128-token blocks, a table of 32
MLA_HEADS, MLA_LAT, MLA_WIDTH, MLA_BLOCK, MLA_TABLE = 16, 512, 640, 128, 32


def _mla_decode(q, pool, slots, pos, bt):
    return mla_decode_attention(q, pool, slots, pos, bt, MLA_LAT, 192 ** -0.5,
                                interpret=False)


def _mla_prefill(q, pool, ts, tp, tv, bt):
    return mla_prefill_attention(q[..., :MLA_LAT], q[..., MLA_LAT:], pool, ts,
                                 tp, tv, bt, TILE, 192 ** -0.5,
                                 interpret=False)


# DeepSeek-V3.2-Exp's sparse attention at the longctx-pool cell's shapes: 16
# decode rows beside 3 tiles, 128 heads over 640-lane rows, a 64 x 128
# indexer keeping 2,048 rows, 128-token blocks behind a table of 64
DSA_ROWS, DSA_TILES, DSA_TABLE, DSA_KEEP = 16, 3, 64, 2048
DSA_HEADS, DSA_INDEX_HEADS, DSA_INDEX_DIM = 128, 64, 128


def _dsa_index(q, w, pool, slots, pos, bt, ts, tp, tv):
    return dsa.dsa_index_scores(q, w, pool, slots, pos, bt,
                                (DSA_ROWS, ts, tp, tv, TILE), interpret=False)


def _dsa_decode(q, rows, n):
    return dsa.dsa_decode_attention(q, rows, n, MLA_LAT, 192 ** -0.5,
                                    interpret=False)


def _dsa_walk(q, pool, slots, pos, bt, keep):
    return mla_decode_attention(q, pool, slots, pos, bt, MLA_LAT, 192 ** -0.5,
                                keep=keep, interpret=False)


def _dsa_prefill(q, pool, bias, ts, tp, tv, bt):
    return dsa.dsa_prefill_attention(q[..., :MLA_LAT], q[..., MLA_LAT:], pool,
                                     bias, ts, tp, tv, bt, TILE, 192 ** -0.5,
                                     interpret=False)


def _dsa_args(kernel, devices):
    dev = jax.sharding.SingleDeviceSharding(devices[0])

    def s(shape, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=dev)

    t = DSA_ROWS + DSA_TILES * TILE
    tiles = s((DSA_TILES,), jnp.int32)
    bt = s((DSA_ROWS + 1, DSA_TABLE), jnp.int32)
    if kernel is _dsa_index:
        rows = s((t,), jnp.int32)
        return (s((t, DSA_INDEX_HEADS, DSA_INDEX_DIM)),
                s((t, DSA_INDEX_HEADS), jnp.float32),
                s((64, MLA_BLOCK, DSA_INDEX_DIM)), rows, rows, bt,
                tiles, tiles, tiles)
    if kernel is _dsa_decode:
        return (s((DSA_ROWS, DSA_HEADS, MLA_WIDTH)),
                s((DSA_ROWS, DSA_KEEP, MLA_WIDTH)), s((DSA_ROWS,), jnp.int32))
    if kernel is _dsa_walk:
        rows = s((DSA_ROWS,), jnp.int32)
        return (s((DSA_ROWS, DSA_HEADS, MLA_WIDTH)),
                s((64, MLA_BLOCK, MLA_WIDTH)), rows, rows, bt,
                s((DSA_ROWS, DSA_TABLE * MLA_BLOCK), jnp.bool_))
    return (s((DSA_HEADS, DSA_TILES * TILE, MLA_WIDTH)),
            s((64, MLA_BLOCK, MLA_WIDTH)),
            s((DSA_TILES * TILE, DSA_TABLE * MLA_BLOCK), jnp.float32),
            tiles, tiles, tiles, bt)


# the grouped expert FFN at the two MoE cells' widths, in the shapes of a
# full call (``models/experts.py`` gives every step those): (experts, picks a
# token, hidden, expert FFN, rows, rows a pass)
MOE_GEOMETRIES = {"mixtral": (8, 2, 4096, 14336, 512, 128),
                  "moonlight": (64, 6, 2048, 1408, 512, 64),
                  # 128 held of 512, ungated relu**2 in the 1,024-wide latent
                  "nemotron": (128, 22, 1024, 2688, 512, 64),
                  # every one of 128 small experts whole on the chip (PR 47)
                  "sdar": (128, 8, 2048, 768, 512, 64)}
UNGATED = {"nemotron"}


@functools.lru_cache(maxsize=None)     # one function a geometry: ``_compiled``
def _moe_gmm(tm, max_rows, gated=True):
    def kernel(x, w_gate, w_up, w_down, row0, counts):
        return grouped_swiglu(x, w_gate if gated else None, w_up, w_down, row0,
                              counts, tm, max_rows, interpret=False)

    return kernel


def _moe_args(geometry, devices):
    dev = jax.sharding.SingleDeviceSharding(devices[0])

    def s(shape, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=dev)

    e, k, d, f, t, tm = MOE_GEOMETRIES[geometry]
    rows = -(-(t * k + e * (ROW_ALIGN - 1)) // ROW_ALIGN) * ROW_ALIGN + tm
    return (s((rows, d)), s((e, d, f)), s((e, d, f)), s((e, f, d)),
            s((e,), jnp.int32), s((e,), jnp.int32))


@functools.lru_cache(maxsize=None)
def _compiled(kernel, *args):
    """``kernel`` compiled for the described chip at ``args``' shapes, once:
    the cases that read other facts of one lowering (its calls, its memory,
    its instructions' names) share it."""
    return jax.jit(kernel).lower(*args).compile()


def _args(kernel, hq, hkv, d, devices, mla_block=MLA_BLOCK):
    dev = jax.sharding.SingleDeviceSharding(devices[0])

    def s(shape, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=dev)

    if kernel in (_mla_decode, _mla_prefill):
        pool = s((64, mla_block, MLA_WIDTH))
        bt = s((129, MLA_TABLE), jnp.int32)
        if kernel is _mla_decode:
            rows = s((128,), jnp.int32)
            return s((128, MLA_HEADS, MLA_WIDTH)), pool, rows, rows, bt
        tiles = s((3,), jnp.int32)
        return s((MLA_HEADS, 3 * TILE, MLA_WIDTH)), pool, tiles, tiles, tiles, bt
    if kernel in (_flash_fwd, _flash_bwd):
        return s((1, SEQ, hq, d)), s((1, SEQ, hkv, d)), s((1, SEQ, hkv, d))
    pool = s((64, BLOCK, hkv * d))
    bt = s((9, MAX_BLOCKS), jnp.int32)
    if kernel is _decode:
        rows = s((8,), jnp.int32)
        return s((8, hq, d)), pool, pool, rows, rows, bt
    tiles = s((2,), jnp.int32)
    return s((2 * TILE, hq, d)), pool, pool, tiles, tiles, tiles, bt


@pytest.mark.parametrize("hq,hkv,d", GEOMETRIES)
@pytest.mark.parametrize("kernel,n_calls", [
    (_flash_fwd, 1), (_flash_bwd, 3), (_decode, 1), (_prefill, 1)],
    ids=["flash_fwd", "flash_bwd", "paged_decode", "tiled_prefill"])
def test_kernel_compiles_for_v5e(v5e, kernel, n_calls, hq, hkv, d):
    args = _args(kernel, hq, hkv, d, v5e)
    text = _compiled(kernel, *args).as_text()
    assert text.count("tpu_custom_call") >= n_calls
    if kernel in (_flash_fwd, _flash_bwd):
        # what was lowered walks sub-blocks: its work is bounded by the
        # causal sub-block pairs, and the whole square only without a mask
        sub = flash_blocks(*args[:2], None, "pallas")[2]
        assert flash_pair_share(SEQ, SEQ, sub) <= 0.75
        assert flash_pair_share(SEQ, SEQ, sub, causal=False) == 1.0


# the decode rows of the three K/V-pool serving cells: (q heads, kv heads,
# head size, block size, table width, rows, pool blocks)
DECODE_CELLS = {"gpt2-xl.chat": (25, 25, 64, 32, 32, 4, 513),
                "mixtral.chat": (32, 8, 128, 128, 8, 16, 1025),
                "mixtral.longdoc": (32, 8, 128, 128, 64, 16, 1537),
                # 20 query heads on ONE K/V head: a pool row is one lane tile
                "jamba.reason": (20, 1, 128, 128, 32, 256, 8193),
                # eight query heads a K/V head, a table of 8K tokens
                "solar.longctx": (64, 8, 128, 128, 64, 16, 1025),
                # 64-lane heads, four query heads a K/V head: the WIDE form
                # at 512 rows ([32, 512] query matrix a row)
                "lfm2.reason": (32, 8, 64, 128, 32, 512, 6145)}


@pytest.mark.parametrize("cell", sorted(DECODE_CELLS))
def test_decode_kernel_compiles_at_the_cells_shapes(v5e, cell):
    """``paged_decode`` at a cell's block size and table width: a grid whose
    length is traced, one operand a block of a step (GPT-2 XL's 1600-lane
    blocks, which no hand-written DMA may slice), and nothing beside the
    kernel larger than the [rows, heads, lanes] query it is handed."""
    hq, hkv, d, block, table, rows, blocks = DECODE_CELLS[cell]
    dev = jax.sharding.SingleDeviceSharding(v5e[0])

    def s(shape, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=dev)

    pool, i32 = s((blocks, block, hkv * d)), s((rows,), jnp.int32)
    compiled = jax.jit(_decode).lower(
        s((rows, hq, d)), pool, pool, i32, i32,
        s((rows + 1, table), jnp.int32)).compile()
    assert compiled.as_text().count("tpu_custom_call") == 1
    assert compiled.memory_analysis().temp_size_in_bytes < 2**21


DECODE_FIXTURE = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                              "fixtures", "paged_decode_jaxprs_pr58.json")


def single_query_decode_digests() -> dict:
    """sha256 of what ``paged_decode_attention(block=None)`` traces to (the
    wrapper's XLA operations, the grid, the index maps and the kernel's
    body) at GPT-2 XL's and Mixtral's cells' shapes. ``python
    tests/unit/test_compile_tpu.py OUT`` writes them from whatever tree
    ``PYTHONPATH`` names (the fixture: PR 58's)."""
    import hashlib

    def s(shape, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct(shape, dtype)

    out = {}
    for cell in ("gpt2-xl.chat", "mixtral.chat", "mixtral.longdoc"):
        hq, hkv, d, block, table, rows, blocks = DECODE_CELLS[cell]
        pool, i32 = s((blocks, block, hkv * d)), s((rows,), jnp.int32)
        text = str(jax.make_jaxpr(_decode)(
            s((rows, hq, d)), pool, pool, i32, i32,
            s((rows + 1, table), jnp.int32)))
        assert "paged_decode" in text
        out[cell] = hashlib.sha256(
            re.sub(r"0x[0-9a-f]+", "0x", text).encode()).hexdigest()
    return out


def test_single_query_decode_traces_what_the_parent_of_the_split_traced():
    """(PR 59) A decoding block got a kernel body of its own; a row of one
    query (``block=None``) goes through the wrapper to ``_paged_decode`` and
    ``_decode_kernel`` as before: the same text, operation for operation."""
    import json

    with open(DECODE_FIXTURE) as f:
        assert single_query_decode_digests() == json.load(f)


@pytest.mark.parametrize("kernel,block", [
    (_mla_decode, MLA_BLOCK), (_mla_prefill, MLA_BLOCK), (_mla_decode, 16)],
    ids=["mla_decode", "mla_prefill", "mla_decode_8_blocks_a_step"])
def test_mla_kernel_compiles_for_v5e(v5e, kernel, block):
    """At Moonlight's widths and the reason-pool cell's shapes (128 decode
    rows, 3 prefill tiles): the hand-written DMAs of a step's 640-lane
    blocks (four of 128 tokens; eight, the most the rule gives, of 16), the
    dynamic trip count, the 64-row sub-tile's scoped VMEM; and no gather of
    the padded context beside the kernel."""
    assert decode_step_blocks(block, MLA_WIDTH, 2, arrays=1) == (
        4 if block == MLA_BLOCK else 8)
    compiled = _compiled(kernel, *_args(kernel, 0, 0, 0, v5e, mla_block=block))
    assert compiled.as_text().count("tpu_custom_call") == 1
    assert compiled.memory_analysis().temp_size_in_bytes < 2**20


@pytest.mark.parametrize("kernel,calls", [(_dsa_index, 2), (_dsa_decode, 1),
                                          (_dsa_prefill, 1), (_dsa_walk, 1)],
                         ids=["dsa_index", "dsa_attn_decode",
                              "dsa_attn_prefill", "dsa_attn_decode_walk"])
def test_dsa_kernel_compiles_for_v5e(v5e, kernel, calls):
    """At DeepSeek-V3.2-Exp's widths and the longctx-pool cell's shapes: the
    indexer's two bodies (a decode row's 64 heads in one product, a tile's
    head-major products), a decode row's 2,048 gathered rows of 640 lanes in
    one block (2.6 MB, double-buffered), the 8-query sub-tile of 128 heads
    with its bias rows; the masked walk of a decode row's own blocks
    (``mla_decode`` with a selection: 128 heads' [128, 512] scores a step,
    a row of the selection resident beside three buffers of four blocks)."""
    compiled = _compiled(kernel, *_dsa_args(kernel, v5e))
    assert compiled.as_text().count("tpu_custom_call") == calls
    # beside the walk its [16, 8192] selection as float32, and re-laid out
    # a chunk a sublane: 0.5 MB each
    assert compiled.memory_analysis().temp_size_in_bytes < (
        2**21 if kernel is _dsa_walk else 2**20)


@pytest.mark.parametrize("geometry", sorted(MOE_GEOMETRIES))
def test_moe_gmm_compiles_for_v5e(v5e, geometry):
    """At the published widths: the hand-written
    DMA of ``tm`` rows at a dynamic ``ROW_ALIGN``-ed row, the dynamic trip
    counts, and the VMEM the weight tiles and an expert's rows take under
    the raised limit."""
    *_, t, tm = MOE_GEOMETRIES[geometry]
    compiled = _compiled(_moe_gmm(tm, t, geometry not in UNGATED),
                         *_moe_args(geometry, v5e))
    assert compiled.as_text().count("tpu_custom_call") == 1
    assert compiled.memory_analysis().temp_size_in_bytes < 2**20


def _ssm_decode(state, rows, da, dtx, bt, ct):
    from deepspeed_tpu.ops.pallas.ssm import ssm_decode

    return ssm_decode(state, rows, da, dtx, bt, ct, interpret=False)


# the Mamba state of the two cells with such layers: layers, slots, groups,
# the decode bucket's rows, all of [128, 8192] float32
SSM_CELLS = {"nemotron-3-super-120b-d11-ep4": (5, 129, 8, 128),
             "granite-4.0-h-small-d10-ep2": (9, 65, 1, 64)}


def _ssm_args(devices, rows=128, layers=5, slots=129, groups=8):
    dev = jax.sharding.SingleDeviceSharding(devices[0])

    def s(shape, dtype=jnp.float32):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=dev)

    return (s((layers * slots, 128, 8192)), s((rows,), jnp.int32),
            s((rows, 8192)), s((rows, 8192)), s((rows, 128, groups)),
            s((rows, 128, groups)))


@pytest.mark.parametrize("cell", sorted(SSM_CELLS))
def test_ssm_decode_compiles_for_v5e_and_updates_in_place(v5e, cell):
    """A row's whole state (4 MB) a grid step, in and out double-buffered
    under the raised VMEM limit, a [128, 1] column broadcast over the lanes
    (at ONE group over all 8,192 of them); the donated state is the output
    (aliased), and nothing else in the program is as large as ONE row's
    state."""
    layers, slots, groups, rows = SSM_CELLS[cell]
    compiled = jax.jit(_ssm_decode, donate_argnums=(0,)).lower(
        *_ssm_args(v5e, rows, layers, slots, groups)).compile()
    assert compiled.as_text().count("tpu_custom_call") == 1
    mem = compiled.memory_analysis()
    assert mem.alias_size_in_bytes >= layers * slots * 128 * 8192 * 4
    assert mem.temp_size_in_bytes < 128 * 8192 * 4


def _ssd_chunk(state, rows, rows_w, fresh, cont, write, x, dt, a, b, c):
    from deepspeed_tpu.ops.pallas.ssm import ssd_chunk

    return ssd_chunk(state, rows, rows_w, fresh, cont, write, x, dt, a, b, c,
                     impl="pallas", interpret=False)


# MiniCPM-SALA's Lightning state in its cell: 6 layers x 17 slots of [128,
# 4096] float32 (32 heads of 128 x 128, a group a head)
SSD_ROWS, SSD_HEADS = 6 * 17, 32


def _ssd_chunk_args(devices, tiles):
    """The cell's state and a step's ``tiles`` prefill tiles of 128 rows in
    bfloat16, the heads' 128 lanes side by side as the projections leave
    them."""
    dev = jax.sharding.SingleDeviceSharding(devices[0])
    hp = SSD_HEADS * 128

    def s(shape, dtype=jnp.float32):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=dev)

    return (s((SSD_ROWS, 128, hp)), *(s((tiles,), jnp.int32),) * 2,
            *(s((tiles,), jnp.bool_),) * 3, s((tiles, 128, hp), jnp.bfloat16),
            s((tiles, 128, SSD_HEADS)), s((SSD_HEADS,)),
            *(s((tiles, 128, hp), jnp.bfloat16),) * 2)


@pytest.mark.parametrize("tiles", [1, 3])
def test_ssd_chunk_compiles_for_v5e_and_updates_in_place(v5e, tiles):
    """Eight heads and a tile a grid step (4 x 3 grid steps in the cell's
    mixed step): a head's ``x`` / ``B`` / ``C`` a lane slice of the block,
    four bfloat16 products of 128 x 128 x 128 a head, one of them over the
    rows of both operands (``B^T xw``), a head's column of ``[128, 64]`` by a
    masked lane sum and its row by a dynamic sublane index in Mosaic; the
    donated state is the output (aliased), nothing else in the program is as
    large as ONE row's state, and the tiles' operands reach the kernel as
    they are handed in (no transpose to a head-major layout, no copy)."""
    compiled = jax.jit(_ssd_chunk, donate_argnums=(0,)).lower(
        *_ssd_chunk_args(v5e, tiles)).compile()
    text = compiled.as_text()
    assert text.count("tpu_custom_call") == 1
    assert not re.search(
        rf"= bf16\[{tiles},128,{SSD_HEADS * 128}\]\S* (copy|transpose)\(",
        text)
    mem = compiled.memory_analysis()
    assert mem.alias_size_in_bytes >= SSD_ROWS * 128 * SSD_HEADS * 128 * 4
    assert mem.temp_size_in_bytes < 128 * SSD_HEADS * 128 * 4


def _kda_decode(state, rows, a, k, q, v, beta):
    from deepspeed_tpu.ops.pallas.kda import kda_decode

    return kda_decode(state, rows, a, k, q, v, beta, impl="pallas",
                      interpret=False)


# the KDA state of the two cells that have one: (layers x slots, heads, the
# decode bucket): Kimi-Linear's 10 layers x 129 slots of [128, 4096] float32
# (32 heads of 128 x 128, 2 MB a row), Solar-Open2's 3 x 17 of [128, 8192]
# (64 heads, 4 MB a row: 16 MB double-buffered each way, [128, 64] operands)
KDA_CELLS = {"kimi-linear": (10 * 129, 32, 128), "solar-open2": (3 * 17, 64, 16)}


def _kda_args(devices, rows=None, cell="kimi-linear"):
    """A cell's KDA state and a decode bucket of ``rows`` (the cell's own)."""
    dev = jax.sharding.SingleDeviceSharding(devices[0])
    n, heads, bucket = KDA_CELLS[cell]
    rows, hv = rows or bucket, heads * 128

    def s(shape, dtype=jnp.float32):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=dev)

    return (s((n, 128, hv)), s((rows,), jnp.int32),
            s((rows, 128, heads)), s((rows, 128, heads)),
            s((rows, 128, heads)), s((rows, hv)), s((rows, hv)))


@pytest.mark.parametrize("cell", sorted(KDA_CELLS))
def test_kda_decode_compiles_for_v5e_and_updates_in_place(v5e, cell):
    """A row's whole state (2 MB, 4 MB at 64 heads) a grid step, in and out
    double-buffered, a head's [128, 1] columns broadcast over its lanes; the
    donated state is the output (aliased), and nothing else in the program
    is as large as ONE row's state."""
    n, heads, _ = KDA_CELLS[cell]
    compiled = jax.jit(_kda_decode, donate_argnums=(0,)).lower(
        *_kda_args(v5e, cell=cell)).compile()
    assert compiled.as_text().count("tpu_custom_call") == 1
    mem = compiled.memory_analysis()
    assert mem.alias_size_in_bytes >= n * 128 * heads * 128 * 4
    assert mem.temp_size_in_bytes < 128 * heads * 128 * 4


def _kda_chunk(state, rows, rows_w, fresh, cont, write, q, k, g, v, beta):
    from deepspeed_tpu.ops.pallas.kda import kda_chunk

    return kda_chunk(state, rows, rows_w, fresh, cont, write, q, k, g, v,
                     beta, 16, impl="pallas", interpret=False)


def _kda_chunk_args(devices, tiles=3, cell="kimi-linear"):
    """The same state, and a mixed step's ``tiles`` prefill tiles of 128
    rows: the heads' 128 channels side by side on the lanes."""
    dev = jax.sharding.SingleDeviceSharding(devices[0])
    n, heads, _ = KDA_CELLS[cell]
    hv = heads * 128

    def s(shape, dtype=jnp.float32):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=dev)

    return (s((n, 128, hv)), *(s((tiles,), jnp.int32),) * 2,
            *(s((tiles,), jnp.bool_),) * 3, *(s((tiles, 128, hv)),) * 4,
            s((tiles, 128, heads)))


@pytest.mark.parametrize("cell,tiles", [("kimi-linear", 3), ("solar-open2", 4)])
def test_kda_chunk_compiles_for_v5e_and_updates_in_place(v5e, cell, tiles):
    """Four heads and a tile a grid step (16 x 4 grid steps in the Solar
    cell's widest step): products of bfloat16 parts, one of them against a
    transposed operand, lane sums of ``[8, 16, 128]`` blocks, a bit mask over
    float32 and a sub-chunk loop in
    Mosaic; the donated state is the output (aliased), nothing else in the
    program is as large as ONE row's state, and the tiles' operands reach
    the kernel as they are handed in (no transpose to a head-major layout,
    no copy)."""
    n, heads, _ = KDA_CELLS[cell]
    compiled = jax.jit(_kda_chunk, donate_argnums=(0,)).lower(
        *_kda_chunk_args(v5e, tiles=tiles, cell=cell)).compile()
    text = compiled.as_text()
    assert text.count("tpu_custom_call") == 1
    assert not re.search(
        rf"= f32\[{tiles},128,{heads * 128}\]\S* (copy|transpose)\(", text)
    mem = compiled.memory_analysis()
    assert mem.alias_size_in_bytes >= n * 128 * heads * 128 * 4
    assert mem.temp_size_in_bytes < 128 * heads * 128 * 4


def _eqns(jaxpr):
    """Every equation of ``jaxpr`` and of the jaxprs its equations hold."""
    for eqn in jaxpr.eqns:
        yield eqn
        for param in eqn.params.values():
            for inner in param if isinstance(param, (tuple, list)) else (param,):
                inner = getattr(inner, "jaxpr", inner)
                if hasattr(inner, "eqns"):
                    yield from _eqns(inner)


def _kda_chunk_loop(rows, tiles=3, cell="solar-open2"):
    """The ``exp`` and ``dot_general`` equations of ``kda_chunk``'s sub-chunk
    loop at a cell's heads and ``rows`` rows a tile, as (primitive, operand
    shapes, operand dtypes), and the loop's trip count: read off the
    ``pallas_call``'s kernel jaxpr, nothing compiled."""
    n, heads, _ = KDA_CELLS[cell]
    hv = heads * 128

    def s(shape, dtype=jnp.float32):
        return jax.ShapeDtypeStruct(shape, dtype)

    traced = jax.make_jaxpr(_kda_chunk)(
        s((n, 128, hv)), *(s((tiles,), jnp.int32),) * 2,
        *(s((tiles,), jnp.bool_),) * 3, *(s((tiles, rows, hv)),) * 4,
        s((tiles, rows, heads)))
    (call,) = [e for e in _eqns(traced.jaxpr) if e.primitive.name == "pallas_call"]
    loops = [e for e in _eqns(call.params["jaxpr"]) if e.primitive.name == "scan"]
    assert len(loops) == 1, "ONE loop over the sub-chunks, not copies of its body"
    (loop,) = loops
    return loop.params["length"], [
        (e.primitive.name, tuple(v.aval.shape for v in e.invars),
         tuple(str(v.aval.dtype) for v in e.invars))
        for e in _eqns(loop.params["jaxpr"].jaxpr)
        if e.primitive.name in ("exp", "dot_general")]


def test_kda_chunk_loop_pays_a_turn_for_its_sub_chunk_alone():
    """The mechanism of PR 58, held off the chip: a turn of ``kda_chunk``'s
    sub-chunk loop costs what is new to its 16 rows. At the Solar cell's
    sizes (64 heads, four a grid step) the loop's body takes ``exp`` of
    nothing larger than a band of the pairwise block ``[8, 16, 128]`` and of
    no ``[R, K]`` operand, every product is of bfloat16 PARTS (no float32
    operand for the compiler to split and push six times), and the only
    128-row operands are the carried state's three parts ``[K, V]``, pushed
    once each a head, the identity that turns the keys to columns and the
    turned keys ``[K, 128]``. The same kernel over tiles of 256 rows has the
    SAME equations in its loop, twice the turns: nothing in a turn is as long
    as the tile (the parent rebuilt, split and pushed ``cols`` ``[R, K]`` and
    ``u`` ``[R, V]`` whole every turn)."""
    turns, body = _kda_chunk_loop(128)
    turns_256, body_256 = _kda_chunk_loop(256)
    assert (turns, turns_256) == (8, 16)
    assert body == body_256
    sub, kd, heads = 16, 128, 4
    exps = [shapes[0] for name, shapes, _ in body if name == "exp"]
    assert exps and max(math.prod(x) for x in exps) == sub // 2 * sub * kd
    assert not [x for x in exps if len(x) == 2 and x[0] > sub]
    dots = [(shapes, dtypes) for name, shapes, dtypes in body
            if name == "dot_general"]
    assert all(dtypes == ("bfloat16", "bfloat16") for _, dtypes in dots)
    # a head: the state's three parts under the rows' parts, the identity
    # under the keys' parts, the turned keys over U's parts and over the ones
    assert len(dots) == 6 * heads
    state_parts = [shapes for shapes, _ in dots if shapes[1] == (kd, 128)
                   and shapes[0][0] in (6 * sub, 4 * sub, 2 * sub)]
    assert len(state_parts) == 3 * heads
    streamed = sorted({shapes[0][0] for shapes, _ in dots})
    assert streamed == [2 * sub, 4 * sub, 6 * sub, kd]


def _selscan_decode(state, rows, fresh, dt, x, a, b, c):
    from deepspeed_tpu.ops.pallas.selscan import selscan_decode

    return selscan_decode(state, rows, fresh, dt, x, a, b, c, impl="pallas",
                          interpret=False)


def _selscan_tile(state, rows, rows_w, fresh, cont, write, dt, x, a, b, c):
    from deepspeed_tpu.ops.pallas.selscan import selscan_tile

    return selscan_tile(state, rows, rows_w, fresh, cont, write, dt, x, a, b,
                        c, impl="pallas", interpret=False)


SELSCAN = (26 * 257, 16, 5120)   # the Jamba cell's state: layers x slots


def _selscan_args(devices, kernel, n):
    """The Mamba-1 state of the Jamba cell, 26 layers x 257 slots of [16,
    5120] float32, and a decode bucket of ``n`` rows or ``n`` tiles of 128."""
    dev = jax.sharding.SingleDeviceSharding(devices[0])
    rows_n, ns, ch = SELSCAN

    def s(shape, dtype=jnp.float32):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=dev)

    if kernel is _selscan_decode:
        return (s(SELSCAN), s((n,), jnp.int32), s((n,), jnp.bool_), s((n, ch)),
                s((n, ch), jnp.bfloat16), s((ns, ch)), s((n, ns)), s((n, ns)))
    return (s(SELSCAN), *(s((n,), jnp.int32),) * 2, *(s((n,), jnp.bool_),) * 3,
            s((n, 128, ch)), s((n, 128, ch), jnp.bfloat16), s((ns, ch)),
            s((n, 128, ns)), s((n, 128, ns)))


@pytest.mark.parametrize("kernel,n", [(_selscan_decode, 256),
                                      (_selscan_decode, 128),
                                      (_selscan_tile, 3), (_selscan_tile, 1)],
                         ids=["decode_256", "decode_128", "tile_3", "tile_1"])
def test_selscan_kernels_compile_for_v5e_and_update_in_place(v5e, kernel, n):
    """``selscan_decode``: a row's whole state (327 KB) a grid step beside
    the layer's resident ``A``, the row's ``B`` and ``C`` one ``[1, 32]`` lane
    row whose diagonal is the column; ``selscan_tile``: a block of 1,280
    channels and a tile a grid step, a dynamic float32 row load and store a
    row, the bfloat16 ``x`` 16 rows at a time, under the raised VMEM limit.
    The donated state is the output (aliased) and nothing else in the
    program is as large as one tile's ``dt``."""
    compiled = jax.jit(kernel, donate_argnums=(0,)).lower(
        *_selscan_args(v5e, kernel, n)).compile()
    text = compiled.as_text()
    assert text.count("tpu_custom_call") == 1
    assert not re.search(r"= (f32|bf16)\[\d+,128,5120\]\S* (copy|transpose)\(",
                         text)
    mem = compiled.memory_analysis()
    assert mem.alias_size_in_bytes >= math.prod(SELSCAN) * 4
    assert mem.temp_size_in_bytes < 128 * 5120 * 4


@pytest.mark.parametrize("kernel,name", [
    (_flash_fwd, "flash_fwd"), (_flash_bwd, "flash_bwd_dkv"),
    (_flash_bwd, "flash_bwd_dq"), (_decode, "paged_decode"),
    (_prefill, "tiled_prefill"), (_mla_decode, "mla_decode"),
    (_mla_prefill, "mla_prefill"), ("moe_gmm", "moe_gmm"),
    ("moe_gmm_ungated", "moe_gmm"), ("ssm_decode", "ssm_decode"),
    ("kda_decode", "kda_decode"), (_kda_chunk, "kda_chunk"),
    (_selscan_decode, "selscan_decode"), (_selscan_tile, "selscan_tile"),
    (_dsa_index, "dsa_index"), (_dsa_decode, "dsa_attn_decode"),
    (_dsa_prefill, "dsa_attn_prefill"), (_dsa_walk, "dsa_attn_decode")],
    ids=["flash_fwd", "flash_bwd_dkv", "flash_bwd_dq", "paged_decode",
         "tiled_prefill", "mla_decode", "mla_prefill", "moe_gmm",
         "moe_gmm_ungated", "ssm_decode", "kda_decode", "kda_chunk",
         "selscan_decode", "selscan_tile",
         "dsa_index",
         "dsa_attn_decode",
         "dsa_attn_prefill", "dsa_attn_decode_walk"])
def test_kernel_instruction_goes_by_its_name(v5e, kernel, name):
    """``pl.pallas_call(name=...)``: the compiled custom call is
    ``%<name>.N`` (``%transpose_jvp_<name>__.N`` under a bare ``jax.grad``),
    which is an operation's event name in a device trace, and
    ``benchmark/kernels/<name>.json``'s pattern finds it and no other."""
    import json
    import os
    import re

    kernels = os.path.join(os.path.dirname(__file__), os.pardir, os.pardir,
                           "benchmark", "kernels")
    patterns = {}
    for f in os.listdir(kernels):
        with open(os.path.join(kernels, f)) as fh:
            patterns[f[:-5]] = re.compile(json.load(fh)["trace_pattern"])
    if kernel == "moe_gmm":
        *_, t, tm = MOE_GEOMETRIES["moonlight"]
        text = _compiled(_moe_gmm(tm, t, True),
                         *_moe_args("moonlight", v5e)).as_text()
    elif kernel == "moe_gmm_ungated":
        *_, t, tm = MOE_GEOMETRIES["nemotron"]
        text = _compiled(_moe_gmm(tm, t, False),
                         *_moe_args("nemotron", v5e)).as_text()
    elif kernel == "ssm_decode":
        text = _compiled(_ssm_decode, *_ssm_args(v5e, rows=8)).as_text()
    elif kernel == "kda_decode":
        text = _compiled(_kda_decode, *_kda_args(v5e, rows=8)).as_text()
    elif kernel is _kda_chunk:
        text = _compiled(kernel, *_kda_chunk_args(v5e, tiles=2)).as_text()
    elif kernel in (_selscan_decode, _selscan_tile):
        text = _compiled(kernel, *_selscan_args(v5e, kernel, 2)).as_text()
    elif kernel in (_dsa_index, _dsa_decode, _dsa_prefill, _dsa_walk):
        text = _compiled(kernel, *_dsa_args(kernel, v5e)).as_text()
    else:
        text = _compiled(kernel, *_args(kernel, *GEOMETRIES[0], v5e)).as_text()
    calls = [ln.strip().removeprefix("ROOT ") for ln in text.splitlines()
             if " custom-call(" in ln and "tpu_custom_call" in ln]
    mine = [ln for ln in calls if patterns[name].search(ln)]
    assert mine and all(name in ln.split(" = ")[0] for ln in mine)
    if kernel in (_mla_decode, _dsa_walk):
        # ONE walk under two names: the steps' three vectors, slots,
        # positions, the table, q and the pool; a selection is a ninth operand
        operands = re.search(r"custom-call\(([^)]*)\)", mine[0]).group(1)
        assert operands.count("%") == (9 if kernel is _dsa_walk else 8)
    for ln in calls:  # every kernel call is some named kernel's, and one's only
        hits = [k for k, rx in patterns.items()
                if k != "pallas_custom_call" and rx.search(ln)]
        assert len(hits) == 1 and patterns["pallas_custom_call"].search(ln)


def test_flash_compiles_on_a_described_mesh(v5e, monkeypatch):
    """GSPMD refuses a Mosaic kernel on more than one device; through
    ``ShardCtx.attention`` it runs manual over the mesh (ZeRO-3 on four
    chips, ``chip_smoke.py --chips 4``), forward and backward."""
    import numpy as np

    from deepspeed_tpu.models.api import ShardCtx

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")  # no interpret
    mesh = jax.sharding.Mesh(np.array(v5e).reshape(4), ("fsdp",))
    ctx = ShardCtx(mesh=mesh)
    hq, hkv, d = GEOMETRIES[1]
    spec = jax.sharding.NamedSharding(mesh, jax.sharding.PartitionSpec("fsdp"))
    q = jax.ShapeDtypeStruct((4, SEQ, hq, d), jnp.bfloat16, sharding=spec)
    kv = jax.ShapeDtypeStruct((4, SEQ, hkv, d), jnp.bfloat16, sharding=spec)
    text = jax.jit(jax.grad(
        lambda q, k, v: ctx.attention(q, k, v).astype(jnp.float32).sum(),
        (0, 1, 2))).lower(q, kv, kv).compile().as_text()
    assert text.count("tpu_custom_call") >= 3


# the tiles of the five K/V-pool serving cells: (q heads, kv heads, head
# size, block size, window, table width, pool blocks, blocks a grid step)
PREFILL_CELLS = {"gpt2-xl.chat": (25, 25, 64, 32, None, 32, 513, 8),
                 "mixtral.chat": (32, 8, 128, 128, None, 8, 1025, 4),
                 "mixtral.longdoc": (32, 8, 128, 128, None, 64, 1537, 4),
                 "nemotron.reason": (32, 2, 128, 128, None, 32, 4097, 4),
                 "smallthinker.full": (28, 4, 128, 128, None, 64, 641, 4),
                 "smallthinker.window": (28, 4, 128, 128, 4096, 64, 529, 4),
                 "jamba.reason": (20, 1, 128, 128, None, 32, 8193, 4),
                 # a tile's query block is [8, 1024, 128]
                 "solar.longctx": (64, 8, 128, 128, None, 64, 1025, 4),
                 # 64-lane heads padded to 128 in the kernel's scratches
                 "lfm2.reason": (32, 8, 64, 128, None, 32, 6145, 4)}


@pytest.mark.parametrize("cell,tiles", [(c, 3) for c in sorted(PREFILL_CELLS)]
                         + [("smallthinker.window", 1)])
def test_tile_kernel_compiles_at_the_cells_shapes(v5e, cell, tiles):
    """The tile kernel at a cell's block size, table width and heads, at the
    blocks a grid step ``prefill_step_blocks`` gives it (each an operand of
    its own: GPT-2 XL's 1600-lane blocks), a whole 128-row tile in the VMEM
    it asks for; beside the kernel only q laid out by KV head and the output
    laid back (a program of ONE tile was the first thing Mosaic's default
    16 MiB refused: 17.3)."""
    hq, hkv, d, block, window, table, blocks, nb = PREFILL_CELLS[cell]
    assert prefill_step_blocks(block, hkv * d, 2) == nb
    assert prefill_kernel_tile(TILE, hq, hkv, d, 2, nb * block) == TILE
    dev = jax.sharding.SingleDeviceSharding(v5e[0])

    def s(shape, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=dev)

    pool, i32 = s((blocks, block, hkv * d)), s((tiles,), jnp.int32)
    compiled = jax.jit(lambda *a: ragged_prefill_attention(
        *a, TILE, interpret=False, window=window)).lower(
        s((tiles * TILE, hq, d)), pool, pool, i32, i32, i32,
        s((17, table), jnp.int32)).compile()
    assert compiled.as_text().count("tpu_custom_call") == 1
    assert compiled.memory_analysis().temp_size_in_bytes <= (
        2 * tiles * TILE * hq * max(d, 128) * 2)


def test_prefill_tile_split_is_what_the_compiler_needs():
    """Every cell's geometry keeps the scheduler's whole 128-row tile in the
    48 MiB the kernel asks for (Mosaic's default 16 MiB refused 32 heads x
    128 at 17.3-20.5). The rule splits where the compiler refuses: handed
    the size one step above each of the last three (``_tiled_prefill(ct=)``,
    AOT for a v5e, PR 45) it says 56.6, 54.8 and 57.6 MiB of 48, and takes
    what the rule gives."""
    assert prefill_kernel_tile(TILE, 32, 8, 128, 2, 512) == TILE
    assert prefill_kernel_tile(TILE, 25, 25, 64, 2, 256) == TILE
    assert prefill_kernel_tile(TILE, 16, 16, 64, 2, 256) == TILE
    assert prefill_kernel_tile(8, 32, 8, 128, 2, 512) == 8
    assert prefill_kernel_tile(256, 64, 8, 128, 2, 512) == 128
    assert prefill_kernel_tile(512, 32, 8, 128, 2, 512) == 256
    assert prefill_kernel_tile(TILE, 128, 8, 128, 2, 512) == 64
    # 16 heads x (512-lane accumulator, 640-lane q): 128 rows are 19 MiB
    assert mla_prefill_kernel_tile(TILE, MLA_HEADS, MLA_LAT, MLA_WIDTH,
                                   MLA_BLOCK) == 64


# ------------------------------------------------------- the paged contract
# A family's ragged step at its real head geometry and the serving cells'
# pool (blocks, block size); depth, FFN and vocabulary are small, so an array
# as large as a layer's slice of the pool can only be the pool's.
def _step_family(name):
    from deepspeed_tpu.models import deepseek, gpt2, llama, mixtral

    if name == "moonlight":    # moonlight-16b-a3b-d8.json's attention and pool
        return deepseek, deepseek.DeepseekConfig(
            vocab_size=512, intermediate_size=256, moe_intermediate_size=128,
            num_layers=3, num_experts=4, top_k=2), 2049, 128, 32
    if name == "gpt2-xl":      # 25 x 64, benchmark/configs/gpt2-xl.json
        return gpt2, gpt2.GPT2Config(vocab_size=512, hidden_size=1600,
                                     num_layers=4, num_heads=25), 513, 32, 8
    if name == "mixtral":      # 32q / 8kv x 128, mixtral-8x7b-d3.json
        return mixtral, mixtral.MixtralConfig(
            vocab_size=512, intermediate_size=256, num_layers=3,
            num_experts=4), 1537, 128, 32
    return llama, llama.LlamaConfig(           # Llama-3-8B's heads
        vocab_size=512, intermediate_size=256, num_layers=4,
        num_kv_heads=8), 1025, 128, 32


def _abstract_step(v5e, mod, cfg, blocks, block, codec=None):
    """A family's bf16 parameters and paged cache as shapes on the first
    described chip, and ``on_chip(tree)`` / ``i32(*shape)`` for the rest of
    a step's arguments."""
    dev = jax.sharding.SingleDeviceSharding(v5e[0])

    def on_chip(tree):
        return jax.tree_util.tree_map(
            lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=dev),
            tree)

    def i32(*shape):
        return jax.ShapeDtypeStruct(shape, jnp.int32, sharding=dev)

    params = jax.eval_shape(lambda: jax.tree_util.tree_map(
        lambda a: a.astype(jnp.bfloat16),
        mod.init_params(cfg, jax.random.PRNGKey(0))))
    cache = jax.eval_shape(lambda: mod.init_paged_cache(
        cfg, blocks, block, jnp.bfloat16, codec=codec))
    return on_chip, i32, params, cache


_HLO_BYTES = {"pred": 1, "s8": 1, "u8": 1, "f8e4m3fn": 1, "bf16": 2, "f16": 2,
              "s32": 4, "u32": 4, "f32": 4}
# results that are another buffer's bytes under a new name
_HLO_ALIASES = ("parameter", "get-tuple-element", "bitcast", "tuple", "while")
# ``_materialized``'s opcodes that write into an operand's buffer: a row
# scatter, a Pallas kernel's aliased operand, a dynamic-update-slice
_IN_PLACE = ("scatter", "kernel", "update")


def _computations(text):
    """``{name: [instruction lines]}`` of the optimized HLO's computations."""
    bodies, comp = {}, None
    for ln in text.splitlines():
        if ln and not ln[0].isspace() and ln.rstrip().endswith("{"):
            comp = ln.removeprefix("ENTRY ").split(" ")[0]
            bodies[comp] = []
        elif comp is not None:
            bodies[comp].append(ln)
    return bodies


def _materialized(text):
    """``(bytes, opcode, line)`` of every array-valued instruction of the
    optimized HLO that gets a buffer of its own: those outside fused
    computations, a fusion with several results once a result. A fusion's
    opcode is ``scatter`` where that is what its computation holds,
    ``update`` where it is a dynamic-update-slice (or a tuple of them: the
    compiler fuses a K and a V update of one block), a Pallas kernel's is
    ``kernel``."""
    import re

    fused = set(re.findall(r"calls=(%[\w.\-]+)", text))
    bodies = _computations(text)

    def updates_in_place(body):
        made = {m.group(1): m.group(2) for b in body for m in [re.match(
            r"\s+(?:ROOT )?(%[\w.\-]+) = .*? ([\w\-]+)\(", b)] if m}
        (root,) = [b for b in body if b.lstrip().startswith("ROOT ")]
        if " dynamic-update-slice(" in root:
            return True
        parts = re.search(r" tuple\((.*?)\)", root)
        return bool(parts) and all(
            made.get(name) == "dynamic-update-slice"
            for name in parts.group(1).split(", "))

    out = []
    for comp, lines in bodies.items():
        if comp in fused:
            continue
        for ln in lines:
            m = re.match(r"\s+(?:ROOT )?%[\w.\-]+ = (\(.*?\)|\w+\[[\d,]*\]\S*) "
                         r"([\w\-]+)\(", ln)
            if not m or (m.group(1)[0] == "(" and m.group(2) != "fusion"):
                continue
            op = m.group(2)
            called = re.search(r"calls=(%[\w.\-]+)", ln)
            if op == "fusion" and called and any(
                    " scatter(" in b for b in bodies[called.group(1)]):
                op = "scatter"
            elif op == "fusion" and called and updates_in_place(
                    bodies[called.group(1)]):
                op = "update"   # one slice of its operand, in place
            elif op == "custom-call" and "tpu_custom_call" in ln:
                op = "kernel"
            if op in _HLO_ALIASES:
                continue
            for dtype, dims in re.findall(r"(\w+)\[([\d,]*)\]", m.group(1)):
                if dtype in _HLO_BYTES:
                    size = _HLO_BYTES[dtype]
                    for n in filter(None, dims.split(",")):
                        size *= int(n)
                    out.append((size, op, ln.strip()))
    return out


@pytest.mark.parametrize("quantized", [False, True], ids=["fp", "int8"])
@pytest.mark.parametrize("family", ["gpt2-xl", "mixtral", "llama3-8b"])
def test_step_program_holds_no_layer_slice_of_the_pool(v5e, monkeypatch,
                                                       family, quantized):
    """The paged contract (``models/paged.py``), on the compiled program: a
    ragged step with a donated pool writes its rows by in-place scatters
    and reads through block tables. Nothing else in it — no slice, copy or
    re-layout — is as large as one layer's slice of the pool, and its
    temporaries together stay under one slice. The fp step runs the tiled
    prefill kernel and, at every table width, the decode kernel; a
    ``QuantizedKV`` pool takes the XLA gather, whose float32 context is
    rows x table wide: here four decode rows over a table of two blocks."""
    from deepspeed_tpu.ops import kvquant

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")  # no interpret
    mod, cfg, blocks, block, table = _step_family(family)
    rows, tiles, table = (4, 0, 2) if quantized else (8, 1, table)
    on_chip, i32, params, cache = _abstract_step(
        v5e, mod, cfg, blocks, block,
        codec=kvquant.get_codec("int8") if quantized else None)
    payload = jax.tree_util.tree_leaves(cache)[0]       # k (or its payload)
    assert payload.shape == (cfg.num_layers, blocks, block,
                             payload.shape[-1])
    layer_slice = blocks * block * payload.shape[-1] * payload.dtype.itemsize

    def step(params, cache, tokens, slots, positions, tables, ts, tp, tv):
        return mod.ragged_forward(
            cfg, params, tokens, slots, positions, tables, cache,
            prefill_tiles=(rows, ts, tp, tv, TILE) if tiles else None)

    t = rows + tiles * TILE
    compiled = jax.jit(step, donate_argnums=(1,)).lower(
        on_chip(params), on_chip(cache), i32(t), i32(t), i32(t),
        i32(33, table), i32(1), i32(1), i32(1)).compile()
    big = [(size, op, ln) for size, op, ln in _materialized(compiled.as_text())
           if size >= layer_slice]
    scatters = [ln for _, op, ln in big if op == "scatter"]
    # k and v (and their scales' arrays are smaller than a payload slice)
    assert len(scatters) == 2, scatters
    assert [ln for _, op, ln in big if op not in _IN_PLACE] == []
    if not quantized:
        assert compiled.as_text().count("tpu_custom_call") >= 2
    assert compiled.memory_analysis().temp_size_in_bytes < layer_slice


def test_chat_decode_step_gathers_no_table_wide_context(v5e, monkeypatch):
    """GPT-2 XL's chat decode step (4 rows, a table of 32 blocks of 32
    tokens): before PR 29 every layer gathered each row's whole table as
    ``f32[4,1024,25,64]``, 58% of the step on the chip. The step holds the
    decode kernel and no array of rows x 1,024 positions x the heads, in any
    layout or precision."""
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")  # no interpret
    mod, cfg, blocks, block, _ = _step_family("gpt2-xl")
    rows, table = 4, 32
    on_chip, i32, params, cache = _abstract_step(v5e, mod, cfg, blocks, block)

    def step(params, cache, tokens, slots, positions, tables):
        return mod.ragged_forward(cfg, params, tokens, slots, positions,
                                  tables, cache)

    text = jax.jit(step, donate_argnums=(1,)).lower(
        on_chip(params), on_chip(cache), i32(rows), i32(rows), i32(rows),
        i32(33, table)).compile().as_text()
    calls = [ln for ln in text.splitlines() if " custom-call(" in ln
             and "tpu_custom_call" in ln]
    assert calls and all("paged_decode" in ln.split(" = ")[0] for ln in calls)
    context = rows * table * block * cfg.num_heads * (
        cfg.hidden_size // cfg.num_heads)
    # (the pool itself passes through the kernel and the two row scatters)
    assert [ln for size, op, ln in _materialized(text)
            if op not in ("kernel", "scatter") and size >= context] == []


@pytest.mark.parametrize("family,rows,tiles", [("mixtral", 8, 3),
                                               ("moonlight", 128, 1)])
def test_grouped_step_copies_no_expert_weights(v5e, monkeypatch, family, rows,
                                               tiles):
    """A Mosaic kernel's operand is an array in HBM: handed a layer scan's
    slice of the stacked expert weights, the step copied a layer's whole
    expert weights (2.8 GB at Mixtral's widths) every layer of every step,
    and ran at half the einsum's speed on the chip (PR 27). The scan closes
    over the weights whole (``experts.expert_stacks``) and ``moe_gmm``
    addresses the layer in them: its weight operands are every layer's
    experts, and nothing shaped like one layer's gets a buffer."""
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")  # no interpret
    mod, cfg, blocks, block, table = _step_family(family)
    on_chip, i32, params, cache = _abstract_step(v5e, mod, cfg, blocks, block)

    def step(params, cache, tokens, slots, positions, tables, ts, tp, tv):
        return mod.ragged_forward(
            cfg, params, tokens, slots, positions, tables, cache,
            prefill_tiles=(rows, ts, tp, tv, TILE))

    t = rows + tiles * TILE
    text = jax.jit(step, donate_argnums=(1,)).lower(
        on_chip(params), on_chip(cache), i32(t), i32(t), i32(t),
        i32(129, table), i32(tiles), i32(tiles), i32(tiles)
    ).compile().as_text()
    e, d = cfg.num_experts, cfg.hidden_size
    f = getattr(cfg, "moe_intermediate_size", cfg.intermediate_size)
    layer_weights = (f"bf16[{e},{d},{f}]", f"bf16[{e},{f},{d}]")
    assert [ln for _, _, ln in _materialized(text)
            if ln.split(" = ")[1].startswith(layer_weights)] == []
    (call,) = [ln for ln in text.splitlines()
               if " custom-call(" in ln and "moe_gmm" in ln.split(" = ")[0]]
    layouts = call.split("operand_layout_constraints=")[1]
    n = e * (cfg.num_layers - getattr(cfg, "first_k_dense", 0))
    assert layouts.count(f"bf16[{n},{d},{f}]") == 2      # every layer's
    assert layouts.count(f"bf16[{n},{f},{d}]") == 1


def test_held_grouped_layer_moves_the_rows_that_exist(v5e, monkeypatch):
    """One rank's share of an expert-parallel layer at the window cell's
    shapes (a 400-row step, 8 of 64 experts held, 6 picks a token, 2,560
    wide): the kernel's 3,264-row buffer is a shape, and what the program
    moves around the kernel follows the rows its experts got (PR 46). Before,
    a gather filled all 3,264 rows and the results came back as one float32
    row a PICK, ``f32[2400,2560]``, 66 MB a layer for ~300 real rows. Now the
    buffer is allocated and never set (no broadcast, no gather of its shape:
    blocks of 128 sorted rows are written into it in place under a ``while``)
    and the combine is a second ``while`` over the same blocks."""
    from deepspeed_tpu.models import experts

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")  # no interpret
    t, d, f, e, routed, k, layers = 400, 2560, 768, 8, 64, 6, 2
    dev = jax.sharding.SingleDeviceSharding(v5e[0])

    def s(shape, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=dev)

    def layer(h, topv, topi, w_gate, w_up, w_down, first):
        return experts._grouped_experts(h, topv, topi, w_gate, w_up, w_down,
                                        first, e, (0, routed), 0, "relu")

    text = jax.jit(layer).lower(
        s((t, d)), s((t, k), jnp.float32), s((t, k), jnp.int32),
        s((layers * e, d, f)), s((layers * e, d, f)), s((layers * e, f, d)),
        s((), jnp.int32)).compile().as_text()
    made = _materialized(text)
    rows = 3264  # 512 x 6 picks + 8 x 15 up to ROW_ALIGN, + a pass of 64
    assert [ln for _, _, ln in made if f"f32[{t * k},{d}]" in
            ln.split(" = ")[1].split(" ")[0]] == []
    buffer = {op for _, op, ln in made
              if ln.split(" = ")[1].startswith(f"bf16[{rows},{d}]")}
    assert buffer == {"custom-call", "update"}, buffer
    assert text.count(" while(") == 2
    block = experts._SORTED_BLOCK
    assert any(op == "fusion" and ln.split(" = ")[1].startswith(
        f"bf16[{block},{d}]") for _, op, ln in made)


def test_decode_step_relays_out_no_expert_stack(v5e, monkeypatch):
    """The einsum form reads the scan's own slice of the expert weights,
    which XLA fuses into the einsums. Given every layer's weights whole (what
    the grouped kernel takes), it re-laid the ``w_down`` stack out for the
    einsum outside the layer loop: 2.6 GB copied every decode step at
    Moonlight's widths, -10% in the cell (PR 27). Those widths, 3 expert
    layers, the 128-row decode program: its temporaries stay far under one
    expert matrix of a layer."""
    from deepspeed_tpu.models import deepseek

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")  # no interpret
    cfg = deepseek.DeepseekConfig(vocab_size=512, num_layers=4)
    assert (cfg.hidden_size, cfg.moe_intermediate_size, cfg.num_experts,
            cfg.top_k) == (2048, 1408, 64, 6)
    on_chip, i32, params, cache = _abstract_step(v5e, deepseek, cfg, 65, 128)

    def step(params, cache, tokens, slots, positions, tables):
        return deepseek.ragged_forward(cfg, params, tokens, slots, positions,
                                       tables, cache)

    compiled = jax.jit(step, donate_argnums=(1,)).lower(
        on_chip(params), on_chip(cache), i32(128), i32(128), i32(128),
        i32(129, 32)).compile()
    one_matrix = (cfg.num_experts * cfg.hidden_size
                  * cfg.moe_intermediate_size * 2)
    assert compiled.memory_analysis().temp_size_in_bytes < one_matrix // 8


def test_latent_step_reads_the_pool_once(v5e, monkeypatch):
    """The paged contract on a pool that is ONE leaf (``deepseek``, MLA): a
    mixed step (128 decode rows beside a prefill tile) with a donated pool
    scatters each layer's rows in place (the dense layer outside the scan,
    the expert layers inside it), both attention kernels take the pool
    itself, once each (a block is fetched once for scores and for values:
    no K pool beside a V pool, no copy), and nothing else in the program is
    as large as one layer's slice of the pool."""
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")  # no interpret
    mod, cfg, blocks, block, table = _step_family("moonlight")
    rows, tiles = 128, 1
    on_chip, i32, params, cache = _abstract_step(v5e, mod, cfg, blocks, block)
    (leaf,) = jax.tree_util.tree_leaves(cache)
    assert leaf.shape == (cfg.num_layers, blocks, block, MLA_WIDTH)
    layer_slice = blocks * block * MLA_WIDTH * 2

    def step(params, cache, tokens, slots, positions, tables, ts, tp, tv):
        return mod.ragged_forward(
            cfg, params, tokens, slots, positions, tables, cache,
            prefill_tiles=(rows, ts, tp, tv, TILE))

    t = rows + tiles * TILE
    compiled = jax.jit(step, donate_argnums=(1,)).lower(
        on_chip(params), on_chip(cache), i32(t), i32(t), i32(t),
        i32(129, table), i32(1), i32(1), i32(1)).compile()
    text = compiled.as_text()
    big = [(size, op, ln) for size, op, ln in _materialized(text)
           if size >= layer_slice]
    scatters = [ln for _, op, ln in big if op == "scatter"]
    assert len(scatters) == 2, scatters   # the dense layer's, the scan body's
    assert [ln for _, op, ln in big if op not in _IN_PLACE] == []
    pool_shape = f"bf16[{cfg.num_layers * blocks},{block},{MLA_WIDTH}]"
    kernels = [ln for ln in text.splitlines()
               if " custom-call(" in ln and "tpu_custom_call" in ln]
    # at 256 rows the scan body's routed experts take the grouped form
    grouped = [ln for ln in kernels if "moe_gmm" in ln.split(" = ")[0]]
    attention = [ln for ln in kernels if ln not in grouped]
    assert len(grouped) == 1
    assert len(attention) == 4            # decode and prefill, twice each
    for ln in attention:
        layouts = ln.split("operand_layout_constraints=")[1]
        assert layouts.count(pool_shape) == 1, ln
    assert compiled.memory_analysis().temp_size_in_bytes < layer_slice


# ------------------------------------------------ rows to heads (PR 50)
def _row_major(v5e):
    """``pin(tree)``: ``tree``'s shapes on the first described chip in the
    row-major layout an engine's own arrays have. Left to itself the compiler
    gives a described program's arguments layouts of its own choosing (GPT-2
    XL's ``wte`` comes in column-major and is copied for the row gather: a
    copy no chip run has)."""
    from jax.experimental.layout import Format, Layout

    dev = jax.sharding.SingleDeviceSharding(v5e[0])

    def pin(tree):
        return jax.tree_util.tree_map(
            lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=Format(
                Layout(major_to_minor=tuple(range(len(a.shape)))), dev)),
            tree)

    return pin


def _projection_results(text, params, names):
    """``(weight's name, line)`` of every result of the optimized HLO that is
    a projection weight in a buffer of its own: bf16, as many elements as one
    layer of a stacked ``[L, a, b]`` leaf called one of ``names``, ``a`` or
    ``b`` among its dimensions (``[1, a, b]``, a transpose, the output axis
    split into heads: no array of a step's rows is that), and computed by the
    step. The compiler's own prefetches (``slice-start`` / ``copy-start`` into
    its nearer memory ``S(1)``, their ``-done``, the ``ConcatBitcast`` that
    joins them) keep the stored layout and run beside the products: they are
    not counted, and neither is a kernel's or a scatter's in-place operand."""
    want = {}
    for path, leaf in jax.tree_util.tree_flatten_with_path(params)[0]:
        if getattr(path[-1], "key", None) in names and len(leaf.shape) == 3:
            want[leaf.shape[1:]] = path[-1].key
    out = []
    for _, op, ln in _materialized(text):
        shape = re.match(r"bf16\[([\d,]*)\]", ln.split(" = ")[1])
        if (op in _IN_PLACE or op.endswith("-done") or op == "custom-call"
                or not shape):
            continue
        dims = [int(n) for n in shape.group(1).split(",") if n]
        out += [(name, ln) for (a, b), name in want.items()
                if math.prod(dims) == a * b and (a in dims or b in dims)]
    return out


def _relayout_family(name):
    """``(module, config, pool blocks or (full, sliding), decode slots,
    table width, projections, those known to be re-laid out still)`` at the
    serving cells' attention widths; depth, FFN, experts and vocabulary
    small."""
    from deepspeed_tpu.models import (
        deepseek,
        granite_hybrid,
        jamba,
        lfm2_moe,
        smallthinker,
        solar_open2,
    )

    if name == "lfm2":
        # lfm2-8b-a1b-d12.json's two operators, dense FFN and pool at their
        # published widths: c c a c c (a dense run of two, an attention layer,
        # an expert run of two); 8 of 32 experts, a 128th of the table. Not
        # ``wk`` / ``wv``: [2048, 512] has as many elements as the 512 rows
        # of 2,048 lanes one call of the grouped expert kernel takes
        return lfm2_moe, lfm2_moe.Lfm2MoeConfig(
            vocab_size=512, num_layers=5, layer_types=(
                "conv", "conv", "full_attention", "conv", "conv"),
            num_experts=8, top_k=2), 6145, 513, 32, (
                "w_in", "w_out", "wq", "wo", "router", "w_gate", "w_up",
                "w_down"), ()
    if name == "solar":
        # solar-open2-250b-d4-ep8.json's two mixers, shared expert and pool at
        # their published widths, TWO periods so that the G layer's weights
        # are a stack too (the cell's one period keeps them as ``lead``); 4 of
        # 8 experts, a 48th of the held table. Not ``w_fa`` / ``w_ga``:
        # [4096, 128] has a tile's rows x the hidden size's elements
        return solar_open2, solar_open2.SolarOpen2Config(
            vocab_size=512, num_layers=8, gqa_layers=(0, 4), num_experts=8,
            experts_held=4, top_k=2), 1025, 17, 64, (
                "wq", "wk", "wv", "w_g", "wo", "w_qkv", "w_fb", "w_gb",
                "router", "ws_gate", "ws_up", "ws_down"), ()
    if name == "jamba":
        # ai21-jamba2-3b.json's mixers, MLP and pool at their published
        # widths: m a m m (runs of 1, 1 and 2); a 128th of the table
        return jamba, jamba.JambaConfig(
            vocab_size=512, num_layers=4, attn_layer_period=4,
            attn_layer_offset=1), 8193, 257, 32, (
                "wq", "wk", "wv", "wo", "w_in", "w_x", "w_dt", "w_out",
                "w_gate", "w_up", "w_down"), ()
    if name == "mixtral":
        mod, cfg, blocks, _, table = _step_family(name)
        return mod, cfg, blocks, 33, table, ("wq", "wk", "wv", "wo"), ()
    if name == "granite":
        # granite-4.0-h-small-d10-ep2.json's mixers, shared MLP and pool at
        # their published widths: two runs of Mamba layers around the
        # attention layer; 4 of 8 experts, a sixteenth of the held table
        return granite_hybrid, granite_hybrid.GraniteHybridConfig(
            vocab_size=GRANITE_VOCAB, num_layers=5, layer_types=(
                "mamba", "mamba", "attention", "mamba", "mamba"),
            num_experts=8, experts_held=4, top_k=2), 513, 65, 8, (
                "wq", "wk", "wv", "wo", "w_in", "w_out", "router", "ws_gate",
                "ws_up", "ws_down"), ()
    if name == "smallthinker":   # smallthinker-21b-a3b-ep8.json: F W W W
        return smallthinker, smallthinker.SmallThinkerConfig(
            vocab_size=512, hidden_size=2560, moe_intermediate_size=128,
            num_layers=8, num_heads=SWA_HEADS, num_kv_heads=SWA_KV,
            head_dim=SWA_D, num_experts=8, top_k=2,
            sliding_window=SWA_WINDOW, max_seq_len=8192), SWA_POOLS, 17, \
            SWA_TABLE, ("wq", "wk", "wv", "wo"), ()
    # deepseek-v32-exp-d5-ep16.json's query: 1,536 -> 128 heads of 192. The
    # absorbed products' ``wkv_b`` is a batched product over heads, which
    # wants the head axis outermost: sliced and transposed still (ROADMAP S13)
    return deepseek, deepseek.DeepseekConfig(
        vocab_size=512, intermediate_size=256, moe_intermediate_size=128,
        num_layers=3, num_heads=DSA_HEADS, q_lora_rank=1536, num_experts=4,
        top_k=2), 1025, 17, DSA_TABLE, (
            "wq_a", "wq_b", "wkv_a", "wkv_b", "wo"), ("wkv_b",)


GRANITE_VOCAB = 3136   # no array of a step's rows has 3,136 x 4,096 elements


def _step_text(v5e, mod, cfg, blocks, slots, table, rows, tiles, block=128):
    """``(optimized HLO, abstract bf16 parameters)`` of ``mod``'s ragged step
    of ``rows`` decode rows and ``tiles`` tiles, compiled for the first
    described chip with the arguments laid out as an engine's are
    (``_row_major``); ``blocks`` a pair: a full and a sliding pool, a block
    table each."""
    window = isinstance(blocks, tuple)
    pin = _row_major(v5e)
    params = jax.eval_shape(lambda: jax.tree_util.tree_map(
        lambda a: a.astype(jnp.bfloat16),
        mod.init_params(cfg, jax.random.PRNGKey(0))))
    cache = jax.eval_shape(lambda: mod.init_paged_cache(
        cfg, blocks[0] if window else blocks, block, jnp.bfloat16,
        num_slots=slots))

    def i32(*shape):
        return pin(jax.ShapeDtypeStruct(shape, jnp.int32))

    def step(params, cache, tokens, slots, positions, tables, ts, tp, tv):
        return mod.ragged_forward(
            cfg, params, tokens, slots, positions, tables, cache,
            prefill_tiles=(rows, ts, tp, tv, TILE) if tiles else None)

    t, n = rows + tiles * TILE, max(tiles, 1)
    bt = i32(slots, table)
    text = jax.jit(step, donate_argnums=(1,)).lower(
        pin(params), pin(cache), i32(t), i32(t), i32(t),
        (bt, bt) if window else bt, i32(n), i32(n), i32(n)).compile().as_text()
    return text, params


@pytest.mark.parametrize("family,rows,tiles", [
    ("mixtral", 8, 3), ("mixtral", 4, 0), ("smallthinker", 16, 3),
    ("mla", 16, 1), ("granite", 64, 3), ("granite", 64, 0),
    ("jamba", 256, 3), ("jamba", 256, 0), ("solar", 16, 3), ("solar", 16, 0),
    ("lfm2", 256, 3), ("lfm2", 256, 0)])
def test_step_program_relays_out_no_projection_weight(v5e, monkeypatch, family,
                                                      rows, tiles):
    """The paged contract's *Rows to heads* (``models/paged.py``), on the
    compiled program with the arguments laid out as an engine's are: a step
    reads a layer's projection weights where the stack keeps them. Before
    PR 50 the q and k products came out head-major for the rotation and the
    compiler paid for that on the weights: in every rotated layer's body
    ``%constant_dynamic-slice_fusion = bf16[1,4096,4096]{2,1,0}`` (a layer's
    ``wq`` sliced out of the stack) and ``%copy = bf16[1,4096,4096]{1,2,0}``
    (transposed), the same for ``wk``: 5.2% of the window cell's device time
    (ledger, PR 49). ``paged.rows_to_heads`` pins the product and the rows
    are copied instead. The check for the next family: give it a case."""
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")  # no interpret
    mod, cfg, blocks, slots, table, names, known = _relayout_family(family)
    text, params = _step_text(v5e, mod, cfg, blocks, slots, table, rows,
                              tiles)
    assert text.count("tpu_custom_call") >= 1
    found = _projection_results(text, params, names)
    assert [ln for name, ln in found if name not in known] == []
    # what is known to stay is still there: whoever cures it says so here
    assert {name for name, _ in found} == set(known)
    if family == "granite":
        # the tied table is gathered from as the embedding and multiplied by
        # as the head where it lies: no copy, transposed or not (the
        # compiler's own prefetch of this small one into its nearer memory
        # keeps the stored layout, as ``_projection_results`` says)
        table = GRANITE_VOCAB * cfg.hidden_size
        assert [ln for size, op, ln in _materialized(text)
                if size == 2 * table and " bf16[" in ln
                and op not in _IN_PLACE and not op.endswith("-done")] == []


def _cell_step_text(v5e, monkeypatch, cell, rows, tiles):
    """``_step_text`` of a benchmark cell's own step program: its depth,
    experts, vocabulary, pool, slots and table, read through
    ``benchmark/cellspec.py`` as the harness reads them -> ``(optimized HLO,
    abstract parameters, config)``."""
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")  # no interpret
    monkeypatch.syspath_prepend(os.path.join(
        os.path.dirname(__file__), os.pardir, os.pardir, "benchmark"))
    import cellspec

    spec = cellspec.resolve(cell)
    mod, cfg, _ = cellspec.model(spec)
    sizes = {**spec["config"]["serve"]["engine"],   # as serve_cell.py's
             **spec["cell"].get("engine", {})}
    text, params = _step_text(
        v5e, mod, cfg, sizes["num_blocks"], sizes["max_seqs"] + 1,
        sizes["max_blocks_per_seq"], rows, tiles, block=sizes["block_size"])
    return text, params, cfg


@pytest.mark.parametrize("cell,rows,tiles", [
    ("granite-4.0-h-small-d10-ep2.chat-open", 64, 3),
    ("granite-4.0-h-small-d10-ep2.chat-open", 64, 2),
    ("granite-4.0-h-small-d10-ep2.chat-open", 64, 0),
    ("nemotron-3-super-120b-d11-ep4.reason-pool", 128, 3)],
    ids=["granite-64-3", "granite-64-2", "granite-64-0", "nemotron-128-3"])
def test_mamba2_step_makes_the_in_projection_once(v5e, monkeypatch, cell,
                                                  rows, tiles):
    """A Mamba-2 layer's step makes each column of ``h @ W_in`` exactly once
    (``models/mamba2.split``), on the step programs of the two cells that run
    the mixer, compiled at the cells' OWN depth, expert count, vocabulary,
    pool and slots, read through ``benchmark/cellspec.py`` as the harness
    reads them (12.3-12.6 GB of arguments: the 5-layer configuration of
    ``test_step_program_relays_out_no_projection_weight[granite-64-3]`` does
    not reproduce this). Before PR 54 ``z``, ``xBC`` and ``dt`` were slices
    of one result whose last reader (``z``: the gated norm) comes after the
    whole layer, so the compiler evicted the 15 MB product from its nearer
    memory and, rather than fetch it back, made it AGAIN for the gate:
    ``%fusion.669.remat = bf16[448,16768]{1,0:T(8,128)(2,1)S(1)}`` and
    ``%fusion.730.remat`` in Granite's two scanned bodies (0.0894 + 0.0715 =
    0.1609 s of a 4.0 s slice, ledger PR 53), ``bf16[320,16768]`` at two
    tiles, ``%fusion.423.remat = bf16[512,18560]`` in the hybrid cell's
    (0.0858 s). Held here: no instruction the compiler rematerialised yields
    the product or one of its column parts; each scanned Mamba body has ONE
    reader of its ``W_in`` stack, the product, which reads the stack in place
    (no ``bf16[D, d_inner + conv_width + H]`` of a slice or a copy: splitting
    the product over the weight's columns gave a 137 MB one a layer)."""
    text, params, cfg = _cell_step_text(v5e, monkeypatch, cell, rows, tiles)
    t = rows + tiles * TILE
    d, parts = cfg.hidden_size, (cfg.d_inner, cfg.conv_width,
                                 cfg.mamba_num_heads)
    made = re.compile(r"\s+(?:ROOT )?(%[\w.\-]+) = bf16\[(\d+),(\d+)\]")
    again = [m.group(0) for ln in text.splitlines() for m in [made.match(ln)]
             if m and ".remat" in m.group(1) and int(m.group(2)) == t
             and int(m.group(3)) in parts + (sum(parts),)]
    assert again == []
    # a scanned Mamba body is a computation that takes its run's stack out
    # of the loop's carry; its readers, the tuple that carries it on aside
    stack = re.compile(rf"\s+(%[\w.\-]+) = bf16\[\d+,{d},{sum(parts)}\]\S* "
                       r"get-tuple-element\(")
    fused = set(re.findall(r"calls=(%[\w.\-]+)", text))
    readers = [
        [ln.strip() for ln in lines
         if re.search(rf"[(, ]{re.escape(m.group(1))}[,)]", ln)
         and not re.search(r" (tuple|while)\(", ln)]
        for comp, lines in _computations(text).items() if comp not in fused
        for m in map(stack.match, lines) if m]
    assert len(readers) == sum(
        getattr(path[-1], "key", None) == "w_in"
        for path, _ in jax.tree_util.tree_flatten_with_path(params)[0])
    for lines in readers:
        assert len(lines) == 1 and f" = bf16[{t},{sum(parts)}]" in lines[0], \
            lines
    assert _projection_results(text, params, ("w_in",)) == []


@pytest.mark.parametrize("rows,tiles", [(16, 3), (0, 4), (16, 0)],
                         ids=["mixed-d16-t3", "prefill-t4", "decode-d16"])
def test_solar_cell_step_moves_its_rows_and_nothing_of_a_leafs_size(
        v5e, monkeypatch, rows, tiles):
    """The step programs of ``solar-open2-250b-d4-ep8.longctx-pool`` at the
    cell's OWN sizes (``benchmark/cellspec.py``: 7.38 GB of arguments; a lead
    ``G`` and a scan over three ``K``, two layer bodies), compiled before the
    first chip call as ROADMAP Queue R asks: the four kernels are there at
    their new geometries (``kda_decode`` on 4 MB rows, ``kda_chunk`` on a
    grid of 16 blocks of four heads x tiles, the paged kernels on 64 query heads over 8 K/V
    heads) and the grouped expert kernel in both bodies of a step of 256
    rows or more; nothing the size of a layer's slice of the float32 state
    (``[17, 128, 8192]``, 71 MB) or of the K/V pool gets a buffer of its own
    but in place, the compiler's own prefetches into its nearer memory
    aside; the window leaf ``[3, 17, 48, 1536]`` enters row-major in whole
    tiles and no array of its shape is copied, compressed or uncompressed;
    no projection of a step's rows (``[rows, 8192]``, ``[rows, 24576]``) is
    made a second time (``.remat``); no stacked projection weight is re-laid
    out (D14; ``test_step_program_relays_out_no_projection_weight[solar-*]``
    has the G layer's in a stack too)."""
    text, params, cfg = _cell_step_text(
        v5e, monkeypatch, "solar-open2-250b-d4-ep8.longctx-pool", rows, tiles)
    assert (cfg.layer_pattern, cfg.kda_heads, cfg.held) == ("GKKK", 64, 40)
    names = [ln.split(" = ")[0] for ln in text.splitlines()
             if " custom-call(" in ln and "tpu_custom_call" in ln]
    count = {k: sum(k in n for n in names) for k in (
        "kda_decode", "kda_chunk", "paged_decode", "tiled_prefill", "moe_gmm")}
    assert count == {"kda_decode": bool(rows), "kda_chunk": bool(tiles),
                     "paged_decode": bool(rows), "tiled_prefill": bool(tiles),
                     "moe_gmm": 2 * (rows + tiles * TILE >= 256)}
    state_slice = 17 * 128 * 8192 * 4
    assert [ln for size, op, ln in _materialized(text)
            if size >= state_slice and op not in _IN_PLACE
            and not op.endswith("-done") and "ConcatBitcast" not in ln] == []
    entry = _computations(text)[re.search(r"ENTRY (%[\w.\-]+)", text).group(1)]
    assert [m.group(1)[:9] for ln in entry for m in [re.search(
        r"= bf16\[3,17,48,1536\](\{[^ ]*\}) parameter\(", ln)] if m] == [
            "{3,2,1,0:"]
    assert [ln for _, op, ln in _materialized(text)
            if re.search(r"= bf16\[(3,17|51),48,1536\]", ln)
            and op not in _IN_PLACE] == []
    assert "remat_compressed" not in text and "remat_uncompressed" not in text
    t = rows + tiles * TILE
    assert [ln for ln in text.splitlines() if re.match(
        rf"\s+(?:ROOT )?%[\w.\-]*\.remat[\w.\-]* = \w+\[{t},(8192|24576)\]",
        ln)] == []
    assert _projection_results(text, params, (
        "w_qkv", "w_fb", "w_gb", "wo", "router", "ws_gate", "ws_up",
        "ws_down")) == []


LFM2_CELL = "lfm2-8b-a1b-d12.reason-pool"


@pytest.mark.parametrize("rows,tiles", [(512, 4), (0, 8), (512, 0)],
                         ids=["mixed-d512-t4", "prefill-t8", "decode-d512"])
def test_lfm2_cell_step_moves_its_rows_and_nothing_of_a_leafs_size(
        v5e, monkeypatch, rows, tiles):
    """The step programs of ``lfm2-8b-a1b-d12.reason-pool`` at the cell's OWN
    sizes (``benchmark/cellspec.py``: 7.86 GB of weights, a pool of 6,145
    blocks, 513 slots, tables of 32; runs 2 c (dense), a, 3 c, a, 3 c, a, c:
    seven layer bodies), compiled before the first chip call: the paged
    kernels are there at 64 lanes a head and four query heads a K/V head (a
    decode and a tile kernel an attention layer) and the grouped expert
    kernel, one in each of the six bodies that hold experts (at 513-1,024
    rows it sits in a loop of two turns, ``lax.map`` over
    ``experts._GROUPED_MAX_ROWS`` rows: the layer's experts are read twice a
    step); nothing the size of a layer's slice of
    the K/V pool (``[6145, 128, 512]`` bf16, 805 MB) gets a buffer of its own
    but in place; the window leaf ``[9, 513, 32, 128]`` enters row-major in
    whole tiles and no array of its shape (or of a layer's, ``[513, 32,
    128]``) is copied, compressed or uncompressed: a step's rows are one
    gather and one scatter a layer; no stacked expert or dense weight is
    re-laid out (D14)."""
    text, params, cfg = _cell_step_text(v5e, monkeypatch, LFM2_CELL, rows,
                                        tiles)
    assert [n for _, n in cfg.runs] == [2, 1, 3, 1, 3, 1, 1]
    names = [ln.split(" = ")[0] for ln in text.splitlines()
             if " custom-call(" in ln and "tpu_custom_call" in ln]
    count = {k: sum(k in n for n in names) for k in (
        "paged_decode", "tiled_prefill", "moe_gmm")}
    t = rows + tiles * TILE
    # an expert run is one body: six runs hold experts
    assert t >= 256 and count == {"paged_decode": 3 * bool(rows),
                                  "tiled_prefill": 3 * bool(tiles),
                                  "moe_gmm": 6}
    pool_slice = 6145 * 128 * 512 * 2
    assert [ln for size, op, ln in _materialized(text)
            if size >= pool_slice and op not in _IN_PLACE
            and not op.endswith("-done") and "ConcatBitcast" not in ln] == []
    entry = _computations(text)[re.search(r"ENTRY (%[\w.\-]+)", text).group(1)]
    assert [m.group(1)[:9] for ln in entry for m in [re.search(
        r"= bf16\[9,513,32,128\](\{[^ ]*\}) parameter\(", ln)] if m] == [
            "{3,2,1,0:"]
    assert [ln for _, op, ln in _materialized(text)
            if re.search(r"= bf16\[(9,513|4617|513),32,128\]", ln)
            and op not in _IN_PLACE] == []
    assert "remat_compressed" not in text and "remat_uncompressed" not in text
    # not ``wk`` / ``wv``: [2048, 512] has the elements of 512 rows' 2,048
    # lanes (``test_step_program_relays_out_no_projection_weight[lfm2-*]``
    # holds them at 256 rows)
    assert _projection_results(text, params, (
        "router", "w_gate", "w_up", "w_down")) == []


SALA_CELL = "minicpm-sala-d8.longctx32k-pool"


@pytest.mark.parametrize("rows,tiles", [(16, 3), (0, 4), (16, 0), (0, 3)],
                         ids=["mixed-d16-t3", "prefill-t4", "decode-d16",
                              "prefill-t3"])
def test_sala_cell_step_moves_its_rows_and_nothing_of_a_leafs_size(
        v5e, monkeypatch, rows, tiles):
    """The step programs of ``minicpm-sala-d8.longctx32k-pool`` at the cell's
    OWN sizes (``benchmark/cellspec.py``: pages of 512 tokens, tables of 64,
    17 slots; runs ``S``, ``L`` x 6, ``S``: three layer bodies), compiled
    before the first chip call: the block-sparse kernels are there (a decode
    and a tile kernel a sparse layer), ``ssm_decode`` at a group a head
    (``G = H`` = 32) and ``ssd_chunk`` over the tiles; no head is cut out of
    the tiles' rows as an array of its own (``bf16[3,128,1,128]``, 32 of them
    a tile and layer and 21% of the cell's device time when
    ``mamba2.ssd_tiles`` ran here: ledger, PR 60), in a program without
    decode rows either; nothing the size of a layer's slice of the K/V pool
    (``[1089, 512, 256]`` bf16, 285 MB), of the compressed keys or of the
    float32 state (``[17, 128, 4096]``, 36 MB) gets a buffer of its own but
    in place (the pool seen in blocks of a tile's and of the selection's rows
    is a bitcast: ``paged.sub_blocks``); no projection of a step's rows is
    made a second time; no stacked projection weight is re-laid out."""
    text, params, cfg = _cell_step_text(v5e, monkeypatch, SALA_CELL, rows,
                                        tiles)
    assert [n for _, n in cfg.runs] == [1, 6, 1]
    names = [ln.split(" = ")[0] for ln in text.splitlines()
             if " custom-call(" in ln and "tpu_custom_call" in ln]
    count = {k: sum(k in n for n in names) for k in (
        "bsa_decode", "bsa_prefill", "ssm_decode", "ssd_chunk")}
    assert count == {"bsa_decode": 2 * bool(rows), "bsa_prefill": 2 * bool(tiles),
                     "ssm_decode": bool(rows), "ssd_chunk": bool(tiles)}
    assert [ln for _, _, ln in _materialized(text)
            if re.search(r"= \w+\[\d+,128,1,128\]", ln)] == []
    # a leaf, whole or a layer's slice of it, however the step sees it: the
    # state, the pages (in pages, in tiles' rows, in selection blocks), the
    # compressed keys
    leaf = re.compile(
        r"= (f32\[(6,17|102),128,4096\]|bf16\[(2,1089|2178),(512|32),256\]"
        r"|bf16\[8712,128,256\]|bf16\[17424,64,256\])")
    assert [ln for _, op, ln in _materialized(text)
            if leaf.search(ln) and op not in _IN_PLACE] == []
    t = rows + tiles * TILE
    assert [ln for ln in text.splitlines() if re.match(
        rf"\s+(?:ROOT )?%[\w.\-]*\.remat[\w.\-]* = \w+\[{t},(4096|16384)\]",
        ln)] == []
    assert _projection_results(text, params, (
        "wq", "wk", "wv", "w_z", "wo", "w_gate", "w_up", "w_down")) == []


@pytest.mark.parametrize("cell,rows,kernel", [
    ("deepseek-v32-exp-d5-ep16.longctx-pool", 16, "dsa_attn_prefill"),
    ("moonlight-16b-a3b-d8.reason-pool", 128, "mla_prefill")],
    ids=["sparse-d16-t3", "moonlight-d128-t3"])
def test_latent_tile_rows_cross_memory_once_each_way(v5e, monkeypatch, cell,
                                                     rows, kernel):
    """The paged contract's *Rows to heads*, second clause
    (``models/paged.py``): the absorbed products are batched over heads and
    lay their rows head-major, and the latent prefill kernels take a step's
    TILE rows and give them back that way. On the mixed step programs of the
    two cells, compiled at the cells' own sizes: in every layer body the
    bfloat16 arrays of ``tile rows x H x lat`` (or ``x W``) elements are the
    absorbed product's result, which IS the kernel's ``q_lat`` operand (the
    roped lanes ride beside it, a quarter of its size, and the kernel joins
    the two in VMEM), and the kernel's result, which the value product reads
    where the
    kernel wrote it. Before PR 56 the kernels' rows were (query, head) and
    the sparse cell's scanned body held, 128 heads x 384 tile rows + 16
    decode rows,

        %fusion.560 = bf16[128,512,400]{1,2,0}          52.4 MB  thn,lhn->thl
        %copy_bitcast_fusion.9 = bf16[400,128,512]{2,1,0}  52.4  its transpose
        %maximum_maximum_fusion.6 = bf16[400,128,640]   65.5  [q_lat, q_rope, 0]
        %slice_multiply_fusion.3 = bf16[384,128,640]    62.9  q[n_dec:] * scale
        %dsa_attn_prefill.13 = bf16[49152,512]          50.3  the kernel's result
        %copy.235 = bf16[384,128,512]{2,0,1}            50.3  head-major again

    three re-layouts of rows that were right somewhere already, ~330 MB of
    traffic a layer and step (the dense lead layer's ``copy_bitcast_fusion.3``,
    ``slice_multiply_fusion.1``, ``copy.153`` alike; Moonlight's at 16 heads a
    sixteenth of it)."""
    text, _, cfg = _cell_step_text(v5e, monkeypatch, cell, rows, 3)
    h, lat, width = cfg.num_heads, cfg.kv_lora_rank, cfg.row_lanes
    tile_rows = 3 * TILE
    sizes = {n * h * lanes for n in (tile_rows, tile_rows + rows)
             for lanes in (lat, width)}
    body_of = {ln.strip(): comp for comp, lines in _computations(text).items()
               for ln in lines}

    def name(ln):
        return ln.removeprefix("ROOT ").split(" = ")[0]

    def operands(ln):
        return re.findall(r"%[\w.\-]+", ln.split(" = ", 1)[1].split(
            ", custom_call_target")[0].split(", kind=")[0])

    bodies = {}
    for size, op, ln in _materialized(text):
        # but the compiler's own prefetches: Moonlight's 2,048 x 2,048
        # projections have as many elements as its 512 rows x 16 x 512
        if (ln.split(" = ")[1].startswith("bf16[") and size // 2 in sizes
                and not op.endswith("-done")):
            bodies.setdefault(body_of[ln], []).append((op, ln))
    held = {comp: found for comp, found in bodies.items()
            if any(op == "kernel" and name(ln).startswith("%" + kernel)
                   for op, ln in found)}
    assert len(held) == 2, sorted(bodies)      # the lead layer, the scan's
    for comp, found in held.items():
        (call,) = [ln for op, ln in found if op == "kernel"]
        (product,) = [ln for op, ln in found if op != "kernel"]
        assert "dot_general" in product
        assert operands(call)[4] == name(product)   # after the 4 prefetched
        readers = [ln for ln in _computations(text)[comp]
                   if " = " in ln and name(call) in operands(ln.strip())]
        assert readers and not [ln for ln in readers if re.search(
            r" (copy|transpose)\(", ln)], readers


def test_a_table_kept_column_major_is_copied_for_its_row_gather(v5e,
                                                                monkeypatch):
    """GPT-2 XL's chat step held ``copy bf16[50257,1600]`` every step, 7.6% of
    it (ledger, PR 49), and it is not the tied head's: a v5e keeps an array
    whose rows are no whole number of 128-lane tiles column-major
    (``major_to_minor=(1, 0)``: ``wte`` and ``wpe`` at 1,600 lanes, as
    ``jax.jit`` hands them back on the chip), the head's product reads that in
    place, and the token GATHER wants contiguous rows and copies the table for
    them. The same step with ``wte`` row-major copies nothing of its size:
    0.73 -> 0.41 ms for gather + head on the chip (PERF.md section 6, PR 50).
    Since PR 52 the engine holds such a table row-major from the moment it
    takes the parameters: the next test drives its rule."""
    from jax.experimental.layout import Format, Layout

    from deepspeed_tpu.models import gpt2

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")  # no interpret
    cfg = gpt2.GPT2Config(vocab_size=50257, hidden_size=1600, num_layers=2,
                          num_heads=25)
    pin = _row_major(v5e)
    dev = jax.sharding.SingleDeviceSharding(v5e[0])
    params = pin(jax.eval_shape(lambda: jax.tree_util.tree_map(
        lambda a: a.astype(jnp.bfloat16),
        gpt2.init_params(cfg, jax.random.PRNGKey(0)))))
    cache = pin(jax.eval_shape(lambda: gpt2.init_paged_cache(
        cfg, 513, 32, jnp.bfloat16)))
    rows = pin(jax.ShapeDtypeStruct((4,), jnp.int32))
    tables = pin(jax.ShapeDtypeStruct((33, 32), jnp.int32))

    def step(params, cache, tokens, slots, positions, tables):
        logits, cache = gpt2.ragged_forward(cfg, params, tokens, slots,
                                            positions, tables, cache)
        return jnp.argmax(logits.astype(jnp.float32), axis=-1), cache

    def copies(params):
        text = jax.jit(step, donate_argnums=(1,)).lower(
            params, cache, rows, rows, rows, tables).compile().as_text()
        return [ln for size, op, ln in _materialized(text)
                if op == "copy" and size >= 50257 * 1600 * 2]

    assert copies(params) == []
    as_the_chip_keeps_it = {**params, "wte": jax.ShapeDtypeStruct(
        (50257, 1600), jnp.bfloat16,
        sharding=Format(Layout(major_to_minor=(1, 0)), dev))}
    (copy,) = copies(as_the_chip_keeps_it)
    assert "bf16[50257,1600]{1,0" in copy and "params[" in copy


def _as_the_chip_hands_back(v5e, shapes):
    """``shapes`` ({name: shape}, bf16) as abstract arrays in the formats the
    described chip gives the results of a ``jax.jit`` that says none: what an
    engine's caller hands it (``benchmark/serve_cell.py`` makes the weights
    so). On the chip ``[50257, 1600]`` and ``[1024, 1600]`` come back
    ``major_to_minor=(1, 0)`` (PERF.md section 6, PR 50); the described
    compile says the same."""
    dev = jax.sharding.SingleDeviceSharding(v5e[0])

    def made(key):
        return {name: jax.random.normal(key, shape, jnp.bfloat16)
                for name, shape in shapes.items()}

    compiled = jax.jit(made, out_shardings=dev).lower(jax.ShapeDtypeStruct(
        (2,), jnp.uint32, sharding=dev)).compile()
    return {name: jax.ShapeDtypeStruct(shapes[name], jnp.bfloat16, sharding=f)
            for name, f in compiled.output_formats.items()}


# [vocabulary, hidden] of every other configuration under benchmark/configs
OTHER_TABLES = {
    "mixtral": (32000, 4096), "moonlight": (163840, 2048),
    "nemotron": (32768, 4096), "sparse": (16160, 7168),
    "longcat": (16384, 6144), "kimi": (20480, 2304),
    "smallthinker": (18992, 2560), "sdar": (151936, 2048),
    "granite": (50176, 4096)}


def test_the_engine_lays_a_column_major_table_out_for_its_row_gather(
        v5e, monkeypatch):
    """The engine's rule (``inference/ragged.py``, PR 52) on the described
    chip's own layouts. GPT-2 XL's tables as the chip hands them back are
    column-major, ``row_gather_tables`` finds both, ONE program
    (``ragged_tables_row_major``) re-lays both, and the step program compiled
    from its results copies nothing of a table's size, where the same step on
    the tables as they came copies ``wte``. Every other configuration's table
    (``[32000, 4096]`` ...: rows of whole tiles) comes back row-major: the
    rule finds nothing, builds nothing and hands the tree back as it is."""
    from deepspeed_tpu.inference import ragged
    from deepspeed_tpu.models import gpt2

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")  # no interpret
    cfg = gpt2.GPT2Config(vocab_size=50257, hidden_size=1600, num_layers=2,
                          num_heads=25)
    names = gpt2.build(cfg).woq_skip
    pin = _row_major(v5e)
    tables = _as_the_chip_hands_back(
        v5e, {"wte": (50257, 1600), "wpe": (1024, 1600), **OTHER_TABLES})
    others = {cell: {"embed": tables[cell]} for cell in OTHER_TABLES}
    assert ragged.row_gather_tables(others, ("embed",)) == []
    assert ragged.lay_out_for_row_gather(others, ("embed",)) == (others, ())
    params = {**pin(jax.eval_shape(lambda: jax.tree_util.tree_map(
        lambda a: a.astype(jnp.bfloat16),
        gpt2.init_params(cfg, jax.random.PRNGKey(0))))),
        "wte": tables["wte"], "wpe": tables["wpe"]}
    found = ragged.row_gather_tables(params, names)
    assert sorted(path[-1].key for path, _ in found) == ["wpe", "wte"]
    came = tuple(leaf for _, leaf in found)
    program = ragged.row_major_program(came).lower(came).compile()
    assert "ragged_tables_row_major" in program.as_text()
    relaid = {path[-1].key: jax.ShapeDtypeStruct(
        leaf.shape, leaf.dtype, sharding=f)
        for (path, leaf), f in zip(found, program.output_formats)}
    assert ragged.row_gather_tables(relaid, names) == []
    cache = pin(jax.eval_shape(lambda: gpt2.init_paged_cache(
        cfg, 513, 32, jnp.bfloat16)))
    rows = pin(jax.ShapeDtypeStruct((4,), jnp.int32))
    block_tables = pin(jax.ShapeDtypeStruct((33, 32), jnp.int32))

    def step(params, cache, tokens, slots, positions, block_tables):
        logits, cache = gpt2.ragged_forward(cfg, params, tokens, slots,
                                            positions, block_tables, cache)
        return jnp.argmax(logits.astype(jnp.float32), axis=-1), cache

    def copies(params):
        text = jax.jit(step, donate_argnums=(1,)).lower(
            params, cache, rows, rows, rows, block_tables).compile().as_text()
        return [ln for size, op, ln in _materialized(text)
                if op == "copy" and size >= 1024 * 1600 * 2]

    assert len(copies(params)) >= 1
    assert copies({**params, **relaid}) == []


# ------------------------------------------------- the contract's slot leaves
def _tiled_bytes(shape, layout, itemsize):
    """Bytes an array of ``shape`` occupies as the compiler lays it out:
    ``layout`` is the HLO's ``{minor_to_major:T(rows,lanes)...}``. The two
    minor axes are padded to whole tiles; a ``(2,1)`` second tile packs two
    rows of a 2-byte type into one sublane."""
    import math
    import re

    order = [int(i) for i in layout.strip("{}").split(":")[0].split(",")]
    rows, lanes = map(int, re.search(r"T\((\d+),(\d+)\)", layout).groups())
    packed = re.search(r"\)\((\d+),1\)", layout)
    rows *= int(packed.group(1)) if packed else 1
    dims = [shape[i] for i in order]
    dims[0] = math.ceil(dims[0] / lanes) * lanes
    dims[1] = math.ceil(dims[1] / rows) * rows
    return math.prod(dims) * itemsize


def _window_leaf_stays_put(text, leaf, scatters):
    """The window leaf's clause of the contract (``models/paged.py``, *Window
    leaves*), on the optimized HLO ``text`` of a step program whose cache
    holds ``leaf``: the argument is whole tiles (no axis padded by more than
    a tenth, no axis order of the compiler's own: the layers x slots merge is
    a bitcast), the only arrays of its size with a buffer of their own are
    the rows' ``scatters`` (in place), and the compiler neither compresses
    nor uncompresses anything between uses."""
    shape = ",".join(map(str, leaf.shape))
    logical = math.prod(leaf.shape) * leaf.dtype.itemsize
    entry = _computations(text)[re.search(r"ENTRY (%[\w.\-]+)", text).group(1)]
    layouts = [m.group(1) for ln in entry for m in [re.search(
        rf"= bf16\[{shape}\](\{{[^ ]*\}}) parameter\(", ln)] if m]
    assert len(layouts) == 1, layouts
    assert layouts[0].startswith("{3,2,1,0:"), layouts[0]
    assert _tiled_bytes(leaf.shape, layouts[0], 2) <= 1.1 * logical, layouts[0]
    big = [(size, op, ln) for size, op, ln in _materialized(text)
           if size >= logical]
    assert [ln for _, op, ln in big
            if op not in _IN_PLACE] == []
    assert len([ln for size, _, ln in big if size == logical]) == scatters
    assert "remat_compressed" not in text and "remat_uncompressed" not in text


@pytest.mark.parametrize("window", [False, True], ids=["state", "window"])
@pytest.mark.parametrize("rows,tiles", [(8, 1), (8, 0), (0, 2)],
                         ids=["mixed", "decode", "prefill"])
def test_step_program_holds_no_layer_slice_of_the_slot_state(v5e, monkeypatch,
                                                             rows, tiles,
                                                             window):
    """The paged contract's clause for slot leaves (``models/paged.py``), on
    the compiled program, at Nemotron-3-Super's Mamba and attention widths
    and the cell's pool and slots (FFN, experts and vocabulary small, so an
    array as large as a layer's slice of the state can only be the state's):
    a step reads the rows of its slots and writes them back in place. The
    decode rows go through ``ssm_decode`` (state aliased in and out), a
    tile's row is ONE dynamic slice and ONE dynamic-update-slice, the
    attention layer's K and V are scattered as in every other family. No
    gather, copy or re-layout of the float32 state ``[2 x 129, 128, 8192]``
    or of one layer's ``[129, 128, 8192]``: with a whole-row gather XLA
    re-laid the entire leaf out in lane quarters, and with one einsum over
    the group axis it transposed it, every step (PERF.md section 6, PR 31).

    ``window``: the same for the convolution's window leaf, at the cell's
    five Mamba layers and a hidden size small enough that the leaf (129
    slots x 3 rows of 10,240 channels a layer) is the largest bf16 array
    beside the pool: ``_window_leaf_stays_put``. With the three rows on the
    sublanes the compiler kept the argument in an axis order of its own and
    copied the whole leaf to a padded layout and back, every step (PR 41)."""
    from deepspeed_tpu.models import nemotron_h

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")  # no interpret
    cfg = nemotron_h.NemotronHConfig(
        vocab_size=512, num_layers=5, hybrid_override_pattern="*EMEM",
        moe_intermediate_size=128, moe_shared_expert_intermediate_size=256,
        num_experts=16, experts_held=4, top_k=6)
    if window:
        cfg = dataclasses.replace(cfg, hidden_size=512, num_layers=11,
                                  hybrid_override_pattern="*" + "EM" * 5)
    mamba = cfg.layers_of("M")
    blocks, block, table, slots = 4097, 128, 32, 129
    dev = jax.sharding.SingleDeviceSharding(v5e[0])

    def on_chip(tree):
        return jax.tree_util.tree_map(
            lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=dev),
            tree)

    def i32(*shape):
        return jax.ShapeDtypeStruct(shape, jnp.int32, sharding=dev)

    params = jax.eval_shape(lambda: jax.tree_util.tree_map(
        lambda a: a.astype(jnp.bfloat16),
        nemotron_h.init_params(cfg, jax.random.PRNGKey(0))))
    cache = jax.eval_shape(lambda: nemotron_h.init_paged_cache(
        cfg, blocks, block, jnp.bfloat16, num_slots=slots))
    ssm = cache["slots"]["ssm"]
    assert ssm.shape == (mamba, slots, 128, 8192) and ssm.dtype == jnp.float32
    assert cache["k"].shape == (1, blocks, block, 256)
    state_slice = slots * 128 * 8192 * 4
    pool_slice = blocks * block * 256 * 2

    def step(params, cache, tokens, slots, positions, tables, ts, tp, tv):
        return nemotron_h.ragged_forward(
            cfg, params, tokens, slots, positions, tables, cache,
            prefill_tiles=(rows, ts, tp, tv, TILE))

    t, nt = rows + tiles * TILE, max(tiles, 1)
    compiled = jax.jit(step, donate_argnums=(1,)).lower(
        on_chip(params), on_chip(cache), i32(t), i32(t), i32(t),
        i32(slots, table), i32(nt), i32(nt), i32(nt)).compile()
    text = compiled.as_text()
    big = [(size, op, ln) for size, op, ln in _materialized(text)
           if size >= pool_slice]
    # in place: the K and V scatters of the decode rows and updates of the
    # tiles' blocks, the kernel's aliased state, a tile's row of the state
    assert [ln for _, op, ln in big
            if op not in _IN_PLACE] == []
    assert len([ln for _, op, ln in big if op == "scatter"]) == (
        2 if rows else 0)
    assert len([ln for _, op, ln in big
                if op == "update" and " = f32[" in ln]) == tiles
    names = [ln.split(" = ")[0] for ln in text.splitlines()
             if " custom-call(" in ln and "tpu_custom_call" in ln]
    assert sum("ssm_decode" in n for n in names) == (1 if rows else 0)
    # the whole state's and a layer's shape appear as nothing but the
    # parameter, its merged view, the loop's carry and the in-place updates
    assert compiled.memory_analysis().temp_size_in_bytes < min(state_slice,
                                                               pool_slice)
    if window:
        # one layer body: a scatter of the decode rows, one of the tiles
        _window_leaf_stays_put(text, cache["slots"]["conv"],
                               bool(rows) + bool(tiles))


@pytest.mark.parametrize("rows,tiles", [(256, 2), (256, 0), (0, 2)],
                         ids=["mixed", "decode", "prefill"])
def test_step_program_holds_no_layer_slice_of_the_selscan_state(
        v5e, monkeypatch, rows, tiles):
    """The slot-leaf clause for the third recurrence, at AI21-Jamba2-3B's
    Mamba-1 and attention widths and the cell's pool and slots (m a m m; MLP
    and vocabulary small): the decode rows go through ``selscan_decode`` and
    the tiles through ``selscan_tile``, both with the float32 state ``[3 x
    257, 16, 5120]`` aliased in and out, so no gather, slice, copy or
    re-layout of it or of a layer's part exists; the K and V of ONE K/V head
    (128 lanes a row) are scattered as in every other family; and the
    convolution's window leaf ``[3, 257, 24, 640]`` (5,120 channels fold over
    HALF a bfloat16 tile's rows: ``paged.init_window_leaf``) enters row-major
    and is touched by the rows' scatters alone. Kept ``[.., 3, 5120]`` the
    compiler copied the whole leaf (205 MB at the cell's 26 layers) to an
    axis order of its own and back, every step (PERF.md section 6, PR 53)."""
    from deepspeed_tpu.models import jamba

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")  # no interpret
    cfg = jamba.JambaConfig(vocab_size=512, intermediate_size=256,
                            num_layers=4, attn_layer_period=4,
                            attn_layer_offset=1)
    blocks, block, table, slots = 8193, 128, 32, 257
    pin = _row_major(v5e)
    params = jax.eval_shape(lambda: jax.tree_util.tree_map(
        lambda a: a.astype(jnp.bfloat16),
        jamba.init_params(cfg, jax.random.PRNGKey(0))))
    cache = jax.eval_shape(lambda: jamba.init_paged_cache(
        cfg, blocks, block, jnp.bfloat16, num_slots=slots))
    ssm, conv = cache["slots"]["ssm"], cache["slots"]["conv"]
    assert ssm.shape == (3, slots, 16, 5120) and ssm.dtype == jnp.float32
    assert conv.shape == (3, slots, 24, 640)
    assert cache["k"].shape == (1, blocks, block, 128)
    state_slice = slots * 16 * 5120 * 4

    def i32(*shape):
        return pin(jax.ShapeDtypeStruct(shape, jnp.int32))

    def step(params, cache, tokens, slots, positions, tables, ts, tp, tv):
        return jamba.ragged_forward(
            cfg, params, tokens, slots, positions, tables, cache,
            prefill_tiles=(rows, ts, tp, tv, TILE))

    t, nt = rows + tiles * TILE, max(tiles, 1)
    compiled = jax.jit(step, donate_argnums=(1,)).lower(
        pin(params), pin(cache), i32(t), i32(t), i32(t), i32(slots, table),
        i32(nt), i32(nt), i32(nt)).compile()
    text = compiled.as_text()
    # (the compiler's own prefetches of a layer's weights into its nearer
    # memory keep the stored layout: ``_projection_results`` says the same)
    big = [(size, op, ln) for size, op, ln in _materialized(text)
           if size >= state_slice and not op.endswith("-done")
           and op != "custom-call"]
    assert [ln for _, op, ln in big if op not in _IN_PLACE] == []
    names = [ln.split(" = ")[0] for ln in text.splitlines()
             if " custom-call(" in ln and "tpu_custom_call" in ln]
    # two layer bodies with Mamba layers (a run of one, a run of two)
    assert sum("selscan_decode" in n for n in names) == (2 if rows else 0)
    assert sum("selscan_tile" in n for n in names) == (2 if tiles else 0)
    assert compiled.memory_analysis().temp_size_in_bytes < state_slice
    # the window leaf enters as the engine's array lies and no copy of it
    # (or of a layer's part) exists
    entry = _computations(text)[re.search(r"ENTRY (%[\w.\-]+)", text).group(1)]
    shape = ",".join(map(str, conv.shape))
    layouts = [m.group(1) for ln in entry for m in [re.search(
        rf"= bf16\[{shape}\](\{{[^ ]*\}}) parameter\(", ln)] if m]
    assert len(layouts) == 1 and layouts[0].startswith("{3,2,1,0:"), layouts
    assert not re.search(r"= bf16\[(3,257|771|257),24,640\]\S* (copy|transpose)\(",
                         text)


@pytest.mark.parametrize("window", [False, True], ids=["state", "window"])
@pytest.mark.parametrize("rows,tiles", [(8, 1), (8, 0), (0, 2)],
                         ids=["mixed", "decode", "prefill"])
def test_step_program_holds_no_layer_slice_of_the_kda_state(v5e, monkeypatch,
                                                            rows, tiles,
                                                            window):
    """The same clause for the first cache that has slot leaves BESIDE a
    latent pool, at Kimi-Linear's KDA and MLA widths and the cell's pool and
    slots (FFN, experts and vocabulary small): lead ``D``, period ``KM``. The
    decode rows go through ``kda_decode``, the tiles through ``kda_chunk``
    (each with the state aliased in and out), the MLA layer's latent rows
    are scattered as in ``deepseek``. No gather, copy or re-layout of the
    float32 state ``[3 x 129, 128, 4096]``: in a step program with tiles and
    NO decode row nothing held the array's layout until the tiles' states
    moved through a kernel, and XLA laid all of it out with the key channels
    on the lanes, 2.7 GB in and out at the cell's ten layers (the compiled
    program, PR 40). The chunk form is the kernel and nothing beside it: no
    float32 product of a tile's ``[.., 32, 128, 128]`` blocks is left to XLA.

    ``window``: the same for the convolutions' window leaf, at the cell's
    thirteen layers (ten of them KDA) and a hidden size small enough that
    the leaf (129 slots x 3 rows of 12,288 channels a layer) is the largest
    bf16 array beside the pool: ``_window_leaf_stays_put``. With the three
    rows on the sublanes the compiler copied the whole leaf to a padded
    layout and back and, in the cell's program, compressed and uncompressed
    it between the layers: 9.4% of the cell's device time (ledger, PR 40)."""
    from deepspeed_tpu.models import kimi_linear, paged

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")  # no interpret
    widths = {"head_dim": 128, "num_heads": 32, "short_conv_kernel_size": 4}
    cfg = kimi_linear.KimiLinearConfig(
        vocab_size=512, num_layers=5, intermediate_size=256,
        moe_intermediate_size=128, num_experts=16, experts_held=4, top_k=6,
        linear_attn_config={"kda_layers": [1, 2, 4], "full_attn_layers": [3, 5],
                            **widths})
    assert paged.stack_plan_tail(cfg.layer_pattern) == ("D", "KM", 2, "")
    if window:
        cfg = dataclasses.replace(
            cfg, hidden_size=512, num_layers=13, linear_attn_config={
                "kda_layers": [1, 2, 3, 5, 6, 7, 9, 10, 11, 13],
                "full_attn_layers": [4, 8, 12], **widths})
    bodies = "".join(paged.stack_plan_tail(cfg.layer_pattern)[i] for i in (0, 1, 3))
    n_kda, n_mla = bodies.count("D") + bodies.count("K"), bodies.count("M")
    blocks, block, table, slots = 4097, 128, 32, 129
    dev = jax.sharding.SingleDeviceSharding(v5e[0])

    def on_chip(tree):
        return jax.tree_util.tree_map(
            lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=dev),
            tree)

    def i32(*shape):
        return jax.ShapeDtypeStruct(shape, jnp.int32, sharding=dev)

    params = jax.eval_shape(lambda: jax.tree_util.tree_map(
        lambda a: a.astype(jnp.bfloat16),
        kimi_linear.init_params(cfg, jax.random.PRNGKey(0))))
    cache = jax.eval_shape(lambda: kimi_linear.init_paged_cache(
        cfg, blocks, block, jnp.bfloat16, num_slots=slots))
    kda = cache["slots"]["kda"]
    assert kda.shape == (cfg.layers_of("DK"), slots, 128, 4096)
    assert kda.dtype == jnp.float32
    assert cache["kv"].shape == (cfg.layers_of("MA"), blocks, block, 640)
    state_slice = slots * 128 * 4096 * 4
    pool_slice = blocks * block * 640 * 2

    def step(params, cache, tokens, slots, positions, tables, ts, tp, tv):
        return kimi_linear.ragged_forward(
            cfg, params, tokens, slots, positions, tables, cache,
            prefill_tiles=(rows, ts, tp, tv, TILE))

    t, nt = rows + tiles * TILE, max(tiles, 1)
    compiled = jax.jit(step, donate_argnums=(1,)).lower(
        on_chip(params), on_chip(cache), i32(t), i32(t), i32(t),
        i32(slots, table), i32(nt), i32(nt), i32(nt)).compile()
    text = compiled.as_text()
    big = [(size, op, ln) for size, op, ln in _materialized(text)
           if size >= min(state_slice, pool_slice)]
    # in place: the latent rows' scatter, the kernels' aliased state
    assert [ln for _, op, ln in big if op not in _IN_PLACE] == []
    names = [ln.split(" = ")[0] for ln in text.splitlines()
             if " custom-call(" in ln and "tpu_custom_call" in ln]
    assert sum("kda_decode" in n for n in names) == (n_kda if rows else 0)
    assert sum("kda_chunk" in n for n in names) == (n_kda if tiles else 0)
    assert not re.search(r"= f32\[\d+,32,128,128\]\S* (convolution|dot)\(", text)
    assert sum("mla_decode" in n for n in names) == (n_mla if rows else 0)
    assert compiled.memory_analysis().temp_size_in_bytes < min(state_slice,
                                                               pool_slice)
    if window:
        # a KDA layer body: a scatter of the decode rows, one of the tiles
        _window_leaf_stays_put(text, cache["slots"]["conv"],
                               n_kda * (bool(rows) + bool(tiles)))
        # the step's rows are folded as a copy of their own: handed the fold
        # behind the projection, the compiler transposed a layer's whole
        # ``w_qkv`` to emit the decode rows folded (``decode_windows``)
        assert not re.search(r"= bf16\[1,512,12288\]\S* copy\(", text)


# ------------------------------------------------ ZeRO stage 3 on four chips
# The loss and its gradient as the training engine builds them (bf16 compute
# of fp32 masters on the plan's stage-3 shardings, full remat, 16 x 1024
# tokens), compiled for the four described chips: gpt2 at GPT-2 XL's widths
# (benchmark/configs/gpt2-xl.json), llama at TinyLlama-1.1B's
# (gate / up / down), two layers and a small vocabulary where the tied table
# is not what is looked at.
def _stage3_family(name):
    from deepspeed_tpu.models import gpt2, llama

    if name == "gpt2-xl":
        return gpt2, gpt2.GPT2Config(
            vocab_size=50257, hidden_size=1600, num_layers=2, num_heads=25,
            max_seq_len=1024), ("wq", "w_in", "w_out")
    return llama, llama.LlamaConfig(
        vocab_size=512, hidden_size=2048, intermediate_size=5632,
        num_layers=2, num_heads=32, num_kv_heads=4,
        max_seq_len=1024), ("wq", "w_gate", "w_down")


def _stage3_loss_and_grad(v5e, mod, cfg, stated=True):
    """``(optimized HLO text, plan, abstract params)`` of a family's stage-3
    loss + gradient over ``{"fsdp": 4}``, with the engine's gather hook or
    without it."""
    from deepspeed_tpu.comm.topology import MeshTopology
    from deepspeed_tpu.config.config import MeshConfig
    from deepspeed_tpu.models.api import ShardCtx
    from deepspeed_tpu.parallel.partition import plan_sharding
    from deepspeed_tpu.parallel.qwz import WeightGather
    from deepspeed_tpu.runtime import precision

    topo = MeshTopology.build(MeshConfig(data=1, fsdp=4), devices=list(v5e))
    ctx = ShardCtx(mesh=topo.mesh, remat=True)
    spec = mod.build(cfg, ctx=ctx)
    abstract = jax.eval_shape(spec.init_fn, jax.random.PRNGKey(0))
    plan = plan_sharding(spec.param_logical_axes, abstract, topo, zero_stage=3,
                         use_tp=False, dim_units=spec.logical_dim_units)
    if stated:
        ctx.weight_gather = WeightGather(topo.mesh, plan.param_specs)

    def step(params, ids):
        loss, grads = jax.value_and_grad(
            lambda p: spec.loss_fn(p, {"input_ids": ids}, None))(
                precision.cast_to_compute(params, jnp.bfloat16))
        return loss, jax.tree_util.tree_map(
            lambda g, s: jax.lax.with_sharding_constraint(
                g.astype(jnp.float32), s), grads, plan.grad_shardings)

    params = jax.tree_util.tree_map(
        lambda a, s: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=s),
        abstract, plan.param_shardings)
    ids = jax.ShapeDtypeStruct((16, 1024), jnp.int32,
                               sharding=plan.batch_sharding)
    return jax.jit(step).lower(params, ids).compile().as_text(), plan, abstract


def _loop_lines(text):
    """The instructions of every ``while`` body of the optimized HLO and of
    the computations they call."""
    import re

    bodies = _computations(text)
    todo = set(re.findall(r"body=(%[\w.\-]+)", text))
    seen = set()
    while todo:
        comp = todo.pop()
        seen.add(comp)
        for ln in bodies.get(comp, ()):
            todo.update(set(re.findall(
                r"(?:calls|body|condition|to_apply)=(%[\w.\-]+)", ln)) - seen)
    return [ln for comp in seen for ln in bodies.get(comp, ())]


def _results(lines, opcode):
    """Array shapes ``(dtype, dims)`` among the results of ``opcode``
    instructions (a tuple's members each)."""
    import re

    out = []
    for ln in lines:
        m = re.match(r"\s+(?:ROOT )?%[\w.\-]+ = (.*?) " + opcode + r"\(", ln)
        if m:
            out += [(dt, tuple(int(n) for n in dims.split(",") if n))
                    for dt, dims in re.findall(r"(\w+)\[([\d,]*)\]", m.group(1))]
    return out


@pytest.mark.parametrize("family", ["gpt2-xl", "tinyllama"])
def test_stage3_gathers_a_layers_weights_before_it_multiplies(
        v5e, monkeypatch, family):
    """ZeRO stage 3 states its gathers (``parallel/qwz.WeightGather``): the
    program holds one all-gather a layer weight and whole matmuls. Left to
    the partitioner (ledger PR 31, ``gpt2-xl.train-zero3-x4``) a projection
    was a ring of four K = 400 partial products and ``h @ w_in`` four
    1600-column pieces, each written into the ``[4,1024,6400]`` result by a
    ``dynamic-update-slice``: 8.7% of the step."""
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")  # no interpret
    mod, cfg, names = _stage3_family(family)
    text, plan, stacked = _stage3_loss_and_grad(v5e, mod, cfg)
    d = cfg.hidden_size
    f = cfg.ffn if family == "gpt2-xl" else cfg.intermediate_size
    loops = _loop_lines(text)
    assert loops
    # no FFN-wide activation is assembled from ring pieces
    lines = text.splitlines()
    assert (4, 1024, f) not in [s for _, s in _results(
        lines, "dynamic-update-slice")]
    # nothing the size of a quarter of a layer's smallest matrix, weight or
    # gradient, travels a ring inside the layer loops
    ring = [s for _, s in _results(loops, "collective-permute-start")
            if len(s) >= 2 and s[-2] * s[-1] >= d * d // 4]
    assert ring == [], ring
    # each kind of layer matrix is gathered whole, in the compute dtype
    gathered = {s[-2:] for dt, s in _results(lines, "all-gather")
                if dt == "bf16" and len(s) >= 2}
    for name in names:
        assert plan.param_specs["layers"][name] != jax.sharding.PartitionSpec()
        assert stacked["layers"][name].shape[1:] in gathered, (name, gathered)
    if family == "gpt2-xl":     # the tied table: once, for lookup and head
        assert [s for _, s in _results(lines, "all-gather")].count(
            (cfg.vocab_size, d)) == 1


def test_stage3_left_to_the_partitioner_is_what_the_ledger_showed(
        v5e, monkeypatch):
    """The same program with the hook removed: the windowed form the issue
    started from, so the test above cannot pass for a reason of its own."""
    import dataclasses

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    mod, cfg, _ = _stage3_family("gpt2-xl")
    text, _, _ = _stage3_loss_and_grad(
        v5e, mod, dataclasses.replace(cfg, vocab_size=512), stated=False)
    updates = [s for _, s in _results(text.splitlines(),
                                      "dynamic-update-slice")]
    assert (4, 1024, 6400) in updates
    pieces = [s for _, s in _results(_loop_lines(text),
                                     "collective-permute-start")]
    assert (400, 1600) in pieces and (1600, 1600) in pieces


@pytest.mark.parametrize("table", [DSA_TABLE, 8 * DSA_TABLE],
                         ids=["table_8k_walks", "table_64k_gathers"])
def test_sparse_step_reads_the_kept_rows_only(v5e, monkeypatch, table):
    """The paged contract with TWO block leaves and a selection
    (``deepseek_v32`` at DeepSeek-V3.2-Exp's attention widths, a small FFN and
    vocabulary): a mixed step (16 decode rows beside a tile) with a donated
    pool scatters each layer's latent rows and index keys in place. At the
    longctx-pool cell's table of 8,192 tokens the decode rows WALK their own
    blocks under the selection (``mla_decode`` with a ninth operand, named
    ``dsa_attn_decode``) and nothing is gathered; past
    ``WALK_MAX_TABLE_TOKENS`` their only read of the latent pool is a gather
    of 2,048 rows a row (``bf16[16,2048,640]``). Either way no array of rows
    x the table's positions x 640 lanes exists; the index scores are ``[T,
    S]`` float32 summed over the heads (no such array a head); every kernel
    goes by its name."""
    import re

    from deepspeed_tpu.models import deepseek_v32 as v32

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")  # no interpret
    cfg = v32.DeepseekV32Config(
        vocab_size=512, hidden_size=2048, intermediate_size=256,
        moe_intermediate_size=128, num_layers=3, num_heads=DSA_HEADS,
        q_lora_rank=1536, num_experts=8, experts_held=4, top_k=2, n_group=4,
        topk_group=2, rope_scaling={
            "type": "yarn", "factor": 40, "beta_fast": 32, "beta_slow": 1,
            "mscale": 1, "mscale_all_dim": 1,
            "original_max_position_embeddings": 4096})
    blocks, rows, tiles = 1025, DSA_ROWS, 1
    on_chip, i32, params, cache = _abstract_step(v5e, v32, cfg, blocks, MLA_BLOCK)
    assert cache["kv"].shape == (3, blocks, MLA_BLOCK, MLA_WIDTH)
    assert cache["idx"].shape == (3, blocks, MLA_BLOCK, DSA_INDEX_DIM)

    def step(params, cache, tokens, slots, positions, tables, ts, tp, tv):
        return v32.ragged_forward(
            cfg, params, tokens, slots, positions, tables, cache,
            prefill_tiles=(rows, ts, tp, tv, TILE))

    t = rows + tiles * TILE
    assert v32.decode_form(table * MLA_BLOCK) == (
        "walk" if table == DSA_TABLE else "gather")
    text = jax.jit(step, donate_argnums=(1,)).lower(
        on_chip(params), on_chip(cache), i32(t), i32(t), i32(t),
        i32(rows + 1, table), i32(1), i32(1), i32(1)).compile().as_text()
    arrays = _materialized(text)
    idx_slice = blocks * MLA_BLOCK * DSA_INDEX_DIM * 2
    # both leaves, the dense layer's and the scan body's
    assert len([ln for size, op, ln in arrays
                if op == "scatter" and size >= idx_slice]) == 4
    assert [ln for size, op, ln in arrays if size >= 5 * idx_slice  # "kv"'s
            and op not in _IN_PLACE] == []
    shapes = {shape for _, _, ln in arrays for shape in re.findall(
        r"(\w+\[[\d,]*\])", ln.split(" = ", 1)[1].split("(%")[0])}
    width = table * MLA_BLOCK
    gathered = {f"bf16[{rows},{DSA_KEEP},{MLA_WIDTH}]",            # the gather
                f"bf16[{rows * DSA_KEEP},{MLA_WIDTH}]"} & shapes
    assert bool(gathered) == (table != DSA_TABLE)
    wide = [s for s in shapes
            if s.endswith(f",{width},{MLA_WIDTH}]")                 # whole tables
            or re.search(rf"\[\d+,({DSA_HEADS}|{DSA_INDEX_HEADS}),{width}\]",
                         s)]                                        # [T, H, S]
    assert wide == []
    names = [ln.split(" = ")[0] for ln in text.splitlines()
             if " custom-call(" in ln and "tpu_custom_call" in ln]
    for kernel, calls in (("dsa_index", 4), ("dsa_attn_decode", 2),
                          ("dsa_attn_prefill", 2)):
        assert sum(kernel in n for n in names) == calls, (kernel, names)


# ------------------------------------------- two block layers a model layer
# longcat-flash-omni-d4-ep32.json as it is served: every published width, 4
# double layers, 16 held experts, 1/8 vocabulary, the reason-pool cell's pool
LONGCAT_HEADS, LONGCAT_BLOCKS, LONGCAT_TABLE = 64, 2049, 32


@pytest.mark.parametrize("rows,tiles", [(128, 3), (128, 0), (0, 4)],
                         ids=["mixed", "decode", "prefill"])
def test_double_layer_step_scatters_two_rows_a_layer(v5e, monkeypatch, rows,
                                                     tiles):
    """The paged contract where a model layer owns TWO block layers
    (``longcat_flash`` at the benchmark cell's own sizes): the scan's body
    scatters the step's rows twice, once a sublayer (4 layers: 8 row
    scatters a step), ``mla_decode`` and ``mla_prefill`` at 64 heads compile
    and go by the names ``benchmark/kernels/*.json`` find, once a sublayer
    each, the 256-row-and-up steps' expert branch is ONE ``moe_gmm`` call, the
    counts the step hands back are ``[3, T]``, nothing else in the program is
    as large as one block layer's slice of the pool, and its temporaries fit
    beside 10.35 GB of weights and the 2.69 GB pool."""
    import json
    import os
    import re

    from deepspeed_tpu.models import longcat_flash as lc

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")  # no interpret
    cfg = lc.LongcatFlashConfig(vocab_size=16384, num_layers=4,
                                experts_held=16)
    assert lc.num_params(cfg) == 5_172_749_312
    on_chip, i32, params, cache = _abstract_step(v5e, lc, cfg, LONGCAT_BLOCKS,
                                                 MLA_BLOCK)
    assert cache["kv"].shape == (8, LONGCAT_BLOCKS, MLA_BLOCK, MLA_WIDTH)
    layer_slice = LONGCAT_BLOCKS * MLA_BLOCK * MLA_WIDTH * 2

    def step(params, cache, tokens, slots, positions, tables, ts, tp, tv):
        return lc.ragged_forward(
            cfg, params, tokens, slots, positions, tables, cache,
            prefill_tiles=(rows, ts, tp, tv, TILE) if tiles else None,
            row_counts=True)

    t, n = rows + tiles * TILE, max(tiles, 1)
    compiled = jax.jit(step, donate_argnums=(1,)).lower(
        on_chip(params), on_chip(cache), i32(t), i32(t), i32(t),
        i32(129, LONGCAT_TABLE), i32(n), i32(n), i32(n)).compile()
    text = compiled.as_text()
    big = [(size, op, ln) for size, op, ln in _materialized(text)
           if size >= layer_slice]
    # the compiler may write a sublayer's scatter out twice (``.remat``: the
    # same rows into the same donated buffer again, in place)
    scatters = {ln.split(" = ")[0].removesuffix(".remat")
                for _, op, ln in big if op == "scatter"}
    assert len(scatters) == (2 if rows else 0), scatters
    # ... in the body of the scan over the 4 layers: 8 row scatters a step
    cond = [body for body in _computations(text).values()
            if any('op_name="jit(step)/while/cond/lt"' in ln for ln in body)]
    assert len(cond) == 1 and any(" constant(4)" in ln for ln in cond[0])
    assert all("/while/body/" in ln for _, op, ln in big if op == "scatter")
    assert [ln for _, op, ln in big if op not in _IN_PLACE] == []
    kernels = os.path.join(os.path.dirname(__file__), os.pardir, os.pardir,
                           "benchmark", "kernels")
    calls = [ln.strip().removeprefix("ROOT ") for ln in text.splitlines()
             if " custom-call(" in ln and "tpu_custom_call" in ln]
    found = {}
    for name in ("mla_decode", "mla_prefill", "moe_gmm"):
        with open(os.path.join(kernels, name + ".json")) as f:
            rx = re.compile(json.load(f)["trace_pattern"])
        found[name] = sum(bool(rx.search(ln)) for ln in calls)
    assert found == {"mla_decode": 2 if rows else 0,
                     "mla_prefill": 2 if tiles else 0,
                     "moe_gmm": 1 if t >= 256 else 0}
    assert len(calls) == sum(found.values())
    pool_shape = f"bf16[{8 * LONGCAT_BLOCKS},{MLA_BLOCK},{MLA_WIDTH}]"
    for ln in calls:
        if "moe_gmm" not in ln.split(" = ")[0]:
            assert ln.split("operand_layout_constraints=")[1].count(
                pool_shape) == 1, ln
    assert f"s32[3,{t}]" in text
    assert compiled.memory_analysis().temp_size_in_bytes < 2**30


# ------------------------------------------ sliding-window layers, two pools
# SmallThinker-21BA3B's attention at its cell's shapes: 28 query heads over 4
# KV heads of 128, a window of 4,096 over 128-token blocks, a table of 64, 16
# decode rows beside 128-row tiles; pools of 641 and 16 x 33 + 1 blocks
SWA_HEADS, SWA_KV, SWA_D, SWA_WINDOW, SWA_TABLE = 28, 4, 128, 4096, 64
SWA_POOLS = (641, 529)


@pytest.mark.parametrize("kernel", ["swa_decode", "swa_prefill"])
def test_window_kernels_compile_at_the_cells_shapes(v5e, kernel):
    """The two paged kernels with ``window``: 28 heads (no multiple of 8) in
    the ``[Hq, Hkv*D]`` query form, a whole 128-row tile in scoped VMEM, a
    prefill grid of 33 blocks, not the table's 64; the instruction goes by
    the window layers' name, which ``benchmark/kernels/<name>.json`` finds
    and the full layers' pattern does not."""
    import json
    import os

    dev = jax.sharding.SingleDeviceSharding(v5e[0])

    def s(shape, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=dev)

    pool = s((SWA_POOLS[1], 128, SWA_KV * SWA_D))
    bt = s((17, SWA_TABLE), jnp.int32)
    if kernel == "swa_decode":
        rows = s((16,), jnp.int32)
        fn = lambda *a: paged_decode_attention(  # noqa: E731
            *a, interpret=False, window=SWA_WINDOW)
        args = (s((16, SWA_HEADS, SWA_D)), pool, pool, rows, rows, bt)
    else:
        tiles = s((4,), jnp.int32)
        fn = lambda *a: ragged_prefill_attention(  # noqa: E731
            *a, TILE, interpret=False, window=SWA_WINDOW)
        args = (s((4 * TILE, SWA_HEADS, SWA_D)), pool, pool, tiles, tiles,
                tiles, bt)
    compiled = jax.jit(fn).lower(*args).compile()
    calls = [ln.strip() for ln in compiled.as_text().splitlines()
             if " custom-call(" in ln and "tpu_custom_call" in ln]
    assert len(calls) == 1
    kernels = os.path.join(os.path.dirname(__file__), os.pardir, os.pardir,
                           "benchmark", "kernels")
    found = []
    for f in os.listdir(kernels):
        with open(os.path.join(kernels, f)) as fh:
            if re.search(json.load(fh)["trace_pattern"], calls[0]):
                found.append(f[:-5])
    assert sorted(found) == sorted([kernel, "pallas_custom_call"])
    assert compiled.memory_analysis().temp_size_in_bytes < 2**23


def test_window_step_holds_no_layer_slice_of_either_pool(v5e, monkeypatch):
    """The paged contract with sliding leaves: a step of 16 decode rows beside
    a tile writes each pool's rows by in-place scatters (K and V, twice) and
    reads both through their tables; nothing else is as large as a layer's
    slice of EITHER pool (67 MB: at this hidden size of 256 every weight of
    the four-body period, ``models/smallthinker.py``, passes under that;
    that no layer's weights are copied out of their stack is
    ``test_step_program_relays_out_no_projection_weight``'s to hold, at the
    cell's 2,560), and all four attention kernels are in it under their
    names."""
    from deepspeed_tpu.models import smallthinker

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")  # no interpret
    cfg = smallthinker.SmallThinkerConfig(
        vocab_size=512, hidden_size=256, moe_intermediate_size=128,
        num_layers=8, num_heads=SWA_HEADS, num_kv_heads=SWA_KV,
        head_dim=SWA_D, num_experts=8, top_k=2, sliding_window=SWA_WINDOW,
        max_seq_len=8192)
    dev = jax.sharding.SingleDeviceSharding(v5e[0])

    def on_chip(tree):
        return jax.tree_util.tree_map(
            lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=dev),
            tree)

    def i32(*shape):
        return jax.ShapeDtypeStruct(shape, jnp.int32, sharding=dev)

    params = jax.eval_shape(lambda: jax.tree_util.tree_map(
        lambda a: a.astype(jnp.bfloat16),
        smallthinker.init_params(cfg, jax.random.PRNGKey(0))))
    cache = jax.eval_shape(lambda: smallthinker.init_paged_cache(
        cfg, SWA_POOLS[0], 128, jnp.bfloat16, num_slots=17))
    assert cache["k"].shape == (2, SWA_POOLS[0], 128, 512)
    assert cache["swa"]["k"].shape == (6, SWA_POOLS[1], 128, 512)
    layer_slice = min(SWA_POOLS) * 128 * 512 * 2
    rows, t = 16, 16 + TILE

    def step(params, cache, tokens, slots, positions, bt, bt_win, ts, tp, tv):
        return smallthinker.ragged_forward(
            cfg, params, tokens, slots, positions, (bt, bt_win), cache,
            prefill_tiles=(rows, ts, tp, tv, TILE))

    compiled = jax.jit(step, donate_argnums=(1,)).lower(
        on_chip(params), on_chip(cache), i32(t), i32(t), i32(t),
        i32(17, SWA_TABLE), i32(17, SWA_TABLE), i32(1), i32(1), i32(1)).compile()
    text = compiled.as_text()
    big = [(size, op, ln) for size, op, ln in _materialized(text)
           if size >= layer_slice]
    # K and V of either pool; twice where the compiler unrolls the two repeats
    assert len([ln for _, op, ln in big if op == "scatter"]) in (4, 8)
    assert [ln for _, op, ln in big if op not in _IN_PLACE] == []
    names = set(re.findall(r"%(\w+?)\.\d+ = [^\n]*tpu_custom_call", text))
    assert {"paged_decode", "swa_decode", "tiled_prefill",
            "swa_prefill"} <= names
    assert compiled.memory_analysis().temp_size_in_bytes < layer_slice


# ------------------------------------- a model that generates by blocks
# (PR 47) the SDAR cell's attention: 32 query heads over 4 KV heads of 128,
# blocks of 4 rows, 96 decoding sequences beside a 128-row tile, a table of 16
# 128-token blocks, a pool of 1,537
BLK_HEADS, BLK_KV, BLK_D, BLK_LEN, BLK_TABLE, BLK_POOL = 32, 4, 128, 4, 16, 1537


@pytest.mark.parametrize("kernel", ["blk_decode", "blk_prefill"])
def test_block_kernels_compile_at_the_cells_shapes(v5e, kernel):
    """The two paged kernels with ``block``: a decoding block's 4 x 32 = 128
    query heads laid out by KV head, ``[96, 4, 32, 128]`` (a KV head's 32
    queries against that head's 128 lanes of a chunk; no ``[96, 128, 512]``
    query of three quarters zeros is made), a whole 128-row tile under the
    block-causal mask; the instruction goes by the blocks' name, which
    ``benchmark/kernels/<name>.json`` finds and the causal kernels' patterns
    do not."""
    import json
    import os

    dev = jax.sharding.SingleDeviceSharding(v5e[0])

    def s(shape, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=dev)

    pool = s((BLK_POOL, 128, BLK_KV * BLK_D))
    bt = s((97, BLK_TABLE), jnp.int32)
    if kernel == "blk_decode":
        rows = s((96,), jnp.int32)
        fn = lambda *a: paged_decode_attention(  # noqa: E731
            *a, interpret=False, block=BLK_LEN)
        args = (s((96, BLK_LEN, BLK_HEADS, BLK_D)), pool, pool, rows, rows, bt)
    else:
        tiles = s((1,), jnp.int32)
        fn = lambda *a: ragged_prefill_attention(  # noqa: E731
            *a, TILE, interpret=False, block=BLK_LEN)
        args = (s((TILE, BLK_HEADS, BLK_D)), pool, pool, tiles, tiles, tiles,
                bt)
    compiled = jax.jit(fn).lower(*args).compile()
    calls = [ln.strip() for ln in compiled.as_text().splitlines()
             if " custom-call(" in ln and "tpu_custom_call" in ln]
    assert len(calls) == 1
    kernels = os.path.join(os.path.dirname(__file__), os.pardir, os.pardir,
                           "benchmark", "kernels")
    found = []
    for f in os.listdir(kernels):
        with open(os.path.join(kernels, f)) as fh:
            if re.search(json.load(fh)["trace_pattern"], calls[0]):
                found.append(f[:-5])
    assert sorted(found) == sorted([kernel, "pallas_custom_call"])
    assert compiled.memory_analysis().temp_size_in_bytes < 2**24
    if kernel == "blk_decode":
        # the split is there: q goes in, and the output comes back, by KV head
        assert calls[0].count("bf16[96,4,32,128]") >= 2
        assert "bf16[96,128,512]" not in compiled.as_text()


def test_block_step_holds_no_layer_slice_of_the_pool(v5e, monkeypatch):
    """The paged contract under blocks of rows: a step of 8 decoding blocks
    (32 rows) beside a tile writes the pool's rows by two in-place scatters
    and reads it through the table; nothing else is as large as a layer's
    slice, the q/k norm and the 128-head query form included, and both
    block kernels are in it under their names (no causal one is)."""
    from deepspeed_tpu.models import sdar

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")  # no interpret
    cfg = sdar.SdarConfig(
        vocab_size=512, hidden_size=256, moe_intermediate_size=128,
        num_layers=3, num_heads=BLK_HEADS, num_kv_heads=BLK_KV,
        head_dim=BLK_D, num_experts=8, top_k=2, max_seq_len=2048,
        mask_token_id=511)
    on_chip, i32, params, cache = _abstract_step(v5e, sdar, cfg, BLK_POOL, 128)
    assert cache["k"].shape == (3, BLK_POOL, 128, 512)
    layer_slice = BLK_POOL * 128 * 512 * 2
    rows, t = 8 * BLK_LEN, 8 * BLK_LEN + TILE

    def step(params, cache, tokens, slots, positions, tables, ts, tp, tv):
        return sdar.ragged_forward(
            cfg, params, tokens, slots, positions, tables, cache,
            prefill_tiles=(rows, ts, tp, tv, TILE))

    compiled = jax.jit(step, donate_argnums=(1,)).lower(
        on_chip(params), on_chip(cache), i32(t), i32(t), i32(t),
        i32(97, BLK_TABLE), i32(1), i32(1), i32(1)).compile()
    text = compiled.as_text()
    big = [(size, op, ln) for size, op, ln in _materialized(text)
           if size >= layer_slice]
    assert len([ln for _, op, ln in big if op == "scatter"]) == 2
    assert [ln for _, op, ln in big if op not in _IN_PLACE] == []
    names = set(re.findall(r"%(\w+?)\.\d+ = [^\n]*tpu_custom_call", text))
    assert {"blk_decode", "blk_prefill"} <= names
    assert not {"paged_decode", "tiled_prefill"} & names
    assert compiled.memory_analysis().temp_size_in_bytes < layer_slice


if __name__ == "__main__":
    import json
    import sys

    with open(sys.argv[1], "w") as f:
        json.dump(single_query_decode_digests(), f, indent=1)
