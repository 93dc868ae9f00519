"""Ragged / continuous-batching inference engine (FastGen v2 analog).

Role parity with the reference second inference engine:
``inference/v2/engine_v2.py:30 InferenceEngineV2`` (``put()`` scheduling),
``inference/v2/ragged/ragged_manager.py:19 DSStateManager`` (per-sequence
state + host descriptors), ``inference/v2/ragged/blocked_allocator.py``
(KV block free list), and the SplitFuse token-budget policy from the FastGen
blog (``blogs/deepspeed-fastgen``): every engine step processes a fixed
budget of tokens that freely mixes ongoing decodes (1 token/seq, scheduled
first for latency) with prompt-prefill *chunks*, so long prompts never stall
running generations and short ones never wait for a batch to drain.

TPU-native shape: instead of the reference's ragged CUDA kernel set
(``inference/v2/kernels/ragged_ops``), the whole mixed step is ONE
static-shape jitted XLA program over a flat ``[T]`` token batch — each token
carries (slot, position), new KV is scattered into a paged block pool before
attention, and each token attends over its sequence's gathered blocks under a
position mask. Static shapes mean exactly one compile, ever, per engine; the
scheduler pads the tail of the token batch onto a scratch block (block 0).

The paged-attention gather is pure XLA (correct everywhere, including the
CPU test mesh); a Pallas flash-decode kernel over the same block pool is the
drop-in optimization point.
"""

from __future__ import annotations

import pickle
import random
import time
import weakref
from dataclasses import dataclass, field, fields
from functools import partial
from typing import Any, Callable

import jax
import jax.numpy as jnp
import numpy as np

from deepspeed_tpu.models.api import ModelSpec, ShardCtx
from deepspeed_tpu.models.paged import (
    block_leaves,
    full_leaves,
    slot_leaves,
    sliding_blocks_per_seq,
    sliding_leaves,
    tiles_go_as_slices,
)
from deepspeed_tpu.ops import kvquant
from deepspeed_tpu.ops.attention import prefill_step_keys
from deepspeed_tpu.telemetry import get_telemetry
from deepspeed_tpu.telemetry.memledger import is_resource_exhausted, record_oom
from deepspeed_tpu.telemetry.tracing import format_traceparent
from deepspeed_tpu.utils.faults import (
    POINT_ALLOC,
    POINT_DISPATCH,
    POINT_H2D,
    POINT_READBACK,
    classify_transient,
    get_fault_injector,
)
from deepspeed_tpu.utils.logging import log_dist
from deepspeed_tpu.utils.tracing import phase, span

def _kept_pairs(pos0: int, take: int, topk: int, rule=None) -> int:
    """The query x kept-row pairs of a chunk of ``take`` queries from
    ``pos0``: ``sum(min(p + 1, topk) for p in range(pos0, pos0 + take))``, or
    with a family's ``rule`` (``ModelSpec.index_blocks``) the sum of what the
    rule says a query keeps."""
    if rule is not None:
        return int(rule.kept(np.arange(pos0, pos0 + take), topk).sum())
    full = max(0, min(take, topk - pos0))     # queries that keep every row
    return (full * pos0 + full * (full + 1) // 2) + (take - full) * topk


def _kept_keys(pos: int, topk: int, rule=None) -> int:
    """The keys the query at ``pos`` keeps: ``_kept_pairs`` of that one."""
    return _kept_pairs(pos, 1, topk, rule)


# ---- the tables a step indexes, laid out for their row gather ----
# A v5e keeps an array whose rows are no whole number of 128-lane tiles
# column-major (GPT-2 XL's ``wte`` / ``wpe`` at 1,600 lanes: 12.5 tiles; the
# padding is smaller that way). A product reads that in place; a GATHER of
# rows wants them contiguous, so every step program copied the whole table
# first (161 MB, 0.48 ms of a 6.34 ms step; PERF.md section 6, PRs 50 and 52).
# The engine therefore holds such a table row-major from the start. What
# decides is the array's own layout and nothing else.
def _lies_row_major(a) -> bool:
    """Whether ``a`` (an array, or a ``ShapeDtypeStruct`` that says) lies
    with its last axis minor. An array that says nothing (a host array, a
    backend without layouts) has no other layout to be in."""
    layout = getattr(getattr(a, "format", None), "layout", None)
    order = getattr(layout, "major_to_minor", None)
    return order is None or tuple(order) == tuple(range(len(a.shape)))


def row_gather_tables(params, names) -> list[tuple]:
    """``(path, leaf)`` of the tables among ``params`` that a row gather
    would copy: a leaf with one of ``names`` on its path (``ModelSpec.
    woq_skip``: "tables the model indexes rather than matmuls", matched as
    ``ops.quantizer.quantize_params`` matches them) that is not row-major.
    Empty wherever the tables' rows are whole tiles, and off the chip."""
    from deepspeed_tpu.ops.quantizer import path_names

    names = set(names)
    return [(path, leaf)
            for path, leaf in jax.tree_util.tree_flatten_with_path(params)[0]
            if names & path_names(path)
            and len(getattr(leaf, "shape", ())) >= 2
            and not _lies_row_major(leaf)]


def row_major_program(tables: tuple):
    """THE program that re-lays ``tables`` (arrays, or abstract values with
    their formats) row-major: one jitted function for all of them, under one
    name (``jit_ragged_tables_row_major``), so that a process builds one
    program more for them, however many they are. It compiles in a tenth of
    a second on the chip, under the second from which jax's persistent cache
    keeps an entry: every process builds it, and that is its whole price."""
    from jax.experimental.layout import Format, Layout

    def ragged_tables_row_major(tables):
        return tables

    return jax.jit(ragged_tables_row_major, out_shardings=tuple(
        Format(Layout(major_to_minor=tuple(range(len(t.shape)))), t.sharding)
        for t in tables))


def lay_out_for_row_gather(params, names) -> tuple[Any, tuple]:
    """``params`` with every table of ``row_gather_tables`` row-major, and
    those tables as they are now. The same tree of the same values; with
    nothing to do nothing is built and ``params`` comes back as it is. A
    re-laid table's old array is DELETED, so that no table is held twice:
    whoever handed the engine its parameters reads them from
    ``engine.params`` afterwards. (Deleted and not donated: a donation the
    compiler cannot alias, and it cannot where the layouts differ, left the
    buffer with its owner on the chip, 164 MB for as long as the caller kept
    its tree: PERF.md section 6, PR 52.)"""
    found = row_gather_tables(params, names)
    if not found:
        return params, ()
    tables = tuple(leaf for _, leaf in found)
    relaid = jax.block_until_ready(row_major_program(tables)(tables))
    for table in tables:
        table.delete()
    by_path = {path: new for (path, _), new in zip(found, relaid)}
    return jax.tree_util.tree_map_with_path(
        lambda path, leaf: by_path.get(path, leaf), params), relaid


def abstract_like(tree):
    """``tree``'s arrays as the abstract values from which a lowering builds
    the very program a dispatch of the arrays themselves looks up: shape,
    dtype and, for a COMMITTED array, its format (the layout, and the
    sharding beside it). Without the layout a re-laid table's program is
    lowered for the default one, and without the sharding a committed
    argument's annotation is missing from the program's text: either way the
    executable is one no dispatch ever asks for. An uncommitted array says
    neither to a dispatch, so it says neither here."""
    def one(x):
        fmt = x.format if getattr(x, "committed", False) else None
        return jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=fmt)

    return jax.tree_util.tree_map(one, tree)


# why a model with slot state cannot be resumed from blocks alone
_NO_SNAPSHOT = ("a sequence's recurrent state is not in its blocks, and there "
                "is no snapshot of it at a block boundary to restore beside "
                "them")
# why a model with sliding leaves cannot be resumed from a prefix of blocks
_NO_PREFIX = ("a sequence's sliding blocks are returned as its window slides "
              "past them, so a prefix of its blocks no longer holds the "
              "window layers' rows")
# a model that keeps part of a sequence outside its chain of blocks: (what it
# keeps, its leaves beside a pool, why a chain of blocks does not restore it)
_BESIDE_BLOCKS = {
    "slot": ("recurrent state in slot leaves", "slot state", _NO_SNAPSHOT),
    "sliding": ("its window layers' K and V in sliding leaves",
                "sliding leaves", _NO_PREFIX),
}


# why a model that generates by blocks cannot take what follows (each names
# the piece that is missing)
_BY_BLOCKS = {
    "enable_prefix_cache": "a published prefix would have to end on a "
    "committed block and be spliced under the block-causal mask; no test "
    "shows that right yet",
    "kv_tier": "the tiers hold demoted prefix blocks, and the prefix cache "
    "is refused",
    "KVHandoff": "a hand-off would have to carry the block a slot is "
    "denoising (its tokens and what of it is masked) beside the pool's "
    "blocks, and the record has no field for it",
    "quant": "a denoise pass rewrites its block's rows every pass, and a "
    "quantized pool under that is not tested",
    "temperature > 0": "the block step picks by argmax on the device; "
    "sampling a block's positions from per-position keys is not implemented",
    "device_state=False": "the host-staged step does not run blocks: the "
    "block a slot denoises lives in the device's slot rows",
    "prefill_tile": "a prompt's whole blocks are cached by tiles under the "
    "block-causal mask, and a tile has to be whole blocks",
    "block_size": "a pool block has to be whole diffusion blocks, so that no "
    "block of rows straddles two of them",
}


class BlockedAllocator:
    """Ref-counted free-list allocator over the KV block pool
    (reference ``inference/v2/ragged/blocked_allocator.py``, grown the
    SGLang/vLLM prefix-cache direction: blocks carry refcounts so several
    sequences can share one prefix block, and retired prompt blocks can be
    *published* into a hash-chained prefix index instead of freed).

    Block 0 is reserved as the scratch block that padding tokens write into;
    it is never handed out. Published blocks with refcount 0 sit in an LRU
    and are evicted on demand when ``allocate`` finds the free list dry —
    the prefix cache is strictly free-memory-funded: ``free_blocks`` counts
    evictable cached blocks as allocatable, so admission reservations see
    the same capacity they would without caching and can never deadlock on
    retained blocks.

    Prefix keys are exact hash-chains: ``key = (parent_key, block_tokens)``
    per full block (structural sharing keeps them cheap); exact tuples
    rather than digests so a hash collision can never splice wrong KV.
    """

    def __init__(self, num_blocks: int):
        if num_blocks < 2:
            raise ValueError("need at least 2 blocks (one is the scratch block)")
        self._free = list(range(num_blocks - 1, 0, -1))  # pop() -> lowest first
        self.num_blocks = num_blocks
        self._refs = [0] * num_blocks
        # prefix cache state (inert until publish() is first called)
        self._index: dict = {}   # chain key -> block id
        self._keys: dict[int, Any] = {}  # block id -> its chain key
        self._lru: dict[int, None] = {}  # refcount-0 published blocks, LRU->MRU
        self.evictions = 0  # cumulative cached blocks reclaimed under pressure
        self.allocated_total = 0  # cumulative blocks handed out (all paths)
        # optional publish/evict listener (serving cluster prefix index):
        # an object with on_publish(key) / on_evict(key), called on the
        # engine thread as keys enter/leave the index. None = standalone.
        self.listener = None
        # optional tiering hook: demote_hook(block, key) -> bool is called
        # as an LRU eviction reclaims a published block, WHILE the payload
        # is still intact — True means the block was captured into a lower
        # tier (inference/kvtier.py) rather than dropped. None = untiered
        # (the eviction path is bit-identical to the pre-tiering engine).
        self.demote_hook = None

    @property
    def free_blocks(self) -> int:
        """Allocatable blocks: truly free + evictable (refcount-0 cached)."""
        return len(self._free) + len(self._lru)

    @property
    def busy_blocks(self) -> int:
        """Blocks holding live or retained KV right now: everything except
        the scratch block and the truly-free list. The cost meter's pool
        occupancy integral sums this over time (retained cached blocks ARE
        occupancy — they are the prefix cache's rent)."""
        return self.num_blocks - 1 - len(self._free)

    @property
    def cached_blocks(self) -> int:
        """Blocks currently published in the prefix index (any refcount)."""
        return len(self._keys)

    @property
    def retained_blocks(self) -> int:
        """Refcount-0 cached blocks held back from the free list (the
        memory the prefix cache is actually occupying right now)."""
        return len(self._lru)

    def allocate(self, n: int) -> list[int]:
        if n > self.free_blocks:
            raise RuntimeError(
                f"KV pool exhausted: need {n} blocks, {self.free_blocks} free"
            )
        while len(self._free) < n:
            self._evict_lru()
        out = [self._free.pop() for _ in range(n)]
        for b in out:
            self._refs[b] = 1
        self.allocated_total += n
        return out

    def _evict_lru(self) -> None:
        b = next(iter(self._lru))  # oldest entry (LRU order)
        del self._lru[b]
        key = self._keys.pop(b)
        del self._index[key]
        demoted = False
        if self.demote_hook is not None:
            # tiering: capture the payload device->host NOW — once the id
            # is back on the free list the next allocation may rewrite it
            try:
                demoted = bool(self.demote_hook(b, key))
            except Exception:  # noqa: BLE001 - demotion is best-effort
                demoted = False
        self.evictions += 1
        if self.listener is not None:
            # notify BEFORE the id returns to the free list: a cluster-index
            # entry must never promise a block its replica could already be
            # rewriting. A captured block demotes (the key stays servable
            # from a lower tier); an uncaptured one is a plain eviction.
            on_demote = getattr(self.listener, "on_demote", None)
            if demoted and on_demote is not None:
                on_demote(key)
            else:
                self.listener.on_evict(key)
        self._free.append(b)

    def shrink_retained(self, budget: int) -> int:
        """Evict LRU cached blocks until at most ``budget`` refcount-0
        blocks stay retained (headroom-driven cache budget: when measured
        free-byte headroom is scarce, retention shrinks before admission
        starves). Returns how many blocks were evicted; a budget at or
        above the current retention is a no-op — the ample-headroom case
        stays bit-identical to the unbudgeted LRU."""
        n = 0
        while len(self._lru) > max(0, budget):
            self._evict_lru()
            n += 1
        return n

    def free(self, blocks: list[int]) -> None:
        """Drop one reference per block; a block reaching refcount 0 returns
        to the free list, or to the evictable LRU if it is published."""
        for b in blocks:
            if b == 0 or b >= self.num_blocks:
                raise ValueError(f"bad block id {b}")
            if self._refs[b] <= 0:
                raise ValueError(f"double free of block {b}")
            self._refs[b] -= 1
            if self._refs[b] == 0:
                if b in self._keys:
                    self._lru[b] = None  # dict preserves insertion = MRU last
                else:
                    self._free.append(b)

    # ------------------------------------------------------- prefix cache
    def lookup(self, key) -> int | None:
        """Block id published under ``key``, or None. Read-only (no LRU
        touch) so the serving router can probe concurrently."""
        return self._index.get(key)

    def acquire(self, blocks: list[int]) -> None:
        """Take a reference on cached blocks (a prefix hit splicing them
        into a sequence's block table). A refcount-0 block leaves the
        evictable LRU."""
        for b in blocks:
            if self._refs[b] == 0:
                del self._lru[b]
            self._refs[b] += 1

    def publish(self, block: int, key) -> bool:
        """Register ``block``'s content under its chain key (called at
        sequence release, BEFORE ``free``). Returns False when the key is
        already cached (dedupe: the existing block stays authoritative)."""
        if key in self._index:
            return False
        self._index[key] = block
        self._keys[block] = key
        if self.listener is not None:
            self.listener.on_publish(key)
        return True


@dataclass
class RaggedConfig:
    """Engine sizing. ``max_tokens_per_step`` is the SplitFuse token budget."""

    max_tokens_per_step: int = 256
    max_seqs: int = 8
    block_size: int = 16
    num_blocks: int = 257  # 256 usable + scratch
    max_blocks_per_seq: int = 32
    # tiled prefill: lay prefill chunks at tile-aligned offsets so the tiled
    # Pallas kernel fetches each KV block once per TILE instead of once per
    # token (ops/pallas ragged_prefill_attention — the SplitFuse blocked
    # flash attention). 0 disables (per-token kernel for everything).
    prefill_tile: int = 0
    # device-resident scheduler state (the steady-state decode fix): slot
    # rows (last token / position / seed / prompt length / sampling params)
    # live in persistent device arrays updated in place by donated jitted
    # updaters at admission, and the block table is device-resident with a
    # dirty-row delta upload — so a steady decode step stages NO per-row
    # host arrays (the packed staging buffer byte-compares equal and is
    # reused) and token readback for dispatch t overlaps dispatch t+1.
    # False takes the host-staged step instead (in ``_step_impl``: the same
    # packer, token and position fed from host state, tokens read back at
    # once) — token-identical, the tests' reference and rung 1 of the
    # watchdog's ladder.
    device_state: bool = True
    # ---- dispatch watchdog (docs/FAULT_TOLERANCE.md) ----
    # wall-clock budget for one step(); a step exceeding it counts toward
    # the degradation ladder like a transient failure (the device path is
    # limping even though it completed). 0 disables the deadline check.
    step_deadline_s: float = 0.0
    # transient step failures retried in place (with backoff) before the
    # error escalates out of step(); fatal errors never retry
    dispatch_retries: int = 2
    retry_backoff_s: float = 0.05
    # consecutive device-path failures that trigger automatic degradation:
    # device-resident state -> host-staged step -> the same with prefill
    # tiles off (token-identical rungs). 0 disables degradation.
    degrade_after: int = 3
    # block-level prefix caching (SGLang/vLLM-style): retired sequences
    # publish their full prompt blocks into a hash-chained index; admission
    # splices the longest cached full-block prefix into a new sequence's
    # block table (refcounts bumped) and prefills only the tail. Cached
    # blocks with no referents stay evictable (LRU) so the cache is funded
    # purely by free memory. Off by default: disabled, scheduling behavior
    # is bit-identical to an uncached engine.
    enable_prefix_cache: bool = False
    # headroom-driven admission (telemetry/memledger.py): cap admission by
    # MEASURED free-byte headroom alongside the static block count. The KV
    # pool is preallocated at init, so its free blocks are credited as
    # already-funded bytes — the gate only bites when OTHER owners
    # (checkpoint staging, compile temps, co-located jobs) have eaten the
    # device's guard band beyond what the pool itself could fund. Opt-in:
    # admission from a preallocated pool allocates no new device bytes, so
    # most deployments want the static path; a backend that reports no
    # bytes_limit (the CPU test accelerator) yields "unknown" headroom and
    # the static path verbatim either way.
    headroom_admission: bool = False
    # consecutive zero-progress scheduler ticks spent headroom-pinned before
    # the stall alarm raises (a headroom wait must never be a silent forever
    # hang — external pressure is expected to lift, and when it doesn't the
    # operator needs a loud failure, not an idle loop). 0 disables the alarm.
    headroom_stall_alarm_ticks: int = 1000
    # ---- hierarchical KV-cache tiering (inference/kvtier.py) ----
    # three-tier prefix cache: HBM (tier 0, the pool above) -> bounded
    # host-RAM arena (tier 1) -> disk spill directory (tier 2). LRU eviction
    # becomes *demotion* (the evicted block's payload is gathered to host
    # before the id is reused) and admission *promotes* demoted chain links
    # back through the standard allocate->scatter->publish path when the
    # restore_beats_prefill cost model favors it — token-identical either
    # way. Requires enable_prefix_cache. Off by default: eviction drops
    # payloads exactly as before, bit-identical to the untiered engine.
    kv_tier: bool = False
    # tier-1 budget in KV blocks (must be > 0 when kv_tier is on)
    kv_tier_host_blocks: int = 64
    # tier-2 budget in records; 0 disables the disk tier (host overflow is
    # then dropped, which is exactly the old eviction for those blocks)
    kv_tier_disk_blocks: int = 0
    # spill directory; swept for torn temp files at engine startup
    kv_tier_dir: str = "runs/kvtier"
    # modeled host<->device bandwidth for the promotion cost model. <= 0 =
    # unknown, which conservatively never restores from that tier.
    kv_tier_host_gbps: float = 100.0
    # modeled prefill throughput the restore competes against (the same
    # constant ClusterConfig.prefill_tokens_per_s models for wire transfers)
    kv_tier_prefill_tokens_per_s: float = 50000.0
    # ---- low-bit serving (ops/kvquant.py) ----
    # ONE config surface for the full low-bit path, grammar
    # "off" | "int8" | "fp8" | "woq8" | "woq4" | "qcol" joined with "+"
    # (e.g. "int8+woq8"). The KV codec makes the *block* the unit of
    # quantization everywhere a block lives — HBM pool, host/disk tiers,
    # prefix-cache retained set, KVHandoff wire — quantized at write time,
    # dequant fused into the jitted gather; ~2x resident blocks per HBM
    # byte under a measured drift budget (kvquant.DRIFT_BUDGET). "woqN"
    # is the weight-only path (same as the quantize_bits ctor arg);
    # "qcol" quantizes the TP inference collectives (needs a mesh — the
    # GSPMD-sharded InferenceEngine; inert on this single-host engine).
    # Off by default: the default path is bit-identical to an engine
    # that predates this knob (pinned by test).
    quant: str = "off"

    @property
    def max_seq_len(self) -> int:
        return self.block_size * self.max_blocks_per_seq


# The engine's constants, each with the one method that reads it.
# ``_backoff``: the cap of the doubling sleep between retries, and the
# multiplicative jitter on it (engine-seeded, so a replayed run backs off
# identically)
RETRY_BACKOFF_MAX_S = 2.0
RETRY_JITTER = 0.25
# ``admission_headroom_blocks``: share of bytes_limit held back from the
# measured free bytes (allocator slack + fragmentation)
HEADROOM_GUARD_FRACTION = 0.05
# ``_init``: modeled disk read bandwidth of the tier promotion cost model
KV_TIER_DISK_GBPS = 8.0


@dataclass
class _SeqState:
    """Host descriptor of one request (reference DSStateManager sequence)."""

    uid: Any
    prompt: list[int]
    max_new_tokens: int
    eos_token_id: int | None = None
    slot: int = -1
    pos: int = 0  # tokens whose KV has been scheduled into the cache
    generated: list[int] = field(default_factory=list)
    blocks: list[int] = field(default_factory=list)
    reserved_remaining: int = 0  # worst-case blocks reserved but not yet held
    done: bool = False
    # prompt tokens whose KV came from the prefix cache (block-aligned; the
    # leading cached_prefix // block_size entries of ``blocks`` are SHARED
    # blocks this sequence must never write — pos starts past them)
    cached_prefix: int = 0
    # sampling controls (reference generate kwargs; 0-temperature = greedy)
    temperature: float = 0.0
    top_k: int = 0
    top_p: float = 1.0
    # per-request sampling seed: token g of this request draws from
    # fold_in(fold_in(SAMPLE_ROOT, seed), g) — independent of batch
    # composition and dispatch history, so a sampled generation is
    # reproducible on any engine (cache hit == cold, device == host-staged)
    seed: int = 0
    # dispatches not yet read back that reference this sequence (release is
    # deferred until they drain)
    refs: int = 0
    # request-lifecycle telemetry (perf_counter stamps; 0.0 = not recorded):
    # enqueue -> admit is queue wait, enqueue -> first token is TTFT
    t_enqueue: float = 0.0
    t_admit: float = 0.0
    t_first_token: float = 0.0
    t_last_token: float = 0.0
    # decode steps where this sequence stalled on KV-pool pressure
    preemptions: int = 0
    # abort path (serving tier): absolute perf_counter deadline (0.0 = none)
    # and terminal status — "finished" until cancel()/deadline expiry flips it
    # to "cancelled"/"timeout", which makes ``finished`` true so every
    # dispatch mode's release machinery retires the sequence on the next step
    deadline: float = 0.0
    status: str = "finished"
    # request-trace context (telemetry.tracing.TraceContext). Only ever
    # non-None while the tracer is enabled AND this request was sampled, so
    # ``seq.trace is not None`` is the complete hot-path guard
    trace: Any = None
    # disaggregated serving (serving/cluster.py): a prefill-stage request.
    # The engine runs the prompt plus the FIRST token only, then parks the
    # sequence (KV blocks held, slot freed) until export_handoff() gathers
    # the blocks into a KVHandoff record for a decode replica to import.
    # ``handoff_budget`` carries the request's FULL max_new_tokens through
    # to the record (the prefill stage itself runs with max_new_tokens=1).
    handoff: bool = False
    handoff_budget: int = 0
    # the cached-prefix token count the router credited at placement time
    # (advisory probe); admission re-validates the actual splice against it
    # and counts the shortfall instead of over-crediting (stale-probe fix)
    expected_cached: int = 0
    # cost attribution (telemetry/costmeter.py): billing identity plus the
    # per-request RequestCost record. ``cost`` is only ever non-None while
    # a cost meter is configured, so ``seq.cost is not None`` is the
    # complete hot-path guard at every charging seam.
    tenant: str = "default"
    sla_class: str = "interactive"
    cost: Any = None

    # a model with slot state, after the watchdog's recovery: positions
    # [0, replay) are run again as prefill, from an empty state (the prompt
    # and the generated tokens before the resume point; none of them emits)
    replay: int = 0
    # a model with sliding leaves (``ModelSpec.sliding_window``): the
    # sequence's blocks of the sliding pool by block ordinal (the slide takes
    # the oldest back), the most it may hold between steps, what of that is
    # reserved and not yet held, and what it held of either pool at its end
    win_blocks: dict[int, int] = field(default_factory=dict)
    win_cap: int = 0
    win_reserved: int = 0
    blocks_at_end: tuple[int, int] = (0, 0)
    # a model that generates by blocks (``ModelSpec.block_gen``): the block
    # length (0: a token a step); of the block at ``pos``, the positions
    # still masked when its next pass starts and the positions that were
    # known before it started (the prompt's remainder: not generated, so
    # not handed on); the denoise and commit passes scheduled so far. The
    # host's mirror of what the device does to the slot's block, pass by pass
    blk: int = 0
    blk_masked: int = 0
    blk_known: int = 0
    blk_passes: list[int] = field(default_factory=lambda: [0, 0])

    def token_at(self, p: int) -> int:
        if p < len(self.prompt):
            return self.prompt[p]
        return self.generated[p - len(self.prompt)]

    def tokens_at(self, start: int, end: int) -> list[int]:
        if end <= len(self.prompt):
            return self.prompt[start:end]
        return (self.prompt + self.generated)[start:end]

    @property
    def prefill_end(self) -> int:
        """The first position that runs as a decode row (by blocks: the
        prompt's whole blocks are prefilled, its remainder opens the first
        block)."""
        whole = len(self.prompt) - (len(self.prompt) % self.blk
                                    if self.blk else 0)
        return max(whole, self.replay)

    @property
    def emits_at_prompt_end(self) -> bool:
        """The row of the prompt's last token picks the first generated
        token, unless a replay is passing it: that token is known. (By
        blocks the logits are unshifted: no prompt row picks anything.)"""
        return not self.generated and not self.blk

    @property
    def in_decode(self) -> bool:
        return self.pos >= self.prefill_end

    @property
    def finished(self) -> bool:
        if self.status != "finished":
            return True
        if self.done:
            return True
        if len(self.generated) >= self.max_new_tokens:
            return True
        return bool(self.generated) and self.generated[-1] == self.eos_token_id


@dataclass
class KVHandoff:
    """Compact prefill→decode handoff record for disaggregated serving.

    Produced by ``export_handoff`` on a prefill replica after the prompt
    (plus the first generated token) has run; consumed by ``import_handoff``
    on a decode replica, which allocates fresh blocks, scatters the payloads,
    and resumes decode token-identically (per-request sampling keys depend
    only on (seed, gen_idx), never on the engine).

    The record is deliberately transport-agnostic: plain numpy payloads, the
    device-row snapshot in the PR-4 slot-row format (``row_iv``/``row_fv``
    mirror ``_write_slot_row``'s packed int/float planes), and primitive
    request metadata — an RDMA/ICI channel can serialize it without touching
    engine internals. The in-memory channel just passes the object through.
    """

    uid: Any
    prompt: list[int]
    generated: list[int]        # tokens emitted by the prefill stage (>= 1)
    pos: int                    # KV scheduled for positions [0, pos)
    max_new_tokens: int         # the DECODE side's budget (full request)
    eos_token_id: int | None
    temperature: float
    top_k: int
    top_p: float
    seed: int                   # effective per-request sampling seed
    deadline_remaining_s: float  # seconds of deadline left at export (0 = none)
    # KV payload covering ceil(pos / block_size) blocks: a pytree mirroring
    # the engine's paged cache with each leaf sliced to the exported blocks
    # along axis 1 ([num_layers, n_blocks, block_size, ...] per leaf), as
    # host numpy arrays
    block_payload: Any = None
    # device-row snapshot (PR-4 dirty-row format): int plane
    # (tok, pos, seed, prompt_len, top_k) + float plane (temperature, top_p)
    row_iv: np.ndarray = None
    row_fv: np.ndarray = None
    # W3C trace context of the originating request, so the decode replica
    # parents its spans under the same trace_id (fleet trace stitching)
    traceparent: str | None = None
    # KV codec of block_payload ("off" = fp payload). A decode replica
    # running a DIFFERENT codec config must reject the record
    # (import_handoff raises; the cluster falls back to a cold submit)
    # instead of scattering bytes it would dequantize wrong.
    codec: str = "off"
    # billing identity carried across the prefill->decode seam so the decode
    # replica's cost meter attributes the adopted request to the same tenant
    # (defaulted: records pickled by older peers import as tenant "default")
    tenant: str = "default"
    sla_class: str = "interactive"

    @property
    def n_blocks(self) -> int:
        leaves = jax.tree_util.tree_leaves(self.block_payload)
        return int(leaves[0].shape[1]) if leaves else 0

    @property
    def nbytes(self) -> int:
        n = sum(int(a.nbytes)
                for a in jax.tree_util.tree_leaves(self.block_payload))
        for a in (self.row_iv, self.row_fv):
            if a is not None:
                n += a.nbytes
        return n

    def to_bytes(self) -> bytes:
        """Serialize the record with length+sha256 framing
        (``kvtier.frame_bytes``) so the disk spill tier and any cross-host
        transport share one end-to-end integrity check — a torn or
        bit-flipped buffer fails loudly in ``from_bytes`` instead of
        splicing corrupt KV."""
        from deepspeed_tpu.inference.kvtier import HANDOFF_MAGIC, frame_bytes

        body = pickle.dumps({f.name: getattr(self, f.name)
                             for f in fields(self)}, protocol=4)
        return HANDOFF_MAGIC + frame_bytes(body)

    @classmethod
    def from_bytes(cls, buf: bytes) -> "KVHandoff":
        """Inverse of ``to_bytes``. Raises ValueError for anything short of
        a byte-exact record (bad magic, torn frame, digest mismatch,
        trailing garbage)."""
        from deepspeed_tpu.inference.kvtier import (
            HANDOFF_MAGIC,
            unframe_bytes,
        )

        buf = bytes(buf)
        if not buf.startswith(HANDOFF_MAGIC):
            raise ValueError("not a KVHandoff record (bad magic)")
        body, end = unframe_bytes(buf, len(HANDOFF_MAGIC))
        if end != len(buf):
            raise ValueError("trailing bytes after KVHandoff frame")
        return cls(**pickle.loads(body))


@dataclass
class PrefixPayload:
    """Published prefix-cache blocks in transferable form: the prompt slice
    they cover plus their KV payloads. ``import_prefix`` re-derives the hash
    chain from the tokens (exact tuples, same keying as the local index) so
    a transferred block can never splice under the wrong key."""

    tokens: list[int]        # the covered block-aligned prompt prefix
    block_payload: Any = None  # cache pytree, leaves [L, n_blocks, bs, ...]
    # trace context of the exporting request (cross-replica span links)
    traceparent: str | None = None
    # KV codec of block_payload; a mismatched importer declines the splice
    # (prefix reuse is an optimization — a miss, not an error)
    codec: str = "off"

    @property
    def n_blocks(self) -> int:
        leaves = jax.tree_util.tree_leaves(self.block_payload)
        return int(leaves[0].shape[1]) if leaves else 0

    @property
    def nbytes(self) -> int:
        return sum(int(a.nbytes)
                   for a in jax.tree_util.tree_leaves(self.block_payload))


class RaggedInferenceEngine:
    """Continuous-batching engine over a ``ModelSpec`` with ragged hooks.

    ``put()`` requests at any time; ``step()`` advances every admitted request
    by up to one token (decodes) and/or one prompt chunk (prefills) inside one
    XLA call; finished sequences free their blocks and their slot is reused
    immediately (reference ``engine_v2.put`` + ``DSStateManager`` lifecycle).

    ``params`` handed in are the engine's from then on: read them back from
    ``engine.params`` (the same tree of the same values). Where the chip
    keeps a table the steps gather rows from column-major, the engine holds
    it row-major and deletes the array it was handed
    (``lay_out_for_row_gather``; ``tables_relaid`` says how many).
    """

    def __init__(self, model, ragged_config: RaggedConfig | None = None,
                 dtype=jnp.bfloat16, params: Any = None, seed: int = 0,
                 eos_token_id: int | None = None, quantize_bits: int = 0):
        # the pool, the slot leaves, the device state: once a process
        with phase("engine/init"):
            self._init(model, ragged_config, dtype, params, seed,
                       eos_token_id, quantize_bits)

    def _init(self, model, ragged_config, dtype, params, seed, eos_token_id,
              quantize_bits):
        self.cfg = ragged_config or RaggedConfig()
        self.ctx = ShardCtx()
        self.spec: ModelSpec = model(self.ctx) if callable(model) else model
        if self.spec.ragged_forward_fn is None or self.spec.init_paged_cache_fn is None:
            raise ValueError(f"model {self.spec.name} has no ragged/paged support")
        self.dtype = dtype
        self.eos_token_id = eos_token_id

        if params is None:
            params = self.spec.init_fn(jax.random.PRNGKey(seed))
        self.params = jax.tree_util.tree_map(
            lambda x: x.astype(dtype) if jnp.issubdtype(x.dtype, jnp.floating) else x,
            params,
        )
        # ---- low-bit serving (ops/kvquant.py) ----
        # ONE config surface: cfg.quant carries the KV codec, the woq bits
        # and the collective flag; the quantize_bits ctor arg stays as the
        # back-compat spelling of the woq component.
        parsed = kvquant.parse_quant(self.cfg.quant)
        self._kvq = parsed.kv
        self._kvq_name = parsed.kv.name if parsed.kv else "off"
        if parsed.qcol:
            # the quantized TP logits collective needs a mesh; this engine
            # is GSPMD-free single-host — accepted so one quant string works
            # across both engines, but inert here
            log_dist("ragged engine: quant '+qcol' has no mesh here; "
                     "ignored (see inference/engine.py)", ranks=[0])
        woq_bits = int(quantize_bits) or parsed.woq_bits
        if woq_bits:
            # weight-only quantization over the paged-KV engine (reference
            # inference/quantization WOQ composed with the v2 ragged engine)
            from deepspeed_tpu.ops.quantizer import quantize_params

            self.params = jax.jit(
                lambda p: quantize_params(p, bits=woq_bits,
                                          skip=tuple(self.spec.woq_skip))
            )(self.params)
        self.quantize_bits = woq_bits
        # the parameters as the engine will hold them: a table a step gathers
        # rows from lies row-major from here on (``lay_out_for_row_gather``)
        self.params, relaid = lay_out_for_row_gather(self.params,
                                                     self.spec.woq_skip)
        self.tables_relaid = len(relaid)
        self.tables_relaid_bytes = sum(int(t.nbytes) for t in relaid)
        # a re-laid table is COMMITTED to its device (its layout is a thing
        # only a committed array tells a dispatch), and a program that takes
        # a committed array commits everything it returns: the first step
        # program would hand the pool and the slot rows back committed, and
        # every program that had taken them uncommitted be built once more.
        # So beside such a table they are committed from the start
        # (``_beside_the_tables``); with none re-laid nothing is
        self._tables_on = relaid[0].sharding if relaid else None
        self.cache = self._build_cache()
        # a model whose layers carry a recurrent state a slot keeps it in
        # slot leaves beside the pool's block leaves (models/paged.py); the
        # engine learns it from the cache the model's own hook built
        self._slot_state = slot_leaves(self.cache) is not None
        self._slot_bytes = self.state_bytes_per_slot()
        k_pool = full_leaves(self.cache).get("k")
        self._tile_step_keys = (None if k_pool is None
                                else prefill_step_keys(k_pool))
        if self._slot_state:
            self._refuse_beside_blocks("slot")
        # a model some of whose layers attend over a window keeps their K and
        # V in sliding leaves, a second pool (models/paged.py): a second
        # allocator and a second block table of the same width beside the
        # first, and a sequence's blocks of it go back as its window slides
        sliding = sliding_leaves(self.cache)
        self._window = self.spec.sliding_window if sliding is not None else 0
        if sliding is not None and not self._window:
            raise ValueError(f"model {self.spec.name} built sliding leaves "
                             "but says no ModelSpec.sliding_window")
        if self._window:
            self._refuse_beside_blocks("sliding")
        # a model that generates by blocks (``ModelSpec.block_gen``): a
        # decoding sequence's step is a block of rows, the block lives in the
        # device's slot rows, and what that cannot carry refuses here
        self._blk = self.spec.block_gen
        self._rows_per_decode = 1 if self._blk is None else self._blk.length
        if self._blk is not None:
            self._refuse_for_blocks()
        # a family whose attention reads a selection of the context only
        # (``ModelSpec.index_topk`` rows a query): counted beside the context
        # on ``engine/dispatch``
        self._topk = self.spec.index_topk
        # ... by the family's own rule where it selects by blocks
        # (``ModelSpec.index_blocks``), else ``min(position + 1, index_topk)``
        self._sel_rule = self.spec.index_blocks
        # counts only a step program knows (``ModelSpec.step_counters``): it
        # hands their sums back behind the picked tokens, the reconcile folds
        # them into ``step_counts`` (totals) and ``_counts_unspanned`` (what
        # no ``engine/dispatch`` span has carried yet)
        self._counters = tuple(self.spec.step_counters)
        self.step_counts = dict.fromkeys(self._counters, 0)
        self._counts_unspanned = dict.fromkeys(self._counters, 0)
        # bytes one block would cost unquantized at the engine dtype / at
        # fp16: the baselines for kvquant_bytes_saved_total and the
        # resident-block multiplier the bench gates on. The blocks base
        # accumulates allocated_total of allocators retired by reset_state
        # so the saved-bytes counter stays monotonic across containment.
        self._kvq_blocks_allocated = 0
        self._kvquant_saved_seen = 0
        if self._kvq is not None:
            self._fp_block_bytes = kvquant.paged_block_bytes(
                self.spec.init_paged_cache_fn, self.cfg.num_blocks,
                self.cfg.block_size, dtype)
            self._fp16_block_bytes = kvquant.paged_block_bytes(
                self.spec.init_paged_cache_fn, self.cfg.num_blocks,
                self.cfg.block_size, jnp.float16)
        self.allocator = BlockedAllocator(self.cfg.num_blocks)
        self.window_allocator = BlockedAllocator(
            jax.tree_util.tree_leaves(sliding)[0].shape[1]) \
            if self._window else None
        # sliding blocks promised to admitted sequences and not yet held, the
        # blocks the slide has taken back, and the window's rows of the step
        # packed last (all of them, and its decode rows')
        self._win_reserved = 0
        self.window_blocks_slid = 0
        self._win_step = (0, 0, 0)
        self._slot_resets = 0    # slots the step being packed starts from zeros
        # the rows of the step packed last that the pool's write site takes
        # as slices (``_note_pool_rows``)
        self._pool_slice_rows = 0
        # ---- hierarchical KV tiering (inference/kvtier.py) ----
        # tier store + allocator demote hook; None with kv_tier off, and
        # the allocator's eviction path is then bit-identical to before
        self._kvtier = None
        self._kvtier_seen: dict[str, int] = {}
        if self.cfg.kv_tier:
            if not self.cfg.enable_prefix_cache:
                raise ValueError("kv_tier requires enable_prefix_cache "
                                 "(the tiers hold demoted prefix blocks)")
            if self.cfg.kv_tier_host_blocks <= 0:
                raise ValueError("kv_tier needs kv_tier_host_blocks > 0")
            from deepspeed_tpu.inference.kvtier import KVTierStore

            self._kvtier = KVTierStore(
                host_blocks=self.cfg.kv_tier_host_blocks,
                disk_blocks=self.cfg.kv_tier_disk_blocks,
                directory=self.cfg.kv_tier_dir,
                host_gbps=self.cfg.kv_tier_host_gbps,
                disk_gbps=KV_TIER_DISK_GBPS,
                prefill_tokens_per_s=self.cfg.kv_tier_prefill_tokens_per_s,
                bytes_per_token=self.kv_bytes_per_token(),
                codec=self._kvq_name,
            )
            self.allocator.demote_hook = self._demote_hook()
        # row max_seqs is the all-zeros padding row -> scratch block 0
        self.block_tables = np.zeros(
            (self.cfg.max_seqs + 1, self.cfg.max_blocks_per_seq), np.int32
        )
        # the sliding pool's table: the same width, the same addressing
        self.window_tables = np.zeros_like(self.block_tables) \
            if self._window else None
        self._free_slots = list(range(self.cfg.max_seqs - 1, -1, -1))
        # blocks promised to admitted sequences but not yet allocated;
        # admission reserves worst case (prompt + max_new) so an admitted
        # sequence can always finish (reference conservative admission)
        self._reserved = 0
        self._queued: list[_SeqState] = []
        self._running: dict[int, _SeqState] = {}  # slot -> seq
        self._results: dict[Any, _SeqState] = {}
        # token-batch size buckets: decode-heavy steps run a small compiled
        # size instead of padding to the full SplitFuse budget (the static-
        # shape analog of the reference's truly-ragged kernel batches); jit
        # specializes once per bucket shape, so at most log2 programs compile
        b = 4
        self._buckets = []
        while b < self.cfg.max_tokens_per_step:
            self._buckets.append(b)
            b *= 2
        self._buckets.append(self.cfg.max_tokens_per_step)
        self._step_jit = self._build_step()
        self._use_tiles = self.cfg.prefill_tile > 0
        if self._use_tiles and not self.spec.supports_prefill_tiles:
            raise ValueError(
                f"prefill_tile={self.cfg.prefill_tile} but model "
                f"{self.spec.name} does not accept prefill_tiles (its "
                "ragged_forward has no tiled path); it would silently no-op")
        if self._use_tiles and self.cfg.prefill_tile > self.cfg.max_tokens_per_step:
            raise ValueError("prefill_tile exceeds max_tokens_per_step")
        self._tiled_jits: dict = {}
        # decode-region buckets for the tiled path (decodes <= max_seqs):
        # doubling from the model's smallest (``ModelSpec.decode_bucket_min``)
        self._dec_buckets = []
        b = self.spec.decode_bucket_min
        while b < self.cfg.max_seqs:
            self._dec_buckets.append(b)
            b *= 2
        self._dec_buckets.append(self.cfg.max_seqs)
        # ---- device-resident scheduler state (cfg.device_state) ----
        # per-slot persistent rows (+1 scratch row at index max_seqs):
        # (last_token, next_position, seed, prompt_len, temp, top_k, top_p).
        # Written in place by a donated single-row updater at admission and
        # by the dispatch programs themselves (picked token / advanced
        # position), so a steady decode dispatch reads everything per-row
        # from device memory instead of re-packed host arrays.
        # A model that generates by blocks adds the block a slot is
        # denoising: its tokens ``[S + 1, B]`` and which of them are still
        # masked, beside ``next_position`` = the block's first position.
        self._dev_state = self._fresh_dev_state()

        def ragged_slot_rows(st, row, iv, fv, *blk):
            return (st[0].at[row].set(iv[0]), st[1].at[row].set(iv[1]),
                    st[2].at[row].set(iv[2]), st[3].at[row].set(iv[3]),
                    st[4].at[row].set(fv[0]), st[5].at[row].set(iv[4]),
                    st[6].at[row].set(fv[1]),
                    *(a.at[row].set(v) for a, v in zip(st[7:], blk)))

        self._slot_row_jit = jax.jit(ragged_slot_rows, donate_argnums=(0,))
        # device-resident block table: host self.block_tables stays ground
        # truth; rows dirtied by allocation/splice/release are delta-uploaded
        # (pow2-bucketed row count) before the next dispatch instead of
        # re-shipping a fresh _table_view slice every step
        self._bt_dev = jnp.asarray(self.block_tables)
        self._bt_dirty: set[int] = set()
        # the sliding table's mirror: the same shape, so the same row program
        self._bt_win_dev = jnp.asarray(self.window_tables) \
            if self._window else None
        self._bt_win_dirty: set[int] = set()
        def ragged_bt_rows(bt, idx, vals):
            return bt.at[idx].set(vals)

        self._bt_row_jit = jax.jit(ragged_bt_rows, donate_argnums=(0,))
        # packed staging buffer cache: one flat int32 upload per dispatch,
        # and ZERO uploads when the bytes match the previous dispatch at the
        # same size (the steady-decode case)
        self._staging_cache: dict[int, tuple[bytes, Any]] = {}
        # double-buffered readback: dispatched steps whose tokens have not
        # been read back yet (depth 1: readback of step t overlaps the
        # device executing step t+1)
        self._pending: list[dict] = []
        self._dev_step_jits: dict = {}
        # dispatch-overhead accounting (plain ints so the bench reads them
        # with telemetry off; telemetry mirrors them when enabled)
        self.host_stage_ns = 0
        self.readback_ns = 0
        self.h2d_bytes = 0
        self._h2d_seen = 0
        # per-request sampling: token g of a request with effective seed s
        # draws from fold_in(fold_in(_sample_root, s), g). The root is a
        # FIXED constant (not engine-seeded) so an explicitly seeded request
        # reproduces on any engine; auto-assigned seeds mix the engine seed
        # + put order in instead (legacy whole-engine determinism).
        self._sample_root = jax.random.PRNGKey(0x5A3D1E)
        self._engine_seed = int(seed)
        self._put_counter = 0
        # prefix-cache accounting (plain ints so the bench can read them
        # with telemetry off; telemetry mirrors them when enabled)
        self.prefix_hits = 0
        self.prefix_misses = 0
        self.prefix_tokens_reused = 0
        self.prefix_stale_probes = 0  # admissions whose splice came up short
        self._evictions_seen = 0  # high-water for the eviction counter delta
        # ---- disaggregated serving (serving/cluster.py) ----
        # finished prefill-stage sequences whose KV blocks are parked for
        # export_handoff(); cluster prefix-index listener survives
        # reset_state() by being reinstalled on the fresh allocator
        self._handoffs: dict[Any, _SeqState] = {}
        self._prefix_listener = None
        self._kv_gather_jits: dict[int, Any] = {}
        self._kv_scatter_jits: dict[int, Any] = {}
        self.kv_blocks_exported = 0
        self.kv_blocks_imported = 0
        # scheduling efficiency telemetry (padding fraction; comparable to the
        # dense engine's pad-to-max waste) + dispatch accounting (host
        # dispatches per generated token)
        self.tokens_scheduled = 0
        self.tokens_padded = 0
        self.dispatch_count = 0
        self.tokens_emitted = 0
        self.preemptions = 0
        # structured telemetry bus: request spans (queue wait / TTFT /
        # per-token decode latency / preemptions) + KV-occupancy gauges; every
        # emit is behind the singleton's enabled flag
        self.telemetry = get_telemetry()
        # request tracer: the object reference is stable for the process
        # lifetime (only its enabled flag toggles), so dispatch paths guard
        # on one attribute read and allocate nothing while tracing is off
        self._tracer = self.telemetry.tracer
        # ---- cost attribution (telemetry/costmeter.py) ----
        # the meter is read live off the bus at each seam (reconfiguration
        # mid-flight picks it up); per-seq charges guard on seq.cost, and
        # with no meter configured none of this state is ever touched.
        # _block_tenant maps published block id -> publishing tenant so the
        # retained-prefix carveout and cross-tenant splice credit/debit know
        # who to bill (bounded by num_blocks; overwritten on republish).
        self._block_tenant: dict[int, str] = {}
        self._cost_last_tick = 0.0
        self._flops_per_token: float | None = None
        # compile observability: every dispatch notes whether its jitted
        # program already existed (warm) or was created now (cold = a jit
        # cache miss at serve time); warmup() flips _warmed so coverage
        # distinguishes expected first-compiles from shape-busting traffic
        self.program_dispatches = 0
        self.program_cold_dispatches = 0
        self._warmed = False
        # ``warmup`` sets it where a persistent compilation cache is on: a
        # function that says how many lookups have missed it so far (the
        # first step program's tells whether the cache is cold)
        self._cache_misses: Callable[[], int] | None = None
        # specialization keys already dispatched for the paths whose jit
        # cache is internal to jax (no explicit program dict to probe)
        self._step_keys: set = set()
        # ---- dispatch watchdog (docs/FAULT_TOLERANCE.md) ----
        # degraded_mode: 0 = full configured path, 1 = host-staged fallback
        # (device_state flipped off), 2 = the same with prefill tiles off
        # (the plain SplitFuse step). Every rung is token-identical; the ladder
        # trades dispatch efficiency for a smaller failure surface.
        self._faults = get_fault_injector()
        self._retry_rng = random.Random(self._engine_seed ^ 0x5EED)
        self.degraded_mode = 0
        self.degraded_reason: str | None = None
        self.step_failures = 0   # transient device-path failures observed
        self.step_retries = 0    # in-place retries the watchdog issued
        self._consec_failures = 0
        # ---- memory ledger (telemetry/memledger.py) ----
        # per-owner byte attribution: fixed allocations (KV pool, device
        # scheduler rows, spec history) register handles; derived owners
        # (prefix LRU, parked handoffs, staging cache) register weakref'd
        # providers. All of it only exists when the ledger is configured —
        # with it off this is one attribute read and two None stores.
        self._kv_block_bytes: int | None = None
        self._mem_stats_fn: Callable | None = None  # test hook: fake stats
        self._memledger_handles: dict | None = None
        self._headroom_wait = False  # admission pinned by measured headroom
        self._headroom_stall_ticks = 0  # consecutive zero-progress waits
        self.last_oom_report: str | None = None
        self._register_memory_owners()
        if self.telemetry.enabled:
            g = self.telemetry.gauge
            g("engine_tables_relaid", "tables a step gathers rows from that "
              "the engine re-laid row-major when it took the parameters").set(
                  self.tables_relaid)
            g("engine_tables_relaid_bytes", "bytes of those tables").set(
                self.tables_relaid_bytes)
        log_dist(
            f"RaggedInferenceEngine: model={self.spec.name} "
            f"budget={self.cfg.max_tokens_per_step} max_seqs={self.cfg.max_seqs} "
            f"blocks={self.cfg.num_blocks}x{self.cfg.block_size} "
            f"tables_relaid={self.tables_relaid} "
            f"({self.tables_relaid_bytes} bytes)", ranks=[0],
        )

    # ------------------------------------------------------------------ put
    def put(self, uid, prompt_tokens, max_new_tokens: int = 64,
            eos_token_id: int | None = None, temperature: float = 0.0,
            top_k: int = 0, top_p: float = 1.0,
            deadline_s: float | None = None,
            seed: int | None = None, trace=None,
            handoff: bool = False,
            expected_cached_tokens: int = 0,
            tenant: str = "default",
            sla_class: str = "interactive") -> None:
        """Enqueue a request (reference ``engine_v2.py put()``). Admission into
        the running batch happens inside ``step()`` as slots/budget free up.
        ``temperature``/``top_k``/``top_p`` select per-request sampling
        (0-temperature = greedy), applied inside the compiled step with no
        host round trip (``inference/sampling.py``). ``seed`` pins the request's
        sampling stream: token g draws from a key derived only from
        (seed, g), so the same seeded request yields identical tokens on any
        engine regardless of batch composition, dispatch mode, or prefix-
        cache hits; None assigns an engine-seed + arrival-order seed (same
        engine seed + same put order still reproduces). ``deadline_s``
        bounds the request's whole lifetime (queue wait included): past it
        the sequence is released on the next ``step()`` with span
        status=timeout. ``trace`` threads a serving-side trace context
        (``telemetry.tracing.TraceContext``) so the request's engine spans
        parent under the HTTP root; with the tracer enabled and no context
        given, the engine head-samples a fresh trace per request."""
        prompt = [int(t) for t in np.asarray(prompt_tokens).reshape(-1)]
        if not prompt:
            raise ValueError("empty prompt")
        if handoff:
            self._refuse_block_transfer("KVHandoff")
        if self._blk is not None and temperature > 0.0:
            self._refuse_by_blocks("temperature > 0")
        if max_new_tokens < 1:
            raise ValueError("max_new_tokens must be >= 1")
        # a prefill-stage (handoff) request runs prompt + ONE token here;
        # the decode replica that imports the record owns the full budget
        # (and re-validates it against its own caps at import)
        eff_new = 1 if handoff else max_new_tokens
        total = len(prompt) + eff_new
        if total > self.cfg.max_seq_len:
            raise ValueError(
                f"request length {total} exceeds engine max_seq_len "
                f"{self.cfg.max_seq_len}"
            )
        worst = -(-total // self.cfg.block_size)
        if worst > min(self.cfg.num_blocks - 1, self.cfg.max_blocks_per_seq):
            raise ValueError(
                f"request needs {worst} KV blocks but at most "
                f"{min(self.cfg.num_blocks - 1, self.cfg.max_blocks_per_seq)} "
                "are available per sequence — it could never be admitted"
            )
        if deadline_s is not None and deadline_s <= 0:
            raise ValueError("deadline_s must be positive")
        if seed is None:
            eff_seed = (self._engine_seed * 1000003
                        + self._put_counter) & 0x7FFFFFFF
        else:
            eff_seed = int(seed) & 0x7FFFFFFF
        self._put_counter += 1
        # re-putting a retired uid supersedes its old record (idempotent
        # failover resubmission: the router replays a request that died with
        # its replica; get_request/_results must reflect the live attempt,
        # not the stale error)
        self._results.pop(uid, None)
        if self._tracer.enabled:
            # seq.trace is the request's umbrella "engine/request" span:
            # a child of the serving root when one was threaded in, or a
            # fresh head-sampled root for direct engine use. The span id is
            # allocated now so queue/admission/dispatch/readback children
            # can parent to it; the span itself is recorded at release.
            trace_ctx = (self._tracer.begin(trace) if trace is not None
                         else self._tracer.extract(None))
        else:
            trace_ctx = None
        seq = _SeqState(
            uid=uid, prompt=prompt, max_new_tokens=eff_new,
            eos_token_id=eos_token_id if eos_token_id is not None else self.eos_token_id,
            temperature=float(temperature), top_k=int(top_k),
            top_p=float(top_p), seed=eff_seed,
            deadline=(time.perf_counter() + deadline_s) if deadline_s else 0.0,
            t_enqueue=time.perf_counter() if self.telemetry.enabled else 0.0,
            trace=trace_ctx,
            handoff=bool(handoff), handoff_budget=int(max_new_tokens),
            expected_cached=max(0, int(expected_cached_tokens)),
            tenant=str(tenant), sla_class=str(sla_class),
            blk=self._blk.length if self._blk is not None else 0,
        )
        cm = self.telemetry.costmeter
        if cm is not None:
            seq.cost = cm.start(seq.tenant, seq.sla_class)
        self._queued.append(seq)
        if self.telemetry.enabled:
            self.telemetry.counter(
                "inference_requests_queued_total", "requests accepted").inc()

    @property
    def has_work(self) -> bool:
        return bool(self._queued or self._running or self._pending)

    @property
    def finished_uids(self):
        """UIDs of completed requests (public completion signal; the full
        token lists come from ``generate_all`` / the per-uid state)."""
        return set(self._results)

    def get_request(self, uid):
        """Host descriptor of a request at any lifecycle stage (queued,
        running, or retired), or None if the uid is unknown. The serving
        tier's token-delivery loop reads ``generated``/``status`` off it."""
        seq = self._results.get(uid)
        if seq is not None:
            return seq
        for seq in self._running.values():
            if seq.uid == uid:
                return seq
        for seq in self._queued:
            if seq.uid == uid:
                return seq
        return None

    def cancel(self, uid) -> bool:
        """Abort a request. The reference engine has no abort path (only a
        full drain); a serving frontend needs one or a hung client leaks KV
        pages forever. A queued request is dropped and a running one releases
        its KV blocks on the next ``step()`` (``_release`` via the normal
        retirement machinery — the release defers until the pending
        dispatches referencing the sequence reconcile). The
        request span is emitted with ``status=cancelled``. Returns False if
        the uid is unknown or already retired."""
        for seq in self._queued:
            if seq.uid == uid and seq.status == "finished":
                seq.status = "cancelled"
                return True
        for seq in self._running.values():
            if seq.uid == uid and seq.status == "finished":
                seq.status = "cancelled"
                return True
        return False

    def _sweep_aborts(self) -> None:
        """Retire cancelled/deadline-expired sequences (queued AND running)
        at the top of every step, so an abort can never outlive one step
        boundary. Queued sequences hold no blocks and retire directly;
        running ones go through ``_release`` (KV blocks + slot freed) unless
        a pending dispatch still references them (``refs`` > 0), in which
        case ``_reconcile_pending`` releases them as the window drains."""
        now = None
        for seq in (*self._queued, *self._running.values()):
            if seq.status == "finished" and seq.deadline:
                if now is None:
                    now = time.perf_counter()
                if now >= seq.deadline:
                    seq.status = "timeout"
        aborted = [s for s in self._queued if s.status != "finished"]
        if aborted:
            self._queued = [s for s in self._queued if s.status == "finished"]
            for seq in aborted:
                self._results[seq.uid] = seq
                if self.telemetry.enabled:
                    self._emit_request_span(seq)
        for seq in list(self._running.values()):
            if seq.status != "finished" and seq.refs == 0:
                self._release(seq)

    # ------------------------------------------------------------------ step
    def _worst_case_blocks(self, seq: _SeqState) -> int:
        total = len(seq.prompt) + seq.max_new_tokens
        return -(-total // self.cfg.block_size)

    def _sliding_blocks_cap(self, seq: _SeqState) -> int:
        """The sliding blocks ``seq`` holds between steps at most: what the
        family's pool gives a slot (``models/paged.sliding_blocks_per_seq``),
        or its whole length where that is shorter; 0 for a model with no
        window."""
        if not self._window:
            return 0
        return min(self._worst_case_blocks(seq),
                   sliding_blocks_per_seq(self._window, self.cfg.block_size))

    # ---------------------------------------------------------- prefix cache
    def _match_prefix(self, prompt: list[int]) -> list[int]:
        """Longest cached full-block prefix of ``prompt``: walk the hash
        chain block by block until the first miss. Capped one token short of
        the full prompt — the first generated token needs the LAST prompt
        position's logits, which only a real forward produces, and recomputing
        that token's KV must land in a fresh (unshared) block — so at least
        the prompt's final block always prefills."""
        bs = self.cfg.block_size
        max_blocks = (len(prompt) - 1) // bs
        alloc = self.allocator
        blocks: list[int] = []
        key = None
        for i in range(max_blocks):
            key = (key, tuple(prompt[i * bs:(i + 1) * bs]))
            b = alloc.lookup(key)
            if b is None:
                break
            blocks.append(b)
        return blocks

    def cached_prefix_len(self, prompt_tokens) -> int:
        """Tokens of ``prompt_tokens`` the prefix cache could serve right now
        (block-aligned, always < len(prompt)). Read-only — no refcount or
        LRU mutation — so the serving router can probe it for admission math
        from another thread; the answer is advisory (the cache can evict
        between probe and admission) and admission re-checks under the
        engine's own reservation accounting."""
        if not self.cfg.enable_prefix_cache:
            return 0
        prompt = [int(t) for t in np.asarray(prompt_tokens).reshape(-1)]
        if not prompt:
            return 0
        return len(self._match_prefix(prompt)) * self.cfg.block_size

    def _publish_prompt_blocks(self, seq: _SeqState) -> None:
        """Publish the retired sequence's full prompt blocks into the prefix
        index (refcount handling stays in ``free``: published blocks fall
        into the evictable LRU instead of the free list when their last
        referent drops). Only blocks whose KV was actually scheduled count —
        a cancelled request mid-prefill publishes just its computed region."""
        bs = self.cfg.block_size
        n_full = min(seq.pos, len(seq.prompt)) // bs
        key = None
        track = seq.cost is not None
        for i in range(n_full):
            key = (key, tuple(seq.prompt[i * bs:(i + 1) * bs]))
            self.allocator.publish(seq.blocks[i], key)
            if track:
                # record the publisher so retained-prefix occupancy and
                # cross-tenant splices can be billed to the right party
                self._block_tenant[seq.blocks[i]] = seq.tenant

    # ------------------------------------- KV transfer (disaggregated serving)
    def set_prefix_listener(self, listener) -> None:
        """Attach a publish/evict listener (the cluster prefix index) to the
        allocator; survives ``reset_state`` (reinstalled on the fresh
        allocator, with ``listener.on_reset()`` telling the index to drop
        this replica's entries)."""
        self._prefix_listener = listener
        self.allocator.listener = listener

    def _refuse_beside_blocks(self, kind: str) -> None:
        """What assumes that a sequence's context is a chain of blocks, and
        nothing else, cannot serve a model that keeps part of it elsewhere
        (``kind``: slot leaves, or sliding leaves, whose blocks go back as the
        window slides): each refusal names the piece that is missing."""
        cfg = self.cfg
        _, leaves, why = _BESIDE_BLOCKS[kind]
        refused = [
            (cfg.enable_prefix_cache, "enable_prefix_cache", why),
            (cfg.kv_tier, "kv_tier", why),
            (self._kvq is not None, f"quant={cfg.quant!r}",
             f"a quantized pool beside {leaves} is not implemented"),
        ]
        if kind == "slot":
            refused.append(
                (not cfg.prefill_tile, "prefill_tile=0",
                 "a prompt's rows in one step must be a tile: the recurrence "
                 "runs them as one chunk, in order"))
        for on, name, why in refused:
            if on:
                self._refuse(kind, name, why)

    def _refuse(self, kind: str, what: str, why: str) -> None:
        raise ValueError(f"model {self.spec.name} keeps "
                         f"{_BESIDE_BLOCKS[kind][0]}; {what} is refused: {why}")

    def _refuse_block_transfer(self, what: str) -> None:
        """``KVHandoff`` moves a sequence as its blocks."""
        for kind, on in (("slot", self._slot_state), ("sliding", self._window)):
            if on:
                self._refuse(kind, what, _BESIDE_BLOCKS[kind][2])
        if self._blk is not None:
            self._refuse_by_blocks(what)

    def _refuse_by_blocks(self, what: str, shown: str | None = None) -> None:
        raise ValueError(
            f"model {self.spec.name} generates by blocks of "
            f"{self._blk.length}; {shown or what} is refused: "
            f"{_BY_BLOCKS[what]}")

    def _refuse_for_blocks(self) -> None:
        """What a model that generates by blocks cannot be served with,
        refused at construction, each by name."""
        cfg, b = self.cfg, self._blk.length
        for on, what, shown in (
                (cfg.kv_tier, "kv_tier", None),
                (cfg.enable_prefix_cache, "enable_prefix_cache", None),
                (self._kvq is not None, "quant", f"quant={cfg.quant!r}"),
                (not cfg.device_state, "device_state=False", None),
                (not cfg.prefill_tile or cfg.prefill_tile % b, "prefill_tile",
                 f"prefill_tile={cfg.prefill_tile}"),
                (cfg.block_size % b, "block_size",
                 f"block_size={cfg.block_size}")):
            if on:
                self._refuse_by_blocks(what, shown)
        if self._slot_state or self._window:
            raise NotImplementedError(
                f"model {self.spec.name}: generation by blocks beside slot "
                "state or sliding leaves is not implemented")
        if b * self.spec.decode_bucket_min > cfg.max_tokens_per_step:
            raise ValueError(
                f"max_tokens_per_step={cfg.max_tokens_per_step} holds no "
                f"decode bucket of {self.spec.decode_bucket_min} blocks of "
                f"{b} rows")

    def _fresh_dev_state(self):
        """The device's slot rows, all empty (``_init``'s comment)."""
        s1 = self.cfg.max_seqs + 1
        state = (
            jnp.zeros(s1, jnp.int32), jnp.zeros(s1, jnp.int32),
            jnp.zeros(s1, jnp.int32), jnp.zeros(s1, jnp.int32),
            jnp.zeros(s1, jnp.float32), jnp.zeros(s1, jnp.int32),
            jnp.ones(s1, jnp.float32),
        )
        if self._blk is not None:
            state += (jnp.zeros((s1, self._blk.length), jnp.int32),
                      jnp.ones((s1, self._blk.length), bool))
        return self._beside_the_tables(state)

    def _beside_the_tables(self, tree):
        """``tree`` (what the step programs hand back: the pool, the slot
        rows), committed to the device the re-laid tables are committed to;
        the same buffers, nothing is copied. As it is where no table was
        re-laid (``_init``'s comment)."""
        if self._tables_on is None:
            return tree
        return jax.device_put(tree, self._tables_on)

    def _blocks(self):
        """The cache's block leaves (``[L, NB, ...]`` each): what every
        per-block operation and byte count below sees."""
        return block_leaves(self.cache)

    def kv_bytes_per_token(self) -> int:
        """Bytes of paged-cache state one token position occupies across all
        block leaves — the bytes side of the transfer-vs-prefill cost model.
        A model's slot leaves are not a token's: ``state_bytes_per_slot``.
        Sliding leaves count: a token inside the window has a row in them."""
        bs = self.cfg.block_size
        total = 0
        for a in jax.tree_util.tree_leaves(self._blocks()):
            per_block = int(a.shape[0]) * int(np.prod(a.shape[2:])) \
                * a.dtype.itemsize
            total += per_block // bs
        return total

    @staticmethod
    def _leaves_block_bytes(leaves) -> int:
        """Bytes one block occupies across ``leaves`` (``[L, NB, ...]``)."""
        return sum(int(a.shape[0]) * int(np.prod(a.shape[2:])) * a.dtype.itemsize
                   for a in jax.tree_util.tree_leaves(leaves))

    def state_bytes_per_slot(self) -> int:
        """Bytes of recurrent state one slot holds across all slot leaves,
        whatever its sequence's length; 0 for a model with none."""
        return sum(int(a.shape[0]) * int(np.prod(a.shape[2:])) * a.dtype.itemsize
                   for a in jax.tree_util.tree_leaves(slot_leaves(self.cache)))

    def _block_bytes(self) -> int:
        """Bytes one KV block occupies across all cache leaves (cached)."""
        if self._kv_block_bytes is None:
            self._kv_block_bytes = \
                self.kv_bytes_per_token() * self.cfg.block_size
        return self._kv_block_bytes

    def _build_cache(self):
        """Build the paged KV pool: the family's plain fp pool when quant is
        off (bit-identical to the pre-quant engine), else the low-bit
        ``QuantizedKV`` pool built directly at storage precision (no
        transient fp allocation at the full pool size)."""
        return self._beside_the_tables(self.spec.init_paged_cache_fn(
            self.cfg.num_blocks, self.cfg.block_size, self.dtype,
            codec=self._kvq, num_slots=self.cfg.max_seqs + 1))

    def kv_quant_stats(self) -> dict | None:
        """Low-bit KV summary for bench/telemetry readers; None = quant off.
        ``resident_multiplier_vs_fp16`` is the blocks-per-HBM-byte gain the
        acceptance bar measures (fp16 block bytes / quantized block bytes)."""
        if self._kvq is None:
            return None
        bb = self._block_bytes()
        return {
            "codec": self._kvq_name,
            "block_bytes": bb,
            "fp16_block_bytes": self._fp16_block_bytes,
            "fp_block_bytes": self._fp_block_bytes,
            "resident_multiplier_vs_fp16": self._fp16_block_bytes / bb,
            "blocks_allocated_total": self._kvq_alloc_total(),
            "bytes_saved_total":
                self._kvq_alloc_total() * (self._fp_block_bytes - bb),
        }

    def _kvq_alloc_total(self) -> int:
        """Cumulative blocks allocated over the engine's lifetime (survives
        reset_state's allocator replacement via the accumulated base)."""
        return self._kvq_blocks_allocated + self.allocator.allocated_total

    # ------------------------------------------------------- memory ledger
    def _register_memory_owners(self) -> None:
        """Attribute this engine's long-lived device allocations to ledger
        owners. Providers close over a weakref so a retired engine is never
        pinned by the process-wide ledger (a dead ref returns None, which
        the ledger prunes). Called at construction AND retried from the
        per-step telemetry hook: telemetry is often configured after the
        engine is built (the training engine has the same lazy pattern),
        and an engine that never registers would make every census read
        ~100% unattributed. The handle cache makes re-entry a no-op."""
        led = self.telemetry.memledger
        if led is None or self._memledger_handles is not None:
            return
        h = {
            "params": led.register("params", "ragged/model_params",
                                   self.params),
            "kv_pool": led.register("kv_pool", "ragged/paged_kv_cache",
                                    self._blocks()),
            "device_sched_state": led.register(
                "device_sched_state", "ragged/slot_rows+block_table",
                (self._dev_state, self._bt_dev)),
        }
        if self._slot_state:
            h["slot_state"] = led.register(
                "slot_state", "ragged/recurrent_slot_state",
                slot_leaves(self.cache))
        self._memledger_handles = h
        ref = weakref.ref(self)

        def _staging_bytes():
            eng = ref()
            if eng is None:
                return None
            return sum(len(b) for b, _ in eng._staging_cache.values())

        def _prefix_retained_bytes():
            eng = ref()
            if eng is None:
                return None
            return eng.allocator.retained_blocks * eng._block_bytes()

        def _handoff_bytes():
            eng = ref()
            if eng is None:
                return None
            return sum(len(s.blocks) for s in eng._handoffs.values()) \
                * eng._block_bytes()

        led.register_provider("staging_buffers", "ragged/staging_cache",
                              _staging_bytes)
        if self._kvtier is not None:
            def _host_tier_bytes():
                eng = ref()
                if eng is None or eng._kvtier is None:
                    return None
                return eng._kvtier.host_nbytes

            def _disk_tier_bytes():
                eng = ref()
                if eng is None or eng._kvtier is None:
                    return None
                return eng._kvtier.disk_nbytes

            # off-device owners: host-RAM/disk bytes show in the breakdown
            # and gauges but are EXCLUDED from the census reconciliation
            # against jax.live_arrays() — they are not device bytes, and
            # counting them would fake overattribution
            led.register_provider("host_kv_tier", "ragged/kvtier_host_arena",
                                  _host_tier_bytes, offdevice=True)
            led.register_provider("disk_kv_tier", "ragged/kvtier_disk_spill",
                                  _disk_tier_bytes, offdevice=True)
        # retained prefix blocks and parked handoff blocks live INSIDE the
        # kv_pool arrays registered above — carve-outs, so the breakdown
        # shows them as their own owners while the attributed total still
        # counts each pool byte exactly once
        led.register_provider("prefix_cache_retained", "ragged/prefix_lru",
                              _prefix_retained_bytes, carveout_of="kv_pool")
        led.register_provider("kv_handoff", "ragged/parked_handoffs",
                              _handoff_bytes, carveout_of="kv_pool")

    def _refresh_memory_handles(self) -> None:
        """Re-measure ledger handles after crash containment rebuilt the
        cache/state arrays (the old buffers are garbage now)."""
        led = self.telemetry.memledger
        h = self._memledger_handles
        if led is None or h is None:
            return
        led.update(h["kv_pool"], self._blocks())
        if "slot_state" in h:
            led.update(h["slot_state"], slot_leaves(self.cache))
        led.update(h["device_sched_state"],
                   (self._dev_state, self._bt_dev))

    def _note_oom(self, seam: str, exc: BaseException) -> None:
        """OOM forensics: snapshot the per-owner breakdown + census into a
        crash-report JSON the moment RESOURCE_EXHAUSTED surfaces (never
        raises; marks the exception so nested seams report once)."""
        if getattr(exc, "_oom_recorded", False):
            return
        try:
            exc._oom_recorded = True
        except Exception:
            pass
        path = record_oom(seam, exc, context={
            "running": len(self._running),
            "queued": len(self._queued),
            "free_blocks": self.allocator.free_blocks,
            "reserved_blocks": self._reserved,
            "retained_blocks": self.allocator.retained_blocks,
            "degraded_mode": self.degraded_mode,
        })
        if path is not None:
            self.last_oom_report = path

    # --------------------------------------------- headroom-driven admission
    def _device_memory_stats(self) -> dict:
        if self._mem_stats_fn is not None:
            try:
                return self._mem_stats_fn() or {}
            except Exception:
                return {}
        try:
            from deepspeed_tpu.accelerator.real_accelerator import (
                get_accelerator,
            )

            return get_accelerator().memory_stats() or {}
        except Exception:
            return {}

    def admission_headroom_blocks(self) -> int:
        """MEASURED free-byte headroom expressed in KV blocks, net of the
        pool's own preallocated footprint: the pool's allocatable blocks
        (free list + evictable prefix LRU) are bytes the device already
        funds, so admission drawing from them consumes no new HBM and must
        never be gated by a full-looking device. Only a deficit beyond what
        the pool could fund — other owners eating the guard band — shrinks
        the answer. -1 = unknown (no ``bytes_limit`` reported, or headroom
        admission disabled) — callers must fall back to the static
        block-count path, bit-identically."""
        cfg = self.cfg
        if not cfg.headroom_admission:
            return -1
        stats = self._device_memory_stats()
        limit = int(stats.get("bytes_limit") or 0)
        if limit <= 0:
            return -1
        bb = max(1, self._block_bytes())
        free = limit - int(stats.get("bytes_in_use") or 0)
        pool_funded = self.allocator.free_blocks * bb
        if self._window:
            # ``bb`` is a block of each pool; what each pool's own free list
            # funds is its own blocks' bytes
            bb_win = self._leaves_block_bytes(sliding_leaves(self.cache))
            pool_funded = (self.allocator.free_blocks * (bb - bb_win)
                           + self.window_allocator.free_blocks * bb_win)
        usable = free + pool_funded - int(HEADROOM_GUARD_FRACTION * limit)
        return max(0, usable // bb)

    def _enforce_retained_budget(self) -> int:
        """Shed the prefix-cache LRU under POOL-level pressure: retention
        may hold only what outstanding reservations don't need, i.e. evict
        until the free list alone covers ``self._reserved``. Deliberately
        not a device-byte budget — evicting a retained block returns it to
        the preallocated pool's free list and frees zero HBM, so a
        measured-byte budget here would wipe the cache on a full device
        without recovering anything. When reservations already fit the free
        list this is a no-op (static-path parity)."""
        alloc = self.allocator
        budget = alloc.free_blocks - self._reserved
        if budget >= alloc.retained_blocks:
            return 0
        evicted = alloc.shrink_retained(budget)
        if evicted and self.telemetry.enabled:
            self.telemetry.counter(
                "prefix_cache_headroom_evictions_total",
                "cached blocks evicted so the pool free list covers "
                "outstanding admission reservations",
            ).inc(evicted)
        return evicted

    def _kv_jits(self):
        if "g" not in self._kv_gather_jits:
            self._kv_gather_jits["g"] = jax.jit(
                lambda c, i: jax.tree_util.tree_map(lambda a: a[:, i], c))
            # donated: the scatter replaces self.cache in place
            self._kv_scatter_jits["s"] = jax.jit(
                lambda c, i, p: jax.tree_util.tree_map(
                    lambda a, pa: a.at[:, i].set(pa.astype(a.dtype)), c, p),
                donate_argnums=(0,))
        return self._kv_gather_jits["g"], self._kv_scatter_jits["s"]

    def _gather_blocks(self, blocks: list[int]):
        """Read the KV rows of ``blocks`` back to host numpy (pow2-bucketed
        index so the gather compiles O(log max_blocks_per_seq) times; pad
        rows re-read the scratch block and are sliced off)."""
        g, _ = self._kv_jits()
        n = len(blocks)
        r = 1
        while r < n:
            r *= 2
        idx = np.zeros(r, np.int32)
        idx[:n] = blocks
        out = g(self._blocks(), jnp.asarray(idx))
        return jax.tree_util.tree_map(lambda a: np.asarray(a[:, :n]), out)

    def _scatter_blocks(self, blocks: list[int], payload) -> None:
        """Write transferred KV payloads into ``blocks`` (donated in-place
        update of the paged cache; pad rows land in the scratch block)."""
        _, s = self._kv_jits()
        n = len(blocks)
        r = 1
        while r < n:
            r *= 2
        idx = np.zeros(r, np.int32)
        idx[:n] = blocks
        if r != n:
            payload = jax.tree_util.tree_map(
                lambda a: np.concatenate(
                    [a, np.zeros((a.shape[0], r - n) + a.shape[2:], a.dtype)],
                    axis=1),
                payload)
        self.h2d_bytes += idx.nbytes + sum(
            int(a.nbytes) for a in jax.tree_util.tree_leaves(payload))
        blocks = s(self._blocks(), jnp.asarray(idx), payload)
        self.cache = ({**self.cache, **blocks} if self._slot_state
                      else blocks)

    def export_handoff(self, uid) -> KVHandoff | None:
        """Turn a finished prefill-stage request (``put(handoff=True)``) into
        a transferable KVHandoff record, then retire its blocks locally
        (publishing the prompt blocks into this replica's prefix cache
        first, exactly like a normal retirement). None if ``uid`` has no
        parked handoff state (cancelled / timed out / already exported)."""
        seq = self._handoffs.pop(uid, None)
        if seq is None:
            return None
        bs = self.cfg.block_size
        # canonical resume point: feeding token_at(pos) at position pos
        # produces generated index pos - len(prompt) + 1, so the decode
        # side must resume one position behind the newest emitted token.
        # (The step in flight may have scheduled KV further; re-writing
        # that cell on resume is masked until the position is reached.)
        pos = len(seq.prompt) + len(seq.generated) - 1
        n_ctx = -(-pos // bs)
        payload = self._gather_blocks(seq.blocks[:n_ctx])
        self.kv_blocks_exported += n_ctx
        tok = seq.token_at(pos) if pos >= len(seq.prompt) else 0
        iv = np.asarray([tok, pos, seq.seed, len(seq.prompt), seq.top_k],
                        np.int32)
        fv = np.asarray([seq.temperature, seq.top_p], np.float32)
        rem = (max(0.0, seq.deadline - time.perf_counter())
               if seq.deadline else 0.0)
        rec = KVHandoff(
            uid=seq.uid, prompt=list(seq.prompt),
            generated=list(seq.generated), pos=pos,
            max_new_tokens=seq.handoff_budget or seq.max_new_tokens,
            eos_token_id=seq.eos_token_id, temperature=seq.temperature,
            top_k=seq.top_k, top_p=seq.top_p, seed=seq.seed,
            deadline_remaining_s=rem, block_payload=payload,
            row_iv=iv, row_fv=fv,
            traceparent=(format_traceparent(seq.trace)
                         if seq.trace is not None else None),
            codec=self._kvq_name,
            tenant=seq.tenant, sla_class=seq.sla_class)
        if seq.cost is not None:
            # settle the parked occupancy and bill the exported payload
            self._cost_tick()
            seq.cost.handoff_export_bytes += rec.nbytes
        if self.cfg.enable_prefix_cache:
            self._publish_prompt_blocks(seq)
        self.allocator.free(seq.blocks)
        seq.blocks = []
        self._finalize_cost(seq)
        if self.telemetry.enabled:
            self.telemetry.counter(
                "kv_transfer_blocks_total",
                "KV blocks moved by handoff/prefix transfers",
            ).inc(n_ctx, direction="export")
        return rec

    def discard_handoff(self, uid) -> bool:
        """Release a parked handoff without exporting it (the cluster's
        failure paths: transfer cancelled, decode side gone)."""
        seq = self._handoffs.pop(uid, None)
        if seq is None:
            return False
        if seq.cost is not None:
            self._cost_tick()
        if self.cfg.enable_prefix_cache:
            self._publish_prompt_blocks(seq)
        self.allocator.free(seq.blocks)
        seq.blocks = []
        self._finalize_cost(seq)
        return True

    def import_handoff(self, h: KVHandoff) -> bool:
        """Adopt a prefill replica's handoff: allocate fresh blocks, scatter
        the KV payload, seed the slot's device row from the record's PR-4
        row snapshot, and resume decode token-identically. Returns False
        when no slot or insufficient unreserved blocks are available right
        now (the cluster falls back to a cold submit); raises ValueError for
        requests this engine could never serve."""
        self._refuse_block_transfer("KVHandoff")
        cfg = self.cfg
        bs = cfg.block_size
        if getattr(h, "codec", "off") != self._kvq_name:
            # scattering a payload quantized under a different codec would
            # dequantize garbage (or splice int8 bytes as fp) — never
            # servable here, so raise (the loop surfaces import_rejected
            # and the cluster falls back to a cold submit)
            raise ValueError(
                f"handoff KV codec {getattr(h, 'codec', 'off')!r} does not "
                f"match this engine's quant config {self._kvq_name!r}")
        prompt = [int(t) for t in h.prompt]
        total = len(prompt) + int(h.max_new_tokens)
        if total > cfg.max_seq_len:
            raise ValueError(
                f"handoff length {total} exceeds engine max_seq_len "
                f"{cfg.max_seq_len}")
        worst = -(-total // bs)
        if worst > min(cfg.num_blocks - 1, cfg.max_blocks_per_seq):
            raise ValueError(
                f"handoff needs {worst} KV blocks but at most "
                f"{min(cfg.num_blocks - 1, cfg.max_blocks_per_seq)} are "
                "available per sequence")
        pos = int(h.pos)
        n_ctx = -(-pos // bs)
        if h.n_blocks != n_ctx:
            raise ValueError(
                f"handoff payload covers {h.n_blocks} blocks but pos={pos} "
                f"needs {n_ctx}")
        if not self._free_slots:
            return False
        if worst > self.allocator.free_blocks - self._reserved:
            return False
        seq = _SeqState(
            uid=h.uid, prompt=prompt, max_new_tokens=int(h.max_new_tokens),
            eos_token_id=h.eos_token_id, temperature=float(h.temperature),
            top_k=int(h.top_k), top_p=float(h.top_p), seed=int(h.seed),
            generated=list(h.generated), pos=pos,
            deadline=(time.perf_counter() + h.deadline_remaining_s)
            if h.deadline_remaining_s else 0.0,
            t_enqueue=time.perf_counter() if self.telemetry.enabled else 0.0,
            tenant=str(getattr(h, "tenant", "default")),
            sla_class=str(getattr(h, "sla_class", "interactive")),
        )
        cm = self.telemetry.costmeter
        if cm is not None:
            seq.cost = cm.start(seq.tenant, seq.sla_class)
            seq.cost.handoff_import_bytes += h.nbytes
        if self._tracer.enabled and h.traceparent:
            # adopt the prefill replica's trace: this request's decode-side
            # spans parent under the exporting span, so the fleet-merged
            # timeline shows ONE trace_id across both replicas
            seq.trace = self._tracer.extract(h.traceparent)
        self._results.pop(h.uid, None)  # supersede any stale retired record
        blocks = self.allocator.allocate(n_ctx)
        self._scatter_blocks(blocks, h.block_payload)
        self.kv_blocks_imported += n_ctx
        if self.telemetry.enabled:
            self.telemetry.counter(
                "kv_transfer_blocks_total",
                "KV blocks moved by handoff/prefix transfers",
            ).inc(n_ctx, direction="import")
        seq.blocks = blocks
        if seq.finished:
            # the prefill stage already hit EOS (or the budget was 1):
            # nothing to decode — retire immediately, seeding the local
            # prefix cache with the transferred prompt blocks
            if cfg.enable_prefix_cache:
                self._publish_prompt_blocks(seq)
            self.allocator.free(blocks)
            seq.blocks = []
            self._finalize_cost(seq)
            self._results[seq.uid] = seq
            return True
        slot = self._free_slots.pop()
        seq.slot = slot
        seq.reserved_remaining = worst - n_ctx
        self._reserved += seq.reserved_remaining
        self.block_tables[slot, :] = 0
        self.block_tables[slot, :n_ctx] = blocks
        self._bt_dirty.add(slot)
        self._running[slot] = seq
        if cfg.device_state:
            # the record's device-row snapshot IS the slot row (PR-4 format);
            # only the slot index is local
            iv = np.asarray(h.row_iv, np.int32)
            fv = np.asarray(h.row_fv, np.float32)
            self.h2d_bytes += iv.nbytes + fv.nbytes + 4
            self._dev_state = self._slot_row_jit(
                self._dev_state, np.int32(slot), iv, fv)
        return True

    def export_prefix(self, prompt_tokens, trace=None) -> PrefixPayload | None:
        """Export the longest locally-cached full-block prefix of a prompt
        as a transferable payload (cluster prefix transfer: the holder
        ships published blocks to the replica the router actually picked).
        None when nothing is cached. ``trace`` (a TraceContext) stamps the
        payload's ``traceparent`` so the importer's span links back to the
        requesting trace across processes."""
        if not self.cfg.enable_prefix_cache:
            return None
        prompt = [int(t) for t in np.asarray(prompt_tokens).reshape(-1)]
        if not prompt:
            return None
        if self._kvtier is not None:
            # a demoted chain is still this replica's to export: promote it
            # back to HBM first so the cluster index's tier-aware promises
            # stay serveable
            self._tier_promote(prompt)
        hit = self._match_prefix(prompt)
        if not hit:
            return None
        self.allocator.acquire(hit)  # pin against eviction during the gather
        try:
            payload = self._gather_blocks(hit)
        finally:
            self.allocator.free(hit)
        self.kv_blocks_exported += len(hit)
        if self.telemetry.enabled:
            self.telemetry.counter(
                "kv_transfer_blocks_total",
                "KV blocks moved by handoff/prefix transfers",
            ).inc(len(hit), direction="export")
        return PrefixPayload(
            tokens=prompt[:len(hit) * self.cfg.block_size],
            block_payload=payload,
            traceparent=(format_traceparent(trace)
                         if trace is not None else None),
            codec=self._kvq_name)

    def import_prefix(self, payload: PrefixPayload | None) -> int:
        """Install transferred prefix blocks into the local prefix cache
        (allocate → scatter → publish under the re-derived hash chain →
        refcount-0 into the evictable LRU, so the import stays strictly
        free-memory-funded). Returns the contiguous-from-root token count
        now cached locally. Already-published chain links are kept (dedupe);
        imports past the unreserved budget are dropped, never forced."""
        if payload is None or not self.cfg.enable_prefix_cache:
            return 0
        if getattr(payload, "codec", "off") != self._kvq_name:
            # prefix transfer is opportunistic — a codec mismatch is a
            # graceful miss (the importer just prefills), unlike handoff
            # adoption where mid-stream state makes it a hard error
            return 0
        t_imp0 = (time.perf_counter()
                  if self._tracer.enabled and payload.traceparent else 0.0)
        bs = self.cfg.block_size
        tokens = [int(t) for t in payload.tokens]
        n = min(payload.n_blocks, len(tokens) // bs)
        alloc = self.allocator
        keys = []
        missing = []
        key = None
        for i in range(n):
            key = (key, tuple(tokens[i * bs:(i + 1) * bs]))
            keys.append(key)
            if alloc.lookup(key) is None:
                missing.append(i)
        budget = max(0, alloc.free_blocks - self._reserved)
        missing = missing[:budget]
        if missing:
            blocks = alloc.allocate(len(missing))
            midx = np.asarray(missing)
            self._scatter_blocks(
                blocks,
                jax.tree_util.tree_map(lambda a: a[:, midx],
                                       payload.block_payload))
            for b, i in zip(blocks, missing):
                alloc.publish(b, keys[i])
            alloc.free(blocks)  # refcount 0 + published -> evictable LRU
            self.kv_blocks_imported += len(blocks)
            if self.telemetry.enabled:
                self.telemetry.counter(
                    "kv_transfer_blocks_total",
                    "KV blocks moved by handoff/prefix transfers",
                ).inc(len(blocks), direction="import")
        m = 0
        for k in keys:
            if alloc.lookup(k) is None:
                break
            m += 1
        if t_imp0:
            # span-link back to the exporting request's trace: the import
            # renders on this replica's track under the exporter's trace_id
            ctx = self._tracer.extract(payload.traceparent)
            self._tracer.finish(ctx, "kv/prefix_import", t_imp0,
                                time.perf_counter(),
                                blocks=len(missing), tokens=m * bs)
        return m * bs

    # --------------------------------- hierarchical KV tiering (kvtier.py)
    def _demote_hook(self):
        """The allocator's way back into the engine on eviction. It holds
        the engine weakly: engine -> allocator -> bound method -> engine is
        a cycle, and a dropped tiered engine would keep its weights and pool
        on the device until the cycle collector next ran (the memory
        ledger's census reads them as unattributed bytes). The engine owns
        the allocator, so the hook never outlives what it points at."""
        demote = weakref.WeakMethod(self._demote_block)
        return lambda block, key: demote()(block, key)

    def _demote_block(self, block: int, key) -> bool:
        """Allocator demote hook: gather one evicted block's payload
        device->host and park it in the tier store. Runs on the engine
        thread inside ``_evict_lru`` while the payload is still intact;
        True = captured (the cluster index hears a demotion, not a drop)."""
        store = self._kvtier
        if store is None:
            return False
        try:
            payload = self._gather_blocks([block])
        except Exception:  # noqa: BLE001 - a failed gather is a plain evict
            return False
        ok = store.demote(key, payload)
        if ok:
            cm = self.telemetry.costmeter
            if cm is not None:
                # the demoted payload is the publishing tenant's working set
                # moving tier-ward; the publisher carries the byte charge
                tenant = self._block_tenant.get(block)
                if tenant is not None:
                    cm.demote_bytes(tenant, self._block_bytes())
        return ok

    def _chain_keys(self, prompt: list[int]) -> list:
        """The prompt's full-block hash-chain keys, root-first, capped one
        token short of the prompt exactly like ``_match_prefix``."""
        bs = self.cfg.block_size
        keys = []
        key = None
        for i in range((len(prompt) - 1) // bs):
            key = (key, tuple(prompt[i * bs:(i + 1) * bs]))
            keys.append(key)
        return keys

    def _tier_promote(self, prompt: list[int]) -> int:
        """Restore demoted chain links of ``prompt`` from the host/disk
        tiers back into the HBM prefix index, in chain order, when the
        cost model says the restore beats re-prefilling them. The restore
        is the ``import_prefix`` template — allocate -> scatter -> publish
        -> refcount-0 into the evictable LRU — so a subsequent
        ``_match_prefix`` splices promoted blocks exactly like blocks that
        never left HBM (token identity is free). Returns blocks promoted.

        Budget discipline matches ``import_prefix``: promotion draws only
        from unreserved allocatable blocks, and the allocation itself may
        demote colder LRU entries — the tiers churn, admission never
        starves."""
        store = self._kvtier
        if store is None:
            return 0
        bs = self.cfg.block_size
        alloc = self.allocator
        t0 = time.perf_counter()
        # contiguous-from-root restorable run: links already in HBM pass
        # through; the first link in neither HBM nor a tier ends the chain
        cand: list[tuple[Any, Any, int]] = []  # (key, payload, tier)
        for key in self._chain_keys(prompt):
            if alloc.lookup(key) is not None:
                continue
            tier = store.tier_of(key)
            if tier == 0:
                break  # held nowhere: the contiguous chain ends here
            if not store.should_restore(bs, tier):
                # a held link the cost model declines also ends the run —
                # splicing past a gap is impossible anyway
                store.restore_declined += 1
                break
            got = store.fetch(key)
            if got is None:
                break  # raced an overflow drop between tier_of and fetch
            cand.append((key, got[0], got[1]))
        budget = max(0, alloc.free_blocks - self._reserved)
        cand = cand[:budget]
        if not cand:
            return 0
        blocks = alloc.allocate(len(cand))
        payload = jax.tree_util.tree_map(
            lambda *xs: np.concatenate(xs, axis=1), *[p for _, p, _ in cand])
        self._scatter_blocks(blocks, payload)
        for b, (key, _, _) in zip(blocks, cand):
            alloc.publish(b, key)
        alloc.free(blocks)  # refcount 0 + published -> evictable LRU (MRU)
        dt = time.perf_counter() - t0
        tiers = [t for _, _, t in cand]
        store.note_restored(tiers, dt)
        if self.telemetry.enabled:
            self.telemetry.histogram(
                "kvtier_restore_seconds",
                "wall time of one tiered prefix restore (gather from tier, "
                "scatter to HBM, publish)",
            ).observe(dt, tier="disk" if 2 in tiers else "host")
        return len(cand)

    def _tier_admit(self, seq: _SeqState) -> None:
        """Admission-time tier pass, just before ``_match_prefix``: resolve
        the request's async prefetch (hit when staging finished during the
        queue wait, abandoned when admission outran it) and run the
        synchronous promotion — cheap when the prefetch landed, a full
        tier read when it didn't. Either way ``_match_prefix`` then sees
        the restored links in the ordinary HBM index."""
        store = self._kvtier
        keys = self._chain_keys(seq.prompt)
        if not keys:
            return
        store.note_admission(keys[-1])
        promoted = self._tier_promote(seq.prompt)
        if promoted and seq.cost is not None:
            # the admitting request is who needed the restore: it carries
            # the promote-byte charge (restored bytes re-entering HBM)
            seq.cost.tier_promote_bytes += promoted * self._block_bytes()

    def tier_prefetch_async(self, prompt_tokens) -> bool:
        """Advisory cross-thread prefetch kick (the serving router calls
        this at placement): queue a background staging job for the prompt's
        chain links missing from HBM so their restore overlaps the queue
        wait. Thread-safe — touches only the tier store (its own lock) and
        the same racy-but-safe read-only index probes
        ``cached_prefix_len`` already makes off-thread."""
        store = self._kvtier
        if store is None:
            return False
        prompt = [int(t) for t in np.asarray(prompt_tokens).reshape(-1)]
        keys = self._chain_keys(prompt)
        if not keys:
            return False
        pending = [k for k in keys if self.allocator.lookup(k) is None]
        if not pending:
            return False
        return store.prefetch(pending, sig=keys[-1])

    def kv_tier_stats(self) -> dict | None:
        """Tier store counters/occupancy (None when tiering is off).
        Thread-safe: the store snapshots under its own lock, so the
        frontend's ``/debug/memory`` can read it off-thread."""
        return None if self._kvtier is None else self._kvtier.stats()

    def _ensure_capacity(self, seq: _SeqState, upto: int) -> bool:
        """Grow seq's block table to cover positions [0, upto); False if the
        pool can't satisfy it right now. Admitted sequences draw from their
        admission-time reservation, so this cannot fail for them."""
        need = -(-upto // self.cfg.block_size) - len(seq.blocks)
        win_need = self._sliding_need(seq, upto) if self._window else ()
        if need <= 0 and not win_need:
            return True
        if need > self.allocator.free_blocks:
            return False
        if need > 0 and len(seq.blocks) + need > self.cfg.max_blocks_per_seq:
            return False
        # past what the sequence has reserved, the sliding pool gives only
        # what no other sequence's reservation counts on
        if win_need and len(win_need) > self.window_allocator.free_blocks - (
                self._win_reserved - seq.win_reserved):
            return False
        if self._faults.enabled:
            try:
                self._faults.fire(POINT_ALLOC, request_id=str(seq.uid))
            except Exception as e:
                if is_resource_exhausted(e):
                    self._note_oom("alloc", e)
                raise
        if win_need:
            for b, blk in zip(win_need, self.window_allocator.allocate(
                    len(win_need))):
                seq.win_blocks[b] = blk
                self.window_tables[seq.slot, b] = blk
            self._bt_win_dirty.add(seq.slot)
            self._sliding_rereserve(seq)
        if need <= 0:
            return True
        new = self.allocator.allocate(need)
        start = len(seq.blocks)
        seq.blocks.extend(new)
        drawn = min(seq.reserved_remaining, len(new))
        seq.reserved_remaining -= drawn
        self._reserved -= drawn
        self.block_tables[seq.slot, start:start + len(new)] = new
        self._bt_dirty.add(seq.slot)
        return True

    def _sliding_need(self, seq: _SeqState, upto: int) -> list[int]:
        """The block ordinals of the sliding pool that queries at positions
        ``seq.pos .. upto - 1`` read or write and ``seq`` does not hold:
        from the block of ``seq.pos``'s oldest key to that of ``upto - 1``."""
        bs = self.cfg.block_size
        first = max(0, seq.pos - self._window + 1) // bs
        return [b for b in range(first, -(-upto // bs))
                if b not in seq.win_blocks]

    def _sliding_rereserve(self, seq: _SeqState) -> None:
        """``seq``'s reservation in the sliding pool is what it may still
        come to hold between steps (``win_cap``) less what it holds."""
        want = max(0, seq.win_cap - len(seq.win_blocks))
        self._win_reserved += want - seq.win_reserved
        seq.win_reserved = want

    def _slide_windows(self) -> None:
        """**The slide**, after a step is dispatched: every running
        sequence's sliding blocks that no query from its next position on
        can read (block ``b`` once ``pos - W + 1 > BS b + BS - 1``) go back
        to the free list and their table entries to the scratch block. The
        step just dispatched read them through the table as it was
        uploaded; the blocks are handed out again by the NEXT step's
        packing at the earliest, so by a later program."""
        bs = self.cfg.block_size
        for seq in self._running.values():
            first = max(0, seq.pos - self._window + 1) // bs
            # held by ascending ordinal: the oldest tells whether any goes
            if not seq.win_blocks or next(iter(seq.win_blocks)) >= first:
                continue
            gone = [b for b in seq.win_blocks if b < first]
            self.window_allocator.free([seq.win_blocks.pop(b) for b in gone])
            self.window_tables[seq.slot, gone] = 0
            self._bt_win_dirty.add(seq.slot)
            self.window_blocks_slid += len(gone)
            self._sliding_rereserve(seq)
            if self.telemetry.enabled:
                self.telemetry.counter(
                    "inference_window_blocks_slid_total",
                    "sliding-pool blocks returned because their sequence's "
                    "window slid past them").inc(len(gone))

    def _drop_sliding_blocks(self, seq: _SeqState, cap: int = 0) -> None:
        """Return every sliding block ``seq`` holds and clear its row of the
        sliding table; its reservation becomes ``cap`` (0: it is leaving)."""
        if seq.win_blocks:
            self.window_allocator.free(list(seq.win_blocks.values()))
            seq.win_blocks = {}
        self.window_tables[seq.slot, :] = 0
        self._bt_win_dirty.add(seq.slot)
        seq.win_cap = cap
        self._sliding_rereserve(seq)

    @staticmethod
    def _stamp_emission(seq: _SeqState, now: float) -> None:
        if not seq.t_first_token:
            seq.t_first_token = now
        seq.t_last_token = now

    def _release(self, seq: _SeqState) -> None:
        self._reserved -= seq.reserved_remaining  # return unused reservation
        seq.reserved_remaining = 0
        if self._window:
            seq.blocks_at_end = (len(seq.blocks), len(seq.win_blocks))
            self._drop_sliding_blocks(seq)
        if seq.cost is not None:
            # close the occupancy integral over this sequence's final slice
            # before its blocks return to the pool
            self._cost_tick()
        if seq.handoff and seq.status == "finished":
            # prefill-stage retirement: PARK the KV blocks (refcounts held)
            # for export_handoff() instead of freeing them — only the slot
            # and reservation return to the pool. Cancel/timeout/error paths
            # fall through to the normal free below.
            self.block_tables[seq.slot, :] = 0
            self._bt_dirty.add(seq.slot)
            self._free_slots.append(seq.slot)
            del self._running[seq.slot]
            seq.slot = -1
            self._handoffs[seq.uid] = seq
            self._results[seq.uid] = seq
            if self.telemetry.enabled:
                self._emit_request_span(seq)
            return
        if self.cfg.enable_prefix_cache:
            # publish BEFORE free: blocks whose last referent drops here land
            # in the evictable LRU instead of the free list
            self._publish_prompt_blocks(seq)
        self.allocator.free(seq.blocks)
        seq.blocks = []
        self.block_tables[seq.slot, :] = 0
        self._bt_dirty.add(seq.slot)
        self._free_slots.append(seq.slot)
        del self._running[seq.slot]
        seq.slot = -1
        self._results[seq.uid] = seq
        if self.telemetry.enabled:
            self._emit_request_span(seq)

    def _emit_request_span(self, seq: _SeqState) -> None:
        """One request-lifecycle span at completion: queue wait, TTFT, mean
        per-token decode latency, preemption count (FastGen's serving SLO
        metrics, machine-readable)."""
        tel = self.telemetry
        n_gen = len(seq.generated)
        ttft = (seq.t_first_token - seq.t_enqueue
                if seq.t_first_token and seq.t_enqueue else None)
        queue_wait = (seq.t_admit - seq.t_enqueue
                      if seq.t_admit and seq.t_enqueue else None)
        # mean inter-token latency after the first token
        decode_latency = ((seq.t_last_token - seq.t_first_token) / (n_gen - 1)
                          if n_gen > 1 and seq.t_first_token else None)
        dur = (seq.t_last_token - seq.t_enqueue
               if seq.t_last_token and seq.t_enqueue else 0.0)
        cost_attrs = {}
        if seq.cost is not None:
            if queue_wait is not None:
                seq.cost.queue_wait_s = max(0.0, queue_wait)
            cost_attrs = seq.cost.span_attrs()
        tel.emit_span(
            "inference/request", dur, uid=str(seq.uid),
            status=seq.status,
            queue_wait_s=queue_wait, ttft_s=ttft,
            decode_latency_s=decode_latency,
            prompt_tokens=len(seq.prompt), new_tokens=n_gen,
            preemptions=seq.preemptions, **cost_attrs,
            **({"blk_denoise_passes": seq.blk_passes[0],
                "blk_commit_passes": seq.blk_passes[1]} if seq.blk else {}),
            **({"full_blocks": seq.blocks_at_end[0],
                "window_blocks": seq.blocks_at_end[1]} if self._window else {}))
        if seq.status == "cancelled":
            tel.counter("inference_requests_cancelled_total",
                        "requests aborted via cancel()").inc()
        elif seq.status == "timeout":
            tel.counter("inference_requests_timeout_total",
                        "requests expired past their deadline").inc()
        tel.counter("inference_requests_total", "requests completed").inc()
        tel.counter("inference_tokens_generated_total",
                    "tokens generated").inc(n_gen)
        if seq.preemptions:
            tel.counter("inference_preemptions_total",
                        "decode steps stalled on KV-pool pressure").inc(
                            seq.preemptions)
        if ttft is not None:
            tel.histogram("inference_ttft_seconds",
                          "time to first token").observe(ttft)
            tel.observe_slo("ttft", ttft, sla_class=seq.sla_class)
        if decode_latency is not None:
            tel.histogram("inference_decode_latency_seconds",
                          "mean inter-token decode latency").observe(
                              decode_latency)
            tel.observe_slo("decode_latency", decode_latency,
                            sla_class=seq.sla_class)
        if seq.trace is not None:
            # close the request's umbrella span: every queue/admission/
            # dispatch/readback child recorded along the way nests under it
            t_end = seq.t_last_token or time.perf_counter()
            t_start = seq.t_enqueue or t_end
            self._tracer.finish(
                seq.trace, "engine/request", t_start, t_end,
                uid=str(seq.uid), status=seq.status,
                prompt_tokens=len(seq.prompt), new_tokens=n_gen,
                ttft_s=ttft, preemptions=seq.preemptions or None)
            if not (seq.handoff and seq.status == "finished"):
                seq.trace = None  # released: nothing records under it now
            # a finished prefill-stage seq keeps its context parked with the
            # KV blocks: export_handoff stamps it as the record's traceparent
            # so the decode replica's spans stitch under this trace
        if not (seq.handoff and seq.status == "finished"):
            # a parked handoff keeps accruing block-seconds until export/
            # discard retires its blocks; everyone else settles up now
            self._finalize_cost(seq)

    def _finalize_cost(self, seq: _SeqState) -> None:
        """Fold the request's RequestCost into the meter exactly once."""
        cost = seq.cost
        if cost is None:
            return
        seq.cost = None
        cm = self.telemetry.costmeter
        if cm is not None:
            if not cost.queue_wait_s and seq.t_admit and seq.t_enqueue:
                cost.queue_wait_s = max(0.0, seq.t_admit - seq.t_enqueue)
            cm.observe(cost)

    def _cost_tick(self) -> None:
        """Advance the KV occupancy integral: charge every block-holding
        sequence (running + parked handoffs) and the retained prefix
        carveout (credited to publishing tenants) for the slice since the
        last tick. Called at the seams where block ownership changes —
        admission, release, handoff export/discard — plus the periodic
        step-telemetry sampler so long decodes accrue continuously."""
        cm = self.telemetry.costmeter
        if cm is None:
            return
        now = time.perf_counter()
        last = self._cost_last_tick
        self._cost_last_tick = now
        if not last:
            return  # first tick only establishes the baseline
        dt = now - last
        if dt <= 0.0:
            return
        live = [(s.cost, len(s.blocks)) for s in self._running.values()
                if s.cost is not None and s.blocks]
        for s in self._handoffs.values():
            if s.cost is not None and s.blocks:
                live.append((s.cost, len(s.blocks)))
        alloc = self.allocator
        retained: list[tuple[str, int]] = []
        if alloc._lru:
            bt = self._block_tenant
            counts: dict[str, int] = {}
            for b in alloc._lru:
                t = bt.get(b)
                if t is not None:
                    counts[t] = counts.get(t, 0) + 1
            retained = list(counts.items())
        cm.tick(dt, live, retained, alloc.busy_blocks)

    def _cost_fair_index(self, cm) -> int:
        """Index of the queued request admission should try next under the
        fair-share policy: the first whose tenant is at/under its fair share
        of outstanding blocks. Single-tenant queues (and queues where every
        tenant is over — everyone equally hungry) return 0, i.e. plain FIFO."""
        q = self._queued
        first = q[0].tenant
        if all(s.tenant == first for s in q):
            return 0
        for i, s in enumerate(q):
            share, fair = cm.outstanding_share(s.tenant)
            if share <= fair + 1e-9:
                return i
        return 0

    def _flops_per_token_value(self) -> float:
        """Analytic forward FLOPs per token (lazy; one profile per engine)."""
        if self._flops_per_token is None:
            try:
                from deepspeed_tpu.profiling.flops_profiler import (
                    get_model_profile,
                )
                prof = get_model_profile(self.spec, 1, 128,
                                         with_compiled=False)
                self._flops_per_token = float(prof.flops_fwd) / 128.0
            except Exception:
                self._flops_per_token = 0.0  # profile unavailable: tokens
                # still counted, FLOPs column reads 0 rather than failing
        return self._flops_per_token

    def _build_step(self) -> Callable:
        fwd = self.spec.ragged_forward_fn

        def step_fn(params, cache, tokens, slots, positions, block_tables):
            return fwd(params, tokens, slots, positions, block_tables, cache)

        return jax.jit(step_fn, donate_argnums=(1,))

    # ------------------------------------------- device-resident dispatch
    def _write_slot_row(self, seq: _SeqState) -> None:
        """Admission hook: write one slot's persistent device row in place
        (donated updater; ~32 bytes H2D instead of per-step re-packing).
        ``pos`` starts past any spliced cached prefix; at admission ``tok``
        is reset (the prompt-completing dispatch publishes the first feed
        token). When the watchdog rebuilds a mid-decode sequence's row,
        ``pos`` is already past the prompt and the host-known token at that
        position seeds the device feed instead."""
        feed = max(seq.pos, seq.replay)  # where the next decode row feeds
        # (by blocks nothing is fed from ``tok``: ``_first_block``)
        tok = seq.token_at(feed) \
            if feed >= len(seq.prompt) and not seq.blk else 0
        iv = np.asarray([tok, seq.pos, seq.seed, len(seq.prompt), seq.top_k],
                        np.int32)
        fv = np.asarray([seq.temperature, seq.top_p], np.float32)
        blk = self._first_block(seq) if self._blk is not None else ()
        self.h2d_bytes += iv.nbytes + fv.nbytes + 4 + sum(
            a.nbytes for a in blk)
        self._dev_state = self._slot_row_jit(
            self._dev_state, np.int32(seq.slot), iv, fv, *blk)

    def _first_block(self, seq: _SeqState):
        """The block a sequence's decoding starts with, at ``prefill_end``:
        the tokens the host knows from there (the prompt's remainder; after a
        recovery, nothing more: a block is handed on whole) and the mask
        over the rest, as the slot's rows ``(tokens [B], masked [B])``; sets
        the host's mirror of it."""
        b, p0 = self._blk.length, seq.prefill_end
        known = seq.tokens_at(
            p0, min(len(seq.prompt) + len(seq.generated), p0 + b))
        seq.blk_known, seq.blk_masked = len(known), b - len(known)
        return (np.asarray(known + [0] * seq.blk_masked, np.int32),
                np.arange(b) >= len(known))

    def _sync_bt(self) -> None:
        """Delta-upload block-table rows dirtied since the last dispatch
        (allocation growth, prefix splice, release) into the device-resident
        table. Row count is pow2-bucketed so the scatter compiles
        O(log max_seqs) times; padding index rows re-write the always-zero
        scratch row."""
        if self._bt_dirty:
            self._bt_dev = self._upload_rows(self._bt_dev, self.block_tables,
                                             self._bt_dirty)
        if self._bt_win_dirty:
            self._bt_win_dev = self._upload_rows(
                self._bt_win_dev, self.window_tables, self._bt_win_dirty)

    def _upload_rows(self, dev, host: np.ndarray, dirty: set):
        """``dev`` with the ``dirty`` rows of ``host`` written (and
        ``dirty`` cleared): one table's part of ``_sync_bt``."""
        rows = sorted(dirty)
        dirty.clear()
        r = 1
        while r < len(rows):
            r *= 2
        idx = np.full(r, self.cfg.max_seqs, np.int32)
        idx[:len(rows)] = rows
        vals = np.zeros((r, self.cfg.max_blocks_per_seq), np.int32)
        vals[:len(rows)] = host[rows]
        self.h2d_bytes += idx.nbytes + vals.nbytes
        return self._bt_row_jit(dev, jnp.asarray(idx), jnp.asarray(vals))

    def _tables_dev(self):
        """What a device step program is handed as its block tables: the
        table's mirror, or ``(full, sliding)`` for a model with a window."""
        if self._window:
            return self._bt_dev, self._bt_win_dev
        return self._bt_dev

    def _stage(self, arr: np.ndarray):
        """Upload ONE packed int32 staging buffer for a dispatch, skipping
        the H2D copy entirely when the bytes match the previous dispatch at
        this size — the steady-decode case: slots/flags planes are static
        across steps and tokens/positions live on device, so the whole
        buffer byte-compares equal."""
        if self._faults.enabled:
            self._faults.fire(POINT_H2D)
        arr = np.ascontiguousarray(arr, np.int32)
        raw = arr.tobytes()
        hit = self._staging_cache.get(arr.shape[0])
        if hit is not None and hit[0] == raw:
            return hit[1]
        dev = jnp.asarray(arr)
        self._staging_cache[arr.shape[0]] = (raw, dev)
        self.h2d_bytes += arr.nbytes
        return dev

    def _h2d(self, arr: np.ndarray):
        """The host-staged step's upload: jnp.asarray + H2D byte accounting,
        so the host-staged and device-resident paths report comparable
        ``h2d_bytes`` to the bench and telemetry."""
        if isinstance(arr, tuple):  # (full, sliding) table views
            return tuple(self._h2d(a) for a in arr)
        if self._faults.enabled:
            self._faults.fire(POINT_H2D)
        self.h2d_bytes += arr.nbytes
        return jnp.asarray(arr)

    def _note_dispatch(self, t0: float) -> None:
        """Per-dispatch overhead epilogue: host staging wall time (packing +
        upload + dispatch enqueue, NOT device execution) into the plain
        counter and, when enabled, the ``ragged_dispatch_host_ms``
        histogram. For a model with a window, the slide: its tables are
        uploaded and its step is on its way (``_slide_windows``)."""
        dt = time.perf_counter() - t0
        self.host_stage_ns += int(dt * 1e9)
        self.dispatch_count += 1
        if self._window:
            self._slide_windows()
        if self.telemetry.enabled:
            self.telemetry.histogram(
                "ragged_dispatch_host_ms",
                "host-side staging time per ragged dispatch",
                buckets=(0.05, 0.1, 0.25, 0.5, 1.0, 2.5, 5.0, 10.0, 25.0,
                         50.0)).observe(dt * 1e3)

    def _trace_spans(self, t0: float, t1: float, pairs, **attrs) -> None:
        """Record one child span per traced sequence over the window
        [t0, t1]. ``pairs`` is ``[(seq, span_name, tokens)]`` — callers
        build it (and call this) only when ``self._tracer.enabled``, so the
        untraced hot path allocates nothing."""
        tr = self._tracer
        for seq, name, ntok in pairs:
            tr.record(seq.trace, name, t0, t1, tokens=ntok, **attrs)

    def _note_program(self, kind: str, novel: bool) -> None:
        """Compile observability: every dispatch notes whether its jitted
        program already existed (warm) or had to be created (cold — the
        request's shape fell outside the cached bucket ladder, so XLA is
        compiling mid-serve). Feeds the ``warmup_coverage`` gauge and the
        per-family miss counter; ``warmup()`` zeroes the running totals so
        coverage reflects post-warmup traffic only."""
        self.program_dispatches += 1
        if not novel:
            return
        self.program_cold_dispatches += 1
        if self.telemetry.enabled:
            self.telemetry.counter(
                "ragged_program_cache_misses_total",
                "dispatches that created a new jitted program (shape "
                "outside the cached bucket ladder)").inc(kind=kind)

    def _get_dev_step(self, t: int, nd: int, nt: int, w: int, sampled: bool,
                      has_tk: bool, has_tp: bool):
        """Device-resident SplitFuse step (plain or tiled): feed tokens and
        positions gathered from the persistent slot rows (flag bit 0), pick
        next tokens ON DEVICE (greedy or per-request sampled, keys derived
        from device seed/position/prompt-len rows), and update the slot
        rows in place — the host touches only the packed staging buffer and
        the eventual token readback. Statics: (t_total, nd, nt, table
        width, sampling-filter flags)."""
        key = (t, nd, nt, w, sampled, has_tk, has_tp)
        fn = self._dev_step_jits.get(key)
        self._note_program("dev_step", fn is None)
        if fn is None:
            fn = self._dev_step_jits[key] = self._build_dev_step(*key)
        return fn

    def _build_dev_step(self, t: int, nd: int, nt: int, w: int, sampled: bool,
                        has_tk: bool, has_tp: bool):
        """The jitted program of ``_get_dev_step``'s key, newly made."""
        if self._blk is not None:
            return self._build_blk_step(t, nd, nt, w)
        fwd = self.spec.ragged_forward_fn
        if self._counters:
            fwd = partial(fwd, row_counts=True)
        ct = self.cfg.prefill_tile if self._use_tiles else 0
        max_seqs = self.cfg.max_seqs
        ntl = max(nt, 1)

        def step_fn(params, cache, state, bt_full, staged, root):
            from deepspeed_tpu.inference.sampling import (keys_for_positions,
                                                          sample_tokens)
            tok_st, pos_st, seed_st, plen_st, temp_st, topk_st, topp_st = state
            tokens = staged[0:t]
            slots = staged[t:2 * t]
            positions = staged[2 * t:3 * t]
            flags = staged[3 * t:4 * t]
            feed = (flags & 1) > 0
            real = slots != max_seqs
            tokens = jnp.where(feed, tok_st[slots], tokens)
            positions = jnp.where(feed & real, pos_st[slots], positions)
            if isinstance(bt_full, tuple):  # (full, sliding): one width
                bt = tuple(b[:, :w] for b in bt_full)
            else:
                bt = bt_full[:, :w] if w < bt_full.shape[1] else bt_full
            if ct:
                ts = staged[4 * t:4 * t + ntl]
                tp_ = staged[4 * t + ntl:4 * t + 2 * ntl]
                tv = staged[4 * t + 2 * ntl:4 * t + 3 * ntl]
                logits, cache, *counts = fwd(
                    params, tokens, slots, positions, bt, cache,
                    prefill_tiles=(nd, ts, tp_, tv, ct))
            else:
                logits, cache, *counts = fwd(params, tokens, slots, positions,
                                             bt, cache)
            if sampled:
                keys = keys_for_positions(root, seed_st[slots], positions,
                                          plen_st[slots])
                picked, _ = sample_tokens(
                    logits, keys, temp_st[slots],
                    top_k=topk_st[slots] if has_tk else None,
                    top_p=topp_st[slots] if has_tp else None)
            else:
                picked = jnp.argmax(logits.astype(jnp.float32),
                                    axis=-1).astype(jnp.int32)
            em = ((flags & 2) > 0) & real
            sl_t = jnp.where(em, slots, max_seqs)
            tok_st = tok_st.at[sl_t].set(jnp.where(em, picked, tok_st[sl_t]))
            sl_p = jnp.where(real, slots, max_seqs)
            pos_st = pos_st.at[sl_p].max(jnp.where(real, positions + 1, 0))
            state = (tok_st, pos_st, seed_st, plen_st, temp_st, topk_st,
                     topp_st)
            if counts:
                # the model's counts over the step's real rows ride behind
                # the picked tokens: one array, the readback there already is
                picked = jnp.concatenate([picked, jnp.sum(
                    jnp.where(real[None, :], counts[0], 0), axis=1,
                    dtype=jnp.int32)])
            return picked, state, cache

        # the program's name in a trace (``jit_ragged_step_d8_t3``): the same
        # string the engine/dispatch span carries
        step_fn.__name__ = self._step_program_name(t, nd, nt)
        return jax.jit(step_fn, donate_argnums=(1, 2))

    def _build_blk_step(self, t: int, nd: int, nt: int, w: int):
        """The device step program of a model that generates by blocks
        (``ModelSpec.block_gen``): ``nd`` counts SEQUENCES, each a block of
        ``B`` rows at the head of the step (``B x nd`` decode rows, then
        ``nt`` tiles). A block's tokens come from the slot's rows (a position
        still masked is fed as the mask token), its positions are ``p0 ..
        p0 + B - 1`` with ``p0`` the slot's ``next_position``. After the
        forward the program itself moves every block one pass on: where
        anything is masked it takes ``argmax`` of the UNSHIFTED logits at the
        masked positions and unmasks ``B / T`` of them (the leftmost, or
        those whose pick is most probable: ``remask``); a block that came in
        with nothing masked was the commit pass, whose rows the forward has
        just written to the pool: its ``p0`` moves on by ``B`` and the slot
        holds a new block, all masked. The readback carries every block's
        tokens as they stand after the pass, a row each (the host takes them
        when a block's last denoise pass reconciles; it knows which pass
        that is from the schedule and reads nothing else). Prefill rows move
        their slot's position on and pick nothing."""
        fwd = self.spec.ragged_forward_fn
        blk = self._blk
        b, unmask, mask_id = blk.length, blk.unmask, blk.mask_token_id
        by_confidence = blk.remask == "low_confidence_static"
        ct, max_seqs = self.cfg.prefill_tile, self.cfg.max_seqs
        ntl, rows = max(nt, 1), b * nd

        def step_fn(params, cache, state, bt_full, staged, root):
            del root  # greedy: sampling a block is refused at ``put``
            *rest, blk_tok, blk_mask = state
            pos_st = rest[1]
            tokens = staged[0:t]
            slots = staged[t:2 * t]
            positions = staged[2 * t:3 * t]
            pre = slots != max_seqs        # real rows ...
            if nd:
                pre = pre.at[:rows].set(False)   # ... of the tiles
                dsl = slots[:rows:b]                                # [nd]
                held, masked = blk_tok[dsl], blk_mask[dsl]          # [nd, B]
                tokens = tokens.at[:rows].set(
                    jnp.where(masked, mask_id, held).reshape(-1))
                positions = positions.at[:rows].set(
                    (pos_st[dsl][:, None] + jnp.arange(b)).reshape(-1))
            bt = bt_full[:, :w] if w < bt_full.shape[1] else bt_full
            ts = staged[4 * t:4 * t + ntl]
            tp_ = staged[4 * t + ntl:4 * t + 2 * ntl]
            tv = staged[4 * t + 2 * ntl:4 * t + 3 * ntl]
            logits, cache = fwd(params, tokens, slots, positions, bt, cache,
                                prefill_tiles=(rows, ts, tp_, tv, ct))
            picked = jnp.zeros(t, jnp.int32)
            if nd:
                lg = logits[:rows].astype(jnp.float32)
                x0 = jnp.argmax(lg, axis=-1).astype(jnp.int32).reshape(nd, b)
                if by_confidence:
                    # -log softmax(logits)[x0]: the smaller, the surer
                    doubt = jax.nn.logsumexp(
                        lg - jnp.max(lg, axis=-1, keepdims=True),
                        axis=-1).reshape(nd, b)
                    j, k = jnp.arange(b)[:, None], jnp.arange(b)[None, :]
                    ahead = masked[:, None, :] & (
                        (doubt[:, None, :] < doubt[:, :, None])
                        | ((doubt[:, None, :] == doubt[:, :, None]) & (k < j)))
                    rank = jnp.sum(ahead, axis=-1, dtype=jnp.int32)
                else:
                    rank = jnp.cumsum(masked, axis=1, dtype=jnp.int32) - 1
                take = masked & (rank < unmask)
                held = jnp.where(take, x0, held)
                live = dsl != max_seqs
                commit = live & ~jnp.any(masked, axis=1)
                left = (masked & ~take) | commit[:, None]
                # a padding block is the scratch slot's: it stays as it is
                blk_tok = blk_tok.at[dsl].set(
                    jnp.where(live[:, None], held, blk_tok[dsl]))
                blk_mask = blk_mask.at[dsl].set(
                    jnp.where(live[:, None], left, blk_mask[dsl]))
                pos_st = pos_st.at[dsl].add(jnp.where(commit, b, 0))
                picked = picked.at[:rows].set(held.reshape(-1))
            sl_p = jnp.where(pre, slots, max_seqs)
            pos_st = pos_st.at[sl_p].max(jnp.where(pre, positions + 1, 0))
            state = (rest[0], pos_st, *rest[2:], blk_tok, blk_mask)
            return picked, state, cache

        step_fn.__name__ = self._step_program_name(t, nd, nt)
        return jax.jit(step_fn, donate_argnums=(1, 2))

    def _step_zoo(self) -> list[tuple]:
        """``(t, nd, nt, w)`` of every greedy device step program the
        scheduler can pick with prefill tiles on: a function of the engine's
        sizes alone (each decode bucket beside each tile count
        ``_plan_prefill_tiles`` can return, each table width)."""
        cfg = self.cfg
        ct, budget = cfg.prefill_tile, cfg.max_tokens_per_step
        per = self._rows_per_decode   # rows a decoding sequence takes
        zoo = []
        for nd in [0] + self._dec_buckets:
            cap = (budget - nd * per) // ct
            tiles, p = {cap}, 1
            while p < cap:
                tiles.add(p)
                p *= 2
            for nt in sorted(tiles - {0}) + ([0] if nd else []):
                zoo += [(nd * per + nt * ct, nd, nt, w)
                        for w in self._width_ladder()]
        return zoo

    def _precompile_zoo_in_background(self) -> None:
        """The first step program of this engine found nothing in the
        persistent compilation cache, so none of them will: compile the
        others now, several at a time on threads of their own
        (``lower().compile()``: nothing runs, no engine state is touched, the
        executables go to the cache and are dropped), while the caller goes
        on dispatching. A program the foreground reaches later is traced
        there as ever and its compile is a cache hit; one it reaches before
        the background has is compiled twice, which costs only spare cores.
        Compiling is what a cold set-up spends its time on (27 programs of
        4-7 s each at the benchmark's 128-slot engines). The chip's compiler
        takes one program at a time whatever the threads, so what this buys
        is the overlap of the foreground's tracing, lowering and running with
        the compiling: 178 -> 146 s on the chip's host, twice (PERF.md
        section 6, PR 31), not the several-fold a CPU-only compile shows.
        Only a zoo of more programs than the pool has threads is worth it."""
        import os
        from concurrent.futures import ThreadPoolExecutor

        fixed = abstract_like((self.params, self.cache, self._dev_state,
                               self._tables_dev()))
        root = abstract_like(self._sample_root)

        def compile_one(key):
            t, _, nt, _ = key
            try:
                staged = jax.ShapeDtypeStruct((4 * t + 3 * max(nt, 1),),
                                              jnp.int32)
                self._build_dev_step(*key, False, False, False).lower(
                    *fixed, staged, root).compile()
            except Exception as e:  # the foreground compiles it itself
                log_dist(f"ragged engine: background compile of step "
                         f"program {key} failed: {e!r}", ranks=[0])

        workers = max(1, min(8, (os.cpu_count() or 2) - 2))
        have = {key[:4] for key in self._dev_step_jits}
        todo = [key for key in self._step_zoo() if key not in have]
        if len(todo) <= workers:
            # every one of them would start at once and none be done before
            # the foreground, slowed by all of them, has compiled it itself:
            # 7 step programs of 6-14 s each took 148 s that way where the
            # foreground alone takes ~75 (PERF.md section 6, PR 40)
            return
        pool = ThreadPoolExecutor(workers, thread_name_prefix="ragged-compile")
        for key in todo:
            pool.submit(compile_one, key)
        pool.shutdown(wait=False)
        log_dist(f"ragged engine: cold compilation cache; compiling the step "
                 f"programs {workers} at a time in the background", ranks=[0])

    def _dispatch_step_device(self) -> bool:
        """The device-resident SplitFuse step (plain or tiled): stage the
        packer's plan as one packed buffer (decode rows carry no
        token/position — those live on device), dispatch the step program,
        and queue the picked-token readback as a pending record. Returns
        False when nothing is schedulable.

        One host span per phase (``engine/schedule``, ``engine/stage``,
        ``engine/dispatch``; ``engine/readback`` is the reconcile's). The
        dispatch span carries the work of the dispatch, which the
        benchmark's readers match to the program's execution on the device:
        ``kv_tokens`` is the context every scheduled sequence attends over
        (once a sequence), ``attn_pairs`` the query x key pairs, and
        ``dec_kv_tokens`` the decode rows' part of ``kv_tokens`` (a decode
        row is one query, so also its part of the pairs); ``moe`` is the
        form of the step's expert FFNs (``_moe_attr``); a model with slot
        state adds what of it the step moves (``_state_attr``); a model
        that selects the rows a query attends over (``ModelSpec.index_topk``
        a query; the three above are then what its indexer scores) adds
        ``sel_pairs``, the query x kept-row pairs (``min(position + 1,
        index_topk)`` a query), ``sel_kv_tokens``, the fewest cached rows
        any implementation must read (``min(context, index_topk)`` a decode
        row and a prefill tile), ``dec_sel_kv_tokens``, the decode rows'
        part of that, and ``sel_decode``, the form the program's decode rows
        read the pool in (``_sel_decode_attr``); one that selects by blocks
        past a dense length (``ModelSpec.index_blocks``) counts the three by
        its own rule (every key up to the dense length, the kept blocks past
        it) and adds ``sel_queries``, the queries past that length, and
        ``cmp_kv_tokens``, the compressed keys their scores must read
        (``_count_selecting``); a model whose step program
        counts what only it knows (``ModelSpec.step_counters``: a router's
        ``moe_picks``, ``moe_zero_picks``, ``moe_held_picks``) adds those
        counts of the steps before this one (``_counts_attr``); a model with
        a window (``ModelSpec.sliding_window``) adds what its window layers
        read and what the two pools hold (``_window_attr``); a program with
        tiles over a K/V pool says the keys a grid step of its tile kernel
        takes (``_tiles_attr``). ``pool_slice_rows`` is the part of ``tokens``
        the pool's write site takes as slices (``_note_pool_rows``)."""
        t0 = time.perf_counter()
        with span("engine/schedule"):
            plan = self._pack_step(host_feed=False)
        if plan is None:
            return False
        (parts, emit, tpairs, t_total, n, nd, nt, max_pos, kv_dec, kv_pre,
         pairs_pre, n_dec, n_pre, sel, blk) = plan
        # a decode row is one query over its context; a block of rows is B
        pairs_dec = kv_dec * self._rows_per_decode
        sampled = any(s.temperature > 0.0 for _, s in emit)
        has_tk = sampled and any(s.top_k > 0 for _, s in emit)
        has_tp = sampled and any(s.top_p < 1.0 for _, s in emit)
        with span("engine/stage"):
            self._sync_bt()
            staged = self._stage(np.concatenate(parts))
        width = self._table_width(max_pos)
        fn = self._get_dev_step(t_total, nd, nt, width, sampled, has_tk,
                                has_tp)
        # the FIRST step program an engine builds tells whether the persistent
        # cache is cold (``warmup`` counts its misses). Only the first: a warm
        # cache that misses a program or two later (its size limit thins it)
        # must not pay for a background compile of programs it mostly has
        probe = (self._cache_misses is not None and self._use_tiles
                 and len(self._dev_step_jits) == 1
                 and self.program_dispatches == self.program_cold_dispatches == 1)
        misses = self._cache_misses() if probe else 0
        if self._faults.enabled:
            self._faults.fire(POINT_DISPATCH)
        state = self._state_attr(n_dec, n_pre, n, nt, nd)
        with span("engine/dispatch",
                  program=self._step_program_name(t_total, nd, nt),
                  tokens=n, pad=t_total - n,
                  pool_slice_rows=self._pool_slice_rows,
                  kv_tokens=kv_dec + kv_pre,
                  attn_pairs=pairs_dec + pairs_pre, dec_kv_tokens=kv_dec,
                  **self._moe_attr(t_total), **state, **sel,
                  **self._sel_decode_attr(width), **self._counts_attr(),
                  **self._window_attr(), **self._tiles_attr(nt),
                  **self._block_attr(blk)):
            picked, self._dev_state, self.cache = fn(
                self.params, self.cache, self._dev_state, self._tables_dev(),
                staged, self._sample_root)
        if state and self.telemetry.enabled:
            moved = self.telemetry.counter(
                "inference_slot_state_bytes_total",
                "slot-state bytes the steps had to read and write (a slot's "
                "state once each way), by the rows that moved them")
            kind = {k: v for k, v in state.items() if k == "state_kind"}
            moved.inc(state["dec_state_bytes"], part="decode", **kind)
            moved.inc(state["state_bytes"] - state["dec_state_bytes"],
                      part="prefill", **kind)
            if "chunk_slots" in state:
                self.telemetry.counter(
                    "inference_chunk_tiles_total",
                    "prefill tiles the step programs ran a recurrence's "
                    "chunk form over, a layer (padding tiles too)").inc(
                        state["chunk_tiles"], **kind)
            self.telemetry.counter(
                "inference_slot_state_pad_rows_total",
                "rows of the steps' decode buckets that were padding: they "
                "name the scratch slot and move its state all the same").inc(
                    state["state_pad_rows"], **kind)
            self.telemetry.counter(
                "inference_slot_resets_total",
                "slots the steps started from zeros (a sequence's first "
                "tile: an arrival, or a preempted request run again)").inc(
                    state["slot_resets"], **kind)
        if sel and self.telemetry.enabled:
            tel = self.telemetry
            tel.counter("inference_sparse_selected_tokens_total",
                        "cached rows the queries' selections kept (a "
                        "query x kept-row pair each)").inc(sel["sel_pairs"])
            tel.counter("inference_sparse_context_tokens_total",
                        "cached rows the same queries could have read (a "
                        "query x context-row pair each)").inc(
                            kv_dec + pairs_pre)
            if "sel_queries" in sel:
                tel.counter("inference_sparse_selecting_queries_total",
                            "queries past the family's dense length: those "
                            "that scored blocks and kept some").inc(
                                sel["sel_queries"])
                tel.counter("inference_sparse_compressed_keys_total",
                            "compressed keys the selecting queries' scores "
                            "had to read (once a decode row and a tile)").inc(
                                sel["cmp_kv_tokens"])
        if probe and self._cache_misses() > misses:
            self._precompile_zoo_in_background()
        participants: dict[int, _SeqState] = {}
        for _, seq in emit:
            participants[seq.slot] = seq
        if blk is not None:
            # a pass that hands nothing on still writes its sequence's blocks
            for seq in blk["seqs"]:
                participants[seq.slot] = seq
            if self.telemetry.enabled:
                passes = self.telemetry.counter(
                    "inference_block_passes_total",
                    "passes of a block of rows through the model, by what "
                    "the pass was (denoise: unmasked part of the block; "
                    "commit: wrote the finished block's K and V)")
                passes.inc(len(blk["seqs"]) - blk["commit"], phase="denoise")
                passes.inc(blk["commit"], phase="commit")
                self.telemetry.counter(
                    "inference_block_tokens_unmasked_total",
                    "positions the denoise passes unmasked").inc(
                        blk["unmasked"])
        for seq in participants.values():
            seq.refs += 1
        self._pending.append({"picked": picked, "emit": emit,
                              "participants": list(participants.values())})
        self._note_dispatch(t0)
        if tpairs is not None:
            self._trace_spans(t0, time.perf_counter(), tpairs,
                              mode="dev_step")
        return True

    def _block_attr(self, blk: dict | None) -> dict:
        """For a model that generates by blocks, what the step packed last
        did by blocks: ``blk_seqs``, the sequences that ran a block through
        the model; ``blk_commit_seqs``, those of them in their commit pass;
        ``blk_unmasked``, the positions the step unmasks (the host knows it
        from the schedule); ``blk_len`` and ``blk_steps``, the block length
        and the denoise passes a block. The span's other arguments keep their
        meaning: ``tokens`` rows computed, ``kv_tokens`` / ``dec_kv_tokens``
        the context read once a sequence and pass (``p0 + B``),
        ``attn_pairs`` query x key pairs (``B`` times that a block). Nothing
        for a model with none."""
        if blk is None:
            return {}
        return {"blk_seqs": len(blk["seqs"]), "blk_commit_seqs": blk["commit"],
                "blk_unmasked": blk["unmasked"],
                "blk_len": self._blk.length, "blk_steps": self._blk.steps}

    def _moe_attr(self, t: int) -> dict:
        """``{"moe": "grouped" | "dense"}``: the form the model's routed
        experts take in a step program of ``t`` rows, from the predicate the
        model itself calls; nothing for a family without routed experts."""
        form = self.spec.moe_form
        return {} if form is None else {"moe": form(t)}

    def _counts_attr(self) -> dict:
        """The model's step counters (``ModelSpec.step_counters``) for an
        ``engine/dispatch`` span: the sums of the steps reconciled since the
        last span took them. A step's own counts are on the device when its
        span is written, and waiting for them would stall the pipeline; so a
        span carries EARLIER steps' counts (the one or two before it in a
        steady loop), every step's exactly once. Nothing for a model with
        none."""
        out, self._counts_unspanned = (
            self._counts_unspanned, dict.fromkeys(self._counters, 0))
        return out

    def _window_attr(self) -> dict:
        """For a model with a window, what its window layers read in the
        step packed last: ``win_kv_tokens``, the rows a window leaves of
        every scheduled sequence's context (``min(context, window)``, once a
        sequence: beside ``kv_tokens``, which is what the full layers read),
        ``dec_win_kv_tokens`` the decode rows' part, ``win_attn_pairs`` the
        query x key pairs inside the window (``min(position + 1, window)`` a
        query, beside ``attn_pairs``), and the blocks either
        pool holds as the step is dispatched (``full_blocks_busy``,
        ``win_blocks_busy``: the sliding pool's saving is their ratio).
        Nothing for a model with none."""
        if not self._window:
            return {}
        win_all, win_dec, win_pairs = self._win_step
        return {"win_kv_tokens": win_all, "dec_win_kv_tokens": win_dec,
                "win_attn_pairs": win_pairs,
                "full_blocks_busy": self.allocator.busy_blocks,
                "win_blocks_busy": self.window_allocator.busy_blocks}

    def _tiles_attr(self, nt: int) -> dict:
        """``{"prefill_step_keys": n}`` for a step program with ``nt`` > 0
        tiles over a K/V pool whose tiles the Pallas kernel runs: the keys
        one grid step of it takes (``ops.attention.prefill_step_keys``, the
        rule the dispatcher itself goes by; a static of the program).
        Nothing for a program without tiles, a latent pool, the XLA path."""
        keys = self._tile_step_keys if nt else None
        return {} if keys is None else {"prefill_step_keys": keys}

    def _sel_decode_attr(self, width: int) -> dict:
        """``{"sel_decode": "walk" | "gather"}`` for a family that selects:
        how the decode rows of a step program whose block table is ``width``
        blocks wide read the pool, from the rule the model itself calls."""
        form = self.spec.sparse_decode_form
        return {} if form is None else {
            "sel_decode": form(width * self.cfg.block_size)}

    def _state_attr(self, n_dec: int, n_pre: int, n: int, nt: int,
                    nd: int) -> dict:
        """For a model with slot state, what of it a step moves:
        ``state_bytes``, the slot-state bytes the step must read and write
        (its decode rows and its distinct prefilling slots, a slot's state
        once each way), ``dec_state_bytes`` the decode rows' part,
        ``ssm_prefill_tokens``, the prompt tokens its tiles run through the
        recurrence, ``chunk_tiles``, the tiles the step program runs the
        recurrence's chunk form over, a layer (padding tiles too: a tile is
        the same work whatever it holds; ``kda_chunk``'s grid is heads x
        these), ``chunk_slots``, the distinct prefilling slots of the step:
        the states the chunk form must read and write once each (the prefill
        part of ``state_bytes`` over a slot's bytes; not for ``"mamba1"``,
        which has no chunk form, and neither of the two for ``"shortconv"``,
        whose state is a convolution's carried rows alone: no matrix, no
        chunk form, no scan), ``state_pad_rows``, the rows of the
        program's decode bucket (``nd``) past its ``n_dec`` real ones (they
        name the scratch slot and the decode kernel moves its state like any
        row's), ``slot_resets``,
        the slots the step starts from zeros (the sequences whose first tile
        it carries: the arrivals since the last dispatch, and a preempted
        request run again), and ``state_kind``, the recurrence's name where
        the model gives one (``ModelSpec.state_kind``: ``"mamba2"``,
        ``"kda"``, ``"mamba1"``, ``"shortconv"``), and for ``"mamba1"``
        ``scan_tiles``, the
        tiles the step program runs the selective scan over, a layer (no
        chunk form computes that recurrence: ``selscan_tile``'s grid is
        channel blocks x these). The dispatch feeds the same to
        ``inference_slot_state_bytes_total`` (``part`` ``decode`` /
        ``prefill``), ``inference_chunk_tiles_total`` (a family with a chunk
        form), ``inference_slot_state_pad_rows_total`` and
        ``inference_slot_resets_total``. Nothing for a model with none."""
        if not self._slot_state:
            return {}
        per_slot = 2 * self._slot_bytes
        kind = self.spec.state_kind
        if kind == "shortconv":
            tiles = {}
        elif kind == "mamba1":
            tiles = {"chunk_tiles": nt, "scan_tiles": nt}
        else:
            tiles = {"chunk_tiles": nt, "chunk_slots": n_pre}
        return {"state_bytes": (n_dec + n_pre) * per_slot,
                "dec_state_bytes": n_dec * per_slot,
                "ssm_prefill_tokens": n - n_dec,
                "state_pad_rows": nd - n_dec,
                "slot_resets": self._slot_resets,
                **tiles,
                **({} if kind is None else {"state_kind": kind})}

    def _count_selecting(self, sel: dict, pos0: int, take: int) -> None:
        """For a family that selects by blocks (``ModelSpec.index_blocks``),
        add a run of ``take`` queries from ``pos0`` (a decode row, a tile) to
        ``sel``: ``sel_queries``, those of them past the dense length, and
        ``cmp_kv_tokens``, the compressed keys any implementation must read
        to score them (those its last query sees, once a row or tile)."""
        rule = self._sel_rule
        if rule is None:
            return
        n = int(rule.selects(np.arange(pos0, pos0 + take)).sum())
        sel["sel_queries"] += n
        if n:
            sel["cmp_kv_tokens"] += int(rule.compressed(pos0 + take - 1))

    def _step_program_name(self, t: int, nd: int, nt: int) -> str:
        """The name a device-resident step program goes by in a trace: its
        decode-row bucket and tile count (untiled: its token bucket)."""
        if self._use_tiles:
            return f"ragged_step_d{nd}_t{nt}"
        return f"ragged_step_n{t}"

    def _pack_step(self, host_feed: bool):
        """The one place that decides which rows a step carries, for the
        device step and the host-staged step alike: decode rows first, then
        prefill tiles or chunks (a partial chunk under pool pressure), the
        rows that emit, the bucket; allocates their blocks and packs the
        planes. A decode row's token and position come from the device's
        slot rows (flag 3) or, with ``host_feed``, from host state here.
        Returns None when nothing is schedulable."""
        cfg = self.cfg
        ct = cfg.prefill_tile if self._use_tiles else 0
        budget = cfg.max_tokens_per_step
        trace_on = self._tracer.enabled
        tpairs = [] if trace_on else None
        kv_dec = kv_pre = pairs_pre = sched = 0
        win_dec = win_pre = win_pairs = 0   # the same, cut to the window
        window = self._window
        topk = self._topk
        rule = self._sel_rule
        sel = dict.fromkeys(
            ("sel_pairs", "sel_kv_tokens", "dec_sel_kv_tokens")
            + (("cmp_kv_tokens", "sel_queries") if rule else ()), 0) \
            if topk else {}
        size = budget + ct
        tokens = np.zeros(size, np.int32)
        slots = np.full(size, cfg.max_seqs, np.int32)
        positions = np.zeros(size, np.int32)
        flags = np.zeros(size, np.int32)
        emit: list[tuple[int, _SeqState]] = []
        max_pos = 0
        per = self._rows_per_decode
        blk = {"seqs": [], "commit": 0, "unmasked": 0} \
            if self._blk is not None else None
        dec_cap = min(budget // per, cfg.max_seqs) if ct else budget
        n_dec = 0
        for seq in list(self._running.values()):
            if seq.finished or not seq.in_decode or n_dec >= dec_cap:
                continue
            # the feed at limit-1 yields the final budgeted token; one
            # position past it is scheduled too (its token is discarded at
            # reconcile)
            lim = len(seq.prompt) + seq.max_new_tokens
            if seq.pos >= lim:
                continue  # fully scheduled; retires as pending reconciles
            if blk is not None:
                # a block of ``per`` rows at seq.pos .. seq.pos + per - 1; the
                # last block a request needs is denoised and never committed
                if not seq.blk_masked and seq.pos + per >= lim:
                    continue
                if not self._ensure_capacity(seq, seq.pos + per):
                    seq.preemptions += 1
                    self.preemptions += 1
                    continue
                slots[n_dec * per:(n_dec + 1) * per] = seq.slot
                flags[n_dec * per:(n_dec + 1) * per] = 1   # fed by the device
                if trace_on:
                    tpairs.append((seq, "engine/decode", per))
                max_pos = max(max_pos, seq.pos + per - 1)
                kv_dec += seq.pos + per
                self._schedule_block_pass(seq, n_dec * per, emit, blk)
                n_dec += 1
                continue
            if not self._ensure_capacity(seq, seq.pos + 1):
                seq.preemptions += 1
                self.preemptions += 1
                continue
            slots[n_dec] = seq.slot
            if host_feed:
                tokens[n_dec] = seq.token_at(seq.pos)
                positions[n_dec] = seq.pos
                flags[n_dec] = 2  # emit
            else:
                flags[n_dec] = 3  # feed token+position from device state | emit
            emit.append((n_dec, seq))
            if trace_on:
                tpairs.append((seq, "engine/decode", 1))
            max_pos = max(max_pos, seq.pos)
            seq.pos += 1
            kv_dec += seq.pos
            win_dec += min(seq.pos, window)
            n_dec += 1
            if topk:            # a decode row: one query, its kept rows
                kept = _kept_keys(seq.pos - 1, topk, rule)
                for key in ("sel_pairs", "sel_kv_tokens", "dec_sel_kv_tokens"):
                    sel[key] += kept
                self._count_selecting(sel, seq.pos - 1, 1)

        ts = tpz = tv = None
        if ct:
            nd = 0 if n_dec == 0 else next(b for b in self._dec_buckets
                                           if b >= n_dec)
            chunks, nt = self._plan_prefill_tiles(nd * per, budget)
            ts = np.full(max(nt, 1), cfg.max_seqs, np.int32)
            tpz = np.zeros(max(nt, 1), np.int32)
            tv = np.zeros(max(nt, 1), np.int32)
            # read by ``_state_attr`` for a model with slot state, which
            # always packs tiles (``prefill_tile=0`` is refused beside slot
            # leaves and the ladder of degraded modes keeps its tiles)
            self._slot_resets = sum(seq.pos == 0 for seq, _, _ in chunks)
            for seq, tile0, take in chunks:
                start = nd * per + tile0 * ct
                sl = slice(start, start + take)
                tokens[sl] = seq.tokens_at(seq.pos, seq.pos + take)
                slots[sl] = seq.slot
                positions[sl] = np.arange(seq.pos, seq.pos + take,
                                          dtype=np.int32)
                for ti in range(-(-take // ct)):
                    ts[tile0 + ti] = seq.slot
                    tpz[tile0 + ti] = seq.pos + ti * ct
                    tv[tile0 + ti] = min(ct, take - ti * ct)
                max_pos = max(max_pos, seq.pos + take - 1)
                # a query sees its position's keys; by blocks, its block's
                pairs_pre += take * seq.pos + take * (take + per) // 2
                win_pairs += _kept_pairs(seq.pos, take, window)
                if topk:
                    sel["sel_pairs"] += _kept_pairs(seq.pos, take, topk, rule)
                    # a tile must read what its last query keeps at least
                    sel["sel_kv_tokens"] += sum(
                        _kept_keys(seq.pos + min(i + ct, take) - 1, topk, rule)
                        for i in range(0, take, ct))
                    for i in range(0, take, ct):
                        self._count_selecting(sel, seq.pos + i,
                                              min(ct, take - i))
                seq.pos += take
                kv_pre += seq.pos
                win_pre += min(seq.pos, window)
                sched += take
                if trace_on:
                    tpairs.append((seq, "engine/prefill", take))
                if seq.pos == len(seq.prompt) and seq.emits_at_prompt_end:
                    flags[start + take - 1] |= 2
                    emit.append((start + take - 1, seq))
            n = n_dec * per + sched
            t_total = nd * per + nt * ct
        else:
            nd = nt = 0
            n = n_dec
            for seq in list(self._running.values()):
                if seq.finished or seq.in_decode or n >= budget:
                    continue
                take = min(budget - n, seq.prefill_end - seq.pos)
                while take and not self._ensure_capacity(seq, seq.pos + take):
                    take -= 1  # partial chunk under pool pressure
                if take <= 0:
                    continue
                sl = slice(n, n + take)
                tokens[sl] = seq.tokens_at(seq.pos, seq.pos + take)
                slots[sl] = seq.slot
                positions[sl] = np.arange(seq.pos, seq.pos + take,
                                          dtype=np.int32)
                max_pos = max(max_pos, seq.pos + take - 1)
                pairs_pre += take * seq.pos + take * (take + 1) // 2
                win_pairs += _kept_pairs(seq.pos, take, window)
                if topk:
                    sel["sel_pairs"] += _kept_pairs(seq.pos, take, topk, rule)
                    sel["sel_kv_tokens"] += _kept_keys(seq.pos + take - 1,
                                                       topk, rule)
                    self._count_selecting(sel, seq.pos, take)
                seq.pos += take
                kv_pre += seq.pos
                win_pre += min(seq.pos, window)
                n += take
                if trace_on:
                    tpairs.append((seq, "engine/prefill", take))
                if seq.pos == len(seq.prompt) and seq.emits_at_prompt_end:
                    flags[n - 1] |= 2
                    emit.append((n - 1, seq))
            t_total = 0 if n == 0 else next(b for b in self._buckets
                                            if b >= n)
        if n == 0:
            return None
        self.tokens_scheduled += n
        self.tokens_padded += t_total - n
        self._note_pool_rows(n, sched)
        self._win_step = (win_dec + win_pre, win_dec, win_dec + win_pairs)
        parts = [tokens[:t_total], slots[:t_total], positions[:t_total],
                 flags[:t_total]]
        if ct:
            parts += [ts, tpz, tv]
        return (parts, emit, tpairs, t_total, n, nd, nt, max_pos, kv_dec,
                kv_pre, pairs_pre, n_dec, len(chunks) if ct else 0, sel, blk)

    def _note_pool_rows(self, n: int, tile_rows: int) -> None:
        """Of the ``n`` real rows of the step being packed, ``tile_rows`` lie
        in prefill tiles: the pool's write site takes those as slices where a
        tile is whole runs of a block's rows (``models/paged.py``,
        ``tiles_go_as_slices``: the rule it goes by itself; a quantized pool
        keeps single rows) and every other row as a single row. Kept for the
        dispatch span (``pool_slice_rows``) and counted."""
        sliced = tile_rows if self._kvq is None and tiles_go_as_slices(
            self.cfg.prefill_tile, self.cfg.block_size) else 0
        self._pool_slice_rows = sliced
        if self.telemetry.enabled:
            written = self.telemetry.counter(
                "inference_pool_rows_written_total",
                "rows the steps wrote into the paged pool, by the form the "
                "write site took them in (slice: a prefill tile's rows, whole "
                "blocks at a time; row: a scatter of single rows)")
            written.inc(sliced, form="slice")
            written.inc(n - sliced, form="row")

    def _schedule_block_pass(self, seq: _SeqState, row0: int, emit: list,
                             blk: dict) -> None:
        """The host's mirror of what the step program does to ``seq``'s
        block in the pass being packed (``_build_blk_step``): with a static
        schedule the host knows, without reading anything back, how many
        positions the pass unmasks, whether it is the block's last denoise
        pass (its readback then carries the finished block: the rows of the
        positions that were not known before are the step's emit rows) and
        whether it is the commit (``pos`` moves on, the slot holds a new
        block, all masked)."""
        per = self._blk.length
        blk["seqs"].append(seq)
        if seq.cost is not None:
            seq.cost.decode_dispatches += 1
        if seq.blk_masked:
            n = min(self._blk.unmask, seq.blk_masked)
            seq.blk_masked -= n
            seq.blk_passes[0] += 1
            blk["unmasked"] += n
            if not seq.blk_masked:
                emit.extend((row0 + i, seq) for i in range(seq.blk_known, per))
        else:
            seq.blk_passes[1] += 1
            blk["commit"] += 1
            seq.pos += per
            seq.blk_masked, seq.blk_known = per, 0

    def _reconcile_pending(self) -> dict:
        """Read back the OLDEST pending dispatch's tokens and fold them
        into host state (a sequence past its EOS token or ``max_new_tokens``
        takes no more: ``_append_token``; release is deferred until a
        sequence's last pending reference drains)."""
        if self._faults.enabled:
            self._faults.fire(POINT_READBACK)
        rec = self._pending.pop(0)
        t0 = time.perf_counter()
        out: dict = {}
        with span("engine/readback"):
            picked = np.asarray(rec["picked"])
        t1 = time.perf_counter()
        self.readback_ns += int((t1 - t0) * 1e9)
        if self._tracer.enabled:
            self._trace_spans(t0, t1, [(s, "engine/readback", 1)
                                       for _, s in rec["emit"]])
        for row, seq in rec["emit"]:
            self._append_token(seq, int(picked[row]), out)
        if self._counters:
            self._note_step_counts(picked[-len(self._counters):])
        for seq in rec["participants"]:
            seq.refs -= 1
            if seq.finished and seq.refs == 0 and seq.slot >= 0:
                self._release(seq)
        return out

    def _note_step_counts(self, counts) -> None:
        """Fold one step's counter sums (the tail of its readback) into the
        totals, the next dispatch span's arguments and ``/metrics``."""
        for name, value in zip(self._counters, counts):
            value = int(value)
            self.step_counts[name] += value
            self._counts_unspanned[name] += value
            if self.telemetry.enabled:
                self.telemetry.counter(
                    f"inference_{name}_total",
                    "summed over the device-resident steps' real rows, by "
                    "the step programs themselves").inc(value)

    def _step_device(self) -> dict:
        """One device-resident turn: admit, dispatch one step if anything is
        schedulable, then reconcile the oldest pending dispatch once the
        window holds two — so the blocking ``np.asarray`` readback of step t
        overlaps the device executing step t+1."""
        with span("engine/schedule"):
            self._admit_queued()
        dispatched = self._dispatch_step_device()
        if self._pending and (not dispatched or len(self._pending) >= 2):
            return self._reconcile_pending()
        if not dispatched and not self._pending and (
                self._queued or self._running):
            self._deadlock_guard(0)
        return {}

    def _table_view(self, max_pos: int):
        """Slice the block table to the bucketed block count covering
        ``max_pos`` (the highest position any token in this dispatch will
        touch). The tiled prefill kernel grids its KV loop over the TABLE
        WIDTH (a grid step for every entry, the ones past a tile's context
        predicated off), and the XLA gather of a quantized pool or the CPU
        gathers the whole width; the decode kernel walks each row's own
        context whatever the width.

        Tables of 64 blocks and fewer pass through whole: every distinct
        width is a fresh program shape to compile and warm. Power-of-4
        buckets keep the long-context compile count tiny."""
        width = self._table_width(max_pos)
        if self._window:  # (full, sliding): ``_h2d`` ships both
            return (self.block_tables[:, :width],
                    self.window_tables[:, :width])
        return self.block_tables[:, :width]

    def _table_width(self, max_pos: int) -> int:
        """Bucketed block-table width covering ``max_pos`` (the shared
        bucketing behind ``_table_view``; the device-resident path keeps the
        full table on device and bakes this width into the program as a
        static, so the prefill kernel's grid is bounded without any per-step
        upload)."""
        mb = self.cfg.max_blocks_per_seq
        if mb <= 64:
            return mb
        need = max_pos // self.cfg.block_size + 1
        b = 16
        while b < need:
            b *= 4
        return min(b, mb)

    def _plan_prefill_tiles(self, nd: int, budget: int):
        """Pick tile-aligned prompt chunks for this step (the tile-capacity
        walk, the capacity backoff under pool pressure, and the power-of-2
        tile rounding with its non-power-of-2 cap fixup).

        Returns ``(chunks, nt)``: ``chunks`` is ``[(seq, tile0, take)]``
        with ``tile0`` the chunk's first tile index relative to the tile
        region; ``nt`` the padded tile count. Does NOT advance ``seq.pos`` —
        callers fill their token arrays from the current pos, then advance.
        """
        ct = self.cfg.prefill_tile
        ntiles_cap = max(0, (budget - nd) // ct)
        tiles_used = 0
        chunks: list[tuple[_SeqState, int, int]] = []
        for seq in list(self._running.values()):
            if seq.finished or seq.in_decode or tiles_used >= ntiles_cap:
                continue
            avail = (ntiles_cap - tiles_used) * ct
            take = min(avail, seq.prefill_end - seq.pos)
            while take and not self._ensure_capacity(seq, seq.pos + take):
                take -= 1  # partial chunk under pool pressure
            if take <= 0:
                continue
            chunks.append((seq, tiles_used, take))
            tiles_used += -(-take // ct)
        if tiles_used == 0:
            return chunks, 0
        nt = 1
        while nt < tiles_used:
            nt *= 2
        nt = min(nt, max(1, ntiles_cap))
        if nt < tiles_used:  # cap can be non-power-of-2
            nt = tiles_used
        return chunks, nt

    def _width_ladder(self) -> list[int]:
        """Block-table widths ``_table_width`` can actually dispatch (jit
        caches are shape-keyed; warming the wrong width warms nothing)."""
        mb = self.cfg.max_blocks_per_seq
        if mb <= 64:
            return [mb]
        widths, b = [], 16
        while b < mb:
            widths.append(b)
            b *= 4
        widths.append(mb)
        return widths

    def warmup(self, sampled: bool = False, has_tk: bool = False,
               has_tp: bool = False) -> int:
        """Turn the persistent compilation cache on
        (``utils/compile_cache.py``). The step programs are NOT compiled here
        (a server runs each once at set-up; they reach later processes
        through the cache), so the arguments choose nothing and the count of
        programs compiled, which is returned, is 0. What is set here is the
        cold-cache probe's counter, the compile watch's
        (``telemetry/compile_watch.py``: builds whose executable jax wrote to
        the cache): if the FIRST step program a server then runs misses the
        cache and is written to it, none of them was in it, and the
        engine compiles the rest in the background while the server goes on
        (``_precompile_zoo_in_background``); a warm cache sees no change."""
        from deepspeed_tpu.telemetry.compile_watch import WATCH
        from deepspeed_tpu.utils.compile_cache import enable_compile_cache

        with phase("engine/warmup"):
            if enable_compile_cache() and self._cache_misses is None:
                self._cache_misses = WATCH.cache_writes
        # program-cache fills before this are not serve-time misses: reset
        # the dispatch baseline so warmup_coverage reflects live traffic only
        self._warmed = True
        self.program_dispatches = 0
        self.program_cold_dispatches = 0
        return 0

    def _append_token(self, seq: _SeqState, tok: int, out: dict) -> None:
        """Fold one read-back token of the device step into its sequence
        (the host-staged step's emission is ``_emit_tokens``)."""
        if seq.finished:
            return  # the row of a step dispatched past the sequence's end
        if seq.cost is not None:
            # by blocks a pass is charged where it is scheduled
            seq.cost.decode_dispatches += not seq.blk
            seq.cost.decode_tokens += 1
        seq.generated.append(tok)
        out[seq.uid] = tok
        self.tokens_emitted += 1
        if self.telemetry.enabled:
            self._stamp_emission(seq, time.perf_counter())

    def drain(self) -> dict:
        """Reconcile every pending dispatch (a flush point for callers that
        need host-complete state)."""
        out: dict = {}
        while self._pending:
            out.update(self._reconcile_pending())
        return out

    def _admit_queued(self) -> None:
        """Pass 2: admit queued requests while slots remain (their prompt
        chunks are scheduled by pass 3); admission reserves the request's
        worst-case block count so admitted work always finishes.

        With the prefix cache on, admission first splices the longest cached
        full-block prefix into the sequence's block table (refcounts bumped
        via ``acquire``) and reserves only the REMAINDER — a hit both skips
        prefill compute and shrinks the reservation, raising effective
        capacity. ``seq.pos`` starts past the cached region, so the tail
        prefill (always >= 1 token, see ``_match_prefix``) produces the
        first token exactly as a cold prompt's final chunk would."""
        use_cache = self.cfg.enable_prefix_cache
        headroom = -1
        self._headroom_wait = False
        if self._queued:
            # measured free-byte headroom (net of the pool's preallocated
            # footprint — pool-funded blocks are never gated) rides
            # alongside the static block count; -1 (unknown backend or
            # knob off) keeps the static path bit-identical. The prefix
            # LRU sheds under pool pressure first so retention never
            # starves admission's reservations.
            headroom = self.admission_headroom_blocks()
            if headroom >= 0:
                self._enforce_retained_budget()
        cm = self.telemetry.costmeter
        if cm is not None and self._queued:
            # advance the occupancy integral before any splice moves blocks
            # between the retained carveout and a live sequence
            self._cost_tick()
        while self._queued and self._free_slots:
            qidx = 0
            if cm is not None and len(self._queued) > 1:
                # fair-share admission: prefer the first queued request
                # whose tenant is at/under its fair share of live blocks.
                # With one tenant (or one queued request) the pick is index
                # 0 — byte-identical FIFO admission order.
                qidx = self._cost_fair_index(cm)
            seq = self._queued[qidx]
            t_adm0 = time.perf_counter() if seq.trace is not None else 0.0
            worst = self._worst_case_blocks(seq)
            if headroom >= 0 and worst > headroom:
                # even counting the pool's own allocatable blocks the
                # device can't fund the worst case: external HBM pressure.
                # Wait for it to lift (flagged so the deadlock guard knows
                # this stall is externally resolvable, not a livelock —
                # and starts the stall-duration alarm clock)
                self._headroom_wait = True
                break
            if use_cache and self._kvtier is not None:
                # tiered restore first (prefetch resolution + cost-model
                # promotion): _match_prefix below then finds promoted links
                # in the ordinary HBM index, so the splice — and the tokens
                # — are identical to blocks that never left HBM
                self._tier_admit(seq)
            hit: list[int] = self._match_prefix(seq.prompt) if use_cache else []
            if hit:
                # take the references first: free_blocks counts refcount-0
                # cached blocks as allocatable, so the remainder check below
                # must see them already claimed
                self.allocator.acquire(hit)
                worst -= len(hit)
            win_cap = self._sliding_blocks_cap(seq)
            if win_cap and win_cap > self.window_allocator.free_blocks \
                    - self._win_reserved:
                break  # the sliding pool is short: retry as windows slide
            if worst > self.allocator.free_blocks - self._reserved:
                if hit:
                    # deref back; published blocks re-enter the LRU (at the
                    # MRU end — they were just asked for)
                    self.allocator.free(hit)
                break  # pool pressure: retry admission as blocks free up
            self._queued.pop(qidx)
            if seq.expected_cached and len(hit) * self.cfg.block_size \
                    < seq.expected_cached:
                # the placement-time cached_prefix_tokens probe promised more
                # splice than admission found (LRU eviction in between):
                # proceed as a cold/shorter prefill — the re-match above IS
                # the re-validation — and make the over-credit observable
                self.prefix_stale_probes += 1
                if self.telemetry.enabled:
                    self.telemetry.counter(
                        "prefix_probe_stale_total",
                        "admissions whose placement-time prefix probe "
                        "over-credited cached_tokens",
                    ).inc()
            seq.slot = self._free_slots.pop()
            seq.reserved_remaining = worst
            self._reserved += worst
            if win_cap:
                seq.win_cap = win_cap
                self._sliding_rereserve(seq)
            if headroom >= 0:
                # this admission will draw from the pool; clamp at 0 so the
                # cap stays armed for the rest of the pass
                headroom = max(0, headroom - worst)
            if hit:
                seq.blocks = list(hit)
                seq.cached_prefix = len(hit) * self.cfg.block_size
                seq.pos = seq.cached_prefix
                self.block_tables[seq.slot, :len(hit)] = hit
                self._bt_dirty.add(seq.slot)
            if seq.cost is not None:
                # prefill is charged at admission: tokens the device will
                # actually prefill (splice-skipped prefix excluded) times the
                # analytic per-token forward FLOPs
                n_pref = max(0, len(seq.prompt) - seq.pos)
                seq.cost.prefill_tokens += n_pref
                seq.cost.prefill_flops += n_pref * self._flops_per_token_value()
                if hit:
                    # cross-tenant prefix reuse: debit the consumer, credit
                    # each publishing tenant block-for-block
                    transfers: dict[str, int] = {}
                    for b in hit:
                        pub = self._block_tenant.get(b)
                        if pub is not None and pub != seq.tenant:
                            transfers[pub] = transfers.get(pub, 0) + 1
                    # the transfer lands straight in the ledger (the
                    # publisher's request is usually long gone); the
                    # consumer's RequestCost must NOT also carry the debit
                    # or finalize would double-fold it
                    for pub, nblk in transfers.items():
                        cm.prefix_transfer(pub, seq.tenant, nblk)
            self._running[seq.slot] = seq
            if self.cfg.device_state:
                with span("engine/stage"):
                    self._write_slot_row(seq)
            if use_cache:
                tel = self.telemetry
                if hit:
                    self.prefix_hits += 1
                    self.prefix_tokens_reused += seq.cached_prefix
                    if tel.enabled:
                        tel.counter("prefix_cache_hits_total",
                                    "admissions with a cached prefix").inc()
                        tel.counter(
                            "prefix_tokens_reused_total",
                            "prompt tokens served from cached KV blocks",
                        ).inc(seq.cached_prefix)
                else:
                    self.prefix_misses += 1
                    if tel.enabled:
                        tel.counter("prefix_cache_misses_total",
                                    "admissions with no cached prefix").inc()
            if self.telemetry.enabled:
                seq.t_admit = time.perf_counter()
                if seq.trace is not None:
                    tr = self._tracer
                    # queue wait (enqueue -> admission pickup) and the
                    # admission work itself (prefix match + splice +
                    # reservation), both children of the request span
                    if seq.t_enqueue:
                        tr.record(seq.trace, "request/queue",
                                  seq.t_enqueue, t_adm0)
                    tr.record(seq.trace, "request/admission",
                              t_adm0, seq.t_admit, slot=seq.slot,
                              blocks_reserved=seq.reserved_remaining,
                              cached_prefix_tokens=seq.cached_prefix or None)
        if not self._headroom_wait:
            # pass ended unpinned (admitted, empty queue, or plain pool
            # pressure): the stall-duration alarm clock rearms
            self._headroom_stall_ticks = 0

    def _emit_tokens(self, logits, emit) -> dict:
        """Shared step epilogue: pick at the emit indices (greedy, or the
        request's sampling config), extend the sequences, release finished
        ones."""
        out: dict = {}
        if emit:
            if self._faults.enabled:
                self._faults.fire(POINT_READBACK)
            t0 = time.perf_counter()
            idx = np.asarray([i for i, _ in emit])
            if any(seq.temperature > 0.0 for _, seq in emit):
                # jitted (cached per active-filter set; specializes per emit
                # count): eager sampling here would be ~a dozen separate
                # dispatches on a path whose whole cost model is dispatch
                # count, and unconditional top-k/top-p would sort the vocab
                # twice per step even for plain-temperature requests
                tk = np.asarray([s.top_k for _, s in emit], np.int32)
                tp = np.asarray([s.top_p for _, s in emit], np.float32)
                fkey = (bool(tk.any()), bool((tp < 1.0).any()))
                if not hasattr(self, "_sample_jits"):
                    self._sample_jits = {}
                skey = ("sample", fkey, len(emit))
                self._note_program("sample", skey not in self._step_keys)
                self._step_keys.add(skey)
                if fkey not in self._sample_jits:
                    from deepspeed_tpu.inference.sampling import (
                        per_request_keys, sample_tokens)

                    has_tk, has_tp = fkey
                    self._sample_jits[fkey] = jax.jit(
                        lambda lg, root, seeds, gidx, t, tk, tp: sample_tokens(
                            lg, per_request_keys(root, seeds, gidx), t,
                            top_k=tk if has_tk else None,
                            top_p=tp if has_tp else None)[0])
                picked = np.asarray(self._sample_jits[fkey](
                    logits[idx], self._sample_root,
                    np.asarray([s.seed for _, s in emit], np.int32),
                    np.asarray([len(s.generated) for _, s in emit], np.int32),
                    np.asarray([s.temperature for _, s in emit], np.float32),
                    tk, tp))
            else:
                picked = np.asarray(
                    jnp.argmax(logits[idx].astype(jnp.float32), axis=-1))
            t1 = time.perf_counter()
            self.readback_ns += int((t1 - t0) * 1e9)
            if self._tracer.enabled:
                self._trace_spans(t0, t1, [(s, "engine/readback", 1)
                                           for _, s in emit])
            now = time.perf_counter() if self.telemetry.enabled else 0.0
            for (_, seq), tok in zip(emit, picked):
                seq.generated.append(int(tok))
                out[seq.uid] = int(tok)
                self.tokens_emitted += 1
                if seq.cost is not None:
                    seq.cost.decode_tokens += 1
                    seq.cost.decode_dispatches += 1
                if now:
                    self._stamp_emission(seq, now)
                if seq.finished:
                    self._release(seq)
        return out

    def _deadlock_guard(self, n: int) -> None:
        if n > 0:
            self._headroom_stall_ticks = 0
            return
        if n == 0:
            if self._headroom_wait:
                # not a livelock: admission is pinned by measured device
                # headroom, which another owner freeing bytes can lift —
                # idle this tick instead of declaring deadlock. But a wait
                # that never lifts must not become a silent forever-hang:
                # after headroom_stall_alarm_ticks consecutive idle ticks
                # the stall alarm raises with the measured picture.
                self._headroom_stall_ticks += 1
                if self.telemetry.enabled:
                    self.telemetry.counter(
                        "kv_headroom_stalls_total",
                        "scheduler ticks idled because measured free-byte "
                        "headroom cannot fund any queued admission").inc()
                alarm = self.cfg.headroom_stall_alarm_ticks
                if alarm and self._headroom_stall_ticks >= alarm:
                    stats = self._device_memory_stats()
                    raise RuntimeError(
                        "headroom admission stalled: measured free-byte "
                        f"headroom funded no admission for {alarm} "
                        "consecutive scheduler ticks "
                        f"(queued={len(self._queued)} "
                        f"free_blocks={self.allocator.free_blocks} "
                        f"bytes_in_use={stats.get('bytes_in_use')} "
                        f"bytes_limit={stats.get('bytes_limit')}); another "
                        "HBM owner is pinning the device — free the "
                        "external allocation or disable headroom_admission"
                    )
                return
            # has_work but nothing schedulable: every sequence is stalled on
            # KV-pool capacity and nothing can ever free a block — a silent
            # livelock without this guard. (The reference avoids this state
            # with conservative admission; we surface it instead.)
            raise RuntimeError(
                "KV pool deadlock: all sequences stalled waiting for blocks "
                f"({self.allocator.free_blocks} free of "
                f"{self.cfg.num_blocks - 1} usable); enlarge num_blocks or "
                "lower max_seqs/max_new_tokens"
            )

    # ------------------------------------------------- dispatch watchdog
    def _recover_device_path(self) -> None:
        """Re-anchor the engine on host ground truth after a failed step:
        discard ALL unread steps (the pending readbacks — partially
        draining them could interleave token order) and rewind every running
        sequence's schedule position to what its host-visible ``generated``
        list proves was delivered. Re-running
        the discarded positions rewrites identical KV and — because token
        ``g`` of a request samples from a key derived only from (seed, g) —
        re-picks identical tokens, so recovery is invisible in the output
        stream. Injected faults fire BEFORE a jitted call consumes its
        donated buffers, and a real mid-execution failure raises out of the
        dispatch before the host bindings are swapped, so cache/state
        references here are the pre-dispatch values."""
        self._pending.clear()
        self._staging_cache.clear()
        for seq in self._running.values():
            seq.refs = 0
            g = len(seq.generated)
            if g:
                # decode invariant: feeding token_at(pos) at position pos
                # produces generated index pos - len(prompt) + 1
                seq.pos = len(seq.prompt) + g - 1
            elif seq.pos >= len(seq.prompt):
                # prompt fully scheduled but its first token never landed:
                # re-run the final prompt position (>= cached_prefix, so
                # shared prefix blocks are never rewritten)
                seq.pos = len(seq.prompt) - 1
            else:
                # mid-prefill: re-prefill the uncached tail (idempotent)
                seq.pos = seq.cached_prefix
            if self._blk is not None:
                # a block in flight is dropped and denoised again (the same
                # result at temperature 0): everything the host holds, whole
                # blocks of it, is run again as prefill under the block's
                # mask, and the block after it starts over
                known = len(seq.prompt) + g
                seq.replay = known - known % seq.blk if g else 0
                seq.pos = 0
            if self._slot_state or self._window:
                # re-running a position rewrites identical K and V but would
                # move a recurrent state a second time, and there is no
                # rollback: the sequence starts again from an empty state
                # and runs everything before its resume point as prefill.
                # A model with a window likewise: the rows a rewound query
                # reads may lie in sliding blocks the slide has taken back,
                # so its sliding blocks go and are written again from 0
                seq.replay = seq.pos if g else 0
                seq.pos = 0
            if self._window:
                self._drop_sliding_blocks(seq, seq.win_cap)
        # device mirrors are stale by construction now: rebuild the block
        # table wholesale and re-seed the slot rows from host truth
        self._bt_dirty.clear()
        self._bt_dev = jnp.asarray(self.block_tables)
        if self._window:
            self._bt_win_dirty.clear()
            self._bt_win_dev = jnp.asarray(self.window_tables)
        if self.cfg.device_state:
            for seq in self._running.values():
                self._write_slot_row(seq)
        # sequences whose release was deferred on in-flight refs would
        # otherwise never retire (every scheduler loop skips finished seqs)
        for seq in list(self._running.values()):
            if seq.finished:
                self._release(seq)

    def _maybe_degrade(self, exc: Exception) -> bool:
        """Walk one rung down the degradation ladder once failures repeat:
        full device-resident path -> host-staged step (``device_state``
        off) -> the same with prefill tiles off (the plain SplitFuse step,
        no tiled kernel). Returns True when a rung was taken;
        every rung is token-identical (pinned by the mode-parity tests), so
        degradation costs dispatch efficiency, never output."""
        cfg = self.cfg
        if not cfg.degrade_after or self._consec_failures < cfg.degrade_after:
            return False
        reason = f"{type(exc).__name__}: {exc}"
        if self._blk is not None:
            # the host-staged step does not run blocks (``_BY_BLOCKS``)
            log_dist(f"ragged watchdog: model {self.spec.name} generates by "
                     "blocks, which only the device step runs; not degrading "
                     f"({reason})", ranks=[0])
            return False
        if cfg.device_state:
            cfg.device_state = False
            self.degraded_mode = 1
            rung = "host-staged fallback (device_state off)"
        elif self._use_tiles and not self._slot_state:
            cfg.prefill_tile = 0
            self._use_tiles = False
            self.degraded_mode = 2
            rung = "plain-step fallback (prefill tiles off)"
        else:
            return False  # already at the bottom rung
        self.degraded_reason = reason
        self._consec_failures = 0
        log_dist(
            f"ragged watchdog: degrading to {rung} after repeated "
            f"device-path failures ({reason})", ranks=[0])
        tel = self.telemetry
        if tel.enabled:
            tel.gauge(
                "degraded_mode",
                "0 full | 1 host-staged fallback | 2 plain-step fallback",
            ).set(self.degraded_mode)
            tel.event("inference/degraded", mode=self.degraded_mode,
                      reason=reason)
        return True

    def _backoff(self, attempt: int) -> None:
        cfg = self.cfg
        base = min(RETRY_BACKOFF_MAX_S,
                   cfg.retry_backoff_s * (2 ** (attempt - 1)))
        time.sleep(base * (1.0 + RETRY_JITTER * self._retry_rng.random()))

    def _step_watched(self) -> dict:
        """Run ``_step_impl`` under the dispatch watchdog: transient
        failures (see ``faults.classify_transient``) recover host state and
        retry in place with exponential backoff + jitter; repeated failure
        walks the degradation ladder (each rung resets the retry budget);
        fatal errors and an exhausted budget escalate to the caller (the
        engine loop's crash containment)."""
        cfg = self.cfg
        attempts = 0
        while True:
            t0 = time.perf_counter()
            try:
                out = self._step_impl()
            except Exception as e:
                oom = is_resource_exhausted(e)
                if not oom and not classify_transient(e):
                    raise
                attempts += 1
                self.step_failures += 1
                self._consec_failures += 1
                if self.telemetry.enabled:
                    self.telemetry.counter(
                        "dispatch_retries_total",
                        "transient step failures recovered by the "
                        "watchdog").inc(kind=type(e).__name__)
                log_dist(
                    f"ragged watchdog: transient step failure "
                    f"({type(e).__name__}: {e}); attempt {attempts}",
                    ranks=[0])
                if oom:
                    # OOM forensics: snapshot the ledger breakdown before
                    # any recovery mutates it, then hand the ladder a hint —
                    # retrying the exact same program into the exact same
                    # full device is pointless, shedding device-resident
                    # state is the move that frees bytes
                    self._note_oom("dispatch", e)
                    if cfg.degrade_after:
                        self._consec_failures = max(
                            self._consec_failures, cfg.degrade_after)
                self._recover_device_path()
                if self._maybe_degrade(e):
                    attempts = 0  # a fresh rung gets a fresh retry budget
                    continue
                if attempts > max(0, cfg.dispatch_retries):
                    raise
                self.step_retries += 1
                self._backoff(attempts)
                continue
            if cfg.step_deadline_s and \
                    time.perf_counter() - t0 > cfg.step_deadline_s:
                # the step completed but blew its wall-clock budget: the
                # work is kept, yet it counts toward degradation — a
                # limping device path should fall back before it stalls
                # the whole serving loop
                self._consec_failures += 1
                if self.telemetry.enabled:
                    self.telemetry.counter(
                        "dispatch_deadline_exceeded_total",
                        "steps exceeding cfg.step_deadline_s").inc()
                self._maybe_degrade(TimeoutError(
                    f"step exceeded deadline {cfg.step_deadline_s:g}s"))
            else:
                self._consec_failures = 0
            return out

    def reset_state(self) -> int:
        """Crash containment (serving/engine_loop.py): rebuild every piece
        of mutable engine state after a poisoned step — fresh KV cache and
        allocator, zeroed block tables and device mirrors — keeping params
        and all compiled programs. Every queued/running request is retired
        with ``status='error'`` (the loop surfaces structured errors for
        them); returns how many were failed."""
        failed = 0
        if self.telemetry.costmeter is not None:
            self._cost_tick()  # settle the occupancy integral's last slice
        for seq in (*self._queued, *self._running.values()):
            seq.status = "error"
            seq.blocks = []
            seq.reserved_remaining = 0
            seq.win_blocks, seq.win_cap, seq.win_reserved = {}, 0, 0
            seq.refs = 0
            seq.slot = -1
            self._results[seq.uid] = seq
            failed += 1
            if self.telemetry.enabled:
                self._emit_request_span(seq)
            self._finalize_cost(seq)
        for seq in self._handoffs.values():
            seq.status = "error"
            seq.blocks = []
            seq.slot = -1
            self._results[seq.uid] = seq
            failed += 1
            self._finalize_cost(seq)
        self._handoffs.clear()
        self._queued = []
        self._running = {}
        self._pending.clear()
        self._staging_cache.clear()
        self._kvq_blocks_allocated += self.allocator.allocated_total
        self.allocator = BlockedAllocator(self.cfg.num_blocks)
        if self._kvtier is not None:
            # the tier store SURVIVES reset: its records are keyed by exact
            # token chains, valid for any allocator generation of the same
            # params — demoted prefixes stay restorable after containment
            self.allocator.demote_hook = self._demote_hook()
        if self._prefix_listener is not None:
            # fresh allocator has no published keys: tell the cluster index
            # to forget this replica, then keep listening
            self.allocator.listener = self._prefix_listener
            self._prefix_listener.on_reset()
        self.block_tables[:] = 0
        self._bt_dirty.clear()
        self._bt_dev = jnp.asarray(self.block_tables)
        if self._window:
            self.window_allocator = BlockedAllocator(
                self.window_allocator.num_blocks)
            self.window_tables[:] = 0
            self._bt_win_dirty.clear()
            self._bt_win_dev = jnp.asarray(self.window_tables)
            self._win_reserved = 0
        self._free_slots = list(range(self.cfg.max_seqs - 1, -1, -1))
        self._reserved = 0
        self._dev_state = self._fresh_dev_state()
        self._block_tenant.clear()  # fresh allocator: stale block ids
        self._cost_last_tick = 0.0
        self.cache = self._build_cache()
        self._consec_failures = 0
        self._refresh_memory_handles()
        if failed:
            log_dist(
                f"ragged engine: state reset failed {failed} in-flight "
                "request(s)", ranks=[0])
        return failed

    def step(self) -> dict:
        """One SplitFuse step. Returns {uid: token} for sequences that emitted
        a token this step. Runs under the dispatch watchdog: transient
        device-path failures are retried (and eventually degraded) in
        place, so callers only ever see fatal errors."""
        if not self.has_work:
            return {}
        out = self._step_watched()
        if self.telemetry.enabled:
            self._sample_step_telemetry()
        return out

    def _sample_step_telemetry(self) -> None:
        """Scheduler-state gauges after each step: KV-page occupancy, queue
        depth, cumulative dispatch/padding counters."""
        tel = self.telemetry
        if self._memledger_handles is None and tel.memledger is not None:
            # ledger configured after engine construction: register now
            # (mirrors the training engine's lazy first-step registration)
            self._register_memory_owners()
        if tel.costmeter is not None:
            # long decodes accrue block-seconds continuously, not only at
            # admission/release seams
            self._cost_tick()
        usable = self.cfg.num_blocks - 1  # block 0 is scratch
        free = self.allocator.free_blocks
        g = tel.gauge
        g("kv_pages_free", "free KV blocks").set(free)
        g("kv_page_occupancy",
          "fraction of usable KV blocks in use").set(
              (usable - free) / max(usable, 1))
        if self._window:
            for pool, alloc in (("full", self.allocator),
                                ("window", self.window_allocator)):
                for state, n in (("free", alloc.free_blocks),
                                 ("busy", alloc.busy_blocks)):
                    g("kv_pool_blocks", "blocks of a model's two pools (full "
                      "layers, window layers) by state").set(
                          n, pool=pool, state=state)
        g("inference_queue_depth", "requests waiting for admission").set(
            len(self._queued))
        g("inference_running_seqs", "admitted sequences").set(
            len(self._running))
        if self._slot_state:
            g("inference_state_slots",
              "slots holding a sequence's recurrent state").set(
                  len(self._running))
        g("inference_tokens_scheduled", "useful token-slots scheduled").set(
            self.tokens_scheduled)
        g("inference_tokens_padded", "padding token-slots scheduled").set(
            self.tokens_padded)
        g("inference_dispatch_count", "device dispatches issued").set(
            self.dispatch_count)
        if self.tokens_emitted:
            g("ragged_dispatches_per_token",
              "device dispatches divided by tokens emitted").set(
                  self.dispatch_count / self.tokens_emitted)
        g("degraded_mode",
          "0 full | 1 host-staged fallback | 2 plain-step fallback").set(
              self.degraded_mode)
        if self.h2d_bytes > self._h2d_seen:
            tel.counter(
                "ragged_h2d_bytes_total",
                "bytes staged host-to-device by ragged dispatches").inc(
                    self.h2d_bytes - self._h2d_seen)
            self._h2d_seen = self.h2d_bytes
        if self.program_dispatches:
            g("ragged_warmup_coverage",
              "fraction of dispatches served by an already-built jitted "
              "program (1.0 = no serve-time compiles since warmup)").set(
                  1.0 - self.program_cold_dispatches
                  / self.program_dispatches)
        if self.cfg.enable_prefix_cache:
            alloc = self.allocator
            bb = self._block_bytes()
            if alloc.evictions > self._evictions_seen:
                delta = alloc.evictions - self._evictions_seen
                tel.counter(
                    "prefix_cache_evictions_total",
                    "cached KV blocks reclaimed under pool pressure",
                ).inc(delta)
                tel.counter(
                    "prefix_cache_evicted_bytes_total",
                    "HBM bytes reclaimed from the prefix cache",
                ).inc(delta * bb)
                self._evictions_seen = alloc.evictions
            g("prefix_cache_blocks_published",
              "KV blocks registered in the prefix index").set(
                  alloc.cached_blocks)
            g("prefix_cache_blocks_retained",
              "refcount-0 cached blocks held from the free list").set(
                  alloc.retained_blocks)
            g("prefix_cache_retained_bytes",
              "HBM bytes pinned by refcount-0 cached blocks").set(
                  alloc.retained_blocks * bb)
            decided = self.prefix_hits + self.prefix_misses
            g("prefix_cache_hit_rate",
              "fraction of admissions with a cached prefix").set(
                  self.prefix_hits / decided if decided else 0.0)
        if self._kvtier is not None:
            st = self._kvtier.stats()
            g("kvtier_bytes", "bytes parked in the KV tier").set(
                st["host_bytes"], tier="host")
            g("kvtier_bytes", "bytes parked in the KV tier").set(
                st["disk_bytes"], tier="disk")
            g("kvtier_blocks", "KV blocks parked in the tier").set(
                st["host_blocks"], tier="host")
            g("kvtier_blocks", "KV blocks parked in the tier").set(
                st["disk_blocks"], tier="disk")
            seen = self._kvtier_seen
            for name, help_ in (
                ("demotions", "KV blocks demoted HBM->host on eviction"),
                ("spills", "KV blocks spilled host->disk on overflow"),
                ("promotions", "KV blocks promoted back into HBM"),
                ("prefetch_hits",
                 "admissions whose tier prefetch finished in time"),
                ("prefetch_abandoned",
                 "admissions that outran their tier prefetch"),
            ):
                delta = st[name] - seen.get(name, 0)
                if delta > 0:
                    tel.counter(f"kvtier_{name}_total", help_).inc(delta)
                    seen[name] = st[name]
        g("kvquant_enabled",
          "low-bit KV pool active (1 = quantized, 0 = fp pool)").set(
              1.0 if self._kvq is not None else 0.0, codec=self._kvq_name)
        if self._kvq is not None:
            saved = self._kvq_alloc_total() \
                * (self._fp_block_bytes - self._block_bytes())
            delta = saved - self._kvquant_saved_seen
            if delta > 0:
                tel.counter(
                    "kvquant_bytes_saved_total",
                    "HBM bytes the low-bit pool saved vs the fp pool, "
                    "accumulated over allocated blocks",
                ).inc(delta, codec=self._kvq_name)
                self._kvquant_saved_seen = saved
            g("kvquant_block_multiplier",
              "resident KV blocks per HBM byte vs an fp16 pool").set(
                  self._fp16_block_bytes / max(1, self._block_bytes()),
                  codec=self._kvq_name)
        hb = self.admission_headroom_blocks()
        if hb >= 0:
            g("kv_headroom_blocks",
              "KV blocks fundable from measured free-byte headroom").set(hb)
        tel.sample_memory(step=self.dispatch_count)

    def _step_impl(self) -> dict:
        """The two ways the engine takes a step:

        sweep aborts -> ``_step_device()`` if ``cfg.device_state`` (the
                        normal path: admit -> ``_dispatch_step_device``
                        -> reconcile)
                     -> the host-staged step below otherwise (rung 1 of the
                        watchdog's ladder; the tests' reference)

        The host-staged step takes the plan ``_step_device`` would dispatch,
        with the decode rows' token and position fed from host state, ships
        the arrays one by one, and picks the tokens from the logits and
        reads them back at once (no pending window, no device slot rows).
        It is written here and not in a routine of its own because this
        frame lies under every trace of a step program: CPython keeps
        frames in 16 KiB chunks and frees a chunk as its first frame
        returns, so how many bytes the frames below jax's recursion take
        decides which of its hot calls allocate a chunk each time. With the
        host step elsewhere (232 bytes less here) a serving cell's set-up
        read 5 s longer on the chip and another's 2 s shorter (PERF.md,
        PR 28)."""
        self._sweep_aborts()
        if not self.has_work:
            return {}  # the sweep retired everything schedulable
        if self.cfg.device_state:
            return self._step_device()
        self._admit_queued()
        t0 = time.perf_counter()
        plan = self._pack_step(host_feed=True)
        if plan is None:
            self._deadlock_guard(0)  # raises unless idling on headroom
            return {}
        parts, emit, tpairs, t_total, n, nd, nt, max_pos, *_ = plan
        self._deadlock_guard(n)
        tokens, slots, positions = (self._h2d(p) for p in parts[:3])
        if self._use_tiles:
            mode, step_fn = "tiled", self._get_tiled_step(nd, nt)
            tiles = [self._h2d(p) for p in parts[4:]]
        else:
            skey = ("step", t_total, self._table_width(max_pos))
            self._note_program("step", skey not in self._step_keys)
            self._step_keys.add(skey)
            mode, step_fn, tiles = "step", self._step_jit, []
        table = self._h2d(self._table_view(max_pos))
        if self._faults.enabled:
            self._faults.fire(POINT_DISPATCH)
        logits, self.cache = step_fn(
            self.params, self.cache, tokens, slots, positions, *tiles, table)
        self._note_dispatch(t0)
        if tpairs is not None:
            self._trace_spans(t0, time.perf_counter(), tpairs, mode=mode)
        return self._emit_tokens(logits, emit)

    def _get_tiled_step(self, nd: int, nt: int):
        """Jitted step with a static (decode-count, tile-count) split; one
        program per bucket pair."""
        key = (nd, nt)
        fn = self._tiled_jits.get(key)
        self._note_program("tiled", fn is None)
        if fn is None:
            fwd = self.spec.ragged_forward_fn
            ct = self.cfg.prefill_tile

            def step_fn(params, cache, tokens, slots, positions, ts, tp, tv, bt):
                return fwd(params, tokens, slots, positions, bt, cache,
                           prefill_tiles=(nd, ts, tp, tv, ct))

            fn = jax.jit(step_fn, donate_argnums=(1,))
            self._tiled_jits[key] = fn
        return fn

    # ------------------------------------------------------------------ convenience
    def generate_all(self, max_steps: int = 10_000) -> dict:
        """Drive ``step()`` until all queued/admitted work finishes; returns
        {uid: generated token list}."""
        for _ in range(max_steps):
            if not self.has_work:
                break
            self.step()
        if self.has_work:
            raise RuntimeError(f"work left after {max_steps} steps")
        return {uid: list(seq.generated) for uid, seq in self._results.items()}
