"""Block-level prefix caching: ref-counted KV block reuse across requests.

The contract under test: with ``enable_prefix_cache=True``, a request whose
prompt shares full cached blocks with a retired request splices those blocks
(no re-prefill) and still generates EXACTLY the tokens a cold engine would —
greedy and sampled-with-fixed-seed, in every dispatch mode. Plus the
allocator invariants that make sharing safe: refcounts, LRU eviction funded
strictly by free memory, and double-free detection.
"""

import jax.numpy as jnp
import numpy as np
import pytest
from step_modes import MODES  # tests/unit is rootdir-inserted by pytest

from deepspeed_tpu.inference.ragged import (
    BlockedAllocator,
    RaggedConfig,
    RaggedInferenceEngine,
)
from deepspeed_tpu.models import llama
from deepspeed_tpu.serving.engine_loop import ReplicaStats
from deepspeed_tpu.serving.router import RouterConfig, plan_placement

CFG = llama.LlamaConfig(
    vocab_size=97, hidden_size=32, intermediate_size=64,
    num_layers=2, num_heads=4, num_kv_heads=2, max_seq_len=128,
)

BS = 4  # block size used throughout — prompts below are built around it


def _engine(cache=False, **over):
    kw = dict(max_tokens_per_step=16, max_seqs=3, block_size=BS,
              num_blocks=49, max_blocks_per_seq=16,
              enable_prefix_cache=cache)
    kw.update(over)
    return RaggedInferenceEngine(
        model=lambda ctx: llama.build(CFG, ctx=ctx),
        ragged_config=RaggedConfig(**kw), dtype=jnp.float32, seed=0)


SHARED = [11, 7, 3, 5, 2, 13, 17, 19]          # two full blocks of 4
PROMPT_A = SHARED + [23, 29, 31]               # warms the cache
PROMPT_B = SHARED + [37, 41]                   # must hit both shared blocks


class TestBlockedAllocatorRefcounts:
    def test_acquire_free_refcount_roundtrip(self):
        a = BlockedAllocator(9)
        blocks = a.allocate(2)
        a.acquire(blocks)          # second owner
        a.free(blocks)             # first owner drops
        assert a.free_blocks == 6  # still held by the second owner
        a.free(blocks)
        assert a.free_blocks == 8

    def test_double_free_raises(self):
        a = BlockedAllocator(9)
        blocks = a.allocate(1)
        a.free(blocks)
        with pytest.raises(ValueError, match="double free"):
            a.free(blocks)

    def test_published_blocks_are_retained_then_evicted_lru(self):
        a = BlockedAllocator(5)  # 4 usable
        blocks = a.allocate(4)
        for i, b in enumerate(blocks):
            a.publish(b, ("k", i))
        a.free(blocks)  # all refcount 0 -> retained, LRU order = free order
        assert a.retained_blocks == 4 and a.free_blocks == 4
        # allocation is funded by evicting the OLDEST published blocks
        got = a.allocate(2)
        assert a.evictions == 2
        assert a.lookup(("k", 0)) is None and a.lookup(("k", 1)) is None
        assert a.lookup(("k", 2)) is not None
        a.free(got)

    def test_acquire_removes_from_lru(self):
        a = BlockedAllocator(5)
        blocks = a.allocate(2)
        a.publish(blocks[0], "key0")
        a.free(blocks)
        hit = [a.lookup("key0")]
        a.acquire(hit)  # refcount 0 -> 1, leaves the evictable LRU
        assert a.retained_blocks == 0
        # exhausting the pool must NOT evict the re-referenced block
        a.allocate(a.free_blocks)
        assert a.lookup("key0") == hit[0]

    def test_exhaustion_still_raises(self):
        a = BlockedAllocator(5)
        a.allocate(4)
        with pytest.raises(RuntimeError, match="exhausted"):
            a.allocate(1)


class TestHitVsColdParity:
    """A cache hit must be token-identical to a cold run — the KV spliced
    from the index stands in for KV the engine would have computed."""

    @pytest.mark.parametrize("mode", list(MODES))
    def test_token_exact_greedy_and_seeded_sampled(self, mode):
        kw = MODES[mode]
        cold = _engine(cache=False, **kw)
        cold.put("g", PROMPT_B, max_new_tokens=8)
        cold.put("s", PROMPT_B, max_new_tokens=8, temperature=0.9, top_k=20,
                 seed=123)
        want = cold.generate_all()

        warm = _engine(cache=True, **kw)
        warm.put("warmup", PROMPT_A, max_new_tokens=6)
        warm.generate_all()
        assert warm.prefix_misses == 1 and warm.prefix_hits == 0

        warm.put("g", PROMPT_B, max_new_tokens=8)
        warm.put("s", PROMPT_B, max_new_tokens=8, temperature=0.9, top_k=20,
                 seed=123)
        got = warm.generate_all()
        assert got["g"] == want["g"]
        assert got["s"] == want["s"]
        # both requests spliced the two shared blocks (8 tokens each)
        assert warm.prefix_hits == 2
        assert warm.prefix_tokens_reused == 2 * len(SHARED)
        # sampled-with-fixed-seed really sampled (not greedy fallback)
        assert want["s"] != want["g"]

    def test_partial_block_prefix_falls_back_to_prefill(self):
        warm = _engine(cache=True)
        warm.put(0, [11, 7, 3], max_new_tokens=4)  # < one full block
        warm.generate_all()
        warm.put(1, [11, 7, 3, 99], max_new_tokens=4)
        warm.generate_all()
        assert warm.prefix_hits == 0 and warm.prefix_misses == 2
        # a full-prompt re-ask caps the match one block short of the prompt:
        # 4-token prompt = 1 full block, cap (len-1)//bs = 0 -> still a miss
        warm.put(2, [11, 7, 3, 99], max_new_tokens=4)
        warm.generate_all()
        assert warm.prefix_hits == 0

    def test_disabled_by_default_stays_cold(self):
        eng = _engine(cache=False)
        eng.put(0, PROMPT_A, max_new_tokens=4)
        eng.generate_all()
        eng.put(1, PROMPT_B, max_new_tokens=4)
        eng.generate_all()
        assert eng.prefix_hits == eng.prefix_misses == 0
        assert eng.allocator.cached_blocks == 0
        assert eng.allocator.retained_blocks == 0
        assert eng.cached_prefix_len(PROMPT_B) == 0


class TestLifecycleInvariants:
    def test_refcounts_consistent_under_interleaved_cancel(self):
        eng = _engine(cache=True)
        for uid in range(5):
            eng.put(uid, SHARED + [60 + uid, 61 + uid, 62 + uid],
                    max_new_tokens=6)
        eng.step()
        eng.cancel(1)  # mid-flight: shared blocks must survive the cancel
        eng.generate_all()
        eng.put(9, PROMPT_B, max_new_tokens=4)
        out = eng.generate_all()
        assert len(out[9]) == 4 and eng.prefix_hits >= 1
        alloc = eng.allocator
        # everything is retired: no live references anywhere, and every
        # usable block is either free or retained by the cache
        assert all(r == 0 for r in alloc._refs)
        assert len(alloc._free) + alloc.retained_blocks == alloc.num_blocks - 1
        assert alloc.free_blocks == alloc.num_blocks - 1

    def test_eviction_under_pool_pressure(self):
        # 13 blocks usable (12 + scratch is block 0 of 14): each retired
        # request publishes its full prompt blocks; distinct prompts pile up
        # until allocation must evict
        eng = _engine(cache=True, num_blocks=14, max_seqs=2,
                      max_blocks_per_seq=8)
        rng = np.random.default_rng(7)
        # each retired request publishes 2 blocks and returns 1 to the free
        # list, so the free list shrinks by 2 per round: 8 rounds drain it
        for uid in range(8):
            eng.put(uid, list(rng.integers(0, 97, (8,))), max_new_tokens=4)
            eng.generate_all()
        assert eng.allocator.evictions > 0
        # the pool never deadlocks: a fresh worst-case request still admits
        eng.put("last", list(rng.integers(0, 97, (8,))), max_new_tokens=4)
        assert len(eng.generate_all()["last"]) == 4

    def test_cache_hit_shares_blocks_between_live_sequences(self):
        eng = _engine(cache=True)
        eng.put(0, PROMPT_A, max_new_tokens=4)
        eng.generate_all()
        eng.put(1, PROMPT_B, max_new_tokens=6)
        eng.put(2, SHARED + [71], max_new_tokens=6)
        eng.step()  # admits both; each splices the SAME two cached blocks
        live = list(eng._running.values())
        assert len(live) == 2 and eng.prefix_hits == 2
        assert live[0].blocks[:2] == live[1].blocks[:2]
        # refcount 2: one reference per live sequence sharing the block
        assert all(eng.allocator._refs[b] == 2 for b in live[0].blocks[:2])
        out = eng.generate_all()
        assert len(out[1]) == 6 and len(out[2]) == 6


class TestRouterCacheAwareAdmission:
    def _stats(self, name, free_blocks, outstanding=0):
        return ReplicaStats(
            name=name, alive=True, draining=False, queued=0, inflight=0,
            outstanding_tokens=outstanding, free_blocks=free_blocks,
            pending_blocks=0, block_size=4, usable_blocks=48,
            max_request_blocks=16, max_request_tokens=64)

    def test_cached_prefix_nets_out_block_need(self):
        cfg = RouterConfig(max_queue_tokens=4096)
        # 24 total tokens = 6 blocks worst case; replica has only 4 free
        stats = [self._stats("r0", free_blocks=4)]
        idx, verdict = plan_placement(stats, 24, cfg)
        assert verdict == "queue"
        # 8 cached tokens = 2 blocks already resident -> need 4 -> admit
        idx, verdict = plan_placement(stats, 24, cfg, cached_tokens=[8])
        assert (idx, verdict) == (0, "admit")

    def test_cached_prefix_nets_out_queue_bound(self):
        cfg = RouterConfig(max_queue_tokens=30)
        stats = [self._stats("r0", free_blocks=48, outstanding=10)]
        assert plan_placement(stats, 24, cfg)[1] == "overloaded"
        assert plan_placement(stats, 24, cfg, cached_tokens=[8])[1] == "admit"

    def test_tie_breaks_to_the_replica_holding_the_prefix(self):
        cfg = RouterConfig()
        stats = [self._stats("r0", free_blocks=48),
                 self._stats("r1", free_blocks=48)]
        idx, verdict = plan_placement(stats, 24, cfg, cached_tokens=[0, 8])
        assert (idx, verdict) == (1, "admit")

    def test_partial_block_cached_tokens_do_not_over_credit(self):
        cfg = RouterConfig()
        stats = [self._stats("r0", free_blocks=6)]
        # 3 cached tokens < one block: block need must NOT shrink
        idx, verdict = plan_placement(stats, 24, cfg, cached_tokens=[3])
        assert (idx, verdict) == (0, "admit")
        stats = [self._stats("r0", free_blocks=5)]
        assert plan_placement(stats, 24, cfg, cached_tokens=[3])[1] == "queue"
