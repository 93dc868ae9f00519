"""Hierarchical KV-cache tiering (inference/kvtier.py).

The contract under test: with ``kv_tier=True`` the prefix-cache LRU
*demotes* evicted published blocks (HBM → bounded host arena → disk spill)
instead of dropping them, and admission *promotes* demoted chain links back
through the jitted scatter path when the restore-vs-prefill cost model says
so — producing EXACTLY the tokens a cold engine would, greedy and
sampled-with-fixed-seed, in every dispatch mode. Plus the tier mechanics
that make that safe: length+sha256 framing, atomic disk records with a
torn-file sweep, LRU order in the host arena, conservative cost-model
edges, the async prefetch hit/abandoned protocol, and the
notify-before-free ordering the cluster index depends on.
"""

import gc
import os
import pickle
import threading
import weakref

import jax.numpy as jnp
import numpy as np
import pytest
from step_modes import MODES  # tests/unit is rootdir-inserted by pytest

from deepspeed_tpu.inference.kvtier import (
    DiskTier,
    HostTier,
    KVCodecMismatch,
    KVTierStore,
    RECORD_MAGIC,
    _key_digest,
    frame_bytes,
    restore_beats_prefill,
    unframe_bytes,
)
from deepspeed_tpu.inference.ragged import (
    BlockedAllocator,
    KVHandoff,
    RaggedConfig,
    RaggedInferenceEngine,
)
from deepspeed_tpu.models import llama

CFG = llama.LlamaConfig(
    vocab_size=97, hidden_size=32, intermediate_size=64,
    num_layers=2, num_heads=4, num_kv_heads=2, max_seq_len=128,
)

BS = 4  # block size used throughout — prompts below are built around it


def _engine(cache=True, **over):
    kw = dict(max_tokens_per_step=16, max_seqs=3, block_size=BS,
              num_blocks=13, max_blocks_per_seq=16,
              enable_prefix_cache=cache)
    kw.update(over)
    return RaggedInferenceEngine(
        model=lambda ctx: llama.build(CFG, ctx=ctx),
        ragged_config=RaggedConfig(**kw), dtype=jnp.float32, seed=0)


SHARED = [11, 7, 3, 5, 2, 13, 17, 19]          # two full blocks of 4
PROMPT_A = SHARED + [23, 29, 31]               # warms the cache
PROMPT_B = SHARED + [37, 41]                   # must hit both shared blocks


def _churn(eng, n=6, max_new=4):
    """Distinct single-use prompts that force LRU eviction (and with
    tiering on, demotion) of earlier published prefix blocks."""
    for i in range(n):
        eng.put(f"churn{i}", [50 + i * 7 + j for j in range(9)],
                max_new_tokens=max_new)
        eng.generate_all()


# ------------------------------------------------------------------ framing
class TestFraming:
    def test_roundtrip_and_chaining(self):
        a, b = b"hello", b"\x00" * 33
        buf = frame_bytes(a) + frame_bytes(b)
        got_a, off = unframe_bytes(buf)
        got_b, end = unframe_bytes(buf, off)
        assert (got_a, got_b) == (a, b) and end == len(buf)

    def test_flipped_byte_raises(self):
        buf = bytearray(frame_bytes(b"payload"))
        buf[-1] ^= 0x01
        with pytest.raises(ValueError):
            unframe_bytes(bytes(buf))

    def test_truncation_raises(self):
        buf = frame_bytes(b"payload")
        for cut in (1, 8, 39, len(buf) - 1):
            with pytest.raises(ValueError):
                unframe_bytes(buf[:cut])


class TestKVHandoffBytes:
    def _record(self):
        return KVHandoff(
            uid="r1", prompt=[1, 2, 3, 4, 5], generated=[7], pos=5,
            max_new_tokens=8, eos_token_id=None, temperature=0.9, top_k=20,
            top_p=1.0, seed=123, deadline_remaining_s=0.0,
            block_payload={"k": np.arange(24, dtype=np.float32
                                          ).reshape(2, 2, 2, 3)},
            row_iv=np.arange(5, dtype=np.int32),
            row_fv=np.asarray([0.9, 1.0], np.float32))

    def test_roundtrip(self):
        rec = self._record()
        back = KVHandoff.from_bytes(rec.to_bytes())
        assert back.uid == rec.uid and back.prompt == rec.prompt
        assert back.seed == rec.seed and back.pos == rec.pos
        np.testing.assert_array_equal(back.block_payload["k"],
                                      rec.block_payload["k"])
        np.testing.assert_array_equal(back.row_iv, rec.row_iv)

    def test_corruption_and_truncation_raise(self):
        buf = self._record().to_bytes()
        flipped = bytearray(buf)
        flipped[len(buf) // 2] ^= 0x01
        with pytest.raises(ValueError):
            KVHandoff.from_bytes(bytes(flipped))
        with pytest.raises(ValueError):
            KVHandoff.from_bytes(buf[:-3])
        with pytest.raises(ValueError):
            KVHandoff.from_bytes(b"XXXX" + buf[4:])
        with pytest.raises(ValueError):
            KVHandoff.from_bytes(buf + b"trailing")


# --------------------------------------------------------------- cost model
class TestRestoreCostModel:
    def test_zero_length_never_restores(self):
        assert not restore_beats_prefill(0, 1024, 100.0, 1000.0)
        assert not restore_beats_prefill(-4, 1024, 100.0, 1000.0)

    def test_exact_tie_prefers_prefill(self):
        # 125_000 B/token over 1 Gb/s = 1 ms/token; 1000 tok/s prefill =
        # 1 ms/token — a dead tie must NOT restore (strict <)
        assert not restore_beats_prefill(64, 125_000, 1.0, 1000.0)
        assert restore_beats_prefill(64, 124_999, 1.0, 1000.0)

    def test_unknown_bandwidth_is_conservative(self):
        # a -1 "unknown" bandwidth/rate would flip the inequality by going
        # negative; both must mean "re-prefill"
        assert not restore_beats_prefill(64, 16, -1.0, 1000.0)
        assert not restore_beats_prefill(64, 16, 100.0, -1.0)
        assert not restore_beats_prefill(64, 16, 0.0, 1000.0)


# ------------------------------------------------------------------- tiers
class TestHostTier:
    def test_lru_overflow_sheds_oldest(self):
        t = HostTier(2)
        assert t.put("a", 1) == []
        assert t.put("b", 2) == []
        shed = t.put("c", 3)
        assert shed == [("a", 1)] and len(t) == 2
        assert t.get("a") is None and t.get("c") == 3

    def test_get_touches_to_mru(self):
        t = HostTier(2)
        t.put("a", 1)
        t.put("b", 2)
        t.get("a")                       # a becomes MRU
        assert t.put("c", 3) == [("b", 2)]
        assert t.get("a") == 1

    def test_reput_touches_without_shedding(self):
        t = HostTier(2)
        t.put("a", 1)
        t.put("b", 2)
        assert t.put("a", 1) == []       # same chain key = same KV: touch
        assert t.put("c", 3) == [("b", 2)]


class TestDiskTier:
    def test_put_get_roundtrip_atomic(self, tmp_path):
        d = DiskTier(str(tmp_path / "kv"), budget_blocks=8)
        key = (None, (1, 2, 3, 4))
        payload = {"k": np.arange(8, dtype=np.float32)}
        assert d.put(key, payload)
        assert not any(".tmp." in n for n in os.listdir(d.directory))
        np.testing.assert_array_equal(d.get(key)["k"], payload["k"])
        assert d.get((None, (9, 9, 9, 9))) is None

    def test_budget_evicts_oldest(self, tmp_path):
        d = DiskTier(str(tmp_path / "kv"), budget_blocks=2)
        keys = [(None, (i,)) for i in range(3)]
        for k in keys:
            d.put(k, np.zeros(4))
        assert len(d) == 2
        assert d.get(keys[0]) is None and d.get(keys[2]) is not None

    def test_sweep_removes_torn_and_temp_files(self, tmp_path):
        root = str(tmp_path / "kv")
        d = DiskTier(root, budget_blocks=8)
        good = (None, (1, 2, 3, 4))
        d.put(good, np.arange(4))
        valid = os.path.join(root, os.listdir(root)[0])
        # a torn write (truncated record), a corrupt one, and a leftover temp
        with open(valid, "rb") as f:
            buf = f.read()
        with open(os.path.join(root, "torn" + DiskTier.SUFFIX), "wb") as f:
            f.write(buf[:len(buf) // 2])
        flipped = bytearray(buf)
        flipped[-1] ^= 0x01
        with open(os.path.join(root, "bad" + DiskTier.SUFFIX), "wb") as f:
            f.write(bytes(flipped))
        with open(os.path.join(root, f"x{DiskTier.SUFFIX}.tmp.123"),
                  "wb") as f:
            f.write(b"partial")
        # engine startup re-opens the directory: the sweep keeps only the
        # intact record
        d2 = DiskTier(root, budget_blocks=8)
        assert d2.sweep_removed == 3
        assert sorted(os.listdir(root)) == [os.path.basename(valid)]
        assert d2.get(good) is not None

    def test_codec_recorded_and_matched(self, tmp_path):
        root = str(tmp_path / "kv")
        key = (None, (1, 2, 3, 4))
        d = DiskTier(root, budget_blocks=8, codec="int8")
        d.put(key, {"q": np.zeros(4, np.int8), "s": np.ones(1, np.float16)})
        got = DiskTier(root, budget_blocks=8, codec="int8").get(key)
        assert got is not None and got["q"].dtype == np.int8

    def test_codec_mismatch_raises_not_misses(self, tmp_path):
        # a spill written under int8 read by an fp16/off engine is a CONFIG
        # error: silently dequantizing (or splicing raw int8 as fp rows)
        # would corrupt tokens, so get() must raise, never return None
        root = str(tmp_path / "kv")
        key = (None, (1, 2, 3, 4))
        DiskTier(root, budget_blocks=8, codec="int8").put(key, np.zeros(4))
        for other in ("off", "fp8"):
            reader = DiskTier(root, budget_blocks=8, codec=other)
            with pytest.raises(KVCodecMismatch, match="int8"):
                reader.get(key)
            # the record is intact, not a casualty: the matching engine
            # still reads it afterwards
            assert DiskTier(root, budget_blocks=8,
                            codec="int8").get(key) is not None

    def test_legacy_bare_key_record_reads_as_off(self, tmp_path):
        # records written before codec framing carry a bare pickled chain
        # key: they read fine under codec "off" and raise under a quant one
        root = str(tmp_path / "kv")
        os.makedirs(root)
        key = (None, (7, 8, 9))
        body = (RECORD_MAGIC
                + frame_bytes(pickle.dumps(key, protocol=4))
                + frame_bytes(pickle.dumps(np.arange(4), protocol=4)))
        with open(os.path.join(root, _key_digest(key) + DiskTier.SUFFIX),
                  "wb") as f:
            f.write(body)
        got = DiskTier(root, budget_blocks=8, codec="off").get(key)
        np.testing.assert_array_equal(got, np.arange(4))
        with pytest.raises(KVCodecMismatch):
            DiskTier(root, budget_blocks=8, codec="int8").get(key)

    def test_store_threads_codec_to_disk_and_stats(self, tmp_path):
        st = KVTierStore(host_blocks=2, disk_blocks=4,
                         directory=str(tmp_path / "kv"), codec="fp8")
        assert st.disk.codec == "fp8"
        assert st.stats()["codec"] == "fp8"


# ---------------------------------------------- allocator demotion ordering
class _RecordingListener:
    """Captures, at notification time, whether the block id was already
    back in the allocator free list — the satellite-1 invariant: the
    cluster index must hear about the eviction BEFORE the payload's block
    id is reusable."""

    def __init__(self, alloc):
        self.alloc = alloc
        self.events = []

    def on_publish(self, key):
        self.events.append(("publish", key))

    def on_evict(self, key):
        self.events.append(
            ("evict", key, self._freed()))

    def on_demote(self, key):
        self.events.append(("demote", key, self._freed()))

    def on_reset(self):
        self.events.append(("reset",))

    def _freed(self):
        return len(self.alloc._free)


class TestDemotionNotifyOrdering:
    def _evict_one(self, hook):
        a = BlockedAllocator(3)      # 2 usable
        lst = _RecordingListener(a)
        a.listener = lst
        a.demote_hook = hook
        blocks = a.allocate(2)
        a.publish(blocks[0], "key0")
        a.free(blocks)               # key0 retained, block[1] free
        a.allocate(2)                # forces eviction of key0
        return lst.events[-1]

    def test_demote_notified_before_block_freed(self):
        seen = {}

        def hook(block, key):
            seen["args"] = (block, key)
            return True

        ev = self._evict_one(hook)
        assert seen["args"][1] == "key0"
        # one block was free before the eviction; the evicted id must not
        # have joined it yet when the listener runs
        assert ev == ("demote", "key0", 1)

    def test_failed_demotion_falls_back_to_evict(self):
        ev = self._evict_one(lambda b, k: False)
        assert ev == ("evict", "key0", 1)

    def test_raising_hook_is_contained(self):
        def hook(b, k):
            raise RuntimeError("gather failed")

        ev = self._evict_one(hook)
        assert ev == ("evict", "key0", 1)


# --------------------------------------------------------- engine round-trip
class TestTieredParity:
    """Demoted-then-promoted prefixes must be invisible in the tokens."""

    @pytest.mark.parametrize("mode", list(MODES))
    def test_demote_promote_token_exact(self, mode, tmp_path):
        kw = MODES[mode]
        cold = _engine(cache=False, num_blocks=49, **kw)
        cold.put("g", PROMPT_B, max_new_tokens=8)
        cold.put("s", PROMPT_B, max_new_tokens=8, temperature=0.9,
                 top_k=20, seed=123)
        want = cold.generate_all()

        t = _engine(kv_tier=True, kv_tier_host_blocks=16,
                    kv_tier_dir=str(tmp_path / "kv"), **kw)
        t.put("warm", PROMPT_A, max_new_tokens=6)
        t.generate_all()
        _churn(t)                    # 13-block pool: the prefix demotes
        st = t.kv_tier_stats()
        assert st["demotions"] > 0

        t.put("g", PROMPT_B, max_new_tokens=8)
        t.put("s", PROMPT_B, max_new_tokens=8, temperature=0.9,
              top_k=20, seed=123)
        got = t.generate_all()
        assert got["g"] == want["g"]
        assert got["s"] == want["s"]
        st = t.kv_tier_stats()
        assert st["promotions_host"] > 0
        assert st["promoted_admissions_host"] >= 1

    def test_disk_spill_prefetch_hit_and_parity(self, tmp_path):
        t = _engine(kv_tier=True, kv_tier_host_blocks=2,
                    kv_tier_disk_blocks=32,
                    kv_tier_dir=str(tmp_path / "kv"))
        t.put("warm", PROMPT_A, max_new_tokens=6)
        t.generate_all()
        _churn(t, n=8)               # 2-block host arena overflows to disk
        st = t.kv_tier_stats()
        assert st["spills"] > 0 and st["disk_blocks"] > 0

        # the router-side kick: stage disk records host-ward off-thread,
        # then admit — the resolved job counts as a prefetch hit
        assert t.tier_prefetch_async(PROMPT_B)
        assert t._kvtier.wait_idle(10.0)
        t.put("g", PROMPT_B, max_new_tokens=8)
        got = t.generate_all()
        st = t.kv_tier_stats()
        assert st["prefetch_hits"] == 1
        assert st["promotions"] >= 2  # both shared blocks restored

        cold = _engine(cache=False, num_blocks=49)
        cold.put("g", PROMPT_B, max_new_tokens=8)
        assert got["g"] == cold.generate_all()["g"]
        t._kvtier.close()

    def test_prefetch_abandoned_is_token_identical(self, tmp_path):
        t = _engine(kv_tier=True, kv_tier_host_blocks=2,
                    kv_tier_disk_blocks=32,
                    kv_tier_dir=str(tmp_path / "kv"))
        t.put("warm", PROMPT_A, max_new_tokens=6)
        t.generate_all()
        _churn(t, n=8)
        # park the worker: admission outruns the staging job
        gate = threading.Event()
        t._kvtier._stall_for_test = gate
        assert t.tier_prefetch_async(PROMPT_B)
        t.put("g", PROMPT_B, max_new_tokens=8)
        got = t.generate_all()
        gate.set()
        st = t.kv_tier_stats()
        assert st["prefetch_abandoned"] == 1 and st["prefetch_hits"] == 0
        # the synchronous restore covered for it — tokens identical
        cold = _engine(cache=False, num_blocks=49)
        cold.put("g", PROMPT_B, max_new_tokens=8)
        assert got["g"] == cold.generate_all()["g"]
        t._kvtier.close()

    def test_cost_model_decline_still_correct(self, tmp_path):
        # a hopeless tier bandwidth: every restore is declined, the request
        # re-prefills — slower, never wrong
        t = _engine(kv_tier=True, kv_tier_host_blocks=16,
                    kv_tier_host_gbps=1e-9,
                    kv_tier_dir=str(tmp_path / "kv"))
        t.put("warm", PROMPT_A, max_new_tokens=6)
        t.generate_all()
        _churn(t)
        t.put("g", PROMPT_B, max_new_tokens=8)
        got = t.generate_all()
        st = t.kv_tier_stats()
        assert st["restore_declined"] > 0 and st["promotions"] == 0
        cold = _engine(cache=False, num_blocks=49)
        cold.put("g", PROMPT_B, max_new_tokens=8)
        assert got["g"] == cold.generate_all()["g"]

    def test_tier_store_survives_reset(self, tmp_path):
        t = _engine(kv_tier=True, kv_tier_host_blocks=16,
                    kv_tier_dir=str(tmp_path / "kv"))
        t.put("warm", PROMPT_A, max_new_tokens=6)
        t.generate_all()
        _churn(t)
        assert t.kv_tier_stats()["demotions"] > 0
        t.reset_state()
        # content-keyed records outlive the allocator generation: the
        # rewired demote hook and the parked payloads still promote
        assert t.allocator.demote_hook is not None
        t.put("g", PROMPT_B, max_new_tokens=8)
        got = t.generate_all()
        assert t.kv_tier_stats()["promotions"] > 0
        cold = _engine(cache=False, num_blocks=49)
        cold.put("g", PROMPT_B, max_new_tokens=8)
        assert got["g"] == cold.generate_all()["g"]

    def test_dropped_tiered_engine_is_freed_without_the_cycle_collector(
            self, tmp_path):
        """The allocator's demote hook holds the engine weakly: a dropped
        tiered engine gives its weights and pool back at once, not whenever
        a full collection next runs (until then the memory ledger's census
        reads them as unattributed bytes, in whatever test runs next)."""
        gc.collect()
        gc.disable()
        try:
            t = _engine(kv_tier=True, kv_tier_host_blocks=16,
                        kv_tier_dir=str(tmp_path / "kv"))
            t.put("warm", PROMPT_A, max_new_tokens=6)
            t.generate_all()
            _churn(t)
            assert t.kv_tier_stats()["demotions"] > 0
            t.reset_state()  # installs the hook on the fresh allocator too
            t._kvtier.close()
            gone = weakref.ref(t)
            del t
            assert gone() is None
        finally:
            gc.enable()


class TestTierConfigGates:
    def test_default_is_off(self):
        cfg = RaggedConfig()
        assert cfg.kv_tier is False and cfg.kv_tier_disk_blocks == 0

    def test_tier_requires_prefix_cache(self):
        with pytest.raises(ValueError, match="prefix_cache"):
            _engine(cache=False, kv_tier=True)

    def test_engine_without_tiering_has_no_store(self):
        t = _engine(cache=True)
        assert t._kvtier is None and t.kv_tier_stats() is None
        assert t.allocator.demote_hook is None


class TestStoreMechanics:
    def test_fetch_prefers_host_and_reports_tier(self, tmp_path):
        s = KVTierStore(host_blocks=1, disk_blocks=8,
                        directory=str(tmp_path / "kv"))
        s.demote("k1", np.arange(4))
        s.demote("k2", np.arange(4))     # k1 sheds to disk
        assert s.tier_of("k2") == 1 and s.tier_of("k1") == 2
        assert s.fetch("k2")[1] == 1
        assert s.fetch("k1")[1] == 2
        assert s.fetch("nope") is None
        assert s.stats()["spills"] == 1
        s.close()

    def test_spill_drop_without_disk_tier(self):
        s = KVTierStore(host_blocks=1)
        s.demote("k1", np.arange(4))
        s.demote("k2", np.arange(4))
        assert s.stats()["spill_drops"] == 1
        assert s.tier_of("k1") == 0      # gone for good
        s.close()

    def test_prefetch_dedupes_by_signature(self, tmp_path):
        s = KVTierStore(host_blocks=1, disk_blocks=8,
                        directory=str(tmp_path / "kv"))
        gate = threading.Event()
        s._stall_for_test = gate
        s.demote("k1", np.arange(4))
        assert s.prefetch(["k1"], sig="req")
        assert not s.prefetch(["k1"], sig="req")   # already pending
        assert not s.prefetch(["absent"], sig="other")  # nothing to stage
        gate.set()
        assert s.wait_idle(5.0)
        assert s.note_admission("req") == "hit"
        assert s.note_admission("req") is None     # resolved exactly once
        s.close()
