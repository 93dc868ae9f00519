"""HBM memory ledger (telemetry/memledger.py + docs/OBSERVABILITY.md):
per-owner byte attribution (handles + weakref'd providers), the
``jax.live_arrays()`` census and its drift alarm, OOM forensics via
injected RESOURCE_EXHAUSTED faults, headroom-driven admission parity, the
byte-scale histogram preset, Perfetto counter tracks, the per-device HBM
sampler, and the off-is-free guarantee (tracemalloc-pinned)."""

import json
import os
import tracemalloc

import jax.numpy as jnp
import numpy as np
import pytest

from deepspeed_tpu import telemetry
from deepspeed_tpu.inference.ragged import (
    BlockedAllocator,
    RaggedConfig,
    RaggedInferenceEngine,
)
from deepspeed_tpu.models import llama
from deepspeed_tpu.utils.faults import (
    POINT_ALLOC,
    POINT_DISPATCH,
    get_fault_injector,
)
from deepspeed_tpu.telemetry import (
    BYTE_BUCKETS,
    MEMORY_OWNERS,
    TELEMETRY,
    MemoryLedger,
    is_resource_exhausted,
    tree_nbytes,
)

CFG = llama.LlamaConfig(
    vocab_size=97, hidden_size=32, intermediate_size=64,
    num_layers=2, num_heads=4, num_kv_heads=2, max_seq_len=128,
)
# plain host-staged path: cheapest to compile; the device-state OOM
# ladder is exercised by the CI memory-ledger smoke
PCFG = dict(
    max_tokens_per_step=16, max_seqs=3, block_size=4, num_blocks=49,
    max_blocks_per_seq=16, prefill_tile=0,
    device_state=False, dispatch_retries=2,
    retry_backoff_s=0.01, degrade_after=2)


def _engine(**over):
    rcfg = RaggedConfig(**{**PCFG, **over})
    return RaggedInferenceEngine(
        lambda ctx: llama.build(CFG, ctx=ctx), rcfg,
        dtype=jnp.float32, seed=0)


def _prompt(n, seed=0):
    return [int(t) for t in
            np.random.default_rng(seed).integers(0, CFG.vocab_size, n)]


PROMPTS = [_prompt(6, seed=1), _prompt(11, seed=2), _prompt(17, seed=3)]


def _put_all(eng, max_new=5):
    for i, p in enumerate(PROMPTS):
        eng.put(i, p, max_new_tokens=max_new, temperature=0.8, seed=100 + i)


def _ledger(tmp_path, **over):
    telemetry.configure(enabled=True, memledger={
        "enabled": True, "report_dir": str(tmp_path / "oom"), **over})
    return TELEMETRY.memledger


@pytest.fixture(scope="module")
def ref_tokens():
    """Ledger-off reference tokens: every ledger-on run must match."""
    eng = _engine()
    _put_all(eng)
    return eng.generate_all()


# -------------------------------------------------------------- accounting
class TestLedgerAccounting:
    def test_register_update_release(self, tmp_path):
        led = _ledger(tmp_path)
        h = led.register("kv_pool", "test/pool", 1000)
        assert led.owner_bytes()["kv_pool"] == 1000
        led.update(h, {"a": np.zeros(16, np.float32)})  # 64 bytes
        assert led.owner_bytes()["kv_pool"] == 64
        led.release(h)
        assert led.owner_bytes()["kv_pool"] == 0
        led.release(h)  # double release is harmless
        assert led.attributed_bytes() == 0

    def test_owner_taxonomy_enforced(self, tmp_path):
        led = _ledger(tmp_path)
        with pytest.raises(ValueError):
            led.register("nonsense_owner", "x", 1)
        with pytest.raises(ValueError):
            led.register_provider("nonsense_owner", "x", lambda: 0)
        assert set(led.owner_bytes()) == set(MEMORY_OWNERS)

    def test_provider_none_prunes(self, tmp_path):
        """The weakref idiom: a provider returning None (dead engine) is
        dropped and never read again."""
        led = _ledger(tmp_path)
        calls = []

        def fn():
            calls.append(1)
            return None if len(calls) > 1 else 512

        led.register_provider("staging_buffers", "test/dying", fn)
        assert led.owner_bytes()["staging_buffers"] == 512
        assert led.owner_bytes()["staging_buffers"] == 0  # fn -> None: pruned
        led.owner_bytes()
        assert len(calls) == 2  # pruned providers are not called again

    def test_offdevice_provider_excluded_from_census(self, tmp_path):
        """Host/disk KV-tier bytes are real and shown in the breakdown but
        invisible to jax.live_arrays() — the census must reconcile against
        device-resident attribution only, or tiering-on would read as
        over-attribution and trip the drift alarm."""
        led = _ledger(tmp_path)
        led.register("kv_pool", "test/pool", 1000)
        led.register_provider("host_kv_tier", "test/host_arena",
                              lambda: 700, offdevice=True)
        led.register_provider("disk_kv_tier", "test/disk_spill",
                              lambda: 300, offdevice=True)
        owners = led.owner_bytes()
        assert owners["host_kv_tier"] == 700
        assert owners["disk_kv_tier"] == 300
        assert led.owner_bytes(device_only=True)["host_kv_tier"] == 0
        rows = {r["name"]: r for r in led.breakdown()["providers"]}
        assert rows["test/host_arena"]["offdevice"] is True
        c = led.census(update_state=False)
        # attributed (device) = 1000; the 1000 off-device bytes ride in
        # their own column instead of skewing unattributed_fraction
        assert c["attributed_bytes"] == 1000
        assert c["offdevice_bytes"] == 1000

    def test_carveout_provider_moves_bytes_not_adds(self, tmp_path):
        """prefix-LRU / handoff bytes live INSIDE the kv_pool arrays: a
        carve-out re-attributes them without double-counting, so the
        attributed total still equals the real pool bytes."""
        led = _ledger(tmp_path)
        led.register("kv_pool", "test/pool", 1000)
        led.register_provider("prefix_cache_retained", "test/lru",
                              lambda: 300, carveout_of="kv_pool")
        led.register_provider("kv_handoff", "test/parked",
                              lambda: 100, carveout_of="kv_pool")
        owners = led.owner_bytes()
        assert owners["kv_pool"] == 600
        assert owners["prefix_cache_retained"] == 300
        assert owners["kv_handoff"] == 100
        assert led.attributed_bytes() == 1000  # each byte counted once
        providers = led.breakdown()["providers"]
        assert {"owner": "prefix_cache_retained", "name": "test/lru",
                "carveout_of": "kv_pool"} in providers
        with pytest.raises(ValueError):
            led.register_provider("kv_handoff", "x", lambda: 0,
                                  carveout_of="nonsense_owner")

    def test_carveout_never_drives_parent_negative(self, tmp_path):
        led = _ledger(tmp_path)
        led.register("kv_pool", "test/pool", 100)
        led.register_provider("prefix_cache_retained", "test/over",
                              lambda: 500, carveout_of="kv_pool")
        owners = led.owner_bytes()
        assert owners["kv_pool"] == 0
        assert owners["prefix_cache_retained"] == 100  # clamped to parent
        assert led.attributed_bytes() == 100

    def test_engine_pool_bytes_not_double_counted(self, tmp_path):
        """End-to-end: retained prefix blocks re-attribute pool bytes, so
        kv_pool + carve-outs must equal the real cache bytes exactly (the
        pre-carve-out ledger summed to cache + retained, overstating)."""
        led = _ledger(tmp_path)
        eng = _engine(enable_prefix_cache=True)
        _put_all(eng)
        eng.generate_all()
        assert eng.allocator.retained_blocks > 0  # retirement published
        owners = led.owner_bytes()
        assert owners["prefix_cache_retained"] \
            == eng.allocator.retained_blocks * eng._block_bytes()
        pool_total = (owners["kv_pool"] + owners["prefix_cache_retained"]
                      + owners["kv_handoff"])
        assert pool_total == tree_nbytes(eng.cache)

    def test_tree_nbytes(self):
        assert tree_nbytes(None) == 0
        assert tree_nbytes(12345) == 12345
        tree = {"w": np.zeros((4, 4), np.float32),
                "b": [jnp.zeros(8, jnp.int32)]}
        assert tree_nbytes(tree) == 64 + 32

    def test_byte_buckets_pow2(self):
        assert all(b == 2.0 ** p
                   for b, p in zip(BYTE_BUCKETS, range(10, 37, 2)))
        h = TELEMETRY.registry.histogram(
            "test_alloc_bytes", "x", buckets=BYTE_BUCKETS)
        h.observe(5000.0)
        assert h is not None


# ------------------------------------------------------------------ census
class TestCensus:
    def test_engine_reconciles_within_5pct(self, tmp_path):
        led = _ledger(tmp_path)
        # delta-based: a full-suite process carries live arrays leaked by
        # earlier tests (jit-cache constants etc.), so reconcile the bytes
        # THIS engine adds, not the process-wide absolute. The absolute
        # fresh-process <=5% pin lives in the CI memory-ledger smoke.
        base = led.census()["unattributed_bytes"]
        eng = _engine()
        _put_all(eng)
        eng.generate_all()
        c = led.census(step=1)
        assert c["live_bytes"] > 0
        grown = c["unattributed_bytes"] - base
        assert grown <= 0.05 * c["attributed_bytes"], (grown, c)
        owners = led.owner_bytes()
        assert owners["params"] > 0 and owners["kv_pool"] > 0
        assert owners["device_sched_state"] > 0
        # gauges materialized for every owner
        prom = TELEMETRY.registry.render_prometheus()
        for o in MEMORY_OWNERS:
            assert f'memory_bytes{{owner="{o}"}}' in prom

    def test_drift_alarm_needs_consecutive_censuses(self, tmp_path):
        led = _ledger(tmp_path, drift_threshold=0.0, drift_consecutive=3)
        leak = jnp.zeros(1024)  # held live + unattributed for the test
        leak.block_until_ready()
        assert not led.census()["drift_alarm"]
        assert not led.census()["drift_alarm"]
        c = led.census()  # third consecutive over-threshold census
        assert c["drift_alarm"] and c["drift_alarms_total"] == 1
        assert not led.census()["drift_alarm"]  # streak reset after firing

    def test_readonly_census_leaves_drift_state_alone(self, tmp_path):
        """GET /debug/memory and OOM forensics run read-only censuses: a
        scrape at any cadence must not advance (or reset) the step-loop's
        N-consecutive-census alarm streak."""
        led = _ledger(tmp_path, drift_threshold=0.0, drift_consecutive=3)
        leak = jnp.zeros(1024)
        leak.block_until_ready()
        assert not led.census()["drift_alarm"]
        assert not led.census()["drift_alarm"]  # streak = 2
        for _ in range(5):
            ro = led.census(update_state=False)
            assert not ro["drift_alarm"]
        led.debug_payload()  # endpoint scrape: also read-only
        # third state-updating census still completes the streak exactly
        c = led.census()
        assert c["drift_alarm"] and c["drift_alarms_total"] == 1

    def test_census_interval(self, tmp_path):
        led = _ledger(tmp_path, census_interval_steps=3)
        assert led.maybe_census(1) is None
        assert led.maybe_census(2) is None
        assert led.maybe_census(3) is not None

    def test_lazy_registration_after_configure(self, tmp_path):
        """Ledger configured AFTER engine construction (the common serving
        bring-up order): the per-step hook registers the owners on the
        first telemetry-enabled step instead of never."""
        telemetry.configure(enabled=False)
        eng = _engine()
        assert eng._memledger_handles is None  # nothing to register yet
        led = _ledger(tmp_path)
        _put_all(eng)
        eng.generate_all()
        assert eng._memledger_handles is not None
        owners = led.owner_bytes()
        assert owners["kv_pool"] > 0 and owners["params"] > 0

    def test_reset_state_refreshes_handles(self, tmp_path):
        led = _ledger(tmp_path)
        base = led.census()["unattributed_bytes"]
        eng = _engine()
        _put_all(eng)
        before = led.owner_bytes()["kv_pool"]
        eng.reset_state()
        assert led.owner_bytes()["kv_pool"] == before  # same-shape rebuild
        c = led.census()
        # the rebuilt pool must be re-attributed: only delta-growth allowed
        # (suite processes carry unattributed leftovers from earlier tests)
        grown = c["unattributed_bytes"] - base
        assert grown <= 0.05 * c["attributed_bytes"] + before, (grown, c)

    def test_perfetto_counter_track(self, tmp_path):
        telemetry.configure(enabled=True, tracing=True, memledger={
            "enabled": True, "report_dir": str(tmp_path / "oom")})
        led = TELEMETRY.memledger
        led.register("params", "t", 4096)
        led.refresh_gauges()
        trace = TELEMETRY.dump_trace()
        counters = [e for e in trace["traceEvents"] if e.get("ph") == "C"]
        assert counters and counters[-1]["args"]["params"] == 4096


# ----------------------------------------------------------- OOM forensics
class TestOomForensics:
    def test_alloc_seam_oom_report_and_recovery(self, tmp_path, ref_tokens):
        led = _ledger(tmp_path)
        inj = get_fault_injector()
        inj.arm(POINT_ALLOC, kind="oom", times=1)
        eng = _engine()
        _put_all(eng)
        toks = eng.generate_all()
        assert toks == ref_tokens  # watchdog retried; tokens identical
        assert eng.last_oom_report and os.path.exists(eng.last_oom_report)
        rep = json.load(open(eng.last_oom_report))
        assert rep["seam"] == "alloc"  # alloc seam won the _oom_recorded race
        assert rep["owners"]["kv_pool"] > 0 and rep["owners"]["params"] > 0
        assert "census" in rep and "device" in rep
        assert rep["context"]["free_blocks"] >= 0
        assert led.oom_reports == [eng.last_oom_report]
        prom = TELEMETRY.registry.render_prometheus()
        assert 'oom_events_total{seam="alloc"} 1' in prom

    def test_dispatch_seam_records_once(self, tmp_path, ref_tokens):
        led = _ledger(tmp_path)
        inj = get_fault_injector()
        inj.arm(POINT_DISPATCH, kind="oom", times=1)
        eng = _engine()
        _put_all(eng)
        assert eng.generate_all() == ref_tokens
        assert len(led.oom_reports) == 1
        assert json.load(open(led.oom_reports[0]))["seam"] == "dispatch"

    def test_is_resource_exhausted(self):
        assert is_resource_exhausted(RuntimeError("RESOURCE_EXHAUSTED: oom"))
        assert is_resource_exhausted(ValueError("Out of memory allocating"))
        assert not is_resource_exhausted(RuntimeError("UNAVAILABLE: retry"))
        # the compiler refusing a program is not the device running out
        assert not is_resource_exhausted(RuntimeError(
            "RESOURCE_EXHAUSTED: Ran out of memory in memory space vmem"))
        assert not is_resource_exhausted(RuntimeError(
            "RESOURCE_EXHAUSTED: XLA:TPU compile permanent error. Ran out "
            "of memory in memory space hbm."))

    def test_record_oom_without_ledger_never_raises(self):
        # telemetry off entirely: the seam hook must be inert
        from deepspeed_tpu.telemetry.memledger import record_oom

        assert record_oom("dispatch", RuntimeError("RESOURCE_EXHAUSTED")) \
            is None


# ------------------------------------------------------ headroom admission
class TestHeadroomAdmission:
    def test_unknown_backend_is_static_parity(self, ref_tokens):
        # CPU accelerator: bytes_limit=0 -> headroom -1 even when enabled
        eng = _engine(headroom_admission=True)
        assert eng.admission_headroom_blocks() == -1
        _put_all(eng)
        assert eng.generate_all() == ref_tokens

    def test_ample_headroom_is_parity(self, ref_tokens):
        eng = _engine(headroom_admission=True)
        bb = eng._block_bytes()
        eng._mem_stats_fn = lambda: {
            "bytes_limit": 10_000 * bb, "bytes_in_use": 0}
        assert eng.admission_headroom_blocks() > eng.cfg.num_blocks
        _put_all(eng)
        assert eng.generate_all() == ref_tokens

    def test_headroom_nets_out_preallocated_pool(self):
        """The pool's free blocks are device bytes already funded at init:
        a device that merely LOOKS full because the pool preallocated it
        must not pin admission (the silent-hang regression)."""
        eng = _engine(headroom_admission=True)
        bb = eng._block_bytes()
        free_pool = eng.allocator.free_blocks  # 48: num_blocks-1 usable
        # device "full" but the deficit is exactly the pool's own footprint:
        # headroom = free_dev(10) + pool(48) - guard(5% of 1000 = 50) = 8
        eng._mem_stats_fn = lambda: {
            "bytes_limit": 1000 * bb, "bytes_in_use": 990 * bb}
        assert eng.admission_headroom_blocks() == 10 + free_pool - 50

    def test_scarce_headroom_pins_admission(self):
        eng = _engine(headroom_admission=True)
        bb = eng._block_bytes()
        # external pressure beyond what the pool could fund: free_dev=0,
        # pool credit 48 blocks, guard 5% of 2000 = 100 blocks -> 0
        eng._mem_stats_fn = lambda: {
            "bytes_limit": 2000 * bb, "bytes_in_use": 2000 * bb}
        assert eng.admission_headroom_blocks() == 0
        _put_all(eng)
        eng.step()
        assert not eng._running and len(eng._queued) == 3  # nobody admitted
        # pressure lifts: the same queue drains normally
        eng._mem_stats_fn = lambda: {
            "bytes_limit": 10_000 * bb, "bytes_in_use": 0}
        eng.step()
        assert eng._running

    def test_headroom_stall_alarm_raises(self):
        """A headroom wait that never lifts must become a loud failure,
        not a silent forever-idle loop (the guard suppression bug)."""
        eng = _engine(headroom_admission=True, headroom_stall_alarm_ticks=3)
        bb = eng._block_bytes()
        eng._mem_stats_fn = lambda: {
            "bytes_limit": 5000 * bb, "bytes_in_use": 5000 * bb}
        _put_all(eng)
        eng.step()
        eng.step()
        with pytest.raises(RuntimeError, match="headroom admission stalled"):
            eng.step()

    def test_default_is_off_and_disabled_knob_is_unknown(self):
        eng = _engine()
        assert eng.cfg.headroom_admission is False  # opt-in by default
        eng._mem_stats_fn = lambda: {"bytes_limit": 1 << 40, "bytes_in_use": 0}
        assert eng.admission_headroom_blocks() == -1

    def test_replica_stats_surface_headroom(self):
        from deepspeed_tpu.serving.engine_loop import EngineLoop

        eng = _engine(headroom_admission=True)
        bb = eng._block_bytes()
        free_pool = eng.allocator.free_blocks
        eng._mem_stats_fn = lambda: {
            "bytes_limit": 1000 * bb, "bytes_in_use": 0}
        loop = EngineLoop(eng, name="r0")
        try:
            s = loop.stats()
            assert s.headroom_blocks == 1000 + free_pool - 50
        finally:
            loop.close()

    def test_shrink_retained_to_budget(self):
        alloc = BlockedAllocator(10)
        blocks = alloc.allocate(6)
        for i, b in enumerate(blocks):
            alloc.publish(b, ("k", i))
        alloc.free(blocks)  # refcount 0 published -> retained in the LRU
        assert alloc.retained_blocks == 6
        assert alloc.shrink_retained(2) == 4  # evict LRU down to budget
        assert alloc.retained_blocks == 2
        assert alloc.shrink_retained(5) == 0  # ample budget: no-op


# ----------------------------------------------------------- HBM sampler
class _FakeAccel:
    def memory_stats_all_devices(self):
        return [
            {"bytes_in_use": 100, "bytes_limit": 1000, "bytes_reserved": 160,
             "largest_free_block_bytes": 700, "peak_bytes_in_use": 150},
            {"bytes_in_use": 900, "bytes_limit": 1000, "bytes_reserved": 960},
        ]


class TestHbmSampler:
    def test_per_device_and_fragmentation_gauges(self):
        from deepspeed_tpu.telemetry.memory import HbmWatermarkSampler

        telemetry.configure(enabled=True)
        s = HbmWatermarkSampler(TELEMETRY)
        s._accelerator = _FakeAccel()
        out = s.sample(step=1)
        assert out["bytes_in_use"] == 100  # device-0 legacy aggregate
        prom = TELEMETRY.registry.render_prometheus()
        assert 'hbm_device_bytes_in_use{device="1"} 900' in prom
        assert 'hbm_fragmentation_bytes{device="0"} 60' in prom
        assert 'hbm_fragmentation_bytes{device="1"} 60' in prom
        assert 'hbm_largest_free_block_bytes{device="0"} 700' in prom

    def test_no_stats_backend_goes_silent(self):
        from deepspeed_tpu.telemetry.memory import HbmWatermarkSampler

        telemetry.configure(enabled=True)

        class Broken:
            def memory_stats_all_devices(self):
                raise RuntimeError("no stats")

        s = HbmWatermarkSampler(TELEMETRY)
        s._accelerator = Broken()
        assert s.sample() == {}
        assert s._broken and s.sample() == {}


# -------------------------------------------------------------- off is free
class TestOffIsFree:
    def test_disabled_ledger_zero_allocations(self, ref_tokens):
        """Telemetry (and therefore the ledger) off: serving a full batch
        must execute zero memledger.py code — pinned by tracemalloc."""
        eng = _engine()
        _put_all(eng)
        tracemalloc.start()
        try:
            toks = eng.generate_all()
            snap = tracemalloc.take_snapshot()
        finally:
            tracemalloc.stop()
        assert toks == ref_tokens
        stats = snap.filter_traces([tracemalloc.Filter(
            True, "*/telemetry/memledger.py")]).statistics("filename")
        total = sum(s.size for s in stats)
        assert total == 0, f"memledger allocated {total}B while disabled"

    def test_ledger_on_tokens_identical(self, tmp_path, ref_tokens):
        _ledger(tmp_path, census_interval_steps=2)
        eng = _engine()
        _put_all(eng)
        assert eng.generate_all() == ref_tokens

    def test_debug_payload_serializable(self, tmp_path):
        led = _ledger(tmp_path)
        eng = _engine()
        _put_all(eng)
        eng.generate_all()
        payload = led.debug_payload()
        assert payload["enabled"] is True
        json.dumps(payload)
        assert payload["census"]["live_bytes"] > 0
        assert payload["owners"]["kv_pool"] > 0
