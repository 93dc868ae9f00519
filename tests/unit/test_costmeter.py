"""Request-level cost attribution (telemetry/costmeter.py +
docs/OBSERVABILITY.md "Cost attribution & tenant metering"):

- the occupancy-integral invariant: per-tenant KV block-seconds (live +
  retained carveout) must sum to the pool's busy-block integral (+-5%)
- cross-tenant prefix reuse is a symmetric credit/debit transfer
- tenant label cardinality is bounded (LRU cap, overflow folds into
  ``__other__``) while the ledger keeps exact rows
- meter off: the serving hot path executes ZERO costmeter.py code
  (tracemalloc-pinned) and tokens are identical to the unmetered engine
- per-SLA-class SLO windows burn independently (a batch backlog cannot
  flip the interactive objective, or vice versa)
"""

import json
import time
import tracemalloc

import jax.numpy as jnp
import numpy as np
import pytest

from deepspeed_tpu import telemetry
from deepspeed_tpu.inference.ragged import RaggedConfig, RaggedInferenceEngine
from deepspeed_tpu.models import llama
from deepspeed_tpu.telemetry import (
    TELEMETRY,
    CostMeter,
    MetricsRegistry,
    OTHER_TENANT,
    RequestCost,
    SloMonitor,
    TenantLedger,
    default_class_objectives,
    default_objectives,
)

CFG = llama.LlamaConfig(
    vocab_size=97, hidden_size=32, intermediate_size=64,
    num_layers=2, num_heads=4, num_kv_heads=2, max_seq_len=128,
)
PCFG = dict(
    max_tokens_per_step=16, max_seqs=3, block_size=4, num_blocks=49,
    max_blocks_per_seq=16, prefill_tile=0, device_state=False)


def _engine(**over):
    rcfg = RaggedConfig(**{**PCFG, **over})
    return RaggedInferenceEngine(
        lambda ctx: llama.build(CFG, ctx=ctx), rcfg,
        dtype=jnp.float32, seed=0)


def _prompt(n, seed=0):
    return [int(t) for t in
            np.random.default_rng(seed).integers(0, CFG.vocab_size, n)]


def _meter(**over):
    telemetry.configure(enabled=True,
                        costmeter={"enabled": True, **over})
    return TELEMETRY.costmeter


@pytest.fixture(autouse=True, scope="module")
def _reset_telemetry():
    yield
    telemetry.configure(enabled=False)


@pytest.fixture(scope="module")
def ref_tokens():
    """Meter-off reference: every meter-on run must match."""
    telemetry.configure(enabled=False)
    eng = _engine()
    for i in range(3):
        eng.put(i, _prompt(9, seed=i), max_new_tokens=5)
    return eng.generate_all()


# ------------------------------------------------------------ pure ledger
class TestLedger:
    def test_transfer_symmetry(self):
        led = TenantLedger()
        led.transfer("pub", "con", 3)
        led.transfer("pub", "con", 2)
        rows = {r["tenant"]: r for r in led.rows()}
        assert rows["pub"]["prefix_credit_blocks"] == 5
        assert rows["con"]["prefix_debit_blocks"] == 5
        assert rows["pub"]["prefix_debit_blocks"] == 0
        assert rows["con"]["prefix_credit_blocks"] == 0

    def test_outstanding_share_single_tenant_parity(self):
        led = TenantLedger()
        led.set_outstanding({"only": 7})
        share, fair = led.outstanding_share("only")
        assert share == 1.0 and fair == 1.0  # penalty vanishes exactly

    def test_outstanding_share_multi_tenant(self):
        led = TenantLedger()
        led.set_outstanding({"hog": 9, "small": 3})
        share, fair = led.outstanding_share("hog")
        assert share == pytest.approx(0.75) and fair == pytest.approx(0.5)

    def test_label_cap_folds_to_other(self):
        reg = MetricsRegistry()
        cm = CostMeter(reg, max_tenants=2)
        for t in ("a", "b", "c", "d"):
            cost = RequestCost(tenant=t, sla_class="interactive")
            cost.decode_tokens = 1
            cost.kv_block_seconds = 0.5
            cm.observe(cost)
        prom = reg.render_prometheus()
        assert 'tenant="a"' in prom and 'tenant="b"' in prom
        assert 'tenant="c"' not in prom and 'tenant="d"' not in prom
        assert f'tenant="{OTHER_TENANT}"' in prom
        assert cm.label_folds >= 2
        # the ledger keeps EXACT rows past the label cap
        rows = {r["tenant"] for r in cm.ledger.rows()}
        assert {"a", "b", "c", "d"} <= rows
        payload = cm.debug_payload()
        json.dumps(payload)  # /debug/tenants must stay serializable
        assert payload["distinct_tenant_labels"] == 2
        assert payload["label_folds"] >= 2

    def test_tick_accumulates_and_attributes(self):
        reg = MetricsRegistry()
        cm = CostMeter(reg)
        a = cm.start("a", "interactive")
        b = cm.start("b", "batch")
        cm.tick(2.0, [(a, 3), (b, 1)], retained=[("a", 2)],
                pool_busy_blocks=6)
        assert a.kv_block_seconds == pytest.approx(6.0)
        assert b.kv_block_seconds == pytest.approx(2.0)
        rows = {r["tenant"]: r for r in cm.ledger.rows()}
        assert rows["a"]["retained_block_seconds"] == pytest.approx(4.0)
        # per-tenant integrals sum to the pool integral exactly here
        assert 6.0 + 2.0 + 4.0 == pytest.approx(6 * 2.0)


# ----------------------------------------------------- engine attribution
class TestEngineAttribution:
    def test_block_seconds_sum_matches_pool_integral(self):
        """Distinct prompts (no cross-seq block sharing): the per-tenant
        occupancy integrals must reconstruct the pool's busy integral."""
        cm = _meter()
        eng = _engine(enable_prefix_cache=True)
        for i in range(3):
            eng.put(i, _prompt(9, seed=10 + i), max_new_tokens=5,
                    tenant=f"t{i % 2}",
                    sla_class="interactive" if i % 2 else "batch")
        eng.generate_all()
        payload = cm.debug_payload()
        per_tenant = sum(
            r["kv_block_seconds"] + r["retained_block_seconds"]
            for r in payload["tenants"].values())
        pool = payload["pool_block_seconds"]
        assert pool > 0
        assert per_tenant == pytest.approx(pool, rel=0.05)

    def test_cross_tenant_prefix_credit_debit(self):
        """Tenant B splicing blocks tenant A published is a symmetric
        ledger transfer: A's credit == B's debit == spliced blocks."""
        cm = _meter()
        eng = _engine(enable_prefix_cache=True)
        shared = _prompt(8, seed=42)  # two full blocks at block_size=4
        eng.put("pub", shared, max_new_tokens=2, tenant="alice")
        eng.generate_all()
        eng.put("con", shared + _prompt(4, seed=43), max_new_tokens=2,
                tenant="bob")
        eng.generate_all()
        rows = {r["tenant"]: r for r in cm.ledger.rows()}
        credit = rows["alice"]["prefix_credit_blocks"]
        debit = rows["bob"]["prefix_debit_blocks"]
        assert credit == debit == 2
        assert rows["bob"]["prefix_credit_blocks"] == 0

    def test_queue_and_prefill_charged(self):
        cm = _meter()
        eng = _engine()
        eng.put(0, _prompt(9, seed=7), max_new_tokens=3, tenant="q")
        eng.generate_all()
        row = {r["tenant"]: r for r in cm.ledger.rows()}["q"]
        assert row["prefill_tokens"] == 9
        assert row["decode_tokens"] >= 3
        assert row["decode_dispatches"] >= 1
        assert row["requests"] == 1

    def test_reset_state_finalizes_costs(self):
        cm = _meter()
        eng = _engine()
        eng.put(0, _prompt(9, seed=3), max_new_tokens=40, tenant="rz")
        eng.step()
        eng.reset_state()
        rows = {r["tenant"]: r for r in cm.ledger.rows()}
        assert rows["rz"]["requests"] == 1  # folded exactly once
        assert not eng._block_tenant


# ------------------------------------------------------------ off is free
class TestOffIsFree:
    def test_meter_off_zero_allocations(self, ref_tokens):
        """Telemetry on but the meter off: serving a full batch must
        execute zero costmeter.py code — pinned by tracemalloc."""
        telemetry.configure(enabled=True)
        assert TELEMETRY.costmeter is None
        eng = _engine()
        for i in range(3):
            eng.put(i, _prompt(9, seed=i), max_new_tokens=5)
        tracemalloc.start()
        try:
            toks = eng.generate_all()
            snap = tracemalloc.take_snapshot()
        finally:
            tracemalloc.stop()
        assert toks == ref_tokens
        stats = snap.filter_traces([tracemalloc.Filter(
            True, "*/telemetry/costmeter.py")]).statistics("filename")
        total = sum(s.size for s in stats)
        assert total == 0, f"costmeter allocated {total}B while disabled"

    def test_meter_on_tokens_identical(self, ref_tokens):
        _meter()
        eng = _engine()
        for i in range(3):
            eng.put(i, _prompt(9, seed=i), max_new_tokens=5,
                    tenant=f"t{i}")
        assert eng.generate_all() == ref_tokens


# ------------------------------------------------------- per-class SLO
class TestClassSlo:
    def _monitor(self, reg=None):
        reg = reg or MetricsRegistry()
        return SloMonitor(
            default_objectives(window_s=60.0), reg,
            class_objectives=default_class_objectives(window_s=60.0)), reg

    def test_batch_breach_does_not_flip_interactive(self):
        mon, reg = self._monitor()
        # breaching_classes() reads the real monotonic clock, so the
        # samples must sit inside its window, not at a synthetic epoch
        now = time.monotonic()
        for i in range(10):
            # terrible for batch (threshold 5s), recorded against batch only
            mon.record("ttft", 20.0, now=now + i, sla_class="batch")
            # healthy interactive samples
            mon.record("ttft", 0.01, now=now + i, sla_class="interactive")
        t = now + 10
        assert mon.stats("ttft", now=t, sla_class="batch")["breaching"]
        assert not mon.stats("ttft", now=t,
                             sla_class="interactive")["breaching"]
        assert ("batch", "ttft") in mon.breaching_classes()
        assert ("interactive", "ttft") not in mon.breaching_classes()
        prom = reg.render_prometheus()
        assert 'slo_good_fraction{objective="ttft",sla_class="batch"}' in prom
        assert ('slo_good_fraction{objective="ttft",'
                'sla_class="interactive"}') in prom

    def test_class_thresholds_differ(self):
        mon, _ = self._monitor()
        now = 2000.0
        # 1s TTFT: bad for interactive (0.5s), fine for batch (5s)
        for i in range(10):
            mon.record("ttft", 1.0, now=now + i, sla_class="interactive")
            mon.record("ttft", 1.0, now=now + i, sla_class="batch")
        t = now + 10
        assert mon.stats("ttft", now=t,
                         sla_class="interactive")["breaching"]
        assert not mon.stats("ttft", now=t, sla_class="batch")["breaching"]

    def test_health_includes_by_class(self):
        mon, _ = self._monitor()
        mon.record("ttft", 0.1, now=10.0, sla_class="interactive")
        h = mon.health()
        assert "by_class" in h
        assert "interactive" in h["by_class"]
        assert "ttft" in h["by_class"]["interactive"]
