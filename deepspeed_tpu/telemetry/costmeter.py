"""Request-level cost attribution and per-tenant metering.

The observability plane can already answer "what is the fleet doing"
(fleet.py), "where did the step go" (stepscope/devprof) and "who owns the
HBM" (memledger) — this module answers "**who is consuming the capacity**".
Every served request accumulates a :class:`RequestCost` record at the seams
the ragged engine already owns:

- prefill tokens x analytic FLOPs/token (``flops_profiler.get_model_profile``)
- decode tokens and host dispatches
- KV **block-seconds**: the occupancy integral of the request's blocks from
  admission to release, including a retained-prefix carveout credited to
  the *publishing* tenant while its blocks sit in the cache, and a
  credit/debit transfer when another tenant's request splices them
- tier promote/demote bytes, handoff export/import bytes, queue wait

Finished records are folded into a ``request_cost_*{tenant=,sla_class=}``
counter/histogram family and a rolling :class:`TenantLedger`. Label
cardinality is bounded: the meter keeps an LRU of at most ``max_tenants``
distinct tenant label values and folds overflow into ``tenant="__other__"``
(the ledger itself keeps exact per-tenant rows up to a larger hard cap so
`/debug/tenants` stays useful even past the label cap).

Off by default: the meter only exists when
``telemetry.configure(costmeter=...)`` asked for it, every engine seam
guards on one attribute read, and with the meter off the serving hot path
executes zero code from this file (tracemalloc-pinned in
``tests/unit/test_costmeter.py``).
"""

from __future__ import annotations

import threading
import time
from collections import OrderedDict, deque
from dataclasses import dataclass, field

# overflow label once the distinct-tenant LRU cap is hit
OTHER_TENANT = "__other__"

# hard bound on exact ledger rows (not metric series) — beyond this even
# /debug/tenants folds into the overflow row
LEDGER_MAX_ROWS = 1024

# per-request block-seconds histogram buckets: 1ms .. ~5 min of one block
BLOCK_SECONDS_BUCKETS = tuple(0.001 * (4 ** p) for p in range(10))


@dataclass
class RequestCost:
    """Per-request resource-consumption record, accumulated in place by the
    engine and folded into the meter exactly once at release."""

    tenant: str = "default"
    sla_class: str = "interactive"
    prefill_tokens: int = 0
    prefill_flops: float = 0.0
    decode_tokens: int = 0
    decode_dispatches: int = 0
    kv_block_seconds: float = 0.0
    prefix_credit_blocks: int = 0   # cached blocks this request published
    prefix_debit_blocks: int = 0    # cached blocks spliced from other tenants
    tier_promote_bytes: int = 0
    tier_demote_bytes: int = 0
    handoff_export_bytes: int = 0
    handoff_import_bytes: int = 0
    queue_wait_s: float = 0.0

    def span_attrs(self) -> dict:
        """Attributes merged into the finished ``inference/request`` span."""
        return {
            "tenant": self.tenant,
            "sla_class": self.sla_class,
            "cost_prefill_flops": self.prefill_flops,
            "cost_decode_dispatches": self.decode_dispatches,
            "cost_kv_block_seconds": round(self.kv_block_seconds, 6),
            "cost_tier_promote_bytes": self.tier_promote_bytes,
            "cost_tier_demote_bytes": self.tier_demote_bytes,
            "cost_handoff_bytes": (self.handoff_export_bytes
                                   + self.handoff_import_bytes),
        }


@dataclass
class _TenantRow:
    """One tenant's cumulative ledger row."""

    tenant: str
    requests: int = 0
    prefill_tokens: int = 0
    prefill_flops: float = 0.0
    decode_tokens: int = 0
    decode_dispatches: int = 0
    kv_block_seconds: float = 0.0
    retained_block_seconds: float = 0.0
    prefix_credit_blocks: int = 0
    prefix_debit_blocks: int = 0
    tier_promote_bytes: int = 0
    tier_demote_bytes: int = 0
    handoff_bytes: int = 0
    queue_wait_s: float = 0.0
    outstanding_blocks: int = 0     # live blocks right now (set each tick)
    by_class: dict = field(default_factory=dict)  # sla_class -> requests

    def as_dict(self) -> dict:
        return {
            "tenant": self.tenant,
            "requests": self.requests,
            "prefill_tokens": self.prefill_tokens,
            "prefill_flops": self.prefill_flops,
            "decode_tokens": self.decode_tokens,
            "decode_dispatches": self.decode_dispatches,
            "kv_block_seconds": round(self.kv_block_seconds, 6),
            "retained_block_seconds": round(self.retained_block_seconds, 6),
            "prefix_credit_blocks": self.prefix_credit_blocks,
            "prefix_debit_blocks": self.prefix_debit_blocks,
            "tier_promote_bytes": self.tier_promote_bytes,
            "tier_demote_bytes": self.tier_demote_bytes,
            "handoff_bytes": self.handoff_bytes,
            "queue_wait_s": round(self.queue_wait_s, 6),
            "outstanding_blocks": self.outstanding_blocks,
            "by_class": dict(self.by_class),
        }


class TenantLedger:
    """Rolling per-tenant aggregator behind `/debug/tenants` and the
    router's fair-share signal. Cumulative rows plus a pruned window of
    recent request finishes for rate estimates."""

    def __init__(self, window_s: float = 300.0,
                 max_rows: int = LEDGER_MAX_ROWS):
        self.window_s = float(window_s)
        self.max_rows = int(max_rows)
        self._rows: dict[str, _TenantRow] = {}
        # (monotonic, tenant, decode_tokens, kv_block_seconds)
        self._recent: deque = deque()
        self._lock = threading.Lock()

    def _row_locked(self, tenant: str) -> _TenantRow:
        row = self._rows.get(tenant)
        if row is None:
            if len(self._rows) >= self.max_rows:
                tenant = OTHER_TENANT
                row = self._rows.get(tenant)
                if row is None:
                    row = self._rows[tenant] = _TenantRow(tenant)
            else:
                row = self._rows[tenant] = _TenantRow(tenant)
        return row

    def charge(self, cost: RequestCost, now: float | None = None) -> None:
        """Fold one finished request into its tenant's row."""
        t = time.monotonic() if now is None else now
        with self._lock:
            row = self._row_locked(cost.tenant)
            row.requests += 1
            row.prefill_tokens += cost.prefill_tokens
            row.prefill_flops += cost.prefill_flops
            row.decode_tokens += cost.decode_tokens
            row.decode_dispatches += cost.decode_dispatches
            row.kv_block_seconds += cost.kv_block_seconds
            row.prefix_credit_blocks += cost.prefix_credit_blocks
            row.prefix_debit_blocks += cost.prefix_debit_blocks
            row.tier_promote_bytes += cost.tier_promote_bytes
            row.tier_demote_bytes += cost.tier_demote_bytes
            row.handoff_bytes += (cost.handoff_export_bytes
                                  + cost.handoff_import_bytes)
            row.queue_wait_s += cost.queue_wait_s
            cls = cost.sla_class
            row.by_class[cls] = row.by_class.get(cls, 0) + 1
            self._recent.append((t, row.tenant, cost.decode_tokens,
                                 cost.kv_block_seconds))
            self._prune_locked(t)

    def add_retained(self, tenant: str, block_seconds: float) -> None:
        """Credit retained-prefix occupancy to the publishing tenant."""
        with self._lock:
            self._row_locked(tenant).retained_block_seconds += block_seconds

    def transfer(self, publisher: str, consumer: str, blocks: int) -> None:
        """Cross-tenant prefix splice: credit the publisher, debit the
        consumer — symmetric by construction."""
        with self._lock:
            self._row_locked(publisher).prefix_credit_blocks += blocks
            self._row_locked(consumer).prefix_debit_blocks += blocks

    def set_outstanding(self, blocks_by_tenant: dict) -> None:
        """Refresh the live-block view (the fair-share input) each tick."""
        with self._lock:
            for row in self._rows.values():
                row.outstanding_blocks = 0
            for tenant, n in blocks_by_tenant.items():
                self._row_locked(tenant).outstanding_blocks = int(n)

    def _prune_locked(self, now: float) -> None:
        horizon = now - self.window_s
        recent = self._recent
        while recent and recent[0][0] < horizon:
            recent.popleft()

    # --------------------------------------------------------------- queries
    def outstanding_share(self, tenant: str) -> tuple[float, float]:
        """(tenant's share of live blocks, fair share). Fair share is
        ``1 / active_tenants``; with one active tenant both are 1.0, so the
        soft fairness penalty vanishes exactly (single-tenant parity)."""
        with self._lock:
            live = {t: r.outstanding_blocks for t, r in self._rows.items()
                    if r.outstanding_blocks > 0}
            total = sum(live.values())
            if total <= 0 or not live:
                return 0.0, 1.0
            n_active = len(live) if tenant in live else len(live) + 1
            return live.get(tenant, 0) / total, 1.0 / n_active

    def rows(self) -> list[dict]:
        with self._lock:
            return [r.as_dict() for r in self._rows.values()]

    def recent_rates(self, now: float | None = None) -> dict:
        """Per-tenant decode tokens/s and block-seconds/s over the rolling
        window (rates go to zero as an idle tenant ages out)."""
        t = time.monotonic() if now is None else now
        with self._lock:
            self._prune_locked(t)
            out: dict[str, dict] = {}
            for ts, tenant, toks, bs in self._recent:
                d = out.setdefault(tenant, {"decode_tokens": 0,
                                            "kv_block_seconds": 0.0})
                d["decode_tokens"] += toks
                d["kv_block_seconds"] += bs
        w = self.window_s
        return {k: {"decode_tokens_per_s": v["decode_tokens"] / w,
                    "kv_block_seconds_per_s": v["kv_block_seconds"] / w}
                for k, v in out.items()}


class CostMeter:
    """The metering plane: owns the ledger, the bounded-cardinality label
    map, and the ``request_cost_*`` metric family."""

    def __init__(self, registry, max_tenants: int = 32,
                 window_s: float = 300.0, top_k: int = 10,
                 fairness_weight: float = 1.0):
        self._registry = registry
        self.max_tenants = int(max_tenants)
        self.top_k = int(top_k)
        # scales the router's soft fair-share penalty (0 disables steering
        # while keeping measurement on)
        self.fairness_weight = float(fairness_weight)
        self.ledger = TenantLedger(window_s=window_s)
        # LRU of tenant -> label value actually published; once full, new
        # tenants map to OTHER_TENANT (fold counted for the docs/tests)
        self._labels: OrderedDict[str, str] = OrderedDict()
        self._lock = threading.Lock()
        self.label_folds = 0
        reg = registry
        self._c_prefill_tok = reg.counter(
            "request_cost_prefill_tokens_total",
            "prompt tokens prefilled, by tenant")
        self._c_prefill_flops = reg.counter(
            "request_cost_prefill_flops_total",
            "analytic forward FLOPs spent on prefill, by tenant")
        self._c_decode_tok = reg.counter(
            "request_cost_decode_tokens_total",
            "tokens decoded, by tenant")
        self._c_dispatches = reg.counter(
            "request_cost_decode_dispatches_total",
            "host dispatches a request participated in, by tenant")
        self._c_block_s = reg.counter(
            "request_cost_kv_block_seconds_total",
            "KV block-seconds consumed (occupancy integral), by tenant")
        self._c_retained_s = reg.counter(
            "request_cost_retained_block_seconds_total",
            "retained-prefix block-seconds credited to the publisher")
        self._c_promote_b = reg.counter(
            "request_cost_tier_promote_bytes_total",
            "KV bytes restored from lower tiers on admission, by tenant")
        self._c_demote_b = reg.counter(
            "request_cost_tier_demote_bytes_total",
            "published KV bytes demoted tier-ward, by publishing tenant")
        self._c_handoff_b = reg.counter(
            "request_cost_handoff_bytes_total",
            "KV handoff bytes moved (export + import), by tenant")
        self._c_queue_s = reg.counter(
            "request_cost_queue_wait_seconds_total",
            "seconds requests waited for admission, by tenant")
        self._c_pool_s = reg.counter(
            "request_cost_pool_block_seconds_total",
            "pool-wide busy block-seconds (the attribution denominator)")
        self._c_folds = reg.counter(
            "request_cost_label_folds_total",
            "requests whose tenant label folded into __other__")
        self._h_block_s = reg.histogram(
            "request_cost_block_seconds", "per-request KV block-seconds",
            buckets=BLOCK_SECONDS_BUCKETS)

    # ----------------------------------------------------------- label cap
    def tenant_label(self, tenant: str) -> str:
        """Bounded-cardinality label for ``tenant``: at most
        ``max_tenants`` distinct values ever reach the registry; later
        tenants fold into ``__other__`` (LRU refresh keeps hot tenants
        labeled through churn)."""
        with self._lock:
            if tenant in self._labels:
                self._labels.move_to_end(tenant)
                return tenant
            if len(self._labels) < self.max_tenants:
                self._labels[tenant] = tenant
                return tenant
            self.label_folds += 1
        self._c_folds.inc()
        return OTHER_TENANT

    # --------------------------------------------------------- accumulation
    def start(self, tenant: str, sla_class: str) -> RequestCost:
        """Fresh per-request record (attached to the engine's seq state)."""
        return RequestCost(tenant=tenant, sla_class=sla_class)

    def tick(self, dt: float, live, retained=None,
             pool_busy_blocks: int = 0) -> None:
        """Advance the occupancy integral by ``dt`` seconds.

        ``live`` iterates ``(RequestCost, n_blocks)`` for every sequence
        currently holding blocks (running, queued-with-reservation and
        parked handoffs alike); ``retained`` iterates
        ``(publisher_tenant, n_blocks)`` for refcount-0 cached blocks.
        ``pool_busy_blocks`` is the allocator's total non-free block count —
        the denominator the per-tenant integrals must sum to (the invariant
        ``tests/unit/test_costmeter.py`` pins at +-5%).
        """
        if dt <= 0.0:
            return
        outstanding: dict[str, int] = {}
        for cost, n in live:
            if n <= 0:
                continue
            cost.kv_block_seconds += n * dt
            outstanding[cost.tenant] = outstanding.get(cost.tenant, 0) + n
        if retained:
            for tenant, n in retained:
                if n <= 0:
                    continue
                self.ledger.add_retained(tenant, n * dt)
                self._c_retained_s.inc(n * dt,
                                       tenant=self.tenant_label(tenant))
                outstanding[tenant] = outstanding.get(tenant, 0) + n
        if pool_busy_blocks > 0:
            self._c_pool_s.inc(pool_busy_blocks * dt)
        self.ledger.set_outstanding(outstanding)

    def prefix_transfer(self, publisher: str, consumer: str,
                        blocks: int) -> None:
        """Cross-request prefix hit across tenants: the consumer's debit is
        the publisher's credit, block for block."""
        if blocks <= 0 or publisher == consumer:
            return
        self.ledger.transfer(publisher, consumer, blocks)

    def observe(self, cost: RequestCost) -> None:
        """Fold one finished request into the ledger and metric family."""
        self.ledger.charge(cost)
        labels = {"tenant": self.tenant_label(cost.tenant),
                  "sla_class": cost.sla_class}
        if cost.prefill_tokens:
            self._c_prefill_tok.inc(cost.prefill_tokens, **labels)
        if cost.prefill_flops:
            self._c_prefill_flops.inc(cost.prefill_flops, **labels)
        if cost.decode_tokens:
            self._c_decode_tok.inc(cost.decode_tokens, **labels)
        if cost.decode_dispatches:
            self._c_dispatches.inc(cost.decode_dispatches, **labels)
        self._c_block_s.inc(cost.kv_block_seconds, **labels)
        if cost.tier_promote_bytes:
            self._c_promote_b.inc(cost.tier_promote_bytes, **labels)
        if cost.tier_demote_bytes:
            # demotions are publisher-attributed, not class-attributed
            self._c_demote_b.inc(cost.tier_demote_bytes,
                                 tenant=labels["tenant"])
        if cost.handoff_export_bytes or cost.handoff_import_bytes:
            self._c_handoff_b.inc(cost.handoff_export_bytes
                                  + cost.handoff_import_bytes, **labels)
        if cost.queue_wait_s:
            self._c_queue_s.inc(cost.queue_wait_s, **labels)
        self._h_block_s.observe(cost.kv_block_seconds, **labels)

    def demote_bytes(self, tenant: str, nbytes: int) -> None:
        """Tier demotion happens after the publishing request finished, so
        it is charged straight to the ledger/counters, not a RequestCost."""
        if nbytes <= 0:
            return
        with self.ledger._lock:
            self.ledger._row_locked(tenant).tier_demote_bytes += nbytes
        self._c_demote_b.inc(nbytes, tenant=self.tenant_label(tenant))

    # ----------------------------------------------------- routing signal
    def outstanding_share(self, tenant: str) -> tuple[float, float]:
        return self.ledger.outstanding_share(tenant)

    # ------------------------------------------------------------- payload
    def debug_payload(self) -> dict:
        """JSON-serializable breakdown for ``GET /debug/tenants``: every
        ledger row plus the top-K tenants by cumulative block-seconds."""
        rows = self.ledger.rows()
        rows.sort(key=lambda r: r["kv_block_seconds"], reverse=True)
        pool_s = self._c_pool_s.value()
        return {
            "enabled": True,
            "tenants": {r["tenant"]: r for r in rows},
            "top_by_block_seconds": [
                {"tenant": r["tenant"],
                 "kv_block_seconds": r["kv_block_seconds"]}
                for r in rows[:self.top_k]],
            "pool_block_seconds": round(pool_s, 6),
            "recent_rates": self.ledger.recent_rates(),
            "distinct_tenant_labels": len(self._labels),
            "label_folds": self.label_folds,
            "max_tenant_labels": self.max_tenants,
        }
