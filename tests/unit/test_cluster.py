"""Disaggregated prefill/decode serving (serving/cluster.py).

The contract under test: a request whose prompt runs on a dedicated
prefill replica and whose decode resumes on a different replica via the
KV-handoff record generates EXACTLY the tokens a single engine would —
greedy and sampled-with-fixed-seed, in every dispatch mode. Plus the
cluster-wide prefix index (a replica that never saw a prompt can serve
its cached prefix after a block transfer), the role-aware placement
invariants (decode traffic never lands on a prefill replica), handoff
failover (prefill death mid-handoff, decode import rejection), the
stale-probe re-validation at admission, and the SLO-burn decode
autoscaler policy.
"""

import http.client
import json
import time

import jax.numpy as jnp
import numpy as np
import pytest
from step_modes import MODES  # tests/unit is rootdir-inserted by pytest

from deepspeed_tpu.inference.ragged import RaggedConfig, RaggedInferenceEngine
from deepspeed_tpu.models import llama
from deepspeed_tpu.serving import (
    ClusterConfig,
    ClusterPrefixIndex,
    CompletionRequest,
    DecodeAutoscaler,
    EngineLoop,
    ReplicaRouter,
    ReplicaStats,
    RouterConfig,
    ServingCluster,
    build_cluster_server,
    plan_placement,
    transfer_beats_prefill,
)
from deepspeed_tpu.utils.faults import POINT_LOOP, get_fault_injector

CFG = llama.LlamaConfig(
    vocab_size=97, hidden_size=32, intermediate_size=64,
    num_layers=2, num_heads=4, num_kv_heads=2, max_seq_len=128,
)

BS = 4  # block size used throughout — prompts below are built around it


def _engine(cache=False, params=None, **over):
    kw = dict(max_tokens_per_step=16, max_seqs=3, block_size=BS,
              num_blocks=49, max_blocks_per_seq=16,
              enable_prefix_cache=cache)
    kw.update(over)
    return RaggedInferenceEngine(
        model=lambda ctx: llama.build(CFG, ctx=ctx),
        ragged_config=RaggedConfig(**kw), dtype=jnp.float32, seed=0,
        params=params)


SHARED = [11, 7, 3, 5, 2, 13, 17, 19]          # two full blocks of 4
PROMPT_A = SHARED + [23, 29, 31]
PROMPT_B = SHARED + [37, 41]
SAMPLED = dict(temperature=0.9, top_k=20, seed=123)
MAX_NEW = 6


def _run(eng, uid):
    deadline = time.perf_counter() + 120
    while uid not in eng.finished_uids:
        assert time.perf_counter() < deadline, "engine did not finish"
        eng.step()
    return list(eng._results[uid].generated)


@pytest.fixture(scope="module")
def ref_tokens():
    """Single-engine reference generations every split run must match."""
    eng = _engine()
    eng.put("ga", PROMPT_A, max_new_tokens=MAX_NEW)
    eng.put("sa", PROMPT_A, max_new_tokens=MAX_NEW, **SAMPLED)
    eng.put("gb", PROMPT_B, max_new_tokens=MAX_NEW)
    out = eng.generate_all()
    return {k: list(v) for k, v in out.items()}


# ----------------------------------------------------- transfer cost model
class TestTransferCostModel:
    def test_fast_link_small_kv_prefers_transfer(self):
        cfg = ClusterConfig(transfer_gbps=100.0, prefill_tokens_per_s=1000.0)
        assert transfer_beats_prefill(64, bytes_per_token=1024, cfg=cfg)

    def test_slow_link_fat_kv_prefers_prefill(self):
        cfg = ClusterConfig(transfer_gbps=0.001,
                            prefill_tokens_per_s=1_000_000.0)
        assert not transfer_beats_prefill(64, bytes_per_token=1 << 20,
                                          cfg=cfg)

    def test_zero_length_prefix_never_transfers(self):
        cfg = ClusterConfig(transfer_gbps=100.0, prefill_tokens_per_s=1000.0)
        assert not transfer_beats_prefill(0, bytes_per_token=1024, cfg=cfg)
        assert not transfer_beats_prefill(-3, bytes_per_token=1024, cfg=cfg)

    def test_exact_cost_tie_prefers_prefill(self):
        # wire: 125_000 B/token * 8 b/B / 1 Gb/s = 1 ms/token;
        # prefill: 1000 tok/s = 1 ms/token — a dead tie must NOT transfer
        # (strict <: the local prefill avoids the channel's failure modes)
        cfg = ClusterConfig(transfer_gbps=1.0, prefill_tokens_per_s=1000.0)
        assert not transfer_beats_prefill(64, bytes_per_token=125_000,
                                          cfg=cfg)
        # one byte under the tie flips it
        assert transfer_beats_prefill(64, bytes_per_token=124_999, cfg=cfg)

    def test_unknown_bandwidth_is_conservative(self):
        # an unreported (-1) bandwidth or prefill rate would go negative in
        # the divisor and claim a free wire — both must mean "no transfer"
        cfg = ClusterConfig(transfer_gbps=-1.0, prefill_tokens_per_s=1000.0)
        assert not transfer_beats_prefill(64, bytes_per_token=16, cfg=cfg)
        cfg = ClusterConfig(transfer_gbps=100.0, prefill_tokens_per_s=-1.0)
        assert not transfer_beats_prefill(64, bytes_per_token=16, cfg=cfg)


# ----------------------------------------------------- cluster prefix index
def _chain(tokens):
    """Hash-chain keys for full blocks of ``tokens`` — the allocator's
    exact keying: (parent_key, tuple(block_tokens))."""
    keys, key = [], None
    for i in range(len(tokens) // BS):
        key = (key, tuple(tokens[i * BS:(i + 1) * BS]))
        keys.append(key)
    return keys


class TestClusterPrefixIndex:
    def test_best_holder_longest_contiguous_chain(self):
        idx = ClusterPrefixIndex()
        k1, k2 = _chain(SHARED)
        idx.publish("A", k1)
        idx.publish("A", k2)
        idx.publish("B", k1)
        prompt = SHARED + [1]  # 9 tokens: both blocks eligible
        assert idx.best_holder(prompt, BS) == (8, "A")
        # coverage must be on a SINGLE replica: excluding A falls back to
        # B's one-block chain, not a two-replica stitch
        assert idx.best_holder(prompt, BS,
                               exclude=frozenset({"A"})) == (4, "B")
        assert idx.hits == 2

    def test_missing_root_is_a_miss(self):
        idx = ClusterPrefixIndex()
        _, k2 = _chain(SHARED)
        idx.publish("A", k2)  # link without its root: unusable for a splice
        assert idx.best_holder(SHARED + [1], BS) == (0, None)
        assert idx.misses == 1

    def test_match_capped_one_block_short_of_prompt(self):
        idx = ClusterPrefixIndex()
        for k in _chain(SHARED):
            idx.publish("A", k)
        # 8-token prompt: only (8-1)//4 = 1 block may splice — a full
        # splice must still leave a real first-token forward
        assert idx.best_holder(SHARED, BS) == (4, "A")

    def test_evict_and_drop_replica_invalidate(self):
        idx = ClusterPrefixIndex()
        k1, k2 = _chain(SHARED)
        for name in ("A", "B"):
            idx.publish(name, k1)
            idx.publish(name, k2)
        idx.evict("A", k2)
        assert idx.best_holder(SHARED + [1], BS) == (8, "B")
        assert idx.drop_replica("B") == 2
        assert idx.best_holder(SHARED + [1], BS) == (4, "A")
        assert idx.invalidations == 3
        assert idx.stats()["entries"] == 1

    def test_listener_bridges_publish_evict_reset(self):
        idx = ClusterPrefixIndex()
        lst = idx.listener_for("r0")
        k1, k2 = _chain(SHARED)
        lst.on_publish(k1)
        lst.on_publish(k2)
        assert idx.best_holder(SHARED + [1], BS) == (8, "r0")
        lst.on_evict(k2)
        assert idx.best_holder(SHARED + [1], BS) == (4, "r0")
        lst.on_reset()
        assert idx.stats()["entries"] == 0


class TestTierAwareIndex:
    """Demotion keeps the holder (the replica can restore from its tiers)
    but tags the entry so placement ties prefer blocks still in HBM."""

    def test_demote_keeps_holder_routable(self):
        idx = ClusterPrefixIndex()
        k1, k2 = _chain(SHARED)
        lst = idx.listener_for("A")
        lst.on_publish(k1)
        lst.on_publish(k2)
        lst.on_demote(k2)
        # still full coverage: a request routed to A restores k2 at
        # admission — unlike on_evict, which would cap the match at 4
        assert idx.best_holder(SHARED + [1], BS) == (8, "A")
        s = idx.stats()
        assert s["demoted_entries"] == 1 and s["demotions"] == 1
        assert s["invalidations"] == 0

    def test_tie_prefers_hbm_holder(self):
        idx = ClusterPrefixIndex()
        k1, k2 = _chain(SHARED)
        for name in ("A", "B"):
            idx.publish(name, k1)
            idx.publish(name, k2)
        # equal coverage; A's chain is part-demoted -> B wins despite the
        # name tie-break preferring "A"
        idx.demote("A", k1)
        assert idx.best_holder(SHARED + [1], BS) == (8, "B")

    def test_republish_is_the_promotion_edge(self):
        idx = ClusterPrefixIndex()
        k1, k2 = _chain(SHARED)
        for name in ("A", "B"):
            idx.publish(name, k1)
            idx.publish(name, k2)
        idx.demote("A", k1)
        idx.publish("A", k1)  # restored to HBM: republish resets the tag
        assert idx.best_holder(SHARED + [1], BS) == (8, "A")
        assert idx.stats()["demoted_entries"] == 0

    def test_demoted_entry_still_evictable(self):
        idx = ClusterPrefixIndex()
        k1, _ = _chain(SHARED)
        idx.publish("A", k1)
        idx.demote("A", k1)
        idx.evict("A", k1)  # the tiers dropped it too (disk budget/clear)
        assert idx.stats()["entries"] == 0
        assert idx.best_holder(SHARED + [1], BS) == (0, None)


# ------------------------------------------------------ role-aware placement
def _stats(name="r0", role="unified", alive=True, draining=False,
           outstanding_tokens=0, free_blocks=48):
    return ReplicaStats(
        name=name, alive=alive, draining=draining, queued=0, inflight=0,
        outstanding_tokens=outstanding_tokens, free_blocks=free_blocks,
        pending_blocks=0, block_size=4, usable_blocks=48,
        max_request_blocks=16, max_request_tokens=128, role=role)


class TestPlacementRoles:
    def test_default_roles_never_pick_prefill(self):
        stats = [_stats("pre", role="prefill", outstanding_tokens=0),
                 _stats("dec", role="decode", outstanding_tokens=100)]
        # the prefill replica is idle and would win on load — the role
        # filter (which resubmit/failover also goes through) excludes it
        assert plan_placement(stats, 20, RouterConfig()) == (1, "admit")

    def test_prefill_only_pool_is_unplaceable(self):
        stats = [_stats("pre", role="prefill")]
        idx, verdict = plan_placement(stats, 20, RouterConfig())
        assert idx is None and verdict == "draining"

    def test_explicit_prefill_role_selects_prefill(self):
        stats = [_stats("pre", role="prefill"),
                 _stats("dec", role="decode")]
        idx, _ = plan_placement(stats, 20, RouterConfig(),
                                roles=("prefill",))
        assert idx == 0


# --------------------------------------------- engine-level handoff parity
@pytest.mark.parametrize("mode", sorted(MODES))
class TestHandoffParity:
    def test_split_prefill_decode_token_identical(self, mode, ref_tokens):
        a = _engine(**MODES[mode])
        b = _engine(**MODES[mode])
        for uid, sampling in (("ga", {}), ("sa", SAMPLED)):
            a.put(uid, PROMPT_A, max_new_tokens=MAX_NEW, handoff=True,
                  **sampling)
            first = _run(a, uid)
            assert len(first) == 1  # prefill emits exactly one token
            record = a.export_handoff(uid)
            assert record is not None and record.uid == uid
            assert record.n_blocks * BS >= len(PROMPT_A)
            assert b.import_handoff(record)
            got = _run(b, uid)
            # decode replica re-delivers from index 0: the prefill token
            # plus every decode token, identical to the unsplit run
            assert got == ref_tokens[uid], (mode, uid)
        assert a.kv_blocks_exported > 0
        assert b.kv_blocks_imported == a.kv_blocks_exported


class TestHandoffEdgeCases:
    def test_handoff_after_prefix_hit_still_parity(self, ref_tokens):
        a = _engine(cache=True)
        a.put("warm", PROMPT_A, max_new_tokens=MAX_NEW)
        _run(a, "warm")  # retires + publishes SHARED's blocks
        a.put("gb", PROMPT_B, max_new_tokens=MAX_NEW, handoff=True)
        _run(a, "gb")
        assert a.prefix_hits == 1  # the handoff prompt spliced cached blocks
        record = a.export_handoff("gb")
        b = _engine(cache=True)
        assert b.import_handoff(record)
        assert _run(b, "gb") == ref_tokens["gb"]

    def test_reset_state_fails_parked_handoffs(self):
        a = _engine()
        a.put("u", PROMPT_A, max_new_tokens=MAX_NEW, handoff=True)
        _run(a, "u")
        a.reset_state()
        assert a._results["u"].status == "error"
        assert a.export_handoff("u") is None

    def test_stale_cached_prefix_probe_falls_back_to_cold(self, ref_tokens):
        # the router promised 8 cached tokens (a stale cluster-index read);
        # the local cache is cold — admission must count the stale probe
        # and cold-prefill rather than splice garbage
        eng = _engine(cache=True)
        eng.put("ga", PROMPT_A, max_new_tokens=MAX_NEW,
                expected_cached_tokens=8)
        assert _run(eng, "ga") == ref_tokens["ga"]
        assert eng.prefix_stale_probes == 1


# ----------------------------------------- cross-replica prefix transfer
class TestPrefixTransfer:
    def test_import_gives_hits_on_replica_that_never_saw_prompt(
            self, ref_tokens):
        a = _engine(cache=True)
        a.put("warm", PROMPT_A, max_new_tokens=MAX_NEW)
        _run(a, "warm")
        payload = a.export_prefix(PROMPT_A)
        assert payload is not None and payload.tokens == SHARED

        b = _engine(cache=True)  # never ran any prompt
        assert b.import_prefix(payload) == len(SHARED)
        b.put("gb", PROMPT_B, max_new_tokens=MAX_NEW)
        got = _run(b, "gb")
        assert b.prefix_hits == 1  # reuse without ever prefilling SHARED
        assert got == ref_tokens["gb"]

    def test_export_prefix_none_when_cold_or_disabled(self):
        assert _engine().export_prefix(PROMPT_A) is None
        assert _engine(cache=True).export_prefix(PROMPT_A) is None


# --------------------------------------------------------- cluster end-to-end
def _post(frontend, body, timeout=120):
    conn = http.client.HTTPConnection(frontend.host, frontend.port,
                                      timeout=timeout)
    conn.request("POST", "/v1/completions", body=json.dumps(body),
                 headers={"Content-Type": "application/json"})
    resp = conn.getresponse()
    out = json.loads(resp.read())
    conn.close()
    return resp.status, out


class TestClusterEndToEnd:
    def test_disagg_cluster_over_http(self, ref_tokens):
        pre = _engine(cache=True)
        params = pre.params
        frontend, cluster, loops = build_cluster_server(
            [pre], [_engine(cache=True, params=params),
                    _engine(cache=True, params=params)],
            router_cfg=RouterConfig(max_queue_tokens=512))
        try:
            status, out = _post(frontend, {"prompt": PROMPT_A,
                                           "max_tokens": MAX_NEW})
            assert status == 200
            assert out["choices"][0]["tokens"] == ref_tokens["ga"]
            status, out = _post(frontend, {"prompt": PROMPT_A,
                                           "max_tokens": MAX_NEW, **SAMPLED})
            assert status == 200
            assert out["choices"][0]["tokens"] == ref_tokens["sa"]
            status, out = _post(frontend, {"prompt": PROMPT_B,
                                           "max_tokens": MAX_NEW})
            assert status == 200
            assert out["choices"][0]["tokens"] == ref_tokens["gb"]

            cs = cluster.cluster_stats()
            assert cs["disagg_requests"] == 3
            assert cs["handoffs"]["ok"] == 3 and cs["handoffs"]["failed"] == 0
            assert cs["fallbacks"] == {}
            # PROMPT_A warmed the index; PROMPT_B's chain resolved a holder
            assert cs["prefix_index"]["hits"] >= 1
            assert cs["roles"] == {"prefill": 1, "decode": 2}

            conn = http.client.HTTPConnection(frontend.host, frontend.port,
                                              timeout=60)
            conn.request("GET", "/healthz")
            resp = conn.getresponse()
            hz = json.loads(resp.read())
            conn.close()
            assert resp.status == 200
            roles = {r["name"]: r["role"] for r in hz["replicas"]}
            assert roles == {"prefill-0": "prefill", "decode-0": "decode",
                             "decode-1": "decode"}
            assert hz["cluster"]["disagg_requests"] == 3
        finally:
            cluster.begin_drain()
            for lp in loops:
                lp.join(timeout=60)
            frontend.close()

    def test_decode_import_rejection_fails_over(self, ref_tokens):
        pre = _engine()
        params = pre.params
        loops = [EngineLoop(pre, name="prefill-0", role="prefill"),
                 EngineLoop(_engine(params=params), name="decode-0",
                            role="decode"),
                 EngineLoop(_engine(params=params), name="decode-1",
                            role="decode")]
        cluster = ServingCluster([loops[0]], loops[1:],
                                 router_cfg=RouterConfig(max_queue_tokens=512))
        for lp in loops:
            lp.start()
        try:
            # decode-0 rejects every import (capacity lie) — the cluster
            # must retry the handoff on decode-1, not fail the request
            loops[1].call(
                lambda e: setattr(e, "import_handoff", lambda h: False))
            stream = cluster.submit(
                CompletionRequest(prompt=PROMPT_A, max_tokens=MAX_NEW))
            tokens, reason = stream.collect(timeout=120)
            assert tokens == ref_tokens["ga"] and reason == "length"
            assert loops[2].call(lambda e: e.kv_blocks_imported) > 0
            cs = cluster.cluster_stats()
            assert cs["handoffs"]["ok"] == 1 and cs["fallbacks"] == {}
        finally:
            cluster.begin_drain()
            for lp in loops:
                lp.join(timeout=60)

    def test_prefill_death_mid_handoff_replays_identically(self, ref_tokens):
        pre0 = _engine()
        params = pre0.params
        loops = [EngineLoop(pre0, name="prefill-0", role="prefill",
                            max_respawns=0),
                 EngineLoop(_engine(params=params), name="prefill-1",
                            role="prefill"),
                 EngineLoop(_engine(params=params), name="decode-0",
                            role="decode")]
        cluster = ServingCluster(loops[:2], loops[2:],
                                 router_cfg=RouterConfig(max_queue_tokens=512))
        for lp in loops:
            lp.start()
        inj = get_fault_injector()
        try:
            # one fatal loop fault: it fires on the replica that picks up
            # the prompt (idle loops never reach POINT_LOOP), killing
            # prefill-0 mid-handoff; the retry replays on prefill-1 and the
            # per-request seed makes the output token-identical
            inj.configure([{"point": POINT_LOOP, "fatal": True, "times": 1}])
            stream = cluster.submit(
                CompletionRequest(prompt=PROMPT_A, max_tokens=MAX_NEW,
                                  **SAMPLED))
            tokens, reason = stream.collect(timeout=120)
            assert tokens == ref_tokens["sa"] and reason == "length"
            assert not loops[0].stats().alive
            cs = cluster.cluster_stats()
            assert cs["handoffs"]["ok"] == 1 and cs["fallbacks"] == {}
        finally:
            inj.reset()
            cluster.begin_drain()
            for lp in loops:
                lp.join(timeout=60)


# --------------------------------------------------- router pool management
class TestRouterPool:
    def test_add_remove_replica(self):
        e = _engine()
        a = EngineLoop(e, name="a")
        b = EngineLoop(_engine(params=e.params), name="b")
        router = ReplicaRouter([a], RouterConfig())
        assert not router.remove_replica(a)  # refuses to empty the pool
        router.add_replica(b)
        assert [r["name"] for r in router.health()] == ["a", "b"]
        assert router.remove_replica(a)
        assert [r["name"] for r in router.health()] == ["b"]
        assert router.health()[0]["role"] == "unified"


# ------------------------------------------------------------- autoscaler
class TestDecodeAutoscaler:
    def test_burn_driven_scale_up_down_with_bounds(self):
        pre = _engine()
        params = pre.params
        loops = [EngineLoop(pre, name="prefill-0", role="prefill"),
                 EngineLoop(_engine(params=params), name="decode-0",
                            role="decode")]
        cfg = ClusterConfig(min_decode_replicas=1, max_decode_replicas=2,
                            autoscale_cooldown_s=0.0)
        cluster = ServingCluster(loops[:1], loops[1:], cfg=cfg)
        for lp in loops:
            lp.start()
        burn = [2.0]

        def factory(name):
            return EngineLoop(_engine(params=params), name=name,
                              role="decode")

        scaler = DecodeAutoscaler(cluster, factory, cfg=cfg,
                                  burn_fn=lambda: burn[0])
        try:
            assert scaler.tick() == 1
            assert cluster.cluster_stats()["roles"]["decode"] == 2
            assert scaler.tick() == 0      # at max_decode_replicas
            burn[0] = 0.0
            assert scaler.tick() == -1
            assert scaler.tick() == 0      # at min_decode_replicas
            deadline = time.perf_counter() + 60
            while (cluster.cluster_stats()["roles"]["decode"] != 1
                   and time.perf_counter() < deadline):
                time.sleep(0.01)           # drain reaper removes the victim
            assert cluster.cluster_stats()["roles"]["decode"] == 1
            events = [e["direction"]
                      for e in cluster.cluster_stats()["autoscale_events"]]
            assert events == ["up", "down"]
        finally:
            scaler.stop()
            cluster.begin_drain()
            cluster.drain(timeout=60)

    def test_cooldown_dwell_blocks_back_to_back_actions(self):
        pre = _engine()
        loops = [EngineLoop(pre, name="prefill-0", role="prefill"),
                 EngineLoop(_engine(params=pre.params), name="decode-0",
                            role="decode")]
        cfg = ClusterConfig(autoscale_cooldown_s=3600.0,
                            max_decode_replicas=4)
        cluster = ServingCluster(loops[:1], loops[1:], cfg=cfg)
        scaler = DecodeAutoscaler(
            cluster, lambda name: None, cfg=cfg, burn_fn=lambda: 2.0)
        scaler._last_action = time.perf_counter()  # as if it just acted
        assert scaler.tick() == 0
