"""End-to-end request tracing (telemetry/tracing.py + the serving thread):
W3C traceparent propagation, span-tree integrity across frontend → router →
engine loop → ragged engine, Chrome trace-event export validity, the
zero-allocation-when-off pin on the ragged hot path, compile-cache miss
observability, and SLO burn-rate health reflection.

(``tests/unit/test_tracing.py`` covers the utils-level profiler tracing;
this file covers the request-tracing subsystem added with the serving
observability work.)"""

import http.client
import json
import time
import tracemalloc

import jax.numpy as jnp
import numpy as np
import pytest

from deepspeed_tpu import telemetry
from deepspeed_tpu.inference.ragged import RaggedConfig, RaggedInferenceEngine
from deepspeed_tpu.models import llama
from deepspeed_tpu.serving import (
    EngineLoop,
    ReplicaRouter,
    RouterConfig,
    ServingFrontend,
)
from deepspeed_tpu.serving.protocol import decode_sse
from deepspeed_tpu.telemetry.slo import SloMonitor, default_objectives
from deepspeed_tpu.telemetry.tracing import (
    TraceContext,
    Tracer,
    format_traceparent,
    parse_traceparent,
)

CFG = llama.LlamaConfig(
    vocab_size=97, hidden_size=32, intermediate_size=64,
    num_layers=2, num_heads=4, num_kv_heads=2, max_seq_len=128,
)
RCFG = RaggedConfig(
    max_tokens_per_step=16, max_seqs=3, block_size=4,
    num_blocks=49, max_blocks_per_seq=16,
)


def _engine():
    return RaggedInferenceEngine(
        lambda ctx: llama.build(CFG, ctx=ctx), RCFG, dtype=jnp.float32, seed=0)


def _prompt(n, seed=0):
    return [int(t) for t in
            np.random.default_rng(seed).integers(0, CFG.vocab_size, n)]


def _drain(eng, max_steps=500):
    for _ in range(max_steps):
        eng.step()
        if not eng.has_work:
            return
    raise AssertionError("engine did not drain")


# ---------------------------------------------------------- W3C context
class TestTraceparent:
    def test_parse_valid(self):
        tid = "a" * 32
        sid = "b" * 16
        assert parse_traceparent(f"00-{tid}-{sid}-01") == (tid, sid, True)
        assert parse_traceparent(f"00-{tid}-{sid}-00") == (tid, sid, False)
        # case/whitespace tolerant
        assert parse_traceparent(f"  00-{tid.upper()}-{sid}-01 ") == (
            tid, sid, True)

    def test_parse_rejects_malformed(self):
        tid, sid = "a" * 32, "b" * 16
        for bad in (
            None, "", 42, "garbage",
            f"ff-{tid}-{sid}-01",            # reserved version
            f"00-{'0' * 32}-{sid}-01",       # zero trace id
            f"00-{tid}-{'0' * 16}-01",       # zero span id
            f"00-{tid[:-1]}-{sid}-01",       # short trace id
            f"00-{tid}-{sid}",               # missing flags
        ):
            assert parse_traceparent(bad) is None

    def test_format_round_trip(self):
        ctx = TraceContext("c" * 32, "d" * 16)
        assert parse_traceparent(format_traceparent(ctx)) == (
            "c" * 32, "d" * 16, True)
        assert format_traceparent(ctx, sampled=False).endswith("-00")


class TestTracer:
    def _tracer(self, **kw):
        return Tracer(telemetry.get_telemetry().registry).configure(**kw)

    def test_disabled_is_inert(self):
        tr = Tracer(telemetry.get_telemetry().registry)
        assert tr.extract("00-" + "a" * 32 + "-" + "b" * 16 + "-01") is None
        assert tr.begin(TraceContext("a" * 32, "b" * 16)) is None
        tr.finish(None, "x", 0.0, 1.0)
        assert tr.snapshot() == []

    def test_extract_honors_upstream_decision(self):
        tr = self._tracer()
        hdr = "00-" + "a" * 32 + "-" + "b" * 16 + "-01"
        ctx = tr.extract(hdr)
        assert ctx.trace_id == "a" * 32 and ctx.parent_id == "b" * 16
        assert ctx.span_id != "b" * 16  # fresh server-side span
        # sampled flag 0: upstream opted out, no partial trees
        assert tr.extract(hdr[:-2] + "00") is None
        # malformed header -> fresh root
        root = tr.extract("bogus")
        assert root.parent_id is None and len(root.trace_id) == 32

    def test_head_sampling_is_deterministic(self):
        tr = self._tracer(sample_rate=0.25)
        kept = sum(tr.extract(None) is not None for _ in range(100))
        assert kept == 25
        tr = self._tracer(sample_rate=0.0)
        assert all(tr.extract(None) is None for _ in range(10))

    def test_ring_is_bounded(self):
        tr = self._tracer(ring_capacity=8)
        root = tr.extract(None)
        for i in range(20):
            tr.record(root, f"s{i}", float(i), float(i) + 0.5)
        spans = tr.snapshot()
        assert len(spans) == 8
        assert spans[0]["name"] == "s12" and spans[-1]["name"] == "s19"

    def test_span_histogram_feeds_registry(self):
        reg = telemetry.get_telemetry().registry
        tr = Tracer(reg).configure()
        root = tr.extract(None)
        tr.record(root, "unit/span", 0.0, 0.125)
        h = reg.histogram("trace_span_seconds")
        assert h.count(name="unit/span") == 1
        assert h.sum(name="unit/span") == pytest.approx(0.125)

    def test_chrome_export_shape_and_nesting(self):
        tr = self._tracer()
        root = tr.extract(None)
        child = tr.begin(root)
        tr.finish(child, "child", 1.0, 2.0, tokens=3)
        tr.finish(root, "root", 0.5, 2.5)
        trace = tr.export_chrome()
        events = trace["traceEvents"]
        assert len(events) == 2 and trace["displayTimeUnit"] == "ms"
        by_name = {e["name"]: e for e in events}
        for e in events:
            assert e["ph"] == "X"
            assert isinstance(e["ts"], float) and isinstance(e["dur"], float)
            assert e["pid"] and e["tid"]
        c, r = by_name["child"], by_name["root"]
        assert c["args"]["parent_id"] == r["args"]["span_id"]
        assert c["args"]["trace_id"] == r["args"]["trace_id"]
        assert c["args"]["tokens"] == 3
        # timestamp containment: the child renders nested under the root
        assert r["ts"] <= c["ts"] and c["ts"] + c["dur"] <= r["ts"] + r["dur"]
        json.dumps(trace)  # wire-serializable as-is
        # filtered export excludes other traces
        other = tr.extract(None)
        tr.finish(other, "noise", 3.0, 4.0)
        only = tr.export_chrome(root.trace_id)
        assert {e["name"] for e in only["traceEvents"]} == {"child", "root"}


# ------------------------------------------------------- engine integration
class TestEngineTracing:
    def test_request_span_tree(self):
        telemetry.configure(enabled=True, tracing=True)
        eng = _engine()
        for uid, n in [("a", 5), ("b", 11)]:
            eng.put(uid, _prompt(n, seed=hash(uid) % 100), max_new_tokens=4)
        _drain(eng)
        spans = telemetry.get_telemetry().tracer.snapshot()
        per_trace = {}
        for s in spans:
            per_trace.setdefault(s["trace_id"], []).append(s)
        assert len(per_trace) == 2  # one tree per request, no cross-talk
        for tree in per_trace.values():
            names = {s["name"] for s in tree}
            assert {"engine/request", "request/admission",
                    "engine/prefill", "engine/decode",
                    "engine/readback"} <= names
            req = [s for s in tree if s["name"] == "engine/request"]
            assert len(req) == 1
            root_id = req[0]["span_id"]
            # every other span hangs off the request umbrella
            for s in tree:
                if s["name"] != "engine/request":
                    assert s["parent_id"] == root_id
            # dispatch spans carry the token count + dispatch mode
            for s in tree:
                if s["name"] in ("engine/prefill", "engine/decode"):
                    assert s["attrs"]["tokens"] >= 1
                    assert "mode" in s["attrs"]

    def test_put_parents_under_given_context(self):
        telemetry.configure(enabled=True, tracing=True)
        tr = telemetry.get_telemetry().tracer
        root = tr.extract(None)
        eng = _engine()
        eng.put("u", _prompt(5), max_new_tokens=2, trace=root)
        _drain(eng)
        req = [s for s in tr.snapshot() if s["name"] == "engine/request"]
        assert len(req) == 1
        assert req[0]["trace_id"] == root.trace_id
        assert req[0]["parent_id"] == root.span_id

    def test_sampling_drops_whole_requests(self):
        telemetry.configure(enabled=True, tracing={"enabled": True,
                                                   "sample_rate": 0.0})
        eng = _engine()
        eng.put("u", _prompt(5), max_new_tokens=2)
        _drain(eng)
        assert telemetry.get_telemetry().tracer.snapshot() == []

    def test_disabled_hot_path_allocates_nothing_in_tracer(self):
        """The zero-allocation pin: with tracing off, a full serve cycle
        must execute no allocating statement in tracing.py (the emit paths
        are guarded by one attribute read / a ``seq.trace is None`` check)."""
        telemetry.configure(enabled=True)  # telemetry on, tracing OFF
        eng = _engine()
        eng.put("w", _prompt(4, seed=9), max_new_tokens=2)
        _drain(eng)  # warm the jit caches outside the measured window
        tracemalloc.start(1)
        try:
            eng.put("u", _prompt(5), max_new_tokens=4)
            eng.put("v", _prompt(9, seed=1), max_new_tokens=4)
            _drain(eng)
            snap = tracemalloc.take_snapshot()
        finally:
            tracemalloc.stop()
        stats = snap.filter_traces(
            [tracemalloc.Filter(True, "*/telemetry/tracing.py")]).statistics(
                "filename")
        assert sum(s.count for s in stats) == 0, stats

    def test_shape_bust_increments_program_cache_misses(self):
        """A dispatch outside the already-built program set is a serve-time
        jit cache miss: the engine-side counter and coverage gauge see it
        (independent of jax.monitoring, so it holds on any backend)."""
        telemetry.configure(enabled=True)
        eng = _engine()
        eng.put("a", _prompt(5), max_new_tokens=2)
        _drain(eng)
        tel = telemetry.get_telemetry()

        def total_misses() -> float:
            # kind-agnostic: which dispatch path serves depends on config
            return sum(
                float(line.rsplit(" ", 1)[1])
                for line in tel.registry.render_prometheus().splitlines()
                if line.startswith("ragged_program_cache_misses_total"))

        cold0 = total_misses()
        assert cold0 >= 1  # first dispatch compiled a fresh program
        warm = eng.program_cold_dispatches
        # same shapes again: no new programs
        eng.put("b", _prompt(5, seed=2), max_new_tokens=2)
        _drain(eng)
        assert eng.program_cold_dispatches == warm
        # bust the bucket ladder: three concurrent decodes need a wider
        # batch bucket than the single-request runs ever built
        for uid in ("c", "d", "e"):
            eng.put(uid, _prompt(4, seed=ord(uid[0])), max_new_tokens=3)
        _drain(eng)
        assert eng.program_cold_dispatches > warm
        assert total_misses() > cold0
        cov = tel.registry.gauge("ragged_warmup_coverage").value()
        assert 0.0 < cov < 1.0

    def test_backend_compile_counter_on_cpu(self):
        """jax.monitoring's backend-compile event fires on every program
        build, so building + serving a fresh engine must increment
        ``jit_cache_misses_total{source="monitoring"}``."""
        telemetry.configure(enabled=True)  # attaches the compile watch
        tel = telemetry.get_telemetry()
        assert tel.compile_watch is not None
        before = tel.registry.counter(
            "jit_cache_misses_total").value(source="monitoring")
        eng = _engine()
        eng.put("a", _prompt(5), max_new_tokens=2)
        _drain(eng)
        after = tel.registry.counter(
            "jit_cache_misses_total").value(source="monitoring")
        assert after > before
        # the series renders at scrape time even when it is still zero
        assert "jit_cache_misses_total" in tel.registry.render_prometheus()


# ------------------------------------------------------------------- SLO
class TestSloMonitor:
    def test_burn_rate_math(self):
        reg = telemetry.get_telemetry().registry
        mon = SloMonitor(default_objectives(ttft_threshold_s=0.1,
                                            target=0.9, window_s=60.0), reg)
        for _ in range(8):
            mon.record("ttft", 0.05, now=100.0)
        for _ in range(2):
            mon.record("ttft", 0.5, now=100.0)
        s = mon.stats("ttft", now=100.0)
        assert s["count"] == 10 and s["good_fraction"] == pytest.approx(0.8)
        # bad fraction 0.2 over budget 0.1 -> burning 2x
        assert s["burn_rate"] == pytest.approx(2.0)
        assert s["breaching"]
        assert reg.gauge("slo_breaching").value(objective="ttft") == 1.0
        # bad samples age out of the window -> healthy again
        s = mon.stats("ttft", now=200.0)
        assert s["count"] == 0 and not s["breaching"]
        assert s["good_fraction"] == 1.0

    def test_min_samples_guards_noise(self):
        mon = SloMonitor(default_objectives(ttft_threshold_s=0.1),
                         telemetry.get_telemetry().registry)
        for _ in range(SloMonitor.MIN_SAMPLES - 1):
            mon.record("ttft", 9.9, now=10.0)  # 100% bad but too few
        assert not mon.stats("ttft", now=10.0)["breaching"]
        mon.record("ttft", 9.9, now=10.0)
        assert mon.stats("ttft", now=10.0)["breaching"]

    def test_unknown_objective_ignored(self):
        mon = SloMonitor(default_objectives(),
                         telemetry.get_telemetry().registry)
        mon.record("nope", 1.0)  # must not raise
        assert "nope" not in mon.health()


# ------------------------------------------------------- serving end-to-end
@pytest.fixture
def traced_server():
    # telemetry (and the CompileWatch) must be live BEFORE the engine
    # builds so its compiles are observed
    telemetry.configure(
        enabled=True, tracing=True, slo={"enabled": True, "window_s": 60.0})
    eng = _engine()
    loop = EngineLoop(eng, name="traced")
    router = ReplicaRouter([loop], RouterConfig(max_queue_tokens=96))
    frontend = ServingFrontend(router, port=0)
    loop.start()
    frontend.start()
    yield frontend, router, loop, eng
    frontend.router.begin_drain()
    loop.join(timeout=60)
    frontend.close()


def _post(frontend, body, headers=None, timeout=120):
    conn = http.client.HTTPConnection(frontend.host, frontend.port,
                                      timeout=timeout)
    hdrs = {"Content-Type": "application/json"}
    hdrs.update(headers or {})
    conn.request("POST", "/v1/completions", body=json.dumps(body),
                 headers=hdrs)
    return conn, conn.getresponse()


def _get(frontend, path):
    conn = http.client.HTTPConnection(frontend.host, frontend.port,
                                      timeout=60)
    conn.request("GET", path)
    resp = conn.getresponse()
    body = resp.read()
    status, headers = resp.status, dict(resp.getheaders())
    conn.close()
    return status, headers, body


class TestServingTracePropagation:
    def test_client_traceparent_threads_to_engine_spans(self, traced_server):
        frontend, _, _, _ = traced_server
        trace_id = "f" * 32
        parent = "1234567890abcdef"
        conn, resp = _post(
            frontend, {"prompt": _prompt(5), "max_tokens": 3},
            headers={"traceparent": f"00-{trace_id}-{parent}-01"})
        assert resp.status == 200
        echoed = parse_traceparent(resp.getheader("traceparent"))
        body = json.loads(resp.read())
        conn.close()
        assert echoed[0] == trace_id  # same trace, server-side span id
        assert body["trace_id"] == trace_id
        # the handler closes `http/request` after the body's last byte is
        # out: a client that has read it can be ahead of the span by a moment
        deadline = time.monotonic() + 10
        while True:
            spans = telemetry.get_telemetry().tracer.snapshot(trace_id)
            names = {s["name"] for s in spans}
            if "http/request" in names or time.monotonic() > deadline:
                break
            time.sleep(0.01)
        assert {"http/request", "router/submit", "loop/inbox_wait",
                "engine/request", "request/admission", "engine/prefill",
                "engine/decode", "engine/readback"} <= names
        by_id = {s["span_id"]: s for s in spans}
        root = [s for s in spans if s["name"] == "http/request"]
        assert len(root) == 1 and root[0]["parent_id"] == parent
        # single connected tree: every non-root span's parent is recorded
        for s in spans:
            if s is root[0]:
                continue
            assert s["parent_id"] in by_id, s
        # the engine umbrella hangs off the HTTP root and the per-dispatch
        # spans hang off the umbrella
        req = next(s for s in spans if s["name"] == "engine/request")
        assert req["parent_id"] == root[0]["span_id"]
        for s in spans:
            if s["name"].startswith("engine/") and s is not req:
                assert s["parent_id"] == req["span_id"]
        # ... and /debug/trace serves the same tree as valid Chrome JSON
        status, headers, raw = _get(frontend,
                                    f"/debug/trace?trace_id={trace_id}")
        assert status == 200
        trace = json.loads(raw)
        assert {e["name"] for e in trace["traceEvents"]} == names
        for e in trace["traceEvents"]:
            assert e["ph"] == "X" and e["pid"] and e["tid"]
            assert e["args"]["trace_id"] == trace_id

    def test_sse_frames_carry_trace_id(self, traced_server):
        frontend, _, _, _ = traced_server
        conn, resp = _post(frontend, {"prompt": _prompt(5), "max_tokens": 3,
                                      "stream": True})
        assert resp.status == 200
        trace_id = parse_traceparent(resp.getheader("traceparent"))[0]
        frames = decode_sse(resp.read())
        conn.close()
        tokens = [f for f in frames if "token" in f]
        assert tokens and all(f["trace_id"] == trace_id for f in tokens)
        final = frames[-2]
        assert final["trace_id"] == trace_id

    def test_metrics_route_ignores_query_string(self, traced_server):
        frontend, _, _, _ = traced_server
        status, _, body = _get(frontend, "/metrics?foo=1&bar=2")
        assert status == 200
        page = body.decode()
        assert "jit_cache_misses_total" in page
        assert "slo_burn_rate" in page
        status, _, _ = _get(frontend, "/healthz?verbose=1")
        assert status == 200

    def test_timeout_maps_to_504_with_retry_hint(self):
        telemetry.configure(enabled=True, tracing=True)
        eng = _engine()
        loop = EngineLoop(eng, name="slowpoke")
        router = ReplicaRouter([loop], RouterConfig(max_queue_tokens=96))
        frontend = ServingFrontend(router, port=0,
                                   request_timeout_s=0.02)
        loop.start()
        frontend.start()
        try:
            conn, resp = _post(frontend, {"prompt": _prompt(5),
                                          "max_tokens": 8})
            assert resp.status == 504  # gateway timeout, not client error
            assert resp.getheader("Retry-After") == "1"
            err = json.loads(resp.read())["error"]
            conn.close()
            assert err["retry_after_s"] == 1.0
            assert err["timeout_s"] == pytest.approx(0.02)
            assert "did not complete" in err["message"]
        finally:
            frontend.router.begin_drain()
            loop.join(timeout=60)
            frontend.close()

    def test_healthz_reflects_slo_burn(self, traced_server):
        frontend, _, _, _ = traced_server
        tel = telemetry.get_telemetry()
        status, _, body = _get(frontend, "/healthz")
        assert status == 200
        h = json.loads(body)
        assert h["status"] == "ready"
        assert "ttft" in h["slo"] and not h["slo"]["ttft"]["breaching"]
        # burn the whole error budget: every in-window TTFT is bad
        for _ in range(SloMonitor.MIN_SAMPLES + 1):
            tel.observe_slo("ttft", 99.0)
        status, _, body = _get(frontend, "/healthz")
        h = json.loads(body)
        assert status == 200  # degraded still serves
        assert h["status"] == "degraded"
        assert h["slo"]["ttft"]["breaching"]
        assert h["slo"]["ttft"]["burn_rate"] > 1.0
