"""Serving tier: protocol validation + SSE framing, router placement and
admission math (pure, no sockets), engine-level cancel/deadline KV release,
EngineLoop delivery/drain, and one end-to-end HTTP test (ephemeral port, SSE
stream, 429 + Retry-After under overload, SIGTERM-style graceful drain)."""

import http.client
import json
import os
import signal
import threading
import time

import jax.numpy as jnp
import numpy as np
import pytest

from deepspeed_tpu import telemetry
from deepspeed_tpu.elasticity.agent import PreemptionHandler
from deepspeed_tpu.inference.ragged import RaggedConfig, RaggedInferenceEngine
from deepspeed_tpu.models import llama
from deepspeed_tpu.serving import (
    CompletionRequest,
    EngineLoop,
    Overloaded,
    ProtocolError,
    ReplicaStats,
    RouterConfig,
    ServingFrontend,
    ReplicaRouter,
    decode_sse,
    encode_sse,
    plan_placement,
    sse_done,
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


# --------------------------------------------------------------- protocol
class TestProtocol:
    def test_validation_rejects_bad_requests(self):
        for body in (
            {},                                     # missing prompt
            {"prompt": []},                         # empty prompt
            {"prompt": [1, "x"]},                   # non-integer token
            {"prompt": [-1]},                       # negative id
            {"prompt": [1], "max_tokens": 0},
            {"prompt": [1], "temperature": -0.1},
            {"prompt": [1], "top_p": 0.0},
            {"prompt": [1], "deadline_s": -1},
            {"prompt": [1], "seed": -3},
            {"prompt": [1], "frequency_penalty": 1.0},  # unknown field
        ):
            with pytest.raises(ProtocolError):
                CompletionRequest.from_json(body)

    def test_from_json_defaults_and_budget(self):
        req = CompletionRequest.from_json(
            {"prompt": [3, 1, 4], "max_tokens": 5, "stream": True})
        assert req.stream and req.total_tokens == 8
        assert req.request_id.startswith("cmpl-")
        assert req.seed is None
        req = CompletionRequest.from_json({"prompt": [3], "seed": 7})
        assert req.seed == 7

    def test_priority_bounds_validated(self):
        from deepspeed_tpu.serving.protocol import PRIORITY_MAX, PRIORITY_MIN

        # the exact boundaries are accepted verbatim
        for edge in (PRIORITY_MIN, PRIORITY_MAX, 0):
            req = CompletionRequest.from_json(
                {"prompt": [1], "priority": edge})
            assert req.priority == edge
        # anything outside (or non-integer) is a protocol error, never a
        # silent clamp — the scheduler must see exactly what the client sent
        for bad in (PRIORITY_MIN - 1, PRIORITY_MAX + 1, 10**9, "high", 1.5):
            with pytest.raises(ProtocolError):
                CompletionRequest.from_json({"prompt": [1], "priority": bad})

    def test_tenant_and_sla_class_validated(self):
        req = CompletionRequest.from_json(
            {"prompt": [1], "tenant": "acme", "sla_class": "batch"})
        assert req.tenant == "acme" and req.sla_class == "batch"
        # defaults when absent from the wire
        req = CompletionRequest.from_json({"prompt": [1]})
        assert req.tenant == "default" and req.sla_class == "interactive"
        for body in (
            {"prompt": [1], "tenant": ""},
            {"prompt": [1], "tenant": "x" * 65},
            {"prompt": [1], "sla_class": "platinum"},
        ):
            with pytest.raises(ProtocolError):
                CompletionRequest.from_json(body)

    def test_sse_round_trip(self):
        frames = [{"id": "r1", "token": 17, "index": 0},
                  {"id": "r1", "token": 3, "index": 1},
                  {"choices": [{"finish_reason": "length"}]}]
        wire = b"".join(encode_sse(f) for f in frames) + sse_done()
        decoded = decode_sse(wire)
        assert decoded[:-1] == frames and decoded[-1] == "[DONE]"

    def test_sse_event_and_multiline_data(self):
        wire = encode_sse({"a": 1}, event="error")
        assert wire.startswith(b"event: error\n")
        # spec: multiple data: lines join with newlines
        assert decode_sse(b"data: [DO\ndata: NE]\n\n") == ["[DO\nNE]"]


# ----------------------------------------------------------------- router
def _stats(name="r0", alive=True, draining=False, queued=0, inflight=0,
           outstanding_tokens=0, free_blocks=48, pending_blocks=0,
           block_size=4, usable_blocks=48, max_request_blocks=16,
           max_request_tokens=128):
    return ReplicaStats(
        name=name, alive=alive, draining=draining, queued=queued,
        inflight=inflight, outstanding_tokens=outstanding_tokens,
        free_blocks=free_blocks, pending_blocks=pending_blocks,
        block_size=block_size, usable_blocks=usable_blocks,
        max_request_blocks=max_request_blocks,
        max_request_tokens=max_request_tokens)


class TestPlacement:
    def test_least_outstanding_tokens_wins(self):
        stats = [_stats("a", outstanding_tokens=100),
                 _stats("b", outstanding_tokens=10),
                 _stats("c", outstanding_tokens=50)]
        idx, verdict = plan_placement(stats, 20, RouterConfig())
        assert (idx, verdict) == (1, "admit")

    def test_kv_pressure_falls_back_to_queue(self):
        # needs ceil(20/4)=5 blocks; only 2 free after pending — queue it
        stats = [_stats(free_blocks=4, pending_blocks=2)]
        idx, verdict = plan_placement(stats, 20, RouterConfig())
        assert (idx, verdict) == (0, "queue")

    def test_admit_prefers_free_blocks_over_shorter_queue(self):
        stats = [_stats("full", outstanding_tokens=5, free_blocks=0),
                 _stats("free", outstanding_tokens=90, free_blocks=48)]
        idx, verdict = plan_placement(stats, 20, RouterConfig())
        assert (idx, verdict) == (1, "admit")

    def test_queue_bound_rejects(self):
        cfg = RouterConfig(max_queue_tokens=64)
        stats = [_stats(outstanding_tokens=60, free_blocks=0)]
        idx, verdict = plan_placement(stats, 20, cfg)
        assert (idx, verdict) == (None, "overloaded")

    def test_draining_and_dead_replicas_excluded(self):
        stats = [_stats(draining=True), _stats(alive=False)]
        assert plan_placement(stats, 4, RouterConfig()) == (None, "draining")


# ------------------------------------------------- engine cancel/deadline
class TestEngineAbort:
    def test_cancel_frees_kv_and_emits_span(self):
        telemetry.configure(enabled=True)
        eng = _engine()
        baseline = eng.allocator.free_blocks
        eng.put("keep", _prompt(5), max_new_tokens=6)
        eng.put("kill", _prompt(9, seed=1), max_new_tokens=6)
        for _ in range(3):  # admit + a few decode steps
            eng.step()
        assert eng.cancel("kill") is True
        assert eng.cancel("kill") is False  # idempotent: already aborted
        assert eng.cancel("nope") is False
        while eng.has_work:
            eng.step()
        assert eng.allocator.free_blocks == baseline
        assert eng._results["kill"].status == "cancelled"
        assert len(eng._results["keep"].generated) == 6
        assert telemetry.TELEMETRY.counter(
            "inference_requests_cancelled_total").value() == 1

    def test_cancel_queued_request_never_admits(self):
        eng = _engine()
        baseline = eng.allocator.free_blocks
        eng.put("q", _prompt(5), max_new_tokens=4)
        assert eng.cancel("q") is True
        out = eng.step()
        assert out == {} or "q" not in out
        assert not eng.has_work
        assert eng.allocator.free_blocks == baseline
        assert eng._results["q"].status == "cancelled"

    def test_deadline_expiry_times_out(self):
        telemetry.configure(enabled=True)
        eng = _engine()
        baseline = eng.allocator.free_blocks
        eng.put("slow", _prompt(5), max_new_tokens=8, deadline_s=0.01)
        eng.step()  # admit
        time.sleep(0.03)
        while eng.has_work:
            eng.step()
        assert eng._results["slow"].status == "timeout"
        assert len(eng._results["slow"].generated) < 8
        assert eng.allocator.free_blocks == baseline
        assert telemetry.TELEMETRY.counter(
            "inference_requests_timeout_total").value() == 1

    def test_deadline_validation(self):
        eng = _engine()
        with pytest.raises(ValueError):
            eng.put("bad", _prompt(4), deadline_s=0.0)


# -------------------------------------------------------------- EngineLoop
class TestEngineLoop:
    def test_stream_delivery_and_drain(self):
        loop = EngineLoop(_engine(), name="t0").start()
        try:
            streams = [loop.submit(CompletionRequest(
                prompt=_prompt(5 + 3 * i, seed=i), max_tokens=4))
                for i in range(3)]
            for s in streams:
                tokens, reason = s.collect(timeout=60)
                assert len(tokens) == 4 and reason == "length"
        finally:
            assert loop.close(timeout=60)
        assert not loop.stats().alive

    def test_cancel_mid_stream_frees_blocks(self):
        eng = _engine()
        baseline = eng.allocator.free_blocks
        loop = EngineLoop(eng, name="t1").start()
        try:
            s = loop.submit(CompletionRequest(prompt=_prompt(5),
                                              max_tokens=32))
            ev = s.events(timeout=60)
            kind, _ = next(ev)
            assert kind == "token"
            loop.cancel(s.request_id)
            kinds = [k for k, _ in ev]
            assert kinds[-1] == "done" and s.finish_reason == "cancelled"
        finally:
            loop.close(timeout=60)
        assert eng.allocator.free_blocks == baseline

    def test_submit_after_drain_rejected(self):
        loop = EngineLoop(_engine(), name="t2").start()
        loop.begin_drain()
        from deepspeed_tpu.serving import ReplicaDraining

        with pytest.raises(ReplicaDraining):
            loop.submit(CompletionRequest(prompt=[1], max_tokens=1))
        assert loop.join(timeout=60)


# ---------------------------------------------------------- end-to-end HTTP
@pytest.fixture
def server():
    eng = _engine()
    loop = EngineLoop(eng, name="e2e")
    router = ReplicaRouter([loop], RouterConfig(max_queue_tokens=96))
    frontend = ServingFrontend(router, port=0)
    loop.start()
    frontend.start()
    yield frontend, router, loop, eng
    frontend.router.begin_drain()
    loop.join(timeout=60)
    frontend.close()


def _post(frontend, body, timeout=120):
    conn = http.client.HTTPConnection(frontend.host, frontend.port,
                                      timeout=timeout)
    conn.request("POST", "/v1/completions", body=json.dumps(body),
                 headers={"Content-Type": "application/json"})
    return conn, conn.getresponse()


class TestEndToEnd:
    def test_sse_completion_stream(self, server):
        frontend, _, _, _ = server
        conn, resp = _post(frontend, {"prompt": _prompt(5), "max_tokens": 4,
                                      "stream": True})
        assert resp.status == 200
        assert resp.getheader("Content-Type") == "text/event-stream"
        frames = decode_sse(resp.read())
        conn.close()
        assert frames[-1] == "[DONE]"
        tokens = [f["token"] for f in frames if "token" in f]
        final = frames[-2]
        assert final["choices"][0]["finish_reason"] == "length"
        assert final["choices"][0]["tokens"] == tokens and len(tokens) == 4
        assert final["usage"]["prompt_tokens"] == 5

    def test_non_streaming_json(self, server):
        frontend, _, _, _ = server
        conn, resp = _post(frontend, {"prompt": _prompt(5), "max_tokens": 3})
        assert resp.status == 200
        body = json.loads(resp.read())
        conn.close()
        assert body["object"] == "completion"
        assert len(body["choices"][0]["tokens"]) == 3
        assert body["usage"]["total_tokens"] == 8

    def test_bad_request_400(self, server):
        frontend, _, _, _ = server
        conn, resp = _post(frontend, {"prompt": []})
        assert resp.status == 400
        assert "error" in json.loads(resp.read())
        conn.close()

    def test_out_of_range_priority_400(self, server):
        frontend, _, _, _ = server
        for bad in (1000, -1000, "urgent"):
            conn, resp = _post(frontend, {"prompt": _prompt(4),
                                          "priority": bad})
            assert resp.status == 400
            err = json.loads(resp.read())["error"]
            assert "priority" in err["message"]
            conn.close()

    def test_tenant_identity_echoed(self, server):
        frontend, _, _, _ = server
        conn, resp = _post(frontend, {"prompt": _prompt(5), "max_tokens": 2,
                                      "tenant": "acme", "sla_class": "batch"})
        assert resp.status == 200
        body = json.loads(resp.read())
        conn.close()
        assert body["tenant"] == "acme"
        assert body["sla_class"] == "batch"
        # invalid identity is a structured 400, not a silent default
        conn, resp = _post(frontend, {"prompt": _prompt(4),
                                      "sla_class": "platinum"})
        assert resp.status == 400
        assert "sla_class" in json.loads(resp.read())["error"]["message"]
        conn.close()

    def test_overload_429_retry_after(self):
        # cold loop (never started): submissions pile up in the inbox, so
        # admission state is deterministic — no race with the step loop
        eng = _engine()
        loop = EngineLoop(eng, name="cold")
        router = ReplicaRouter([loop], RouterConfig(
            max_queue_tokens=30, retry_after_s=2.5))
        frontend = ServingFrontend(router, port=0).start()
        try:
            router.submit(CompletionRequest(prompt=_prompt(20), max_tokens=10))
            conn, resp = _post(frontend, {"prompt": _prompt(20),
                                          "max_tokens": 10})
            assert resp.status == 429
            assert resp.getheader("Retry-After") == "2.5"
            assert "replicas past" in json.loads(resp.read())["error"]["message"]
            conn.close()
            # healthz agrees the server is saturated
            c2 = http.client.HTTPConnection(frontend.host, frontend.port)
            c2.request("GET", "/healthz")
            h = c2.getresponse()
            assert h.status == 200
            assert json.loads(h.read())["status"] == "overloaded"
            c2.close()
        finally:
            frontend.close()

    def test_oversized_request_400_not_429(self, server):
        frontend, _, _, _ = server
        conn, resp = _post(frontend, {"prompt": _prompt(100),
                                      "max_tokens": 100})
        assert resp.status == 400  # can never fit -> client error, not retry
        conn.close()

    def test_metrics_endpoint(self, server):
        frontend, _, _, _ = server
        telemetry.configure(enabled=True)
        conn, resp = _post(frontend, {"prompt": _prompt(5), "max_tokens": 2})
        resp.read()
        conn.close()
        c = http.client.HTTPConnection(frontend.host, frontend.port)
        c.request("GET", "/metrics")
        m = c.getresponse()
        assert m.status == 200
        assert m.getheader("Content-Type").startswith("text/plain")
        page = m.read().decode()
        c.close()
        assert "serving_requests_admitted_total 1" in page
        assert "serving_queue_depth" in page
        assert "serving_draining 0" in page

    def test_sigterm_drain_finishes_inflight(self):
        eng = _engine()
        loop = EngineLoop(eng, name="drain")
        router = ReplicaRouter([loop], RouterConfig(max_queue_tokens=96))
        frontend = ServingFrontend(router, port=0)
        loop.start()
        frontend.start()
        handler = PreemptionHandler(signals=(signal.SIGTERM,))
        frontend.install_preemption_handler(handler)
        try:
            results = {}

            def run_one(i):
                conn, resp = _post(frontend, {
                    "prompt": _prompt(5 + i, seed=i), "max_tokens": 6,
                    "stream": True})
                results[i] = decode_sse(resp.read())
                conn.close()

            threads = [threading.Thread(target=run_one, args=(i,))
                       for i in range(2)]
            for t in threads:
                t.start()
            # wait until BOTH requests are genuinely inflight (a request the
            # front end has not routed yet is refused by the drain, rightly)
            t_end = time.monotonic() + 60
            while (len(eng._running) + len(eng._queued) < 2
                   and time.monotonic() < t_end):
                time.sleep(0.005)
            # the preemption notice, through the handler SIGTERM is bound to:
            # a real signal would go to the xdist worker this test runs in
            signal.getsignal(signal.SIGTERM)(signal.SIGTERM, None)
            assert handler.should_stop
            assert router.state() == "draining"
            # new work is refused while draining (healthz -> 503)
            c = http.client.HTTPConnection(frontend.host, frontend.port)
            c.request("GET", "/healthz")
            assert c.getresponse().status == 503
            c.close()
            conn, resp = _post(frontend, {"prompt": _prompt(4),
                                          "max_tokens": 2})
            assert resp.status == 503
            conn.close()
            # ... but inflight requests run to completion
            for t in threads:
                t.join(timeout=120)
            assert loop.join(timeout=60)
            for i in range(2):
                final = results[i][-2]
                assert final["choices"][0]["finish_reason"] == "length"
                assert len(final["choices"][0]["tokens"]) == 6
            assert eng.allocator.free_blocks == RCFG.num_blocks - 1
        finally:
            handler.restore()
            frontend.close()
