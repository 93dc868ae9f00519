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
from types import SimpleNamespace

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
            # a consumer that reads as tokens come: the request streams
            s = loop.submit(CompletionRequest(prompt=_prompt(5),
                                              max_tokens=32, stream=True))
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


# ------------------------------------- a stream's two kinds, on a fake engine
class _FakeSeq:
    def __init__(self, prompt, max_new_tokens, eos_token_id):
        self.prompt, self.max_new_tokens = prompt, max_new_tokens
        self.eos_token_id = eos_token_id
        self.generated: list[int] = []
        self.pos = len(prompt)
        self.status = "running"
        self.t_admit = self.t_first_token = 0.0


class _FakeEngine:
    """What ``EngineLoop`` asks of an engine, with no model behind it: a step
    gives every running request its next token (11, 12, ...); a request ends
    at ``max_new_tokens``, by ``cancel`` or by ``expire`` (its deadline)."""

    cfg = SimpleNamespace(block_size=4, num_blocks=49, max_blocks_per_seq=16,
                          max_seq_len=128)

    def __init__(self):
        self.allocator = SimpleNamespace(free_blocks=48)
        self._reserved = 0
        self._queued: list = []
        self._running: dict = {}
        self._results: dict = {}
        self._seqs: dict = {}

    has_work = property(lambda self: bool(self._running))

    def put(self, uid, prompt, max_new_tokens, eos_token_id=None, **_):
        self._seqs[uid] = self._running[uid] = _FakeSeq(
            prompt, max_new_tokens, eos_token_id)

    def get_request(self, uid):
        return self._seqs.get(uid)

    def step(self):
        for uid, seq in list(self._running.items()):
            seq.generated.append(11 + len(seq.generated))
            if len(seq.generated) >= seq.max_new_tokens:
                self._end(uid, "finished")

    def _end(self, uid, status):
        seq = self._running.pop(uid, None)
        if seq is not None:
            seq.status = status
            self._results[uid] = list(seq.generated)

    def cancel(self, uid):
        self._end(uid, "cancelled")

    def expire(self, uid):
        self._end(uid, "timeout")

    def reset_state(self):
        self._running.clear()


def _turn(loop, step=True):
    """One turn of ``EngineLoop._run_loop``, on the caller's thread."""
    loop._drain_inbox()
    if step and loop._engine.has_work:
        loop._engine.step()
    loop._deliver_turn()


def _fake_loop(stream: bool, max_tokens=8, turns=3):
    """A never-started loop over a fake engine with one request ``turns``
    tokens in; returns ``(loop, its stream)``."""
    loop = EngineLoop(_FakeEngine(), name="fake")
    s = loop.submit(CompletionRequest(prompt=[1, 2, 3], max_tokens=max_tokens,
                                      stream=stream))
    assert s.incremental is stream
    for _ in range(turns):
        _turn(loop)
    return loop, s


def _end_request(loop, s, how):
    """End the open request with NO new token in that turn."""
    eng = loop._engine
    if how == "cancel":          # EngineLoop.cancel: the client went away
        loop.cancel(s.request_id)
    elif how == "abort":         # the engine dropped it (scheduler abort)
        eng.cancel(s.request_id)
    elif how == "deadline":
        eng.expire(s.request_id)
    else:                        # "crash": a step raised, _contain fails it
        loop._contain(RuntimeError("boom"))
        return
    _turn(loop, step=False)


class TestStreamKinds:
    @pytest.mark.parametrize("how", ["done", "error", "cancel"])
    def test_a_stream_not_read_as_it_comes_wakes_its_consumer_once(self, how):
        loop, s = _fake_loop(stream=False, max_tokens=5)
        # three tokens in: nothing on the queue, so nobody was woken
        assert s._q.qsize() == 0 and s._held == [11, 12, 13]
        if how == "done":
            _turn(loop), _turn(loop)
            want = [("token", t) for t in (11, 12, 13, 14, 15)] + [
                ("done", "length")]
        elif how == "error":
            _end_request(loop, s, "crash")
            want = [("token", t) for t in (11, 12, 13)]
        else:
            _end_request(loop, s, "cancel")
            want = [("token", t) for t in (11, 12, 13)] + [
                ("done", "cancelled")]
        assert s._q.qsize() == 1  # the terminal event alone
        got = list(s.events(timeout=5))
        if how == "error":  # the held tokens first, then the error
            assert got[:-1] == want and got[-1][0] == "error"
            assert s.error_reason == "engine_crash" and s.error_code == 500
        else:
            assert got == want
        assert not loop._open

    def test_a_stream_read_as_it_comes_yields_each_token_as_pushed(self):
        loop, s = _fake_loop(stream=True, max_tokens=5, turns=0)
        ev = s.events(timeout=5)
        for n in range(1, 6):
            _turn(loop)
            # one queue event a token, there before the request's end
            assert s._q.qsize() == (1 if n < 5 else 2) and s._held == []
            assert next(ev) == ("token", 10 + n)
        assert list(ev) == [("done", "length")]

    @pytest.mark.parametrize("stream", [False, True])
    @pytest.mark.parametrize("how,kind,value", [
        ("abort", "done", "cancelled"), ("cancel", "done", "cancelled"),
        ("deadline", "done", "timeout"), ("crash", "error", None)])
    def test_an_end_with_no_token_that_turn_still_ends_the_stream(
            self, stream, how, kind, value):
        loop, s = _fake_loop(stream=stream)
        before = s._q.qsize()
        assert before == (3 if stream else 0)
        _end_request(loop, s, how)
        assert s._q.qsize() == before + 1 and not loop._open
        got = list(s.events(timeout=5))
        assert got[:-1] == [("token", t) for t in (11, 12, 13)]
        assert got[-1][0] == kind and (value is None or got[-1][1] == value)

    @pytest.mark.parametrize("how", ["done", "cancel", "crash"])
    def test_collect_is_the_same_on_both_kinds(self, how):
        out = []
        for stream in (False, True):
            loop, s = _fake_loop(stream=stream, max_tokens=4)
            if how == "done":
                _turn(loop)
            else:
                _end_request(loop, s, how)
            try:
                out.append(s.collect(timeout=5))
            except Exception as e:  # noqa: BLE001 - compared below
                out.append((type(e).__name__, str(e), s.error_reason))
        assert out[0] == out[1]
        assert out[0][0] == {"done": [11, 12, 13, 14], "cancel": [11, 12, 13],
                             "crash": "StreamError"}[how]

    @pytest.mark.parametrize("stream", [False, True])
    def test_consumers_on_many_threads_see_every_token_in_order(self, stream):
        """More consumers than cores, each parked in ``collect`` while ONE
        producer pushes: the held list is read only after the terminal event
        came off the queue, so no token is lost or seen early."""
        import sys

        from deepspeed_tpu.serving.engine_loop import TokenStream

        n, per = 48, 200
        streams = [TokenStream(f"s{i}", incremental=stream) for i in range(n)]
        got: dict = {}

        def consume(i):
            got[i] = streams[i].collect(timeout=30)

        threads = [threading.Thread(target=consume, args=(i,)) for i in range(n)]
        old = sys.getswitchinterval()
        sys.setswitchinterval(1e-5)
        try:
            for t in threads:
                t.start()
            for k in range(per):
                for i, s in enumerate(streams):
                    s._push(1000 * i + k)
            for s in streams:
                s._finish("length")
            for t in threads:
                t.join(timeout=30)
        finally:
            sys.setswitchinterval(old)
        assert not any(t.is_alive() for t in threads)
        assert got == {i: ([1000 * i + k for k in range(per)], "length")
                       for i in range(n)}

    @pytest.mark.parametrize("stream", [False, True])
    def test_what_the_loop_put_on_the_queues_is_counted(self, stream):
        telemetry.configure(enabled=True)
        loop = EngineLoop(_FakeEngine(), name="counted")
        for _ in range(2):
            loop.submit(CompletionRequest(prompt=[1], max_tokens=3,
                                          stream=stream))
        for _ in range(3):
            _turn(loop)
        c = telemetry.TELEMETRY.counter("serving_stream_events_total")
        assert c.value(replica="counted", kind="token") == (6 if stream else 0)
        assert c.value(replica="counted", kind="final") == 2

    @pytest.mark.parametrize("stream", [False, True])
    def test_a_clusters_outer_stream_is_read_as_the_request_streams(
            self, stream, monkeypatch):
        from deepspeed_tpu.serving.cluster import ServingCluster
        from deepspeed_tpu.serving.engine_loop import TokenStream

        cluster = ServingCluster(
            [EngineLoop(_FakeEngine(), name="p0", role="prefill")],
            [EngineLoop(_FakeEngine(), name="d0", role="decode")])
        # the two-stage worker is not this test's business
        monkeypatch.setattr(cluster, "_serve_disagg", lambda req, out: None)
        req = CompletionRequest(prompt=[1, 2], max_tokens=4, stream=stream)
        out = cluster.submit(req)
        assert out.incremental is stream
        # the hand-on: a decode replica's stream piped into the outer one
        src = TokenStream(req.request_id, incremental=stream)
        for t in (5, 6, 7):
            src._push(t)
        src._finish("length")
        assert cluster._pipe(src, out, req, skip=1) == (True, 2)
        assert out._q.qsize() == (3 if stream else 1)
        assert out.collect(timeout=5) == ([6, 7], "length")


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
