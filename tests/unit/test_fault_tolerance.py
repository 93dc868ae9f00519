"""Serving-path fault tolerance (docs/FAULT_TOLERANCE.md): the deterministic
fault-injection harness, the ragged engine's dispatch watchdog (retry +
automatic degradation), engine-loop crash containment and thread respawn,
the router's circuit breaker with half-open recovery, replica failover with
token-identical replay, deadline shedding, SIGTERM drain under injected
faults, and client-disconnect KV release."""

import http.client
import json
import os
import signal
import socket
import struct
import threading
import time

import jax.numpy as jnp
import numpy as np
import pytest

from deepspeed_tpu.elasticity.agent import PreemptionHandler
from deepspeed_tpu.inference.ragged import RaggedConfig, RaggedInferenceEngine
from deepspeed_tpu.models import llama
from deepspeed_tpu.serving import (
    POINT_DISPATCH,
    POINT_LOOP,
    POINT_SUBMIT,
    CompletionRequest,
    EngineLoop,
    FatalFaultError,
    FaultError,
    FaultSpec,
    Overloaded,
    ReplicaRouter,
    RouterConfig,
    ServingFrontend,
    StreamError,
    classify_transient,
    get_fault_injector,
)
from deepspeed_tpu.utils.faults import POINT_ALLOC, POINT_READBACK
from deepspeed_tpu.serving.router import DeadlineExceeded

CFG = llama.LlamaConfig(
    vocab_size=97, hidden_size=32, intermediate_size=64,
    num_layers=2, num_heads=4, num_kv_heads=2, max_seq_len=128,
)
# the device-resident tiled step (the path that serves): exercises the
# watchdog where retries, recovery and the ladder matter
WCFG = dict(
    max_tokens_per_step=16, max_seqs=3, block_size=4, num_blocks=49,
    max_blocks_per_seq=16, prefill_tile=8, device_state=True,
    dispatch_retries=2, retry_backoff_s=0.01, degrade_after=2)
# plain host-staged single-program path: cheapest to compile, used by the
# loop/router tests that don't care which dispatch family runs
PCFG = dict(
    max_tokens_per_step=16, max_seqs=3, block_size=4, num_blocks=49,
    max_blocks_per_seq=16, prefill_tile=0, device_state=False,
    dispatch_retries=2, retry_backoff_s=0.01, degrade_after=2)


def _engine(cfg=PCFG, **over):
    rcfg = RaggedConfig(**{**cfg, **over})
    return RaggedInferenceEngine(
        lambda ctx: llama.build(CFG, ctx=ctx), rcfg,
        dtype=jnp.float32, seed=0)


def _prompt(n, seed=0):
    return [int(t) for t in
            np.random.default_rng(seed).integers(0, CFG.vocab_size, n)]


PROMPTS = [_prompt(6, seed=1), _prompt(11, seed=2), _prompt(17, seed=3)]


def _put_all(eng, max_new=6):
    for i, p in enumerate(PROMPTS):
        eng.put(i, p, max_new_tokens=max_new, temperature=0.8, seed=100 + i)


@pytest.fixture(scope="module")
def ref_tokens():
    """Fault-free reference generation on the full device path; every
    fault-injected run below must reproduce these tokens exactly."""
    eng = _engine(WCFG)
    _put_all(eng)
    return eng.generate_all()


# ----------------------------------------------------------- the injector
class TestFaultInjector:
    def test_off_by_default_and_after_reset(self):
        inj = get_fault_injector()
        assert not inj.enabled
        inj.fire(POINT_DISPATCH)  # disarmed: must be a no-op
        inj.arm(POINT_DISPATCH)
        assert inj.enabled
        inj.reset()
        assert not inj.enabled
        inj.fire(POINT_DISPATCH)

    def test_deterministic_schedule(self):
        inj = get_fault_injector()
        inj.configure([FaultSpec(point=POINT_DISPATCH, after=2, times=2,
                                 every=2)])
        fired = []
        for i in range(10):
            try:
                inj.fire(POINT_DISPATCH)
            except FaultError:
                fired.append(i)
        # eligible hits are 3,4,5,... -> every=2 fires on hits 3 and 5
        assert fired == [2, 4]
        assert inj.counts() == {POINT_DISPATCH: 2}

    def test_request_id_filter_and_fatal(self):
        inj = get_fault_injector()
        inj.configure([{"point": POINT_SUBMIT, "request_id": "r1",
                        "fatal": True}])
        inj.fire(POINT_SUBMIT, request_id="r0")  # not the target
        with pytest.raises(FatalFaultError):
            inj.fire(POINT_SUBMIT, request_id="r1")
        inj.fire(POINT_SUBMIT, request_id="r1")  # times=1: spent

    def test_unknown_point_rejected(self):
        with pytest.raises(ValueError):
            FaultSpec(point="engine.nonsense")

    def test_classify_transient_taxonomy(self):
        assert classify_transient(FaultError("x"))
        assert not classify_transient(FatalFaultError("x"))
        assert classify_transient(TimeoutError("stuck"))
        assert classify_transient(ConnectionResetError("gone"))
        assert classify_transient(RuntimeError("transfer UNAVAILABLE: retry"))
        assert not classify_transient(RuntimeError("KV pool exhausted"))
        assert not classify_transient(ValueError("bad shape"))


# ------------------------------------------------------ dispatch watchdog
class TestDispatchWatchdog:
    def test_transient_fault_retried_token_identical(self, ref_tokens):
        eng = _engine(WCFG)
        get_fault_injector().configure(
            [{"point": POINT_DISPATCH, "after": 1}])
        _put_all(eng)
        assert eng.generate_all() == ref_tokens
        assert eng.step_retries >= 1 and eng.step_failures >= 1
        assert eng.degraded_mode == 0
        assert eng.allocator.free_blocks == eng.cfg.num_blocks - 1

    def test_burst_degrades_to_host_staged_fallback(self, ref_tokens):
        eng = _engine(WCFG)
        # two consecutive failures = degrade_after -> automatic fallback
        get_fault_injector().configure(
            [{"point": POINT_DISPATCH, "after": 2, "times": 2}])
        _put_all(eng)
        assert eng.generate_all() == ref_tokens
        assert eng.degraded_mode == 1 and not eng.cfg.device_state
        assert eng.degraded_reason
        assert eng.allocator.free_blocks == eng.cfg.num_blocks - 1

    @pytest.mark.parametrize("message,degrades", [
        # what the v5e compiler says of a kernel over its scoped VMEM
        ("RESOURCE_EXHAUSTED: Ran out of memory in memory space vmem while "
         "allocating on stack for %pallas_call = bf16[128,32,128] "
         "custom-call(...), custom_call_target=\"tpu_custom_call\". Scoped "
         "allocation with size 16.18M and limit 16.00M exceeded scoped vmem "
         "limit by 188.0K.", False),
        # what the runtime says when HBM is full
        ("RESOURCE_EXHAUSTED: Error allocating device buffer: Attempting to "
         "allocate 1.50G. That was not possible. There are 812.4M free.",
         True),
    ], ids=["compile_refusal", "runtime_oom"])
    def test_resource_exhausted_ladder(self, message, degrades, monkeypatch):
        """A RESOURCE_EXHAUSTED raised while compiling stops the engine as
        it is; one raised at run time still walks the degradation ladder."""
        import jax

        eng = _engine(WCFG)
        calls = []

        def step_impl():
            calls.append(1)
            if len(calls) == 1:
                raise jax.errors.JaxRuntimeError(message)
            return {}

        monkeypatch.setattr(eng, "_step_impl", step_impl)
        _put_all(eng)
        if degrades:
            eng.step()
            assert len(calls) == 2  # retried one rung down
            assert eng.degraded_mode == 1 and not eng.cfg.device_state
        else:
            with pytest.raises(jax.errors.JaxRuntimeError, match="vmem"):
                eng.step()
            assert eng.degraded_mode == 0 and eng.cfg.device_state
            assert eng.step_failures == 0 and eng.step_retries == 0

    def test_alloc_and_readback_faults_recover(self, ref_tokens):
        eng = _engine(WCFG)
        get_fault_injector().configure([
            {"point": POINT_ALLOC, "after": 1},
            {"point": POINT_READBACK, "kind": "hang", "after": 3,
             "delay_s": 0.01},
        ])
        _put_all(eng)
        assert eng.generate_all() == ref_tokens
        assert eng.step_failures >= 2
        assert eng.allocator.free_blocks == eng.cfg.num_blocks - 1


# ------------------------------------------------- loop crash containment
class TestCrashContainment:
    def test_fatal_fault_fails_requests_rebuilds_engine(self):
        eng = _engine()
        baseline = eng.allocator.free_blocks
        loop = EngineLoop(eng, name="contain").start()
        try:
            get_fault_injector().configure(
                [{"point": POINT_DISPATCH, "fatal": True}])
            s = loop.submit(CompletionRequest(prompt=_prompt(5),
                                              max_tokens=8))
            with pytest.raises(StreamError):
                s.collect(timeout=60)
            assert s.error_code == 500 and s.error_reason == "engine_crash"
            assert loop.crash_count == 1
            # the loop survived, the engine state was rebuilt, and the
            # replica keeps serving
            assert loop.stats().alive
            assert eng.allocator.free_blocks == baseline
            s2 = loop.submit(CompletionRequest(prompt=_prompt(5),
                                               max_tokens=4))
            tokens, reason = s2.collect(timeout=60)
            assert len(tokens) == 4 and reason == "length"
        finally:
            loop.close(timeout=60)

    def test_loop_thread_death_respawns(self):
        eng = _engine()
        loop = EngineLoop(eng, name="respawn").start()
        try:
            # POINT_LOOP fires outside the step try/except: it kills the
            # loop thread itself, exercising the respawn path
            get_fault_injector().configure(
                [{"point": POINT_LOOP, "fatal": True}])
            s = loop.submit(CompletionRequest(prompt=_prompt(5),
                                              max_tokens=4))
            with pytest.raises(StreamError):
                s.collect(timeout=60)
            assert s.error_code == 503 and s.error_reason == "replica_died"
            deadline = time.perf_counter() + 30
            while loop.respawn_count == 0 and time.perf_counter() < deadline:
                time.sleep(0.01)
            assert loop.respawn_count == 1
            assert loop.stats().alive and not loop.draining
            s2 = loop.submit(CompletionRequest(prompt=_prompt(7, seed=4),
                                               max_tokens=3))
            tokens, reason = s2.collect(timeout=60)
            assert len(tokens) == 3 and reason == "length"
        finally:
            loop.close(timeout=60)

    def test_cancel_during_retry_releases_blocks(self):
        eng = _engine(PCFG, dispatch_retries=10, retry_backoff_s=0.05)
        baseline = eng.allocator.free_blocks
        loop = EngineLoop(eng, name="cancelretry").start()
        inj = get_fault_injector()
        try:
            spec = inj.arm(POINT_DISPATCH, times=4)
            s = loop.submit(CompletionRequest(prompt=_prompt(5),
                                              max_tokens=16))
            while spec.fired == 0:  # the watchdog is now inside its retries
                time.sleep(0.005)
            loop.cancel(s.request_id)
            tokens, reason = s.collect(timeout=60)
            assert reason == "cancelled"
            assert loop.stats().alive
        finally:
            loop.close(timeout=60)
        assert eng.allocator.free_blocks == baseline


# --------------------------------------------- router breaker + shedding
class TestRouterBreaker:
    def test_quarantine_then_half_open_probe_recovers(self):
        # cold loop: nothing steps, so submit failures come only from the
        # injected router.submit faults and the state machine is exact
        loop = EngineLoop(_engine(), name="breaker")
        router = ReplicaRouter([loop], RouterConfig(
            breaker_failures=2, breaker_reset_s=0.2))
        inj = get_fault_injector()
        inj.configure([{"point": POINT_SUBMIT, "times": 2}])
        for _ in range(2):  # two failed submits trip the breaker open
            with pytest.raises(Overloaded):
                router.submit(CompletionRequest(prompt=[1], max_tokens=1))
        assert router.health()[0]["state"] == "quarantined"
        assert router.health()[0]["breaker"] == "open"
        assert router.state() == "degraded"
        # while open (dwell not elapsed) the replica admits nothing
        with pytest.raises(Overloaded) as exc:
            router.submit(CompletionRequest(prompt=[1], max_tokens=1))
        assert exc.value.retry_after_s == 0.2
        time.sleep(0.25)
        # dwell elapsed: one half-open probe goes through and closes it
        stream = router.submit(CompletionRequest(prompt=[1], max_tokens=1))
        assert stream is not None
        assert router.health()[0]["state"] == "healthy"
        assert router.state() == "ready"

    def test_expired_deadline_shed_before_placement(self):
        loop = EngineLoop(_engine(), name="shed")
        router = ReplicaRouter([loop])
        req = CompletionRequest(prompt=_prompt(4), max_tokens=4,
                                deadline_s=0.05)
        req.t_submit = time.perf_counter() - 0.2
        with pytest.raises(DeadlineExceeded):
            router.submit(req)
        # the doomed request never reached the replica
        assert loop.stats().queued == 0

    def test_degraded_engine_surfaces_in_state_and_health(self):
        loop = EngineLoop(_engine(), name="degraded")
        router = ReplicaRouter([loop])
        assert router.state() == "ready"
        loop._engine.degraded_mode = 1
        assert router.state() == "degraded"
        h = router.health()[0]
        assert h["state"] == "degraded" and h["degraded_mode"] == 1


# ---------------------------------------------------------- replica failover
class TestReplicaFailover:
    def test_failover_resubmission_token_identical(self):
        ref = _engine()
        ref.put("ref", PROMPTS[0], max_new_tokens=6, temperature=0.8,
                seed=100)
        expected = ref.generate_all()["ref"]

        eng_a, eng_b = _engine(), _engine()
        loop_a = EngineLoop(eng_a, name="rep-a", max_respawns=0)
        loop_b = EngineLoop(eng_b, name="rep-b")
        router = ReplicaRouter([loop_a, loop_b], RouterConfig(max_failovers=1))
        # only the replica that picked up the request trips the loop fault
        # (an idle loop never reaches POINT_LOOP); max_respawns=0 makes the
        # death final, forcing failover to the survivor
        get_fault_injector().configure(
            [{"point": POINT_LOOP, "fatal": True}])
        loop_a.start()
        loop_b.start()
        try:
            req = CompletionRequest(prompt=PROMPTS[0], max_tokens=6,
                                    temperature=0.8, seed=100)
            stream = router.submit(req)
            with pytest.raises(StreamError):
                stream.collect(timeout=60)
            assert stream.error_reason == "replica_died"
            assert not loop_a.stats().alive
            replay = router.resubmit(req)
            assert replay is not None
            tokens, reason = replay.collect(timeout=60)
            assert tokens == expected and reason == "length"
            # per-request failover budget: a second resubmit is refused
            assert router.resubmit(req) is None
        finally:
            loop_b.close(timeout=60)
            loop_a.join(timeout=10)


# ----------------------------------------- drain + disconnect under faults
def _post(frontend, body, timeout=120):
    conn = http.client.HTTPConnection(frontend.host, frontend.port,
                                      timeout=timeout)
    conn.request("POST", "/v1/completions", body=json.dumps(body),
                 headers={"Content-Type": "application/json"})
    return conn, conn.getresponse()


class TestDrainAndDisconnect:
    def test_sigterm_drain_with_inflight_injected_faults(self):
        eng = _engine()
        loop = EngineLoop(eng, name="faultdrain")
        router = ReplicaRouter([loop], RouterConfig(max_queue_tokens=96))
        frontend = ServingFrontend(router, port=0)
        loop.start()
        frontend.start()
        handler = PreemptionHandler(signals=(signal.SIGTERM,))
        frontend.install_preemption_handler(handler)
        get_fault_injector().configure(
            [{"point": POINT_DISPATCH, "after": 1, "times": 2}])
        try:
            results = {}

            def run_one(i):
                conn, resp = _post(frontend, {
                    "prompt": _prompt(5 + i, seed=i), "max_tokens": 6})
                results[i] = (resp.status, json.loads(resp.read()))
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
            for t in threads:
                t.join(timeout=120)
            assert not any(t.is_alive() for t in threads)
            # inflight work survived the injected faults AND the drain
            for status, body in results.values():
                assert status == 200
                assert len(body["choices"][0]["tokens"]) == 6
            assert loop.join(timeout=60)
            assert eng.step_failures >= 1  # the faults really fired
            assert eng.allocator.free_blocks == eng.cfg.num_blocks - 1
        finally:
            handler.restore()
            frontend.close()

    def test_client_disconnect_mid_sse_releases_kv(self):
        eng = _engine()
        baseline = eng.allocator.free_blocks
        loop = EngineLoop(eng, name="disc")
        router = ReplicaRouter([loop])
        frontend = ServingFrontend(router, port=0)
        loop.start()
        frontend.start()
        try:
            body = json.dumps({"prompt": _prompt(5), "max_tokens": 48,
                               "stream": True}).encode()
            sock = socket.create_connection((frontend.host, frontend.port),
                                            timeout=60)
            sock.sendall(b"POST /v1/completions HTTP/1.1\r\n"
                         b"Host: t\r\nContent-Type: application/json\r\n"
                         b"Content-Length: %d\r\n\r\n%s" % (len(body), body))
            head = sock.recv(4096)  # status line (+ first frames)
            assert b" 200 " in head.split(b"\r\n", 1)[0]
            # abrupt client disconnect mid-stream: RST on close so the
            # server's next SSE write fails immediately
            sock.setsockopt(socket.SOL_SOCKET, socket.SO_LINGER,
                            struct.pack("ii", 1, 0))
            sock.close()
            deadline = time.perf_counter() + 60
            while (eng.allocator.free_blocks != baseline
                   and time.perf_counter() < deadline):
                time.sleep(0.02)
            # the frontend hit the broken pipe, cancelled the request, and
            # the engine released every KV block
            assert eng.allocator.free_blocks == baseline
        finally:
            loop.close(timeout=60)
            frontend.close()
