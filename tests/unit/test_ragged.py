"""Ragged/continuous-batching inference (reference ``tests/unit/inference/v2``:
ragged manager, blocked allocator, engine numerics vs the dense path)."""

import jax.numpy as jnp
import numpy as np
import pytest
from shared import one_engine_each  # tests/unit is rootdir-inserted
from step_modes import MODES

from deepspeed_tpu.inference.engine import InferenceEngine
from deepspeed_tpu.inference.ragged import (
    BlockedAllocator,
    RaggedConfig,
    RaggedInferenceEngine,
)
from deepspeed_tpu.models import llama

CFG = llama.LlamaConfig(
    vocab_size=97, hidden_size=32, intermediate_size=64,
    num_layers=2, num_heads=4, num_kv_heads=2, max_seq_len=128,
)
RCFG = RaggedConfig(
    max_tokens_per_step=16, max_seqs=3, block_size=4,
    num_blocks=49, max_blocks_per_seq=16,
)


class TestBlockedAllocator:
    def test_allocate_free_roundtrip(self):
        a = BlockedAllocator(9)
        assert a.free_blocks == 8  # block 0 reserved as scratch
        got = a.allocate(3)
        assert len(set(got)) == 3 and 0 not in got
        assert a.free_blocks == 5
        a.free(got)
        assert a.free_blocks == 8

    def test_exhaustion_raises(self):
        a = BlockedAllocator(4)
        a.allocate(3)
        with pytest.raises(RuntimeError):
            a.allocate(1)

    def test_double_free_and_scratch_guard(self):
        a = BlockedAllocator(4)
        blocks = a.allocate(2)
        a.free(blocks)
        with pytest.raises(ValueError):
            a.free([blocks[0]])
        with pytest.raises(ValueError):
            a.free([0])


def _dense_reference(prompts, max_new):
    """Greedy continuation per prompt via the dense v1 engine."""
    eng = InferenceEngine(
        lambda ctx: llama.build(CFG, ctx=ctx), dtype=jnp.float32, seed=0
    )
    out = {}
    for uid, p in prompts.items():
        full = eng.generate(np.asarray(p)[None], max_new_tokens=max_new)
        out[uid] = list(np.asarray(full[0, len(p):]))
    return out


def _engine_ds(device_state, **over):
    import dataclasses

    cfg = dataclasses.replace(RCFG, device_state=device_state, **over)
    return RaggedInferenceEngine(
        lambda ctx: llama.build(CFG, ctx=ctx), cfg, dtype=jnp.float32, seed=0)


@pytest.fixture(scope="module")
def engine_of():
    """``engine_of(device_state, second=False, **over)``: the module's ONE
    engine of those options (two with ``second``), as new each time it is
    asked for (``shared.py``). A case that reads an engine's running
    counts, degrades it, or is about its first compile builds its own with
    ``_engine_ds``."""
    get = one_engine_each(_engine_ds)
    return lambda device_state=RCFG.device_state, **over: get(device_state,
                                                              **over)


def _generate(eng, requests):
    """Put ``{uid: (prompt, put options)}`` and run to the end; the tokens
    of these uids alone (a shared engine still holds earlier cases')."""
    for uid, (prompt, kw) in requests.items():
        eng.put(uid, prompt, **kw)
    out = eng.generate_all()
    return {uid: out[uid] for uid in requests}


def _prompts(rng=0):
    r = np.random.default_rng(rng)
    return {
        "a": list(r.integers(0, CFG.vocab_size, 5)),
        "b": list(r.integers(0, CFG.vocab_size, 11)),
        "c": list(r.integers(0, CFG.vocab_size, 23)),
    }


class TestRaggedEngine:
    def test_mixed_length_parity_vs_dense(self, engine_of):
        """Three different-length prompts admitted together produce exactly
        the dense engine's greedy continuations."""
        prompts = _prompts()
        max_new = 8
        ref = _dense_reference(prompts, max_new)

        eng = engine_of()
        for uid, p in prompts.items():
            eng.put(uid, p, max_new_tokens=max_new)
        got = eng.generate_all()
        for uid in prompts:
            assert got[uid] == [int(t) for t in ref[uid]], uid

    def test_tiled_prefill_token_parity(self, engine_of):
        """The tile-aligned prefill layout + tiled attention path must emit
        exactly the per-token engine's greedy tokens (XLA fallback on CPU
        exercises the scheduler layout + metadata; kernel math is covered by
        test_paged_attention's interpret-mode parity)."""
        requests = {uid: (p, dict(max_new_tokens=7))
                    for uid, p in _prompts(13).items()}
        expect = _generate(engine_of(), requests)
        tiled = engine_of(prefill_tile=8)
        assert _generate(tiled, requests) == expect
        assert (any(key[2] > 0 for key in tiled._dev_step_jits)
                or tiled._tiled_jits), "tiled step programs never engaged"

    def test_tiled_prefill_rejected_without_model_support(self):
        import dataclasses

        def build_no_tiles(ctx):
            spec = llama.build(CFG, ctx=ctx)
            spec.supports_prefill_tiles = False
            return spec

        with pytest.raises(ValueError, match="prefill_tiles"):
            RaggedInferenceEngine(
                build_no_tiles,
                dataclasses.replace(RCFG, prefill_tile=8),
                dtype=jnp.float32, seed=0,
            )

    def test_continuous_admission(self, engine_of):
        """A request put() mid-flight (while others decode) still matches the
        dense reference — continuous batching semantics."""
        prompts = _prompts(3)
        max_new = 6
        ref = _dense_reference(prompts, max_new)

        eng = engine_of()
        eng.put("a", prompts["a"], max_new_tokens=max_new)
        eng.put("b", prompts["b"], max_new_tokens=max_new)
        for _ in range(3):  # a/b prefill and start decoding
            eng.step()
        eng.put("c", prompts["c"], max_new_tokens=max_new)  # late admission
        got = eng.generate_all()
        for uid in prompts:
            assert got[uid] == [int(t) for t in ref[uid]], uid

    def test_blocks_and_slots_recycled(self, engine_of):
        eng = engine_of()
        total_free = eng.allocator.free_blocks
        # two waves through the same engine: slots and blocks must recycle
        for wave in range(2):
            for uid, p in _prompts(wave).items():
                eng.put(f"{wave}-{uid}", p, max_new_tokens=4)
            eng.generate_all()
            assert eng.allocator.free_blocks == total_free
            assert len(eng._free_slots) == RCFG.max_seqs

    @pytest.mark.parametrize("mode", list(MODES))
    def test_eos_stops_sequence(self, engine_of, mode):
        """An EOS token ends its sequence there, on every step path. The
        device step reads a token back with the next step already
        dispatched: the row the sequence has in that step surfaces no token,
        and its slot and blocks come back once the step is reconciled."""
        kw = {"device_state": True, **MODES[mode]}
        prompts = _prompts()
        # run once to learn the tokens, then make two of them EOS tokens: x's
        # first, and one y has not produced before, mid-decode
        eng = engine_of(**kw)
        eng.put("px", prompts["a"], max_new_tokens=6)
        eng.put("py", prompts["b"], max_new_tokens=6)
        eng.put("pz", prompts["c"], max_new_tokens=6)
        ref = {uid[1:]: toks for uid, toks in eng.generate_all().items()}
        cut = next(i for i in range(1, 6) if ref["y"][i] not in ref["y"][:i])
        emitted = eng.tokens_emitted
        eng.put("x", prompts["a"], max_new_tokens=6,
                eos_token_id=ref["x"][0])
        eng.put("y", prompts["b"], max_new_tokens=6,
                eos_token_id=ref["y"][cut])
        eng.put("z", prompts["c"], max_new_tokens=6)
        y = eng.get_request("y")
        while not y.finished:
            eng.step()
        if kw["device_state"]:
            assert eng._pending and y.refs == 1 and y.slot >= 0
        else:
            assert not eng._pending and y.slot < 0
        out = {uid: toks for uid, toks in eng.generate_all().items()
               if uid in "xyz"}
        assert out["x"] == ref["x"][:1]  # stopped at eos, not max_new
        assert out["y"] == ref["y"][:cut + 1]
        assert out["z"] == ref["z"]
        assert eng.tokens_emitted - emitted == sum(map(len, out.values()))
        assert len(eng._free_slots) == RCFG.max_seqs
        assert eng.allocator.free_blocks == RCFG.num_blocks - 1

    def test_never_admittable_request_rejected_at_put(self):
        """A request whose worst case exceeds the whole pool is rejected
        upfront instead of stalling the queue and deadlocking the engine."""
        tiny_pool = RaggedConfig(
            max_tokens_per_step=8, max_seqs=2, block_size=2,
            num_blocks=3, max_blocks_per_seq=8,
        )
        eng = RaggedInferenceEngine(
            lambda ctx: llama.build(CFG, ctx=ctx), tiny_pool,
            dtype=jnp.float32, seed=0,
        )
        r = np.random.default_rng(0)
        with pytest.raises(ValueError, match="never be admitted"):
            eng.put("a", r.integers(0, CFG.vocab_size, 6), max_new_tokens=4)
        # a request that does fit the pool still completes
        eng.put("ok", r.integers(0, CFG.vocab_size, 2), max_new_tokens=2)
        assert len(eng.generate_all()["ok"]) == 2

    def test_conservative_admission_completes_oversubscribed_load(self):
        """Requests whose combined worst case exceeds the pool but which fit
        sequentially must all complete: admission reserves worst-case blocks,
        so later requests wait instead of deadlocking mid-decode."""
        pool = RaggedConfig(
            max_tokens_per_step=16, max_seqs=3, block_size=4,
            num_blocks=12, max_blocks_per_seq=8,  # 11 usable blocks
        )
        eng = RaggedInferenceEngine(
            lambda ctx: llama.build(CFG, ctx=ctx), pool,
            dtype=jnp.float32, seed=0,
        )
        r = np.random.default_rng(0)
        # worst cases: ceil(20/4)=5, ceil(22/4)=6, ceil(17/4)=5 -> 16 > 11
        for uid, (plen, new) in {"a": (14, 6), "b": (16, 6), "c": (12, 5)}.items():
            eng.put(uid, r.integers(0, CFG.vocab_size, plen), max_new_tokens=new)
        out = eng.generate_all()
        assert sorted(out) == ["a", "b", "c"]
        assert [len(out[u]) for u in "abc"] == [6, 6, 5]

    def test_splitfuse_efficiency_vs_dense_padding(self, engine_of):
        """Scheduled useful tokens must beat dense pad-to-max batching: the
        dense engine processes batch*max_prompt prefill + batch*max_new decode
        token-slots; the ragged schedule only pays for real tokens plus
        bucket-padding slack, which must come in strictly lower at mixed
        lengths."""
        prompts = _prompts()
        max_new = 8
        eng = engine_of()
        before = eng.tokens_scheduled + eng.tokens_padded
        for uid, p in prompts.items():
            eng.put(uid, p, max_new_tokens=max_new)
        eng.generate_all()
        dense_token_slots = len(prompts) * (
            max(len(p) for p in prompts.values()) + max_new
        )
        ragged_token_slots = eng.tokens_scheduled + eng.tokens_padded - before
        assert ragged_token_slots < dense_token_slots, (
            f"ragged {ragged_token_slots} >= dense {dense_token_slots}"
        )


# what the device-resident state must stay token-identical in, against the
# host-staged step under the same options
DISPATCH_MODES = {m: MODES[m] for m in ("plain", "tiled")}


class TestDeviceResidentState:
    """cfg.device_state keeps slot rows / block table / feed tokens on
    device and double-buffers readback; it must be token-identical to the
    host-staged path in every mode, greedy and seeded-sampled."""

    @pytest.mark.parametrize("mode", list(DISPATCH_MODES))
    def test_token_parity_vs_host_staged(self, engine_of, mode):
        kw = DISPATCH_MODES[mode]
        requests = {uid: (p, dict(max_new_tokens=8))
                    for uid, p in _prompts(17).items()}
        requests["s1"] = (_prompts(19)["b"], dict(
            max_new_tokens=8, temperature=0.9, top_k=20, seed=123))
        requests["s2"] = (_prompts(19)["a"], dict(
            max_new_tokens=6, temperature=0.7, top_p=0.9, seed=7))
        outs = {dev: _generate(engine_of(dev, **kw), requests)
                for dev in (False, True)}
        assert outs[True] == outs[False] and len(outs[True]) == 5
        # the sampled streams really sampled (not a greedy fallback)
        greedy = _generate(engine_of(True, **kw), {
            "s1": (_prompts(19)["b"], dict(max_new_tokens=8))})
        assert greedy["s1"] != outs[True]["s1"]

    @pytest.mark.parametrize("mode", list(DISPATCH_MODES))
    def test_counters_count_what_the_host_staged_steps_do(self, mode):
        """``dispatch_count``, ``tokens_scheduled`` and ``tokens_padded``
        (the benchmark's ``sched.*`` metrics read them as deltas) mean the
        same on both step paths. On one workload the device step's are the
        host-staged step's plus what its pending window adds and nothing
        else: a request's last token is read back with the next step already
        dispatched, so each request has one decode row past its end
        (ROADMAP D1d), and the last of those rows rides a step of its own."""
        kw = DISPATCH_MODES[mode]
        engs = {}
        for dev in (False, True):
            eng = engs[dev] = _engine_ds(dev, **kw)
            p = _prompts(17)
            eng.put("a", p["a"], max_new_tokens=9)
            eng.put("b", p["b"], max_new_tokens=5, temperature=0.9, top_k=20,
                    seed=123)
            eng.put("c", p["c"], max_new_tokens=7, temperature=0.7, top_p=0.9,
                    seed=7)
        host, dev = engs[False], engs[True]
        assert dev.generate_all() == host.generate_all()
        assert host.tokens_scheduled == 5 + 11 + 23 + (9 + 5 + 7 - 3)
        assert dev.tokens_scheduled == host.tokens_scheduled + 3
        assert dev.dispatch_count == host.dispatch_count + 1
        # that step: one row in the smallest bucket there is
        last = (dev._dec_buckets if dev._use_tiles else dev._buckets)[0]
        assert dev.tokens_scheduled + dev.tokens_padded == \
            host.tokens_scheduled + host.tokens_padded + last

    @pytest.mark.parametrize("mode", list(DISPATCH_MODES))
    def test_steady_decode_stages_zero_bytes(self, mode):
        """The whole point: once every sequence is decoding, the packed
        staging buffer byte-compares equal step to step and the block table
        has no dirty rows — further steps upload NOTHING."""
        # block_size 16: the whole request (11 prompt + 5 new = 16 tokens)
        # fits one block, so no mid-decode table growth dirties a row
        eng = _engine_ds(True, block_size=16, num_blocks=13,
                         max_blocks_per_seq=8, **DISPATCH_MODES[mode])
        eng.put("a", _prompts(23)["b"], max_new_tokens=5)
        while eng._queued or not all(s.in_decode
                                     for s in eng._running.values()):
            eng.step()  # prefill dispatch
        eng.step()  # first decode dispatch (staging buffer cached here)
        assert all(s.in_decode for s in eng._running.values())
        h2d0 = eng.h2d_bytes
        for _ in range(2):
            eng.step()
        assert eng.h2d_bytes == h2d0, (
            "steady-state decode still staging host bytes")

    @pytest.mark.parametrize("mode", list(DISPATCH_MODES))
    def test_readback_is_double_buffered(self, engine_of, mode):
        """A dispatched step's tokens are reconciled one step later (window
        of one pending dispatch), and drain() flushes the window."""
        eng = engine_of(True, **DISPATCH_MODES[mode])
        eng.put("a", _prompts()["a"], max_new_tokens=6)
        eng.step()  # prefill dispatched, nothing reconciled yet
        assert len(eng._pending) == 1
        assert eng._results.get("a") is None
        eng.drain()
        assert not eng._pending
        out = eng.generate_all()
        assert len(out["a"]) == 6

    @pytest.mark.parametrize("mode", list(DISPATCH_MODES))
    def test_cancel_mid_flight_with_pending_dispatch(self, engine_of, mode):
        """cancel() while a dispatch is in flight: the sequence retires via
        the deferred-release machinery, its KV blocks and slot recycle, and
        the remaining request still finishes with correct tokens."""
        kw = DISPATCH_MODES[mode]
        want = None
        for with_cancel in (False, True):
            eng = engine_of(True, **kw)
            prompts = _prompts(29)
            eng.put("keep", prompts["b"], max_new_tokens=8)
            if with_cancel:
                eng.put("dead", prompts["c"], max_new_tokens=8)
            eng.step()  # dispatch in flight referencing both
            if with_cancel:
                assert eng.cancel("dead")
            out = eng.generate_all()
            if with_cancel:
                assert eng.get_request("dead").status == "cancelled"
            if want is None:
                want = out["keep"]
            else:
                assert out["keep"] == want
        assert len(eng._free_slots) == RCFG.max_seqs
        usable = RCFG.num_blocks - 1
        assert eng.allocator.free_blocks == usable

    @pytest.mark.parametrize("mode", list(DISPATCH_MODES))
    def test_deadline_timeout_mid_flight(self, engine_of, mode):
        eng = engine_of(True, **DISPATCH_MODES[mode])
        eng.put("t", _prompts()["c"], max_new_tokens=40, deadline_s=0.05)
        eng.step()
        import time as _time

        _time.sleep(0.08)
        eng.generate_all()
        seq = eng.get_request("t")
        assert seq.status == "timeout"
        assert len(eng._free_slots) == RCFG.max_seqs

    @pytest.mark.parametrize("mode", list(DISPATCH_MODES))
    def test_slot_reuse_rewrites_device_rows(self, engine_of, mode):
        """A retired slot reused by a new request must behave as a fresh
        row (seed/params rewritten at admission): an oversubscribed sampled
        workload matches the host-staged path request for request."""
        requests = {f"{wave}-{uid}": (p, dict(
            max_new_tokens=5, temperature=0.8, seed=100 + wave))
            for wave in (0, 1) for uid, p in _prompts(wave).items()}
        got = _generate(engine_of(True, **DISPATCH_MODES[mode]), requests)
        assert len(got) == 6
        assert _generate(engine_of(False, **DISPATCH_MODES[mode]),
                         requests) == got


# ---------------------------------------------------------------- step paths
# The engine takes a step in two ways (device-resident, host-staged), and ONE
# routine decides which rows either carries. The cases drive two engines into
# the same host state (both host-staged, so no pending window lags one of
# them) and pack one plan for each feed.
def _drive_to(eng, scenario):
    p = _prompts(41)
    if scenario == "prefill":
        eng.put("b", p["b"], max_new_tokens=8)
        eng.put("a", p["a"], max_new_tokens=8)
    elif scenario == "decode":
        eng.put("a", p["a"], max_new_tokens=8)
        eng.put("b", p["b"], max_new_tokens=8)
        while not (eng._running
                   and all(s.in_decode for s in eng._running.values())):
            eng.step()
    elif scenario == "mixed":
        eng.put("a", p["a"], max_new_tokens=8)
        eng.step()
        eng.step()
        eng.put("c", p["c"], max_new_tokens=8)
    else:  # "pressure": another holder leaves the pool two free blocks
        eng.put("c", p["c"], max_new_tokens=8)
    eng._admit_queued()
    if scenario == "pressure":
        eng.allocator.allocate(eng.allocator.free_blocks - 2)


class TestStepPaths:
    @pytest.mark.parametrize("scenario",
                             ["decode", "prefill", "mixed", "pressure"])
    @pytest.mark.parametrize("tile", [0, 8], ids=["untiled", "tiled"])
    def test_one_plan_for_device_feed_and_host_feed(self, engine_of, tile,
                                                    scenario):
        plans = []
        for host_feed in (False, True):   # two engines in one state at once
            eng = engine_of(False, second=host_feed, prefill_tile=tile)
            _drive_to(eng, scenario)
            plans.append(eng._pack_step(host_feed=host_feed))
        dev, host = plans
        # bucket, tokens, decode bucket, tiles, highest position, KV work
        assert dev[3:] == host[3:]
        (d_tok, d_slot, d_pos, d_flag), d_tiles = dev[0][:4], dev[0][4:]
        (h_tok, h_slot, h_pos, h_flag), h_tiles = host[0][:4], host[0][4:]
        assert (d_slot == h_slot).all()
        assert [(r, s.uid) for r, s in dev[1]] == \
            [(r, s.uid) for r, s in host[1]]
        assert ((d_flag & 2) == (h_flag & 2)).all()  # the rows that emit
        feed = (d_flag & 1) > 0  # device feed: token, position from slot rows
        assert not (h_flag & 1).any()
        assert (d_tok[~feed] == h_tok[~feed]).all()
        assert (d_pos[~feed] == h_pos[~feed]).all()
        for row, seq in host[1]:
            if feed[row]:  # host feed: the same row, from host state
                assert h_slot[row] == seq.slot
                assert h_pos[row] == seq.pos - 1
                assert h_tok[row] == seq.token_at(seq.pos - 1)
        assert len(d_tiles) == len(h_tiles) == (3 if tile else 0)
        for d, h in zip(d_tiles, h_tiles):
            assert (d == h).all()
        # the scenario is the one its name says
        n, nd, nt = dev[4], dev[5], dev[6]
        assert feed.any() == (scenario in ("decode", "mixed"))
        assert (n > feed.sum()) == (scenario != "decode")
        assert (nd > 0, nt > 0) == ((bool(tile) and feed.any()),
                                    (bool(tile) and scenario != "decode"))
        if scenario == "pressure":
            assert n == 2 * RCFG.block_size  # a partial chunk, not 16 tokens

    def test_warmup_compiles_nothing(self, monkeypatch):
        """What the benchmark's set-up relies on: ``warmup()`` compiles
        nothing, turns the compile cache on and zeroes the cold-dispatch
        baseline."""
        turned_on = []
        monkeypatch.setattr(
            "deepspeed_tpu.utils.compile_cache.enable_compile_cache",
            lambda: turned_on.append(True))
        eng = _engine_ds(True, prefill_tile=8)
        eng.put("a", _prompts()["a"], max_new_tokens=3)
        eng.generate_all()
        assert eng.program_cold_dispatches > 0
        assert eng.warmup() == 0
        assert turned_on == [True]
        assert eng.program_dispatches == eng.program_cold_dispatches == 0
        eng.put("b", _prompts()["a"], max_new_tokens=3)
        eng.generate_all()
        assert eng.program_dispatches > 0
        assert eng.program_cold_dispatches == 0  # the programs were there

    def test_ladder_second_rung_turns_tiles_off(self, engine_of):
        """Failures that go on after rung 1 (host-staged) take rung 2: the
        host-staged step with prefill tiles off, token-identical."""
        from deepspeed_tpu.utils.faults import (POINT_DISPATCH,
                                                  get_fault_injector)

        over = dict(prefill_tile=8, dispatch_retries=2, retry_backoff_s=0.0,
                    degrade_after=2)
        requests = {uid: (p, dict(max_new_tokens=6, temperature=0.8,
                                  seed=7 + len(p)))
                    for uid, p in _prompts(43).items()}
        # the clean run: the ladder's options do nothing while no step fails
        clean = _generate(engine_of(True, prefill_tile=8), requests)
        eng = _engine_ds(True, **over)
        get_fault_injector().configure(
            [{"point": POINT_DISPATCH, "after": 2, "times": 4}])
        assert _generate(eng, requests) == clean
        assert eng.degraded_mode == 2 and eng.step_failures == 4
        assert not eng.cfg.device_state
        assert eng.cfg.prefill_tile == 0 and not eng._use_tiles
        assert eng._step_keys and not eng._pending  # the plain step served
        assert eng.allocator.free_blocks == eng.cfg.num_blocks - 1


# -------------------------------------------- a cold compilation cache (PR 31)
class TestColdCachePrecompile:
    """When the first step program a process builds misses the persistent
    compilation cache, the engine compiles the others in the background;
    a warm cache, or no cache at all, sees no change."""

    def test_step_zoo_is_the_programs_the_sizes_allow(self):
        eng = _engine_ds(True, prefill_tile=4, max_tokens_per_step=16,
                         max_seqs=4)
        w = RCFG.max_blocks_per_seq
        assert eng._step_zoo() == [
            (4, 0, 1, w), (8, 0, 2, w), (16, 0, 4, w),           # no decoders
            (8, 4, 1, w), (12, 4, 2, w), (16, 4, 3, w), (4, 4, 0, w)]

    def test_warmup_hands_the_probe_the_compile_watchs_counter(
            self, monkeypatch, tmp_path):
        """With a cache directory the probe reads the process's ONE compile
        watch (builds whose executable jax wrote to the cache); without one
        there is no probe. The engine registers no listener of its own."""
        from jax._src import monitoring

        from deepspeed_tpu.telemetry.compile_watch import WATCH

        eng = _engine_ds(True, prefill_tile=4, max_tokens_per_step=16,
                         max_seqs=4)
        eng.warmup()
        assert eng._cache_misses is None
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
        eng.warmup()
        assert eng._cache_misses == WATCH.cache_writes
        assert eng._cache_misses() == WATCH.snapshot()["cache_writes"]
        assert [cb for cb in monitoring._event_listeners
                if getattr(cb, "__self__", None) is not WATCH] == []

    @pytest.mark.parametrize("first_miss,cpus", [
        (1, 4), (1, 16), (2, 4), (None, 4)],
        ids=["cold", "cold_small_zoo", "thinned", "warm"])
    def test_the_first_programs_miss_starts_the_others(self, engine_of,
                                                       first_miss, cpus,
                                                       monkeypatch):
        """The counter ``warmup`` installs (the compile watch's
        ``cache_writes``) is read before and after the FIRST step program's
        first call. It rises there (a cold cache: the others
        compile in the background), at a later program (a warm cache its size
        limit has thinned: no background compile of what is mostly there) or
        never. A zoo of no more programs than the pool has threads (six left
        of seven, eight threads on 16 cores) is left to the foreground: all
        of them would start at once and finish after it (PR 40)."""
        import os
        import threading
        import time

        monkeypatch.setattr(os, "cpu_count", lambda: cpus)

        sizes = dict(prefill_tile=4, max_tokens_per_step=16, max_seqs=4)
        eng, ref = _engine_ds(True, **sizes), engine_of(True, **sizes)
        reads = [0]

        def misses():   # two reads a probe: the second of the n-th rises
            reads[0] += 1
            return int(first_miss is not None and reads[0] >= 2 * first_miss)

        eng._cache_misses = misses
        built, build = [], eng._build_dev_step

        def recording(*key):
            if threading.current_thread().name.startswith("ragged-compile"):
                built.append(key[:4])
            return build(*key)

        eng._build_dev_step = recording
        for e in (eng, ref):
            for uid, p in _prompts(23).items():
                e.put(uid, p, max_new_tokens=6)
        first = None
        while eng.has_work:
            eng.step()
            first = first or next(iter(eng._dev_step_jits))[:4]
        ref.generate_all()
        assert {u: s.generated for u, s in eng._results.items()} == \
            {u: ref._results[u].generated for u in eng._results}
        for _ in range(600):
            if not any(t.name.startswith("ragged-compile")
                       for t in threading.enumerate()):
                break
            time.sleep(0.1)
        assert sorted(built) == (
            sorted(k for k in eng._step_zoo() if k != first)
            if first_miss == 1 and cpus == 4 else [])
        assert reads[0] == 2     # one probe an engine


# ----------------------------- tables laid out for their row gather (PR 52)
def _gauges(*names):
    from deepspeed_tpu import telemetry

    metrics = telemetry.snapshot()["metrics"]
    return [metrics[n]["series"][0]["value"] for n in names]


class TestTablesForTheirRowGather:
    """A table a step gathers rows from is held row-major from the moment the
    engine takes the parameters (``ragged.lay_out_for_row_gather``). Off the
    chip every array is row-major and the rule is inert; ``relaid`` makes it
    engage by saying of every table that it is not (the chip says so of GPT-2
    XL's: tests/unit/test_compile_tpu.py drives the rule on the described
    chip's own layouts)."""

    SIZES = dict(prefill_tile=4, max_tokens_per_step=16, max_seqs=4)

    def _served(self, monkeypatch, relaid):
        import dataclasses

        import jax

        from deepspeed_tpu import telemetry
        from deepspeed_tpu.inference import ragged
        from deepspeed_tpu.telemetry.compile_watch import WATCH

        if relaid:
            monkeypatch.setattr(ragged, "_lies_row_major", lambda a: False)
        params = jax.jit(lambda key: llama.init_params(CFG, key))(
            jax.random.PRNGKey(0))
        handed = params["embed"]
        WATCH.install()
        before = len(WATCH.snapshot()["builds"])
        telemetry.configure(enabled=True)
        try:
            eng = RaggedInferenceEngine(
                lambda ctx: llama.build(CFG, ctx=ctx),
                dataclasses.replace(RCFG, **self.SIZES), dtype=jnp.float32,
                params=params, seed=0)
            gauges = _gauges("engine_tables_relaid",
                             "engine_tables_relaid_bytes")
        finally:
            telemetry.configure(enabled=False)
        for uid, p in _prompts(23).items():
            eng.put(uid, p, max_new_tokens=6)
        eng.generate_all()
        tokens = {u: s.generated for u, s in eng._results.items()}
        builds = [b["program"] for b in WATCH.snapshot()["builds"][before:]]
        return eng, handed, gauges, tokens, builds

    @pytest.mark.parametrize("relaid", [False, True],
                             ids=["as_made", "relaid"])
    def test_the_background_lowers_what_a_dispatch_looks_up(
            self, monkeypatch, relaid):
        """``abstract_like``, from which ``_precompile_zoo_in_background``
        lowers: every leaf's shape and dtype, and a committed leaf's format
        (a re-laid table's layout among them; before PR 52 it said shape and
        dtype alone, and a cold set-up compiled every program of such an
        engine twice). What it is for: the program lowered from the abstract
        values is, to the letter, the one lowered from the arrays."""
        import jax

        from deepspeed_tpu.inference.ragged import abstract_like

        eng, *_ = self._served(monkeypatch, relaid)
        real = (eng.params, eng.cache, eng._dev_state, eng._tables_dev())
        leaves = jax.tree_util.tree_leaves(real)
        for x, a in zip(leaves, jax.tree_util.tree_leaves(abstract_like(real))):
            assert (a.shape, a.dtype) == (x.shape, x.dtype)
            assert a.format == x.format if x.committed else \
                a.sharding is None and a.format.layout is None
        assert any(x.committed for x in leaves) == relaid
        t, nd, nt, w = key = eng._step_zoo()[-2]
        staged = jnp.zeros((4 * t + 3 * max(nt, 1),), jnp.int32)

        def text(*args):
            return eng._build_dev_step(*key, False, False, False).lower(
                *args).as_text()

        assert text(*abstract_like(real), abstract_like(staged),
                    abstract_like(eng._sample_root)) == text(
                        *real, staged, eng._sample_root)

    def test_row_major_tables_build_nothing(self, monkeypatch):
        """No table of the model is column-major (none is off the chip, and
        on it none of a family whose rows are whole tiles): no program, no
        array moved, the caller's arrays the engine's, both gauges 0."""
        eng, handed, gauges, _, builds = self._served(monkeypatch, False)
        assert (eng.tables_relaid, eng.tables_relaid_bytes) == (0, 0)
        assert gauges == [0, 0]
        assert eng.params["embed"] is handed and not handed.is_deleted()
        assert "jit_ragged_tables_row_major" not in builds

    def test_relaid_tables_cost_one_program_and_change_no_token(
            self, monkeypatch, engine_of):
        """With a table to re-lay: ONE program for all of them, their old
        buffers given up, the same tree of the same values (the same tokens),
        and no other program built twice: a re-laid table is a committed
        array, what a program returns that took one is committed too, so the
        pool and the slot rows are committed from the start, and again after
        the engine's containment rebuilt them. Without that the first step
        program and ``ragged_slot_rows`` are each built a second time."""
        import jax

        ref = engine_of(True, **self.SIZES)     # built as any engine is
        eng, handed, gauges, tokens, builds = self._served(monkeypatch, True)
        table = CFG.vocab_size * CFG.hidden_size * 4
        assert (eng.tables_relaid, eng.tables_relaid_bytes) == (1, table)
        assert gauges == [1, table]
        assert handed.is_deleted() and eng.params["embed"].committed
        assert builds.count("jit_ragged_tables_row_major") == 1
        for uid, p in _prompts(23).items():
            ref.put(uid, p, max_new_tokens=6)
        ref.generate_all()
        assert tokens == {u: ref._results[u].generated for u in tokens}
        steps = [b for b in builds if b.startswith("jit_ragged_step_")
                 or b == "jit_ragged_slot_rows"]
        assert steps and len(steps) == len(set(steps))
        eng.reset_state()
        assert all(x.committed for x in jax.tree_util.tree_leaves(
            (eng.cache, eng._dev_state)))
