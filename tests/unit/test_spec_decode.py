"""Device-side multi-step decode scheduling + self-speculative decoding
(``sched_steps`` / ``spec_draft``): token-identity parity against the plain
host-staged path across every dispatch mode (greedy AND seeded), prefix-cache
hits, mid-flight cancel during a multi-step chunk, mid-chunk EOS retirement,
warmup coverage of the scheduler program family, and the speculation
telemetry counters."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest
import step_modes  # tests/unit is rootdir-inserted by pytest

from deepspeed_tpu import telemetry
from deepspeed_tpu.inference.ragged import RaggedConfig, RaggedInferenceEngine
from deepspeed_tpu.inference.sampling import accept_drafts, propose_ngram_drafts
from deepspeed_tpu.models import llama

CFG = llama.LlamaConfig(
    vocab_size=97, hidden_size=32, intermediate_size=64,
    num_layers=2, num_heads=4, num_kv_heads=2, max_seq_len=128,
)
RCFG = RaggedConfig(
    max_tokens_per_step=16, max_seqs=3, block_size=4,
    num_blocks=49, max_blocks_per_seq=16,
)

# the step options the scheduler loop rides on and must stay
# token-identical under
MODES = {m: step_modes.MODES[m] for m in ("plain", "tiled")}


def _engine(**over):
    cfg = dataclasses.replace(RCFG, **over)
    return RaggedInferenceEngine(
        lambda ctx: llama.build(CFG, ctx=ctx), cfg, dtype=jnp.float32, seed=0)


def _prompts(rng=0):
    r = np.random.default_rng(rng)
    return {
        "a": list(r.integers(0, CFG.vocab_size, 5)),
        "b": list(r.integers(0, CFG.vocab_size, 11)),
        "c": list(r.integers(0, CFG.vocab_size, 23)),
    }


def _mixed_load(eng, max_new=8):
    """Greedy rows + seeded-sampled rows in one batch (the scheduler
    program's sampled/greedy lanes must agree with the host path on both)."""
    for uid, p in _prompts(17).items():
        eng.put(uid, p, max_new_tokens=max_new)
    eng.put("s1", _prompts(19)["b"], max_new_tokens=max_new,
            temperature=0.9, top_k=20, seed=123)
    eng.put("s2", _prompts(19)["a"], max_new_tokens=max_new,
            temperature=0.7, top_p=0.9, seed=7)
    return eng.generate_all()


class TestSamplingPrimitives:
    def test_propose_ngram_drafts_most_recent_match(self):
        # row 0: suffix [5, 6] occurred earlier twice; the MOST RECENT
        # match (ending at index 6) supplies the continuation [9, 9, 9]
        hist = jnp.asarray([
            [5, 6, 7, 8, 0, 5, 6, 9, 9, 9, 5, 6],
            [1, 2, 3, 4, 5, 6, 7, 8, 9, 10, 11, 12],
        ], jnp.int32)
        pos = jnp.asarray([11, 5], jnp.int32)
        draft, matched = propose_ngram_drafts(hist, pos, ngram=2, depth=3)
        assert bool(matched[0])
        assert list(np.asarray(draft[0])) == [9, 9, 9]
        assert not bool(matched[1])          # no earlier [5, 6] in row 1
        assert list(np.asarray(draft[1])) == [0, 0, 0]

    def test_accept_drafts_prefix_budget_eos(self):
        draft = jnp.asarray([[4, 5, 6], [4, 5, 6], [4, 5, 6]], jnp.int32)
        picked = jnp.asarray([
            [4, 5, 9, 7],    # 2 leading matches -> emit 3 (incl. bonus)
            [4, 5, 6, 7],    # full match, but budget clamps to 2
            [4, 99, 6, 7],   # picked[1] is EOS -> truncate inclusive
        ], jnp.int32)
        budget = jnp.asarray([4, 2, 4], jnp.int32)
        eos = jnp.asarray([-1, -1, 99], jnp.int32)
        n_emit, n_acc = accept_drafts(draft, picked, budget, eos)
        assert list(np.asarray(n_emit)) == [3, 2, 2]
        assert list(np.asarray(n_acc)) == [2, 2, 1]


class TestSchedSpecParity:
    """The multi-step scheduler (and speculation on top of it) must emit
    EXACTLY the plain host-staged streams — greedy and seeded — in every
    dispatch mode, because acceptance is exact-match against the target's
    own deterministic picks."""

    @pytest.mark.parametrize("mode", list(MODES))
    def test_token_parity_vs_host_staged(self, mode):
        kw = MODES[mode]
        want = _mixed_load(_engine(device_state=False, **kw))
        sched = _engine(sched_steps=8, **kw)
        assert _mixed_load(sched) == want
        spec = _engine(sched_steps=8, spec_draft=4, **kw)
        assert _mixed_load(spec) == want
        # the sampled stream really sampled (not a greedy fallback)
        greedy = _engine(**kw)
        greedy.put("s1", _prompts(19)["b"], max_new_tokens=8)
        assert greedy.generate_all()["s1"] != want["s1"]

    def test_sched_cuts_dispatches_per_token(self):
        """The whole point of the tentpole: K decode steps per dispatch
        (no admission pressure: the batch fits max_seqs, so nothing caps
        the chunk depth)."""
        outs, engines = {}, {}
        for name, kw in (("base", {"device_state": False}),
                         ("sched", {"sched_steps": 8})):
            eng = _engine(**kw)
            for uid, p in _prompts(17).items():
                eng.put(uid, p, max_new_tokens=10)
            outs[name] = eng.generate_all()
            engines[name] = eng
        assert outs["sched"] == outs["base"]
        base, sched = engines["base"], engines["sched"]
        assert sched.tokens_emitted == base.tokens_emitted
        assert sched.dispatch_count < base.dispatch_count / 2

    def test_sched_off_by_default(self):
        cfg = RaggedConfig()
        assert cfg.sched_steps == 0 and cfg.spec_draft == 0

    def test_config_validation(self):
        with pytest.raises(ValueError):
            _engine(sched_steps=1)
        with pytest.raises(ValueError):
            _engine(spec_draft=2)          # requires sched_steps >= 2
        with pytest.raises(ValueError):
            _engine(sched_steps=4, spec_draft=2, spec_ngram=0)

    def test_kill_switch_leaves_sched_inert(self):
        """device_state=False (the watchdog's degradation rung) silently
        disables the scheduler loop — bit-identical host-staged behavior,
        zero sched dispatches."""
        base = _engine(device_state=False)
        want = _mixed_load(base)
        killed = _engine(device_state=False, sched_steps=8, spec_draft=4)
        assert _mixed_load(killed) == want
        assert killed.dispatch_count == base.dispatch_count

    def test_eos_mid_sched_chunk_truncates(self):
        """A row hitting EOS inside a multi-step chunk retires ON DEVICE:
        tokens after the EOS are never surfaced and the stream matches the
        host-staged run exactly."""
        prompts = _prompts(5)
        probe = _engine(device_state=False)
        for uid, p in prompts.items():
            probe.put(uid, p, max_new_tokens=10)
        ref = probe.generate_all()
        eos = int(ref["b"][2])  # EOS strikes mid-chunk for row "b"
        outs = {}
        for k, kw in (("host", {"device_state": False}),
                      ("sched", {"sched_steps": 8}),
                      ("spec", {"sched_steps": 8, "spec_draft": 4})):
            eng = _engine(**kw)
            for uid, p in prompts.items():
                eng.put(uid, p, max_new_tokens=10, eos_token_id=eos)
            outs[k] = eng.generate_all()
        assert outs["sched"] == outs["host"]
        assert outs["spec"] == outs["host"]
        assert outs["host"]["b"][-1] == eos and len(outs["host"]["b"]) <= 10

    def test_spec_counters_and_acceptance_gauge(self):
        telemetry.configure(enabled=True)
        eng = _engine(sched_steps=8, spec_draft=4)
        # a repetitive prompt gives the n-gram draft source real matches
        pat = [3, 1, 4, 1, 5] * 4
        eng.put("r", pat, max_new_tokens=12)
        eng.generate_all()
        assert eng.spec_proposed > 0
        assert 0 <= eng.spec_accepted <= eng.spec_proposed
        tel = telemetry.get_telemetry()
        assert tel.registry.counter(
            "spec_tokens_proposed_total").value() == eng.spec_proposed
        assert tel.registry.counter(
            "spec_tokens_accepted_total").value() == eng.spec_accepted
        rate = tel.registry.gauge("spec_acceptance_rate").value()
        assert rate == pytest.approx(
            eng.spec_accepted / eng.spec_proposed)


class TestPrefixCacheHitParity:
    def test_hit_parity_with_sched_and_spec(self):
        """A prefix-cache hit under the scheduler loop must still be
        token-identical to a cold run, greedy and seeded."""
        shared = [11, 7, 3, 5, 2, 13, 17, 19]      # two full blocks of 4
        warm_p = shared + [23, 29, 31]
        hit_p = shared + [37, 41]
        cold = _engine(sched_steps=8, spec_draft=4)
        cold.put("g", hit_p, max_new_tokens=8)
        cold.put("s", hit_p, max_new_tokens=8, temperature=0.9, top_k=20,
                 seed=123)
        want = cold.generate_all()

        warm = _engine(sched_steps=8, spec_draft=4,
                       enable_prefix_cache=True)
        warm.put("w", warm_p, max_new_tokens=6)
        warm.generate_all()
        warm.put("g", hit_p, max_new_tokens=8)
        warm.put("s", hit_p, max_new_tokens=8, temperature=0.9, top_k=20,
                 seed=123)
        got = warm.generate_all()
        assert warm.prefix_hits == 2
        assert got["g"] == want["g"] and got["s"] == want["s"]


class TestCancelMidMultiStep:
    @pytest.mark.parametrize("spec", [0, 4])
    def test_cancel_during_inflight_sched_chunk(self, spec):
        """cancel() while a multi-step chunk is in flight: the sequence
        retires via deferred release, blocks and slot recycle, and the
        surviving request's stream is unperturbed."""
        want = None
        for with_cancel in (False, True):
            eng = _engine(sched_steps=8, spec_draft=spec)
            prompts = _prompts(29)
            eng.put("keep", prompts["b"], max_new_tokens=8)
            if with_cancel:
                eng.put("dead", prompts["c"], max_new_tokens=8)
            # drive until a multi-step chunk is actually in flight
            for _ in range(50):
                eng.step()
                if any(r.get("kind") == "sched" for r in eng._pending):
                    break
            assert any(r.get("kind") == "sched" for r in eng._pending)
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
        assert eng.allocator.free_blocks == RCFG.num_blocks - 1


class TestWarmupCoverage:
    def test_warmup_lowers_sched_programs(self):
        """warmup() must precompile the multi-step scheduler family: live
        traffic then creates NO scheduler program (every cold dispatch is a
        SplitFuse step program, which warmup() leaves to a server's set-up)
        and the coverage gauge says so."""
        telemetry.configure(enabled=True)
        eng = _engine(sched_steps=4)
        assert eng.cfg.device_state
        n = eng.warmup()
        assert n > 0
        assert len(eng._dev_sched_jits) == n   # actually lowered
        legacy = _engine(device_state=False)
        for uid, p in _prompts(31).items():
            eng.put(uid, p, max_new_tokens=6)
            legacy.put(uid, p, max_new_tokens=6)
        assert eng.generate_all() == legacy.generate_all()
        assert len(eng._dev_sched_jits) == n, (
            "serve-time scheduler compile after warmup")
        assert eng.program_dispatches > 0
        assert eng.program_cold_dispatches == len(eng._dev_step_jits)
        tel = telemetry.get_telemetry()
        eng._sample_step_telemetry()
        assert tel.registry.gauge("ragged_warmup_coverage").value() == \
            1.0 - eng.program_cold_dispatches / eng.program_dispatches

    def test_warmup_covers_spec_variant(self):
        eng = _engine(sched_steps=4, spec_draft=2)
        n = eng.warmup()
        assert n > 0
        legacy = _engine(device_state=False)
        for uid, p in _prompts(37).items():
            eng.put(uid, p, max_new_tokens=6)
            legacy.put(uid, p, max_new_tokens=6)
        assert eng.generate_all() == legacy.generate_all()
        assert len(eng._dev_sched_jits) == n
