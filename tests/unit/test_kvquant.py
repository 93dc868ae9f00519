"""Low-bit KV serving (ops/kvquant.py).

The contract under test: with ``RaggedConfig.quant`` set, every KV block —
HBM pool, prefix-cache retained set, host/disk tiers, handoff wire — is
stored low-bit (int8 / fp8-e4m3) with per-row-per-head scales, quantized
ONCE at the paged write site and dequantized inside the jitted gather; the
drift vs the fp path stays inside ``DRIFT_BUDGET`` across every dispatch
mode, the accounting (bytes-per-token, block bytes, memledger, admission
headroom) sees the quantized sizes, a persisted record read back under a
different codec config raises, and ``quant="off"`` (the default) keeps the
engine bit-identical to the unquantized path.
"""

import pickle

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from step_modes import MODES  # tests/unit is rootdir-inserted by pytest

from deepspeed_tpu.comm.comm import init_distributed
from deepspeed_tpu.comm.topology import reset_topology
from deepspeed_tpu.config.config import MeshConfig
from deepspeed_tpu.ops import kvquant
from deepspeed_tpu.ops.kvquant import (
    DRIFT_BUDGET,
    QuantizedKV,
    drift_verdict,
    get_codec,
    paged_block_bytes,
    parse_quant,
    quantize_kv_rows,
    dequantize_kv_rows,
    token_match_rate,
)
from deepspeed_tpu.inference.ragged import (
    KVHandoff,
    RaggedConfig,
    RaggedInferenceEngine,
)
from deepspeed_tpu.models import llama
from deepspeed_tpu.models.paged import init_paged_pool

CFG = llama.LlamaConfig(
    vocab_size=97, hidden_size=32, intermediate_size=64,
    num_layers=2, num_heads=4, num_kv_heads=2, max_seq_len=128,
)

BS = 4

SHARED = [11, 7, 3, 5, 2, 13, 17, 19]          # two full blocks of 4
PROMPT_A = SHARED + [23, 29, 31]
PROMPT_B = SHARED + [37, 41]
PROMPTS = {0: [5, 6, 7, 8, 9, 10], 1: [11, 12, 13],
           2: [1, 2, 3, 4, 5, 6, 7, 8, 9]}


def _engine(quant="off", quantize_bits=0, **over):
    kw = dict(max_tokens_per_step=16, max_seqs=3, block_size=BS,
              num_blocks=29, max_blocks_per_seq=16, quant=quant)
    kw.update(over)
    return RaggedInferenceEngine(
        model=lambda ctx: llama.build(CFG, ctx=ctx),
        ragged_config=RaggedConfig(**kw), dtype=jnp.float32, seed=0,
        quantize_bits=quantize_bits)


def _run(eng, prompts=PROMPTS, max_new=8, temperature=0.0):
    for i, p in prompts.items():
        kw = dict(max_new_tokens=max_new)
        if temperature:
            kw.update(temperature=temperature, seed=100 + int(i))
        eng.put(i, p, **kw)
    return eng.generate_all()


# ----------------------------------------------------------------- codec math
class TestCodec:
    def test_roundtrip_relative_error(self):
        rng = np.random.default_rng(0)
        x = jnp.asarray(rng.normal(size=(64, 2, 64)).astype(np.float32)) * 3.0
        for name, tol in (("int8", 0.02), ("fp8", 0.08)):
            q, s = quantize_kv_rows(x, get_codec(name))
            back = dequantize_kv_rows(q, s)
            err = float(jnp.max(jnp.abs(back - x)))
            amax = float(jnp.max(jnp.abs(x)))
            assert err <= tol * amax, (name, err, amax)

    def test_zero_rows_exact_and_scale_one(self):
        x = jnp.zeros((4, 2, 8), jnp.float32)
        q, s = quantize_kv_rows(x, get_codec("int8"))
        np.testing.assert_array_equal(np.asarray(q), 0)
        np.testing.assert_array_equal(np.asarray(s), 1.0)
        np.testing.assert_array_equal(np.asarray(dequantize_kv_rows(q, s)), 0.0)

    def test_row_independence(self):
        # rewriting one row must not change another's quantization: scales
        # are per (row, head), so quantizing rows separately == together
        rng = np.random.default_rng(1)
        x = jnp.asarray(rng.normal(size=(6, 2, 16)).astype(np.float32))
        c = get_codec("int8")
        q_all, s_all = quantize_kv_rows(x, c)
        q_one, s_one = quantize_kv_rows(x[3:4], c)
        np.testing.assert_array_equal(np.asarray(q_all[3:4]), np.asarray(q_one))
        np.testing.assert_array_equal(np.asarray(s_all[3:4]), np.asarray(s_one))

    def test_fp8_saturates_instead_of_overflowing(self):
        x = jnp.full((1, 1, 4), 1e4, jnp.float32)
        q, s = quantize_kv_rows(x, get_codec("fp8"))
        assert np.all(np.isfinite(np.asarray(q, dtype=np.float32)))

    def test_unknown_codec_raises(self):
        with pytest.raises(ValueError, match="unknown KV codec"):
            get_codec("int3")


class TestParseQuant:
    def test_grammar(self):
        assert parse_quant(None) == (None, 0, False)
        assert parse_quant("off") == (None, 0, False)
        p = parse_quant("int8+woq8+qcol")
        assert p.kv.name == "int8" and p.woq_bits == 8 and p.qcol
        assert parse_quant("fp8").kv.name == "fp8"
        assert parse_quant("woq4").woq_bits == 4

    def test_rejects_conflicts_and_unknowns(self):
        with pytest.raises(ValueError, match="more than one KV codec"):
            parse_quant("int8+fp8")
        with pytest.raises(ValueError, match="more than one woq"):
            parse_quant("woq8+woq4")
        with pytest.raises(ValueError, match="unknown component"):
            parse_quant("int8+turbo")
        with pytest.raises(ValueError, match="must be a string"):
            parse_quant(8)


# ------------------------------------------------------------------ the pytree
def _init_fn(nb, bs, dtype, codec=None, heads=2, dim=64, layers=2):
    """A family's ``init_paged_cache_fn``: the contract's storage form,
    ``[L, nb, bs, H*D]`` (``models/paged.py``)."""
    return init_paged_pool(layers, nb, bs, heads, dim, dtype, codec)


class TestQuantizedKV:
    def test_pool_built_at_storage_precision(self):
        pool = _init_fn(8, BS, jnp.float16, get_codec("int8"))
        k = pool["k"]
        assert k.q.dtype == jnp.int8 and k.s.dtype == jnp.float16
        assert k.shape == (2, 8, BS, 2 * 64)     # payload shape
        assert k.dtype == np.dtype("float16")    # COMPUTE dtype
        # one scale per (row, head), one lane-dense row a block
        assert k.s.shape == (2, 8, BS * 2)

    def test_resident_multiplier_vs_fp16_clears_floor(self):
        # at head_dim 64: int8 payload + f16 per-row-per-head scale is
        # 1 + 2/64 bytes/elem vs 2 -> ~1.94x, over the 1.8x acceptance floor
        pool = _init_fn(8, BS, jnp.float16, get_codec("int8"))
        q_bytes = sum(leaf.nbytes for leaf in pool.values())
        fp16_bytes = sum(
            a.nbytes for a in jax.tree_util.tree_leaves(
                _init_fn(8, BS, jnp.float16)))
        assert fp16_bytes / q_bytes >= 1.8

    def test_paged_block_bytes(self):
        # [L=2, nb, bs=4, H*D=2*64] fp16 k+v: 2*4*2*64*2 bytes * 2 leaves
        assert paged_block_bytes(_init_fn, 8, BS, jnp.float16) == \
            2 * (2 * BS * 2 * 64 * 2)

    def test_tree_map_and_scan_slicing_preserve_wrapper(self):
        pool = _init_fn(4, BS, jnp.float32, get_codec("fp8"))
        sliced = jax.tree_util.tree_map(lambda a: a[:, :2], pool)
        assert isinstance(sliced["k"], QuantizedKV)
        assert sliced["k"].codec == "fp8"
        assert sliced["k"].shape[1] == 2 and sliced["k"].s.shape[1] == 2

    def test_pickle_roundtrip(self):
        pool = _init_fn(4, BS, jnp.float32, get_codec("int8"))
        back = pickle.loads(pickle.dumps(pool["k"]))
        assert back.codec == "int8" and back.is_quantized_kv
        assert np.asarray(back.q).shape == pool["k"].q.shape
        assert back.nbytes == pool["k"].nbytes

    def test_scatter_then_gather_roundtrip(self):
        full = _init_fn(4, BS, jnp.float32, get_codec("int8"))["k"]
        # the pool the way a layer sees it: L and nb merged, through the
        # pytree; layer 1's blocks start at 4
        pool = jax.tree_util.tree_map(
            lambda a: a.reshape((-1,) + a.shape[2:]), full)
        rng = np.random.default_rng(2)
        rows = jnp.asarray(rng.normal(size=(3, 2, 64)).astype(np.float32))
        blk = jnp.asarray([5, 5, 6]); off = jnp.asarray([0, 1, 3])
        pool = pool.scatter_rows(blk, off, rows)
        got = pool.gather_dequant(jnp.asarray([[5, 6]]))  # [1, 2, bs, H, D]
        assert got.shape == (1, 2, BS, 2, 64)
        amax = float(jnp.max(jnp.abs(rows)))
        np.testing.assert_allclose(np.asarray(got[0, 0, 0]),
                                   np.asarray(rows[0]), atol=0.02 * amax)
        np.testing.assert_allclose(np.asarray(got[0, 1, 3]),
                                   np.asarray(rows[2]), atol=0.02 * amax)
        # rows nobody wrote stay zero: scales landed in their own cells
        assert not np.asarray(got[0, 0, 2:]).any()
        assert not np.asarray(got[0, 1, :3]).any()


# --------------------------------------------------------- drift-gated parity
@pytest.fixture(scope="module")
def ref():
    """One fp32 plain-mode reference, greedy and seeded. The dispatch modes
    are token-identical to the plain path by the engine's own contract
    (pinned in test_ragged/test_kvtier), so this single baseline serves
    every mode's drift comparison."""
    eng = _engine()
    return {"greedy": _run(eng), "seeded": _run(eng, temperature=0.8)}


class TestEngineParity:
    def test_quant_off_is_bit_identical_and_plain_pool(self, ref):
        # the off path must not even build QuantizedKV wrappers
        explicit = _engine(quant="off")
        assert not hasattr(explicit.cache["k"], "is_quantized_kv")
        assert _run(explicit) == ref["greedy"]
        assert _run(explicit, temperature=0.8) == ref["seeded"]

    @pytest.mark.parametrize("mode", list(MODES))
    def test_int8_greedy_within_budget_all_modes(self, mode, ref):
        got = _run(_engine("int8", **MODES[mode]))
        assert token_match_rate(ref["greedy"], got) >= \
            DRIFT_BUDGET["greedy_match_min"]

    def test_fp8_greedy_and_seeded_within_budget(self, ref):
        q = _engine("fp8")
        assert token_match_rate(ref["greedy"], _run(q)) >= \
            DRIFT_BUDGET["greedy_match_min"]
        assert token_match_rate(ref["seeded"],
                                _run(q, temperature=0.8)) >= \
            DRIFT_BUDGET["greedy_match_min"]

    def test_int8_seeded_sampling_deterministic(self, ref):
        q = _engine("int8")
        a = _run(q, temperature=0.8)
        b = _run(q, temperature=0.8)
        assert a == b
        assert token_match_rate(ref["seeded"], a) >= \
            DRIFT_BUDGET["greedy_match_min"]

    def test_prefix_cache_hit_parity(self):
        # a quant engine serving PROMPT_B from PROMPT_A's cached blocks must
        # match a cold quant engine exactly: the retained set holds the SAME
        # quantized payload the write produced (no second rounding)
        warm = _engine("int8", enable_prefix_cache=True)
        warm.put("warm", PROMPT_A, max_new_tokens=4)
        warm.generate_all()
        warm.put("g", PROMPT_B, max_new_tokens=6)
        got = warm.generate_all()
        assert warm.prefix_hits >= 1
        cold = _engine("int8", enable_prefix_cache=False)
        cold.put("g", PROMPT_B, max_new_tokens=6)
        assert got["g"] == cold.generate_all()["g"]


class TestTierAndHandoff:
    def test_demote_promote_roundtrip_token_identical(self, tmp_path):
        t = _engine("int8", num_blocks=13, enable_prefix_cache=True,
                    kv_tier=True, kv_tier_host_blocks=2,
                    kv_tier_disk_blocks=64, kv_tier_dir=str(tmp_path),
                    kv_tier_prefill_tokens_per_s=1e-6)
        t.put("warm", PROMPT_A, max_new_tokens=4)
        t.generate_all()
        for i in range(6):  # churn: force demotion of the shared blocks
            t.put(f"churn{i}", [50 + i * 7 + j for j in range(9)],
                  max_new_tokens=4)
            t.generate_all()
        t.put("g", PROMPT_B, max_new_tokens=6)
        got = t.generate_all()
        st = t._kvtier.stats()
        assert st["demotions"] > 0 and st["promotions"] > 0
        assert st["codec"] == "int8"
        cold = _engine("int8", enable_prefix_cache=False)
        cold.put("g", PROMPT_B, max_new_tokens=6)
        assert got["g"] == cold.generate_all()["g"]

    @pytest.fixture(scope="class")
    def int8_handoff(self):
        src = _engine("int8")
        src.put("h", PROMPT_A, max_new_tokens=5, handoff=True)
        src.generate_all()
        return src.export_handoff("h")

    def test_handoff_resume_across_quant_engines(self, int8_handoff):
        assert int8_handoff.codec == "int8"
        dst = _engine("int8")
        assert dst.import_handoff(
            KVHandoff.from_bytes(int8_handoff.to_bytes()))
        got = dst.generate_all()
        cold = _engine("int8", enable_prefix_cache=False)
        cold.put("h", PROMPT_A, max_new_tokens=5)
        assert got["h"] == cold.generate_all()["h"]

    def test_handoff_codec_mismatch_raises(self, int8_handoff):
        with pytest.raises(ValueError, match="codec"):
            _engine("off").import_handoff(int8_handoff)
        with pytest.raises(ValueError, match="codec"):
            _engine("fp8").import_handoff(int8_handoff)

    def test_prefix_transfer_codec_mismatch_is_graceful_miss(self):
        src = _engine("int8", enable_prefix_cache=True)
        src.put("warm", PROMPT_A, max_new_tokens=4)
        src.generate_all()
        payload = src.export_prefix(PROMPT_B)
        assert payload is not None and payload.codec == "int8"
        # matched codec imports; mismatched codec returns 0, never raises
        dst_ok = _engine("int8", enable_prefix_cache=True)
        assert dst_ok.import_prefix(payload) > 0
        dst_off = _engine("off", enable_prefix_cache=True)
        assert dst_off.import_prefix(payload) == 0


# ------------------------------------------------------- accounting surfaces
class TestAccounting:
    def test_bytes_per_token_and_block_bytes_shrink(self):
        off, q = _engine("off"), _engine("int8")
        assert q.kv_bytes_per_token() < off.kv_bytes_per_token()
        assert q._block_bytes() < off._block_bytes()
        # int8 payload + f16 scales at head_dim 8: 1.25 bytes/elem vs 4 fp32
        assert off.kv_bytes_per_token() / q.kv_bytes_per_token() \
            == pytest.approx(3.2)

    def test_kv_quant_stats_surface(self):
        q = _engine("int8")
        _run(q, max_new=4)
        st = q.kv_quant_stats()
        assert st["codec"] == "int8"
        assert st["resident_multiplier_vs_fp16"] == pytest.approx(
            st["fp16_block_bytes"] / st["block_bytes"])
        assert st["blocks_allocated_total"] > 0
        assert st["bytes_saved_total"] == st["blocks_allocated_total"] * (
            st["fp_block_bytes"] - st["block_bytes"])
        assert _engine("off").kv_quant_stats() is None

    def test_memledger_owner_counts_quantized_bytes(self, tmp_path):
        from deepspeed_tpu import telemetry
        tel = telemetry.configure(enabled=True, memledger={
            "enabled": True, "report_dir": str(tmp_path)})
        try:
            q = _engine("int8")
            _run(q, max_new=4)
            led = tel.memledger
            owners = led.breakdown()["owners"]
            want = sum(int(a.nbytes)
                       for a in jax.tree_util.tree_leaves(q.cache))
            assert owners["kv_pool"] == want
            assert led.census()["unattributed_fraction"] <= 0.05
            snap = telemetry.snapshot()["metrics"]
            assert snap["kvquant_enabled"]["series"][0]["value"] == 1.0
            assert snap["kvquant_bytes_saved_total"]["series"][0]["value"] > 0
        finally:
            telemetry.configure(enabled=False)

    def test_woq_component_equals_quantize_bits(self):
        a = _engine("woq8")
        b = _engine(quantize_bits=8)
        assert a.quantize_bits == b.quantize_bits == 8
        assert _run(a, max_new=4) == _run(b, max_new=4)


# ------------------------------------------------- quantized TP collective
class TestQuantizedCollective:
    @pytest.fixture
    def mesh(self):
        reset_topology()
        yield init_distributed(MeshConfig(data=2, tensor=4)).mesh
        reset_topology()

    def test_int8_wire_in_hlo_and_argmax_parity(self, mesh):
        x = jax.random.normal(jax.random.PRNGKey(0), (3, 64), jnp.float32)
        f = jax.jit(lambda v: kvquant.quantized_logits_all_gather(
            v, mesh, axis="tensor"))
        out = f(x)
        assert bool(jnp.all(jnp.argmax(out, -1) == jnp.argmax(x, -1)))
        assert float(jnp.max(jnp.abs(out - x))) < 0.05
        txt = f.lower(x).compile().as_text()
        ag = [l for l in txt.splitlines() if "all-gather" in l]
        assert ag and any("s8[" in l for l in ag)

    def test_identity_fallbacks(self, mesh):
        x = jnp.ones((2, 63))
        assert kvquant.quantized_logits_all_gather(x, None) is x
        # vocab not divisible by the shard count: identity, not an error
        out = kvquant.quantized_logits_all_gather(x, mesh, axis="tensor")
        assert out.shape == x.shape
        assert kvquant.quantized_logits_all_gather(
            x, mesh, axis="absent") is x


# ------------------------------------------------------------- drift verdict
class TestDriftVerdict:
    def test_token_match_rate_prefix_semantics(self):
        want = {0: [1, 2, 3, 4], 1: [5, 6]}
        assert token_match_rate(want, want) == 1.0
        got = {0: [1, 2, 9, 4], 1: [5, 6]}  # divergence stops the prefix
        assert token_match_rate(want, got) == pytest.approx(4 / 6)
        assert token_match_rate({}, {}) == 1.0

    def test_verdict_applies_budget(self):
        ok = drift_verdict(0.99)
        assert ok["ok"] and ok["budget"] == DRIFT_BUDGET
        assert ok["greedy_token_match_rate"] == 0.99
        assert not drift_verdict(0.90)["ok"]
        assert drift_verdict(0.90, {"greedy_match_min": 0.85})["ok"]
