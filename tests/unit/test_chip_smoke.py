"""``chip_smoke.py`` off the chip: ``main()`` refuses the CPU and prints no
result; its phase functions — plain functions of a model config — run at
``GPT2Config.tiny()`` size on the CPU test mesh (Pallas kernels interpreted),
which is the rehearsal every chip run starts from."""

import contextlib
import dataclasses
import io
import json
import os
import subprocess
import sys

import pytest

from deepspeed_tpu.inference.ragged import RaggedConfig
from deepspeed_tpu.models import gpt2

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

import chip_smoke  # noqa: E402

TINY = gpt2.GPT2Config.tiny()
REQUESTS = chip_smoke.serve_requests(8, (8, 64), (4, 16), TINY.vocab_size)


def _run(phase, *args, **kwargs):
    """The phase's result and the one JSON line it printed."""
    out = io.StringIO()
    with contextlib.redirect_stdout(out):
        result = phase(*args, **kwargs)
    lines = [ln for ln in out.getvalue().splitlines() if ln.startswith("{")]
    assert len(lines) == 1 and json.loads(lines[0]) == result
    return result


def test_main_refuses_the_cpu():
    proc = subprocess.run(
        [sys.executable, os.path.join(ROOT, "chip_smoke.py")],
        env={**os.environ, "JAX_PLATFORMS": "cpu"}, cwd=ROOT,
        capture_output=True, text=True, timeout=120)
    assert proc.returncode != 0
    assert '"ok": true' not in proc.stdout and '"phase"' not in proc.stdout
    assert "needs a TPU" in proc.stderr


# ------------------------------------------------------------------- train
@pytest.fixture(scope="module")
def train():
    return _run(chip_smoke.train_phase, TINY, full_layers=48, micro_batch=1,
                seq_len=64, steps=8, expect_kernels=False)


def test_train_loss_falls_on_the_fixed_batch(train):
    assert len(train["losses"]) == 8
    assert train["losses"][-1] < train["losses"][1]


def test_train_checkpoint_round_trip_reproduces_the_next_step(train):
    ckpt = train["checkpoint"]
    assert ckpt["loss_after_round_trip"] == ckpt["loss_without"]


def test_train_line_says_what_was_cut(train):
    assert train["phase"] == "train"
    assert train["reduced"] == {"num_layers": [48, TINY.num_layers]}
    assert train["shapes"]["hidden"] == TINY.hidden_size
    assert train["compile_seconds"] > 0 and train["seconds"] > 0


# ------------------------------------------------------------------- serve
@pytest.fixture(scope="module")
def serve():
    return _run(
        chip_smoke.serve_phase, TINY,
        RaggedConfig(max_tokens_per_step=64, max_seqs=4, block_size=8,
                     num_blocks=65, max_blocks_per_seq=16, prefill_tile=16),
        REQUESTS, sample=2, expect_kernels=False)


def test_serve_answers_every_request_over_http(serve):
    assert serve["requests"]["n"] == 8 and serve["requests"]["sse"] == 2
    assert serve["requests"]["max_in_flight"] >= 4
    assert serve["engine"]["tokens_emitted"] == sum(
        r["max_tokens"] for r in REQUESTS)


def test_serve_uses_the_tiled_prefill_path(serve):
    assert serve["config"]["prefill_tile"] == 16
    assert serve["config"]["device_state"] is True
    assert serve["engine"]["programs"]["dev_step"] >= 1


def test_serve_agrees_with_the_plain_forward(serve):
    numerics = serve["numerics"]
    assert numerics["greedy_match_rate"] >= chip_smoke.MATCH_RATE_MIN
    assert numerics["max_logit_gap"] <= numerics["gap_limit"]


def test_serve_check_fails_on_a_wrong_token():
    """The numerics check is not vacuous: one served token moved to another
    id is caught (float32 here, so noise is ~0 and any move is a gap)."""
    import jax

    params = gpt2.init_params(TINY, jax.random.PRNGKey(0))
    prompt = REQUESTS[0]["prompt"]
    ids = jax.numpy.asarray([prompt])
    good = int(gpt2.forward(TINY, params, ids, attn_impl="xla")[0, -1].argmax())
    assert chip_smoke._reference_check(
        TINY, params, [(prompt, [good])])["greedy_match_rate"] == 1.0
    with pytest.raises(chip_smoke.SmokeFailure, match="below the reference"):
        chip_smoke._reference_check(
            TINY, params, [(prompt, [(good + 1) % TINY.vocab_size])])


# ------------------------------------------------- rehearsals, not tier-1
@pytest.mark.slow
def test_kernels_phase_at_tiny_size():
    out = _run(chip_smoke.kernels_phase, [(4, 2, 16)], seq_len=32, tile=8,
               block=8, ssm=(10, 16, 256, 2), ssd=(6, 2, 3, 16),
               experts=(4, 32, 48, 16, 6),
               kda=(10, 16, 2, 4), chunk=(3, 16, 4),
               selscan=(10, 8, 128, 4, 3, 16),
               swa=(4, 4, 2, 16, 12, 40, 4), blocks=(8, 4, 4, 2, 16, 40, 8))
    assert set(out["max_rel_err"]) == {
        "blk_decode", "blk_decode_as_four_walks",
        "flash_fwd", "flash_bwd_dq", "flash_bwd_dk", "flash_bwd_dv",
        "paged_decode", "tiled_prefill", "ssm_decode_state", "ssm_decode_y",
        "ssd_chunk_state", "ssd_chunk_y",
        "moe_gmm_relu2", "kda_decode_state", "kda_decode_y",
        "kda_chunk_state", "kda_chunk_y", "selscan_decode_state",
        "selscan_decode_y", "selscan_tile_state", "selscan_tile_y",
        "swa_decode", "swa_prefill"}
    assert set(out["ssd_chunk_ms"]) == {"pallas", "xla"}
    assert set(out["kda_decode_ms"]) == {"pallas", "xla"}
    assert set(out["kda_chunk_ms"]) == {"pallas", "xla"}
    assert set(out["selscan_decode_ms"]) == {"pallas", "xla"}
    assert set(out["selscan_tile_ms"]) == {"pallas", "xla"}
    assert set(out["swa_decode_ms"]) == {"pallas", "xla"}
    assert set(out["swa_prefill_ms"]) == {"pallas", "xla"}
    assert set(out["blk_decode_ms"]) == {"pallas", "four_walks", "xla"}


@pytest.mark.slow
def test_sharded_phases_on_the_virtual_mesh():
    """Three engines on eight devices: the four-chip rehearsal. An odd
    vocabulary: nothing divides it, as nothing divides 50257."""
    cfg = dataclasses.replace(TINY, num_layers=4, vocab_size=257)
    out = _run(chip_smoke.sharded_compare_phase,
               dataclasses.replace(cfg, num_layers=2), full_layers=4,
               micro_batch=1, seq_len=64, steps=6,
               expect_reduce_scatter=False)
    assert out["devices"] == 8 and out["global_batch"] == 8
    assert out["reduced"] == {"num_layers": [4, 2]}
    assert out["placement"]["collective_mentions_in_step_hlo"]["all-gather"]
    out = _run(chip_smoke.sharded_full_phase, cfg, seq_len=64, steps=3)
    assert out["losses"][-1] < out["losses"][0]
