"""PR 51's additions to the benchmark: the ``granite_hybrid`` family as files
only (a configuration, a cell on the existing ``chat-open`` mix, a reference,
six readers), the published sizes and the cut's arithmetic, and the new
readers' arithmetic."""

import json
import os

import pytest

import cellspec
import run as runner
from conftest import TINY_CHAT, TINY_GPT2

GRANITE = "granite-4.0-h-small-d10-ep2.chat-open"
NEW_READERS = ("sched.state_pad_row_share", "sched.slot_resets_per_s",
               "kernel.chat_ssm_decode_share",
               "kernel.chat_ssm_decode_roofline",
               "model.chat_ssm_step_roofline_kv",
               "kernel.chat_hybrid_paged_decode_roofline")
PERIOD = ["mamba"] * 5 + ["attention"] + ["mamba"] * 4

TINY_GRANITE = {
    "source": "test", "family": "granite_hybrid",
    "config_class": "GraniteHybridConfig",
    "fields": {"vocab_size": "vocab_size", "hidden_size": "hidden_size",
               "num_layers": "num_hidden_layers", "layer_types": "layer_types",
               "num_heads": "num_attention_heads",
               "num_kv_heads": "num_key_value_heads",
               "mamba_num_heads": "mamba_n_heads",
               "mamba_head_dim": "mamba_d_head", "n_groups": "mamba_n_groups",
               "ssm_state_size": "mamba_d_state",
               "intermediate_size": "intermediate_size",
               "shared_intermediate_size": "shared_intermediate_size",
               "num_experts": "num_local_experts_published",
               "experts_held": "num_local_experts", "expert_rank": "expert_rank",
               "top_k": "num_experts_per_tok",
               "max_seq_len": "max_position_embeddings"},
    "vocab_size": 256, "hidden_size": 64, "num_hidden_layers": 4,
    "layer_types": ["mamba", "attention", "mamba", "mamba"],
    "num_attention_heads": 4, "num_key_value_heads": 2, "mamba_n_heads": 8,
    "mamba_d_head": 16, "mamba_n_groups": 1, "mamba_d_state": 16,
    "intermediate_size": 24, "shared_intermediate_size": 48,
    "num_local_experts_published": 12, "num_local_experts": 6,
    "expert_rank": 1, "num_experts_per_tok": 4,
    "max_position_embeddings": 2048, "reduced": [],
    "serve": {**TINY_GPT2["serve"],
              # 4 of 12 logits flip under bf16 on a 64-wide model
              "check": {"match_rate_min": 0.5}},
}


def test_the_new_cell_resolves_on_the_mix_as_it_is():
    spec = cellspec.resolve(GRANITE)
    assert spec["chips"] == 1 and spec["traffic_name"] == "chat-open"
    assert spec["mix"] == cellspec.resolve("mixtral-8x7b-d3.chat-open")["mix"]
    cell = spec["cell"]
    assert cell["rate"] == pytest.approx(0.8 * cell["knee"])
    assert len(cell["ladder"]) >= 6 and cell["knee_how"]
    assert len(cell["at_the_fixed_rate"]["itl_trim5_ms"]) >= 7
    engine = {**spec["config"]["serve"]["engine"], **cell["engine"]}
    assert engine == spec["config"]["serve"]["engine"] == {
        "block_size": 128, "num_blocks": 513, "max_seqs": 64,
        "max_tokens_per_step": 512, "max_blocks_per_seq": 8,
        "prefill_tile": 128}
    # every slot can hold the mix's longest request: no preemption
    assert (engine["num_blocks"] - 1 == engine["max_seqs"] * engine["max_blocks_per_seq"]
            and spec["mix"]["total_tokens_max"]
            == engine["block_size"] * engine["max_blocks_per_seq"])
    assert {m["name"] for m in spec["end_to_end"]} == {"itl_trim5_ms", "setup_s"}
    names = {m["name"] for m in spec["per_layer"]}
    assert set(NEW_READERS) <= names
    # what the other chat cells report and this one can: their host path's
    # metrics, the idle shares, the decode kernel's, the set-up's
    assert {"loadgen.late_p99_ms", "loadgen.stall_ms", "front.queue_depth_mean",
            "serve.slo_share", "serve.ttft_p50_ms", "serve.ttft_p90_ms",
            "serve.itl_p99_ms", "sched.decode_step_ms_p50",
            "sched.host_wait_share", "device.idle_share",
            "device.idle_unattributed_share", "kernel.chat_paged_decode_share",
            "model.chat_step_roofline",
            "sched.cold_dispatches", "setup.compile_s"} <= names
    # host_spans.attention_geometry multiplies one layer's K/V by num_layers:
    # ten times this model's one attention layer (a roofline share of 10x);
    # ``kernel.chat_hybrid_paged_decode_roofline`` takes the reference's
    assert not names & {"model.chat_step_roofline_kv",
                        "kernel.chat_paged_decode_roofline"}


def test_the_benchmark_has_the_cell_its_configuration_and_its_readers_once_each():
    """What BENCHMARK.json holds of PR 51, wherever in its lists: the next
    PR appends after it (that this PR only appended is its diff's to show)."""
    with open(os.path.join(os.path.dirname(cellspec.HERE), "BENCHMARK.json")) as f:
        bench = json.load(f)
    assert [w["name"] for w in bench["workloads"]].count(GRANITE) == 1
    assert [c["name"] for c in bench["configs"]].count(
        "granite-4.0-h-small-d10-ep2") == 1
    metrics = [m["name"] for m in bench["per_layer"]]
    for m in bench["per_layer"]:
        if m["name"] in NEW_READERS:
            assert metrics.count(m["name"]) == 1
            assert m["workloads"] == [GRANITE] and m["moves"] == "itl_trim5_ms"
    assert set(NEW_READERS) <= set(metrics)
    listed = {m["name"] for m in bench["end_to_end"] + bench["per_layer"]
              if GRANITE in m.get("workloads", [])}
    assert {"itl_trim5_ms", "kernel.chat_paged_decode_share",
            "device.idle_share", "setup.compile_s"} <= listed


def test_the_configuration_is_the_catalog_entry_but_for_its_cut():
    conf = cellspec.resolve(GRANITE)["config"]
    published = {
        "attention_bias": False, "attention_multiplier": 0.0078125,
        "embedding_multiplier": 12, "hidden_act": "silu", "hidden_size": 4096,
        "intermediate_size": 768, "layer_types": PERIOD * 4,
        "logits_scaling": 16, "mamba_chunk_size": 256, "mamba_conv_bias": True,
        "mamba_d_conv": 4, "mamba_d_head": 64, "mamba_d_state": 128,
        "mamba_expand": 2, "mamba_n_groups": 1, "mamba_n_heads": 128,
        "mamba_proj_bias": False, "max_position_embeddings": 131072,
        "model_type": "granitemoehybrid", "normalization_function": "rmsnorm",
        "num_attention_heads": 32, "num_experts_per_tok": 10,
        "num_hidden_layers": 40, "num_key_value_heads": 8,
        "num_local_experts": 72, "position_embedding_type": "nope",
        "residual_multiplier": 0.22, "rms_norm_eps": 1e-05,
        "rope_scaling": None, "rope_theta": 10000,
        "shared_intermediate_size": 1536, "tie_word_embeddings": True,
        "vocab_size": 100352}
    differs = {k for k, v in published.items() if conf.get(k, "absent") != v}
    assert differs == set(conf["reduced"]) == {
        "num_hidden_layers", "layer_types", "num_local_experts", "vocab_size"}
    assert (conf["num_hidden_layers"], conf["layer_types"],
            conf["num_local_experts"], conf["vocab_size"]) == (
                10, PERIOD, 36, 50176)
    # the cut is the published layers 0-9, one whole period, and the held
    # share and the published counts are both in the file
    assert published["layer_types"][:10] == PERIOD
    assert (conf["num_local_experts_published"], conf["expert_rank"],
            conf["expert_ranks"], conf["vocab_size_published"],
            conf["num_hidden_layers_published"]) == (72, 0, 2, 100352, 40)
    # no width is cut: d_inner = expand x hidden = heads x head size
    assert conf["mamba_expand"] * conf["hidden_size"] == \
        conf["mamba_n_heads"] * conf["mamba_d_head"]
    assert set(conf["assumed"]) >= {"expert_width", "state_dtype", "weights",
                                    "stream_growth", "mamba_chunk_size",
                                    "time_step_limit"}
    check = conf["serve"]["check"]
    assert check["match_rate_min"] == 0.5 and "float8_e5m2" in check["why"]


def test_the_sizes_of_the_cut():
    import jax
    import numpy as np

    family, cfg, reference = cellspec.model(cellspec.resolve(GRANITE))
    assert (cfg.num_layers, cfg.num_experts, cfg.held, cfg.top_k, cfg.d_inner,
            cfg.conv_width, cfg.n_groups, cfg.held_share, cfg.head_dim) == (
                10, 72, 36, 10, 8192, 8448, 1, (0, 72), 128)
    assert cfg.runs == [("mamba", 5), ("attention", 1), ("mamba", 4)]
    assert (cfg.embedding_multiplier, cfg.attention_multiplier,
            cfg.residual_multiplier, cfg.logits_scaling) == (12, 0.0078125,
                                                             0.22, 16)
    assert reference.num_params(cfg) == family.num_params(cfg) == 4_757_211_776
    layer = reference._layer_params
    assert layer(cfg, "mamba", 0) == 121_464_448          # ISSUE: 121.46 M
    assert layer(cfg, "attention", 0) == 61_120_512       # 61.12 M
    assert layer(cfg, "mamba", 36) - layer(cfg, "mamba", 0) == 36 * 9_437_184
    # the table is read once, as the head
    assert reference.weight_bytes(cfg) == 2 * 4_757_211_776
    # a token needs 10 x 36 / 72 = 5 of the held experts a layer
    assert reference.active_params(cfg) == pytest.approx(
        4_757_211_776 - 10 * (36 - 5) * 9_437_184)
    assert reference.kv_bytes_per_token(cfg) == 4096      # ONE attention layer
    assert reference.attn_flops_per_pair(cfg) == 4 * 32 * 128
    # a slot: 9 x (128 x 8192 float32 + 3 x 8448 bf16)
    assert reference.state_bytes_per_slot(cfg) == 9 * (4_194_304 + 50_688) \
        == 38_204_928
    assert reference.ssm_flops_per_token(cfg) == 9 * 5 * 128 * 8192
    tree = jax.eval_shape(lambda: family.init_params(cfg, jax.random.PRNGKey(0)))
    assert sum(int(np.prod(a.shape))
               for a in jax.tree_util.tree_leaves(tree)) == 4_757_211_776
    cache = jax.eval_shape(lambda: family.init_paged_cache(
        cfg, 513, 128, jax.numpy.bfloat16, num_slots=65))
    assert cache["k"].shape == (1, 513, 128, 1024)
    assert cache["slots"]["ssm"].shape == (9, 65, 128, 8192)
    # 8,448 channels are 66 lane tiles: no whole bfloat16 tile folds them
    # (``paged.init_window_leaf``), so the carried rows stay rows
    assert cache["slots"]["conv"].shape == (9, 65, 3, 8448)


def test_the_tiny_family_runs_through_the_harness_end_to_end(copy, tmp_path):
    """The rehearsal of the chip run: a tiny ``granite_hybrid`` (rank 1 of 2)
    added as files only, every step program warmed, an open loop over HTTP,
    the served tokens against ``reference/granite_hybrid.py``."""
    import jax
    import numpy as np

    root = copy({
        "benchmark/configs/tiny-granite.json": TINY_GRANITE,
        "benchmark/traffic/tiny-chat.json": TINY_CHAT,
        "benchmark/cells/tiny.cell.json": {"rate": 4.0},
    }, configs=[{"name": "tiny-granite", "source": "test", "reduced": [],
                 "file": "benchmark/configs/tiny-granite.json", "why": "tiny"}],
       workloads=[{"name": "tiny.cell", "config": "tiny-granite",
                   "traffic": "tiny-chat", "chips": 1, "why": "on the CPU"}])
    path = os.path.join(root, "BENCHMARK.json")
    with open(path) as f:
        bench = json.load(f)
    for m in bench["end_to_end"] + bench["per_layer"]:
        if GRANITE in m.get("workloads", []):
            m["workloads"].append("tiny.cell")
    with open(path, "w") as f:
        json.dump(bench, f)
    spec = cellspec.resolve("tiny.cell", root=root)
    family, cfg, reference = cellspec.model(spec)
    tree = family.init_params(cfg, jax.random.PRNGKey(0))
    assert reference.num_params(cfg) == sum(
        int(np.prod(a.shape)) for a in jax.tree_util.tree_leaves(tree))
    reference.Q_BLOCK = 64  # serve_cell pads to multiples of 1024; any divisor
    raw = runner.run_cell(spec, seed=2**31 + 11, seconds=3.0, trace=False,
                          out_dir=str(tmp_path / "out"))
    assert raw["correct"] is True
    assert raw["attempted"] > 0 and raw["failed"] == 0
    assert raw["metrics"]["itl_trim5_ms"] > 0
    counters = raw["window"]["counters"]
    assert counters["compiles"] == 0 and counters["program_cold_dispatches"] == 0
    # an untraced window: every new reader says nothing and does not raise
    raw["window"]["trace"] = {"busy_s": 1.0, "window_s": 2.0, "top_ops": [],
                              "idle_gaps": [], "collective_exposed_s": 0.0,
                              "kernel_s": {}}
    raw["window"].setdefault("samples", [])
    line = runner.result_line(
        spec, raw, {"platform": "cpu", "kind": "cpu", "count": 1}, trace=True,
        peaks={"bf16_flops_per_s": 1.0, "hbm_bytes_per_s": 1.0})
    assert not [k for k in line["metrics"] if k in NEW_READERS]


# ------------------------------------------------- the readers' arithmetic
def _ctx(cell: str, tl: dict) -> dict:
    spec = cellspec.resolve(cell)
    _, cfg, reference = cellspec.model(spec)
    busy = sum(b - a for a, b in tl["busy"]) * 1e-9
    window = {"host_spans": tl, "seconds": 51.0, "counters": {},
              "trace": {"busy_s": busy, "window_s": busy,
                        "kernel_s": {k: sum(d for _, d in v) * 1e-9
                                     for k, v in tl["kernels"].items()}}}
    return {"window": window, "spec": spec, "chips": 1,
            "peaks": cellspec.peaks_for(spec, "TPU v5 lite"),
            "end_to_end": {}, "cfg": cfg, "reference": reference}


SLOT = 2 * 38_204_928    # a slot's state, read and written


def _synthetic(state: bool = True) -> dict:
    """Two dispatches and their executions: a decode step of 24 live rows in
    the bucket of 64 over 6,000 context tokens (16 ms, ``ssm_decode`` 4.5 ms
    of it in 9 calls, ``paged_decode`` 0.1 ms), a mixed step of 20 rows and 2
    tiles of two arrivals (30 ms; ``ssm_decode`` 4.5 ms)."""
    ms = 1e6
    steps = [("ragged_step_d64_t0", 0.0, 16 * ms,
              {"tokens": 24, "pad": 40, "kv_tokens": 6000,
               "attn_pairs": 6000, "dec_kv_tokens": 6000,
               "state_bytes": 24 * SLOT, "dec_state_bytes": 24 * SLOT,
               "ssm_prefill_tokens": 0, "chunk_tiles": 0,
               "state_pad_rows": 40, "slot_resets": 0}),
             ("ragged_step_d64_t2", 20 * ms, 30 * ms,
              {"tokens": 250, "pad": 70, "kv_tokens": 5000 + 230,
               "attn_pairs": 5000 + 15_000, "dec_kv_tokens": 5000,
               "state_bytes": 22 * SLOT, "dec_state_bytes": 20 * SLOT,
               "ssm_prefill_tokens": 230, "chunk_tiles": 2,
               "state_pad_rows": 44, "slot_resets": 2})]
    if not state:
        steps = [(n, s, d, {k: v for k, v in a.items()
                            if "state" not in k and "ssm" not in k
                            and k not in ("slot_resets", "chunk_tiles")})
                 for n, s, d, a in steps]
    host = [["engine/dispatch", start + 0.1 * ms, 0.2 * ms,
             {"program": name, **args}] for name, start, _, args in steps]
    return {
        "host": [{"thread": "engine", "events": host}],
        "modules": [[f"jit_{n}(1)", s + 0.5 * ms, d] for n, s, d, _ in steps],
        "busy": [[s + 0.5 * ms, s + 0.5 * ms + d] for _, s, d, _ in steps],
        "kernels": {
            "ssm_decode": [[1 * ms + i * ms, 0.5 * ms] for i in range(9)]
            + [[21 * ms + i * ms, 0.5 * ms] for i in range(9)],
            "paged_decode": [[12 * ms, 0.1 * ms], [32 * ms, 0.1 * ms]]}}


def test_the_new_readers_count_padding_resets_and_the_state():
    readers = cellspec.layer_readers(cellspec.resolve(GRANITE))
    ctx = _ctx(GRANITE, _synthetic())

    def read(name):
        return readers[name][1](ctx)

    # 84 of the 128 decode rows the two programs executed were padding
    assert read("sched.state_pad_row_share") == pytest.approx(100 * 84 / 128)
    # two slots zeroed in a slice of 50 ms (first start to last end)
    assert read("sched.slot_resets_per_s") == pytest.approx(2 / 50e-3)
    # 44 real decode rows' states once each way against 9 ms in the kernel
    assert read("kernel.chat_ssm_decode_roofline") == pytest.approx(
        100 * (44 * SLOT / 819e9) / 9e-3, rel=1e-9)
    assert read("kernel.chat_ssm_decode_share") == pytest.approx(100 * 9 / 46)
    # 11,000 context tokens of ONE attention layer's K and V (4,096 B a
    # token) against 0.2 ms in ``paged_decode``
    assert read("kernel.chat_hybrid_paged_decode_roofline") == pytest.approx(
        100 * (4096 * 11_000 / 819e9) / 0.2e-3, rel=1e-9)
    ref, cfg = ctx["reference"], ctx["cfg"]
    bytes_s = (2 * ref.weight_bytes(cfg) + 4096 * (6000 + 5230)
               + 46 * SLOT) / 819e9
    flops_s = ((2.0 * ref.active_params(cfg) + ref.ssm_flops_per_token(cfg)) * 274
               + 16384 * (6000 + 20_000)) / 197e12
    assert bytes_s > flops_s
    assert read("model.chat_ssm_step_roofline_kv") == pytest.approx(
        100 * bytes_s / 46e-3, rel=1e-9)
    assert read("sched.decode_step_ms_p50") == pytest.approx(16.0)
    for name in NEW_READERS:
        if "roofline" in name or name.endswith("_share"):
            assert 0.0 <= read(name) <= 100.0, name


@pytest.mark.parametrize("bare", ["no_spans", "no_state_arguments"])
def test_a_program_without_spans_or_state_reads_nothing(bare):
    """The parent of PR 51 (its spans lack the two arguments), or any family
    without slot state: None, no error."""
    if bare == "no_spans":
        tl = dict(_synthetic(), host=[], kernels={
            "ssm_decode": [], "paged_decode": []})
    else:   # spans of a family whose dispatches carry no state arguments
        tl = dict(_synthetic(state=False))
        tl["kernels"] = {**tl["kernels"], "ssm_decode": []}
    readers = cellspec.layer_readers(cellspec.resolve(GRANITE))
    ctx = _ctx(GRANITE, tl)
    # the pool kernel's reader reads ``dec_kv_tokens``, which every program's
    # dispatch spans have carried since PR 18: silent only without spans
    for name in NEW_READERS[:5 if bare == "no_state_arguments" else 6]:
        assert readers[name][1](ctx) is None, name
