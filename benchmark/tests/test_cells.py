"""The harness end to end on the CPU at tiny presets, and the requirement that
a configuration, a mix, a per-layer reader and a cell are added as new files
plus ``BENCHMARK.json`` entries, with no edit to a file that is there."""

import json
import os

import pytest

import cellspec
import run as runner
from conftest import (TINY_CHAT, TINY_GPT2, TINY_MIXTRAL, TINY_POOL,
                      TINY_TRAIN)

READER = '''"""A throw-away reader: what the window's own counters say."""


def read(ctx):
    c = ctx["window"]["counters"]
    return c.get("tokens_emitted", c.get("compiles"))
'''

CASES = {
    "chat": dict(config=("tiny-gpt2", TINY_GPT2), mix=("tiny-chat", TINY_CHAT),
                 cell={"rate": 4.0}, chips=1,
                 e2e=["itl_trim5_ms"]),
    "pool": dict(config=("tiny-mixtral", TINY_MIXTRAL),
                 mix=("tiny-pool", TINY_POOL), cell={"clients": 3}, chips=1,
                 e2e=["serve_tokens_per_s"]),
    "train": dict(config=("tiny-gpt2", TINY_GPT2),
                  mix=("tiny-train", TINY_TRAIN), cell={}, chips=4,
                  e2e=["train_tokens_per_s"]),
}


def add_cell(copy, case) -> str:
    """What a later PR does: new files and new entries; the one change to an
    entry that is there is its cell's name joining a metric's ``workloads``."""
    cname, config = case["config"]
    mname, mix = case["mix"]
    root = copy({
        f"benchmark/configs/{cname}.json": config,
        f"benchmark/traffic/{mname}.json": mix,
        "benchmark/cells/tiny.cell.json": case["cell"],
        "benchmark/layer_metrics/tiny.counter.py": READER,
    }, configs=[{"name": cname, "source": "test", "reduced": [],
                 "file": f"benchmark/configs/{cname}.json", "why": "tiny"}],
       workloads=[{"name": "tiny.cell", "config": cname, "traffic": mname,
                   "chips": case["chips"], "why": "the harness on the CPU"}],
       per_layer=[{"name": "tiny.counter", "unit": "count", "better": "higher",
                   "source": "program_counter", "layer": "ragged scheduler",
                   "moves": "setup_s", "workloads": ["tiny.cell"]}])
    path = os.path.join(root, "BENCHMARK.json")
    with open(path) as f:
        bench = json.load(f)
    for m in bench["end_to_end"] + bench["per_layer"]:
        if m["name"] in case["e2e"] or m.get("moves") in case["e2e"]:
            m["workloads"].append("tiny.cell")
    with open(path, "w") as f:
        json.dump(bench, f)
    return root


@pytest.mark.parametrize("kind", sorted(CASES))
def test_a_cell_added_as_files_only_runs_end_to_end(copy, kind, tmp_path):
    case = CASES[kind]
    spec = cellspec.resolve("tiny.cell", root=add_cell(copy, case))
    assert spec["chips"] == case["chips"]
    assert sorted(m["name"] for m in spec["end_to_end"]) == sorted(
        case["e2e"] + ["setup_s"])
    raw = runner.run_cell(spec, seed=3, seconds=3.0, trace=False,
                          out_dir=str(tmp_path / "out"))
    assert raw["correct"] is True
    assert raw["attempted"] > 0 and raw["failed"] == 0
    assert sorted(raw["metrics"]) == sorted(case["e2e"])
    assert all(v > 0 for v in raw["metrics"].values())
    counters = raw["window"]["counters"]
    assert counters["compiles"] == 0  # nothing compiled inside the window
    if kind != "train":
        assert counters["program_cold_dispatches"] == 0
        assert counters["dispatch_count"] > 0

    device = {"platform": "cpu", "kind": "cpu", "count": case["chips"]}
    peaks = {"bf16_flops_per_s": 1.0, "hbm_bytes_per_s": 1.0}
    line = runner.result_line(spec, raw, device, trace=False, peaks=peaks)
    assert set(line) == {"correct", "attempted", "failed", "metrics", "device"}
    assert sorted(line["metrics"]) == sorted(case["e2e"] + ["setup_s"])
    assert line["metrics"]["setup_s"]["unit"] == "s"

    # the traced line, with a reduced trace put in by hand (a CPU run has no
    # device plane, and prints no device metric): the new reader is found by
    # its name and reports beside the accepted ones
    raw["window"]["trace"] = {"busy_s": 1.0, "window_s": 2.0, "top_ops": [],
                              "idle_gaps": [], "collective_exposed_s": 0.1,
                              "kernel_s": {}}
    raw["window"].setdefault("samples", [])
    traced = runner.result_line(spec, raw, device, trace=True, peaks=peaks)
    assert traced["metrics"]["tiny.counter"]["unit"] == "count"
    assert traced["device"]["busy_s"] == 1.0 and "breakdown" in traced
    assert "setup_s" not in traced["metrics"]
    want = {"chat": "sched.dispatches_per_token", "pool": "sched.pad_share",
            "train": "train.step_ms_p50"}[kind]
    assert traced["metrics"][want]["value"] > 0


def test_every_accepted_cell_resolves_and_reports_what_the_contract_asks():
    with open(os.path.join(cellspec.HERE, os.pardir, "BENCHMARK.json")) as f:
        bench = json.load(f)
    assert sum(w["chips"] == 4 for w in bench["workloads"]) <= max(
        1, len(bench["workloads"]) // 4)
    for w in bench["workloads"]:
        spec = cellspec.resolve(w["name"])
        e2e = {m["name"] for m in spec["end_to_end"]}
        assert "setup_s" in e2e and len(e2e) >= 2
        readers = cellspec.layer_readers(spec)
        assert any(entry["moves"] in e2e for entry, _ in readers.values())
        family, cfg, reference = cellspec.model(spec)
        assert reference.num_params(cfg) == family.num_params(cfg)
        kind = spec["mix"]["kind"]
        assert ("rate" in spec["cell"]) == (kind == "open_loop")
        assert ("clients" in spec["cell"]) == (kind == "closed_loop")


def test_the_published_sizes():
    gpt2 = cellspec.model(cellspec.resolve("gpt2-xl.chat-open"))
    assert gpt2[2].num_params(gpt2[1]) == 1_557_611_200
    mix = cellspec.model(cellspec.resolve("mixtral-8x7b-d3.longdoc-pool"))
    cfg = mix[1]
    assert (cfg.hidden_size, cfg.intermediate_size, cfg.num_heads,
            cfg.num_kv_heads, cfg.num_experts, cfg.top_k, cfg.num_layers) == (
                4096, 14336, 32, 8, 8, 2, 3)
    assert mix[2].num_params(cfg) * 2 == 9_231_917_056  # the rehearsal's bytes


def test_an_unlisted_device_or_workload_is_an_error():
    spec = cellspec.resolve("gpt2-xl.chat-open")
    assert cellspec.peaks_for(spec, "TPU v5 lite")["bf16_flops_per_s"] == 197e12
    with pytest.raises(SystemExit):
        cellspec.peaks_for(spec, "TPU v99")
    with pytest.raises(SystemExit):
        cellspec.resolve("no.such-cell")


def test_the_command_refuses_the_cpu_and_prints_no_result(tmp_path):
    """``run.py`` itself: no TPU -> exit code 2 and nothing on stdout, from the
    repo and from a directory that holds only BENCHMARK.json and the paths."""
    import shutil
    import subprocess
    import sys

    bare = tmp_path / "bare"
    shutil.copytree(cellspec.HERE, bare / "benchmark",
                    ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(os.path.join(cellspec.HERE, os.pardir, "BENCHMARK.json"), bare)
    env = {**os.environ, "JAX_PLATFORMS": "cpu"}
    for cwd in (os.path.join(cellspec.HERE, os.pardir), str(bare)):
        done = subprocess.run(
            [sys.executable, "benchmark/run.py", "--workload",
             "gpt2-xl.chat-open", "--seed", "1", "--seconds", "1",
             "--trace", "0"], cwd=cwd, env=env, capture_output=True,
            text=True, timeout=120)
        assert done.returncode == 2, done.stderr[-500:]
        assert done.stdout == ""
        assert "needs 1 TPU chip" in done.stderr
