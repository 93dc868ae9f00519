"""The ``setup.*`` readers (``setup_log.py``) on start-up logs recorded on the
chip (PR 35): a warm and a cold run of the Moonlight cell and a run of the
training cell, each with the values its traced line printed; and no value,
rather than a wrong one, where the program has no such log."""

import copy as copying
import glob
import gzip
import json
import os

import pytest

import cellspec
import setup_log

HERE = os.path.dirname(os.path.abspath(__file__))
FIXTURES = os.path.join(HERE, "fixtures", "startup")
METRICS = ("setup.cache_hit_share", "setup.program_builds", "setup.trace_s",
           "setup.lower_s", "setup.compile_s", "setup.cache_retrieval_s",
           "setup.background_compile_s", "setup.engine_init_s",
           "setup.train_init_s", "setup.unattributed_s")
RECORDED = sorted(os.path.basename(p)[:-len(".json.gz")]
                  for p in glob.glob(os.path.join(FIXTURES, "*.json.gz")))


def recorded(name: str) -> tuple:
    with gzip.open(os.path.join(FIXTURES, name + ".json.gz"), "rt") as f:
        run = json.load(f)
    with open(os.path.join(FIXTURES, name + ".expect.json")) as f:
        return run, json.load(f)


def reader(metric: str):
    return cellspec.load_module(
        os.path.join(cellspec.HERE, "layer_metrics", metric + ".py"),
        "setup_reader_" + metric.replace(".", "_")).read


def context(run: dict, tmp_path, monkeypatch, startup="recorded") -> dict:
    """What ``run.result_line`` hands a reader, with the process's snapshot
    replaced by the recorded one (``startup="process"``: left alone)."""
    if startup != "process":
        snap = run["startup"] if startup == "recorded" else startup
        monkeypatch.setattr(setup_log, "read_snapshot", lambda: snap)
    return {"window": {"t_window": run["t_window"]},
            "end_to_end": {"setup_s": run["setup_s"]},
            "spec": {"name": run["cell"],
                     "base": str(tmp_path / "benchmark")}}


def test_the_recordings_are_there():
    assert len(RECORDED) == 3
    assert sum("cold" in name for name in RECORDED) == 1
    assert sum("train" in name for name in RECORDED) == 1


@pytest.mark.parametrize("metric", METRICS)
@pytest.mark.parametrize("name", RECORDED)
def test_a_reader_gives_what_the_traced_line_printed(name, metric, tmp_path,
                                                     monkeypatch):
    run, expect = recorded(name)
    got = reader(metric)(context(run, tmp_path, monkeypatch))
    if expect[metric] is None:  # not among the cell's metrics: nothing there
        assert got == 0
    else:
        assert got == pytest.approx(expect[metric], rel=1e-9, abs=1e-9)
    kept = tmp_path / ".bench_out" / (run["cell"] + ".startup.json")
    assert json.loads(kept.read_text())["startup"] == run["startup"]


@pytest.mark.parametrize("name", RECORDED)
def test_the_main_threads_seconds_split_setup_s(name, tmp_path, monkeypatch):
    """trace + lower + compile + retrieval + the phases + the rest is
    ``setup_s``: nothing is counted twice, and the rest is never negative."""
    run, expect = recorded(name)
    ctx = context(run, tmp_path, monkeypatch)
    parts = [reader(m)(ctx) for m in METRICS[2:6] + METRICS[7:]]
    assert all(p >= 0 for p in parts)
    assert sum(parts) == pytest.approx(run["setup_s"], rel=1e-9)
    # records that began in the window or after it are not set-up
    late = [b for b in run["startup"]["builds"] if b["t0"] >= run["t_window"]]
    assert late and expect["setup.unattributed_s"] > 0


@pytest.mark.parametrize("metric", METRICS)
@pytest.mark.parametrize("why", ["no-startup-key", "not-installed",
                                 "overflowed", "no-window"])
def test_no_value_rather_than_a_wrong_one(why, metric, tmp_path, monkeypatch):
    run, _ = recorded(RECORDED[0])
    if why == "overflowed":     # full before the window opened
        snap = copying.deepcopy(run["startup"])
        snap["overflowed"] = True
        snap["builds"] = [b for b in snap["builds"]
                          if b["t0"] < run["t_window"]]
    elif why == "not-installed":
        snap = None
    elif why == "no-window":    # a context the older tests make by hand
        ctx = context(run, tmp_path, monkeypatch)
        del ctx["window"]["t_window"], ctx["end_to_end"]
        assert reader(metric)(ctx) is None
        return
    else:   # the parent's telemetry, read by the real ``read_snapshot``
        from deepspeed_tpu import telemetry

        monkeypatch.setattr(telemetry, "snapshot",
                            lambda: {"ts": 0.0, "metrics": {}})
        snap = "process"
    assert reader(metric)(context(run, tmp_path, monkeypatch, snap)) is None


def test_a_log_that_overflowed_inside_the_window_still_reads(tmp_path,
                                                             monkeypatch):
    run, expect = recorded(RECORDED[0])
    snap = dict(copying.deepcopy(run["startup"]), overflowed=True)
    ctx = context(run, tmp_path, monkeypatch, startup=snap)
    assert reader("setup.trace_s")(ctx) == pytest.approx(
        expect["setup.trace_s"])


def test_the_rest_is_never_negative(tmp_path, monkeypatch):
    run, expect = recorded(RECORDED[0])
    seen = run["setup_s"] - expect["setup.unattributed_s"]
    ctx = context(dict(run, setup_s=seen / 2), tmp_path, monkeypatch)
    assert reader("setup.unattributed_s")(ctx) == 0.0


def test_cache_hit_share_leaves_out_what_no_cache_would_hold(tmp_path,
                                                             monkeypatch):
    """A build that never asked, one under jax's threshold (missed and not
    written) and a hit on an entry this process wrote count on neither
    side; with nothing left there is no value."""
    def build(program, cache, written=False, thread="MainThread"):
        return {"program": program, "thread": thread, "t0": 1.0, "t1": 2.0,
                "trace_s": 0.1, "lower_s": 0.1, "compile_s": 0.5,
                "retrieval_s": 0.0, "saved_s": 0.0, "inner_traces": 0,
                "inner_builds": 0, "cache": cache, "written": written,
                "compiled": True}

    builds = [build("jit_a", "hit"), build("jit_b", "unasked"),
              build("jit_small", "miss"),
              build("jit_c", "miss", written=True, thread="ragged-compile_0"),
              build("jit_c", "hit"), build("jit_d", "miss", written=True)]
    run = {"cell": "c", "t_window": 10.0, "setup_s": 9.0,
           "startup": {"overflowed": False, "phases": [], "builds": builds}}
    ctx = context(run, tmp_path, monkeypatch)
    assert reader("setup.cache_hit_share")(ctx) == pytest.approx(1 / 3)
    assert reader("setup.background_compile_s")(ctx) == pytest.approx(0.5)
    run["startup"]["builds"] = builds[1:3]
    ctx = context(run, tmp_path, monkeypatch)
    assert reader("setup.cache_hit_share")(ctx) is None
