"""What keeps a chat cell's number steady: a trimmed mean in place of a tail,
and a window that is measured again when the machine stood still."""

import json
import os
import signal
import socket
import subprocess
import sys
import time

import pytest

import reduce
import serve_cell
from conftest import BENCH, TINY_CHAT


def test_trimmed_mean_ignores_a_few_huge_values_and_moves_smoothly():
    gaps = [40.0] * 60 + [50.0] * 38
    assert reduce.trimmed_mean(gaps + [11_500.0] * 2, 5) == pytest.approx(
        reduce.trimmed_mean(gaps + [50.0] * 2, 5))
    # 2% of the gaps move from one cluster to the other: the trimmed mean
    # moves by 0.2 of 44, the percentile between the clusters by 10 of 40
    before = [40.0] * 51 + [50.0] * 49
    after = [40.0] * 49 + [50.0] * 51
    assert reduce.trimmed_mean(after, 5) - reduce.trimmed_mean(before, 5) == pytest.approx(0.2, abs=0.03)
    assert reduce.percentile(after, 50) - reduce.percentile(before, 50) == pytest.approx(10.0)
    assert reduce.trimmed_mean([1.0, 2.0, 3.0], 5) == pytest.approx(2.0)
    with pytest.raises(ValueError):
        reduce.trimmed_mean([], 5)


@pytest.mark.parametrize("how,want", [("p50", 50.5), ("trim5", 50.5),
                                      ("p99", 99.01), ("trim25", 50.5)])
def test_stat_reads_the_statistic_from_its_name(how, want):
    assert reduce.stat([float(i) for i in range(1, 101)], how) == pytest.approx(want)


def test_latency_metric_is_named_not_coded():
    recs = [{"due": 0.0, "first": 0.1, "frames": [0.1, 0.2, 0.35],
             "status": 200, "tokens": [1, 2, 3], "max_tokens": 3}]
    assert reduce.latency_metric("itl_trim5_ms", recs, 9e9) == pytest.approx(125.0)
    assert reduce.latency_metric("itl_p99_ms", recs, 9e9) == pytest.approx(149.5)
    assert reduce.latency_metric("ttft_p50_ms", recs, 9e9) == pytest.approx(100.0)
    assert reduce.latency_metric("serve_tokens_per_s", recs, 9e9) is None
    assert reduce.latency_metric("itl_trim5_ms", [], 9e9) is None
    assert reduce.latency_metric("itl_worst_ms", recs, 9e9) is None
    with pytest.raises(ValueError):
        reduce.latency_metric("itl_worst9_ms", recs, 9e9)


class FakeRig:
    """``measured_window`` needs ``window``, ``idle``, ``reduce_trace``."""

    def __init__(self, stalls_by_window):
        self.spec = {"mix": {"lead_seconds": 10}}
        self.stalls_by_window = list(stalls_by_window)
        self.tags, self.idled = [], 0

    def window(self, seconds, trace, tag="window"):
        self.tags.append(tag)
        return {"stalls": self.stalls_by_window[len(self.tags) - 1],
                "t_window": 100.0 * len(self.tags), "seconds": seconds,
                "trace_dir": None, "trace": None, "tag": tag}

    def idle(self):
        self.idled += 1

    def reduce_trace(self, win):
        win["trace"] = "reduced"


CASES = {
    "quiet": ([[]], ["window"]),
    "short stall": ([[{"at": 3.0, "seconds": 0.3}]], ["window"]),
    "before the lead-in": ([[{"at": -14.0, "seconds": 2.0}]], ["window"]),
    "after the window": ([[{"at": 51.5, "seconds": 9.0}]], ["window"]),
    "frozen once": ([[{"at": 31.5, "seconds": 11.5}], []], ["window", "window2"]),
    "into the lead-in": ([[{"at": -11.0, "seconds": 2.0}], []], ["window", "window2"]),
    "frozen twice": ([[{"at": 1.0, "seconds": 0.6}], [{"at": 2.0, "seconds": 0.7}]],
                     ["window", "window2"]),
}


@pytest.mark.parametrize("case", sorted(CASES))
def test_a_window_in_which_the_machine_stood_still_is_measured_again_once(case, capsys):
    stalls, tags = CASES[case]
    rig = FakeRig(stalls)
    win = serve_cell.measured_window(rig, 51.0, False)
    assert rig.tags == tags and rig.idled == len(tags) - 1
    assert win["tag"] == tags[-1] and win["trace"] == "reduced"
    assert win["t_window"] == 100.0  # set-up ends where the first window starts
    said = [json.loads(line)["phase"] for line in capsys.readouterr().out.splitlines()]
    assert said == {"frozen once": ["void"], "into the lead-in": ["void"],
                    "frozen twice": ["void", "frozen"]}.get(case, [])


def test_the_generator_notes_when_its_own_process_stood_still(tmp_path):
    """The real child, stopped by a signal for 0.8 s as a paused machine
    would stop it; its one request finds no server, which is a record too."""
    with socket.socket() as s:  # a port nobody listens on
        s.bind(("127.0.0.1", 0))
        port = s.getsockname()[1]
    spec = {"host": "127.0.0.1", "port": port, "mix": TINY_CHAT, "seed": 1,
            "vocab": 256, "mode": "open", "rate": 1.0, "stream": 0,
            "seconds": 2.5, "t0": time.monotonic() + 1.5, "sample_hz": 0}
    spec_path, out_path = tmp_path / "spec.json", tmp_path / "out.json"
    spec_path.write_text(json.dumps(spec))
    proc = subprocess.Popen([sys.executable, os.path.join(BENCH, "loadgen.py"),
                             str(spec_path), str(out_path)])
    try:
        time.sleep(max(0.0, spec["t0"] + 0.5 - time.monotonic()))
        proc.send_signal(signal.SIGSTOP)
        time.sleep(0.8)
        proc.send_signal(signal.SIGCONT)
        assert proc.wait(timeout=60) == 0
    finally:
        proc.kill()
        proc.wait()
    got = json.loads(out_path.read_text())
    held = serve_cell.frozen({"stalls": got["stalls"], "seconds": 2.5}, TINY_CHAT)
    assert len(held) == 1 and 0.6 < held[0]["seconds"] < 1.5
    assert 0.3 < held[0]["at"] < 0.8
    assert all(r["status"] == -1 for r in got["records"])
