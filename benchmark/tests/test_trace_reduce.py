"""The trace reduction: interval math on a hand-made trace, then the fixture
cut from a trace recorded on the chip (PR 22)."""

import gzip
import json
import os

import pytest

import trace_reduce as tr

FIXTURES = os.path.join(os.path.dirname(os.path.abspath(__file__)), "fixtures")


def test_union_and_intersection():
    assert tr.union([(3, 4), (0, 1), (0.5, 2)]) == [(0, 2), (3, 4)]
    assert tr.union_len([(0, 1), (1, 2), (5, 6)]) == 3
    assert tr.intersect_len((0.5, 3.5), [(0, 2), (3, 4)]) == 2.0


def test_family_and_collectives():
    assert tr.family("%all-gather-start.3") == "all-gather-start"
    assert tr.family("fusion.123") == "fusion"
    assert tr.is_collective("all-reduce-scatter.2")
    assert tr.is_collective("collective-permute-done.7")
    assert not tr.is_collective("fusion.9")


def _plane(name, ops, modules):
    ns = 1e9
    return {"name": name, "lines": [
        {"name": tr.OPS_LINE,
         "events": [[n, a * ns, (b - a) * ns, c] for n, a, b, c in ops]},
        {"name": tr.MODULES_LINE,
         "events": [[n, a * ns, (b - a) * ns, ""] for n, a, b in modules]}]}


def test_reduce_on_a_hand_made_trace():
    ops = [("fusion.1", 0.0, 1.0, ""), ("my_kernel.2", 1.0, 1.5, ""),
           ("all-gather.1", 1.5, 2.0, ""),          # exposed: nothing beside it
           ("fusion.2", 3.0, 4.0, ""),              # gap 2.0-3.0 between programs
           ("fusion.3", 4.5, 5.0, "")]              # gap 4.0-4.5 inside jit_b
    modules = [("jit_a(123)", 0.0, 2.0), ("jit_b(456)", 3.0, 5.0)]
    trace = {"planes": [_plane("/device:TPU:0", ops, modules),
                        _plane("/device:TPU:1", ops[:1], modules[:1])]}
    got = tr.reduce(trace, 10.0, {"mine": r"^my_kernel"})
    assert got["devices"] == 2
    assert got["busy_s"] == pytest.approx((3.5 + 1.0) / 2)
    assert got["collective_exposed_s"] == pytest.approx(0.25)
    assert got["kernel_s"]["mine"] == pytest.approx(0.25)
    assert got["top_ops"][0] == ["fusion", pytest.approx(2.5)]
    assert got["idle_gaps"] == [["jit_a -> jit_b", pytest.approx(1.0)],
                                ["inside jit_b", pytest.approx(0.5)]]


def test_the_window_defaults_to_the_trace_s_own_span():
    ops = [("fusion.1", 1.0, 2.0, ""), ("fusion.2", 4.0, 5.0, "")]
    trace = {"planes": [_plane("/device:TPU:0", ops, [("jit_a(1)", 1.0, 5.0)])]}
    got = tr.reduce(trace)
    assert got["window_s"] == pytest.approx(4.0)
    assert got["busy_s"] == pytest.approx(2.0)


def test_reduce_needs_a_device_plane():
    with pytest.raises(ValueError):
        tr.reduce({"planes": []}, 1.0)


@pytest.mark.parametrize("name", sorted(
    f[:-len(".json.gz")] for f in os.listdir(FIXTURES) if f.endswith(".json.gz"))
    if os.path.isdir(FIXTURES) else [])
def test_fixture_recorded_on_the_chip(name):
    with gzip.open(os.path.join(FIXTURES, name + ".json.gz"), "rt") as f:
        trace = json.load(f)
    with open(os.path.join(FIXTURES, name + ".expect.json")) as f:
        expect = json.load(f)
    kernels = {}
    kdir = os.path.join(os.path.dirname(FIXTURES), os.pardir, "kernels")
    for fn in os.listdir(kdir):
        with open(os.path.join(kdir, fn)) as f:
            kernels[fn[:-5]] = json.load(f)["trace_pattern"]
    got = tr.reduce(trace, expect["window_s"], kernels)
    assert got["devices"] == expect["devices"]
    assert got["busy_s"] == pytest.approx(expect["busy_s"], rel=1e-9)
    assert got["collective_exposed_s"] == pytest.approx(
        expect["collective_exposed_s"], rel=1e-9, abs=1e-12)
    for k, v in expect["kernel_s"].items():
        assert got["kernel_s"][k] == pytest.approx(v, rel=1e-9, abs=1e-12)
    assert 0.0 < got["busy_s"] <= expect["window_s"]
    assert [k for k, _ in got["idle_gaps"]] == [k for k, _ in expect["idle_gaps"]]
    for (k, v), (ek, ev) in zip(got["top_ops"], expect["top_ops"]):
        assert k == ek and v == pytest.approx(ev, rel=1e-9)
    # self times never count a nested operation twice
    assert sum(v for _, v in got["top_ops"]) <= got["busy_s"] * expect["devices"] + 1e-9
