"""The generator: same seed -> byte-identical schedule; another seed -> another;
fixed work per window whatever the seed."""

import json
import os

import trafficgen
from conftest import BENCH


def _mix(name):
    with open(os.path.join(BENCH, "traffic", name + ".json")) as f:
        return json.load(f)


def _dump(mix, seed, vocab=50257):
    sched = trafficgen.open_schedule(mix, 3.0, 0, 30.0)
    for r in sched:
        r["ids"] = trafficgen.prompt_tokens(seed, r["stream_id"], r["i"],
                                            r["prompt_len"], vocab)
    return json.dumps(sched).encode()


def test_same_seed_same_bytes_other_seed_other():
    mix = _mix("chat-open")
    assert _dump(mix, 7) == _dump(mix, 7)
    assert _dump(mix, 7) != _dump(mix, 8)


def test_open_schedule_fixes_the_window_count_and_respects_the_clips():
    mix = _mix("chat-open")
    sched = trafficgen.open_schedule(mix, 3.0, 0, 30.0)
    inside = [r for r in sched if 0.0 <= r["due"] < 30.0]
    lead = [r for r in sched if r["due"] < 0.0]
    assert len(inside) == 90 and len(lead) == round(3.0 * mix["lead_seconds"])
    assert [r["due"] for r in sched] == sorted(r["due"] for r in sched)
    for r in sched:
        assert 32 <= r["prompt_len"] <= 640 and 1 <= r["max_tokens"] <= 384
        assert r["prompt_len"] + r["max_tokens"] <= mix["total_tokens_max"]


def test_stratified_lengths_carry_nearly_the_same_work_for_every_seed():
    mix = _mix("longdoc-pool")
    totals = [sum(trafficgen.request(mix, seed, 0, i)["prompt_len"]
                  for i in range(8 * trafficgen.STRATUM)) for seed in range(6)]
    assert max(totals) / min(totals) < 1.06
    lens = [trafficgen.request(mix, 0, 0, i)["prompt_len"] for i in range(64)]
    assert min(lens) == 2048 and max(lens) <= 8064 and len(set(lens)) > 20


def test_streams_of_one_seed_differ():
    mix = _mix("chat-open")
    a = [trafficgen.request(mix, 1, 0, i) for i in range(8)]
    b = [trafficgen.request(mix, 1, "warm", i) for i in range(8)]
    assert [r["prompt_len"] for r in a] != [r["prompt_len"] for r in b]
    assert (trafficgen.prompt_tokens(1, 0, 0, 16, 100)
            != trafficgen.prompt_tokens(1, "warm", 0, 16, 100))


def test_an_open_loop_s_schedule_is_the_mix_s_and_its_token_ids_the_seed_s():
    mix = _mix("chat-open")
    sched = trafficgen.open_schedule(mix, 3.0, 0, 30.0)
    other = dict(mix, arrivals=dict(mix["arrivals"], pattern_seed=99))
    moved = trafficgen.open_schedule(other, 3.0, 0, 30.0)
    assert [r["due"] for r in sched] != [r["due"] for r in moved]
    assert [r["prompt_len"] for r in sched] != [r["prompt_len"] for r in moved]
    r = sched[0]
    ids = [trafficgen.prompt_tokens(seed, r["stream_id"], r["i"],
                                    r["prompt_len"], 50257) for seed in (1, 2)]
    assert ids[0] != ids[1] and len(ids[0]) == len(ids[1]) == r["prompt_len"]
