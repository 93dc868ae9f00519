"""Percentile and window arithmetic on hand-made records."""

import pytest

import reduce


def rec(i, due, sent, first, frames, end, n=3, status=200, prompt_len=10):
    return {"i": i, "due": due, "sent": sent, "first": first, "frames": frames,
            "end": end, "status": status, "max_tokens": n, "prompt_len": prompt_len,
            "tokens": list(range(n)) if status == 200 else None}


def test_percentile_interpolates_like_numpy():
    assert reduce.percentile([1, 2, 3, 4], 50) == 2.5
    assert reduce.percentile([10], 99) == 10
    assert reduce.percentile(range(101), 90) == 90
    assert reduce.percentile([1, 2, 3, 4, 5], 90) == pytest.approx(4.6)
    with pytest.raises(ValueError):
        reduce.percentile([], 50)


RECORDS = [
    rec(0, -0.5, -0.5, -0.4, [-0.4, -0.3, -0.2], -0.2),        # lead-in
    rec(1, 0.0, 0.01, 0.11, [0.11, 0.16, 0.26], 0.26),         # gaps 50, 100
    rec(2, 1.0, 1.02, 1.52, [1.52, 1.55, 1.60], 1.60),         # gaps 30, 50
    rec(3, 9.9, 9.9, 10.4, [10.4, 10.5, 10.6], 10.6),          # ends after window
    rec(4, 5.0, 5.0, None, [], None, status=0),                # never answered
    rec(5, 10.0, 10.0, 10.1, [10.1, 10.2, 10.3], 10.3),        # due after window
]


def test_open_loop_counts_by_due_time_and_failed_miss_every_tail():
    tried = reduce.attempted(RECORDS, 10.0, True)
    assert [r["i"] for r in tried] == [1, 2, 3, 4]
    assert sum(1 for r in tried if not reduce.ok(r)) == 1
    ttft = reduce.ttft_ms(tried, missing_ms=25_000.0)
    assert ttft == pytest.approx([110.0, 520.0, 500.0, 25_000.0])
    assert reduce.percentile(ttft, 90) > 520.0
    assert sorted(reduce.gaps_ms(tried)) == pytest.approx(
        [30.0, 50.0, 50.0, 100.0, 100.0, 100.0])
    assert reduce.late_ms(tried) == pytest.approx([10.0, 20.0, 0.0, 0.0])


def test_a_short_answer_is_a_failure():
    short = rec(9, 0.0, 0.0, 0.1, [0.1], 0.2, n=3)
    short["tokens"] = [1]
    assert not reduce.ok(short)


def test_closed_loop_spreads_each_request_over_its_time_in_the_system():
    # 13 tokens each: request 1 and 2 lie inside; request 0 (lead-in) lies
    # outside; request 3 (9.9 -> 10.6) has 1/7 of its time inside
    assert reduce.window_tokens(RECORDS, 10.0) == pytest.approx(13 + 13 + 13 / 7)
    straddles_start = [rec(7, -1.0, -1.0, None, [], 1.0)]
    assert reduce.window_tokens(straddles_start, 10.0) == pytest.approx(6.5)
    tried = reduce.attempted(RECORDS, 10.0, False)
    assert [r["i"] for r in tried] == [1, 2, 3, 4]
    assert reduce.request_ms(tried) == pytest.approx([250.0, 580.0, 700.0])


def test_a_request_cut_while_streaming_is_not_failed_and_keeps_its_first_token():
    cut = rec(8, 1.0, 1.0, 1.4, [1.4, 1.7], None, status=0)
    cut["cut"] = True
    assert not reduce.ok(cut) and not reduce.failed(cut)
    assert reduce.failed(RECORDS[4])
    assert reduce.ttft_ms([cut], 9e9) == pytest.approx([400.0])
    assert reduce.gaps_ms([cut]) == pytest.approx([300.0])


def test_in_flight_and_slo_share():
    assert reduce.in_flight(RECORDS, 0.2) == 1
    assert reduce.in_flight(RECORDS, 6.0) == 1      # the unanswered one
    assert reduce.in_flight(RECORDS, 10.05) == 3
    assert reduce.mean_in_flight(RECORDS, 0.2, 2.2, step=1.0) == 1.0
    tried = reduce.attempted(RECORDS, 10.0, True)
    assert reduce.slo_share(tried, 2000.0, 200.0) == 0.75
    assert reduce.slo_share(tried, 200.0, 200.0) == 0.25
    assert reduce.slo_share(tried, 2000.0, 60.0) == 0.25
