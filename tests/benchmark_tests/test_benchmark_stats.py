"""Percentile, rate and schedule arithmetic of the benchmark."""

import math

import pytest

from benchmark import stats, traffic
from benchmark.kinds import sar
from benchmark.run import compare, window_numbers


@pytest.mark.parametrize(
    "values,q,want",
    [
        ([5.0], 50, 5.0),
        ([1, 2, 3, 4], 50, 2),
        ([1, 2, 3, 4, 5], 50, 3),
        (list(range(1, 101)), 95, 95),
        (list(range(1, 101)), 99, 99),
        (list(range(1, 101)), 100, 100),
        ([3, 1, 2], 0, 1),
        (list(range(1, 21)), 95, 19),
    ],
)
def test_percentile_nearest_rank(values, q, want):
    assert stats.percentile(values, q) == want


def test_percentile_of_nothing_is_an_error():
    with pytest.raises(ValueError):
        stats.percentile([], 50)


def test_percentile_keeps_a_planted_stall_in_the_tail():
    latencies = [0.010] * 94 + [1.5] * 6  # six requests sat behind a stall
    assert stats.percentile(latencies, 50) == 0.010
    assert stats.percentile(latencies, 95) == 1.5


@pytest.mark.parametrize(
    "done,t0,seconds,want",
    [
        ([0.1, 0.2, 0.3, 0.4], 0.0, 1.0, 4.0),
        ([0.1, 0.2, 5.0], 0.0, 1.0, 2.0),       # one answer after the close
        ([-0.5, 0.5], 0.0, 1.0, 1.0),           # one before the window
        ([10.0 + k / 100 for k in range(100)], 10.0, 2.0, 50.0),
    ],
)
def test_rate_is_over_the_whole_window(done, t0, seconds, want):
    assert stats.rate_per_s(done, t0, seconds) == want


def test_rate_counts_a_stall_as_lost_time():
    # 100/s for the first second, then a one-second stall: the rate is over
    # both seconds, not over the busy one
    done = [k / 100 for k in range(100)]
    assert stats.rate_per_s(done, 0.0, 2.0) == 50.0


def test_rate_needs_a_window():
    with pytest.raises(ValueError):
        stats.rate_per_s([1.0], 0.0, 0.0)


def test_iqr_share_matches_statistics_quantiles():
    values = [10.0, 10.2, 9.9, 10.1, 10.4, 9.8]
    assert stats.iqr_share(values) == pytest.approx((10.25 - 9.875) / 10.05)


@pytest.mark.parametrize("seed", [0, 7, 3_000_000_019])
def test_schedule_is_a_function_of_the_seed(seed):
    a = traffic.schedule(350.0, 4.0, seed)
    b = traffic.schedule(350.0, 4.0, seed)
    assert a == b
    assert len(a) == 1400
    assert a[0] == 0.0 and all(x < y for x, y in zip(a, a[1:]))
    assert a[-1] < 4.0


def test_schedules_of_two_seeds_hold_the_same_gaps_in_another_order():
    a = traffic.schedule(200.0, 5.0, 1)
    b = traffic.schedule(200.0, 5.0, 2)
    assert a != b
    gaps = lambda due: sorted(round(y - x, 9) for x, y in zip(due, due[1:] + [5.0]))
    assert gaps(a) == gaps(b)


def test_schedule_gaps_are_exponential():
    due = traffic.schedule(100.0, 100.0, 3)
    gaps = [y - x for x, y in zip(due, due[1:])]
    mean = sum(gaps) / len(gaps)
    var = sum((g - mean) ** 2 for g in gaps) / len(gaps)
    assert mean == pytest.approx(0.01, rel=0.02)
    assert math.sqrt(var) == pytest.approx(mean, rel=0.05)  # cv of 1


class _Plan:
    kind = sar

    def __init__(self, loop, seconds):
        self.loop, self.seconds = loop, seconds


def _rec(idx, due, sent, done, status=200, verdict=(True, False, frozenset())):
    return (idx, due, sent, done, status, verdict, "")


def test_open_loop_latency_is_from_due_time_and_keeps_warmup_out():
    t0 = 100.0
    records = [_rec(0, t0 - 1.0, t0 - 1.0, t0 - 0.99)]  # warm-up: uncounted
    # a stall: ten requests due 10 ms apart, all answered at t0 + 1.0
    records += [_rec(1 + k, t0 + k * 0.01, t0 + 0.5, t0 + 1.0) for k in range(10)]
    win = window_numbers(_Plan("open", 2.0), records, t0)
    assert win["attempted"] == 10 and win["failed"] == 0
    assert win["latency_p50_ms"] == pytest.approx(950.0)
    assert win["latency_p95_ms"] == pytest.approx(1000.0)
    assert win["client_late_p99_ms"] == pytest.approx(500.0)


def test_a_failed_request_is_in_the_tail_not_trimmed():
    t0 = 0.0
    records = [_rec(k, 0.1 * k, 0.1 * k, 0.1 * k + 0.01) for k in range(9)]
    records.append(_rec(9, 0.9, 0.9, 0.91, status=0, verdict=None))
    win = window_numbers(_Plan("open", 1.0), records, t0)
    assert win["failed"] == 1 and win["attempted"] == 10
    assert win["latency_p50_ms"] == pytest.approx(10.0)


def test_a_failed_request_counts_at_the_windows_longest():
    """A refusal that comes back at once is not a fast answer."""
    records = [_rec(k, 0.1 * k, 0.1 * k, 0.1 * k + 0.01) for k in range(8)]
    records.append(_rec(8, 0.8, 0.8, 1.3))                       # the longest: 500 ms
    records.append(_rec(9, 0.9, 0.9, 0.901, status=503, verdict=None))
    win = window_numbers(_Plan("open", 2.0), records, 0.0)
    assert win["failed"] == 1
    assert win["client_latency_max_ms"] == pytest.approx(500.0)
    assert win["latency_p95_ms"] == pytest.approx(500.0)
    assert win["latency_p50_ms"] == pytest.approx(10.0)


def test_an_answer_that_says_the_program_gave_up_is_a_failed_request():
    gave_up = (False, False, frozenset({"evaluationError: deadline exceeded"}))
    records = [_rec(0, 0.0, 0.0, 0.01), _rec(1, 0.1, 0.1, 2.6, verdict=gave_up)]
    win = window_numbers(_Plan("closed", 3.0), records, 0.0)
    assert (win["attempted"], win["failed"]) == (2, 1)
    assert win["decisions_per_s"] == pytest.approx(1 / 3.0)
    assert win["over_deadline_share"] == pytest.approx(50.0)


def test_closed_loop_counts_completions_inside_the_window():
    t0 = 10.0
    records = [_rec(0, 9.0, 9.0, 9.5)]                  # done in the warm-up
    records += [_rec(1, 9.9, 9.9, 10.1), _rec(2, 10.5, 10.5, 11.0)]
    records += [_rec(3, 11.9, 11.9, 12.3)]              # in flight at the close
    win = window_numbers(_Plan("closed", 2.0), records, t0)
    assert win["attempted"] == 3
    assert win["decisions_per_s"] == 1.0   # two answers inside two seconds


def test_compare_tells_a_given_up_answer_from_another_answer():
    allow = (True, False, frozenset({"p1"}))
    gave_up = (False, False, frozenset({"evaluationError: deadline exceeded"}))
    other = (False, True, frozenset({"p2"}))
    records = [_rec(0, 0.0, 0.0, 0.01, verdict=allow),
               _rec(1, 0.0, 0.0, 2.5, verdict=gave_up),
               _rec(2, 0.0, 0.0, 0.01, verdict=other),
               _rec(3, 0.0, 0.0, 0.01, status=0, verdict=None)]
    out = compare(records, {0: allow, 1: allow, 2: allow, 3: allow}, sar)
    assert (out["mismatched"], out["with_error"], out["unanswered"]) == (2, 1, 1)
    assert out["compared"] == 4
    # each example says how long its answer took: a given-up one took the deadline
    assert [e["ms"] for e in out["examples"]] == [2500.0, 10.0, 10.0]
