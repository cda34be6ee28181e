"""Tail arithmetic over a window's requests: every request issued counts,
a missed or failed one is infinitely late, and rates span the drain."""

import math

import pytest

from benchmark.corpus import Variant
from benchmark.counters import MissingCounter
from benchmark.harness import Run
from benchmark.loadgen import Request
from benchmark.metrics import load_reader
from benchmark.reference import compare, correct
from benchmark.stats import percentile

V = Variant("killable", 0, b"\x33\xff", (("106", 1),), ("106",))


def req(rid, t0, first=None, done=None, issues=(("106", 1),), error=None):
    r = Request(rid, V, due=t0, start=t0)
    r.first_planted, r.done, r.error = first, done, error
    r.issues = list(issues) if done is not None else []
    return r


def run_of(reqs, t0=0.0):
    return Run(reqs, t0, max(r.done or 0 for r in reqs), 10.0, 5.0, {})


def test_nearest_rank_percentile():
    xs = list(range(1, 11))
    assert percentile(xs, 50) == 5
    assert percentile(xs, 90) == 9
    assert percentile([3.0], 90) == 3.0
    assert percentile([], 50) is None


def test_a_missed_request_in_the_tail_leaves_no_number():
    assert percentile([1.0, 2.0, math.inf], 90) is None
    assert percentile([1.0, 2.0, math.inf], 50) == 2.0


def test_ttfe_and_report_count_from_the_due_time():
    reqs = [req(i, t0=float(i), first=i + 1.0, done=i + 4.0) for i in range(10)]
    run = run_of(reqs)
    assert load_reader("ttfe_p50_s")(run) == 1.0
    assert load_reader("report_p90_s")(run) == 4.0


def test_requests_issued_near_the_window_end_are_followed_to_their_end():
    reqs = [req(i, t0=float(i), first=i + 1.0, done=i + 2.0) for i in range(9)]
    reqs.append(req(9, t0=9.9, first=30.0, done=40.0))  # issued just before close
    run = run_of(reqs)
    assert load_reader("ttfe_p90_s")(run) == 1.0
    assert load_reader("report_p90_s")(run) == 2.0
    assert math.isclose(load_reader("contracts_per_min")(run), 60.0 * 10 / 40.0)


def test_failed_and_missed_requests_count_as_missing():
    reqs = [req(i, t0=float(i), first=i + 1.0, done=i + 2.0) for i in range(8)]
    reqs.append(req(8, t0=8.0, error="ConnectionError"))
    reqs.append(req(9, t0=9.0, done=11.0, issues=()))  # ended without its issue
    run = run_of(reqs)
    assert load_reader("ttfe_p90_s")(run) is None
    assert load_reader("ttfe_p50_s")(run) == 1.0
    assert math.isclose(load_reader("contracts_per_min")(run), 60.0 * 8 / 9.0)
    numbers = compare(reqs)
    assert numbers["failed_requests"] == (1, 0)
    assert numbers["missed_issues"] == (2, 0)
    assert not correct(numbers, reqs)


def test_an_unexpected_issue_fails_and_a_possible_one_does_not():
    v = Variant("token", 0, b"\x02\x01", (("101", 0),), ("101",), (("101", 1),))
    ok = Request(0, v, start=0.0, done=1.0, issues=[("101", 0), ("101", 1)])
    bad = Request(1, v, start=0.0, done=1.0, issues=[("101", 0), ("106", 1)])
    assert correct(compare([ok]), [ok])
    assert compare([bad])["unexpected_issues"] == (1, 0)


def test_service_wait_and_dedup_readers():
    run = Run([], 0.0, 1.0, 1.0, 1.0, {
        "service.queue_wait_s": 0.5, "service.batch_wait_s": 1.5,
        "service.queue_wait_s#count": 4, "service.requests": 10,
        "service.dedup_hits": 3, "service.replay_hits": 1}, served=True)
    assert load_reader("service.wait_ms")(run) == 500.0
    assert load_reader("service.dedup_pct")(run) == 40.0
    assert run.analysed == 6
    assert load_reader("service.wait_ms")(
        Run([], 0, 1, 1, 1, {"service.queue_wait_s#count": 0})) is None
    with pytest.raises(MissingCounter):
        load_reader("service.dedup_pct")(Run([], 0, 1, 1, 1, {}, served=True))
