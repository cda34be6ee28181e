"""The readers of the program's per-analysis accounts, on synthetic
accounts: the window's selection, each metric's arithmetic, and the
``MissingCounter`` cases."""

import math
import types

import pytest

from benchmark import accounts
from benchmark.corpus import Variant
from benchmark.counters import MissingCounter
from benchmark.harness import Run
from benchmark.loadgen import Request
from benchmark.metrics import load_reader

S = 10 ** 9  # ns
FLAGS = Variant("flags", 0, b"", (("106", 42),), ("106",))


def _account(start, end, drain, tiers, issues):
    metrics = {"frontier.drain_s": drain}
    for tier in ("querycache", "prefilter", "host_probe", "device_probe", "devsolver", "cdcl"):
        metrics[f"solver.tier.{tier}.busy_s"] = tiers.get(tier, 0.0)
    return {"start_ns": start * S, "end_ns": end * S, "metrics": metrics,
            "compile_s": 0.0, "issues": [
                {"swc_id": swc, "pc": pc, "discovery_ns": at * S, "solver_time_s": solver_s}
                for swc, pc, at, solver_s in issues]}


# one account before the window (the warm-up), two inside it
ACCOUNTS = [
    _account(1, 9, 5.0, {"devsolver": 9.0}, [("106", 42, 2, 0.5)]),
    _account(11, 19, 3.0, {"device_probe": 1.0, "prefilter": 0.5, "cdcl": 7.0},
             [("101", 7, 12, 0.1), ("106", 42, 13, 2.0), ("106", 42, 15, 3.0)]),
    _account(21, 29, 0.0, {"devsolver": 2.5},
             [("106", 99, 22, 0.2), ("106", 42, 24, 4.0)]),
]


def _request(rid, start, done):
    return Request(rid, FLAGS, start=float(start), done=float(done))


def _run(requests):
    return Run(requests, 10.0, 30.0, 20.0, 1.0, {})


@pytest.fixture
def registry(monkeypatch):
    reg = types.SimpleNamespace(accounts=lambda since_ns=0: [
        a for a in ACCOUNTS if a["start_ns"] >= since_ns])
    monkeypatch.setattr(accounts, "_registry", lambda: reg)
    return reg


def test_the_window_holds_its_own_accounts(registry):
    run = _run([_request(0, 10.5, 19.5), _request(1, 20.5, 29.5)])
    assert accounts.window_accounts(run) == ACCOUNTS[1:]


@pytest.mark.parametrize("metric, value", [
    ("frontier.drain_s", (3.0 + 0.0) / 2),
    ("solver.device_tiers_s", (1.0 + 0.5 + 2.5) / 2),
    # the first planted (SWC, pc) found: 2.0 s and 4.0 s; nearest-rank median
    ("solver.pre_exploit_s", 2.0),
])
def test_readers_on_the_window_accounts(registry, metric, value):
    run = _run([_request(0, 10.5, 19.5), _request(1, 20.5, 29.5)])
    assert math.isclose(load_reader(metric)(run), value)


def test_a_request_without_its_planted_issue_counts_as_missing(registry, monkeypatch):
    window = ACCOUNTS[1:2] + [_account(21, 22, 0.0, {}, []), _account(23, 24, 0.0, {}, [])]
    found, missing = _request(0, 10.5, 19.5), _request(1, 20.5, 22.5)
    # values 2.0 and infinity: the nearest-rank median is the found one
    monkeypatch.setattr(registry, "accounts", lambda since_ns=0: window[:2])
    assert load_reader("solver.pre_exploit_s")(_run([found, missing])) == 2.0
    # values 2.0, infinity, infinity: the median is missing
    monkeypatch.setattr(registry, "accounts", lambda since_ns=0: window)
    run = _run([found, missing, _request(2, 22.5, 24.5)])
    assert load_reader("solver.pre_exploit_s")(run) is None


@pytest.mark.parametrize("metric", ["frontier.drain_s", "solver.pre_exploit_s",
                                    "solver.device_tiers_s"])
def test_a_count_that_does_not_match_fails_the_reader(registry, metric):
    run = _run([_request(0, 10.5, 19.5)])  # one request, two accounts
    with pytest.raises(MissingCounter, match="2 accounts"):
        load_reader(metric)(run)


@pytest.mark.parametrize("metric", ["frontier.drain_s", "solver.device_tiers_s"])
def test_an_account_without_the_counter_fails_the_reader(registry, metric, monkeypatch):
    bare = [dict(a, metrics={}) for a in ACCOUNTS]
    monkeypatch.setattr(registry, "accounts", lambda since_ns=0: [
        a for a in bare if a["start_ns"] >= since_ns])
    with pytest.raises(MissingCounter):
        load_reader(metric)(_run([_request(0, 10.5, 19.5), _request(1, 20.5, 29.5)]))


@pytest.mark.parametrize("metric", ["frontier.drain_s", "solver.pre_exploit_s",
                                    "solver.device_tiers_s"])
def test_a_program_without_accounts(monkeypatch, metric):
    """The helper raises; the readers, laid over a program that predates
    the accounts, read nothing."""
    monkeypatch.setattr(accounts, "_registry", lambda: types.SimpleNamespace())
    run = _run([_request(0, 10.5, 19.5)])
    with pytest.raises(MissingCounter, match="no per-analysis accounts"):
        accounts.window_accounts(run)
    assert load_reader(metric)(run) is None
