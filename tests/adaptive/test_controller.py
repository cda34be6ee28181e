"""Unit tests for the actuation-facing adaptive controller.

Each test gets its own :class:`AdaptiveController` with an injected
:class:`MetricsRegistry`, so nothing here touches the process-wide
singleton or the global registry; ``test_parity.py`` asserts the on/off
contract end to end."""

from typing import Optional

import pytest

from mythril_tpu.adaptive.controller import AdaptiveController
from mythril_tpu.adaptive.plan import PLATEAU_WINDOW, SteeringPlan
from mythril_tpu.observability.metrics import MetricsRegistry
from mythril_tpu.support.support_args import args

H1, H2, H3 = "a" * 64, "b" * 64, "c" * 64


class _StubLedger:
    """Minimal ExplorationLedger stand-in the controller plans from."""

    def __init__(self, bitmaps=None, pct: Optional[float] = None,
                 per_code_pct=None):
        self._bitmaps = bitmaps or {}
        self._pct = pct
        self._per_code = per_code_pct or {}

    def bitmaps(self):
        return dict(self._bitmaps)

    def coverage_pct_reachable(self, code_hash=None):
        if code_hash is not None:
            return self._per_code.get(code_hash, self._pct)
        return self._pct

    def solver_hotspots(self, top=64):
        return []


def _bitmap(n=8, jumpi=3):
    import numpy as np

    taken = np.zeros(n, bool)
    taken[jumpi] = True  # fall edge uncovered -> steering mass
    return {
        "instr": np.ones(n, bool), "edge_taken": taken,
        "edge_fall": np.zeros(n, bool), "jumpis": [jumpi], "total": n,
    }


@pytest.fixture
def ctrl(monkeypatch):
    c = AdaptiveController(registry=MetricsRegistry())
    monkeypatch.setattr(args, "adaptive", True)
    monkeypatch.setattr(args, "coverage_target", None)
    monkeypatch.setattr(
        AdaptiveController, "_ledger", lambda self: _StubLedger()
    )
    return c


def _install_plan(ctrl, weights):
    import time

    ctrl._plan = SteeringPlan(weights=weights)
    ctrl._plan_at = time.monotonic()


class TestPickSeed:
    def test_fifo_when_disabled(self, ctrl, monkeypatch):
        _install_plan(ctrl, {H1: 0.1, H2: 0.9})
        monkeypatch.setattr(args, "adaptive", False)
        assert ctrl.pick_seed([H1, H2, H2]) == 0
        assert ctrl.meta()["resteered_slots"] == 0

    def test_fifo_single_code(self, ctrl):
        _install_plan(ctrl, {H1: 1.0})
        assert ctrl.pick_seed([H1, H1, H1]) == 0

    def test_fifo_without_plan(self, ctrl):
        assert ctrl.pick_seed([H1, H2]) == 0

    def test_deficit_converges_on_weights(self, ctrl):
        """Granted shares track the plan's weights without randomness:
        a 3:1 weight split grants ~3x the slots over a long queue."""
        _install_plan(ctrl, {H1: 0.75, H2: 0.25})
        grants = {H1: 0, H2: 0}
        for _ in range(100):
            queue = [H1, H2]
            pos = ctrl.pick_seed(queue)
            grants[queue[pos]] += 1
        assert grants[H1] == pytest.approx(75, abs=2)
        assert grants[H2] == pytest.approx(25, abs=2)

    def test_resteered_counted_only_off_fifo(self, ctrl):
        _install_plan(ctrl, {H1: 0.05, H2: 0.95})
        pos = ctrl.pick_seed([H1, H2])
        assert pos == 1  # H2's deficit dominates
        assert ctrl.meta()["resteered_slots"] == 1
        # H2 now granted; next pick is FIFO-compatible -> no new count
        pos2 = ctrl.pick_seed([H2, H1])
        assert ctrl.meta()["resteered_slots"] == 1 + (1 if pos2 else 0)

    def test_deterministic(self, ctrl):
        _install_plan(ctrl, {H1: 0.4, H2: 0.35, H3: 0.25})
        seq1 = [ctrl.pick_seed([H1, H2, H3]) for _ in range(30)]
        ctrl.reset_scope()
        _install_plan(ctrl, {H1: 0.4, H2: 0.35, H3: 0.25})
        seq2 = [ctrl.pick_seed([H1, H2, H3]) for _ in range(30)]
        assert seq1 == seq2


class TestPlanning:
    def test_plan_builds_from_ledger_and_counts(self, ctrl, monkeypatch):
        monkeypatch.setattr(
            AdaptiveController, "_ledger",
            lambda self: _StubLedger(
                bitmaps={H1: _bitmap(), H2: _bitmap()},
                per_code_pct={H1: 40.0, H2: 60.0},
            ),
        )
        plan = ctrl.plan(force=True)
        assert set(plan.weights) == {H1, H2}
        assert ctrl.meta()["plans"] == 1
        # history ticked for the plateau verdict
        assert ctrl._history[H1] == [40.0]

    def test_plan_throttled(self, ctrl, monkeypatch):
        monkeypatch.setattr(
            AdaptiveController, "_ledger",
            lambda self: _StubLedger(bitmaps={H1: _bitmap()}),
        )
        ctrl.plan(force=True)
        ctrl.plan()  # inside the min interval: cached, no second build
        assert ctrl.meta()["plans"] == 1
        ctrl.plan(force=True)
        assert ctrl.meta()["plans"] == 2

    def test_throttled_plan_still_reevaluates_requeue(self, ctrl,
                                                      monkeypatch):
        monkeypatch.setattr(
            AdaptiveController, "_ledger",
            lambda self: _StubLedger(bitmaps={H1: _bitmap()}),
        )
        ctrl.plan(force=True)
        plan = ctrl.plan(parked=[("tok", "budget_exhausted")])
        assert plan.requeue == ("tok",)
        assert ctrl.meta()["plans"] == 1

    def test_select_requeue_counts(self, ctrl, monkeypatch):
        monkeypatch.setattr(
            AdaptiveController, "_ledger",
            lambda self: _StubLedger(bitmaps={H1: _bitmap()}),
        )
        picked = ctrl.select_requeue(
            [("t1", "budget_exhausted"), ("t2", "verdict")], live=()
        )
        assert picked == ["t1"]
        assert ctrl.meta()["requeued_paths"] == 1

    def test_select_requeue_disabled(self, ctrl, monkeypatch):
        monkeypatch.setattr(args, "adaptive", False)
        assert ctrl.select_requeue([("t1", "budget_exhausted")]) == []


class TestFlipTargets:
    def test_prefix_match(self, ctrl):
        import time

        ctrl._plan = SteeringPlan(flip_targets={H1: (7, 3)})
        ctrl._plan_at = time.monotonic()
        assert ctrl.flip_targets_for(H1) == (7, 3)
        assert ctrl.flip_targets_for(H1[:10]) == (7, 3)
        assert ctrl.flip_targets_for(H2) == ()

    def test_count_flips(self, ctrl):
        ctrl.count_flips(planned=3, hit=2)
        m = ctrl.meta()
        assert m["flips_planned"] == 3 and m["flips_hit"] == 2


class TestCoverageStop:
    def test_no_target_no_stop(self, ctrl):
        assert ctrl.coverage_stop() is None
        assert ctrl.stop_state() is None

    def test_target_reached_latches(self, ctrl, monkeypatch):
        monkeypatch.setattr(
            AdaptiveController, "_ledger",
            lambda self: _StubLedger(bitmaps={H1: _bitmap()}, pct=92.5),
        )
        assert ctrl.coverage_stop(target=90.0) == "target"
        stop = ctrl.stop_state()
        assert stop["coverage_target_met"] is True
        assert stop["coverage_pct_reachable"] == 92.5
        assert stop["reason"] == "target"
        # latched: a second verdict does not re-stamp
        assert ctrl.coverage_stop(target=90.0) == "target"
        assert ctrl.meta()["coverage_stop"] == stop

    def test_below_target_keeps_exploring(self, ctrl, monkeypatch):
        monkeypatch.setattr(
            AdaptiveController, "_ledger",
            lambda self: _StubLedger(bitmaps={H1: _bitmap()}, pct=10.0),
        )
        assert ctrl.coverage_stop(target=90.0) is None

    def test_all_codes_plateau_stops(self, ctrl, monkeypatch):
        monkeypatch.setattr(
            AdaptiveController, "_ledger",
            lambda self: _StubLedger(
                bitmaps={H1: _bitmap()}, pct=50.0,
                per_code_pct={H1: 50.0},
            ),
        )
        for _ in range(PLATEAU_WINDOW + 2):  # flat history -> plateau
            ctrl.plan(force=True)
        assert ctrl.coverage_stop(target=90.0) == "plateau"
        assert ctrl.meta()["plateau_stops"] == 1

    def test_disabled_never_stops(self, ctrl, monkeypatch):
        monkeypatch.setattr(args, "adaptive", False)
        monkeypatch.setattr(
            AdaptiveController, "_ledger",
            lambda self: _StubLedger(bitmaps={H1: _bitmap()}, pct=99.0),
        )
        assert ctrl.coverage_stop(target=50.0) is None


class TestLifecycle:
    def test_reset_scope(self, ctrl, monkeypatch):
        monkeypatch.setattr(
            AdaptiveController, "_ledger",
            lambda self: _StubLedger(bitmaps={H1: _bitmap()}, pct=99.0),
        )
        ctrl.plan(force=True)
        ctrl.pick_seed([H1, H2])
        ctrl.coverage_stop(target=50.0)
        ctrl.reset_scope()
        assert ctrl.current_plan() is None
        assert ctrl.stop_state() is None
        assert ctrl._history == {} and ctrl._granted == {}
        # counters survive reset (scope is per-analysis, metrics are not)
        assert ctrl.meta()["plans"] == 1

    def test_register_points_bounded(self, ctrl):
        from mythril_tpu.adaptive.controller import _MAX_POINT_CODES

        for i in range(_MAX_POINT_CODES + 1):
            ctrl.register_points("%064x" % i, [{"addr": 1, "score": 1.0}])
        assert len(ctrl._points) <= _MAX_POINT_CODES

    def test_meta_shape(self, ctrl):
        m = ctrl.meta()
        assert m["enabled"] is True
        for k in ("plans", "resteered_slots", "requeued_paths",
                  "flips_planned", "flips_hit", "plateau_stops"):
            assert m[k] == 0
