"""Adaptive steering on/off parity — the ``--no-adaptive`` contract.

The controller only reorders and retimes frontier compute; it must never
change WHAT is explored to completion.  The fast tests pin the engine's
FIFO fallback gates (tier-1); the ``slow``-marked e2e runs the real
cooperative device frontier twice and asserts bit-identical issue sets,
and the parametrised multi-code batches (exploit, wide and loop-tail mixes)
add steering engagement and the coverage stop's segment saving.
"""

import pytest

from mythril_tpu.adaptive import get_adaptive_controller
from mythril_tpu.frontier.engine import (
    _adaptive_coverage_stop,
    _adaptive_pick,
)
from mythril_tpu.observability.exploration import get_exploration_ledger
from mythril_tpu.observability.metrics import get_registry
from mythril_tpu.support.support_args import args as global_args

# selector(kill()=0x41c0e1b5) -> CALLER;SELFDESTRUCT, else revert
SUICIDE_HEX = "60003560e01c6341c0e1b51460145760006000fd5b33ff"
# value-gated kill: two nested comparisons guard the SELFDESTRUCT
GATED_HEX = "60003580600a9010600c57005b80600514601c5780601414601c57005b33ff"
# selector -> kill at 0x1e, fallthrough into a 511-iteration concrete
# loop ending in STOP: coverage saturates (only the loop-exit STOP stays
# uncovered) segments before the unroll finishes, so --coverage-target
# must latch its stop verdict mid-run, never racing the natural end
LOOP_TAIL_HEX = (
    "60003560e01c6341c0e1b514601e5760005b600101806102001160115700"
    "5b33ff"
)


class TestEngineGates:
    """The actuation sites' FIFO fallbacks, no devices involved."""

    def test_pick_fifo_with_single_seed(self):
        assert _adaptive_pick([7], [0], ["a" * 64]) == 0

    def test_pick_fifo_when_disabled(self, monkeypatch):
        monkeypatch.setattr(global_args, "adaptive", False)
        get_adaptive_controller().reset_scope()
        before = get_registry().counter("adaptive.resteered_slots").value
        for _ in range(8):
            assert _adaptive_pick(
                [0, 1], [0, 1], ["a" * 64, "b" * 64]
            ) == 0
        after = get_registry().counter("adaptive.resteered_slots").value
        assert after == before, "--no-adaptive run still resteered"

    def test_coverage_stop_gate_requires_target(self, monkeypatch):
        monkeypatch.setattr(global_args, "coverage_target", None)
        assert _adaptive_coverage_stop() is False


def _clear_module_caches():
    """Detection modules memoize (code, address) pairs per process; a
    parity re-run must see a cold analysis, not the memo."""
    from mythril_tpu.analysis.module.loader import ModuleLoader
    from mythril_tpu.analysis.security import reset_callback_modules

    reset_callback_modules()
    for module in ModuleLoader().get_detection_modules():
        module.cache.clear()


def _cooperative_run(adaptive: bool, coverage_target=None, jobs=None):
    from mythril_tpu.analysis.cooperative import analyze_cooperative

    _clear_module_caches()
    get_registry().reset()
    get_exploration_ledger().reset_scope()
    ctrl = get_adaptive_controller()
    ctrl.reset_scope()
    saved = (global_args.adaptive, global_args.coverage_target,
             global_args.frontier, global_args.frontier_force,
             global_args.frontier_width, global_args.pipeline,
             global_args.loop_bound)
    global_args.adaptive = adaptive
    global_args.coverage_target = coverage_target
    global_args.frontier = True
    global_args.frontier_force = True
    global_args.frontier_width = 64
    global_args.pipeline = True
    global_args.loop_bound = 600  # above LOOP_TAIL's natural exit at 512
    try:
        per_name, _states = analyze_cooperative(
            jobs if jobs is not None else [
                ("suicide", bytes.fromhex(SUICIDE_HEX)),
                ("gated", bytes.fromhex(GATED_HEX)),
            ],
            transaction_count=1,
            execution_timeout=120,
        )
    finally:
        (global_args.adaptive, global_args.coverage_target,
         global_args.frontier, global_args.frontier_force,
         global_args.frontier_width, global_args.pipeline,
         global_args.loop_bound) = saved
    issues = sorted(
        (name, i.swc_id, i.address, i.bytecode_hash)
        for name, found in per_name.items()
        for i in found
    )
    snap = {
        k: v for k, v in get_registry().snapshot().items()
        if k.startswith("adaptive.")
    }
    return issues, snap, ctrl.stop_state()


@pytest.mark.slow
def test_cooperative_issue_sets_bit_identical_on_vs_off():
    on_issues, on_snap, _ = _cooperative_run(adaptive=True)
    off_issues, off_snap, _ = _cooperative_run(adaptive=False)
    assert on_issues, "steered run found nothing (workload broken)"
    assert on_issues == off_issues, (
        "adaptive steering changed the issue set (parity broken): "
        f"{on_issues} != {off_issues}"
    )
    assert not off_snap.get("adaptive.plans", 0), (
        f"--no-adaptive run still planned: {off_snap}"
    )
    assert not off_snap.get("adaptive.resteered_slots", 0), (
        f"--no-adaptive run still resteered: {off_snap}"
    )


@pytest.mark.slow
def test_coverage_target_latches_stop_without_losing_issues():
    jobs = [("loop_tail", bytes.fromhex(LOOP_TAIL_HEX)),
            ("suicide", bytes.fromhex(SUICIDE_HEX))]
    base_issues, _, base_stop = _cooperative_run(adaptive=True, jobs=jobs)
    assert base_stop is None, "run without a target latched a stop"
    issues, _, stop = _cooperative_run(
        adaptive=True, coverage_target=90.0, jobs=jobs
    )
    assert stop is not None, "--coverage-target never latched a verdict"
    assert stop["coverage_target_met"] is True
    assert stop["coverage_target"] == 90.0
    # the 90% bar is only reachable once every kill path executed (the
    # kill instructions sit in the denominator), so the early stop must
    # not cost recall on this workload
    assert issues == base_issues


def _mix(name):
    from bench_contracts import KILLBILLY, wide_contract

    suicide, gated = bytes.fromhex(SUICIDE_HEX), bytes.fromhex(GATED_HEX)
    # (jobs, transactions, coverage target of the steered run): steering
    # only departs from FIFO when the seed queue holds distinct codes, so
    # every workload is a multi-code batch
    return {
        "exploit_mix": ([("suicide", suicide), ("gated", gated),
                         ("killbilly", bytes.fromhex(KILLBILLY))], 2, None),
        "wide_mix": ([(f"wide{n}", wide_contract(n)) for n in (3, 4, 5, 6)],
                     1, None),
        "loop_tail": ([("loop_tail", bytes.fromhex(LOOP_TAIL_HEX)),
                       ("suicide", suicide)], 1, 90.0),
    }[name]


@pytest.mark.parametrize("name", ["exploit_mix", "wide_mix", "loop_tail"])
def test_steering_keeps_issue_set_on_multi_code_batches(name, monkeypatch):
    """--no-adaptive parity on multi-code batches: identical issue sets
    with SWC-106 found, the off run neither plans nor resteers, the mixes
    really resteer, and on ``loop_tail`` the --coverage-target stop
    dispatches fewer segments than the unrolled unsteered run."""
    from tests.parity import cooperative_parity_run

    jobs, txs, target = _mix(name)

    def run(steered):
        out = cooperative_parity_run(
            monkeypatch, jobs, txs, ("adaptive.",), adaptive=steered,
            coverage_target=target if steered else None, pipeline=True,
            frontier_mesh=False,
            # above loop_tail's 511 iterations: the unsteered run unrolls
            # to its natural exit
            loop_bound=600,
        )
        return (*out, get_adaptive_controller().stop_state())

    off, off_snap, off_segments, _ = run(False)
    on, on_snap, on_segments, stop = run(True)
    assert "106" in {swc for _, swc, _, _ in on}, on
    assert on == off
    assert not off_snap.get("adaptive.resteered_slots", 0), off_snap
    assert not off_snap.get("adaptive.plans", 0), off_snap
    if target is None:
        assert on_snap["adaptive.resteered_slots"] > 0, on_snap
    else:
        assert stop is not None and stop["coverage_target_met"], stop
        assert on_segments < off_segments, (on_segments, off_segments)
