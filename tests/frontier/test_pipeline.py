"""Pipelined frontier: correction-ledger protocol, the background
feasibility pool, and pipelined-vs-synchronous issue-set parity.

The parity tests mirror test_frontier_engine's differential idiom — the
synchronous loop is the oracle, the pipelined loop must produce the same
issues (the ISSUE's correctness bar, same contract as --no-staticpass).
"""

from pathlib import Path

import numpy as np
import pytest

from mythril_tpu.frontier.pipeline import CorrectionLedger, FeasibilityPool
from mythril_tpu.support.support_args import args as global_args

TESTDATA = Path(__file__).parent.parent / "testdata" / "inputs"


# ---------------------------------------------------------------------------
# CorrectionLedger
# ---------------------------------------------------------------------------


def test_ledger_touch_rides_next_dispatch():
    led = CorrectionLedger(4)
    led.touch(1)
    assert led.corr_mask[1] and led.active_at[1] == 0
    mask = led.consume(np.array([0, 0, 0, 0]))
    assert mask[1] and mask.sum() == 1
    assert not led.corr_mask.any(), "mask must clear after consume"
    # dispatch 0 pulled: slot 1's host write rode dispatch 0, so its
    # output IS authoritative — nothing to carry
    assert list(led.on_pull()) == []


def test_ledger_carry_until_active_dispatch_pulled():
    led = CorrectionLedger(4)
    led.consume(np.full(4, -1))  # dispatch 0 issued before the touch
    led.touch(2)  # rides dispatch 1
    led.consume(np.full(4, -1))  # dispatch 1 issued
    # pulling dispatch 0: slot 2's write is newer than this output
    assert list(led.on_pull()) == [2]
    # pulling dispatch 1: now the device output reflects the write
    assert list(led.on_pull()) == []


def test_ledger_device_ownership_of_freed_slots():
    led = CorrectionLedger(4)
    host_seed = np.array([5, -1, 7, -1])  # slots 1 and 3 are free
    led.touch(1)  # freed by the host
    led.touch(2)  # live correction
    led.consume(host_seed)
    assert led.device_owned[1], "freed slot exposed to device must be owned"
    assert not led.device_owned[2], "live slot is not grantable"
    assert not led.device_owned[3], "untouched free slot was never exposed"
    led.release_owned()
    assert not led.device_owned.any()


def test_ledger_consume_all_marks_everything():
    led = CorrectionLedger(3)
    led.touch(0)
    led.consume_all()
    assert (led.active_at == 0).all()
    assert not led.corr_mask.any()
    assert list(led.on_pull()) == []


def test_ledger_carry_forward_clears_events():
    from mythril_tpu.frontier.state import empty_state
    from mythril_tpu.frontier.step import Caps

    caps = Caps(B=4)
    prev = empty_state(caps, 4)
    new = empty_state(caps, 4)
    prev.pc[1] = 42
    prev.seed[1] = 9
    new.pc[1] = 7  # stale device value
    new.ev_len[1] = 3  # stale device events

    led = CorrectionLedger(4)
    led.consume(np.full(4, -1))  # dispatch 0 (before the host write)
    led.touch(1)
    led.consume(np.full(4, -1))  # dispatch 1 carries the write
    carried = led.carry_forward(new, prev)  # pull of dispatch 0
    assert carried == 1
    assert new.pc[1] == 42 and new.seed[1] == 9
    assert new.ev_len[1] == 0, "carried slots must not re-drain old events"


# ---------------------------------------------------------------------------
# FeasibilityPool
# ---------------------------------------------------------------------------


def _sym_neq(value: int):
    from mythril_tpu.smt import terms

    x = terms.var("pool_x", 256)
    return terms.not_(terms.eq(x, terms.const(value, 256)))


def test_pool_sat_and_unsat_verdicts():
    from mythril_tpu.smt import terms

    pool = FeasibilityPool(workers=2)
    x = terms.var("pool_y", 256)
    sat_raws = [terms.eq(x, terms.const(5, 256))]
    unsat_raws = [
        terms.eq(x, terms.const(1, 256)),
        terms.eq(x, terms.const(2, 256)),
    ]
    pool.submit(0, "recA", 1, sat_raws, frozenset(t.tid for t in sat_raws))
    pool.submit(1, "recB", 2, unsat_raws,
                frozenset(t.tid for t in unsat_raws))
    pool._executor.shutdown(wait=True)
    verdicts = {slot: ok for slot, rec, n, ok, why in pool.drain()}
    assert verdicts == {0: True, 1: False}
    assert pool.pending() == 0


def test_pool_inflight_dedup_fans_out_one_solve():
    from mythril_tpu.observability.metrics import get_registry
    from mythril_tpu.smt import terms

    get_registry().reset(prefix="pipeline.")
    pool = FeasibilityPool(workers=1)
    x = terms.var("pool_z", 256)
    raws = [terms.eq(x, terms.const(3, 256))]
    key = frozenset(t.tid for t in raws)
    # hold the solver lock so both submits land before the worker runs
    with pool._solver_lock:
        pool.submit(0, "recA", 1, raws, key)
        pool.submit(1, "recB", 1, raws, key)
    pool._executor.shutdown(wait=True)
    out = sorted((slot, ok) for slot, rec, n, ok, why in pool.drain())
    assert out == [(0, True), (1, True)], "both waiters get the verdict"
    reg = get_registry()
    assert reg.counter("pipeline.pool_inflight_dedup").value == 1
    assert reg.counter("pipeline.pool_submitted").value == 1


# ---------------------------------------------------------------------------
# pipelined vs synchronous parity (differential, device forced on)
# ---------------------------------------------------------------------------


def _analyze(code: bytes, tx_count: int, modules, pipeline: bool):
    from mythril_tpu.analysis.module.loader import ModuleLoader
    from mythril_tpu.analysis.security import fire_lasers, reset_callback_modules
    from mythril_tpu.analysis.symbolic import SymExecWrapper

    reset_callback_modules()
    for m in ModuleLoader().get_detection_modules():
        if hasattr(m, "cache"):
            m.cache.clear()
    prev = (global_args.frontier, global_args.frontier_force,
            global_args.frontier_mesh, global_args.pipeline)
    global_args.frontier = True
    global_args.frontier_force = True
    # the harness pins an 8-device virtual CPU mesh (conftest); the
    # pipelined runner is a single-device path, so compare apples to
    # apples with the mesh disabled in both modes
    global_args.frontier_mesh = False
    global_args.pipeline = pipeline
    try:
        sym = SymExecWrapper(
            code,
            address=0x0901D12E,
            strategy="dfs",
            transaction_count=tx_count,
            execution_timeout=120,
            modules=modules,
        )
        return fire_lasers(sym, white_list=modules)
    finally:
        (global_args.frontier, global_args.frontier_force,
         global_args.frontier_mesh, global_args.pipeline) = prev


def _issue_keys(issues):
    return sorted((i.swc_id, i.address, i.function) for i in issues)


@pytest.mark.slow
def test_pipeline_parity_testdata_contracts():
    from mythril_tpu.observability.metrics import get_registry

    code = bytes.fromhex(
        (TESTDATA / "kill_simple.bin-runtime").read_text().strip()
    )
    get_registry().reset(prefix="pipeline.")
    piped = _analyze(code, 1, ["AccidentallyKillable"], pipeline=True)
    snap = get_registry().snapshot(prefix="pipeline.")
    sync = _analyze(code, 1, ["AccidentallyKillable"], pipeline=False)
    assert _issue_keys(piped) == _issue_keys(sync)
    assert len(piped) == 1
    assert snap.get("pipeline.segments_pipelined", 0) > 0, (
        f"pipelined run never chained a dispatch: {snap}"
    )


@pytest.mark.slow
def test_pipeline_parity_multi_tx_storage_gate():
    # storage-gated selfdestruct: needs the 2-tx chain and exercises
    # harvest-driven slot recycling across pipelined segments
    from tests.frontier.test_frontier_engine import DISPATCH

    guarded = DISPATCH + "600054600114601b5733ff5b00"
    code = bytes.fromhex(guarded)
    piped = _analyze(code, 2, ["AccidentallyKillable"], pipeline=True)
    sync = _analyze(code, 2, ["AccidentallyKillable"], pipeline=False)
    assert _issue_keys(piped) == _issue_keys(sync)


@pytest.mark.parametrize("name", ["suicide", "killbilly"])
def test_pipelined_matches_synchronous_and_chains(name, monkeypatch):
    """The --no-pipeline contract on the exploit shapes: identical issue
    sets, the pipelined run chained segments, the synchronous run none."""
    from tests.parity import exploit, parity_run

    target, txs = exploit(name)
    runs = {
        piped: parity_run(monkeypatch, target, txs, ["AccidentallyKillable"],
                          ("pipeline.",), pipeline=piped,
                          frontier_mesh=False)
        for piped in (True, False)
    }
    (on, on_snap), (off, off_snap) = runs[True], runs[False]
    assert any(swc == "106" for swc, _ in on), f"recall lost: {on}"
    assert on == off
    assert on_snap["pipeline.segments_pipelined"] > 0, on_snap
    assert off_snap["pipeline.segments_pipelined"] == 0, off_snap
