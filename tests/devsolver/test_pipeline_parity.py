"""End-to-end parity: the device SAT tier must not change WHAT the
pipelined engine reports, only WHERE path conditions get decided.

Differential on the gated-branch contract (an infeasible selfdestruct
guarded by a range pin plus a feasible one): devsolver on vs off through
the full pipelined analysis must yield identical issue sets, and the on
run must actually route queries through the tier.
"""

import pytest

from bench_contracts import GATED
from mythril_tpu import devsolver
from mythril_tpu.observability.metrics import get_registry
from mythril_tpu.support.support_args import args as global_args


def _analyze(code: bytes, dev: bool):
    from mythril_tpu import absdomain
    from mythril_tpu.analysis.module.loader import ModuleLoader
    from mythril_tpu.analysis.security import (
        fire_lasers, reset_callback_modules)
    from mythril_tpu.analysis.symbolic import SymExecWrapper
    from mythril_tpu.querycache import reset_query_cache
    from mythril_tpu.smt.solver import clear_model_cache

    reset_callback_modules()
    for m in ModuleLoader().get_detection_modules():
        if hasattr(m, "cache"):
            m.cache.clear()
    clear_model_cache()
    reset_query_cache()
    devsolver.reset_state()
    prev = (global_args.frontier, global_args.frontier_force,
            global_args.frontier_mesh, global_args.pipeline,
            global_args.devsolver)
    global_args.frontier = True
    global_args.frontier_force = True
    global_args.frontier_mesh = False
    global_args.pipeline = True
    global_args.devsolver = dev
    try:
        sym = SymExecWrapper(
            code,
            address=0x0901D12E,
            strategy="dfs",
            transaction_count=1,
            execution_timeout=120,
            modules=["AccidentallyKillable"],
        )
        return fire_lasers(sym, white_list=["AccidentallyKillable"])
    finally:
        (global_args.frontier, global_args.frontier_force,
         global_args.frontier_mesh, global_args.pipeline,
         global_args.devsolver) = prev


def _issue_keys(issues):
    return sorted((i.swc_id, i.address, i.function) for i in issues)


@pytest.mark.slow
def test_pipelined_gated_branch_parity_on_vs_off():
    reg = get_registry()
    reg.reset(prefix="devsolver.")
    on = _analyze(GATED, dev=True)
    attempted = reg.counter("devsolver.admitted").value
    bad = reg.counter("devsolver.model_validation_failures").value

    reg.reset(prefix="devsolver.")
    off = _analyze(GATED, dev=False)
    off_attempted = reg.counter("devsolver.admitted").value

    assert _issue_keys(on) == _issue_keys(off), (
        "device SAT tier changed the issue set"
    )
    assert len(on) == 1, f"expected exactly the feasible kill, got {on}"
    assert attempted > 0, "devsolver-on run never admitted a query"
    assert off_attempted == 0, "devsolver-off run touched the tier"
    assert bad == 0, "validated-model contract violated during e2e run"


# x = cld(0); y = cld(32); require x < 16, y < 16, x == y; the selfdestruct
# is guarded by ((x ^ y) & 15) == 15: a relational contradiction that
# intervals and known bits cannot see (neither var is pinned to a point)
# but bit-level search refutes after one decision
RELATIONAL = bytes.fromhex(
    "6000358060109010600c57005b"
    "6020358060109010601957005b"
    "80821460215700" "5b"
    "18600f16600f14602d57005b"
    "33ff"
)


@pytest.mark.parametrize("name", ["suicide", "gated", "relational",
                                  "killbilly"])
def test_devsolver_keeps_issue_set_and_decides_relational(name, monkeypatch):
    """--no-devsolver parity: identical issue sets, the off run admits
    nothing, and the relational contradiction is decided UNSAT by the
    device tier rather than left to the exact tiers."""
    from tests.parity import exploit, parity_run

    # the relational contradiction must report nothing; killbilly runs
    # every detection module, the others the kill detector alone
    target, txs = (RELATIONAL, 1) if name == "relational" else exploit(name)
    modules = None if name == "killbilly" else ["AccidentallyKillable"]
    swc = None if name == "relational" else "106"
    runs = {
        on: parity_run(monkeypatch, target, txs, modules, ("devsolver.",),
                       devsolver=on, pipeline=True, frontier_mesh=False)
        for on in (True, False)
    }
    (on, on_snap), (off, off_snap) = runs[True], runs[False]
    if swc is None:
        assert not on, f"infeasible branch reported: {on}"
    else:
        assert any(s == swc for s, _ in on), f"recall lost: {on}"
    assert on == off
    assert off_snap.get("devsolver.admitted", 0) == 0, off_snap
    if name == "relational":
        assert on_snap["devsolver.decided_unsat"] > 0, on_snap
