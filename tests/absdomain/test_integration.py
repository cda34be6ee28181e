"""Pre-filter integration: feasibility pool publication and solver gates."""

import pytest

from mythril_tpu import absdomain
from mythril_tpu.observability import get_registry
from mythril_tpu.smt import terms


@pytest.fixture(autouse=True)
def _fresh():
    absdomain.reset_state()
    get_registry().reset(prefix="prefilter.")
    get_registry().reset(prefix="pipeline.")
    yield
    absdomain.reset_state()


def _unsat_raws(tag: str):
    x = terms.var(f"pfint_{tag}", 256)
    return [terms.eq(x, terms.const(1, 256)),
            terms.eq(x, terms.const(2, 256))]


# ---------------------------------------------------------------------------
# FeasibilityPool: verdict=False publication
# ---------------------------------------------------------------------------


def test_pool_prefilter_kill_skips_worker():
    from mythril_tpu.frontier.pipeline import FeasibilityPool

    pool = FeasibilityPool(workers=1)
    raws = _unsat_raws("kill")
    key = frozenset(t.tid for t in raws)
    pool.submit(0, "rec", 1, raws, key, verdict=False)
    # no worker ran: the verdict is already drainable
    assert [(s, ok) for s, _, _, ok, _ in pool.drain()] == [(0, False)]
    assert pool.pending() == 0
    reg = get_registry()
    assert reg.counter("pipeline.pool_prefilter_kills").value == 1
    assert not reg.counter("pipeline.pool_submitted").value
    pool.shutdown()


def test_pool_prefilter_kill_publishes_to_inflight_waiters():
    """Bugfix: a pre-filter kill must reach waiters ALREADY deduplicated
    under the same canonical key, not only the killed submission itself."""
    from mythril_tpu.frontier.pipeline import FeasibilityPool

    pool = FeasibilityPool(workers=1)
    raws = _unsat_raws("inflight")
    key = frozenset(t.tid for t in raws)
    # hold the solver lock so the exact worker cannot publish first
    with pool._solver_lock:
        pool.submit(0, "recA", 1, raws, key)            # exact, in flight
        pool.submit(1, "recB", 2, raws, key)            # dedup waiter
        pool.submit(2, "recC", 3, raws, key, verdict=False)  # abstract kill
        verdicts = sorted((s, ok) for s, _, _, ok, _ in pool.drain())
        # all three waiters already resolved, before the worker finished
        assert verdicts == [(0, False), (1, False), (2, False)]
    pool._executor.shutdown(wait=True)
    # the worker's late (key, ok) entry must not crash or re-publish
    assert pool.drain() == []
    assert pool.pending() == 0


def test_pool_duplicate_done_keys_tolerated():
    from mythril_tpu.frontier.pipeline import FeasibilityPool

    pool = FeasibilityPool(workers=1)
    raws = _unsat_raws("dup")
    key = frozenset(t.tid for t in raws)
    pool.submit(0, "recA", 1, raws, key, verdict=False)
    pool.submit(1, "recB", 1, raws, key, verdict=False)
    verdicts = sorted((s, ok) for s, _, _, ok, _ in pool.drain())
    assert verdicts == [(0, False), (1, False)]
    assert pool.drain() == []
    pool.shutdown()


# ---------------------------------------------------------------------------
# solver gates: tier 0.58 and the batched entry
# ---------------------------------------------------------------------------


def test_solve_conjunction_tier_058_kills(monkeypatch):
    from mythril_tpu.smt import solver
    from mythril_tpu.support.support_args import args as global_args

    monkeypatch.setattr(global_args, "prefilter", True, raising=False)
    solver.clear_model_cache()
    reg = get_registry()
    verdict, model = solver.solve_conjunction(_unsat_raws("t058"),
                                              use_cache=False)
    assert verdict == solver.UNSAT and model is None
    assert reg.counter("prefilter.killed").value == 1


def test_no_prefilter_flag_disables_gate(monkeypatch):
    from mythril_tpu.smt import solver
    from mythril_tpu.support.support_args import args as global_args

    monkeypatch.setattr(global_args, "prefilter", False, raising=False)
    solver.clear_model_cache()
    reg = get_registry()
    verdict, _ = solver.solve_conjunction(_unsat_raws("noflag"),
                                          use_cache=False)
    assert verdict == solver.UNSAT  # exact tiers still refute it
    assert not reg.counter("prefilter.evaluated").value


def test_batch_check_prefilter_gate(monkeypatch):
    from mythril_tpu.smt import solver
    from mythril_tpu.support.support_args import args as global_args

    monkeypatch.setattr(global_args, "prefilter", True, raising=False)
    solver.clear_model_cache()
    x = terms.var("pfint_batch_sat", 256)
    sat = [terms.ult(x, terms.const(10, 256))]
    rows = [sat, _unsat_raws("batch")]
    out = solver.check_satisfiable_batch(rows)
    assert out == [True, False]
    assert get_registry().counter("prefilter.killed").value >= 1


# ---------------------------------------------------------------------------
# end to end: --no-prefilter parity on the exploit shapes
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("name", ["suicide", "gated", "killbilly"])
def test_prefilter_keeps_issue_set_and_gated_branch_dies(name, monkeypatch):
    """Pipelined frontier with the pre-filter on and off: identical issue
    sets, the off run evaluates nothing, and on ``gated`` the filter
    refutes the infeasible branch (x == 20 under x < 10) itself."""
    from tests.parity import exploit, parity_run

    target, txs = exploit(name)
    # killbilly runs every detection module: its feasibility traffic is
    # module confirmation demands, the fallthrough side of the filter
    modules = None if name == "killbilly" else ["AccidentallyKillable"]
    runs = {
        on: parity_run(monkeypatch, target, txs, modules, ("prefilter.",),
                       prefilter=on, pipeline=True, frontier_mesh=False)
        for on in (True, False)
    }
    (on, on_snap), (off, off_snap) = runs[True], runs[False]
    assert any(swc == "106" for swc, _ in on), f"recall lost: {on}"
    assert on == off
    assert off_snap.get("prefilter.evaluated", 0) == 0, off_snap
    if name == "gated":
        assert on_snap["prefilter.killed"] > 0, on_snap
