"""Per-tier solver accounting: a query that one tier decides moves that
tier's ``solver.tier.<tier>.{calls,decided,busy_s}`` and no other tier's
``decided``."""

import pytest

from mythril_tpu import devsolver, querycache
from mythril_tpu.observability import get_tracer
from mythril_tpu.observability.metrics import get_registry
from mythril_tpu.smt import solver, terms
from mythril_tpu.support.support_args import args as global_args


def _tiers():
    solver.SolverStatistics().materialize()
    snap = get_registry().snapshot(prefix="solver.tier.")
    return {t: {k: snap[f"solver.tier.{t}.{k}"] for k in ("calls", "decided", "busy_s")}
            for t in solver.TIERS}


def _querycache(tag):
    x = terms.var(f"tier_{tag}_x", 256)
    query = [terms.ugt(x, terms.const(5, 256)), terms.ult(x, terms.const(3, 256))]
    querycache.get_query_cache().record(query, solver.UNSAT)
    return query, solver.ProbeConfig(), solver.UNSAT


def _prefilter(tag):
    x = terms.var(f"tier_{tag}_x", 256)
    # bit 0 of x | 1 is known to be set: the known-bits pass refutes it
    return ([terms.eq(terms.bor(x, terms.const(1, 256)), terms.const(0, 256))],
            solver.ProbeConfig(), solver.UNSAT)


def _devsolver(tag):
    x, y = terms.var(f"tier_{tag}_x", 8), terms.var(f"tier_{tag}_y", 8)
    return ([terms.eq(x, y), terms.eq(terms.bxor(x, y), terms.const(255, 8))],
            solver.ProbeConfig(), solver.UNSAT)


def _host_probe(tag):
    x = terms.var(f"tier_{tag}_x", 256)
    return ([terms.eq(terms.add(x, terms.const(1, 256)), terms.const(6, 256))],
            solver.ProbeConfig(sat_biased=True), solver.SAT)


def _device_probe(tag):
    x = terms.var(f"tier_{tag}_x", 256)
    return ([terms.eq(terms.add(x, terms.const(1, 256)), terms.const(6, 256)),
             terms.ult(x, terms.const(10, 256))],
            solver.ProbeConfig(), solver.SAT)


def _cdcl(tag):
    x = terms.var(f"tier_{tag}_x", 8)
    # no square is 3 mod 4: UNSAT, and no interval or known-bits pass sees it
    return ([terms.eq(terms.mul(x, x), terms.const(3, 8))],
            solver.ProbeConfig(), solver.UNSAT)


CASES = {
    # tier: (query, the switches that let the query reach that tier)
    "querycache": (_querycache, {}),
    "prefilter": (_prefilter, {}),
    "devsolver": (_devsolver, {"prefilter": False}),
    "host_probe": (_host_probe, {"prefilter": False, "devsolver": False,
                                 "probe_backend": "host"}),
    "device_probe": (_device_probe, {"prefilter": False, "devsolver": False,
                                     "probe_backend": "jax"}),
    "cdcl": (_cdcl, {"prefilter": False, "devsolver": False,
                     "probe_backend": "cdcl"}),
}


@pytest.fixture
def clean_solver(monkeypatch):
    solver.clear_model_cache()
    querycache.reset_query_cache()
    querycache.configure(enabled=True, cache_dir=None)
    devsolver.reset_state()
    yield monkeypatch
    solver.clear_model_cache()
    querycache.reset_query_cache()
    devsolver.reset_state()


@pytest.mark.parametrize("tier", solver.TIERS)
def test_a_query_a_tier_decides_is_counted_there_alone(tier, clean_solver):
    build, switches = CASES[tier]
    for key, value in switches.items():
        clean_solver.setattr(global_args, key, value)
    query, config, want = build(tier)
    before = _tiers()
    status, _ = solver.solve_conjunction(query, config)
    after = _tiers()
    assert status == want
    assert after[tier]["calls"] >= before[tier]["calls"] + 1
    assert after[tier]["decided"] == before[tier]["decided"] + 1
    assert after[tier]["busy_s"] > before[tier]["busy_s"]
    for other in set(solver.TIERS) - {tier}:
        assert after[other]["decided"] == before[other]["decided"], other


def test_tier_spans_carry_decided(clean_solver):
    tracer = get_tracer()
    tracer.reset()
    tracer.enabled = True
    try:
        query, config, _ = _prefilter("span")
        solver.solve_conjunction(query, config)
    finally:
        tracer.enabled = False
    spans = [s for s in tracer.spans() if s["name"] == "smt.prefilter"]
    tracer.reset()
    assert spans and spans[-1]["args"] == {"decided": 1}


def test_the_tier_counters_are_in_every_snapshot():
    from mythril_tpu.observability import observability_meta

    names = observability_meta()["metrics"]
    for busy, calls, decided in solver._TIER_COUNTERS.values():
        assert {busy, calls, decided} <= set(names)
