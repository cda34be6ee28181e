"""Facade parity: the registry-backed statistics singletons must be
indistinguishable from the pre-observability attribute-style originals,
and report meta must carry the new observability block."""

import json

import pytest

from mythril_tpu.frontier.stats import FrontierStatistics
from mythril_tpu.observability import (
    get_registry,
    get_tracer,
    reset_analysis_metrics,
)
from mythril_tpu.smt.solver import SolverStatistics


@pytest.fixture(autouse=True)
def _clean_stats():
    FrontierStatistics().reset()
    SolverStatistics().reset()
    yield
    FrontierStatistics().reset()
    SolverStatistics().reset()


# seed-identical as_dict for a freshly reset instance — byte-for-byte
_SEED_EMPTY = (
    '{"device_instructions": 0, "device_paths": 0, "segments": 0, '
    '"mesh_devices": 0, "segment_s": 0.0, "harvest_s": 0.0, '
    '"mid_injections": 0, "mid_encode_failures": 0, "semantic_parks": 0, '
    '"parks_by_opcode": {}, "parks_by_reason": {}}'
)


def test_frontier_as_dict_empty_is_byte_identical_to_seed():
    assert json.dumps(FrontierStatistics().as_dict()) == _SEED_EMPTY


def test_frontier_as_dict_populated_matches_seed_shape():
    stats = FrontierStatistics()
    stats.device_instructions += 1000
    stats.device_paths += 3
    stats.segments += 2
    stats.segment_s += 1.23456
    stats.harvest_s += 0.98765
    stats.mesh_devices = 8
    stats.mid_injections += 1
    stats.semantic_parks += 2
    stats.record_park("CALL")
    stats.record_park("CALL")
    stats.record_park("SHA3")
    stats.record_bulk_park("timeout", 5)
    stats.record_bulk_park("noop", 0)  # n=0 must not create a key
    assert json.dumps(stats.as_dict()) == (
        '{"device_instructions": 1000, "device_paths": 3, "segments": 2, '
        '"mesh_devices": 8, "segment_s": 1.235, "harvest_s": 0.988, '
        '"mid_injections": 1, "mid_encode_failures": 0, "semantic_parks": 2, '
        '"parks_by_opcode": {"CALL": 2, "SHA3": 1}, '
        '"parks_by_reason": {"timeout": 5, "opcode": 3}}'
    )


def test_frontier_singleton_and_registry_share_state():
    FrontierStatistics().segments += 4
    assert FrontierStatistics().segments == 4
    assert get_registry().snapshot()["frontier.segments"] == 4


def test_solver_stats_attribute_assignment_and_repr():
    stats = SolverStatistics()
    stats.query_count += 2
    stats.solver_time += 0.5
    stats.probe_hits = 9  # direct assignment (test_recall_differential style)
    stats.unknown_as_unsat = 0
    assert SolverStatistics() is stats
    assert SolverStatistics().probe_hits == 9
    assert repr(stats) == (
        "Solver statistics: query count: 2, solver time: 0.500, "
        "probe hits: 9, cdcl calls: 0, unknown treated as unsat: 0"
    )


def test_solver_enabled_survives_reset():
    stats = SolverStatistics()
    stats.enabled = True
    stats.query_count += 5
    stats.reset()
    assert stats.enabled is True
    assert stats.query_count == 0


def test_reset_analysis_metrics_sweeps_both_facades_keeps_persistent():
    FrontierStatistics().segments += 3
    SolverStatistics().query_count += 7
    get_registry().counter("frontier.slow_code_verdicts", persistent=True).inc()
    reset_analysis_metrics()
    assert FrontierStatistics().segments == 0
    assert SolverStatistics().query_count == 0
    assert (
        get_registry().counter("frontier.slow_code_verdicts", persistent=True).value
        == 1
    )
    get_registry().reset(include_persistent=True)


def test_report_meta_observability_roundtrip_jsonv2():
    from mythril_tpu.analysis.report import Report
    from mythril_tpu.core.execution_info import SolverStatsInfo

    SolverStatistics().query_count += 11
    report = Report(execution_info=[SolverStatsInfo()])
    meta = json.loads(report.as_swc_standard_format())[0]["meta"]
    # legacy execution-info rollup is untouched
    assert meta["mythril_execution_info"]["solver_query_count"] == 11
    # new block: full metrics snapshot rides the same jsonv2 document
    metrics = meta["observability"]["metrics"]
    assert metrics["solver.query_count"] == 11
    assert "frontier.segments" in metrics


def test_report_meta_includes_trace_summary_when_tracing():
    from mythril_tpu.analysis.report import Report

    tracer = get_tracer()
    tracer.reset()
    tracer.enabled = True
    try:
        with tracer.span("unit.test", cat="test"):
            pass
        meta = json.loads(Report().as_swc_standard_format())[0]["meta"]
        trace = meta["observability"]["trace"]
        assert trace["enabled"] is True
        assert trace["spans"] == 1
    finally:
        tracer.enabled = False
        tracer.reset()
