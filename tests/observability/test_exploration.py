"""Exploration ledger: coverage bitmaps, termination attribution, solver
hotspots — and the coverage-plugin pc-clamp regression (PR-14).
"""

import numpy as np
import pytest

from mythril_tpu.observability.exploration import (
    _MAX_HOTSPOT_LABELS,
    ExplorationLedger,
    TERM_CLASSES,
    VERDICT_CLASS,
    get_exploration_ledger,
)
from mythril_tpu.observability.metrics import MetricsRegistry


def _ledger():
    return ExplorationLedger(registry=MetricsRegistry())


# ---------------------------------------------------------------------------
# termination attribution
# ---------------------------------------------------------------------------


def test_stamp_partitions_exactly():
    led = _ledger()
    led.stamp("completed", 3)
    led.stamp("solver_unsat")
    led.stamp("prefilter_killed", 2)
    term = led.terminated()
    assert term["completed"] == 3
    assert term["solver_unsat"] == 1
    assert term["prefilter_killed"] == 2
    assert sum(term.values()) == led.terminated_total() == 6
    assert led.meta()["partition_ok"]


def test_stamp_rejects_unknown_class():
    with pytest.raises(ValueError):
        _ledger().stamp("fell_off_a_cliff")


def test_every_class_is_stampable():
    led = _ledger()
    for cls in TERM_CLASSES:
        led.stamp(cls)
    assert led.terminated_total() == len(TERM_CLASSES)
    assert all(n == 1 for n in led.terminated().values())


def test_verdict_class_maps_into_classification():
    assert set(VERDICT_CLASS.values()) <= set(TERM_CLASSES)
    assert VERDICT_CLASS["unsat"] == "solver_unsat"
    assert VERDICT_CLASS["unknown"] == "solver_timeout_unknown"
    assert VERDICT_CLASS["prefilter"] == "prefilter_killed"


# ---------------------------------------------------------------------------
# coverage bitmaps
# ---------------------------------------------------------------------------


def test_device_planes_fold_and_pct():
    led = _ledger()
    planes = np.zeros((3, 10), bool)
    planes[0, [0, 1, 2, 5]] = True  # 4/10 instructions
    planes[1, 2] = True  # taken edge at the JUMPI
    planes[2, 2] = True  # fall-through edge
    led.record_device_planes("0xabc", 10, 1, planes)
    cov = led.coverage()["0xabc"]
    assert cov["instructions_seen"] == 4
    assert cov["instruction_pct"] == 40.0
    assert cov["edges_total"] == 2
    assert cov["edges_seen"] == 2
    assert cov["edge_pct"] == 100.0
    assert led.coverage_pct("0xabc") == 40.0


def test_device_planes_union_is_cumulative():
    led = _ledger()
    a = np.zeros((3, 4), bool)
    a[0, 0] = True
    b = np.zeros((3, 4), bool)
    b[0, 3] = True
    led.record_device_planes("0xabc", 4, 0, a)
    led.record_device_planes("0xabc", 4, 0, b)
    assert led.coverage()["0xabc"]["instructions_seen"] == 2


def test_aggregate_coverage_weighted_by_size():
    led = _ledger()
    led.record_instr("0xbig", 100, range(50))  # 50%
    led.record_instr("0xsmall", 10, range(10))  # 100%
    # (50 + 10) / (100 + 10)
    assert led.coverage_pct() == pytest.approx(54.55, abs=0.01)


def test_record_instr_oob_counts_overflow_not_clamp():
    led = _ledger()
    led.record_instr("0xabc", 4, [0, 3, 4, 99])
    cov = led.coverage()["0xabc"]
    assert cov["instructions_seen"] == 2, "OOB indices must not mark"
    assert led.pc_overflow == 2
    assert led.meta()["pc_overflow"] == 2


def test_coverage_gauge_published_per_codehash():
    reg = MetricsRegistry()
    led = ExplorationLedger(registry=reg)
    led.record_instr("0x" + "ab" * 20, 4, [0, 1])
    value = reg.gauge("exploration.coverage_pct", default={}).snapshot()
    assert value == {("0x" + "ab" * 20)[:10]: 50.0}


def test_snapshot_bitmaps_are_index_lists():
    led = _ledger()
    planes = np.zeros((3, 6), bool)
    planes[0, [1, 4]] = True
    planes[1, 4] = True
    led.record_device_planes("0xabc", 6, 1, planes)
    snap = led.snapshot()
    assert snap["bitmaps"]["0xabc"]["instr"] == [1, 4]
    assert snap["bitmaps"]["0xabc"]["edge_taken"] == [4]
    assert snap["bitmaps"]["0xabc"]["edge_fall"] == []


def test_reset_scope_clears_bitmaps_only():
    led = _ledger()
    led.record_instr("0xabc", 4, [0])
    led.stamp("completed")
    led.reset_scope()
    assert led.coverage() == {}
    # registry counters are swept by reset_analysis_metrics, not here
    assert led.terminated_total() == 1


# ---------------------------------------------------------------------------
# solver hotspots
# ---------------------------------------------------------------------------


def test_solver_hotspots_ranked_by_time():
    led = _ledger()
    led.record_solver_time("0xaaaa:0x14", 0.5)
    led.record_solver_time("0xaaaa:0x14", 0.25)
    led.record_solver_time("0xbbbb:0x20", 0.1)
    top = led.solver_hotspots(top=2)
    assert top[0]["point"] == "0xaaaa:0x14"
    assert top[0]["solver_s"] == 0.75
    assert top[0]["queries"] == 2
    assert top[1]["point"] == "0xbbbb:0x20"


def test_solver_hotspot_cardinality_cap():
    led = _ledger()
    for i in range(_MAX_HOTSPOT_LABELS + 10):
        led.record_solver_time(f"0xc:{i:#x}", 0.001)
    secs = led._reg().labeled_counter(
        "exploration.solver_hotspot_s", label_name="point"
    )
    assert len(secs) <= _MAX_HOTSPOT_LABELS + 1  # distinct labels + "other"
    assert "other" in secs


# ---------------------------------------------------------------------------
# process singleton + meta shape
# ---------------------------------------------------------------------------


def test_exploration_meta_shape():
    from mythril_tpu.observability import exploration_meta

    assert get_exploration_ledger() is get_exploration_ledger()
    meta = exploration_meta()
    assert set(meta) == {
        "coverage_pct", "coverage_pct_raw", "coverage_pct_reachable",
        "coverage", "terminated", "terminated_total",
        "partition_ok", "solver_hotspots", "pc_overflow",
    }
    assert set(meta["terminated"]) == set(TERM_CLASSES)


# ---------------------------------------------------------------------------
# coverage-plugin pc clamp regression (the OOB pc used to be clamped onto
# the LAST instruction, silently inflating its coverage)
# ---------------------------------------------------------------------------


class _StubVM:
    def __init__(self):
        self.hooks = {}

    def register_laser_hooks(self, kind, hook):
        self.hooks[kind] = hook


class _StubCode:
    def __init__(self, n):
        self.bytecode = bytes(range(n))
        self.instruction_list = [object()] * n


class _StubState:
    def __init__(self, code, pc):
        import types

        self.environment = types.SimpleNamespace(code=code)
        self.mstate = types.SimpleNamespace(pc=pc)


def _fresh_scoped_registry():
    from mythril_tpu.observability.metrics import get_registry

    get_registry().reset(prefix="exploration.")
    return get_registry()


def test_plugin_oob_pc_counts_overflow_instead_of_clamping():
    from mythril_tpu.plugins.plugins.coverage import InstructionCoverage

    reg = _fresh_scoped_registry()
    plugin = InstructionCoverage()
    vm = _StubVM()
    plugin.initialize(vm)
    code = _StubCode(4)
    vm.hooks["execute_state"](_StubState(code, 1))
    vm.hooks["execute_state"](_StubState(code, 9))  # OOB: off the end
    seen = plugin.coverage[code.bytecode.hex()][1]
    assert seen[1] and not seen[3], "OOB pc must not mark the last instr"
    assert reg.counter("exploration.pc_overflow").value == 1


def test_record_visited_oob_counts_overflow():
    from mythril_tpu.plugins.plugins.coverage import InstructionCoverage

    reg = _fresh_scoped_registry()
    plugin = InstructionCoverage()
    plugin.record_visited("aabb", 4, [0, 2, 7, 8])
    assert plugin.coverage["aabb"][1] == [True, False, True, False]
    assert reg.counter("exploration.pc_overflow").value == 2


def test_coverage_strategy_oob_state_is_not_covered():
    from mythril_tpu.plugins.plugins.coverage import (
        CoverageStrategy,
        InstructionCoverage,
    )

    plugin = InstructionCoverage()
    code = _StubCode(4)
    plugin.coverage[code.bytecode.hex()] = (4, [True, True, True, True])
    strategy = CoverageStrategy.__new__(CoverageStrategy)
    strategy.coverage_plugin = plugin
    assert strategy._is_covered(_StubState(code, 2))
    assert not strategy._is_covered(_StubState(code, 9)), (
        "an OOB pc never executed, so it must not read as covered"
    )


def test_stop_hook_publishes_coverage_gauge():
    from mythril_tpu.plugins.plugins.coverage import InstructionCoverage
    from mythril_tpu.support.support_utils import get_code_hash

    reg = _fresh_scoped_registry()
    get_exploration_ledger().reset_scope()
    plugin = InstructionCoverage()
    vm = _StubVM()
    plugin.initialize(vm)
    code = _StubCode(4)
    vm.hooks["execute_state"](_StubState(code, 0))
    vm.hooks["execute_state"](_StubState(code, 2))
    vm.hooks["stop_sym_exec"]()
    gauge = reg.gauge("exploration.coverage_pct", default={}).snapshot()
    key = get_code_hash(code.bytecode.hex())[:10]
    assert gauge.get(key) == 50.0


# ---------------------------------------------------------------------------
# WorkerContext.exploration_delta (service accounting seam)
# ---------------------------------------------------------------------------


def test_exploration_delta_measures_scope():
    from mythril_tpu.facade.warm import WorkerContext
    from mythril_tpu.observability.metrics import get_registry

    get_registry().reset(prefix="exploration.")
    led = get_exploration_ledger()
    led.reset_scope()
    led.stamp("completed", 5)  # pre-existing: must not land in the delta
    ctx = WorkerContext.__new__(WorkerContext)
    out = {}
    with ctx.exploration_delta(out):
        led.stamp("completed", 2)
        led.stamp("loop_bound")
        led.record_instr("0xddd", 10, range(4))
        led.record_pc_overflow(3)
    assert out["terminated"] == {"completed": 2, "loop_bound": 1}
    assert out["terminated_total"] == 3
    assert out["pc_overflow"] == 3
    assert out["coverage_pct"]["0xddd"] == 40.0
