"""The trace reduction, on a small recorded trace in the reduced form
``benchmark.trace.reduce.load`` produces."""

import math

import pytest

from benchmark.counters import MissingCounter
from benchmark.harness import Run
from benchmark.metrics import load_reader
from benchmark.trace.reduce import module_name, reduce, union

MS = 1e6  # ns
# one chip, a 100 ms window: two segment programs, a pack, overlapping ops
TRACE = {
    "devices": [{
        "plane": "/device:TPU:0",
        "modules": [("jit_segment(41)", 10 * MS, 20 * MS),
                    ("jit_pack_meta(7)", 35 * MS, 5 * MS),
                    ("jit_segment(41)", 60 * MS, 20 * MS),
                    ("jit_segment(41)", 95 * MS, 10 * MS)],  # runs past the close
        "ops": [("fusion.1", 10 * MS, 15 * MS), ("fusion.2", 20 * MS, 10 * MS),
                ("copy.3", 35 * MS, 5 * MS), ("while.4", 60 * MS, 20 * MS),
                ("while.4", 95 * MS, 10 * MS)],
    }],
    "host": [("bench.analyze", 0.0, 100 * MS),
             ("frontier.harvest", 40 * MS, 20 * MS),
             ("backend_compile", 81 * MS, 10 * MS)],
}


def test_union_merges_overlaps():
    assert union([(0, 5), (3, 8), (10, 12)]) == [(0, 8), (10, 12)]


def test_busy_idle_and_program_time():
    r = reduce(TRACE, 100 * MS)
    # busy: [10,30] + [35,40] + [60,80] + [95,100] = 50 ms
    assert math.isclose(r["busy_s"], 0.050)
    assert math.isclose(r["window_s"], 0.100)
    # segment modules clipped to the window: 20 + 20 + 5 ms
    assert math.isclose(r["program_s"], 0.045)
    assert r["device_ops"][0][0] == "jit_segment"
    assert module_name("jit_segment(41)") == "jit_segment"


def test_idle_gaps_are_named_after_the_host_span_open():
    gaps = reduce(TRACE, 100 * MS)["idle_gaps"]
    assert [round(g[1], 6) for g in gaps] == [0.02, 0.015, 0.01, 0.005]
    names = dict((round(s, 6), n) for n, s in gaps)
    assert names[0.02] == "frontier.harvest"      # 40-60 ms
    assert names[0.015] == "backend_compile"      # 80-95 ms
    assert names[0.01] == "bench.analyze"         # 0-10 ms


def test_readers_on_the_reduced_trace():
    r = reduce(TRACE, 100 * MS)
    run = Run([], 0.0, 0.1, 0.1, 1.0, {"frontier.device_instructions": 4500}, r)
    assert math.isclose(load_reader("device.idle_pct")(run), 50.0)
    assert math.isclose(load_reader("kernel.segment_ns_per_instr")(run), 1e4)


def test_a_trace_without_a_device_or_a_segment_reads_nothing():
    assert reduce({"devices": [], "host": []}, 1.0) is None
    r = reduce({"devices": [{"plane": "/device:TPU:0", "ops": [("x", 0, 5)],
                             "modules": [("jit_other(1)", 0, 5)]}]}, 10.0)
    run = Run([], 0.0, 1.0, 1.0, 1.0, {"frontier.device_instructions": 100}, r)
    assert load_reader("kernel.segment_ns_per_instr")(run) is None
    assert load_reader("device.idle_pct")(Run([], 0, 1, 1, 1, {})) is None


def test_a_counter_the_program_never_registered_fails_the_reader():
    r = reduce(TRACE, 100 * MS)
    idle = Run([], 0.0, 0.1, 0.1, 1.0, {"frontier.device_instructions": 0}, r)
    assert load_reader("kernel.segment_ns_per_instr")(idle) is None
    with pytest.raises(MissingCounter):
        load_reader("kernel.segment_ns_per_instr")(Run([], 0.0, 0.1, 0.1, 1.0, {}, r))
