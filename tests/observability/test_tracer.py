"""Span tracer: nesting/ordering, Chrome-trace schema, exporters, overhead."""

import json
import threading

import pytest

from mythril_tpu.observability.tracer import Tracer, get_tracer, traced


@pytest.fixture
def tracer():
    t = Tracer(capacity=1000)
    t.enabled = True
    return t


def test_disabled_tracer_records_nothing():
    t = Tracer()
    with t.span("x", cat="test"):
        pass
    assert len(t) == 0


def test_span_nesting_and_ordering(tracer):
    with tracer.span("outer", cat="test"):
        with tracer.span("inner_a", cat="test"):
            pass
        with tracer.span("inner_b", cat="test"):
            pass

    spans = tracer.spans()
    # spans are recorded on exit: children close before the parent
    assert [s["name"] for s in spans] == ["inner_a", "inner_b", "outer"]
    by_name = {s["name"]: s for s in spans}
    outer, a, b = by_name["outer"], by_name["inner_a"], by_name["inner_b"]
    # containment: both children start and end inside the parent interval
    for child in (a, b):
        assert outer["ts"] <= child["ts"]
        assert child["ts"] + child["dur"] <= outer["ts"] + outer["dur"] + 1e-9
    # ordering: inner_a completed before inner_b started
    assert a["ts"] + a["dur"] <= b["ts"] + 1e-9


def test_span_args_and_set(tracer):
    with tracer.span("q", cat="test", n=3) as sp:
        sp.set(status="sat")
    (span,) = tracer.spans()
    assert span["args"] == {"n": 3, "status": "sat"}


def test_chrome_trace_schema(tracer, tmp_path):
    with tracer.span("parent", cat="test", k=1):
        with tracer.span("child", cat="test"):
            pass
    path = tmp_path / "trace.json"
    tracer.export_chrome_trace(str(path))

    doc = json.loads(path.read_text())
    # the trace_event JSON *object* format Perfetto/chrome://tracing load
    assert isinstance(doc["traceEvents"], list)
    meta = [ev for ev in doc["traceEvents"] if ev["ph"] == "M"]
    slices = [ev for ev in doc["traceEvents"] if ev["ph"] == "X"]
    assert len(slices) == 2
    # metadata names the process and every track that recorded anything
    assert any(
        ev["name"] == "process_name" and ev["args"]["name"] == "mythril-tpu"
        for ev in meta
    )
    named_tids = {ev["tid"] for ev in meta if ev["name"] == "thread_name"}
    for ev in slices:
        assert ev["tid"] in named_tids
        assert isinstance(ev["name"], str)
        assert isinstance(ev["cat"], str)
        # timestamps/durations in microseconds, non-negative
        assert isinstance(ev["ts"], (int, float)) and ev["ts"] >= 0
        assert isinstance(ev["dur"], (int, float)) and ev["dur"] >= 0
        assert isinstance(ev["pid"], int)
        assert isinstance(ev["tid"], int)


def test_jsonl_export(tracer, tmp_path):
    with tracer.span("one", cat="test"):
        pass
    tracer.instant("mark", cat="test")
    path = tmp_path / "trace.jsonl"
    tracer.export_jsonl(str(path))
    lines = [json.loads(line) for line in path.read_text().splitlines()]
    assert [rec["name"] for rec in lines] == ["one", "mark"]
    assert lines[1]["dur"] == 0.0


def test_ring_buffer_bounded_and_counts_drops():
    t = Tracer(capacity=10)
    t.enabled = True
    for i in range(25):
        with t.span(f"s{i}", cat="test"):
            pass
    assert len(t) == 10
    assert t.dropped == 15
    assert t.chrome_trace()["otherData"]["dropped_spans"] == 15
    # the newest spans survive
    assert t.spans()[-1]["name"] == "s24"


def test_thread_safety_all_spans_recorded():
    t = Tracer(capacity=10_000)
    t.enabled = True

    def worker(tid):
        for i in range(100):
            with t.span(f"w{tid}", cat="test"):
                pass

    threads = [threading.Thread(target=worker, args=(k,)) for k in range(8)]
    for th in threads:
        th.start()
    for th in threads:
        th.join()
    assert len(t) == 800
    # every span carries its recording thread's ident (idents may be
    # reused once a thread exits, so only presence is asserted)
    assert all(s["tid"] for s in t.spans())


def test_traced_decorator():
    t = get_tracer()
    t.reset()
    t.enabled = True
    try:
        @traced("deco.fn", cat="test")
        def fn(x):
            return x * 2

        assert fn(21) == 42
        assert [s["name"] for s in t.spans()] == ["deco.fn"]
    finally:
        t.enabled = False
        t.reset()


def test_reset_clears_and_rebases_origin(tracer):
    with tracer.span("a", cat="test"):
        pass
    tracer.reset()
    assert len(tracer) == 0
    with tracer.span("b", cat="test"):
        pass
    (span,) = tracer.spans()
    # origin was rebased: the new span starts near zero
    assert span["ts"] < 60.0


# -- flight-deck additions: flows, counters, named tracks, drop marker ------


def test_flow_events_link_dispatch_to_harvest(tracer):
    fid = tracer.new_flow_id()
    with tracer.span("dispatch", cat="device"):
        tracer.flow("s", fid, "flow.segment", cat="device")
    with tracer.span("pull", cat="device"):
        tracer.flow("t", fid, "flow.segment", cat="device")
    with tracer.span("harvest", cat="frontier"):
        tracer.flow("f", fid, "flow.segment", cat="device")

    doc = tracer.chrome_trace()
    flows = [ev for ev in doc["traceEvents"] if ev["ph"] in ("s", "t", "f")]
    assert [ev["ph"] for ev in flows] == ["s", "t", "f"]
    # all three endpoints share the id and arrive in wall-clock order
    assert {ev["id"] for ev in flows} == {fid}
    assert flows[0]["ts"] <= flows[1]["ts"] <= flows[2]["ts"]
    # the terminator binds to its ENCLOSING slice, not the next one
    assert flows[2]["bp"] == "e"


def test_new_flow_ids_are_unique(tracer):
    ids = [tracer.new_flow_id() for _ in range(100)]
    assert len(set(ids)) == 100


def test_counter_events_on_registered_track(tracer):
    tid = tracer.register_track("heartbeat")
    assert tid >= 1_000_000_000  # never collides with an OS thread ident
    tracer.counter(
        "pipeline.pool_queue_depth", {"value": 3}, tid=tid
    )
    doc = tracer.chrome_trace()
    (c,) = [ev for ev in doc["traceEvents"] if ev["ph"] == "C"]
    assert c["tid"] == tid
    assert c["args"] == {"value": 3}
    # the synthetic track is named via thread_name metadata
    assert any(
        ev["ph"] == "M"
        and ev["name"] == "thread_name"
        and ev["tid"] == tid
        and ev["args"]["name"] == "heartbeat"
        for ev in doc["traceEvents"]
    )


def test_thread_name_captured_lazily():
    t = Tracer(capacity=100)
    t.enabled = True

    def work():
        with t.span("named", cat="test"):
            pass

    th = threading.Thread(target=work, name="mythril-feas-0")
    th.start()
    th.join()
    assert "mythril-feas-0" in t.thread_names().values()
    doc = t.chrome_trace()
    assert any(
        ev["ph"] == "M" and ev["args"]["name"] == "mythril-feas-0"
        for ev in doc["traceEvents"]
    )


def test_dropped_marker_instant_visible_only_when_truncated():
    t = Tracer(capacity=5)
    t.enabled = True
    for i in range(3):
        with t.span(f"s{i}", cat="test"):
            pass
    doc = t.chrome_trace()
    assert not [e for e in doc["traceEvents"] if e["name"].startswith("tracer.dropped")]

    for i in range(10):
        with t.span(f"t{i}", cat="test"):
            pass
    doc = t.chrome_trace()
    (marker,) = [
        e for e in doc["traceEvents"] if e["name"].startswith("tracer.dropped")
    ]
    assert marker["ph"] == "i" and marker["s"] == "g"  # full-height line
    assert marker["args"]["dropped_spans"] == t.dropped > 0
    # the marker sits at the end of the visible timeline
    assert marker["ts"] == max(
        e["ts"] for e in doc["traceEvents"] if "ts" in e
    )


# -- writer storms: _record and the readers must survive 8-way hammering ----

N_STORM_THREADS = 8
N_STORM_ITER = 500


def _storm(worker, n_threads=N_STORM_THREADS):
    barrier = threading.Barrier(n_threads)

    def run(k):
        barrier.wait()  # maximize interleaving
        worker(k)

    threads = [
        threading.Thread(target=run, args=(k,)) for k in range(n_threads)
    ]
    for th in threads:
        th.start()
    for th in threads:
        th.join()


def test_writer_storm_exact_counts_no_drops():
    t = Tracer(capacity=N_STORM_THREADS * N_STORM_ITER * 3)
    t.enabled = True

    def worker(k):
        for i in range(N_STORM_ITER):
            with t.span(f"w{k}", cat="test", i=i):
                pass
            fid = t.new_flow_id()
            t.flow("s", fid, "flow.storm", cat="test")
            t.flow("f", fid, "flow.storm", cat="test")

    _storm(worker)
    assert len(t) == N_STORM_THREADS * N_STORM_ITER * 3
    assert t.dropped == 0
    # every flow id saw exactly one s and one f
    flows = [s for s in t.spans() if s.get("ph") in ("s", "f")]
    by_id = {}
    for s in flows:
        by_id.setdefault(s["flow_id"], []).append(s["ph"])
    assert all(sorted(phs) == ["f", "s"] for phs in by_id.values())


def test_writer_storm_eviction_accounting_is_exact():
    cap = 64
    t = Tracer(capacity=cap)
    t.enabled = True

    def worker(k):
        for i in range(N_STORM_ITER):
            with t.span(f"w{k}", cat="test"):
                pass

    _storm(worker)
    total = N_STORM_THREADS * N_STORM_ITER
    assert len(t) == cap
    assert t.dropped == total - cap
    assert t.chrome_trace()["otherData"]["dropped_spans"] == total - cap


def test_writer_storm_with_concurrent_readers():
    """summary()/spans()/chrome_trace() race 8 writers without corruption."""
    t = Tracer(capacity=4096)
    t.enabled = True
    stop = threading.Event()
    reader_errors = []

    def read_loop():
        try:
            while not stop.is_set():
                s = t.summary()
                assert 0 <= s["spans"] <= t.capacity
                for rec in t.spans():
                    assert isinstance(rec["name"], str)
                json.dumps(t.chrome_trace())  # full export must serialize
        except Exception as exc:  # pragma: no cover - failure path
            reader_errors.append(exc)

    readers = [threading.Thread(target=read_loop) for _ in range(2)]
    for r in readers:
        r.start()

    def worker(k):
        for i in range(N_STORM_ITER):
            with t.span(f"w{k}", cat="test"):
                pass
            t.counter(f"c{k}", {"value": i})

    try:
        _storm(worker)
    finally:
        stop.set()
        for r in readers:
            r.join()
    assert not reader_errors
    assert t.summary()["spans"] + t.dropped == N_STORM_THREADS * N_STORM_ITER * 2


# -- one clock with the device trace ----------------------------------------


def _profile_start_time(trace_dir):
    """``profile_start_time`` (epoch ns) from the ``Task Environment``
    plane of the newest ``.xplane.pb`` under ``trace_dir``, and the
    events of its other planes by name."""
    import glob
    import os

    from jax.profiler import ProfileData

    path = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                            recursive=True), key=os.path.getmtime)[-1]
    start, events = None, {}
    for plane in ProfileData.from_file(path).planes:
        if plane.name == "Task Environment":
            start = dict(plane.stats)["profile_start_time"]
        for line in plane.lines:
            for ev in line.events:
                events.setdefault(ev.name, []).append(ev.start_ns)
    return start, events


def test_spans_on_clock_line_up_with_the_profiler(tracer, tmp_path):
    """A span entered with a ``TraceAnnotation`` lands, mapped onto the
    profiler's ``profile_start_time``, within 1 ms of the annotation."""
    import time

    jax = pytest.importorskip("jax")

    opts = jax.profiler.ProfileOptions()
    opts.host_tracer_level = 1
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    try:
        time.sleep(0.05)
        with tracer.span("clock.probe", cat="test"), \
                jax.profiler.TraceAnnotation("clock.probe.annotation"):
            time.sleep(0.01)
    finally:
        jax.profiler.stop_trace()
    start, events = _profile_start_time(str(tmp_path))
    assert start is not None
    (annotated,) = events["clock.probe.annotation"]
    (span,) = [s for s in tracer.spans_on_clock(start) if s["name"] == "clock.probe"]
    assert abs(span["start_ns"] - annotated) < 1e6
    assert span["duration_ns"] >= 10e6


@pytest.mark.parametrize("export", ["spans_on_clock", "chrome_trace"])
def test_exports_map_ts_zero_onto_the_epoch_clock(tracer, export):
    """Both exports place the tracer's origin on the epoch clock; the
    anchor is taken again at each, and stays within 1 ms of the truth."""
    import time

    with tracer.span("epoch.probe", cat="test"):
        pass
    before = time.time_ns()
    if export == "chrome_trace":
        clock = tracer.chrome_trace()["otherData"]["clock"]
        origin = clock["origin_epoch_ns"]
        assert clock["anchor_epoch_ns"] >= before
    else:
        (span,) = tracer.spans_on_clock(0)
        origin = span["start_ns"] - round(span["ts"] * 1e9)
    assert origin == pytest.approx(tracer.epoch_ns(0.0), abs=1e6)
    assert origin <= before
