"""Device telemetry plane: compile-event attribution, churn, degradation."""

import time

import pytest

from mythril_tpu.observability import deviceplane as dp
from mythril_tpu.observability.metrics import get_registry


@pytest.fixture(autouse=True)
def _fresh_attribution():
    dp.reset_for_tests()
    yield
    dp.reset_for_tests()


def _counter(name):
    return get_registry().counter(name, persistent=True).value or 0


def _labeled(name):
    m = get_registry()._metrics.get(name)
    return dict(m) if isinstance(m, dict) else {}


def test_bucket_tag_and_scope_nesting():
    assert dp.bucket_tag((1, 2, 3, 4)) == "1x2x3x4"
    assert dp.current_bucket() is None
    with dp.dispatch_scope((1, 2, 3, 4)):
        assert dp.current_bucket() == "1x2x3x4"
        with dp.dispatch_scope("8x16x4x2"):  # pre-formatted tags pass through
            assert dp.current_bucket() == "8x16x4x2"
        assert dp.current_bucket() == "1x2x3x4"
    assert dp.current_bucket() is None


def test_compile_event_attributed_to_dispatching_bucket():
    before = _counter("device.compile_wall_s_total")
    by_bucket = dict(_labeled("device.compile_wall_s_by_bucket"))
    with dp.dispatch_scope((4, 8, 2, 1)):
        dp._on_duration(dp._EV_BACKEND_COMPILE, 0.25)
    assert _counter("device.compile_wall_s_total") == pytest.approx(
        before + 0.25)
    after = _labeled("device.compile_wall_s_by_bucket")
    assert after.get("4x8x2x1", 0) == pytest.approx(
        by_bucket.get("4x8x2x1", 0) + 0.25)


def test_compile_event_is_a_span_under_tracing():
    """With the tracer on, a backend compile is an ``xla.compile`` span
    that ends when the listener fires, tagged with bucket and function."""
    from mythril_tpu.observability import get_tracer

    tracer = get_tracer()
    tracer.reset()
    tracer.enabled = True
    try:
        before = time.time_ns()
        with dp.dispatch_scope((3, 3, 3, 3)):
            dp._on_duration(dp._EV_BACKEND_COMPILE, 0.5, fun_name="segment")
        after = time.time_ns()
        (span,) = [s for s in tracer.spans() if s["name"] == "xla.compile"]
        end = tracer.epoch_ns(span["ts"] + span["dur"])
    finally:
        tracer.enabled = False
        tracer.reset()
    assert span["dur"] == 0.5
    assert span["args"] == {"bucket": "3x3x3x3", "fun": "segment"}
    assert before - 1e6 <= end <= after + 1e6


def test_recompile_counted_per_session_not_per_event():
    """One dispatch emits a BURST of backend-compile events (the segment
    plus jax's auxiliary executables); a recompile is a burst for a known
    shape in a LATER dispatch session."""
    rcmp0 = _counter("device.recompiles_total")
    churn0 = _counter("device.shape_churn_total")
    shapes0 = _counter("device.shapes_compiled_total")

    with dp.dispatch_scope("9x9x9x9"):
        dp._on_duration(dp._EV_BACKEND_COMPILE, 0.1)
        dp._on_duration(dp._EV_BACKEND_COMPILE, 0.1)  # same-session burst
    assert _counter("device.recompiles_total") == rcmp0
    assert _counter("device.shapes_compiled_total") == shapes0 + 1

    # a SECOND distinct shape is churn, not a recompile
    with dp.dispatch_scope("7x7x7x7"):
        dp._on_duration(dp._EV_BACKEND_COMPILE, 0.1)
    assert _counter("device.shape_churn_total") == churn0 + 1
    assert _counter("device.recompiles_total") == rcmp0

    # the FIRST shape compiling again in a later session is a recompile,
    # counted once however many events the burst carries
    with dp.dispatch_scope("9x9x9x9"):
        dp._on_duration(dp._EV_BACKEND_COMPILE, 0.1)
        dp._on_duration(dp._EV_BACKEND_COMPILE, 0.1)
    assert _counter("device.recompiles_total") == rcmp0 + 1
    assert _labeled("device.recompiles_by_bucket").get("9x9x9x9", 0) >= 1


def test_cache_events_attributed():
    hits0 = _counter("device.cache_hits")
    with dp.dispatch_scope("2x2x2x2"):
        dp._on_event(dp._EV_CACHE_HIT)
        dp._on_event(dp._EV_CACHE_MISS)
    assert _counter("device.cache_hits") == hits0 + 1
    assert _labeled("device.cache_hits_by_bucket").get("2x2x2x2", 0) >= 1
    assert _labeled("device.cache_misses_by_bucket").get("2x2x2x2", 0) >= 1


def test_unscoped_compile_lands_in_untagged():
    dp._on_duration(dp._EV_BACKEND_COMPILE, 0.05)
    assert _labeled("device.compile_wall_s_by_bucket").get("untagged", 0) > 0


def test_real_jit_dispatch_fires_listener():
    """End to end: a genuinely fresh jit under a dispatch scope must grow
    the compile wall and label it with the scope's bucket."""
    jax = pytest.importorskip("jax")
    import jax.numpy as jnp

    assert dp.install()
    before = _counter("device.compile_wall_s_total")
    tagged_before = _labeled("device.compile_wall_s_by_bucket").get(
        "3x1x4x1", 0)
    # a unique constant guarantees a cache-missing program
    salt = time.time_ns() % 100003

    @jax.jit
    def fresh(x):
        return x * 2 + salt

    with dp.dispatch_scope((3, 1, 4, 1)):
        fresh(jnp.arange(8)).block_until_ready()
    assert _counter("device.compile_wall_s_total") > before
    assert _labeled("device.compile_wall_s_by_bucket").get(
        "3x1x4x1", 0) > tagged_before


def test_observe_segment_emits_labeled_series():
    from mythril_tpu.observability.metrics import prometheus_text

    count0 = _labeled("frontier.segment_device_s_count").get("5x5x5x5", 0)
    dp.observe_segment(0.25, "5x5x5x5")
    dp.observe_segment(0.75, "5x5x5x5")
    assert _labeled("frontier.segment_device_s_count").get(
        "5x5x5x5") == count0 + 2
    assert _labeled("frontier.segment_device_s_sum").get(
        "5x5x5x5", 0) >= 1.0
    text = prometheus_text()
    assert 'frontier_segment_device_s_sum{bucket="5x5x5x5"}' in text


def test_install_env_gate(monkeypatch):
    monkeypatch.setattr(dp, "_installed", False)
    monkeypatch.setenv("MYTHRIL_DEVICEPLANE", "0")
    assert dp.install() is False
    assert dp.installed() is False


def test_device_meta_reads_registry():
    with dp.dispatch_scope("1x1x1x1"):
        dp._on_duration(dp._EV_BACKEND_COMPILE, 0.5)
    dp.observe_segment(2.0, "1x1x1x1")
    meta = dp.device_meta()
    assert meta["compile_wall_s"] > 0
    assert "1x1x1x1" in meta["compile_wall_s_by_bucket"]
    assert meta["segment_device_s"]["count"] >= 1
    assert isinstance(meta["overhead_pct"], float)
    assert meta["cache"].keys() == {"hits", "misses"}
    hb = dp.heartbeat_source()
    assert hb["heartbeat.device_compile_s"] == meta["compile_wall_s"]
