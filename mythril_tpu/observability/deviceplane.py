"""Device telemetry plane: see inside XLA from the host-side registry.

Every telemetry plane built so far (tracer, flight recorder, fleet,
watchtower, history) observes the *host* — the device frontier was a
black box: XLA compile wall, per-bucket segment cost, HBM footprint and
recompile churn were invisible, so a perf drift diagnosis meant a human
eyeballing two result files.  This module turns the device side
into ordinary registry metrics:

* ``install()`` registers ``jax.monitoring`` listeners.  JAX emits
  duration events around tracing/lowering/backend-compile
  (``/jax/core/compile/*_duration``) and plain events for persistent
  compilation-cache hits/misses — the listeners fold them into
  ``device.*`` counters/histograms, attributed to the **dispatching
  bucket shape** via a thread-local dispatch scope (compile happens on
  the thread that dispatches, including the floored-bucket precompile
  daemon thread).
* ``dispatch_scope(bucket)`` tags the calling thread with the bucket
  shape ``(code_cap, instr_cap, addr_cap, loops_cap)`` so compile
  events, device-wall stamps and pull stamps land in per-bucket series.
* ``observe_segment(seconds)`` / ``observe_pull(seconds)`` stamp the
  device-visible wall around the frontier's existing blocking points
  (engine sync loop, pipeline bubble, packed harvest pull) into
  ``frontier.segment_device_s`` / ``frontier.pull_device_s`` histograms
  plus per-bucket ``..._sum{bucket=…}`` / ``..._count{bucket=…}``
  labeled series (the registry has no labeled-histogram kind; a
  sum/count pair per label is the standard Prometheus degradation).
* With the tracer on, every backend compile is also recorded as an
  ``xla.compile`` span (ending when the listener fires, tagged with its
  bucket and the jitted function's name), so a ``--trace-out`` timeline
  shows where compiles sat and what they compiled.

All ``device.*`` metrics are ``persistent=True`` (process-scoped, like
``compilecache.*``): compile/recompile history must survive the
per-analysis registry sweep, and consumers (the benchmark, fleet deltas) read
them as before/after deltas.  Because they are ordinary registry
metrics, the PR-13 fleet fabric ships them per worker with no extra
wiring — pooled runs get ``fleet_device_*{worker=…}`` series for free.

Overhead: the listeners fire per *compile* (rare) and the stamps cost
two counter increments plus one histogram observe per *segment*
(segments are 0.1–10 s).  Time spent inside the plane is self-measured
into ``device.plane_overhead_s`` so ``device_meta()`` can report
overhead as a fraction of the observed segment wall.
"""

from __future__ import annotations

import os
import threading
import time
from contextlib import contextmanager
from typing import Any, Dict, Iterator, Optional, Sequence

from mythril_tpu.observability.metrics import get_registry
from mythril_tpu.observability.tracer import get_tracer

__all__ = [
    "bucket_tag",
    "current_bucket",
    "device_meta",
    "dispatch_scope",
    "heartbeat_source",
    "install",
    "install_deviceplane",
    "installed",
    "observe_pull",
    "observe_segment",
    "reset_for_tests",
]

# JAX-emitted monitoring event names (jax._src.dispatch / compiler).
_EV_BACKEND_COMPILE = "/jax/core/compile/backend_compile_duration"
_EV_TRACE = "/jax/core/compile/jaxpr_trace_duration"
_EV_LOWER = "/jax/core/compile/jaxpr_to_mlir_module_duration"
_EV_CACHE_HIT = "/jax/compilation_cache/cache_hits"
_EV_CACHE_MISS = "/jax/compilation_cache/cache_misses"

_UNTAGGED = "untagged"

_install_lock = threading.Lock()
_installed = False

# dispatch attribution is per-thread: XLA compiles on the thread that
# dispatches (the engine main thread, or the floored-bucket precompile
# daemon thread), so a thread-local scope is exact, not heuristic
_ctx = threading.local()

# process-scoped attribution state (guarded by _install_lock):
# tag -> dispatch-scope session id of its last compile burst.  One
# dispatch (scope entry) triggers SEVERAL backend-compile events — the
# segment program plus jax's auxiliary executables — so recompiles are
# counted per *session*, not per event: a compile burst for an
# already-compiled tag in a LATER session means XLA compiled again for
# a program we thought was warm.
_compiled_tags: Dict[str, int] = {}
_session_seq = [0]


def bucket_tag(bucket: Sequence[int]) -> str:
    """Canonical label for a size bucket: ``"CCxICxACxLC"``."""
    return "x".join(str(int(b)) for b in bucket)


def current_bucket() -> Optional[str]:
    """Bucket tag of the innermost active dispatch scope, if any."""
    return getattr(_ctx, "bucket", None)


@contextmanager
def dispatch_scope(bucket) -> Iterator[None]:
    """Tag the calling thread with the dispatching bucket shape.

    ``bucket`` is either the 4-tuple ``(code_cap, instr_cap, addr_cap,
    loops_cap)`` or an already-formatted tag string.  Scopes nest; the
    innermost wins (the opening natural-bucket dispatch nests inside the
    floored run's scope).
    """
    tag = bucket if isinstance(bucket, str) else bucket_tag(bucket)
    prev = getattr(_ctx, "bucket", None)
    prev_session = getattr(_ctx, "session", 0)
    with _install_lock:
        _session_seq[0] += 1
        _ctx.session = _session_seq[0]
    _ctx.bucket = tag
    try:
        yield
    finally:
        _ctx.bucket = prev
        _ctx.session = prev_session


def _overhead(t0: float) -> None:
    get_registry().counter("device.plane_overhead_s", persistent=True,
                           initial=0.0).inc(time.perf_counter() - t0)


# -- jax.monitoring listeners ---------------------------------------------


def _on_duration(event: str, duration_secs: float, **kwargs) -> None:
    if not event.startswith("/jax/core/compile/"):
        return
    t0 = time.perf_counter()
    reg = get_registry()
    tag = current_bucket() or _UNTAGGED
    if event == _EV_BACKEND_COMPILE:
        get_tracer().record_span(
            "xla.compile", "device", t0 - duration_secs, duration_secs,
            args={"bucket": tag, "fun": kwargs.get("fun_name")})
        reg.observe("device.compile_wall_s", duration_secs)
        reg.counter("device.compile_wall_s_total", persistent=True,
                    initial=0.0).inc(duration_secs)
        reg.labeled_counter("device.compile_wall_s_by_bucket",
                            persistent=True,
                            label_name="bucket").inc(tag, duration_secs)
        session = getattr(_ctx, "session", 0)
        with _install_lock:
            prev_session = _compiled_tags.get(tag)
            _compiled_tags[tag] = session
            n_shapes = len(_compiled_tags)
        if prev_session is None:
            reg.counter("device.shapes_compiled_total",
                        persistent=True).inc()
            if n_shapes > 1:
                # every distinct shape beyond the first is churn: a
                # stream of fresh shapes (bucket floor misconfigured,
                # tables not stacking) shows up as a churn ramp the
                # watchtower can alarm on
                reg.counter("device.shape_churn_total",
                            persistent=True).inc()
        elif prev_session != session:
            # same shape compiling again in a later dispatch: XLA threw
            # away (or never kept) an executable we already paid for.
            # Counted once per dispatch session, not per event burst.
            reg.counter("device.recompiles_total", persistent=True).inc()
            reg.labeled_counter("device.recompiles_by_bucket",
                                persistent=True,
                                label_name="bucket").inc(tag)
    elif event == _EV_TRACE:
        reg.observe("device.trace_wall_s", duration_secs)
        reg.counter("device.trace_wall_s_total", persistent=True,
                    initial=0.0).inc(duration_secs)
    elif event == _EV_LOWER:
        reg.observe("device.lower_wall_s", duration_secs)
        reg.counter("device.lower_wall_s_total", persistent=True,
                    initial=0.0).inc(duration_secs)
    _overhead(t0)


def _on_event(event: str, **kwargs) -> None:
    if not event.startswith("/jax/compilation_cache/"):
        return
    reg = get_registry()
    tag = current_bucket() or _UNTAGGED
    if event == _EV_CACHE_HIT:
        reg.counter("device.cache_hits", persistent=True).inc()
        reg.labeled_counter("device.cache_hits_by_bucket", persistent=True,
                            label_name="bucket").inc(tag)
    elif event == _EV_CACHE_MISS:
        reg.counter("device.cache_misses", persistent=True).inc()
        reg.labeled_counter("device.cache_misses_by_bucket", persistent=True,
                            label_name="bucket").inc(tag)


def install() -> bool:
    """Register the monitoring listeners and heartbeat source (idempotent).

    Returns True when the plane is active.  Safe without jax (the plane
    simply stays disabled) and safe to call from every dispatch path —
    the first caller wins, the rest are no-ops.
    """
    global _installed
    if _installed:
        return True
    if os.environ.get("MYTHRIL_DEVICEPLANE", "1") in ("0", "false", "off"):
        return False
    with _install_lock:
        if _installed:
            return True
        try:
            from jax import monitoring as _mon
        except Exception:  # pragma: no cover - jax is baked into the image
            return False
        _mon.register_event_duration_secs_listener(_on_duration)
        _mon.register_event_listener(_on_event)
        try:
            from mythril_tpu.observability.heartbeat import get_heartbeat
            get_heartbeat().register("device", heartbeat_source)
        except Exception:  # pragma: no cover - heartbeat optional
            pass
        _installed = True
    return True


def installed() -> bool:
    return _installed


# package-level re-export name ("install" is too generic outside the
# deviceplane namespace)
install_deviceplane = install


# -- device-wall stamps ----------------------------------------------------


def _stamp(base: str, seconds: float, tag: Optional[str]) -> None:
    t0 = time.perf_counter()
    reg = get_registry()
    tag = tag or current_bucket() or _UNTAGGED
    reg.observe(base, float(seconds))
    # no labeled-histogram kind exists; a per-bucket sum/count pair is
    # the standard exposition (avg-by-bucket in one PromQL division)
    reg.labeled_counter(base + "_sum", persistent=True,
                        label_name="bucket").inc(tag, float(seconds))
    reg.labeled_counter(base + "_count", persistent=True,
                        label_name="bucket").inc(tag)
    _overhead(t0)


def observe_segment(seconds: float, tag: Optional[str] = None) -> None:
    """Record one segment's device-visible wall (dispatch + host wait)."""
    _stamp("frontier.segment_device_s", seconds, tag)


def observe_pull(seconds: float, tag: Optional[str] = None) -> None:
    """Record one blocking device->host harvest pull."""
    _stamp("frontier.pull_device_s", seconds, tag)


# -- surfaces --------------------------------------------------------------


def _counter_value(reg, name: str) -> float:
    m = reg._metrics.get(name)
    return m.value if m is not None and hasattr(m, "value") else 0


def _labeled_dict(reg, name: str) -> Dict[str, Any]:
    m = reg._metrics.get(name)
    return dict(m) if m is not None and isinstance(m, dict) else {}


def device_meta() -> Dict[str, Any]:
    """The ``meta.device`` block for jsonv2 reports / daemon stats.

    Pure read of the registry — safe without install() (everything
    reads zero/absent) and cheap enough for every report.
    """
    reg = get_registry()
    out: Dict[str, Any] = {"enabled": _installed}
    try:
        import jax
        out["backend"] = jax.default_backend()
    except Exception:  # pragma: no cover
        out["backend"] = None
    out["compile_wall_s"] = round(
        float(_counter_value(reg, "device.compile_wall_s_total")), 3)
    hist = reg._metrics.get("device.compile_wall_s")
    out["compiles"] = getattr(hist, "count", 0)
    out["recompiles"] = int(_counter_value(reg, "device.recompiles_total"))
    out["shape_churn"] = int(_counter_value(reg, "device.shape_churn_total"))
    out["cache"] = {
        "hits": int(_counter_value(reg, "device.cache_hits")),
        "misses": int(_counter_value(reg, "device.cache_misses")),
    }
    by_bucket = _labeled_dict(reg, "device.compile_wall_s_by_bucket")
    out["compile_wall_s_by_bucket"] = {
        k: round(float(v), 3) for k, v in sorted(by_bucket.items())
    }
    seg = reg._metrics.get("frontier.segment_device_s")
    if seg is not None and getattr(seg, "count", 0):
        out["segment_device_s"] = {
            "count": seg.count,
            "sum": round(seg.sum, 3),
            "p50": round(seg.percentile(0.5) or 0.0, 6),
            "p95": round(seg.percentile(0.95) or 0.0, 6),
        }
    overhead = float(_counter_value(reg, "device.plane_overhead_s"))
    wall = getattr(seg, "sum", 0.0) or 0.0
    out["overhead_pct"] = round(100.0 * overhead / wall, 4) if wall else 0.0
    return out


def heartbeat_source() -> Dict[str, Any]:
    """Heartbeat gauges: compile wall / recompiles / churn trajectory."""
    reg = get_registry()
    return {
        "heartbeat.device_compile_s": round(
            float(_counter_value(reg, "device.compile_wall_s_total")), 3),
        "heartbeat.device_recompiles": int(
            _counter_value(reg, "device.recompiles_total")),
        "heartbeat.device_shape_churn": int(
            _counter_value(reg, "device.shape_churn_total")),
    }


def reset_for_tests() -> None:
    """Forget attribution state (compiled shapes).

    Tests only — the listeners stay registered; registry metrics are
    reset separately via ``MetricsRegistry.reset``.
    """
    with _install_lock:
        _compiled_tags.clear()
