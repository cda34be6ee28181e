"""Unified tracing & metrics for the analyzer runtime.

Two pieces, both process-wide singletons:

* :mod:`mythril_tpu.observability.tracer` — a low-overhead span tracer
  (context-manager / decorator API over a thread-safe ring buffer) with
  Chrome-trace/Perfetto JSON and flat JSONL exporters.  Disabled by
  default; when disabled every instrumentation site costs one attribute
  check and returns a shared no-op context manager.

* :mod:`mythril_tpu.observability.metrics` — a registry of named
  counters / gauges / histograms that absorbs the mutable-attribute
  telemetry style of ``FrontierStatistics`` and ``SolverStatistics``.
  Those classes remain as thin facades over the registry so existing
  call sites and report-meta output are unchanged.  The registry also
  keeps one account per analysis (``reset_analysis_metrics`` opens it,
  ``close_analysis_account`` closes it when the report is built).

The convenience re-exports below are the recommended import surface::

    from mythril_tpu.observability import get_tracer, get_registry, span

    with span("frontier.segment", cat="frontier", k=64):
        ...
"""

from mythril_tpu.observability.deviceplane import (  # noqa: F401
    bucket_tag,
    device_meta,
    dispatch_scope,
    install_deviceplane,
)
from mythril_tpu.observability.drift import (  # noqa: F401
    diff_history_windows,
    diff_tables,
    format_drift,
)
from mythril_tpu.observability.exploration import (  # noqa: F401
    TERM_CLASSES,
    ExplorationLedger,
    exploration_meta,
    get_exploration_ledger,
)
from mythril_tpu.observability.fleet import (  # noqa: F401
    WIRE_VERSION,
    FleetAggregator,
    FleetPublisher,
)
from mythril_tpu.observability.flightrecorder import (  # noqa: F401
    FlightRecorder,
    arm_flight_recorder,
    build_bundle,
    disarm_flight_recorder,
    get_flight_recorder,
    register_dump_listener,
    register_flight_context,
    unregister_dump_listener,
    unregister_flight_context,
)
from mythril_tpu.observability.heartbeat import (  # noqa: F401
    HeartbeatSampler,
    get_heartbeat,
)
from mythril_tpu.observability.history import (  # noqa: F401
    HistoryReader,
    MetricsHistory,
)
from mythril_tpu.observability.metrics import (  # noqa: F401
    Counter,
    Gauge,
    Histogram,
    LabeledCounter,
    MetricsRegistry,
    get_registry,
    prometheus_text,
)
from mythril_tpu.observability.tracer import (  # noqa: F401
    Tracer,
    device_annotation,
    get_tracer,
    span,
    traced,
)
from mythril_tpu.observability.watchtower import (  # noqa: F401
    Objective,
    Watchtower,
    default_objectives,
    get_watchtower,
    health_meta,
    load_slo_file,
    set_watchtower,
)


def _materialize_facades() -> None:
    # the facade-backed metrics, so a snapshot always carries the full
    # frontier.*/solver.* key set, even for runs where a stage never
    # executed (e.g. narrow workloads that bail off-device)
    from mythril_tpu.frontier.stats import FrontierStatistics
    from mythril_tpu.querycache.cache import materialize_counters
    from mythril_tpu.smt.solver import SolverStatistics

    FrontierStatistics()._materialize()
    SolverStatistics().materialize()
    materialize_counters()


def observability_meta() -> dict:
    """Snapshot block embedded in report meta."""
    _materialize_facades()
    tracer = get_tracer()
    meta = {"metrics": get_registry().snapshot()}
    if tracer.enabled or len(tracer):
        meta["trace"] = tracer.summary()
    return meta


def reset_analysis_metrics() -> None:
    """Reset per-analysis telemetry at the start of an analysis.

    Clears every non-persistent metric in the registry (which resets the
    ``FrontierStatistics`` / ``SolverStatistics`` facades with it).
    Metrics registered with ``persistent=True`` — e.g. the frontier's
    per-code slow/narrow-segment verdicts, which engine.py deliberately
    keeps across runs so a code that degenerated once is not re-probed —
    survive the sweep.  The exploration ledger's coverage bitmaps are
    swept with the same scope (its counters live in the registry and
    reset with everything else).  It also opens the analysis's account
    in the registry (``close_analysis_account`` closes it).
    """
    get_registry().reset()
    get_registry().open_account()
    get_exploration_ledger().reset_scope()
    # the adaptive controller's plan cache / coverage history / latched
    # coverage-stop verdict all describe the scope being swept
    from mythril_tpu.adaptive import get_adaptive_controller

    get_adaptive_controller().reset_scope()


def close_analysis_account(issues=()) -> dict:
    """Close the running analysis's account when its report is built.

    ``issues`` are per-issue records (SWC id, pc, discovery stamp, solver
    seconds at discovery).  The drain runs from the passing of the
    execution deadline (the execution timer's, which the frontier engine,
    the pipeline and the host engine all check) to now; under
    ``--trace-out`` it is also the span ``analysis.drain``.
    """
    import time

    from mythril_tpu.support.time_handler import time_handler

    _materialize_facades()
    deadline = time_handler.deadline()
    acct = get_registry().close_account(
        issues, None if deadline is None else int(deadline * 1e9))
    drain = acct["metrics"]["frontier.drain_s"]
    if drain:
        get_tracer().record_span("analysis.drain", "analysis",
                                 time.perf_counter() - drain, drain)
    return acct
