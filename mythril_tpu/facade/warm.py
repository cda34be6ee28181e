"""Warm-process facade: reusable flag propagation + per-request scoping.

``MythrilAnalyzer`` was written for one-shot processes: its constructor
copies CLI args into the global flag object, arms the caches, and the
process exits after one report.  The analysis service needs exactly that
propagation WITHOUT constructing an analyzer per request — the process
stays warm and only the per-request telemetry/detector scope resets
between batches.  This module is the shared half:

* ``apply_analyzer_args`` — the flag-propagation block, factored out of
  ``MythrilAnalyzer.__init__`` so the daemon and the one-shot facade run
  the identical configuration path (including ``--cache-root``
  derivation and cache arming).
* ``resolve_cache_root`` — one directory pins the persistent query cache.
* ``reset_analysis_scope`` — the scope sweep that makes each service
  batch behave like a fresh process: non-persistent metrics, detector
  issue lists, and the process-wide (address, bytecode_hash) detection
  caches are cleared; the SMT query cache, interned terms, and compiled
  XLA programs deliberately stay warm (their reuse is sound by
  construction — validated hits only).
* ``WorkerContext`` — the explicit owner of one worker's engine-global
  lifecycle.  The engine keeps genuinely process-global state (the flag
  singleton, the ``module/base`` issue sink, ``smt/terms`` interning),
  which is what confined the daemon to a single worker thread; this
  class names that state and scopes every touch of it, so a worker —
  the daemon's in-process thread or a pool worker *process* — is "the
  thing that owns a WorkerContext".  Process isolation then makes N
  contexts coexist: one per worker process, none shared.
"""

from __future__ import annotations

import contextlib
import os
from typing import Any, Dict, Optional

from mythril_tpu.support.support_args import args

__all__ = [
    "WorkerContext",
    "apply_analyzer_args",
    "reset_analysis_scope",
    "resolve_cache_root",
]


def resolve_cache_root(cache_root: Optional[str]) -> Optional[str]:
    """Map ``--cache-root DIR`` to the query cache directory
    ``DIR/querycache``; an explicit ``--query-cache-dir`` wins over it.
    The XLA compilation cache is not placed here: it follows
    ``JAX_COMPILATION_CACHE_DIR`` (``mythril_tpu.arm_compile_cache``).
    """
    if not cache_root:
        return None
    root = os.path.abspath(os.path.expanduser(cache_root))
    return os.path.join(root, "querycache")


def apply_analyzer_args(cmd_args) -> None:
    """Propagate facade args onto the global flag object and arm caches.

    Mirrors the reference's copy-into-singleton pattern
    (mythril/mythril/mythril_analyzer.py:63-70); shared by the one-shot
    ``MythrilAnalyzer`` and the long-lived ``service.AnalysisService``
    so both configure the engine identically.
    """
    args.solver_timeout = cmd_args.solver_timeout
    args.execution_timeout = cmd_args.execution_timeout
    args.create_timeout = cmd_args.create_timeout
    args.max_depth = cmd_args.max_depth
    args.call_depth_limit = cmd_args.call_depth_limit
    args.loop_bound = cmd_args.loop_bound
    args.transaction_count = cmd_args.transaction_count
    args.unconstrained_storage = cmd_args.unconstrained_storage
    args.sparse_pruning = cmd_args.sparse_pruning
    args.parallel_solving = cmd_args.parallel_solving
    args.solver_log = cmd_args.solver_log
    args.enable_iprof = cmd_args.enable_iprof
    args.benchmark_path = getattr(cmd_args, "benchmark_path", None)
    args.checkpoint_path = getattr(cmd_args, "checkpoint_file", None)
    args.resume_from = getattr(cmd_args, "resume_from", None)
    args.probe_backend = getattr(cmd_args, "probe_backend", "auto")
    if args.probe_backend == "cdcl":
        # forced-exact mode without the native solver would answer every
        # query UNKNOWN and silently prune the whole state space
        from mythril_tpu.native import bitblast

        if not bitblast.available():
            raise RuntimeError(
                "--probe-backend cdcl requires the native CDCL solver "
                "(mythril_tpu/native); it is not available in this build"
            )
    args.frontier = getattr(cmd_args, "frontier", False)
    args.frontier_width = getattr(cmd_args, "frontier_width", 64)
    args.frontier_force = getattr(cmd_args, "frontier_force", False)
    args.query_cache = getattr(cmd_args, "query_cache", True)
    args.staticpass = getattr(cmd_args, "staticpass", True)
    args.staticpass_interproc = getattr(
        cmd_args, "staticpass_interproc", True
    )
    args.code_paging = getattr(cmd_args, "code_paging", True)
    args.code_page_budget = getattr(cmd_args, "code_page_budget", 2048)
    args.pipeline = getattr(cmd_args, "pipeline", True)
    args.prefilter = getattr(cmd_args, "prefilter", True)
    args.devsolver = getattr(cmd_args, "devsolver", True)
    args.devsolver_bit_budget = getattr(cmd_args, "devsolver_bit_budget", 64)
    args.devsolver_iters = getattr(cmd_args, "devsolver_iters", 2048)
    from mythril_tpu import devsolver as _devsolver

    _devsolver.configure(bit_budget=args.devsolver_bit_budget,
                         iters=args.devsolver_iters)
    args.frontier_mesh = getattr(cmd_args, "frontier_mesh", True)
    args.adaptive = getattr(cmd_args, "adaptive", True)
    args.coverage_target = getattr(cmd_args, "coverage_target", None)
    args.solver_workers = getattr(cmd_args, "solver_workers", 2)
    args.harvest_workers = getattr(cmd_args, "harvest_workers", 4)
    args.heartbeat_out = getattr(cmd_args, "heartbeat_out", None)
    args.heartbeat_interval = getattr(cmd_args, "heartbeat_interval", 0.5)
    args.flight_recorder = getattr(cmd_args, "flight_recorder", None)
    args.history_dir = getattr(cmd_args, "history_dir", None)
    args.watchdog_deadline = getattr(cmd_args, "watchdog_deadline", None)
    # --cache-root pins the persistent query cache; an explicit
    # --query-cache-dir wins over the derived path
    args.cache_root = getattr(cmd_args, "cache_root", None)
    args.query_cache_dir = (
        getattr(cmd_args, "query_cache_dir", None)
        or resolve_cache_root(args.cache_root)
    )
    from mythril_tpu.querycache import configure as _configure_query_cache

    _configure_query_cache(
        enabled=args.query_cache, cache_dir=args.query_cache_dir
    )


class WorkerContext:
    """Explicit per-worker handle on the engine's process-global state.

    One worker (the daemon's inline thread, or one pool worker process)
    constructs exactly one context and routes every engine-global touch
    through it: flag-object configuration (``configure``), the per-batch
    scope sweep (``reset_scope``), issue-sink installation
    (``sink_scope``), the host-probe flag flip (``probe_scope``) and
    abstract-pre-filter accounting (``prefilter_delta``).  Nothing here
    is thread-safe by design — the context IS the single-ownership
    contract the old implicit globals only implied.
    """

    def __init__(self, analyzer_args):
        #: the AnalyzerArgs-shaped namespace this worker was armed with
        self.analyzer_args = analyzer_args
        self.configured = False

    def configure(self) -> "WorkerContext":
        """Arm the global flag object + caches for this worker process."""
        apply_analyzer_args(self.analyzer_args)
        self.configured = True
        return self

    def reset_scope(self) -> None:
        """Per-batch sweep: next analysis behaves like a fresh process."""
        reset_analysis_scope()

    def sink_scope(self, sink):
        """Install an issue sink for the scope of one analysis."""
        from mythril_tpu.analysis.module.base import issue_sink_scope

        return issue_sink_scope(sink)

    @contextlib.contextmanager
    def probe_scope(self):
        """Host-first probe configuration: frontier off, host probe
        backend — restored on exit.  (The probe's tighter execution
        timeout travels as an explicit ``run_cooperative_batch``
        argument, not through the flag object.)"""
        saved = (args.frontier, args.probe_backend)
        args.frontier = False
        args.probe_backend = "host"
        try:
            yield
        finally:
            args.frontier, args.probe_backend = saved

    @contextlib.contextmanager
    def prefilter_delta(self, out: Dict[str, int]):
        """Measure this scope's abstract pre-filter activity into ``out``
        (keys ``evaluated``/``killed``) — the scoped counters reset per
        batch, so callers that outlive the batch need the delta."""
        from mythril_tpu.observability.metrics import get_registry

        reg = get_registry()
        e0 = reg.counter("prefilter.evaluated").value
        k0 = reg.counter("prefilter.killed").value
        try:
            yield out
        finally:
            out["evaluated"] = out.get("evaluated", 0) + max(
                reg.counter("prefilter.evaluated").value - e0, 0
            )
            out["killed"] = out.get("killed", 0) + max(
                reg.counter("prefilter.killed").value - k0, 0
            )

    @contextlib.contextmanager
    def devsolver_delta(self, out: Dict[str, int]):
        """Measure this scope's device-SAT-tier activity into ``out``
        (keys ``admitted``/``decided_sat``/``decided_unsat``/``unknown``/
        ``model_validation_failures``) — scoped counters reset per batch,
        same contract as ``prefilter_delta``."""
        from mythril_tpu.observability.metrics import get_registry

        reg = get_registry()
        names = ("admitted", "decided_sat", "decided_unsat", "unknown",
                 "model_validation_failures")
        base = {n: reg.counter("devsolver." + n).value for n in names}
        try:
            yield out
        finally:
            for n in names:
                out[n] = out.get(n, 0) + max(
                    reg.counter("devsolver." + n).value - base[n], 0
                )

    @contextlib.contextmanager
    def exploration_delta(self, out: Dict[str, Any]):
        """Measure this scope's exploration-ledger activity into ``out``:
        per-class terminated-path deltas (``terminated`` dict +
        ``terminated_total``), ``pc_overflow``, and the scope-end
        per-contract ``coverage_pct``.  Like ``prefilter_delta``, the
        ledger resets per analysis scope, so callers that outlive the
        batch (the daemon's persistent mirrors, pool-worker done
        payloads) need the delta."""
        from mythril_tpu.observability.exploration import (
            get_exploration_ledger,
        )

        led = get_exploration_ledger()
        t0 = led.terminated()
        o0 = led.pc_overflow
        try:
            yield out
        finally:
            t1 = led.terminated()
            term = out.setdefault("terminated", {})
            for cls, n in t1.items():
                d = max(n - t0.get(cls, 0), 0)
                if d:
                    term[cls] = term.get(cls, 0) + d
            out["terminated_total"] = sum(term.values())
            out["pc_overflow"] = out.get("pc_overflow", 0) + max(
                led.pc_overflow - o0, 0
            )
            # coverage is a level, not a flow: report the scope-end view
            # (keyed by codehash so the daemon can attribute per request)
            cov = led.coverage()
            out["coverage_pct"] = {
                h: c["instruction_pct"] for h, c in cov.items()
            }
            out["coverage_pct_reachable"] = {
                h: c["instruction_pct_reachable"] for h, c in cov.items()
            }

    @contextlib.contextmanager
    def adaptive_delta(self, out: Dict[str, Any]):
        """Measure this scope's adaptive-controller activity into ``out``
        (keys ``plans``/``resteered_slots``/``requeued_paths``/
        ``flips_planned``/``flips_hit``/``plateau_stops``, plus the
        scope-end ``coverage_stop`` verdict when --coverage-target
        latched one) — scoped counters reset per batch, same contract as
        ``prefilter_delta``."""
        from mythril_tpu.observability.metrics import get_registry

        reg = get_registry()
        names = ("plans", "resteered_slots", "requeued_paths",
                 "flips_planned", "flips_hit", "plateau_stops")
        base = {n: reg.counter("adaptive." + n).value for n in names}
        try:
            yield out
        finally:
            for n in names:
                out[n] = out.get(n, 0) + max(
                    reg.counter("adaptive." + n).value - base[n], 0
                )
            from mythril_tpu.adaptive import get_adaptive_controller

            stop = get_adaptive_controller().stop_state()
            if stop:
                out["coverage_stop"] = stop

    def stats(self) -> Dict[str, Any]:
        """Worker-local engine-global sizes (heartbeat payload)."""
        from mythril_tpu.smt.terms import intern_table_size

        return {"interned_terms": intern_table_size()}


def reset_analysis_scope() -> None:
    """Make the next analysis in this process behave like a fresh one.

    Clears per-analysis telemetry (non-persistent metrics, which resets
    the FrontierStatistics/SolverStatistics facades), detector issue
    lists, and the process-wide (address, bytecode_hash) detection
    caches — without the caches sweep a daemon batch would silently
    suppress re-detection of anything a previous batch already flagged.
    Deliberately does NOT drop the SMT query cache, the interned-term
    tables, or compiled XLA programs: keeping those warm across requests
    is the service's entire point, and their reuse is validated-sound.
    """
    from mythril_tpu.analysis.module.loader import ModuleLoader
    from mythril_tpu.analysis.security import reset_callback_modules
    from mythril_tpu.observability import reset_analysis_metrics

    reset_analysis_metrics()
    reset_callback_modules()
    for module in ModuleLoader().get_detection_modules():
        module.cache.clear()
