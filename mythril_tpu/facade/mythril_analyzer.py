"""Analyzer facade: run symbolic execution + detectors per contract.

Reference parity: mythril/mythril/mythril_analyzer.py:27-189 — copies CLI
args into the global flag object, runs fire_lasers per contract with graceful
degradation to partial results, and offers statespace/graph dumps.
"""

from __future__ import annotations

import logging
import traceback
from dataclasses import dataclass, field
from typing import List, Optional

from mythril_tpu.analysis.report import Issue, Report
from mythril_tpu.analysis.security import fire_lasers, retrieve_callback_issues
from mythril_tpu.analysis.symbolic import SymExecWrapper
from mythril_tpu.smt.solver import SolverStatistics
from mythril_tpu.support.device import DeviceFailure
from mythril_tpu.support.support_args import args

log = logging.getLogger(__name__)


@dataclass
class AnalyzerArgs:
    strategy: str = "dfs"
    max_depth: int = 128
    execution_timeout: int = 86400
    create_timeout: int = 10
    loop_bound: int = 3
    call_depth_limit: int = 3
    transaction_count: int = 2
    modules: Optional[List[str]] = None
    disable_dependency_pruning: bool = False
    solver_timeout: int = 10000
    unconstrained_storage: bool = False
    sparse_pruning: bool = False
    parallel_solving: bool = False
    solver_log: Optional[str] = None
    enable_iprof: bool = False
    benchmark_path: Optional[str] = None
    enable_coverage_strategy: bool = False
    custom_modules_directory: str = ""
    checkpoint_file: Optional[str] = None
    resume_from: Optional[str] = None
    probe_backend: str = "auto"
    frontier: bool = False
    frontier_width: int = 64
    frontier_force: bool = False
    query_cache: bool = True
    query_cache_dir: Optional[str] = None
    staticpass: bool = True
    staticpass_interproc: bool = True
    code_paging: bool = True
    code_page_budget: int = 2048
    pipeline: bool = True
    prefilter: bool = True
    devsolver: bool = True
    devsolver_bit_budget: int = 64
    devsolver_iters: int = 2048
    frontier_mesh: bool = True
    adaptive: bool = True
    coverage_target: Optional[float] = None
    solver_workers: int = 2
    harvest_workers: int = 4
    # one directory for the persistent query cache (querycache/); an
    # explicit query_cache_dir wins over the derivation
    cache_root: Optional[str] = None
    heartbeat_out: Optional[str] = None
    heartbeat_interval: float = 0.5
    flight_recorder: Optional[str] = None
    watchdog_deadline: Optional[float] = None
    #: record the metrics registry into a persistent delta-encoded
    #: history ring under this directory (``myth history`` reads it)
    history_dir: Optional[str] = None


class MythrilAnalyzer:
    def __init__(
        self,
        disassembler,
        cmd_args: AnalyzerArgs,
        strategy: str = "dfs",
        address: Optional[str] = None,
    ):
        self.eth = disassembler.eth
        self.contracts = disassembler.contracts or []
        self.enable_online_lookup = disassembler.enable_online_lookup
        self.strategy = strategy or cmd_args.strategy
        self.address = address
        self.cmd_args = cmd_args

        # anchor issue discovery timestamps before any analysis starts
        from mythril_tpu.analysis.report import StartTime

        StartTime()

        # propagate flags to the global args object (reference :63-70);
        # shared with the long-lived service daemon (facade/warm.py) so
        # one-shot and warm-process runs configure the engine identically
        from mythril_tpu.facade.warm import apply_analyzer_args

        apply_analyzer_args(cmd_args)

    def _sym_exec(self, contract, run_analysis_modules: bool = True) -> SymExecWrapper:
        from mythril_tpu.support.loader import DynLoader

        dynloader = DynLoader(self.eth, active=self.eth is not None)
        return SymExecWrapper(
            contract,
            self.address or "0x" + "0" * 38 + "06",
            strategy=self.strategy,
            dynloader=dynloader,
            max_depth=self.cmd_args.max_depth,
            execution_timeout=self.cmd_args.execution_timeout,
            create_timeout=self.cmd_args.create_timeout,
            loop_bound=self.cmd_args.loop_bound,
            transaction_count=self.cmd_args.transaction_count,
            modules=self.cmd_args.modules,
            disable_dependency_pruning=self.cmd_args.disable_dependency_pruning,
            run_analysis_modules=run_analysis_modules,
            enable_coverage_strategy=self.cmd_args.enable_coverage_strategy,
            custom_modules_directory=self.cmd_args.custom_modules_directory,
        )

    def dump_statespace(self, contract=None) -> str:
        import json

        from mythril_tpu.analysis.traceexplore import get_serializable_statespace

        sym = self._sym_exec(
            contract or self.contracts[0], run_analysis_modules=False
        )
        return json.dumps(get_serializable_statespace(sym))

    def graph_html(
        self, contract=None, enable_physics: bool = False, phrackify: bool = False
    ) -> str:
        from mythril_tpu.analysis.callgraph import generate_graph

        sym = self._sym_exec(
            contract or self.contracts[0], run_analysis_modules=False
        )
        return generate_graph(sym, physics=enable_physics, phrackify=phrackify)

    def fire_lasers(self, modules: Optional[List[str]] = None) -> Report:
        from mythril_tpu.frontier.engine import reset_isolation_gauges

        reset_isolation_gauges()
        SolverStatistics().enabled = True
        benchmark_base = args.benchmark_path
        try:
            all_issues, exceptions, execution_info = self._fire_lasers_loop(
                modules, benchmark_base
            )
        finally:
            args.benchmark_path = benchmark_base

        source_data = self.contracts
        report = Report(
            contracts=source_data,
            exceptions=exceptions,
            execution_info=execution_info,
        )
        for issue in all_issues:
            report.append_issue(issue)
        report.close_account()
        return report

    def _fire_lasers_loop(self, modules, benchmark_base):
        all_issues: List[Issue] = []
        exceptions = []
        execution_info = []
        for n_contract, contract in enumerate(self.contracts):
            if benchmark_base and len(self.contracts) > 1:
                # one series file per contract instead of silent overwrites
                args.benchmark_path = f"{benchmark_base}.{n_contract}"
            # the telemetry singletons are process-wide: without a
            # per-contract sweep, contract N's jsonv2 meta would report
            # parks/segment time/solver queries accumulated from earlier
            # contracts in the same invocation.  The sweep clears every
            # non-persistent metric (FrontierStatistics and
            # SolverStatistics facades included); the frontier's per-code
            # slow/narrow verdicts are persistent-scope and survive — see
            # reset_analysis_metrics / frontier/engine.py.
            from mythril_tpu.observability import reset_analysis_metrics

            reset_analysis_metrics()
            try:
                sym = self._sym_exec(contract)
                issues = fire_lasers(sym, modules or self.cmd_args.modules)
                from mythril_tpu.core.execution_info import (
                    CalibrationInfo,
                    EngineStatsInfo,
                    FrontierStatsInfo,
                    SolverStatsInfo,
                )

                execution_info = [
                    EngineStatsInfo(sym.laser),
                    SolverStatsInfo(),
                    CalibrationInfo(),
                ]
                if args.frontier:
                    execution_info.append(FrontierStatsInfo())
            except DeviceFailure:
                raise  # a device failure fails the analysis, never partial
            except KeyboardInterrupt:
                log.critical("keyboard interrupt: saving partial results")
                issues = retrieve_callback_issues(modules or self.cmd_args.modules)
            except Exception:  # noqa: BLE001 - graceful degradation to partial results
                log.exception("exception during analysis; saving partial results")
                issues = retrieve_callback_issues(modules or self.cmd_args.modules)
                exceptions.append(traceback.format_exc())
            from mythril_tpu.support.signatures import SignatureDB

            sigdb = SignatureDB()
            for issue in issues:
                issue.add_code_info(contract)
                issue.resolve_function_name(sigdb)
            log.info("solver statistics: %s", SolverStatistics())
            all_issues += issues
        return all_issues, exceptions, execution_info
