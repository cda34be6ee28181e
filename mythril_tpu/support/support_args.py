"""Global analysis flags.

The reference threads ~15 flags through a mutable ``Args`` singleton
(mythril/support/support_args.py:5-24).  This build keeps the same access
pattern for engine code but the object is a plain dataclass that the facade
constructs and *also* installs as the module-level default — device-side code
never reads it (flags are baked into traced programs as static arguments), so
the pjit-tracing hazard the survey warns about (SURVEY.md §5.6) does not arise.
"""

from __future__ import annotations

from dataclasses import dataclass, field
from typing import List, Optional


@dataclass
class Args:
    solver_timeout: int = 10000  # ms, per query
    execution_timeout: int = 86400  # s, whole run
    create_timeout: int = 10  # s, creation tx
    max_depth: int = 128
    call_depth_limit: int = 3
    loop_bound: int = 3
    transaction_count: int = 2
    pruning_factor: Optional[float] = None
    unconstrained_storage: bool = False
    sparse_pruning: bool = False
    parallel_solving: bool = False  # TPU probe batches instead of z3 threads
    solver_log: Optional[str] = None
    use_integer_module: bool = True
    use_attack_as_target: bool = False
    enable_iprof: bool = False
    # write the benchmark plugin's series (JSON + SVG chart) to this path
    benchmark_path: Optional[str] = None
    # probe solver tuning
    probe_candidates: int = 48
    probe_rounds: int = 4
    probe_backend: str = "auto"  # auto | host | jax | cdcl (forced exact)
    keccak_backend: str = "auto"  # auto | jax | pallas (pallas on TPU when auto)
    # auto-backend break-even: dispatch to device when DAG-size x candidates
    # exceeds this (host evaluation below it is faster than one round trip).
    # Re-measured after the round-2 probe speedups (~4x faster host tiers):
    # anchor measured at a 100 ms dispatch round trip, per-query dispatch
    # only pays past ~600k (calibration rescales it to the measured round
    # trip); the device's real wins are frontier segments and merged batch
    # dispatches
    device_probe_threshold: int = 600_000
    # frontier checkpointing
    checkpoint_path: Optional[str] = None
    resume_from: Optional[str] = None
    # deterministic replay only: GAS pushes the exact remaining gas instead
    # of a fresh symbol (conformance/concolic drivers; never symbolic runs)
    concrete_gas: bool = False
    # batched device-resident frontier interpreter (SURVEY.md §7.1)
    frontier: bool = False  # run message-call txs on the device frontier
    frontier_width: int = 64  # batch width B (paths held on device)
    # bypass the a-priori narrow gate (engine._device_worthwhile): used by
    # differential tests so frontier=True really exercises the device even
    # on deliberately tiny contracts
    frontier_force: bool = False
    # SPMD the frontier segment over all visible devices (path axis); the
    # engine shards automatically when >1 device is attached, padding the
    # batch width up to a device-count multiple with dead slots.  Composes
    # with the pipelined runner (chained dispatches run as one SPMD
    # program); --no-mesh is the single-device escape hatch
    frontier_mesh: bool = True
    # persistent SMT query cache (mythril_tpu/querycache): the in-process
    # LRU + reuse tiers run whenever query_cache is True; setting a dir
    # adds the disk-backed cross-run/cross-shard store
    query_cache: bool = True
    query_cache_dir: Optional[str] = None
    # partition each symbolic tx's selector space into one seed per
    # function-table entry + a complement seed (core/transaction/symbolic.
    # seed_message_call): same state space, but the work list starts
    # |selectors|+1 wide so the device frontier gets width up front
    multi_selector_seeding: bool = False
    # static bytecode pre-analysis (mythril_tpu/staticpass): CFG recovery +
    # abstract stack-height + taint reachability, gating detector hooks and
    # packed device events.  Over-approximate — the issue set is identical
    # either way; --no-staticpass is the escape hatch
    staticpass: bool = True
    # interprocedural layer on top of the base pass (value-set jump
    # refinement, function recovery, reachable-edge oracle, call graph);
    # --no-staticpass-interproc keeps the base passes only
    # (tests/staticpass/test_interproc_parity.py compares this toggle)
    staticpass_interproc: bool = True
    # large-code frontier (mythril_tpu/frontier/code): per-code bucket
    # isolation (codes cluster into size classes, each dispatched with its
    # own compiled segment instead of one corpus-wide max bucket) plus
    # packed-code paging (codes beyond the residency budget keep only a
    # hot window resident; cold jumps fault to the host for a repack).
    # Issue-set-identical either way; --no-code-paging is the escape
    # hatch (and the parity baseline in tests/frontier/test_code_buckets.py)
    code_paging: bool = True
    # instruction-axis residency budget for packed-code paging: codes
    # whose instruction count exceeds the grown bucket of this value page
    # through a window of that size (0 disables paging, keeping bucket
    # isolation only)
    code_page_budget: int = 2048
    # pipelined frontier (mythril_tpu/frontier/pipeline): overlap device
    # segments with host harvest/solve via chained dispatch + a background
    # feasibility pool.  Issue-set-identical to the synchronous loop;
    # --no-pipeline is the escape hatch (and the parity baseline)
    pipeline: bool = True
    # abstract feasibility pre-filter (mythril_tpu/absdomain): vectorized
    # interval + known-bits pass ahead of the feasibility pool and the
    # solver fast path.  Sound (UNSAT verdicts only), issue-set-identical;
    # --no-prefilter is the escape hatch (and the parity baseline)
    prefilter: bool = True
    # device-resident SAT tier (mythril_tpu/devsolver): batched bit-blast
    # decision procedure between the pre-filter and the exact tiers.
    # UNSAT is exact, SAT models are concrete_eval-validated before trust,
    # UNKNOWN falls through; --no-devsolver is the escape hatch (and the
    # parity baseline in tests/devsolver/test_pipeline_parity.py)
    devsolver: bool = True
    # admission: maximum free decision bits after known-bits/interval
    # narrowing for a query to enter the device tier
    devsolver_bit_budget: int = 64
    # search-kernel iteration budget per batch (budget lapse -> UNKNOWN)
    devsolver_iters: int = 2048
    # feasibility-pool worker threads (solves share one lock — the win is
    # moving solve latency off the harvest critical path, not parallelism)
    solver_workers: int = 2
    # harvest replay worker threads: terminal replays shard by owning
    # laser so no per-laser state is ever touched by two workers; results
    # commit in slot order, so issue sets are identical to the serial
    # sweep.  0 = serial escape hatch (and the parity baseline)
    harvest_workers: int = 4
    # one directory pinning the persistent query cache for service
    # deployments (<root>/querycache, facade/warm.resolve_cache_root); an
    # explicit query_cache_dir wins over the derivation
    cache_root: Optional[str] = None
    # coverage-guided adaptive exploration (mythril_tpu/adaptive): the
    # feedback controller that re-steers frontier dispatch slots at
    # uncovered reachable edges, resurrects budget-parked paths when
    # slots free, and targets concolic flips.  A scheduling-only
    # optimization — the issue set is bit-identical either way;
    # --no-adaptive is the escape hatch (and the parity baseline in
    # tests/adaptive/test_parity.py)
    adaptive: bool = True
    # terminate exploration once reachable-edge/instruction coverage
    # reaches this percent (or all explored codes plateau below it):
    # the "explore to a coverage bar" request contract.  None = explore
    # to the transaction/time budget as before
    coverage_target: Optional[float] = None
    # flight deck (mythril_tpu/observability): heartbeat JSONL of sampled
    # queue depths, sampler period, flight-recorder bundle directory, and
    # the watchdog deadline (seconds without a completed segment before a
    # hang bundle is dumped; None disables the watchdog)
    heartbeat_out: Optional[str] = None
    heartbeat_interval: float = 0.5
    flight_recorder: Optional[str] = None
    watchdog_deadline: Optional[float] = None


args = Args()
