"""End-to-end benchmark suite: every workload, recall asserted.

Workloads (configs 1-5; the reference's envelope is in README.md):
  1. suicide_1tx        unprotected SELFDESTRUCT, one transaction
                        (solc-compiled suicide.sol.o from the reference mount)
  2. killbilly_3tx      storage-gated selfdestruct needing a 2-tx chain
                        (the reference README's headline demo)
  3. overflow_256bit    BECToken-style 256-bit integer overflow/underflow
                        search (solc-compiled overflow.sol.o/underflow.sol.o)
  4. concolic_flip      concolic JUMPI branch-flip (input synthesis for the
                        untaken side of a recorded trace)
  5. corpus_sweep       the whole reference input corpus (17 solc contracts),
                        shardable across hosts via mythril_tpu.parallel.corpus
                        — THE HEADLINE METRIC (wide workloads are where the
                        batched device frontier pays)

Configurations, run interleaved per workload:
  baseline    host big-int probe + host work-list engine — the stand-in for
              the reference's CPU path (the mounted reference itself cannot
              run here: no z3 wheel in the image)
  production  latency-aware hybrid probe + the batched device-resident
              frontier interpreter (args.frontier)

Every run must find its workload's known vulnerabilities (recall asserted) —
a config that loses recall does not get a number.

Output contract: one JSON snapshot line per completed workload pair (each
carrying ``"partial": true``) and a final complete line without the flag —
consumers take the LAST parseable JSON line.  A wall-clock budget
(``BENCH_BUDGET_S``, default 1500 s) trims reps 2+ deterministically so the
driver's timeout can never kill the run before a full table exists; the
latest snapshot is also mirrored to ``BENCH_partial.json``.
"""

from __future__ import annotations

import json
import os
import sys
import time
from pathlib import Path

REFERENCE_INPUTS = Path("/root/reference/tests/testdata/inputs")
# fallback corpus when the reference mount is absent (raw runtime dumps)
LOCAL_INPUTS = Path(__file__).parent / "tests" / "integration" / "inputs"
CORPUS_GLOBS = ("*.sol.o", "*.bin-runtime")

# ---------------------------------------------------------------------------
# workload 2: killbilly (hand-assembled; kept importable for tests)
# ---------------------------------------------------------------------------

# activate() selector 0x0a11ce00 -> 0x1e, kill() selector 0x41c0e1b5 -> 0x25
DISPATCH = (
    "6000" "35" "60e0" "1c" "80"
    "630a11ce00" "14" "601e" "57"
    "6341c0e1b5" "14" "6025" "57"
    "60006000fd"
)
ACTIVATE = "5b600160005500"  # 0x1e: JUMPDEST; SSTORE(0, 1); STOP
KILL = "5b" "600054" "6001" "14" "6034" "57" "60006000fd" "5b" "33" "ff"
KILLBILLY = DISPATCH + ACTIVATE + KILL
_L = f"{len(KILLBILLY) // 2:02x}"
KILLBILLY_CREATION = f"60{_L}600c60003960{_L}6000f3" + KILLBILLY


def _clear_caches() -> None:
    from mythril_tpu.analysis.module.loader import ModuleLoader
    from mythril_tpu.analysis.security import reset_callback_modules
    from mythril_tpu.querycache import reset_query_cache
    from mythril_tpu.smt.solver import clear_model_cache
    from mythril_tpu.support.model import _get_model_cached

    reset_callback_modules()
    clear_model_cache()
    _get_model_cached.cache_clear()
    # drops the in-process query cache but keeps any configured disk store
    # attached — warm runs in query_cache_compare hit via the disk tier
    reset_query_cache()
    for module in ModuleLoader().get_detection_modules():
        module.cache.clear()


def _analyze(contract, address, tx_count, modules=None, strategy="bfs",
             timeout=60):
    from mythril_tpu.analysis.security import fire_lasers
    from mythril_tpu.analysis.symbolic import SymExecWrapper

    sym = SymExecWrapper(
        contract,
        address=address,
        strategy=strategy,
        transaction_count=tx_count,
        execution_timeout=timeout,
        modules=modules,
    )
    issues = fire_lasers(sym, white_list=modules)
    return sym, issues


def _configure(production: bool) -> None:
    """baseline = host probe + host engine.  production = latency-aware
    hybrid probe + the batched device frontier ENABLED EVERYWHERE — the
    engine's own width gating (a-priori narrow gate + adaptive narrow-bail,
    frontier/engine.py) decides per run whether the device pays, so narrow
    workloads run unchanged and wide ones go device-resident."""
    from mythril_tpu.support.support_args import args

    args.probe_backend = "auto" if production else "host"
    args.frontier = production
    args.frontier_force = False
    if production:
        # one production width across workloads (wide_frontier overrides to
        # 1024): every device run shares the segment program _warm_frontier
        # compiled, so no workload pays an XLA compile inside its timer
        args.frontier_width = 256


# ---------------------------------------------------------------------------
# recall helpers
# ---------------------------------------------------------------------------


def _ttfe(issues, t0: float, swc: str = None) -> float:
    """Time-to-first-exploit: wall seconds from analysis start to the first
    (matching) issue's discovery (BASELINE.json's second metric).  Issue
    discovery stamps are process-global (report.StartTime), so they are
    rebased against this run's ``t0``."""
    from mythril_tpu.analysis.report import StartTime

    base = StartTime().global_start_time
    stamps = [
        i.discovery_time for i in issues if swc is None or i.swc_id == swc
    ]
    if not stamps:
        return float("nan")
    return _rebase_stamp(base + min(stamps), t0)


def _selects(input_hex: str, selector: int) -> bool:
    """Does this calldata dispatch to ``selector``?  EVM CALLDATALOAD
    zero-pads past calldatasize, so exact minimization may shave trailing
    zero bytes off the selector itself (0x0a11ce00 -> 3-byte calldata)."""
    data = bytes.fromhex(input_hex[2:] if input_hex.startswith("0x") else input_hex)
    padded = (data + b"\x00" * 4)[:4]
    return int.from_bytes(padded, "big") == selector


def check_recall(issues) -> None:
    """killbilly recall: SWC-106 with activate() then kill()."""
    assert issues, "exploit not found: zero issues"
    issue = issues[0]
    assert issue.swc_id == "106", f"wrong SWC id {issue.swc_id}"
    steps = issue.transaction_sequence["steps"]
    inputs = [s["input"] for s in steps]
    assert any(_selects(i, 0x0A11CE00) for i in inputs), "missing activate() tx"
    assert _selects(inputs[-1], 0x41C0E1B5), "final tx is not kill()"


def run_analysis(probe_backend: str):
    """Killbilly workload under one probe backend (kept for tests)."""
    from mythril_tpu.frontend.evmcontract import EVMContract
    from mythril_tpu.support.support_args import args as global_args

    global_args.probe_backend = probe_backend
    _clear_caches()
    contract = EVMContract(
        code=KILLBILLY, creation_code=KILLBILLY_CREATION, name="KillBilly"
    )
    t0 = time.time()
    sym, issues = _analyze(
        contract, 0x0901D12E, 3, modules=["AccidentallyKillable"], timeout=300
    )
    return sym, issues, time.time() - t0


def query_cache_compare(cache_dir=None) -> dict:
    """Warm-vs-cold query-cache comparison on the killbilly workload.

    Runs the analysis twice against one disk-backed cache directory: the
    cold run populates the store, the warm run (fresh in-process cache via
    ``_clear_caches``) must hit it.  Asserts a nonzero warm hit count and
    an issue set identical to the cold run, then returns (and ``main``
    prints) one JSON-able dict with walls, hit counters and the full
    ``querycache.*`` registry snapshot.
    """
    import tempfile

    from mythril_tpu.observability import get_registry
    from mythril_tpu.querycache import configure, get_query_cache

    def issue_set(issues):
        return sorted((i.swc_id, i.address) for i in issues)

    tmp = None
    if cache_dir is None:
        tmp = tempfile.TemporaryDirectory(prefix="mythril-querycache-")
        cache_dir = tmp.name
    try:
        configure(enabled=True, cache_dir=str(cache_dir))

        get_registry().reset(prefix="querycache.")
        _, cold_issues, cold_wall = run_analysis("host")
        cold_stats = dict(get_query_cache().stats())

        get_registry().reset(prefix="querycache.")
        _, warm_issues, warm_wall = run_analysis("host")
        warm_stats = dict(get_query_cache().stats())
        warm_hits = get_query_cache().hits_total()

        assert warm_hits > 0, f"warm run had zero cache hits: {warm_stats}"
        assert issue_set(cold_issues) == issue_set(warm_issues), (
            "warm issue set diverged from cold: "
            f"{issue_set(cold_issues)} != {issue_set(warm_issues)}"
        )
        lookups = warm_stats.get("lookups", 0)
        return {
            "metric": "query_cache_compare",
            "workload": "killbilly",
            "cache_dir": str(cache_dir),
            "cold_wall_s": round(cold_wall, 3),
            "warm_wall_s": round(warm_wall, 3),
            "warm_hits": warm_hits,
            "warm_hit_rate": round(warm_hits / lookups, 4) if lookups else 0.0,
            "issues": issue_set(cold_issues),
            "cold": cold_stats,
            "warm": warm_stats,
        }
    finally:
        configure(enabled=True, cache_dir=None)
        if tmp is not None:
            tmp.cleanup()


def _interproc_parity() -> dict:
    """Interprocedural-layer on/off bit-identity across the bench corpus.

    For every corpus member (reference corpus when mounted, plus killbilly
    and the assembled real shapes) the full analysis runs twice — interproc
    refinement on, then off — and the issue sets must be IDENTICAL: the
    refinement may only remove edges and work, never findings.  On top,
    the corrected-denominator contract is asserted over every coverage
    entry the runs produced: ``coverage_pct_reachable >= coverage_pct_raw``
    everywhere, strictly higher somewhere (dead code exists in at least
    one analyzed code object — e.g. the unreachable runtime body inside a
    creation frame).
    """
    from mythril_tpu.frontend.evmcontract import EVMContract
    from mythril_tpu.observability.exploration import get_exploration_ledger
    from mythril_tpu.staticpass import clear_cache, reset_views
    from mythril_tpu.support.support_args import args as global_args
    from mythril_tpu.support.support_utils import get_code_hash

    members = [(
        "killbilly",
        EVMContract(code=KILLBILLY, creation_code=KILLBILLY_CREATION,
                    name="KillBilly"),
        KILLBILLY,
    )]
    for path in sorted(p for g in CORPUS_GLOBS for p in _corpus_dir().glob(g)):
        code = _read_runtime(path)
        members.append((path.name, code, code.hex()))
    for name, code in _assembled_corpus():
        hex_code = code.hex() if isinstance(code, (bytes, bytearray)) else code
        members.append((name, code, hex_code))

    prev = (global_args.staticpass, global_args.staticpass_interproc)
    rows = []
    try:
        for name, contract, hex_code in members:
            def one(interproc: bool):
                global_args.staticpass = True
                global_args.staticpass_interproc = interproc
                _clear_caches()
                clear_cache()
                reset_views()
                _, issues = _analyze(contract, 0x0901D12E, 2, timeout=60)
                return sorted((i.swc_id, i.address) for i in issues)

            on_issues = one(True)
            cov = get_exploration_ledger().coverage().get(
                get_code_hash(hex_code)
            ) or {}
            off_issues = one(False)
            assert on_issues == off_issues, (
                f"{name}: interprocedural pruning changed the issue set "
                f"(over-approximation broken): {on_issues} != {off_issues}"
            )
            rows.append({
                "workload": name,
                "issues": on_issues,
                "coverage_pct_raw": cov.get("instruction_pct_raw"),
                "coverage_pct_reachable": cov.get("instruction_pct_reachable"),
            })
        # denominator contract over EVERY code object the runs touched
        # (creation frames included — that's where dead code is common)
        strictly_higher = 0
        for h, cov in get_exploration_ledger().coverage().items():
            raw = cov.get("instruction_pct_raw")
            reach = cov.get("instruction_pct_reachable")
            if raw is None or reach is None:
                continue
            assert reach >= raw, (
                f"{h}: coverage_pct_reachable {reach} < raw {raw} — the "
                "reachable denominator undercounted executed instructions"
            )
            if reach > raw:
                strictly_higher += 1
        assert strictly_higher >= 1, (
            "no analyzed code object had strictly higher reachable "
            "coverage — the corrected denominator changed nothing anywhere"
        )
    finally:
        global_args.staticpass, global_args.staticpass_interproc = prev
    return {
        "contracts": len(rows),
        "identical_issue_sets": True,
        "strictly_higher_reachable": strictly_higher,
        "rows": rows,
    }


def staticpass_compare() -> dict:
    """Static-pass on-vs-off comparison on the killbilly workload.

    Runs the full-module analysis twice — once with the static pre-analysis
    gate enabled, once with ``--no-staticpass`` semantics — and asserts the
    over-approximation contract: the issue sets are IDENTICAL while the
    gated run skipped a nonzero number of modules and elided a nonzero
    number of hooks.  A second sweep (``_interproc_parity``) toggles ONLY
    the interprocedural layer across the whole bench corpus and asserts
    bit-identical issue sets plus the reachable-coverage denominator
    contract.  Returns (and ``main`` prints) one JSON-able dict with both
    walls, both issue sets, the ``staticpass.*`` registry snapshot of the
    gated run and the per-member interproc parity rows.
    """
    from mythril_tpu.frontend.evmcontract import EVMContract
    from mythril_tpu.observability import get_registry
    from mythril_tpu.staticpass import clear_cache, reset_views
    from mythril_tpu.support.support_args import args as global_args

    def issue_set(issues):
        return sorted((i.swc_id, i.address) for i in issues)

    def one_run(enabled: bool):
        global_args.staticpass = enabled
        _clear_caches()
        clear_cache()
        reset_views()
        get_registry().reset(prefix="staticpass.")
        contract = EVMContract(
            code=KILLBILLY, creation_code=KILLBILLY_CREATION, name="KillBilly"
        )
        t0 = time.time()
        # all 14 modules: the gate needs irrelevant detectors to skip
        _, issues = _analyze(contract, 0x0901D12E, 3, modules=None, timeout=300)
        wall = time.time() - t0
        snap = {
            k: v
            for k, v in get_registry().snapshot().items()
            if k.startswith("staticpass.")
        }
        return issue_set(issues), wall, snap

    prev = global_args.staticpass
    try:
        on_issues, on_wall, on_snap = one_run(True)
        off_issues, off_wall, off_snap = one_run(False)
    finally:
        global_args.staticpass = prev

    assert on_snap.get("staticpass.modules_skipped", 0) > 0, (
        f"static pass skipped zero modules: {on_snap}"
    )
    assert on_snap.get("staticpass.hooks_elided", 0) > 0, (
        f"static pass elided zero hooks: {on_snap}"
    )
    assert off_snap.get("staticpass.modules_skipped", 0) == 0, (
        f"--no-staticpass run still gated modules: {off_snap}"
    )
    assert on_issues == off_issues, (
        "static pass changed the issue set (over-approximation broken): "
        f"{on_issues} != {off_issues}"
    )
    return {
        "metric": "staticpass_compare",
        "workload": "killbilly",
        "on_wall_s": round(on_wall, 3),
        "off_wall_s": round(off_wall, 3),
        "modules_skipped": on_snap.get("staticpass.modules_skipped", 0),
        "hooks_elided": on_snap.get("staticpass.hooks_elided", 0),
        "issues": on_issues,
        "staticpass": on_snap,
        "interproc": _interproc_parity(),
    }


def pipeline_compare() -> dict:
    """Pipelined vs synchronous frontier on two small workloads.

    Runs each workload twice with the device frontier forced on — once with
    the pipelined runner (chained dispatch + background feasibility pool),
    once with ``--no-pipeline`` semantics — and asserts the correctness
    contract: the issue sets are IDENTICAL while the pipelined run actually
    overlapped a nonzero number of segments.  Also asserts time-to-first-
    exploit parity (generous bound — CPU-backend walls jitter) so the
    opening-dispatch fix and the pipeline never push the first event behind
    a big-bucket compile again.  Returns (and ``main`` prints) one
    JSON-able dict with both walls, both issue sets and the ``pipeline.*``
    registry snapshot of the pipelined run.
    """
    from mythril_tpu.frontend.evmcontract import EVMContract
    from mythril_tpu.frontier import engine as _eng
    from mythril_tpu.observability import get_registry
    from mythril_tpu.support.support_args import args as global_args

    def issue_set(issues):
        return sorted((i.swc_id, i.address) for i in issues)

    suicide = bytes.fromhex("60003560e01c6341c0e1b51460145760006000fd5b33ff")
    workloads = [
        # (name, contract-or-code, tx_count, modules, recall swc)
        ("suicide", suicide, 1, ["AccidentallyKillable"], "106"),
        ("killbilly",
         EVMContract(code=KILLBILLY, creation_code=KILLBILLY_CREATION,
                     name="KillBilly"),
         3, ["AccidentallyKillable"], "106"),
    ]

    def one_run(target, txs, modules, pipelined: bool):
        global_args.pipeline = pipelined
        _clear_caches()
        # the per-code slow/narrow verdicts and program-warm markers are
        # deliberately process-persistent; a verdict learned in run A must
        # not change run B's control flow when comparing the two modes
        _eng._SLOW_CODES.clear()
        _eng._NARROW_CODES.clear()
        _eng._SLOW_SEGMENTS.clear()
        get_registry().reset(prefix="pipeline.")
        t0 = time.time()
        _, issues = _analyze(target, 0x0901D12E, txs, modules=modules,
                             timeout=300)
        wall = time.time() - t0
        snap = {
            k: v
            for k, v in get_registry().snapshot().items()
            if k.startswith("pipeline.")
        }
        return issue_set(issues), wall, _ttfe(issues, t0), snap

    prev = (global_args.pipeline, global_args.frontier,
            global_args.frontier_force, global_args.frontier_width)
    results = {}
    try:
        global_args.probe_backend = "auto"
        global_args.frontier = True
        global_args.frontier_force = True  # tiny contracts: bypass gates
        global_args.frontier_width = 64
        # warm both programs outside the timers: the pipelined and
        # synchronous paths jit different programs (chained-dispatch merge
        # vs plain push) and a cold XLA compile inside either timed run
        # would swamp the wall/ttfe comparison
        for pipelined in (True, False):
            one_run(suicide, 1, ["AccidentallyKillable"], pipelined)
        for name, target, txs, modules, swc in workloads:
            on_issues, on_wall, on_ttfe, on_snap = one_run(
                target, txs, modules, True
            )
            off_issues, off_wall, off_ttfe, off_snap = one_run(
                target, txs, modules, False
            )
            assert any(s == swc for s, _ in on_issues), (
                f"{name}: pipelined run lost recall: {on_issues}"
            )
            assert on_issues == off_issues, (
                f"{name}: pipeline changed the issue set: "
                f"{on_issues} != {off_issues}"
            )
            assert on_snap.get("pipeline.segments_pipelined", 0) > 0, (
                f"{name}: pipelined run overlapped zero segments: {on_snap}"
            )
            assert off_snap.get("pipeline.segments_pipelined", 0) == 0, (
                f"{name}: --no-pipeline run still pipelined: {off_snap}"
            )
            # parity, not a race: generous bound absorbs CPU-backend jitter
            if on_ttfe == on_ttfe and off_ttfe == off_ttfe:
                assert on_ttfe <= 3.0 * off_ttfe + 2.0, (
                    f"{name}: pipelined ttfe_s regressed: "
                    f"{on_ttfe:.2f}s vs {off_ttfe:.2f}s synchronous"
                )
            results[name] = {
                "pipelined_wall_s": round(on_wall, 3),
                "sync_wall_s": round(off_wall, 3),
                "pipelined_ttfe_s": round(on_ttfe, 3),
                "sync_ttfe_s": round(off_ttfe, 3),
                "issues": on_issues,
                "pipeline": on_snap,
            }
    finally:
        (global_args.pipeline, global_args.frontier,
         global_args.frontier_force, global_args.frontier_width) = prev
    return {"metric": "pipeline_compare", "workloads": results}


def prefilter_compare() -> dict:
    """Abstract pre-filter on-vs-off parity on two exploit workloads.

    Runs each workload twice with the pipelined device frontier forced on —
    once with the interval/known-bits pre-filter enabled, once with
    ``--no-prefilter`` semantics — and asserts the zero-recall-loss
    contract: the issue sets are IDENTICAL while the filtered run proved a
    nonzero number of feasibility queries UNSAT before any exact solve, and
    the harvest solver phase did not regress (generous CPU-jitter bound).
    Returns (and ``main`` prints) one JSON-able dict with both walls, both
    issue sets and the ``prefilter.*`` registry snapshot of the gated run.
    """
    from mythril_tpu import absdomain
    from mythril_tpu.frontend.evmcontract import EVMContract
    from mythril_tpu.frontier import engine as _eng
    from mythril_tpu.observability import get_registry
    from mythril_tpu.support.support_args import args as global_args

    def issue_set(issues):
        return sorted((i.swc_id, i.address) for i in issues)

    suicide = bytes.fromhex("60003560e01c6341c0e1b51460145760006000fd5b33ff")
    # x = calldataload(0); require(x < 10); x == 5 -> selfdestruct
    # (feasible exploit), x == 20 -> selfdestruct (infeasible: the branch
    # constraint contradicts the range pin, exactly the contradiction the
    # abstract harvest refutes without an exact solve)
    gated = bytes.fromhex(
        "60003580600a9010600c57005b80600514601c5780601414601c57005b33ff"
    )
    workloads = [
        # (name, contract-or-code, tx_count, modules, recall swc).
        # killbilly runs ALL detection modules: its feasibility traffic is
        # dominated by module confirmation demands and exercises the
        # fallthrough/parity side; "gated" carries the infeasible branch
        # that the pre-filter must kill before any exact solve
        ("suicide", suicide, 1, ["AccidentallyKillable"], "106"),
        ("gated", gated, 1, ["AccidentallyKillable"], "106"),
        ("killbilly",
         EVMContract(code=KILLBILLY, creation_code=KILLBILLY_CREATION,
                     name="KillBilly"),
         3, None, "106"),
    ]

    def one_run(target, txs, modules, filtered: bool):
        global_args.prefilter = filtered
        _clear_caches()
        absdomain.reset_state()  # verdict memo must not leak across modes
        _eng._SLOW_CODES.clear()
        _eng._NARROW_CODES.clear()
        _eng._SLOW_SEGMENTS.clear()
        reg = get_registry()
        reg.reset(prefix="prefilter.")
        solver_before = reg.histogram("frontier.harvest.solver_s").sum
        t0 = time.time()
        _, issues = _analyze(target, 0x0901D12E, txs, modules=modules,
                             timeout=300)
        wall = time.time() - t0
        solver_s = reg.histogram("frontier.harvest.solver_s").sum - solver_before
        snap = {
            k: v
            for k, v in reg.snapshot().items()
            if k.startswith("prefilter.")
        }
        return issue_set(issues), wall, solver_s, snap

    prev = (global_args.prefilter, global_args.frontier,
            global_args.frontier_force, global_args.frontier_width,
            global_args.pipeline)
    results = {}
    total_killed = 0
    try:
        global_args.probe_backend = "auto"
        global_args.frontier = True
        global_args.frontier_force = True  # tiny contracts: bypass gates
        global_args.frontier_width = 64
        global_args.pipeline = True
        # warm the XLA programs outside the timers (cold compiles would
        # swamp the solver_s comparison)
        one_run(suicide, 1, ["AccidentallyKillable"], True)
        for name, target, txs, modules, swc in workloads:
            on_issues, on_wall, on_solver, on_snap = one_run(
                target, txs, modules, True
            )
            off_issues, off_wall, off_solver, off_snap = one_run(
                target, txs, modules, False
            )
            assert any(s == swc for s, _ in on_issues), (
                f"{name}: filtered run lost recall: {on_issues}"
            )
            assert on_issues == off_issues, (
                f"{name}: pre-filter changed the issue set "
                "(soundness broken): "
                f"{on_issues} != {off_issues}"
            )
            assert off_snap.get("prefilter.evaluated", 0) == 0, (
                f"{name}: --no-prefilter run still evaluated: {off_snap}"
            )
            killed = on_snap.get("prefilter.killed", 0)
            total_killed += killed
            # parity, not a race: the filter must not ADD solver time
            # (generous bound absorbs CPU-backend jitter)
            assert on_solver <= 1.5 * off_solver + 2.0, (
                f"{name}: prefilter regressed harvest solver_s: "
                f"{on_solver:.2f}s vs {off_solver:.2f}s unfiltered"
            )
            results[name] = {
                "filtered_wall_s": round(on_wall, 3),
                "unfiltered_wall_s": round(off_wall, 3),
                "filtered_solver_s": round(on_solver, 3),
                "unfiltered_solver_s": round(off_solver, 3),
                "killed": killed,
                "issues": on_issues,
                "prefilter": on_snap,
            }
    finally:
        (global_args.prefilter, global_args.frontier,
         global_args.frontier_force, global_args.frontier_width,
         global_args.pipeline) = prev
    assert total_killed > 0, (
        "pre-filter killed zero queries across every exploit workload: "
        f"{results}"
    )
    return {"metric": "prefilter_compare", "workloads": results}


def devsolver_compare() -> dict:
    """Device SAT tier on-vs-off parity on the exploit workloads.

    Runs each workload twice with the pipelined device frontier forced on
    — once with the devsolver tier enabled, once with ``--no-devsolver``
    semantics — and asserts the soundness-by-construction contract: the
    issue sets are IDENTICAL while the gated run *decided* (exact UNSAT
    or concrete_eval-validated SAT) a nonzero number of queries that the
    ungated run sent to the exact tiers, with zero model-validation
    failures surviving as verdicts and no harvest-solver regression.
    Mirrors ``prefilter_compare``; one JSON-able dict per run.
    """
    from mythril_tpu import absdomain, devsolver
    from mythril_tpu.frontend.evmcontract import EVMContract
    from mythril_tpu.frontier import engine as _eng
    from mythril_tpu.observability import get_registry
    from mythril_tpu.support.support_args import args as global_args

    def issue_set(issues):
        return sorted((i.swc_id, i.address) for i in issues)

    suicide = bytes.fromhex("60003560e01c6341c0e1b51460145760006000fd5b33ff")
    gated = bytes.fromhex(
        "60003580600a9010600c57005b80600514601c5780601414601c57005b33ff"
    )
    # x=cld(0); y=cld(32); require x<16, y<16, x==y; the selfdestruct is
    # guarded by ((x^y)&15)==15 — a RELATIONAL contradiction the interval
    # and known-bits prefilter cannot see (neither var is pinned to a
    # point) but bit-level search refutes after one decision; without the
    # device tier it rides all the way to native CDCL
    relational = bytes.fromhex(
        "6000358060109010600c57005b"
        "6020358060109010601957005b"
        "80821460215700" "5b"
        "18600f16600f14602d57005b"
        "33ff"
    )
    workloads = [
        # same exploit workloads as prefilter_compare ("gated" carries
        # narrow range-pinned branch conditions, killbilly exercises the
        # wide fallthrough side) plus the devsolver's signature prey: a
        # relational infeasibility with an EMPTY issue set (swc None)
        ("suicide", suicide, 1, ["AccidentallyKillable"], "106"),
        ("gated", gated, 1, ["AccidentallyKillable"], "106"),
        ("relational", relational, 1, ["AccidentallyKillable"], None),
        ("killbilly",
         EVMContract(code=KILLBILLY, creation_code=KILLBILLY_CREATION,
                     name="KillBilly"),
         3, None, "106"),
    ]

    def one_run(target, txs, modules, gated_on: bool):
        global_args.devsolver = gated_on
        _clear_caches()
        absdomain.reset_state()
        devsolver.reset_state()  # verdict memo must not leak across modes
        _eng._SLOW_CODES.clear()
        _eng._NARROW_CODES.clear()
        _eng._SLOW_SEGMENTS.clear()
        reg = get_registry()
        reg.reset(prefix="devsolver.")
        solver_before = reg.histogram("frontier.harvest.solver_s").sum
        t0 = time.time()
        _, issues = _analyze(target, 0x0901D12E, txs, modules=modules,
                             timeout=300)
        wall = time.time() - t0
        solver_s = reg.histogram("frontier.harvest.solver_s").sum - solver_before
        snap = {
            k: v
            for k, v in reg.snapshot().items()
            if k.startswith("devsolver.")
        }
        return issue_set(issues), wall, solver_s, snap

    prev = (global_args.devsolver, global_args.prefilter,
            global_args.frontier, global_args.frontier_force,
            global_args.frontier_width, global_args.pipeline)
    results = {}
    total_decided = 0
    try:
        global_args.probe_backend = "auto"
        global_args.frontier = True
        global_args.frontier_force = True  # tiny contracts: bypass gates
        global_args.frontier_width = 64
        global_args.pipeline = True
        # warm the XLA programs outside the timers
        one_run(suicide, 1, ["AccidentallyKillable"], True)
        for name, target, txs, modules, swc in workloads:
            on_issues, on_wall, on_solver, on_snap = one_run(
                target, txs, modules, True
            )
            off_issues, off_wall, off_solver, off_snap = one_run(
                target, txs, modules, False
            )
            if swc is None:
                assert not on_issues, (
                    f"{name}: infeasible branch produced issues "
                    f"(false positive): {on_issues}"
                )
            else:
                assert any(s == swc for s, _ in on_issues), (
                    f"{name}: devsolver run lost recall: {on_issues}"
                )
            assert on_issues == off_issues, (
                f"{name}: device SAT tier changed the issue set "
                "(soundness broken): "
                f"{on_issues} != {off_issues}"
            )
            assert off_snap.get("devsolver.admitted", 0) == 0, (
                f"{name}: --no-devsolver run still admitted: {off_snap}"
            )
            decided = (on_snap.get("devsolver.decided_sat", 0)
                       + on_snap.get("devsolver.decided_unsat", 0))
            total_decided += decided
            # parity, not a race: the tier must not ADD solver time
            # (generous bound absorbs CPU-backend jitter)
            assert on_solver <= 1.5 * off_solver + 2.0, (
                f"{name}: devsolver regressed harvest solver_s: "
                f"{on_solver:.2f}s vs {off_solver:.2f}s ungated"
            )
            results[name] = {
                "gated_wall_s": round(on_wall, 3),
                "ungated_wall_s": round(off_wall, 3),
                "gated_solver_s": round(on_solver, 3),
                "ungated_solver_s": round(off_solver, 3),
                "decided": decided,
                "fallthrough": on_snap.get("devsolver.unknown", 0),
                "issues": on_issues,
                "devsolver": on_snap,
            }
    finally:
        (global_args.devsolver, global_args.prefilter,
         global_args.frontier, global_args.frontier_force,
         global_args.frontier_width, global_args.pipeline) = prev
    assert total_decided > 0, (
        "device SAT tier decided zero queries across every exploit "
        f"workload: {results}"
    )
    return {"metric": "devsolver_compare", "workloads": results}


def adaptive_compare() -> dict:
    """Coverage-guided steering on-vs-off parity on multi-code batches.

    Runs each cooperative workload twice with the pipelined device
    frontier forced on — once with the adaptive controller enabled, once
    with ``--no-adaptive`` semantics — and asserts the steering
    contract: the issue sets are BIT-IDENTICAL (the controller only
    reorders/retimes frontier compute, it never changes what is
    explored to completion) while the steered run actually exerted
    steering (``adaptive.resteered_slots > 0`` somewhere).  The
    ``loop_tail`` workload additionally runs the steered side under
    ``--coverage-target``: its long concrete loop saturates instruction
    coverage after one iteration, so the steered run must latch a
    coverage stop and dispatch FEWER segments (or less wall) than the
    unsteered run that unrolls the tail to exhaustion — the efficiency
    half of the contract.  Mirrors ``devsolver_compare``; one JSON-able
    dict per run.
    """
    from mythril_tpu.adaptive import get_adaptive_controller
    from mythril_tpu.analysis.cooperative import analyze_cooperative
    from mythril_tpu.observability import get_registry
    from mythril_tpu.observability.exploration import get_exploration_ledger
    from mythril_tpu.support.support_args import args as global_args

    def issue_set(per_name):
        return sorted(
            (name, i.swc_id, i.address, i.bytecode_hash)
            for name, issues in per_name.items()
            for i in issues
        )

    suicide = bytes.fromhex("60003560e01c6341c0e1b51460145760006000fd5b33ff")
    gated = bytes.fromhex(
        "60003580600a9010600c57005b80600514601c5780601414601c57005b33ff"
    )
    killbilly = bytes.fromhex(KILLBILLY)
    # selector dispatch to CALLER;SELFDESTRUCT at 0x1e, fallthrough into a
    # 511-iteration concrete counter loop ending in STOP: every
    # instruction except the loop-exit STOP is covered after ONE
    # iteration, so coverage saturates ~8 segments before the unroll ends
    loop_tail = bytes.fromhex(
        "60003560e01c6341c0e1b514601e5760005b600101806102001160115700"
        "5b33ff"
    )
    workloads = [
        # multi-code batches: steering only deviates from FIFO when the
        # seed queue holds distinct codes with different uncovered-edge
        # mass, so single-code runs would trivially (vacuously) pass
        ("exploit_mix",
         [("suicide", suicide), ("gated", gated),
          ("killbilly", killbilly)],
         2, {"106"}, None),
        ("wide_mix",
         [(f"wide{n}", _wide_contract(n)) for n in (3, 4, 5, 6)],
         1, {"106"}, None),
        # the efficiency workload: steered side carries --coverage-target
        ("loop_tail",
         [("loop_tail", loop_tail), ("suicide", suicide)],
         1, {"106"}, 90.0),
    ]

    def one_run(jobs, txs, steered: bool, target=None):
        global_args.adaptive = steered
        global_args.coverage_target = target if steered else None
        _clear_caches()
        get_exploration_ledger().reset_scope()
        ctrl = get_adaptive_controller()
        ctrl.reset_scope()
        reg = get_registry()
        reg.reset(prefix="adaptive.")
        seg_before = reg.counter("frontier.segments").value
        t0 = time.time()
        per_name, _states = analyze_cooperative(
            jobs, transaction_count=txs, execution_timeout=120
        )
        wall = time.time() - t0
        segments = reg.counter("frontier.segments").value - seg_before
        snap = {
            k: v for k, v in reg.snapshot().items()
            if k.startswith("adaptive.")
        }
        return issue_set(per_name), wall, segments, snap, ctrl.stop_state()

    prev = (global_args.adaptive, global_args.coverage_target,
            global_args.frontier, global_args.frontier_force,
            global_args.frontier_width, global_args.pipeline,
            global_args.loop_bound)
    results = {}
    total_resteered = 0
    any_cheaper = False
    try:
        global_args.probe_backend = "auto"
        global_args.frontier = True
        global_args.frontier_force = True  # tiny contracts: bypass gates
        global_args.frontier_width = 64
        global_args.pipeline = True
        # above loop_tail's 511 iterations so the unsteered run unrolls
        # to natural exit — identical config both sides keeps it fair
        global_args.loop_bound = 600
        # warm the XLA programs outside the timers
        one_run([("suicide", suicide)], 1, True)
        for name, jobs, txs, swcs, target in workloads:
            # unsteered first: it pays any residual compile for this
            # batch shape, so the steered wall is steady-state
            off_issues, off_wall, off_segments, off_snap, _ = one_run(
                jobs, txs, False
            )
            on_issues, on_wall, on_segments, on_snap, on_stop = one_run(
                jobs, txs, True, target
            )
            found = {s for _, s, _, _ in on_issues}
            assert swcs <= found, (
                f"{name}: steered run lost recall: wanted {swcs}, "
                f"got {found}"
            )
            assert on_issues == off_issues, (
                f"{name}: adaptive steering changed the issue set "
                f"(parity broken): {on_issues} != {off_issues}"
            )
            assert not off_snap.get("adaptive.resteered_slots", 0), (
                f"{name}: --no-adaptive run still resteered: {off_snap}"
            )
            assert not off_snap.get("adaptive.plans", 0), (
                f"{name}: --no-adaptive run still planned: {off_snap}"
            )
            resteered = on_snap.get("adaptive.resteered_slots", 0)
            total_resteered += resteered
            if target is not None:
                assert on_stop is not None, (
                    f"{name}: --coverage-target {target} never latched a "
                    f"stop verdict (coverage check dead): {on_snap}"
                )
                assert on_segments < off_segments or on_wall < off_wall, (
                    f"{name}: coverage-target stop saved nothing: "
                    f"{on_segments} vs {off_segments} segments, "
                    f"{on_wall:.2f}s vs {off_wall:.2f}s"
                )
            if on_segments < off_segments or on_wall < off_wall:
                any_cheaper = True
            results[name] = {
                "steered_wall_s": round(on_wall, 3),
                "unsteered_wall_s": round(off_wall, 3),
                "steered_segments": int(on_segments),
                "unsteered_segments": int(off_segments),
                "segments_dispatched_delta": int(on_segments - off_segments),
                "resteered_slots": int(resteered),
                "requeued_paths": int(
                    on_snap.get("adaptive.requeued_paths", 0)
                ),
                "issues": len(on_issues),
                "adaptive": on_snap,
                **({"coverage_stop": on_stop} if on_stop else {}),
            }
    finally:
        (global_args.adaptive, global_args.coverage_target,
         global_args.frontier, global_args.frontier_force,
         global_args.frontier_width, global_args.pipeline,
         global_args.loop_bound) = prev
    assert total_resteered > 0, (
        "adaptive controller resteered zero dispatch slots across every "
        f"multi-code workload (steering never engaged): {results}"
    )
    assert any_cheaper, (
        "no workload got cheaper under steering (fewer segments or "
        f"lower wall with resteered_slots > 0): {results}"
    )
    return {"metric": "adaptive_compare", "workloads": results}


def paging_compare() -> dict:
    """Large-code frontier on-vs-off parity on mixed-size batches.

    Runs each workload twice with the device frontier forced on — once
    with per-code bucket isolation + packed-code paging (the defaults),
    once under ``--no-code-paging`` semantics (one corpus-wide bucket,
    everything fully resident) — and asserts the optimization contract:
    the issue sets are BIT-IDENTICAL (paging only changes which window
    of a code is device-resident; a cold jump degrades to an ordinary
    host park, and the host engine is always correct), the isolated run
    actually split the corpus into >1 bucket class with strictly lower
    pad waste than the single-bucket counterfactual, and the paged
    workload actually faulted and repacked at least once.  Mirrors
    ``adaptive_compare``; one JSON-able dict per run."""
    from mythril_tpu.analysis.cooperative import analyze_cooperative
    from mythril_tpu.observability import get_registry
    from mythril_tpu.support.support_args import args as global_args

    def issue_set(per_name):
        return sorted(
            (name, i.swc_id, i.address, i.bytecode_hash)
            for name, issues in per_name.items()
            for i in issues
        )

    suicide = bytes.fromhex("60003560e01c6341c0e1b51460145760006000fd5b33ff")
    gated = bytes.fromhex(
        "60003580600a9010600c57005b80600514601c5780601414601c57005b33ff"
    )
    # mixed-size batches: the parity only bites when small codes share a
    # batch with an outlier big enough to page (deep cold-jump target)
    workloads = [
        ("largecode_mixed",
         [("bigkill", _largecode_contract()), ("suicide", suicide),
          ("gated", gated)],
         2, {"106"}),
        ("two_outliers",
         [("big_a", _largecode_contract(1200)),
          ("big_b", _largecode_contract(2400)), ("suicide", suicide)],
         1, {"106"}),
    ]

    def one_run(jobs, txs, paged: bool):
        global_args.code_paging = paged
        _clear_caches()
        reg = get_registry()
        before = (
            reg.counter("frontier.page_faults").value,
            reg.counter("frontier.page_repacks").value,
        )
        t0 = time.time()
        per_name, _states = analyze_cooperative(
            jobs, transaction_count=txs, execution_timeout=180
        )
        wall = time.time() - t0
        snap = {
            "bucket_classes": int(
                reg.gauge("frontier.bucket_classes").value or 0),
            "pad_waste_pct": float(
                reg.gauge("frontier.pad_waste_pct").value or 0.0),
            "pad_waste_single_bucket_pct": float(reg.gauge(
                "frontier.pad_waste_single_bucket_pct").value or 0.0),
            "page_faults": int(
                reg.counter("frontier.page_faults").value - before[0]),
            "page_repacks": int(
                reg.counter("frontier.page_repacks").value - before[1]),
        }
        return issue_set(per_name), wall, snap

    prev = (global_args.code_paging, global_args.frontier,
            global_args.frontier_force, global_args.frontier_width,
            global_args.pipeline)
    results = {}
    total_faults = 0
    try:
        global_args.probe_backend = "auto"
        global_args.frontier = True
        global_args.frontier_force = True  # tiny members: bypass gates
        global_args.frontier_width = 64
        global_args.pipeline = True
        # warm the XLA programs outside the timers
        one_run([("suicide", suicide)], 1, True)
        for name, jobs, txs, swcs in workloads:
            off_issues, off_wall, off_snap = one_run(jobs, txs, False)
            on_issues, on_wall, on_snap = one_run(jobs, txs, True)
            found = {s for _, s, _, _ in on_issues}
            assert swcs <= found, (
                f"{name}: paged run lost recall: wanted {swcs}, got {found}"
            )
            assert on_issues == off_issues, (
                f"{name}: bucket isolation / paging changed the issue set "
                f"(parity broken): {on_issues} != {off_issues}"
            )
            assert not off_snap["bucket_classes"], (
                f"{name}: --no-code-paging run still clustered bucket "
                f"classes: {off_snap}"
            )
            assert on_snap["bucket_classes"] > 1, (
                f"{name}: mixed-size batch did not split into >1 bucket "
                f"class: {on_snap}"
            )
            assert (on_snap["pad_waste_pct"]
                    < on_snap["pad_waste_single_bucket_pct"]), (
                f"{name}: per-class pad waste not below the single-bucket "
                f"counterfactual: {on_snap}"
            )
            total_faults += on_snap["page_faults"]
            results[name] = {
                "paged_wall_s": round(on_wall, 3),
                "unpaged_wall_s": round(off_wall, 3),
                "issues": len(on_issues),
                "identical_issue_sets": True,
                **on_snap,
            }
    finally:
        (global_args.code_paging, global_args.frontier,
         global_args.frontier_force, global_args.frontier_width,
         global_args.pipeline) = prev
    assert total_faults > 0, (
        "no workload ever page-faulted (the paged window never engaged "
        f"on the deep cold-jump outliers): {results}"
    )
    return {"metric": "paging_compare", "workloads": results}


def mesh_compare() -> dict:
    """Sharded-pipelined vs single-device parity across every mesh ×
    pipeline combination.

    Runs each workload four times with the device frontier forced on —
    ``--no-mesh``/``--no-pipeline`` toggled independently — and asserts the
    correctness contract: all four issue sets are IDENTICAL, the pipelined
    runs actually chained segments, and (with >1 attached device) the
    mesh runs really executed path-sharded with per-shard delta-pull bytes
    attributed to every shard.  This is the pod parity smoke CI runs under
    ``XLA_FLAGS=--xla_force_host_platform_device_count=8``; returns (and
    ``main`` prints) one JSON-able dict."""
    import jax

    from mythril_tpu.frontend.evmcontract import EVMContract
    from mythril_tpu.frontier import engine as _eng
    from mythril_tpu.frontier.stats import FrontierStatistics
    from mythril_tpu.observability import get_registry
    from mythril_tpu.support.support_args import args as global_args

    def issue_set(issues):
        return sorted((i.swc_id, i.address) for i in issues)

    n_dev = jax.device_count()
    suicide = bytes.fromhex("60003560e01c6341c0e1b51460145760006000fd5b33ff")
    workloads = [
        # (name, contract-or-code, tx_count, modules, recall swc)
        ("suicide", suicide, 1, ["AccidentallyKillable"], "106"),
        ("killbilly",
         EVMContract(code=KILLBILLY, creation_code=KILLBILLY_CREATION,
                     name="KillBilly"),
         3, ["AccidentallyKillable"], "106"),
    ]
    # (mesh, pipeline): the four escape-hatch combinations of the
    # acceptance contract, sharded-pipelined first
    combos = [(True, True), (True, False), (False, True), (False, False)]

    def one_run(target, txs, modules, mesh_on: bool, pipelined: bool):
        global_args.frontier_mesh = mesh_on
        global_args.pipeline = pipelined
        _clear_caches()
        # per-code slow/narrow verdicts and warm markers are deliberately
        # process-persistent; they must not leak control flow across modes
        _eng._SLOW_CODES.clear()
        _eng._NARROW_CODES.clear()
        _eng._SLOW_SEGMENTS.clear()
        reg = get_registry()
        reg.reset(prefix="pipeline.")
        fstats = FrontierStatistics()
        fstats.mesh_devices = 0
        t0 = time.time()
        _, issues = _analyze(target, 0x0901D12E, txs, modules=modules,
                             timeout=300)
        wall = time.time() - t0
        snap = {
            k: v
            for k, v in reg.snapshot().items()
            if k.startswith("pipeline.")
        }
        ttfe = _ttfe(issues, t0)
        return {
            "issues": issue_set(issues),
            "wall_s": round(wall, 3),
            "ttfe_s": round(ttfe, 3) if ttfe == ttfe else None,
            "mesh_devices": int(fstats.mesh_devices),
            "pipeline": snap,
        }

    prev = (global_args.pipeline, global_args.frontier_mesh,
            global_args.frontier, global_args.frontier_force,
            global_args.frontier_width)
    results = {}
    try:
        global_args.probe_backend = "auto"
        global_args.frontier = True
        global_args.frontier_force = True  # tiny contracts: bypass gates
        global_args.frontier_width = 64
        # warm every program variant outside the timers (the sharded and
        # single-device placements lower to different XLA programs)
        for mesh_on, pipelined in combos:
            one_run(suicide, 1, ["AccidentallyKillable"], mesh_on, pipelined)
        for name, target, txs, modules, swc in workloads:
            runs = {}
            for mesh_on, pipelined in combos:
                key = "mesh=%s,pipeline=%s" % (
                    "on" if mesh_on else "off",
                    "on" if pipelined else "off",
                )
                runs[key] = (
                    mesh_on, pipelined,
                    one_run(target, txs, modules, mesh_on, pipelined),
                )
            ref = runs["mesh=off,pipeline=on"][2]
            assert any(s == swc for s, _ in ref["issues"]), (
                f"{name}: single-device pipelined run lost recall: "
                f"{ref['issues']}"
            )
            for key, (mesh_on, pipelined, r) in runs.items():
                assert r["issues"] == ref["issues"], (
                    f"{name} [{key}]: issue set diverged: "
                    f"{r['issues']} != {ref['issues']}"
                )
                seg_p = r["pipeline"].get("pipeline.segments_pipelined", 0)
                if pipelined:
                    assert seg_p > 0, (
                        f"{name} [{key}]: pipelined run chained zero "
                        f"segments: {r['pipeline']}"
                    )
                else:
                    assert seg_p == 0, (
                        f"{name} [{key}]: --no-pipeline run still "
                        f"pipelined: {r['pipeline']}"
                    )
                if mesh_on and n_dev > 1:
                    assert r["mesh_devices"] == n_dev, (
                        f"{name} [{key}]: mesh run used "
                        f"{r['mesh_devices']} devices, expected {n_dev}"
                    )
                else:
                    assert r["mesh_devices"] == 0, (
                        f"{name} [{key}]: --no-mesh run placed on a mesh"
                    )
            if n_dev > 1:
                pod = runs["mesh=on,pipeline=on"][2]["pipeline"]
                assert pod.get("pipeline.delta_pulls", 0) > 0, (
                    f"{name}: sharded-pipelined run never delta-pulled: "
                    f"{pod}"
                )
                by_shard = pod.get(
                    "pipeline.delta_pull_bytes_by_shard", {}
                )
                assert len(by_shard) == n_dev and all(
                    v > 0 for v in by_shard.values()
                ), (
                    f"{name}: per-shard delta-pull attribution incomplete "
                    f"over {n_dev} devices: {by_shard}"
                )
            results[name] = {k: r for k, (_, _, r) in runs.items()}
    finally:
        (global_args.pipeline, global_args.frontier_mesh,
         global_args.frontier, global_args.frontier_force,
         global_args.frontier_width) = prev
    return {
        "metric": "mesh_compare",
        "n_devices": n_dev,
        "workloads": results,
    }


_HARVEST_PHASES = ("ingest", "solver", "replay", "commit")

# device-plane counters delta'd around every bench rep (persistent=True:
# they survive the per-analysis registry reset between runs)
_DEVICE_PLANE_COUNTERS = (
    "device.compile_wall_s_total",
    "device.recompiles_total",
    "device.shape_churn_total",
)


def harvest_compare() -> dict:
    """Sharded vs serial harvest on a multi-tx and a fork-heavy workload.

    Runs each workload twice with the device frontier forced on — once with
    the sharded harvest executor (``--harvest-workers 4``), once serial
    (``--harvest-workers 0``) — and asserts the correctness contract: the
    issue sets are IDENTICAL while the sharded run actually dispatched
    replays to the pool.  Reports per-mode walls, states/sec, the harvest
    wall share, and the per-phase ``frontier.harvest.*_s`` attribution that
    says where the remaining harvest time goes.  Returns (and ``main``
    prints) one JSON-able dict."""
    from mythril_tpu.frontend.evmcontract import EVMContract
    from mythril_tpu.frontier import engine as _eng
    from mythril_tpu.frontier.stats import FrontierStatistics
    from mythril_tpu.observability import get_registry
    from mythril_tpu.support.support_args import args as global_args

    def issue_set(issues):
        return sorted((i.swc_id, i.address) for i in issues)

    workloads = [
        # (name, contract-or-code, tx_count, modules, recall swc)
        ("killbilly",
         EVMContract(code=KILLBILLY, creation_code=KILLBILLY_CREATION,
                     name="KillBilly"),
         3, ["AccidentallyKillable"], "106"),
        # 256 concurrent fork-chained paths: the harvest-bound shape
        ("wide_fork", _wide_contract(8), 1, ["AccidentallyKillable"], "106"),
    ]

    def one_run(target, txs, modules, workers: int):
        global_args.harvest_workers = workers
        _clear_caches()
        _eng._SLOW_CODES.clear()
        _eng._NARROW_CODES.clear()
        _eng._SLOW_SEGMENTS.clear()
        reg = get_registry()
        fstats = FrontierStatistics()
        har_before = fstats.harvest_s
        phases_before = {
            p: reg.histogram("frontier.harvest.%s_s" % p).sum
            for p in _HARVEST_PHASES
        }
        sharded_before = reg.counter("frontier.harvest.sharded_paths").value
        t0 = time.time()
        sym, issues = _analyze(target, 0x0901D12E, txs, modules=modules,
                               timeout=300)
        wall = time.time() - t0
        phases = {
            p: round(
                reg.histogram("frontier.harvest.%s_s" % p).sum
                - phases_before[p], 4,
            )
            for p in _HARVEST_PHASES
        }
        return {
            "issues": issue_set(issues),
            "wall_s": round(wall, 3),
            "states_per_sec": round(sym.laser.total_states / wall, 1)
            if wall > 0 else 0.0,
            "harvest_share_pct": round(
                100 * (fstats.harvest_s - har_before) / wall, 1
            ) if wall > 0 else 0.0,
            "harvest_phase_s": phases,
            "sharded_paths": int(
                reg.counter("frontier.harvest.sharded_paths").value
                - sharded_before
            ),
        }

    prev = (global_args.harvest_workers, global_args.frontier,
            global_args.frontier_force, global_args.frontier_width)
    results = {}
    try:
        global_args.probe_backend = "auto"
        global_args.frontier = True
        global_args.frontier_force = True  # small contracts: bypass gates
        global_args.frontier_width = 64
        # warm the jitted programs outside the timers (both modes run the
        # SAME device program; only the host harvest differs)
        one_run(_wide_contract(4), 1, ["AccidentallyKillable"], 4)
        for name, target, txs, modules, swc in workloads:
            sharded = one_run(target, txs, modules, 4)
            serial = one_run(target, txs, modules, 0)
            assert any(s == swc for s, _ in sharded["issues"]), (
                f"{name}: sharded harvest lost recall: {sharded['issues']}"
            )
            assert sharded["issues"] == serial["issues"], (
                f"{name}: sharded harvest changed the issue set: "
                f"{sharded['issues']} != {serial['issues']}"
            )
            assert sharded["sharded_paths"] > 0, (
                f"{name}: sharded run never dispatched a replay shard"
            )
            assert serial["sharded_paths"] == 0, (
                f"{name}: serial run used the replay pool"
            )
            results[name] = {
                "sharded": sharded,
                "serial": serial,
                "speedup": round(
                    sharded["states_per_sec"]
                    / max(serial["states_per_sec"], 1e-9), 3,
                ),
            }
    finally:
        (global_args.harvest_workers, global_args.frontier,
         global_args.frontier_force, global_args.frontier_width) = prev
    return {"metric": "harvest_compare", "workloads": results}


# ---------------------------------------------------------------------------
# workloads
# ---------------------------------------------------------------------------


def _corpus_dir() -> Path:
    return REFERENCE_INPUTS if REFERENCE_INPUTS.is_dir() else LOCAL_INPUTS


def _read_runtime(path: Path) -> bytes:
    return bytes.fromhex(path.read_text().strip().replace("0x", ""))


class WorkloadSkip(Exception):
    """A workload's inputs are not mounted in this environment.  The driver
    drops the row (it never reaches the table, and the regression gate
    treats absent rows as skipped) instead of killing the whole suite —
    a corpus-less container still gets the synthetic rows and the gate."""


def wl_suicide(production: bool):
    _configure(production)
    path = _corpus_dir() / "suicide.sol.o"
    if not path.exists():  # fall back to the killbilly kill body
        code = bytes.fromhex("60003560e01c6341c0e1b51460145760006000fd5b33ff")
    else:
        code = _read_runtime(path)
    # the analysis completes in ~0.1-0.3 s, where scheduler jitter alone
    # swings single measurements 30%+: sum three consecutive analyses per
    # sample so the row's medians measure the engine, not the OS
    states, t0, ttfe = 0, time.time(), float("nan")
    for _ in range(3):
        _clear_caches()
        t_one = time.time()
        sym, issues = _analyze(code, 0x0901D12E, 1, modules=["AccidentallyKillable"])
        assert any(i.swc_id == "106" for i in issues), "suicide recall lost"
        states += sym.laser.total_states
        if ttfe != ttfe:
            ttfe = _ttfe(issues, t_one, "106")
    return states, time.time() - t0, ttfe


def wl_killbilly(production: bool):
    _configure(production)
    t0 = time.time()
    sym, issues, wall = run_analysis("auto" if production else "host")
    check_recall(issues)
    return sym.laser.total_states, wall, _ttfe(issues, t0, "106")


def wl_overflow(production: bool):
    _configure(production)
    states, t0 = 0, time.time()
    found = set()
    ran = 0
    ttfe = float("nan")
    for name in ("overflow.sol.o", "underflow.sol.o"):
        path = _corpus_dir() / name
        if not path.exists():
            continue
        ran += 1
        _clear_caches()
        t_file = time.time()
        sym, issues = _analyze(
            _read_runtime(path), 0x0901D12E, 2, modules=["IntegerArithmetics"]
        )
        states += sym.laser.total_states
        found |= {i.swc_id for i in issues}
        file_ttfe = _ttfe(issues, t_file, "101")
        if file_ttfe == file_ttfe and not ttfe == ttfe:
            ttfe = file_ttfe
    if ran:
        assert "101" in found, "integer overflow recall lost"
    return states, time.time() - t0, ttfe


def _wide_contract(n_branches: int) -> bytes:
    """n independent symbolic branches that immediately reconverge (2^n
    surviving paths) followed by an unprotected SELFDESTRUCT — the
    frontier-width workload the batched device interpreter is built for."""
    out = b""
    for k in range(n_branches):
        # PUSH1 k; CALLDATALOAD; PUSH1 1; AND; PUSH2 dest; JUMPI; JUMPDEST
        dest = len(out) + 10
        out += bytes([0x60, k, 0x35, 0x60, 0x01, 0x16,
                      0x61, (dest >> 8) & 0xFF, dest & 0xFF, 0x57, 0x5B])
    return out + bytes([0x33, 0xFF])  # CALLER; SELFDESTRUCT


def wl_wide_frontier(production: bool):
    """1024 concurrent paths, the batched device interpreter's home turf:
    the whole state space executes as ONE device segment at width 1024."""
    from mythril_tpu.support.support_args import args

    global _wide_warmed
    _configure(production)
    old_width = args.frontier_width
    if production:
        args.frontier_width = 1024
        # device-only efficiency block (VERDICT r4 #7): first productive
        # segment measures pure compute via chained-dispatch subtraction
        args.frontier_microbench = True
        if not _wide_warmed:
            # warmup outside the timers: the segment program compiles once
            # per (caps, size bucket) (persistently cached when the XLA
            # cache cooperates) — a one-time cost that would swamp this
            # workload; once per process, not per rep
            _clear_caches()
            _analyze(
                _wide_contract(10), 0x0901D12E, 1,
                modules=["AccidentallyKillable"], timeout=300,
            )
            _wide_warmed = True
    try:
        _clear_caches()
        from mythril_tpu.frontier.stats import FrontierStatistics

        fstats = FrontierStatistics()
        dev_before = fstats.device_instructions
        har_before = fstats.harvest_s
        mid_before = _mid_counters(fstats)
        code = _wide_contract(10)  # 1024 concurrent paths
        t0 = time.time()
        sym, issues = _analyze(
            code, 0x0901D12E, 1, modules=["AccidentallyKillable"], timeout=300
        )
        wall = time.time() - t0
        # residency/harvest/mid-frame over the TIMED run only (the warm-up
        # above also runs device segments and harvests)
        dev_delta = fstats.device_instructions - dev_before
        har_delta = fstats.harvest_s - har_before
        mid_delta = _mid_delta(fstats, mid_before)
    finally:
        args.frontier_width = old_width
        args.frontier_microbench = False
    assert any(i.swc_id == "106" for i in issues), "wide-frontier recall lost"
    return (
        sym.laser.total_states, wall, _ttfe(issues, t0, "106"),
        dev_delta if production else None,
        har_delta if production else None,
        float("nan"),  # no ttfr channel for this workload
        mid_delta if production else None,
    )


# if (calldataload(0) == 5) storage[0] = 1 else storage[0] = 2
_FLIP_CODE = "600035600514600f576002600055005b600160005500"
_FLIP_JUMPI = 8


def wl_concolic(production: bool):
    _configure(production)
    _clear_caches()  # both configs must solve the flip from scratch
    from mythril_tpu.concolic.concolic_execution import concolic_execution

    contract = "0x" + "ab" * 20
    data = {
        "initialState": {
            "accounts": {
                contract: {
                    "balance": "0x0",
                    "code": "0x" + _FLIP_CODE,
                    "nonce": 0,
                    "storage": {},
                }
            }
        },
        "steps": [
            {
                "address": contract,
                "blockCoinbase": "0x" + "00" * 20,
                "blockDifficulty": "0x0",
                "blockGasLimit": "0x989680",
                "blockNumber": "0x1",
                "blockTime": "0x1",
                "gasLimit": "0x100000",
                "gasPrice": "0x0",
                "input": "0x" + "00" * 32,
                "origin": "0x" + "cd" * 20,
                "value": "0x0",
            }
        ],
    }
    t0 = time.time()
    flips = 0
    for _ in range(3):
        _clear_caches()  # every rep must solve the flip from scratch
        results = concolic_execution(data, [_FLIP_JUMPI], solver_timeout=30000)
        assert len(results) == 1, "branch flip failed"
        word = int(results[0]["steps"][0]["input"][2:66].ljust(64, "0"), 16)
        assert word == 5, "flipped input does not take the other branch"
        flips += 1
    return flips, time.time() - t0, float("nan")


def wl_bectoken(production: bool):
    """BECToken batchTransfer (CVE-2018-10299, config 3's real
    shape): a hand-assembled ERC20 with the unchecked ``cnt * _value``
    multiply, SafeMath everywhere else, keccak-mapped balances and a
    symbolic-length receiver loop (bench_contracts.py — no solc in the
    image, matching /root/reference/solidity_examples/BECToken.sol:255-268).
    Width comes from the dispatcher x requires x loop x 2-tx crossing."""
    from bench_contracts import bectoken_like

    _configure(production)  # production width 256 = the warmed bucket
    _clear_caches()
    t0 = time.time()
    sym, issues = _analyze(
        bectoken_like(), 0x0901D12E, 2,
        modules=["IntegerArithmetics"], timeout=120,
    )
    assert any(i.swc_id == "101" for i in issues), "batchTransfer recall lost"
    return sym.laser.total_states, time.time() - t0, _ttfe(issues, t0, "101")


def _largecode_contract(n_pad: int = 1500) -> bytes:
    """A creation-heavy-shaped outlier: selector dispatch to a reachable
    CALLER;SELFDESTRUCT whose JUMPDEST sits BEYOND a long straight-line
    pad tail (``n_pad`` PUSH1/POP pairs, ~``2*n_pad`` instructions).  The
    instruction count blows past the smallest size bucket (and, at the
    default residency budget, past the paged window), so the vulnerable
    jump is a cold-page jump: exactly the shape that inflated
    bectoken_batch's shared bucket in BENCH_r19."""
    sel = 0x41C0E1B5  # kill()
    tail = bytes([0x60, 0x00, 0x50]) * n_pad + bytes([0x00])  # pads + STOP
    dest = 16 + len(tail)
    assert dest < 0x10000
    head = bytes([
        0x60, 0x00, 0x35,                     # PUSH1 0; CALLDATALOAD
        0x60, 0xE0, 0x1C,                     # PUSH1 0xE0; SHR
        0x63, (sel >> 24) & 0xFF, (sel >> 16) & 0xFF,
        (sel >> 8) & 0xFF, sel & 0xFF,        # PUSH4 kill()
        0x14,                                 # EQ
        0x61, (dest >> 8) & 0xFF, dest & 0xFF,  # PUSH2 dest
        0x57,                                 # JUMPI
    ])
    assert len(head) == 16
    return head + tail + bytes([0x5B, 0x33, 0xFF])  # JUMPDEST;CALLER;SELFDESTRUCT


def wl_largecode(production: bool):
    """Large-code mixed batch: one pad-tail outlier (~3000 instructions)
    next to three small real-shape codes in ONE cooperative batch — the
    corpus shape whose shared size bucket collapsed bectoken_batch in
    BENCH_r19.  Production runs with bucket isolation + packed-code
    paging on (the defaults); baseline is the sequential host schedule.
    Recall asserted on the outlier's deep SELFDESTRUCT (the cold-page
    jump) and on the small members."""
    from bench_contracts import rubixi_like

    suicide = bytes.fromhex("60003560e01c6341c0e1b51460145760006000fd5b33ff")
    gated = bytes.fromhex(
        "60003580600a9010600c57005b80600514601c5780601414601c57005b33ff"
    )
    jobs = [
        ("bigkill", _largecode_contract()),
        ("suicide", suicide),
        ("gated", gated),
        ("rubixi", rubixi_like()),
    ]
    expected = {"bigkill": "106", "suicide": "106", "rubixi": "105"}

    _configure(production)
    if production:
        from mythril_tpu.support.support_args import args

        args.frontier_force = True  # tiny members: bypass the narrow gate
        try:
            (per_name, states, wall, t0, dev_delta, har_delta,
             mid_delta) = _cooperative_timed_run(jobs, "largecode_mixed")
        finally:
            args.frontier_force = False
    else:
        per_name = {}
        states = 0
        t0 = time.time()
        for name, code in jobs:
            _clear_caches()
            sym, issues = _analyze(code, 0x0901D12E, 2, timeout=120)
            states += sym.laser.total_states
            per_name[name] = issues
        wall = time.time() - t0
        dev_delta = har_delta = mid_delta = None

    for name, swc in expected.items():
        got = {i.swc_id for i in per_name.get(name, [])}
        assert swc in got, (
            f"largecode_mixed recall lost: {name} missing SWC-{swc}"
        )
    all_issues = [i for iss in per_name.values() for i in iss]
    ttfe = _ttfe(
        [i for i in all_issues if i.swc_id in set(expected.values())], t0
    )
    return (
        states, wall, ttfe, dev_delta, har_delta,
        _ttfr(per_name, t0, expected), mid_delta,
    )


# The real-bytecode device flagship (VERDICT r4 #4): the call-free solc
# contracts run as ONE cooperative multi-code batch with multi-selector
# seeding (core/transaction/symbolic.seed_message_call) — the work list
# starts |selectors|+1 wide per contract, so the width-256 device segment
# is saturated with REAL solc dispatch/require/arithmetic code from the
# first round.  Call-free members only: CALL-family ops park semantically,
# and this row's point is device residency on real bytecode.
WIDE_SOLC_NAMES = [
    "underflow.sol.o",
    "overflow.sol.o",
    "ether_send.sol.o",
    "exceptions.sol.o",
    "metacoin.sol.o",
    "origin.sol.o",
    "suicide.sol.o",
    "safe_funcs.sol.o",
    "environments.sol.o",
    "symbolic_exec_bytecode.sol.o",
]
WIDE_SOLC_RECALL = {
    "underflow.sol.o": "101",
    "overflow.sol.o": "101",
    "ether_send.sol.o": "105",
    "exceptions.sol.o": "110",
    "metacoin.sol.o": "101",
    "origin.sol.o": "115",
    "suicide.sol.o": "106",
    "safe_funcs.sol.o": "110",
    "environments.sol.o": "101",
}

_coop_warmed: set = set()


def _cooperative_timed_run(jobs, bucket_key: str, timeout: int = 120):
    """Warm this job set's segment-program bucket once per process (outside
    any timer), then run the tx-2 cooperative analysis timed.  Returns
    (per_name, states, wall, t0, dev_delta, har_delta, mid_delta) with the
    telemetry deltas covering the TIMED run only."""
    from mythril_tpu.analysis.cooperative import analyze_cooperative
    from mythril_tpu.frontier.stats import FrontierStatistics

    if bucket_key not in _coop_warmed:
        _clear_caches()
        analyze_cooperative(jobs, transaction_count=1, execution_timeout=20)
        _coop_warmed.add(bucket_key)
    _clear_caches()
    fstats = FrontierStatistics()
    dev_before = fstats.device_instructions
    har_before = fstats.harvest_s
    mid_before = _mid_counters(fstats)
    t0 = time.time()
    per_name, states = analyze_cooperative(
        jobs, transaction_count=2, execution_timeout=timeout
    )
    wall = time.time() - t0
    return (
        per_name, states, wall, t0,
        fstats.device_instructions - dev_before,
        fstats.harvest_s - har_before,
        _mid_delta(fstats, mid_before),
    )


def wl_wide_solc(production: bool):
    """Wide frontier from REAL solc bytecode (the answer to 'the flagship
    win is synthetic').  Baseline: the reference's natural schedule — one
    contract at a time, single symbolic seed, host engine.  Production: one
    cooperative device batch over the same contracts with the selector
    space partitioned per seed.  Same issues must be found either way
    (asserted per contract); states/sec at equal recall is the metric."""
    from mythril_tpu.support.support_args import args

    corpus_dir = _corpus_dir()
    jobs = [
        (n, _read_runtime(corpus_dir / n))
        for n in WIDE_SOLC_NAMES
        if (corpus_dir / n).exists()
    ]
    if len(jobs) < 4:
        raise WorkloadSkip("wide_solc corpus inputs not mounted")
    expected = {n: swc for n, swc in WIDE_SOLC_RECALL.items()
                if any(n == name for name, _ in jobs)}

    _configure(production)
    if production:
        args.multi_selector_seeding = True
        try:
            (per_name, states, wall, t0, dev_delta, har_delta,
             mid_delta) = _cooperative_timed_run(jobs, "wide_solc")
        finally:
            args.multi_selector_seeding = False
    else:
        per_name = {}
        states = 0
        t0 = time.time()
        for name, code in jobs:
            _clear_caches()
            sym, issues = _analyze(code, 0x0901D12E, 2, timeout=120)
            states += sym.laser.total_states
            per_name[name] = issues
        wall = time.time() - t0
        dev_delta = har_delta = mid_delta = None

    for name, swc in expected.items():
        got = {i.swc_id for i in per_name.get(name, [])}
        assert swc in got, f"wide_solc recall lost: {name} missing SWC-{swc}"
    all_issues = [i for iss in per_name.values() for i in iss]
    ttfe = _ttfe(
        [i for i in all_issues if i.swc_id in set(expected.values())], t0
    )
    return (
        states, wall, ttfe, dev_delta, har_delta,
        _ttfr(per_name, t0, expected), mid_delta,
    )


# known-vulnerable subset of the corpus: file -> SWC id that must be found
CORPUS_RECALL = {
    "suicide.sol.o": "106",
    "overflow.sol.o": "101",
    "underflow.sol.o": "101",
    "ether_send.sol.o": "105",
    "origin.sol.o": "115",
    "exceptions.sol.o": "110",
    # hand-assembled real exploit shapes (bench_contracts.py): the
    # etherstore reentrancy window and rubixi's ownership-takeover drain
    # run as ordinary corpus members in BOTH schedulings
    "etherstore.asm": "107",
    "rubixi.asm": "105",
}


def _assembled_corpus():
    """Real-shape members assembled in-repo (no solc in the image):
    (name, runtime bytecode) pairs matching the reference contracts at
    /root/reference/solidity_examples/{etherstore,rubixi}.sol."""
    from bench_contracts import etherstore_like, rubixi_like

    return [
        ("etherstore.asm", etherstore_like()),
        ("rubixi.asm", rubixi_like()),
    ]


_wide_warmed = False


def _ttfr(per_name, t0: float, expected=None) -> float:
    """Time-to-FULL-recall: wall seconds until EVERY expected corpus
    exploit has been discovered (max over contracts of the earliest
    matching stamp).  First-exploit TTFE structurally favors the
    sequential schedule (contract #1 confirms before contract #2 even
    starts); full recall is what a corpus user actually waits for, and is
    where the cooperative lockstep schedule can win."""
    from mythril_tpu.analysis.report import StartTime

    if expected is None:
        expected = CORPUS_RECALL
    base = StartTime().global_start_time
    latest = None
    for name, swc in expected.items():
        issues = per_name.get(name)
        if issues is None:
            continue  # contract lives on another shard
        stamps = [i.discovery_time for i in issues if i.swc_id == swc]
        if not stamps:
            return float("nan")
        first = min(stamps)
        latest = first if latest is None else max(latest, first)
    if latest is None:
        return float("nan")
    return _rebase_stamp(base + latest, t0)


def _mid_counters(fstats):
    return (
        fstats.mid_injections,
        fstats.mid_encode_failures,
        fstats.semantic_parks,
    )


def _mid_delta(fstats, before):
    after = _mid_counters(fstats)
    return tuple(a - b for a, b in zip(after, before))


def _rebase_stamp(wall: float, t0: float, eps: float = 0.05) -> float:
    """Rebase an absolute discovery stamp against this run's start.  A stamp
    meaningfully BEFORE t0 means the issue was served from a warm/cache path
    rather than discovered by this run — report NaN so the measurement bug
    surfaces instead of a silent perfect 0s."""
    delta = wall - t0
    if delta < -eps:
        return float("nan")
    return max(0.0, delta)


def wl_corpus(production: bool):
    """THE HEADLINE: the whole reference corpus.  Baseline analyzes one
    contract at a time (the reference's corpus flow, mythril_analyzer.py:
    138-175); production runs this shard's slice COOPERATIVELY — lockstep tx
    rounds whose combined seeds execute as one wide multi-code device
    segment (analysis/cooperative.py).  Recall is asserted over the UNION of
    shard findings (single-host: everything; multi-host launches return
    shard-local findings for the driver to union via assert_corpus_recall)."""
    _configure(production)
    from mythril_tpu.parallel.corpus import (
        assert_corpus_recall,
        run_corpus,
        shard_corpus,
        shard_identity,
    )

    corpus = sorted(p for g in CORPUS_GLOBS for p in _corpus_dir().glob(g))
    if not corpus:
        raise WorkloadSkip("no corpus inputs found")
    all_issues = []

    if production:
        mine = shard_corpus([str(p) for p in corpus])
        jobs = [(Path(p).name, _read_runtime(Path(p))) for p in mine]
        if shard_identity()[0] == 0:
            jobs += _assembled_corpus()
        (issues_by_name, states, wall, t0, dev_delta, har_delta,
         mid_delta) = _cooperative_timed_run(jobs, "corpus", timeout=60)
        findings = [
            (name, {i.swc_id for i in issues})
            for name, issues in issues_by_name.items()
        ]
        all_issues = [i for iss in issues_by_name.values() for i in iss]
    else:
        totals = {"states": 0}
        issue_lists = {}

        def analyze_one(path):
            _clear_caches()
            sym, issues = _analyze(
                _read_runtime(Path(path)), 0x0901D12E, 2, timeout=60
            )
            totals["states"] += sym.laser.total_states
            issue_lists[Path(path).name] = issues
            return {i.swc_id for i in issues}

        t0 = time.time()
        results = run_corpus([str(p) for p in corpus], analyze_one)
        findings = [(Path(p).name, res) for p, res in results]
        # the assembled real shapes run sequentially here exactly like the
        # file-backed members do (one contract at a time, the reference's
        # corpus flow); shard 0 only, mirroring the production branch
        assembled = _assembled_corpus() if shard_identity()[0] == 0 else []
        for name, code in assembled:
            _clear_caches()
            sym, issues = _analyze(code, 0x0901D12E, 2, timeout=60)
            totals["states"] += sym.laser.total_states
            issue_lists[name] = issues
            findings.append((name, {i.swc_id for i in issues}))
        wall = time.time() - t0
        states = totals["states"]
        all_issues = [i for iss in issue_lists.values() for i in iss]

    _idx, cnt = shard_identity()
    shard_names = {name for name, _ in findings}
    expected = (
        CORPUS_RECALL
        if cnt == 1
        # multi-host: this process can only vouch for its own slice; the
        # launcher unions the returned findings via assert_corpus_recall
        else {k: v for k, v in CORPUS_RECALL.items() if k in shard_names}
    )
    assert_corpus_recall([findings], expected)
    ttfe = _ttfe(
        [i for i in all_issues if i.swc_id in set(CORPUS_RECALL.values())], t0
    )
    per_name = issues_by_name if production else issue_lists
    return (
        states,
        wall,
        ttfe,
        (dev_delta if production else None),
        (har_delta if production else None),
        _ttfr(per_name, t0),
        (mid_delta if production else None),
    )


def serve_load(clients: int = 8, workers: int = 1) -> dict:
    """Analysis-as-a-service under synthetic traffic (bench.py --serve-load).

    ``clients`` concurrent submitters cycle over a small in-repo contract
    set (duplicates by construction, so admission dedup is exercised) and
    the run asserts the service's three production claims:

    1. determinism — every request's issue-digest multiset is bit-identical
       to a solo one-shot run of the same contract under the same options;
    2. throughput — the warm process serving all requests concurrently
       beats sequential one-shot submission of the SAME requests;
    3. dedup — duplicate submissions share one analysis (dedup_hits > 0).

    Emits a ``workloads.serve_load`` row (requests/sec + service ttfe_s)
    shaped exactly like the suite's rows, so ``--against`` gates service
    throughput and TTFE with zero gate changes.

    With ``workers > 1`` a second measured window replays the SAME
    traffic against a horizontal pool of N worker processes and emits a
    ``workloads.serve_pool`` row: baseline = the single-worker rate just
    measured, production = the pool rate.  Digest identity to solo runs
    is asserted unconditionally; the speedup assertion is gated on
    ``os.cpu_count()`` — on a single-core container N processes time-
    slice one core and the pool physically cannot exceed 1x, so the
    scaling claim is only *asserted* where the hardware can express it
    (the same CPU-CI caveat the frontier rows carry).
    """
    import tempfile
    import threading

    from mythril_tpu.analysis.cooperative import run_cooperative_batch
    from mythril_tpu.facade.warm import reset_analysis_scope
    from mythril_tpu.observability.metrics import get_registry
    from mythril_tpu.service import (
        AnalysisOptions,
        AnalysisService,
        ServiceConfig,
    )
    from mythril_tpu.service.codehash import issue_digest
    from bench_contracts import etherstore_like, rubixi_like

    opts = AnalysisOptions(transaction_count=2, execution_timeout=60)
    contracts = [
        ("killbilly", bytes.fromhex(KILLBILLY)),
        ("etherstore", etherstore_like()),
        ("rubixi", rubixi_like()),
        ("wide4", _wide_contract(4)),
    ]
    # clients cycle over the contract set: with clients > len(contracts)
    # the duplicate-submission path is exercised by construction
    requests = [
        (f"client{i}", *contracts[i % len(contracts)],
         "interactive" if i % 4 == 0 else "batch")
        for i in range(clients)
    ]

    # Host engine on BOTH sides: this bench host simulates device segments
    # with wall-clock linear in batch width, so pooling contracts into wide
    # shared segments is a pessimization HERE (it is the win on real
    # hardware, and the frontier workloads above measure it).  Pinning the
    # host path isolates what serve-load is actually testing — the service
    # layer: admission dedup, warm-process reuse, shared scheduling — under
    # an identical engine for baseline and production.
    _configure(False)

    # -- solo ground truth + sequential one-shot baseline ---------------
    # each request is submitted as its own cold one-shot analysis (the
    # pre-service corpus flow): per-request cache clear, one contract,
    # one run.  The XLA compile cache cannot be un-warmed in-process,
    # which only FLATTERS this baseline — the warm-vs-sequential margin
    # below is therefore conservative.
    solo_digests = {}
    solo_ttfes = []
    t_seq = time.perf_counter()
    for _client, cname, code, _tier in requests:
        _clear_caches()
        reset_analysis_scope()
        t0 = time.time()
        issues_by_name, errors, _states = run_cooperative_batch(
            [(cname, code)],
            transaction_count=opts.transaction_count,
            execution_timeout=opts.execution_timeout,
            strategy=opts.strategy,
            isolate_errors=False,
        )
        assert not errors, f"solo run failed: {errors}"
        issues = issues_by_name[cname]
        solo_digests.setdefault(
            cname, sorted(issue_digest(i) for i in issues)
        )
        ttfe = _ttfe(issues, t0)
        if ttfe == ttfe:  # not NaN
            solo_ttfes.append(ttfe)
    seq_wall = time.perf_counter() - t_seq

    # -- warm service under concurrent traffic ---------------------------
    _clear_caches()
    reset_analysis_scope()
    # request-scoped telemetry rides the measured window with the tracer
    # ON: the determinism assertion below then doubles as proof that
    # per-request span trees and phase accounting never perturb findings
    from mythril_tpu.observability.tracer import get_tracer
    from mythril_tpu.service.telemetry import PHASES as _SERVICE_PHASES

    reg = get_registry()
    for _p in _SERVICE_PHASES:
        reg.histogram(f"service.{_p}_s", persistent=True).reset()
    tracer = get_tracer()
    tracer.reset()
    tracer.enabled = True
    # the watchtower rides the measured window: its SLO verdicts land in
    # the row (the --against gate fails on breaches) and its tick cost is
    # held to the tracing budget.  Targets are CPU-CI-scaled so a clean
    # run reports zero breaches; a real service regression still trips
    # them.  Breach profiling is off — a profiler window inside the
    # measured window would perturb the rate being measured.
    slo_path = os.path.join(
        tempfile.mkdtemp(prefix="bench-slo-"), "slo.json"
    )
    with open(slo_path, "w") as f:
        json.dump({
            "interval_s": 0.5,
            "capture": {"profile": False},
            "objectives": [
                {"name": "ttfe_p95", "kind": "quantile",
                 "metric": "service.ttfe_s", "q": 0.95, "target": 30.0,
                 "fast_window_s": 60, "slow_window_s": 600},
                {"name": "queue_wait_p95", "kind": "quantile",
                 "metric": "service.queue_wait_s", "q": 0.95, "target": 60.0,
                 "fast_window_s": 60, "slow_window_s": 600},
                {"name": "error_rate", "kind": "ratio",
                 "metric": "service.request_errors",
                 "denominator": "service.requests", "target": 0.05,
                 "min_count": 4},
            ],
        }, f)
    slo_breaches_base = int(
        reg.counter("slo.breaches_total", persistent=True).snapshot() or 0
    )
    service = AnalysisService(ServiceConfig(
        default_options=opts,
        max_batch_width=max(clients, 1),
        batch_window_s=0.25,
        frontier=False,  # same engine as the baseline (comment above)
        probe=True,
        warmup=True,
        # pinned explicitly, NOT defaulted: this window is the
        # single-worker comparison leg, and the speedup attribution
        # (sequential vs warm, single vs pool) must stay honest even if
        # ServiceConfig's default worker count ever changes
        workers=1,
        watchtower=True,
        slo_file=slo_path,
    )).start()
    # NOTE: BENCH_INJECT_ADMISSION_SLEEP (the phase-gate fault hook) is
    # honored by AnalysisService.submit itself now, so the injected stall
    # lands inside the TTFE/queue-wait budgets the watchtower holds.
    # warmup is startup cost, not steady-state throughput: the timed
    # window starts from a warm process (the daemon's operating point)
    service.wait_warm(timeout=120)
    per_request = []
    lock = threading.Lock()

    def _submit(client: str, cname: str, code: bytes, tier: str) -> None:
        t0 = time.perf_counter()
        _req, stream, deduped = service.submit(code, name=client, tier=tier,
                                               tenant=client)
        first_issue = None
        issues = None
        for kind, payload in stream.events(timeout=600):
            if kind == "issue" and first_issue is None:
                first_issue = time.perf_counter() - t0
            elif kind == "error":
                raise AssertionError(f"{client}: {payload}")
            elif kind == "done":
                issues = payload["issues"]
        with lock:
            per_request.append({
                "client": client,
                "contract": cname,
                "tier": tier,
                "deduped": deduped,
                "n_issues": len(issues),
                "ttfe_s": round(first_issue, 3) if first_issue else None,
                "digests": sorted(issue_digest(i) for i in issues),
            })

    t_warm = time.perf_counter()
    threads = [
        threading.Thread(target=_submit, args=req, daemon=True)
        for req in requests
    ]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=900)
    warm_wall = time.perf_counter() - t_warm
    drained = service.stop(drain=True, timeout=60)
    tracer.enabled = False
    request_span_count = sum(
        1 for s in tracer.spans() if s["name"] == "service.request"
    )
    tracer.reset()

    # -- the three production claims ------------------------------------
    assert len(per_request) == clients, (
        f"only {len(per_request)}/{clients} requests completed"
    )
    mismatches = [
        r["client"]
        for r in per_request
        if r["digests"] != solo_digests[r["contract"]]
    ]
    identical = not mismatches
    reg = get_registry()
    dedup_hits = int(reg.counter("service.dedup_hits", persistent=True).snapshot())
    seq_rps = clients / seq_wall if seq_wall else 0.0
    warm_rps = clients / warm_wall if warm_wall else 0.0
    service_ttfes = [
        r["ttfe_s"] for r in per_request if r["ttfe_s"] is not None
    ]

    # -- optional second window: N-worker process pool --------------------
    pool_result = None
    if workers > 1:
        pool_result = _serve_pool_window(
            requests, opts, solo_digests, workers, warm_rps
        )

    row = {
        "unit": "requests/sec",
        "baseline": round(seq_rps, 3),
        "production": round(warm_rps, 3),
        "speedup": round(warm_rps / seq_rps, 3) if seq_rps else None,
        "reps": 1,
        "spread": {
            "baseline": [round(seq_rps, 3)] * 2,
            "production": [round(warm_rps, 3)] * 2,
        },
        "spread_n": {"baseline": 1, "production": 1},
        "ttfe_s": {
            "baseline": round(_median(solo_ttfes), 3) if solo_ttfes else None,
            "production": (
                round(_median(service_ttfes), 3) if service_ttfes else None
            ),
        },
    }
    # per-phase service latency percentiles (queue-wait/execute/stream
    # decomposition from the request telemetry plane) — the --against
    # gate asserts these, so an admission or streaming regression fails
    # CI like a production-rate regression does
    phase_row = {}
    for _p in _SERVICE_PHASES:
        h = reg.histogram(f"service.{_p}_s", persistent=True)
        if h.count:
            phase_row[_p] = {
                "count": h.count,
                "p50": round(h.percentile(0.50), 4),
                "p95": round(h.percentile(0.95), 4),
            }
    row["service_phase_s"] = phase_row
    # per-workload prefilter kill rate on this corpus-like traffic (the
    # daemon mirrors the scoped counters into service.prefilter_*)
    pf_eval = int(reg.counter(
        "service.prefilter_evaluated", persistent=True).snapshot() or 0)
    pf_kill = int(reg.counter(
        "service.prefilter_killed", persistent=True).snapshot() or 0)
    row["prefilter"] = {
        "evaluated": pf_eval,
        "killed": pf_kill,
        "kill_rate": round(pf_kill / pf_eval, 4) if pf_eval else 0.0,
    }
    ds_adm = int(reg.counter(
        "service.devsolver_admitted", persistent=True).snapshot() or 0)
    ds_dec = int(reg.counter(
        "service.devsolver_decided_sat", persistent=True).snapshot() or 0
    ) + int(reg.counter(
        "service.devsolver_decided_unsat", persistent=True).snapshot() or 0)
    row["devsolver"] = {
        "admitted": ds_adm,
        "decided": ds_dec,
        "decide_rate": round(ds_dec / ds_adm, 4) if ds_adm else 0.0,
    }
    # SLO verdict for the measured window: the watchtower rode the warm
    # window above, so breaches here ARE service regressions (the counter
    # is persistent — the base snapshot isolates this window's delta)
    slo_breaches = int(
        reg.counter("slo.breaches_total", persistent=True).snapshot() or 0
    ) - slo_breaches_base
    wt = getattr(service, "watchtower", None)
    slo_ok = slo_breaches == 0
    row["slo"] = {
        "ok": slo_ok,
        "breaches": slo_breaches,
        "objectives": len(wt.objectives) if wt is not None else 0,
        "overhead_pct": (
            round(wt.overhead_pct(), 3) if wt is not None else None
        ),
    }
    row["slo_ok"] = slo_ok
    passed = (identical and dedup_hits > 0 and warm_rps > seq_rps
              and drained and slo_ok)
    if pool_result is not None:
        passed = passed and pool_result["pass"]
    result = {
        "metric": "serve_load_requests_per_sec",
        "value": row["production"],
        "clients": clients,
        "unique_contracts": len(contracts),
        "sequential_wall_s": round(seq_wall, 2),
        "warm_wall_s": round(warm_wall, 2),
        "dedup_hits": dedup_hits,
        "identical_issue_sets": identical,
        **({"mismatched_clients": mismatches} if mismatches else {}),
        "drained": drained,
        "request_spans": request_span_count,
        "per_request": [
            {k: v for k, v in r.items() if k != "digests"}
            for r in sorted(per_request, key=lambda r: r["client"])
        ],
        "workloads": {"serve_load": row},
        "service_counters": {
            k: v
            for k, v in get_registry().snapshot().items()
            if k.startswith("service.")
        },
        "pass": passed,
    }
    if pool_result is not None:
        result["workers"] = workers
        result["serve_pool"] = {
            k: v for k, v in pool_result.items() if k != "row"
        }
        result["workloads"]["serve_pool"] = pool_result["row"]
    return result


def _serve_pool_window(requests, opts, solo_digests, workers: int,
                       single_rps: float) -> dict:
    """Replay ``requests`` against an N-worker process pool; return the
    ``serve_pool`` row plus its assertion verdicts.

    Digest identity to solo runs is asserted unconditionally (process
    isolation must never change findings).  The window runs with the
    fleet fabric ON — worker tracers enabled, delta flushes riding the
    event multiplex — so the identity assertion doubles as proof the
    cross-process telemetry never perturbs findings.  The scaling
    assertion is hardware-gated: N spawned engine processes cannot beat
    one worker on a single core, so the >= 2x claim (--workers 4,
    8 clients) is only enforced when this host has the cores to express
    it.
    """
    import threading

    from mythril_tpu.facade.warm import reset_analysis_scope
    from mythril_tpu.observability.tracer import get_tracer
    from mythril_tpu.service import AnalysisService, ServiceConfig
    from mythril_tpu.service.codehash import issue_digest

    _clear_caches()
    reset_analysis_scope()
    clients = len(requests)
    tracer = get_tracer()
    tracer.reset()
    tracer.enabled = True
    service = AnalysisService(ServiceConfig(
        default_options=opts,
        # cap batch width so admitted work fans out across workers
        # instead of piling into one maximal shared batch
        max_batch_width=max(1, (clients + workers - 1) // workers),
        batch_window_s=0.05,
        frontier=False,
        probe=True,
        warmup=True,
        workers=workers,
        trace=True,
        flush_interval_s=0.25,
    )).start()
    assert service.wait_warm(timeout=300), "worker pool never became ready"
    per_request = []
    lock = threading.Lock()

    def _submit(client, cname, code, tier):
        _req, stream, deduped = service.submit(code, name=client, tier=tier,
                                               tenant=client)
        issues = None
        for kind, payload in stream.events(timeout=600):
            if kind == "error":
                raise AssertionError(f"pool {client}: {payload}")
            if kind == "done":
                issues = payload["issues"]
        with lock:
            per_request.append({
                "client": client,
                "contract": cname,
                "deduped": deduped,
                "digests": sorted(issue_digest(i) for i in issues),
            })

    t0 = time.perf_counter()
    threads = [
        threading.Thread(target=_submit, args=req, daemon=True)
        for req in requests
    ]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=900)
    pool_wall = time.perf_counter() - t0
    stats = service.stats()
    fleet = service.fleet.summary()
    drained = service.stop(drain=True, timeout=60)
    tracer.enabled = False
    tsum = tracer.summary()
    foreign_spans = int(tsum.get("foreign_spans", 0) or 0)
    tracer.reset()
    workers_reporting = len(fleet.get("workers") or {})

    assert len(per_request) == clients, (
        f"only {len(per_request)}/{clients} pool requests completed"
    )
    mismatches = [
        r["client"] for r in per_request
        if r["digests"] != solo_digests[r["contract"]]
    ]
    identical = not mismatches
    pool_rps = clients / pool_wall if pool_wall else 0.0
    speedup = round(pool_rps / single_rps, 3) if single_rps else None
    cpus = os.cpu_count() or 1
    # hardware-gated scaling assertion (see docstring)
    if cpus >= max(4, workers) and workers >= 4:
        target = 2.0
    elif cpus >= 2:
        target = 1.0
    else:
        target = None  # single core: record, don't assert
    scaling_ok = (
        True if target is None
        else (speedup or 0.0) >= target
    )
    restarts = int(stats.get("service.worker_restarts") or 0)
    # every worker must have reported over the fabric during the window
    fleet_ok = workers_reporting == workers and foreign_spans > 0
    passed = (identical and drained and scaling_ok and restarts == 0
              and fleet_ok)
    row = {
        "unit": "requests/sec",
        "baseline": round(single_rps, 3),
        "production": round(pool_rps, 3),
        "speedup": speedup,
        "reps": 1,
        "spread": {
            "baseline": [round(single_rps, 3)] * 2,
            "production": [round(pool_rps, 3)] * 2,
        },
        "spread_n": {"baseline": 1, "production": 1},
    }
    return {
        "row": row,
        "workers": workers,
        "cpu_count": cpus,
        "pool_wall_s": round(pool_wall, 2),
        "identical_issue_sets": identical,
        **({"mismatched_clients": mismatches} if mismatches else {}),
        "speedup_target": target,
        "scaling_asserted": target is not None,
        "scaling_ok": scaling_ok,
        "worker_restarts": restarts,
        "drained": drained,
        "fleet": {
            "workers_reporting": workers_reporting,
            "replayed": fleet.get("replayed", 0),
            "discarded": fleet.get("discarded", 0),
            "foreign_spans": foreign_spans,
            "rollup_batches": (fleet.get("rollup") or {})
            .get("counters", {}).get("worker.batches", 0),
        },
        "fleet_ok": fleet_ok,
        "pass": passed,
    }


# (name, fn, unit, reps) — workloads run INTERLEAVED baseline/production
# reps and report the median with min/max spread in the JSON.  Solver-bound
# rows get >= 3 reps: their run-to-run variance is the dominant error term
# (measured +/-20-40% in round 3), and a median-of-3 with reported spread is
# the minimum honest quote.
WORKLOADS = [
    ("suicide_1tx", wl_suicide, "states/sec", 3),
    ("killbilly_3tx", wl_killbilly, "states/sec", 3),
    ("overflow_256bit", wl_overflow, "states/sec", 3),
    ("wide_frontier", wl_wide_frontier, "states/sec", 3),
    ("wide_solc", wl_wide_solc, "states/sec", 3),
    ("bectoken_batch", wl_bectoken, "states/sec", 3),
    ("largecode_mixed", wl_largecode, "states/sec", 3),
    ("concolic_flip", wl_concolic, "flips/sec", 3),
    ("corpus_sweep", wl_corpus, "states/sec", 3),
]


def _warm_frontier() -> None:
    """Compile the segment programs for the production widths OUTSIDE every
    workload timer (the XLA disk cache is invalidated by any program change,
    so a fresh build pays each (caps, bucket) combination once here)."""
    from mythril_tpu.support.support_args import args

    _configure(True)
    args.frontier_force = True
    try:
        for width in (256, 1024):
            args.frontier_width = width
            _clear_caches()
            _analyze(
                _wide_contract(4), 0x0901D12E, 1,
                modules=["AccidentallyKillable"], timeout=300,
            )
    finally:
        args.frontier_force = False


_DEVSOLVER_KEYS = ("admitted", "decided_sat", "decided_unsat",
                   "unknown", "model_validation_failures")

_ADAPTIVE_KEYS = ("plans", "resteered_slots", "requeued_paths",
                  "flips_planned", "flips_hit", "plateau_stops")


def _new_row_data():
    return {
        "samples": {"baseline": [], "production": []},
        "ttfes": {"baseline": [], "production": []},
        "ttfrs": {"baseline": [], "production": []},
        "residency": [],
        "harvest_shares": [],
        "harvest_phases": [],  # per-production-rep {phase: seconds} deltas
        "prefilter": [],  # per-production-rep prefilter.* counter deltas
        "devsolver": [],  # per-production-rep devsolver.* counter deltas
        "adaptive": [],  # per-production-rep adaptive.* counter deltas
        "segments": [],  # per-production-rep frontier.segments deltas
        # per-production-rep large-code frontier reads: bucket classes,
        # pad-waste (isolated vs single-bucket counterfactual), paging
        "frontier": [],
        "exploration": [],  # per-production-rep termination/coverage deltas
        # per-production-rep staticpass.reachable_edge_pct gauge reads
        # (static property of the workload's code; drift across bench
        # artifacts means the corpus or the oracle changed)
        "staticpass_edge_pct": [],
        "mids": [],  # per-production-rep (mid_reentered, mid_bounced, semantic_parked)
        # accumulated per-tag [hits, misses] deltas of the persistent XLA
        # compile cache — did this workload's programs come off disk?
        "compilecache": {"baseline": [0, 0], "production": [0, 0]},
        # first-rep XLA compile wall per tag (device plane), split OUT of
        # the rep-0 timed window so steady-state speedups stop absorbing
        # warmup noise; None until the device plane observes a compile
        "compile_s": {"baseline": None, "production": None},
        # accumulated production-run device-plane deltas:
        # [compile_wall_s, recompiles, shape_churn]
        "device": [0.0, 0, 0],
        "completed_reps": 0,
        "trimmed_reps": [],  # rep numbers the budget clock dropped
    }


def _median(vals):
    return sorted(vals)[len(vals) // 2]


def _prefilter_summary(samples) -> dict:
    """Median prefilter.* counter deltas plus the derived kill rate —
    the per-workload figure that makes the abstract pre-filter's value
    measurable on corpus-like traffic."""
    out = {
        k: _median([p[k] for p in samples])
        for k in ("evaluated", "killed", "fallthrough")
    }
    out["kill_rate"] = (
        round(out["killed"] / out["evaluated"], 4) if out["evaluated"] else 0.0
    )
    return out


def _devsolver_summary(samples) -> dict:
    """Median devsolver.* counter deltas plus the derived decide rate —
    the per-workload figure for how much exact-solver traffic the device
    SAT tier absorbed."""
    out = {
        k: _median([p[k] for p in samples])
        for k in ("admitted", "decided_sat", "decided_unsat", "unknown",
                  "model_validation_failures")
    }
    out["decided"] = out["decided_sat"] + out["decided_unsat"]
    out["decide_rate"] = (
        round(out["decided"] / out["admitted"], 4) if out["admitted"] else 0.0
    )
    return out


def _adaptive_summary(samples) -> dict:
    """Median adaptive.* counter deltas plus the derived flip hit rate —
    the per-workload figure for how much steering the coverage-guided
    controller actually exerted (and whether its concolic flip plans
    landed)."""
    out = {k: _median([s[k] for s in samples]) for k in _ADAPTIVE_KEYS}
    out["flip_hit_rate"] = (
        round(out["flips_hit"] / out["flips_planned"], 4)
        if out["flips_planned"] else 0.0
    )
    return out


def _frontier_summary(samples) -> dict:
    """Median large-code frontier reads — pad-waste after bucket isolation
    next to the single-bucket counterfactual (the row the ISSUE's
    acceptance bar compares), plus paging fault/repack volume."""
    out = {
        "bucket_classes": _median([s["bucket_classes"] for s in samples]),
        "pad_waste_pct": round(
            _median([s["pad_waste_pct"] for s in samples]), 2),
        "pad_waste_single_bucket_pct": round(
            _median([s["pad_waste_single_bucket_pct"] for s in samples]), 2),
        "page_faults": _median([s["page_faults"] for s in samples]),
        "page_repacks": _median([s["page_repacks"] for s in samples]),
        "page_resident_pct": round(
            _median([s["page_resident_pct"] for s in samples]), 1),
    }
    return out


def _exploration_summary(samples) -> dict:
    """Median termination-class deltas + instruction coverage per rep —
    the exploration-quality row the coverage gate compares."""
    from mythril_tpu.observability.exploration import TERM_CLASSES

    term = {
        cls: _median([s["terminated"].get(cls, 0) for s in samples])
        for cls in TERM_CLASSES
    }
    covs = [
        s["coverage_pct"] for s in samples
        if s.get("coverage_pct") is not None
    ]
    covs_reach = [
        s["coverage_pct_reachable"] for s in samples
        if s.get("coverage_pct_reachable") is not None
    ]
    out = {
        "terminated": {cls: n for cls, n in term.items() if n},
        "terminated_total": _median(
            [s["terminated_total"] for s in samples]
        ),
        "coverage_pct": round(_median(covs), 2) if covs else None,
        "coverage_pct_reachable": (
            round(_median(covs_reach), 2) if covs_reach else None
        ),
    }
    # host-only workloads (e.g. a 1-tx probe-sized run that bails off the
    # frontier) feed the coverage bitmaps through the instruction plugin
    # but never reach the frontier's termination stamping, so the row
    # quotes coverage with terminated_total == 0.  That pairing read as
    # "100% coverage over zero paths" in BENCH_r17 — mark it explicitly
    # instead of letting it masquerade as frontier-measured coverage.
    if not out["terminated_total"] and out["coverage_pct"] is not None:
        out["coverage_probe_derived"] = True
    return out


def _row_summary(unit: str, d: dict, configured_reps: int = None) -> dict:
    samples, ttfes, ttfrs = d["samples"], d["ttfes"], d["ttfrs"]
    rates = {tag: _median(vals) for tag, vals in samples.items() if vals}
    med_ttfe = {
        tag: (_median(vals) if vals else None) for tag, vals in ttfes.items()
    }
    dev_pct = (
        round(100 * _median(d["residency"]), 1) if d["residency"] else 0.0
    )
    return {
        "unit": unit,
        "baseline": round(rates.get("baseline", 0.0), 2),
        "production": round(rates.get("production", 0.0), 2),
        "speedup": round(rates["production"] / rates["baseline"], 3)
        if rates.get("baseline") and "production" in rates
        else None,
        "reps": d["completed_reps"],
        # sub-min-rep honesty: a row with fewer completed reps than the
        # workload configured (budget-trimmed runs) has no defensible
        # median/spread — mark it so readers and the --against gate's
        # rate checks treat it as indicative, not authoritative
        **(
            {"low_confidence": True}
            if configured_reps is not None
            and d["completed_reps"] < configured_reps
            else {}
        ),
        # per-row spread: the honest error bars round 3 lacked.  A spread
        # over fewer samples than the workload's configured reps is marked
        # by spread_n + the budget-trimmed rep numbers, so 2-rep data never
        # silently reads as the full-rep figure again (BENCH_r05).
        "spread": {
            tag: [round(min(vals), 2), round(max(vals), 2)]
            for tag, vals in samples.items()
            if vals
        },
        "spread_n": {
            tag: len(vals) for tag, vals in samples.items() if vals
        },
        **(
            {"trimmed_reps": list(d["trimmed_reps"])}
            if d.get("trimmed_reps")
            else {}
        ),
        "ttfe_s": {
            tag: (round(v, 3) if v is not None else None)
            for tag, v in med_ttfe.items()
        },
        "ttfe_spread_s": {
            tag: [round(min(vals), 3), round(max(vals), 3)]
            for tag, vals in ttfes.items()
            if vals
        },
        # corpus only: time-to-FULL-recall — the metric the cooperative
        # schedule optimizes (first-exploit TTFE structurally favors the
        # sequential schedule, which confirms contract #1 before
        # contract #2 even starts)
        **(
            {
                "ttfr_s": {
                    tag: round(_median(vals), 3)
                    for tag, vals in ttfrs.items()
                    if vals
                }
            }
            if any(ttfrs.values())
            else {}
        ),
        "device_residency_pct": dev_pct,
        # persistent-compile-cache traffic attributed to this workload's
        # runs (hits = programs loaded from disk instead of recompiled)
        "compilecache": {
            tag: {"hits": int(v[0]), "misses": int(v[1])}
            for tag, v in d.get("compilecache", {}).items()
        },
        # first-rep XLA compile wall (seconds) per tag — already excluded
        # from that rep's timed window, quoted here so warmup cost stays
        # visible instead of silently vanishing from the table
        **(
            {
                "compile_s": {
                    tag: round(v, 3)
                    for tag, v in d.get("compile_s", {}).items()
                    if v is not None
                }
            }
            if any(
                v is not None for v in d.get("compile_s", {}).values()
            )
            else {}
        ),
        # device-plane deltas over the workload's production runs: total
        # XLA compile wall + same-bucket recompiles (each one is a lost
        # compile-cache bet) + distinct-shape churn
        **(
            {
                "device": {
                    "compile_wall_s": round(d["device"][0], 3),
                    "recompiles": int(d["device"][1]),
                    **(
                        {"shape_churn": int(d["device"][2])}
                        if d["device"][2]
                        else {}
                    ),
                }
            }
            if d.get("device") and (d["device"][0] or d["device"][1])
            else {}
        ),
        "harvest_share_pct": (
            round(100 * _median(d["harvest_shares"]), 1)
            if d["harvest_shares"]
            else None
        ),
        # the harvest share split per executor phase (median across
        # production reps of the frontier.harvest.*_s histogram deltas):
        # which of ingest / solver / replay / commit owns the host cost
        **(
            {
                "harvest_phase_s": {
                    p: round(_median([h[p] for h in d["harvest_phases"]]), 3)
                    for p in _HARVEST_PHASES
                }
            }
            if d["harvest_phases"]
            else {}
        ),
        # abstract pre-filter traffic (production runs): how many feasibility
        # queries the interval/known-bits pass evaluated and proved UNSAT
        # before any exact solve, and the per-workload kill rate
        **(
            {"prefilter": _prefilter_summary(d["prefilter"])}
            if d.get("prefilter")
            else {}
        ),
        # device SAT tier traffic (production runs): how many narrow
        # queries the batched bit-blast kernel decided (exact UNSAT or
        # validated SAT) instead of reaching the exact host tiers
        **(
            {"devsolver": _devsolver_summary(d["devsolver"])}
            if d.get("devsolver")
            else {}
        ),
        # adaptive steering traffic (production runs): plans built,
        # dispatch slots resteered off FIFO order, budget-exhausted paths
        # requeued, and planned-vs-hit concolic flips — quoted whenever
        # the controller exerted any steering on this workload
        **(
            {"adaptive": _adaptive_summary(d["adaptive"])}
            if d.get("adaptive")
            and any(any(s.values()) for s in d["adaptive"])
            else {}
        ),
        # device segment dispatches per production rep: the denominator
        # the adaptive controller tries to shrink at equal issue sets
        **(
            {"segments_dispatched": _median(d["segments"])}
            if d.get("segments") and any(d["segments"])
            else {}
        ),
        # large-code frontier (production runs): bucket classes, pad waste
        # with isolation vs the single-bucket counterfactual, and paging
        # fault/repack pressure — quoted whenever the run clustered codes
        # into classes or paid any paging traffic
        **(
            {"frontier": _frontier_summary(d["frontier"])}
            if d.get("frontier")
            and any(s["bucket_classes"] or s["page_faults"]
                    or s["pad_waste_pct"] for s in d["frontier"])
            else {}
        ),
        # exploration quality (production runs): how many paths stopped,
        # why (the eight-class termination partition), and how much of
        # each contract's instruction space the run actually visited
        **(
            {"exploration": _exploration_summary(d["exploration"])}
            if d.get("exploration")
            else {}
        ),
        # reachable-edge oracle: what share of static JUMPI edges the
        # interprocedural pass proved live for this workload's code set
        **(
            {"staticpass": {"reachable_edge_pct": round(
                _median(d["staticpass_edge_pct"]), 2)}}
            if d.get("staticpass_edge_pct")
            else {}
        ),
        # mid-frame residency (production runs): how many parked/resumed
        # states re-entered the device vs bounced at encoding vs stayed
        # pinned host-side as semantic parks — the counters that quantify
        # the mid-frame re-entry claim on each workload
        **(
            {
                "mid_frame": {
                    key: _median([m[i] for m in d["mids"]])
                    for i, key in enumerate(
                        ("reentered", "bounced", "semantic_parked")
                    )
                }
            }
            if d["mids"]
            else {}
        ),
    }


_UNIT_BLURB = (
    "states/sec over the reference contract corpus "
    "(production: frontier enabled everywhere — the corpus runs "
    "cooperatively as wide multi-code device segments, narrow "
    "workloads auto-bail to host; recall asserted per workload, "
    "ttfe_s = time-to-first-exploit)"
)


def _observability_snapshot() -> dict:
    from mythril_tpu.observability import get_registry

    return get_registry().snapshot()


def _emit_snapshot(table: dict, budget_meta: dict, partial: bool) -> None:
    """One JSON line on stdout + a file copy.  Emitted after EVERY completed
    workload pair so a driver-level timeout can never zero the artifact —
    the final (non-partial) snapshot is the last JSON line printed."""
    from mythril_tpu.frontier.stats import FrontierStatistics

    headline = table.get("corpus_sweep")
    obs = _observability_snapshot()
    obj = {
        "metric": "corpus_sweep_states_per_sec",
        "value": headline["production"] if headline else None,
        "unit": _UNIT_BLURB,
        "vs_baseline": (
            round(headline["production"] / headline["baseline"], 3)
            if headline and headline["baseline"]
            else None
        ),
        "workloads": table,
        "budget": budget_meta,
        # device-only efficiency (pure segment compute via chained-dispatch
        # subtraction, independent of the host<->device link): the per-chip
        # number that tracks distance to the paths/sec north star
        **(
            {"device_microbench": FrontierStatistics().microbench}
            if FrontierStatistics().microbench
            else {}
        ),
        # machine-readable per-stage breakdown: the full metrics-registry
        # snapshot (frontier/solver counters plus the segment/harvest/
        # smt-solve wall-time histograms) accumulated over the sweep
        "observability": obs,
        # the static pre-analysis counters broken out for quick grepping
        # (they also appear inside the full observability snapshot)
        "staticpass": {
            k: v for k, v in obs.items() if k.startswith("staticpass.")
        },
    }
    if partial:
        obj["partial"] = True
    line = json.dumps(obj)
    print(line, flush=True)
    try:
        Path(__file__).with_name("BENCH_partial.json").write_text(line + "\n")
    except OSError:
        pass


# ---------------------------------------------------------------------------
# regression gate: bench.py --against PRIOR.json [--candidate CUR.json]
# ---------------------------------------------------------------------------

# metric thresholds relative to the prior snapshot.  The rate/ttfe tolerance
# is deliberately generous (CPU-jitter across container runs); the absolute
# slacks keep sub-second metrics from tripping on noise.  A halved throughput
# or a doubled TTFE still fails loudly.
GATE_TOLERANCE = 0.35
GATE_TTFE_SLACK_S = 2.0
GATE_HARVEST_SLACK_PCT = 15.0  # absolute harvest-share points
GATE_PHASE_SLACK_S = 0.75  # absolute slack on service phase p95s
GATE_COVERAGE_SLACK_PCT = 10.0  # absolute instruction-coverage points
GATE_TRACING_BUDGET_PCT = 2.0  # tracing overhead must stay under 2% of wall
# spans+flows+counters a fully-instrumented pipelined segment emits (dispatch,
# chain_merge, segment, 4 harvest phases, replay/feasibility workers, 3-point
# segment flow, worker flows, heartbeat counters) — deliberately rounded UP
GATE_SPANS_PER_SEGMENT = 40.0


def _balanced_object(text: str, start: int):
    """Return the substring of one balanced {...} object starting at
    ``text[start] == '{'``, honoring JSON string/escape rules, or None if the
    object is truncated before it closes."""
    depth = 0
    in_str = False
    esc = False
    for i in range(start, len(text)):
        c = text[i]
        if in_str:
            if esc:
                esc = False
            elif c == "\\":
                esc = True
            elif c == '"':
                in_str = False
            continue
        if c == '"':
            in_str = True
        elif c == "{":
            depth += 1
        elif c == "}":
            depth -= 1
            if depth == 0:
                return text[start : i + 1]
    return None


def _salvage_workload_rows(text: str) -> dict:
    """Recover complete per-workload row objects from a (possibly truncated)
    bench stdout fragment.  Rows are recognized as ``"name": {...}`` objects
    that carry both ``unit`` and ``production`` keys — nested objects like
    ``spread``/``ttfe_s`` and the budget/observability blocks do not match."""
    import re

    rows: dict = {}
    for m in re.finditer(r'"([A-Za-z0-9_]+)"\s*:\s*\{', text):
        obj_txt = _balanced_object(text, m.end() - 1)
        if obj_txt is None:
            continue
        try:
            obj = json.loads(obj_txt)
        except ValueError:
            continue
        if isinstance(obj, dict) and "unit" in obj and "production" in obj:
            rows[m.group(1)] = obj
    return rows


def _load_bench_doc(path: str):
    """Load a prior bench artifact into ``(workload_rows, full_doc_or_None)``.

    Accepts, in order of preference:
      1. a plain snapshot JSON with a top-level ``workloads`` table (the
         bench.py output contract / BENCH_partial.json);
      2. a driver wrapper ``{"n", "cmd", "rc", "tail", "parsed"}`` whose
         ``parsed`` field holds the snapshot;
      3. the same wrapper with ``parsed: null`` and a tail that is the LAST
         N chars of stdout — often truncated mid-JSON (BENCH_r05.json), in
         which case complete workload rows are salvaged from the fragment;
      4. raw bench stdout (JSON line per snapshot): last parseable line wins.
    """
    raw = Path(path).read_text()
    try:
        doc = json.loads(raw)
    except ValueError:
        doc = None
    text = raw
    if isinstance(doc, dict):
        if isinstance(doc.get("workloads"), dict):
            return doc["workloads"], doc
        parsed = doc.get("parsed")
        if isinstance(parsed, dict) and isinstance(
            parsed.get("workloads"), dict
        ):
            return parsed["workloads"], parsed
        text = doc.get("tail") or ""
    for line in reversed(text.splitlines()):
        line = line.strip()
        if not line.startswith("{"):
            continue
        try:
            obj = json.loads(line)
        except ValueError:
            continue
        if isinstance(obj, dict) and isinstance(obj.get("workloads"), dict):
            return obj["workloads"], obj
    return _salvage_workload_rows(text), None


def _tracing_overhead_pct(span_rate_hz: float) -> dict:
    """Measure the live per-span cost of the tracer (enabled-vs-disabled
    micro-bench on THIS machine) and scale it by the run's span emission rate
    to a percent-of-wall figure.  The flight deck's contract is that leaving
    tracing on costs <2% of wall; this asserts it with measured numbers
    instead of a hope."""
    from mythril_tpu.observability.tracer import Tracer

    tr = Tracer(capacity=8192)
    n = 20000

    def _spin() -> float:
        t0 = time.perf_counter()
        for _ in range(n):
            with tr.span("bench.overhead", cat="bench"):
                pass
        return (time.perf_counter() - t0) / n

    tr.enabled = False
    cost_off = _spin()
    tr.enabled = True
    cost_on = _spin()
    per_span_s = max(cost_on - cost_off, 0.0)
    return {
        "per_span_us": round(per_span_s * 1e6, 3),
        "span_rate_hz": round(span_rate_hz, 1),
        "overhead_pct": round(100.0 * per_span_s * span_rate_hz, 4),
    }


def _fleet_export_overhead_pct(flush_interval_s: float = 0.5) -> dict:
    """Measure the worker-side cost of one fleet delta flush (collect a
    registry delta + drain a batch of spans) on THIS machine and scale
    it by the flush rate to a percent-of-wall figure.  The fabric's
    contract is the same as the tracer's: leaving it on inside every
    worker costs <2% of wall."""
    from mythril_tpu.observability.fleet import FleetPublisher
    from mythril_tpu.observability.metrics import MetricsRegistry
    from mythril_tpu.observability.tracer import Tracer

    # a representative worker registry: the scoped counter/gauge set a
    # real batch leaves behind, plus phase histograms and span traffic
    reg = MetricsRegistry()
    tr = Tracer(capacity=8192)
    tr.enabled = True
    for i in range(48):
        reg.counter(f"bench.c{i}").inc(i + 1)
    for i in range(8):
        reg.gauge(f"bench.g{i}").set(i)
    lc = reg.labeled_counter("bench.issues", label_name="swc")
    for i in range(6):
        lc.inc(str(100 + i), 1)
    h = reg.histogram("bench.lat_s")
    pub = FleetPublisher(0, registry=reg, tracer=tr)
    pub.collect()  # baseline the full metric set first
    n = 200
    t0 = time.perf_counter()
    for _ in range(n):
        # keep every flush non-empty the way a busy worker's would be
        reg.counter("bench.c0").inc()
        h.observe(0.01)
        with tr.span("bench.worker_batch", cat="bench"):
            tr.flow("f", tr.new_flow_id(), "flow.request", cat="bench")
        pub.collect()
    per_flush_s = (time.perf_counter() - t0) / n
    rate_hz = 1.0 / max(flush_interval_s, 1e-6)
    return {
        "per_flush_us": round(per_flush_s * 1e6, 3),
        "flush_rate_hz": round(rate_hz, 1),
        "overhead_pct": round(100.0 * per_flush_s * rate_hz, 4),
    }


def _gate_span_rate(doc) -> float:
    """Estimate the instrumented-run span emission rate (spans/sec) from a
    bench snapshot's observability block: completed segments over suite wall,
    times a generous spans-per-segment factor.  Falls back to a conservative
    1 kHz when the snapshot lacks the histogram."""
    fallback = 1000.0
    if not isinstance(doc, dict):
        return fallback
    obs = doc.get("observability") or {}
    seg = obs.get("frontier.segment_wall_s") or {}
    count = seg.get("count") or 0
    elapsed = (doc.get("budget") or {}).get("elapsed_s") or 0
    if count and elapsed:
        return max(count / float(elapsed) * GATE_SPANS_PER_SEGMENT, fallback)
    return fallback


def regression_gate(
    against_path: str,
    current_table: dict,
    current_doc=None,
    tol: float = GATE_TOLERANCE,
) -> int:
    """Compare ``current_table`` to the snapshot at ``against_path``; print
    violations, emit one JSON gate-report line, return a process exit code
    (0 = clean, 1 = regression, 2 = unusable prior)."""
    try:
        prior, _prior_doc = _load_bench_doc(against_path)
    except (OSError, ValueError) as exc:
        print(f"[bench] --against: cannot read {against_path}: {exc}",
              file=sys.stderr)
        return 2
    common = sorted(set(prior) & set(current_table))
    if not common:
        print(
            f"[bench] --against: no comparable workloads between "
            f"{against_path} ({sorted(prior)}) and the current run "
            f"({sorted(current_table)})",
            file=sys.stderr,
        )
        return 2

    violations = []
    checks = 0
    low_confidence_skipped = []
    for name in common:
        p, c = prior[name], current_table[name]
        # a row either side marked low_confidence (sub-min-rep data, e.g.
        # budget-trimmed to a single rep) is excluded from the RATE checks:
        # one sample has no spread, so "best rep" == the only rep and the
        # bimodal solver-bound workloads fail on scheduling luck, not
        # regressions.  The absolute checks (coverage, SLO) still apply.
        low_conf = bool(p.get("low_confidence")) or bool(
            c.get("low_confidence")
        )
        if low_conf:
            low_confidence_skipped.append(name)
        # throughput: production rate must hold within the relative
        # tolerance.  The table quotes the MEDIAN rep, but the gate asks
        # "can this tree still achieve the prior rate?" — so it compares
        # the best rep in the row's recorded spread: solver-bound rows are
        # bimodal on CPU-only containers (the adaptive slow-code bail makes
        # some reps run host-side), and a real regression slows every rep,
        # so best-of still fails loudly on an injected slowdown.
        pr, cr = p.get("production"), c.get("production")
        if pr and cr is not None and not low_conf:
            checks += 1
            spread = (c.get("spread") or {}).get("production") or []
            best = max([cr] + [s for s in spread if s is not None])
            floor = pr * (1.0 - tol)
            if best < floor:
                violations.append(
                    f"{name}: production {cr:.2f} (best rep {best:.2f}) "
                    f"< {floor:.2f} (prior {pr:.2f}, tol {tol:.0%})"
                )
        # latency: median production time-to-first-exploit
        pt = (p.get("ttfe_s") or {}).get("production")
        ct = (c.get("ttfe_s") or {}).get("production")
        if pt is not None and ct is not None and not low_conf:
            checks += 1
            ceil = pt * (1.0 + tol) + GATE_TTFE_SLACK_S
            if ct > ceil:
                violations.append(
                    f"{name}: production ttfe_s {ct:.3f} > {ceil:.3f} "
                    f"(prior {pt:.3f}, tol {tol:.0%} + "
                    f"{GATE_TTFE_SLACK_S:.1f}s)"
                )
        # host-cost share: harvest must not grow past an absolute-point band
        ph, ch = p.get("harvest_share_pct"), c.get("harvest_share_pct")
        if ph is not None and ch is not None:
            checks += 1
            ceil = ph + GATE_HARVEST_SLACK_PCT
            if ch > ceil:
                violations.append(
                    f"{name}: harvest_share_pct {ch:.1f} > {ceil:.1f} "
                    f"(prior {ph:.1f} + {GATE_HARVEST_SLACK_PCT:.0f}pt)"
                )
        # exploration quality: instruction coverage must not collapse —
        # a run can be fast because it silently stopped exploring, and the
        # rate checks alone would call that an improvement.  The gate
        # compares the REACHABLE-denominator coverage (raw coverage moves
        # whenever dead code in the corpus changes size, which is noise);
        # it falls back to the raw figure when either artifact predates
        # the reachable key
        p_expl = p.get("exploration") or {}
        c_expl = c.get("exploration") or {}
        cov_key = "coverage_pct_reachable"
        if (p_expl.get(cov_key) is None or c_expl.get(cov_key) is None):
            cov_key = "coverage_pct"
        pcov = p_expl.get(cov_key)
        ccov = c_expl.get(cov_key)
        if pcov is not None and ccov is not None:
            checks += 1
            floor_cov = pcov - GATE_COVERAGE_SLACK_PCT
            if ccov < floor_cov:
                violations.append(
                    f"{name}: exploration {cov_key} {ccov:.1f} < "
                    f"{floor_cov:.1f} (prior {pcov:.1f} - "
                    f"{GATE_COVERAGE_SLACK_PCT:.0f}pt)"
                )
        # service latency decomposition: per-phase p95 (queue_wait /
        # batch_wait / execute / stream from the serve-load row) must
        # stay within the rate tolerance plus an absolute slack — an
        # admission or streaming regression fails like a rate regression
        p_phases = p.get("service_phase_s") or {}
        c_phases = c.get("service_phase_s") or {}
        for phase in sorted(set(p_phases) & set(c_phases)):
            p95p = (p_phases.get(phase) or {}).get("p95")
            p95c = (c_phases.get(phase) or {}).get("p95")
            if p95p is None or p95c is None:
                continue
            checks += 1
            ceil = p95p * (1.0 + tol) + GATE_PHASE_SLACK_S
            if p95c > ceil:
                violations.append(
                    f"{name}: {phase} p95 {p95c:.3f}s > {ceil:.3f}s "
                    f"(prior {p95p:.3f}s, tol {tol:.0%} + "
                    f"{GATE_PHASE_SLACK_S:.2f}s)"
                )
        # watchtower SLO verdict: any breach during the measured window
        # is a service regression in absolute terms — no prior needed,
        # so the check is gated only on the CURRENT row carrying it
        # (older priors without the key compare clean)
        c_slo = c.get("slo")
        if c_slo is not None:
            checks += 1
            if not c_slo.get("ok", True):
                violations.append(
                    f"{name}: {c_slo.get('breaches', '?')} SLO breach(es) "
                    f"during the measured window "
                    f"({c_slo.get('objectives', 0)} objectives held)"
                )
            wt_pct = c_slo.get("overhead_pct")
            if wt_pct is not None:
                checks += 1
                if wt_pct >= GATE_TRACING_BUDGET_PCT:
                    violations.append(
                        f"{name}: watchtower overhead {wt_pct:.3f}% >= "
                        f"{GATE_TRACING_BUDGET_PCT:.1f}% of wall"
                    )

    overhead = _tracing_overhead_pct(_gate_span_rate(current_doc))
    checks += 1
    if overhead["overhead_pct"] >= GATE_TRACING_BUDGET_PCT:
        violations.append(
            f"tracing overhead {overhead['overhead_pct']:.3f}% >= "
            f"{GATE_TRACING_BUDGET_PCT:.1f}% of wall "
            f"({overhead['per_span_us']}us/span x "
            f"{overhead['span_rate_hz']}Hz)"
        )
    fleet_overhead = _fleet_export_overhead_pct()
    checks += 1
    if fleet_overhead["overhead_pct"] >= GATE_TRACING_BUDGET_PCT:
        violations.append(
            f"fleet export overhead {fleet_overhead['overhead_pct']:.3f}% "
            f">= {GATE_TRACING_BUDGET_PCT:.1f}% of wall "
            f"({fleet_overhead['per_flush_us']}us/flush x "
            f"{fleet_overhead['flush_rate_hz']}Hz)"
        )

    # on failure, run the drift doctor over the same pair so the gate
    # names the most-moved phase/counter per violating workload instead
    # of only the breached threshold — the "what moved" next to the
    # "what broke".  Attribution is advisory: a doctor error never
    # changes the gate verdict.
    drift = None
    if violations:
        try:
            from mythril_tpu.observability.drift import (
                attribute,
                diff_tables,
            )

            d_report = diff_tables(
                prior, current_table,
                prior_name=against_path, current_name="current",
            )
            violators = []
            for v in violations:
                w = v.split(":", 1)[0]
                if w in common and w not in violators:
                    violators.append(w)
            drift = {
                "headline": d_report.get("headline"),
                "attribution": (
                    [attribute(d_report, workload=w) for w in violators]
                    if violators
                    else [attribute(d_report)]
                ),
            }
        except Exception as exc:  # advisory only — never mask the verdict
            drift = {"error": f"{type(exc).__name__}: {exc}"}

    report = {
        "gate": {
            "against": against_path,
            "tolerance": tol,
            "workloads_compared": common,
            "checks": checks,
            "violations": violations,
            **(
                {"low_confidence_skipped": low_confidence_skipped}
                if low_confidence_skipped
                else {}
            ),
            "tracing_overhead": overhead,
            "fleet_export_overhead": fleet_overhead,
            "tracing_overhead_budget_pct": GATE_TRACING_BUDGET_PCT,
            "pass": not violations,
            **({"drift": drift} if drift else {}),
        }
    }
    print(json.dumps(report), flush=True)
    if violations:
        lines = list(violations)
        if drift and drift.get("attribution"):
            lines += drift["attribution"]
        print(
            "[bench] regression gate FAILED vs %s:\n  %s"
            % (against_path, "\n  ".join(lines)),
            file=sys.stderr,
        )
        return 1
    print(
        f"[bench] regression gate ok vs {against_path}: {checks} checks over "
        f"{len(common)} workloads, tracing overhead "
        f"{overhead['overhead_pct']:.3f}% + fleet export "
        f"{fleet_overhead['overhead_pct']:.3f}% < "
        f"{GATE_TRACING_BUDGET_PCT:.1f}%",
        file=sys.stderr,
    )
    return 0


def main() -> None:
    import os

    import mythril_tpu

    mythril_tpu.arm_compile_cache()
    if "--query-cache-compare" in sys.argv:
        # standalone warm-vs-cold mode: skip the full suite, emit one line
        idx = sys.argv.index("--query-cache-compare")
        operand = sys.argv[idx + 1] if len(sys.argv) > idx + 1 else None
        cache_dir = None if operand is None or operand.startswith("-") else operand
        print(json.dumps(query_cache_compare(cache_dir)), flush=True)
        return

    if "--staticpass-compare" in sys.argv:
        # standalone on-vs-off mode: skip the full suite, emit one line
        print(json.dumps(staticpass_compare()), flush=True)
        return

    if "--pipeline-compare" in sys.argv:
        # standalone pipelined-vs-sync parity mode: skip the suite, one line
        print(json.dumps(pipeline_compare()), flush=True)
        return

    if "--prefilter-compare" in sys.argv:
        # standalone abstract-prefilter parity mode: skip the suite, one line
        print(json.dumps(prefilter_compare()), flush=True)
        return

    if "--devsolver-compare" in sys.argv:
        # standalone device-SAT-tier parity mode: skip the suite, one line
        print(json.dumps(devsolver_compare()), flush=True)
        return

    if "--adaptive-compare" in sys.argv:
        # standalone steering on-vs-off parity mode: skip the suite, one line
        print(json.dumps(adaptive_compare()), flush=True)
        return

    if "--paging-compare" in sys.argv:
        # standalone large-code bucket-isolation/paging parity mode
        print(json.dumps(paging_compare()), flush=True)
        return

    if "--harvest-compare" in sys.argv:
        # standalone sharded-vs-serial harvest parity mode: one line
        print(json.dumps(harvest_compare()), flush=True)
        return

    if "--mesh-compare" in sys.argv:
        # standalone pod parity mode (all four mesh x pipeline combos)
        print(json.dumps(mesh_compare()), flush=True)
        return

    # --against PRIOR.json [--candidate CUR.json] [--gate-tolerance F]:
    # the regression gate.  With --candidate, compare two artifacts without
    # running the suite (fast CI path); without it, run the full suite and
    # gate the fresh table against the prior snapshot
    against = None
    gate_tol = GATE_TOLERANCE
    if "--against" in sys.argv:
        idx = sys.argv.index("--against")
        against = sys.argv[idx + 1] if len(sys.argv) > idx + 1 else None
        if against is None or against.startswith("-"):
            print("[bench] --against requires a FILE operand", file=sys.stderr)
            sys.exit(2)
    if "--gate-tolerance" in sys.argv:
        idx = sys.argv.index("--gate-tolerance")
        try:
            gate_tol = float(sys.argv[idx + 1])
        except (IndexError, ValueError):
            print("[bench] --gate-tolerance requires a FRACTION operand",
                  file=sys.stderr)
            sys.exit(2)
    if "--serve-load" in sys.argv:
        # standalone analysis-as-a-service traffic mode: N concurrent
        # synthetic clients against a warm in-process service, asserting
        # determinism/throughput/dedup; one JSON line, optionally gated
        # by --against (the serve_load row compares like any other)
        clients = 8
        if "--serve-clients" in sys.argv:
            idx = sys.argv.index("--serve-clients")
            try:
                clients = int(sys.argv[idx + 1])
            except (IndexError, ValueError):
                print("[bench] --serve-clients requires an N operand",
                      file=sys.stderr)
                sys.exit(2)
        workers = 1
        if "--workers" in sys.argv:
            # N > 1 adds a second measured window: the same traffic
            # against an N-worker process pool (workloads.serve_pool)
            idx = sys.argv.index("--workers")
            try:
                workers = int(sys.argv[idx + 1])
            except (IndexError, ValueError):
                print("[bench] --workers requires an N operand",
                      file=sys.stderr)
                sys.exit(2)
        result = serve_load(clients, workers=workers)
        print(json.dumps(result), flush=True)
        if against is not None:
            rc = regression_gate(against, result["workloads"], result,
                                 tol=gate_tol)
            sys.exit(rc or (0 if result["pass"] else 1))
        sys.exit(0 if result["pass"] else 1)

    if "--candidate" in sys.argv:
        if against is None:
            print("[bench] --candidate requires --against", file=sys.stderr)
            sys.exit(2)
        idx = sys.argv.index("--candidate")
        cand = sys.argv[idx + 1] if len(sys.argv) > idx + 1 else None
        if cand is None or cand.startswith("-"):
            print("[bench] --candidate requires a FILE operand",
                  file=sys.stderr)
            sys.exit(2)
        try:
            cand_table, cand_doc = _load_bench_doc(cand)
        except (OSError, ValueError) as exc:
            print(f"[bench] --candidate: cannot read {cand}: {exc}",
                  file=sys.stderr)
            sys.exit(2)
        sys.exit(regression_gate(against, cand_table, cand_doc, tol=gate_tol))

    # --ttfe-budget SECONDS: turn the production TTFE gap into a loud
    # regression — after the suite completes, any workload whose median
    # production time-to-first-exploit exceeds the budget fails the run
    ttfe_budget = None
    if "--ttfe-budget" in sys.argv:
        idx = sys.argv.index("--ttfe-budget")
        try:
            ttfe_budget = float(sys.argv[idx + 1])
        except (IndexError, ValueError):
            print("[bench] --ttfe-budget requires a SECONDS operand",
                  file=sys.stderr)
            sys.exit(2)

    # suite-internal budget clock (monotonic); the per-workload t0 stamps
    # stay time.time() because _ttfe/_rebase_stamp compare them against the
    # epoch-anchored report.StartTime discovery stamps
    t_proc = time.perf_counter()
    # global wall-clock budget: the driver kills long runs (round 4's capture
    # died rc=124 with no JSON emitted), so the suite trims itself instead —
    # rep 1 of every workload always runs (full table first), reps 2+ run
    # only while they fit the budget, trimmed in fixed row order
    budget_s = float(os.environ.get("BENCH_BUDGET_S", "1500"))
    deadline = t_proc + budget_s

    from mythril_tpu.frontier.stats import FrontierStatistics

    _warm_frontier()
    data = {name: _new_row_data() for name, _, _, _ in WORKLOADS}
    pair_cost: dict = {}  # name -> worst observed (baseline+production) wall
    trimmed: list = []
    skipped: dict = {}  # name -> reason (inputs not mounted here)
    max_reps = max(reps for _, _, _, reps in WORKLOADS)

    def budget_meta():
        meta = {
            "budget_s": budget_s,
            "elapsed_s": round(time.perf_counter() - t_proc, 1),
            "trimmed": trimmed,
        }
        if skipped:
            meta["skipped"] = dict(skipped)
        return meta

    for rep in range(max_reps):
        for name, fn, unit, reps in WORKLOADS:
            if rep >= reps or name in skipped:
                continue
            est = pair_cost.get(name, 0.0)
            if rep > 0 and time.perf_counter() + est > deadline:
                # deterministic trim: later reps go first, rep 1 never does;
                # the row's own summary carries the trimmed rep numbers so
                # its spread is readable as N-rep data
                trimmed.append({"workload": name, "rep": rep + 1})
                data[name]["trimmed_reps"].append(rep + 1)
                continue
            d = data[name]
            t_pair = time.perf_counter()
            for tag, production in (("baseline", False), ("production", True)):
                from mythril_tpu.observability import get_registry

                fstats = FrontierStatistics()
                dev_before = fstats.device_instructions
                har_before = fstats.harvest_s
                mid_before = _mid_counters(fstats)
                phases_before = {
                    p: get_registry().histogram(
                        "frontier.harvest.%s_s" % p
                    ).sum
                    for p in _HARVEST_PHASES
                }
                pf_before = {
                    k: get_registry().counter("prefilter.%s" % k).value
                    for k in ("evaluated", "killed", "fallthrough")
                }
                ds_before = {
                    k: get_registry().counter("devsolver.%s" % k).value
                    for k in _DEVSOLVER_KEYS
                }
                ad_before = {
                    k: get_registry().counter("adaptive.%s" % k).value
                    for k in _ADAPTIVE_KEYS
                }
                seg_before = fstats.segments
                page_before = (fstats.page_faults, fstats.page_repacks)
                from mythril_tpu.observability.exploration import (
                    get_exploration_ledger,
                )

                expl_before = get_exploration_ledger().terminated()
                cc_before = (
                    get_registry().counter(
                        "compilecache.hits", persistent=True
                    ).value,
                    get_registry().counter(
                        "compilecache.misses", persistent=True
                    ).value,
                )
                dp_before = tuple(
                    get_registry().counter(k, persistent=True).value
                    for k in _DEVICE_PLANE_COUNTERS
                )
                try:
                    out = fn(production)
                except WorkloadSkip as exc:
                    skipped[name] = str(exc)
                    print(f"[bench] {name:16s} skipped ({exc})",
                          file=sys.stderr)
                    break
                cc = d["compilecache"][tag]
                cc[0] += (
                    get_registry().counter(
                        "compilecache.hits", persistent=True
                    ).value - cc_before[0]
                )
                cc[1] += (
                    get_registry().counter(
                        "compilecache.misses", persistent=True
                    ).value - cc_before[1]
                )
                work, wall, ttfe = out[:3]
                # device plane: per-rep XLA compile wall / recompile /
                # shape-churn deltas attributed to this workload's run
                dp_compile, dp_rcmp, dp_churn = (
                    get_registry().counter(k, persistent=True).value - b
                    for k, b in zip(_DEVICE_PLANE_COUNTERS, dp_before)
                )
                if production:
                    d["device"][0] += dp_compile
                    d["device"][1] += dp_rcmp
                    d["device"][2] += dp_churn
                if rep == 0 and d["compile_s"][tag] is None:
                    # split the first rep's compile wall out of the timed
                    # window — steady-state reps never pay it, so leaving
                    # it in made rep-0 rates read as phantom regressions.
                    # Guard: background precompiles can overlap the wall,
                    # so never let the adjustment eat >95% of it.
                    d["compile_s"][tag] = dp_compile
                    if dp_compile > 0 and wall - dp_compile > 0.05 * wall:
                        wall -= dp_compile
                d["samples"][tag].append(work / wall if wall > 0 else 0.0)
                if ttfe == ttfe:  # not NaN
                    d["ttfes"][tag].append(ttfe)
                if len(out) > 5 and out[5] == out[5]:  # time-to-full-recall
                    d["ttfrs"][tag].append(out[5])
                # residency = device-executed instructions / states explored:
                # meaningful only for state-counting workloads, and a
                # workload that warms up internally supplies its own delta
                if production and work and unit == "states/sec":
                    dev = (
                        out[3]
                        if len(out) > 3 and out[3] is not None
                        else fstats.device_instructions - dev_before
                    )
                    d["residency"].append(dev / work)
                if production and wall > 0:
                    # walker/harvest cost as a share of the workload wall —
                    # the number that says whether host-side event replay
                    # is the frontier's cost center.  A workload with an
                    # internal warm-up supplies its own delta (out[4]),
                    # mirroring the residency channel.
                    har = (
                        out[4]
                        if len(out) > 4 and out[4] is not None
                        else fstats.harvest_s - har_before
                    )
                    d["harvest_shares"].append(har / wall)
                    d["harvest_phases"].append({
                        p: get_registry().histogram(
                            "frontier.harvest.%s_s" % p
                        ).sum - phases_before[p]
                        for p in _HARVEST_PHASES
                    })
                if production:
                    d["prefilter"].append({
                        k: get_registry().counter("prefilter.%s" % k).value
                        - pf_before[k]
                        for k in ("evaluated", "killed", "fallthrough")
                    })
                    d["devsolver"].append({
                        k: get_registry().counter("devsolver.%s" % k).value
                        - ds_before[k]
                        for k in _DEVSOLVER_KEYS
                    })
                    d["adaptive"].append({
                        k: get_registry().counter("adaptive.%s" % k).value
                        - ad_before[k]
                        for k in _ADAPTIVE_KEYS
                    })
                    d["segments"].append(fstats.segments - seg_before)
                    # large-code frontier: per-rep pad economics (gauges
                    # reflect the most recent multi-code run) + paging
                    # pressure deltas attributed to this rep
                    d["frontier"].append({
                        "bucket_classes": int(get_registry().gauge(
                            "frontier.bucket_classes").value or 0),
                        "pad_waste_pct": float(get_registry().gauge(
                            "frontier.pad_waste_pct").value or 0.0),
                        "pad_waste_single_bucket_pct": float(
                            get_registry().gauge(
                                "frontier.pad_waste_single_bucket_pct"
                            ).value or 0.0),
                        "page_faults": int(
                            fstats.page_faults - page_before[0]),
                        "page_repacks": int(
                            fstats.page_repacks - page_before[1]),
                        "page_resident_pct": float(get_registry().gauge(
                            "frontier.page_resident_pct").value or 100.0),
                    })
                    led = get_exploration_ledger()
                    t_after = led.terminated()
                    # partition invariant: every stamped path carries
                    # exactly one class (stamp() increments both sides)
                    assert sum(t_after.values()) == led.terminated_total(), (
                        "exploration termination classes do not partition: "
                        f"{t_after} != total {led.terminated_total()}"
                    )
                    term_delta = {
                        cls: max(n - expl_before.get(cls, 0), 0)
                        for cls, n in t_after.items()
                    }
                    d["exploration"].append({
                        "terminated": term_delta,
                        "terminated_total": sum(term_delta.values()),
                        "coverage_pct": led.coverage_pct(),
                        "coverage_pct_reachable":
                            led.coverage_pct_reachable(),
                    })
                    edge_pct = get_registry().gauge(
                        "staticpass.reachable_edge_pct"
                    ).snapshot()
                    if edge_pct:
                        d["staticpass_edge_pct"].append(float(edge_pct))
                if production:
                    # a workload with an internal warm-up supplies its own
                    # timed-run delta (out[6]), mirroring out[3]/out[4]
                    mid = (
                        out[6]
                        if len(out) > 6 and out[6] is not None
                        else _mid_delta(fstats, mid_before)
                    )
                    d["mids"].append(mid)
            if name in skipped:
                continue
            # LATEST pair wall, not the max: rep 0 includes once-per-process
            # warm-ups (wide_frontier/corpus segment compiles) that later
            # reps never pay — a max would over-trim them
            pair_cost[name] = time.perf_counter() - t_pair
            d["completed_reps"] += 1
            row = _row_summary(unit, d, configured_reps=reps)
            for tag in ("baseline", "production"):
                t = row["ttfe_s"].get(tag)
                print(
                    f"[bench] {name:16s} {tag:10s} {row[tag]:10.1f} {unit}"
                    f"  (rep {d['completed_reps']}"
                    + (f", ttfe {t:.2f}s" if t is not None else "")
                    + (
                        f", device {row['device_residency_pct']}%"
                        if tag == "production"
                        else ""
                    )
                    + ")",
                    file=sys.stderr,
                )
            table = {
                n: _row_summary(u, data[n], configured_reps=r)
                for n, _, u, r in WORKLOADS
                if data[n]["completed_reps"]
            }
            _emit_snapshot(table, budget_meta(), partial=True)

    table = {
        n: _row_summary(u, data[n], configured_reps=r)
        for n, _, u, r in WORKLOADS
        if data[n]["completed_reps"]
    }
    _emit_snapshot(table, budget_meta(), partial=False)

    if ttfe_budget is not None:
        violations = []
        for n, row in table.items():
            t = row.get("ttfe_s", {}).get("production")
            if t is not None and t > ttfe_budget:
                violations.append(
                    f"{n}: production ttfe_s {t:.3f} > budget "
                    f"{ttfe_budget:.3f}"
                )
        if violations:
            print(
                "[bench] TTFE budget exceeded:\n  " + "\n  ".join(violations),
                file=sys.stderr,
            )
            sys.exit(1)
        print(
            f"[bench] TTFE budget ok: every production median within "
            f"{ttfe_budget:.3f}s",
            file=sys.stderr,
        )

    if against is not None:
        doc = {
            "observability": _observability_snapshot(),
            "budget": budget_meta(),
        }
        rc = regression_gate(against, table, doc, tol=gate_tol)
        if rc:
            sys.exit(rc)


if __name__ == "__main__":
    sys.exit(main())
