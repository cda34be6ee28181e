"""FrontierEngine: orchestrates device segments against the host engine.

Replaces the host work-list loop (reference mythril/laser/ethereum/
svm.py:261-304) for message-call transactions: eligible seed states are
packed into the device batch, the jitted segment program executes up to
``caps.K`` instructions per dispatch for the whole batch, and each harvest

  1. pulls the state mirror + new arena rows,
  2. threads new fork children into the host-side path records,
  3. replays completed paths' events through host GlobalStates (walker) —
     firing detector hooks, archiving open world states, and pushing parked
     paths onto ``laser.work_list`` for the host engine to continue,
  4. recycles freed slots for queued seeds / pending forks.

Multi-code batching: the dispatch tables are stacked per code identity and
every path carries a ``code_id``, so seeds from DIFFERENT contracts — a
corpus sweep driven by ``drain_lasers``, or several codes on one work list —
share a single wide segment.  The reference analyzes a corpus strictly
sequentially (mythril/mythril/mythril_analyzer.py:138-175, one contract at a
time); here the corpus IS the batch axis.

Anything the device cannot run (CALL family, creation txs, symbolic memory
addressing, cap overflows) degrades gracefully: the path is parked with its
exact machine state and the ordinary host engine picks it up — the frontier
is a fast path, never a semantics fork.
"""

from __future__ import annotations

import logging
import threading
import time
from typing import Callable, Dict, List, Optional, Sequence, Tuple

import numpy as np

from mythril_tpu.frontier import ops as O
from mythril_tpu.frontier import taint
from mythril_tpu.frontier.arena import HostArena
from mythril_tpu.frontier.code import (
    CTX_ADDRESS,
    CTX_BALANCES,
    CTX_BASEFEE,
    CTX_CALLER,
    CTX_CALLVALUE,
    CTX_CDSIZE,
    CTX_CHAINID,
    CTX_COINBASE,
    CTX_DIFFICULTY,
    CTX_GASLIMIT,
    CTX_GASPRICE,
    CTX_NUMBER,
    CTX_ORIGIN,
    CTX_SEED,
    CTX_STORAGE,
    CTX_TIMESTAMP,
    CodeTables,
    bucket_classes,
    multi_size_bucket,
    pad_waste_pct,
    stacked_device_tables,
    visited_instr_cap,
)
from mythril_tpu.frontier.harvest import HarvestExecutor
from mythril_tpu.frontier.records import PathRecord, snapshot_slot
from mythril_tpu.frontier.state import Caps, FrontierState, clear_slot, empty_state
from mythril_tpu.frontier.stats import FrontierStatistics
from mythril_tpu.observability import deviceplane as _devplane
from mythril_tpu.observability import flightrecorder as _frec
from mythril_tpu.observability import tracer as _otrace
from mythril_tpu.observability.metrics import get_registry as _get_metrics
from mythril_tpu.frontier.step import (
    ArenaDev,
    CfgScalars,
    CodeDev,
    cached_segment,
    pull_harvest,
    push_state,
)
from mythril_tpu.frontier.walker import Walker
from mythril_tpu.support.device import note_host_route
from mythril_tpu.support.support_args import args
from mythril_tpu.support.time_handler import time_handler

log = logging.getLogger(__name__)

# codes a frontier run proved dynamically NARROW (max live paths stayed
# under caps.MIN_LIVE): later narrow drains skip the device for them a
# priori.  A WIDE seed set still admits them — their width comes from many
# seeds, not fanout, and lanes amortize the dispatch regardless.
_NARROW_CODES: set = set()

# codes the throughput bail proved SLOWER THAN THE HOST at whatever width
# they actually reached: unlike the narrow verdict, a wide re-drain of the
# same code just re-pays the proven loss, so this memo outranks the width
# bypass (mixed batches with any unmarked code still go)
_SLOW_CODES: set = set()

# mid-run throughput bail: consecutive post-warmup segments whose
# (device instructions / SEGMENT-ONLY wall — dispatch + transfers, not
# harvest, which is replay/confirmation work the host path pays too) fall
# below the bail threshold hand the run to the host engine.  The only
# correct baseline is the HOST's measured stepping rate on THIS workload
# (laser.host_step_rate — it spans 5..900 states/s: heavy wide-mul term
# construction vs light dispatch code), compared at a 0.7 safety factor.
# Before enough host samples exist the floor below applies — LOW enough
# that slow-host workloads (bectoken segments measure ~230 instr/s against
# a 5 states/s host, anchor measured at a 100 ms dispatch round trip) are
# never bailed blind.  At a sub-millisecond round trip segment walls shrink
# and the bail becomes unreachable.
_SLOW_BAIL_FLOOR = 100.0
_SLOW_BAIL_HOST_FACTOR = 0.7
_SLOW_BAIL_SEGMENTS = 2
# a DECISIVE loss (segment rate under half the bail rate, i.e. the device
# is running at under 0.35x the slowest host alternative) bails after ONE
# warm segment: round 4's first-analysis numbers showed narrow real
# contracts losing 0.3-0.7x for two full segments before the counter
# tripped, and the first analysis is the case that matters
_SLOW_BAIL_DECISIVE = 0.5

# slow-segment counters persist ACROSS runs per code (short explorations
# split into several 1-2 segment runs, so a per-run counter never reaches
# the bail threshold); a fast segment resets its codes.  These dicts are
# deliberately process-lifetime state, NOT per-analysis telemetry: the
# observability registry mirrors the verdicts under persistent-scope
# metrics (frontier.slow_code_verdicts / frontier.narrow_code_verdicts)
# that survive the reset_analysis_metrics() sweep, exactly like the dicts.
_SLOW_SEGMENTS: Dict[object, int] = {}

# (caps, bucket) programs already dispatched once this process: their first
# segment paid any XLA compile, so later runs' first segments count toward
# the throughput bail
_WARM_PROGRAMS: set = set()

# static width hint: below this many JUMPIs across the seed codes a narrow
# seed set cannot fan out wide enough to amortize segment dispatches
_MIN_STATIC_JUMPIS = 8

# observed-width admission gate (calibration-scaled with the link RTT): a
# seed set narrower than this stays host-side even when statically branchy.
# Round 4's static-JUMPI-only gate admitted requires-style contracts
# (overflow/underflow: 10 JUMPIs but observed max work-list width 5-12 —
# every fork's other side reverts) which then lost 0.3-0.7x to segment
# fixed costs on the first analysis.  Genuinely wide workloads prove their
# width ON THE HOST within milliseconds (fork doubling), so demanding
# observed width costs them one drain interval, not a compile or a segment.
_MIN_SEED_WIDTH = 8

_jumpi_count_cache: Dict[object, int] = {}


def _code_key(code):
    bytecode = getattr(code, "bytecode", None)
    return hash(bytecode) if bytecode else id(code)


def _jumpi_count(code) -> int:
    # keyed by bytecode hash (NOT id(code): a freed Disassembly's id can be
    # recycled for a different contract), bounded against unbounded growth
    key = _code_key(code)
    got = _jumpi_count_cache.get(key)
    if got is None:
        got = sum(
            1 for ins in code.instruction_list if ins.opcode == "JUMPI"
        )
        if len(_jumpi_count_cache) >= 4096:
            _jumpi_count_cache.clear()
        _jumpi_count_cache[key] = got
    return got


_code_tag_cache: Dict[object, str] = {}


def _code_tag(code) -> str:
    """Short codehash prefix for solver-hotspot program-point labels."""
    key = _code_key(code)
    tag = _code_tag_cache.get(key)
    if tag is None:
        bytecode = getattr(code, "bytecode", b"") or b""
        if bytecode:
            from mythril_tpu.support.support_utils import get_code_hash

            tag = get_code_hash(bytecode.hex())[:10]
        else:
            tag = "?"
        if len(_code_tag_cache) >= 4096:
            _code_tag_cache.clear()
        _code_tag_cache[key] = tag
    return tag


_code_hash_cache: Dict[object, str] = {}


def _code_hash_full(code) -> str:
    """Full codehash — the exploration ledger's coverage key (and the
    adaptive planner's), so steering weights join coverage bitmaps
    without prefix games."""
    key = _code_key(code)
    h = _code_hash_cache.get(key)
    if h is None:
        bytecode = getattr(code, "bytecode", b"") or b""
        if bytecode:
            from mythril_tpu.support.support_utils import get_code_hash

            h = get_code_hash(bytecode.hex())
        else:
            h = "?"
        if len(_code_hash_cache) >= 4096:
            _code_hash_cache.clear()
        _code_hash_cache[key] = h
    return h


def _adaptive_pick(seed_queue: List[int], seed_code_idx: List[int],
                   table_hash: List[str]) -> int:
    """Queue position of the next seed to inject (0 = the FIFO order every
    pre-adaptive build used).  With >1 code queued and the controller
    enabled, the steering plan's deficit scheduler picks the code whose
    uncovered reachable edges earn the next slot."""
    if len(seed_queue) <= 1:
        return 0
    try:
        from mythril_tpu.adaptive import get_adaptive_controller

        ctrl = get_adaptive_controller()
        if not ctrl.enabled:
            return 0
        ctrl.plan()  # throttled refresh; cheap when recently built
        return ctrl.pick_seed(
            [table_hash[seed_code_idx[si]] for si in seed_queue]
        )
    except Exception:  # steering must never break a dispatch
        log.debug("adaptive seed pick failed", exc_info=True)
        return 0


def _adaptive_coverage_stop() -> bool:
    """True when the --coverage-target contract says stop exploring
    (bar reached or all-codes plateau)."""
    if not getattr(args, "coverage_target", None):
        return False
    try:
        from mythril_tpu.adaptive import get_adaptive_controller

        return get_adaptive_controller().coverage_stop() is not None
    except Exception:  # pragma: no cover - defensive
        log.debug("adaptive coverage check failed", exc_info=True)
        return False


def _strategy_chain(laser):
    """The active strategy and every strategy it wraps (extensions nest via
    ``super_strategy``), outermost first."""
    strategy = laser.strategy
    while strategy is not None:
        yield strategy
        strategy = getattr(strategy, "super_strategy", None)


def _is_concolic(laser) -> bool:
    """Concolic runs are excluded from the frontier: trace recording and the
    ConcolicStrategy depend on the host engine stepping every instruction."""
    from mythril_tpu.core.strategy.concolic import ConcolicStrategy

    return any(isinstance(s, ConcolicStrategy) for s in _strategy_chain(laser))


def _sel_mode(laser) -> int:
    """Map the active host search strategy onto the device fork-grant
    priority (step.SEL_*) — the batched form of the strategy's ordering
    (SURVEY.md §7.2 item 5).  Strategies with host-only scores (beam's
    annotation importance, random) keep slot order; their ordering applies
    when parked/spilled paths re-enter the host work list."""
    from mythril_tpu.core.strategy.basic import (
        BeamSearch,
        BreadthFirstSearchStrategy,
        DepthFirstSearchStrategy,
    )
    from mythril_tpu.frontier import step as step_mod
    from mythril_tpu.plugins.plugins.coverage import CoverageStrategy

    for strategy in _strategy_chain(laser):
        if isinstance(strategy, CoverageStrategy):
            return step_mod.SEL_COVERAGE
        if isinstance(strategy, BeamSearch):
            return step_mod.SEL_BEAM
        if isinstance(strategy, DepthFirstSearchStrategy):
            return step_mod.SEL_DEEP
        if isinstance(strategy, BreadthFirstSearchStrategy):
            return step_mod.SEL_SHALLOW
    return step_mod.SEL_NONE


def _beam_importance(gs) -> int:
    """The host beam score (strategy/basic.py BeamSearch.beam_priority):
    annotations are SHARED across a seed's fork tree, so this is exact for
    every device descendant of ``gs`` at segment time."""
    try:
        return int(sum(a.search_importance for a in gs._annotations))
    except Exception:
        return 0


def _frame_ok(gs) -> bool:
    from mythril_tpu.core.transaction.transaction_models import (
        MessageCallTransaction,
    )

    return (
        isinstance(gs.current_transaction, MessageCallTransaction)
        and gs.environment.code is not None
        and len(gs.environment.code.instruction_list) > 0
        # static (STATICCALL) frames are eligible: the per-path static flag
        # halts state-mutating ops as terminals whose replay raises the
        # host WriteProtection (step.py write-violation override)
    )


def _is_fresh(gs) -> bool:
    return gs.mstate.pc == 0 and not gs.mstate.stack


# leave headroom below the device caps: an injected state that starts near a
# cap would park within a few instructions and bounce host<->device
_MID_STACK_MAX = Caps.STK - 12
_MID_MEM_MAX = Caps.MEM - 12


def _mid_eligible(gs) -> bool:
    """Mid-frame states the device can RE-ENTER: pc > 0 with a bounded
    stack and concretely-addressed memory — resumed callers after inner
    calls, batch-full spills, timeout/arena bulk parks (reference engine
    continues ANY state, svm.py:261-304; round-3 frontier only admitted
    fresh frames so every park left the device permanently)."""
    if len(gs.mstate.stack) > _MID_STACK_MAX:
        return False
    if gs.mstate.pc >= len(gs.environment.code.instruction_list):
        return False
    if len(gs.mstate.memory) > _MID_MEM_MAX * 32:
        return False
    # memoized per (pc, #writes): a state is immutable while it waits on
    # the work list, and drains rescan the list every few instructions —
    # the O(M log M) walk must not repeat per scan
    memo_key = (gs.mstate.pc, len(gs.mstate.memory))
    cached = getattr(gs, "_frontier_mem_ok", None)
    if cached is not None and cached[0] == memo_key:
        return cached[1]
    addrs = gs.mstate.memory.concrete_addresses()
    ok = addrs is not None
    gs._frontier_mem_ok = (memo_key, ok)
    if not ok:
        # symbolic memory addressing blocks the device AT this pc: stamp so
        # the cheap top-level guard skips this state until the host engine
        # has advanced it (fresh copies drop the stamp)
        gs._frontier_park_pc = gs.mstate.pc
    return ok


def _eligible(gs) -> bool:
    """Seed states the device can take: fresh message-call frames (pc 0,
    empty stack) — including INNER call frames, which the nested-frontier
    drains in svm.exec rely on — plus re-entrant mid-frame states (see
    ``_mid_eligible``).

    States the device parked for a SEMANTIC reason carry
    ``_frontier_park_pc``; while still AT that pc they would re-park on
    the first device step (this covers fresh-looking pc=0 parks too)."""
    try:
        if getattr(gs, "_frontier_park_pc", None) == gs.mstate.pc:
            return False
        if not _frame_ok(gs):
            return False
        return _is_fresh(gs) or _mid_eligible(gs)
    except Exception:
        return False


def reset_isolation_gauges() -> None:
    """Clear the per-analysis bucket-isolation latch.

    ``frontier.bucket_classes`` and the pad-waste gauges are sticky
    within one analysis (a multi-class dispatch must survive later
    single-class tail rounds), so each fresh analysis zeroes them here
    before its first dispatch — otherwise a long-lived process (daemon,
    test process) would report the previous corpus's split."""
    reg = _get_metrics()
    for name in (
        "frontier.bucket_classes",
        "frontier.pad_waste_pct",
        "frontier.pad_waste_single_bucket_pct",
        "frontier.page_resident_pct",
    ):
        reg.gauge(name).set(0)


def _latch_resident_pct(pct: float) -> None:
    """Record the LOWEST residency observed this analysis: a fully
    resident class dispatched after a paged one must not mask the paged
    class's figure (gauges are last-write-wins)."""
    gauge = _get_metrics().gauge("frontier.page_resident_pct")
    current = float(gauge.value or 0.0)
    if current <= 0.0 or pct < current:
        gauge.set(pct)


def drain_lasers(
    lasers: List,
    caps: Optional[Caps] = None,
    bucket_floor: Optional[tuple] = None,
    tags: Optional[Sequence[str]] = None,
    flow_cb: Optional[Callable[[], None]] = None,
) -> int:
    """Run eligible seeds from EVERY laser's work list as one multi-code
    frontier batch (the cooperative corpus entry point).  Parked paths land
    back on their own laser's work list.  Returns #instructions executed.

    Lasers must share search configuration (max_depth / strategy family);
    heterogeneous groups run as separate batches.  ``bucket_floor`` pins a
    minimum (code_cap, instr_cap, addr_cap, loops_cap) so every round of a
    cooperative run reuses ONE compiled segment program even as the live
    code set shrinks (a smaller round must not trigger a fresh XLA compile
    mid-sweep).  ``tags`` (service request ids riding this batch) annotate
    every ``frontier.segment`` span so a shared wide device segment is
    attributable to the requests it serves.  ``flow_cb`` is invoked once,
    inside the first ``frontier.segment`` span actually dispatched — the
    service uses it to record per-request trace-flow endpoints there, so
    request span trees join the segment that served them (and no arrow
    dangles when a batch never reaches the device)."""
    groups: Dict[tuple, List[Tuple]] = {}
    for laser in lasers:
        if _is_concolic(laser):
            continue
        seeds = [s for s in laser.work_list if _eligible(s)]
        if not seeds:
            continue
        key = (laser.max_depth, _sel_mode(laser))
        groups.setdefault(key, []).extend((laser, s) for s in seeds)
    # a single corpus-wide floor covers the WHOLE corpus: applying it to a
    # small heterogeneous group would pad that group's device tables to the
    # full code axis (wasted HBM); with one group — the practical
    # cooperative case — the floor is exact.  PER-CLASS floors (a list,
    # from bucket_hint_classes) survive any grouping: each class picks the
    # smallest floor that covers it, so nothing over-pads.
    if len(groups) > 1 and not isinstance(bucket_floor, list):
        bucket_floor = None
    executed = 0
    for pairs in groups.values():
        engine = FrontierEngine(pairs[0][0], caps)
        if tags:
            engine.request_tags = tuple(tags)
        engine.request_flow_cb = flow_cb
        executed += engine._drain_pairs(pairs, bucket_floor=bucket_floor)
    return executed


class FrontierEngine:
    def __init__(self, laser, caps: Optional[Caps] = None):
        self.laser = laser
        self.caps = caps or Caps(B=args.frontier_width)
        # service request ids riding this engine's segments (set by
        # drain_lasers(tags=...)); stamped onto frontier.segment spans
        self.request_tags: Optional[tuple] = None
        # one-shot callback fired inside the first segment span actually
        # dispatched (drain_lasers(flow_cb=...)): the service records its
        # per-request trace-flow endpoints there
        self.request_flow_cb: Optional[Callable[[], None]] = None

    def _fire_request_flows(self) -> None:
        """Invoke the service's flow callback once, inside a segment span."""
        cb, self.request_flow_cb = self.request_flow_cb, None
        if cb is not None:
            try:
                cb()
            except Exception:  # telemetry must never break a dispatch
                log.debug("request flow callback failed", exc_info=True)

    # ------------------------------------------------------------------

    def drain_work_list(self) -> int:
        """Run every eligible work-list state on the device; parked paths
        land back on ``laser.work_list``.  Returns #states executed."""
        laser = self.laser
        if _is_concolic(laser):
            return 0
        seeds = [s for s in laser.work_list if _eligible(s)]
        if not seeds:
            return 0
        return self._drain_pairs([(laser, s) for s in seeds])

    def _drain_pairs(self, pairs: List[Tuple],
                     bucket_floor: Optional[tuple] = None) -> int:
        """Run (laser, seed) pairs as one batch; seeds are removed from
        their work lists and never lost (parked back on failure)."""
        if not self._device_worthwhile(pairs):
            note_host_route("narrow_gate")
            return 0
        for laser, s in pairs:
            laser.work_list.remove(s)
        try:
            return self._run(pairs, bucket_floor=bucket_floor)
        except Exception:
            # never lose a seed: hand everything back to the host engines.
            # Paths a partial frontier run already completed re-run on host;
            # the per-(address, bytecode) issue cache absorbs duplicates.
            for laser, s in pairs:
                laser.work_list.append(s)
            raise

    def _device_worthwhile(self, pairs: List[Tuple]) -> bool:
        """A-priori narrow bail: segment dispatches only amortize over wide
        frontiers, so a seed set that cannot fan out stays host-side.  The
        admission evidence is OBSERVED width (the link-calibrated
        _MIN_SEED_WIDTH); a statically-branchy seed set that has already
        fanned out to half the gate is admitted early."""
        if args.frontier_force:
            return True
        # scale the break-evens to the measured link (no-op after first call)
        from mythril_tpu.support.calibration import calibrate

        calibrate()
        codes = {id(s.environment.code): s.environment.code for _, s in pairs}
        # the slow verdict outranks the width bypass (see _SLOW_CODES)
        if all(_code_key(c) in _SLOW_CODES for c in codes.values()):
            return False
        width_gate = max(self.caps.MIN_LIVE, _MIN_SEED_WIDTH)
        if len(pairs) >= width_gate:
            return True
        if all(
            _code_key(c) in _NARROW_CODES or _code_key(c) in _SLOW_CODES
            for c in codes.values()
        ):
            return False
        # early admission for provably-branchy code that is already halfway
        # to the width gate: fork doubling will cross it within one segment
        # (no MIN_LIVE floor here — at the default gate==MIN_LIVE this
        # clause is only reached when len(pairs) < MIN_LIVE, so flooring
        # would make it dead code)
        return (
            sum(_jumpi_count(c) for c in codes.values()) >= _MIN_STATIC_JUMPIS
            and len(pairs) >= max(2, width_gate // 2)
        )

    # ------------------------------------------------------------------

    @staticmethod
    def _hook_info(laser, summary=None) -> Tuple[set, set, set]:
        """(hooked, concrete-nop, value-gated) opcode sets for this laser.

        When a static summary for the code being packed is supplied
        (mythril_tpu/staticpass), two further elisions apply per code:
        opcodes with no statically reachable instruction leave the hooked
        set (their events could never fire), and an opcode whose EVERY
        hook belongs to a module with a statically dead declared taint
        flow is dropped too — safe because such modules only raise at
        their declared sinks, and those sinks (JUMPI) are _ALWAYS_EVENT
        ops whose events and host hook replays are unaffected by this
        set.

        An opcode is concrete-nop when EVERY hook on it (pre and post) is a
        bound method of a module that declares it in ``concrete_nop_hooks``
        — the device may then suppress its events for all-concrete operands
        (the hook provably does nothing there).

        An opcode is dropped from the hooked set ENTIRELY when every hook
        on it is a declared taint source (module ``taint_source_hooks``):
        its only effect — annotating the pushed value — is reproduced by
        the seeded taint bit on the source's env row plus the walker's
        row-graph closure (frontier/taint.py), so device executions need
        no event at all.

        The value-gated set (module ``value_gated_hooks``) marks opcodes
        whose events the device ships only when the value operand is
        CONCRETE with the solc panic selector in its top 32 bits (the
        MSTORE gate; the hook no-ops on symbolic values too)."""
        # defaultdict access creates empty entries; only real hooks count
        hooked = {
            op
            for reg in (laser._pre_hooks, laser._post_hooks)
            for op, funcs in reg.items()
            if op and funcs
        }
        conc_nop = set()
        for op in hooked:
            if all(
                op in getattr(getattr(hook, "__self__", None),
                              "concrete_nop_hooks", ())
                for reg in (laser._pre_hooks, laser._post_hooks)
                for hook in reg.get(op, [])
            ):
                conc_nop.add(op)
        def _declared_bit(hook, op):
            decl = getattr(getattr(hook, "__self__", None),
                           "taint_source_hooks", {})
            return decl.get(op) if hasattr(decl, "get") else None

        # drop only when every hook declares the op AND the declared bit is
        # actually seeded + registered (taint.suppressible) — an undeclared
        # or unseedable bit would silently disable the detector on device
        taint_src = {
            op
            for op in hooked
            if all(
                (bit := _declared_bit(hook, op)) is not None
                and taint.suppressible(bit)
                for reg in (laser._pre_hooks, laser._post_hooks)
                for hook in reg.get(op, [])
            )
        }
        val_gate = {
            op
            for op in hooked
            if all(
                op in getattr(getattr(hook, "__self__", None),
                              "value_gated_hooks", ())
                for reg in (laser._pre_hooks, laser._post_hooks)
                for hook in reg.get(op, [])
            )
        }
        hooked = hooked - taint_src
        if summary is not None:
            from mythril_tpu.staticpass import GateView, module_relevant

            view = GateView([summary])
            dropped = {
                op for op in hooked if op not in summary.reachable_opcodes
            }
            for op in hooked - dropped:
                owners = {
                    getattr(hook, "__self__", None)
                    for reg in (laser._pre_hooks, laser._post_hooks)
                    for hook in reg.get(op, [])
                }
                if owners and all(
                    m is not None
                    and getattr(m, "static_taint_sources", None)
                    and getattr(m, "static_taint_sinks", None)
                    and not module_relevant(m, view)
                    for m in owners
                ):
                    dropped.add(op)
            if dropped:
                from mythril_tpu.observability import get_registry

                get_registry().counter(
                    "staticpass.hooks_elided_device"
                ).inc(len(dropped))
                hooked -= dropped
        return hooked, conc_nop, val_gate

    def _seed_ctx(self, arena: HostArena, gs, seed_idx: int) -> np.ndarray:
        from mythril_tpu.smt import symbol_factory

        env = gs.environment
        ctx = np.full(16, -1, np.int32)
        ctx[CTX_CALLER] = arena.var_row(env.sender.raw)
        ctx[CTX_CALLVALUE] = arena.var_row(env.callvalue.raw)
        ctx[CTX_ADDRESS] = arena.var_row(env.address.raw)
        ctx[CTX_CDSIZE] = arena.var_row(env.calldata.calldatasize.raw)
        ctx[CTX_BALANCES] = arena.encode(gs.world_state.balances.raw)
        ctx[CTX_STORAGE] = arena.encode(
            env.active_account.storage._array.raw
        )
        ctx[CTX_GASPRICE] = arena.var_row(env.gasprice.raw)
        ctx[CTX_DIFFICULTY] = arena.var_row(
            gs.new_bitvec("block_difficulty", 256).raw
        )
        # taint-source slots use DEDICATED rows (arena.fresh_var_row): host
        # taint is per-USE, and origin aliases the sender term / gaslimit is
        # a constant a program literal could equal — tainting an interned
        # row would leak the bit to non-source uses of the same term
        from mythril_tpu.smt import terms as _T

        # no_fold: a device constant fold emits a REF-LESS row, which would
        # cut the tainted gaslimit constant out of the walker's closure —
        # the host annotation survives folding on the wrapper, so the
        # device must keep the dataflow edge (the branch forks symbolically
        # and the infeasible side dies at the sibling check's decode fold)
        ctx[CTX_GASLIMIT] = arena.fresh_var_row(
            _T.const(gs.mstate.gas_limit, 256), no_fold=True
        )
        ctx[CTX_ORIGIN] = arena.fresh_var_row(env.origin.raw)
        ctx[CTX_TIMESTAMP] = arena.fresh_var_row(
            symbol_factory.BitVecSym("timestamp", 256).raw
        )
        ctx[CTX_NUMBER] = arena.fresh_var_row(env.block_number.raw)
        ctx[CTX_COINBASE] = arena.fresh_var_row(
            gs.new_bitvec("coinbase", 256).raw
        )
        ctx[CTX_CHAINID] = arena.var_row(env.chainid.raw)
        ctx[CTX_BASEFEE] = arena.var_row(env.basefee.raw)
        ctx[CTX_SEED] = seed_idx
        # taint-source seeding: any row whose closure reaches one of these
        # source rows carries the bit — the device-side form of the
        # post-hook annotation.  ENV_SOURCE_SLOTS is the same table
        # taint.suppressible consults, so a suppressible bit is always
        # seeded here.
        for bit, slot in taint.ENV_SOURCE_SLOTS.items():
            arena.add_taint(ctx[slot], bit)
        return ctx

    def _inject(self, st: FrontierState, slot: int, seed_idx: int,
                ctx: np.ndarray, code_idx: int, score: int = 0,
                static: int = 0) -> None:
        clear_slot(st, slot)
        st.seed[slot] = seed_idx
        st.halt[slot] = O.H_RUNNING
        st.ctx[slot] = ctx
        st.code_id[slot] = code_idx
        st.score[slot] = score
        st.static[slot] = static

    def _encode_mid(self, arena: HostArena, gs) -> Optional[dict]:
        """Pack a mid-frame host state for device re-entry, or None.

        Stack words (symbolic included) become arena rows; concretely
        addressed memory is regrouped into the device's disjoint 32-byte
        word entries.  Loop counters start at zero — a re-entered path may
        re-run up to loop_bound extra iterations before the device bound
        trips (bounded, and the host bounded-loops strategy still applies
        to whatever parks back).  Gas starts at zero on device: the walker
        reports seed-relative totals via its per-seed gas_base."""
        I32_MAX = (1 << 31) - 1
        if arena.length > self.caps.ARENA * 9 // 10:
            # near-capacity: an encode raising halfway would strand its
            # already-appended rows (the arena has no rollback); the run is
            # about to park on arena pressure anyway
            return None
        try:
            # validate memory FIRST: stack encoding appends arena rows, and
            # rows for a seed bounced afterwards would leak into the shared
            # arena (pulling the arena-full park forward)
            addrs = gs.mstate.memory.concrete_addresses()
            if addrs is None:
                return None
            windows = []
            i, n = 0, len(addrs)
            while i < n:
                start = addrs[i]
                if i + 32 > n or addrs[i : i + 32] != list(
                    range(start, start + 32)
                ):
                    return None  # partial word: the entry model can't hold it
                if start + 32 > I32_MAX:
                    return None  # device addresses are i32
                windows.append(start)
                i += 32
            if len(windows) > _MID_MEM_MAX:
                return None
            pc = int(gs.mstate.pc)
            mem_size = int(getattr(gs.mstate, "memory_size", 0) or 0)
            depth = int(getattr(gs.mstate, "depth", 0) or 0)
            if max(pc, mem_size, depth) > I32_MAX:
                return None
            # host-installed taint annotations must survive re-entry as row
            # bits or the sink check would miss them (frontier/taint.py).
            # A TAINTED wrapper gets a DEDICATED opaque row: tainting the
            # interned/structural row would leak the bit to every other use
            # of the same term (origin aliases the sender term — the exact
            # false-SWC-115 fabrication fresh_var_row exists to prevent)
            def enc(wrapper) -> int:
                mask = taint.mask_for_annotations(
                    getattr(wrapper, "annotations", ())
                )
                if not mask:
                    return arena.encode(wrapper.raw)
                return arena.tainted_row(wrapper.raw, mask)

            mem_pairs = [
                (a, enc(gs.mstate.memory.get_word_at(a))) for a in windows
            ]
            stack_rows = [enc(v) for v in gs.mstate.stack]
            return {
                "pc": pc,
                "stack": stack_rows,
                "mem": mem_pairs,
                "mem_size": mem_size,
                "depth": depth,
            }
        except Exception as e:
            log.debug("mid-frame encode failed: %s", e)
            return None

    @staticmethod
    def _apply_mid(st: FrontierState, slot: int, enc: dict) -> None:
        st.pc[slot] = enc["pc"]
        for k, row in enumerate(enc["stack"]):
            st.stack[slot, k] = row
        st.stack_len[slot] = len(enc["stack"])
        for k, (addr, row) in enumerate(enc["mem"]):
            st.mem_addr[slot, k] = addr
            st.mem_val[slot, k] = row
        st.mem_len[slot] = len(enc["mem"])
        st.mem_size[slot] = enc["mem_size"]
        st.depth[slot] = enc["depth"]

    # ------------------------------------------------------------------
    # large-code frontier: per-class floors + packed-code paging
    # ------------------------------------------------------------------

    # faults per code before the engine stops repacking for it and pins
    # further faulting paths host-side (semantic park) — a window that
    # keeps missing is a code the host engine runs better
    _PAGE_FAULT_LIMIT = 8

    @staticmethod
    def _pick_floor(floors: List[tuple],
                    natural: tuple) -> Optional[tuple]:
        """Smallest per-class floor that covers ``natural`` in every
        dimension, or None.  A floor that only partially covers would
        produce a third bucket shape (elementwise max) that neither the
        sweep's floored program nor the natural program matches — a
        guaranteed mid-sweep recompile — so partial covers are skipped."""
        best = None
        for f in floors:
            if len(f) != len(natural):
                continue
            if all(fv >= nv for fv, nv in zip(f, natural)):
                if best is None or f[0] * f[1] < best[0] * best[1]:
                    best = f
        return best

    def _note_page_fault(self, code_idx: int, pc: int) -> bool:
        """Record a device page fault (harvest calls this).  Returns True
        when the window will be repacked to cover ``pc`` at the next sync
        point — the faulting path should then re-inject as an ordinary
        park carrier.  Returns False once the code exceeded the fault
        budget: the caller pins the path host-side instead."""
        _get_metrics().counter("frontier.page_faults").inc()
        counts = getattr(self, "_page_fault_counts", None)
        if counts is None or not getattr(self, "_page_tables", None):
            return False
        tables = self._page_tables
        if not (0 <= code_idx < len(tables)):
            return False
        counts[code_idx] = counts.get(code_idx, 0) + 1
        if counts[code_idx] > self._PAGE_FAULT_LIMIT:
            return False
        axis = self._page_bucket[1]
        full = tables[code_idx].fam.shape[0]
        if full <= axis:
            # not actually paged (stale pc past the code end): no repack
            return False
        # window start: a quarter-axis of context before the fault pc so
        # backward jumps inside the new span stay resident, clamped to
        # keep the window inside the code
        base = min(max(0, int(pc) - axis // 4), full - axis)
        self._page_pending[code_idx] = base
        return True

    def _maybe_repack(self):
        """Rebuild the device tables with pending window moves folded in
        (sync points only).  Same bucket, same shapes — the compiled
        segment program is untouched; only table CONTENT re-uploads.
        Returns the fresh CodeDev, or None when nothing is pending."""
        pending = getattr(self, "_page_pending", None)
        if not pending:
            return None
        for ci, base in pending.items():
            self._page_bases[ci] = base
        pending.clear()
        reg = _get_metrics()
        reg.counter("frontier.page_repacks").inc()
        tables, bucket = self._page_tables, self._page_bucket
        axis = bucket[1]
        full_rows = sum(t.fam.shape[0] for t in tables)
        _latch_resident_pct(round(
            100.0 * sum(min(t.fam.shape[0], axis) for t in tables)
            / max(full_rows, 1), 1,
        ))
        return CodeDev(*[
            self._page_placer(a)
            for a in stacked_device_tables(
                tables, bucket, page_bases=self._page_bases)
        ])

    # ------------------------------------------------------------------

    def _run(self, pairs: List[Tuple],
             bucket_floor: Optional[tuple] = None) -> int:
        caps = self.caps
        t_start = time.perf_counter()

        # mesh precondition lift: pad the slot batch up to a multiple of
        # the attached device count so the path axis always shards evenly
        # (the old `caps.B % n_dev == 0` gate silently fell back to a
        # single device).  The extra slots are ordinary dead slots (seed
        # -1, never injected into unless paths need them) — they cost only
        # their share of the packed transfers.
        if args.frontier_mesh:
            import dataclasses

            import jax

            n_dev = jax.device_count()
            if n_dev > 1 and caps.B % n_dev:
                pad = -caps.B % n_dev
                caps = dataclasses.replace(caps, B=caps.B + pad)
                self.caps = caps
                _get_metrics().counter("frontier.mesh_pad_slots").inc(pad)

        seed_lasers = [laser for laser, _ in pairs]
        seeds = [gs for _, gs in pairs]
        lasers: List = []
        for laser in seed_lasers:
            if laser not in lasers:
                lasers.append(laser)

        arena = HostArena(caps.ARENA)
        arena.seeds = seeds
        row_zero = arena.const_row(0, 256)
        row_one = arena.const_row(1, 256)

        # one stacked table entry per (laser, code) identity: hooks differ
        # per laser, so the same bytecode under two lasers gets two entries
        tables: List[CodeTables] = []
        table_laser: List = []
        table_code: List = []
        table_hash: List[str] = []
        table_idx: Dict[tuple, int] = {}
        seed_code_idx: List[int] = []
        for laser, gs in pairs:
            code = gs.environment.code
            key = (id(laser), id(code))
            ci = table_idx.get(key)
            if ci is None:
                ci = len(tables)
                table_idx[key] = ci
                # once-per-bytecode static pre-analysis (cached): prunes
                # events on statically unreachable instructions and feeds
                # the per-code hook elision below; None = pass disabled
                # or failed, packing proceeds exactly as before
                from mythril_tpu.staticpass import (
                    publish_reachability,
                    summary_for_code,
                )

                summary = summary_for_code(code)
                # register the reachable-edge oracle with the exploration
                # ledger so coverage is also quoted against the statically
                # reachable denominator (coverage_pct_reachable), and hand
                # the static interesting points to the adaptive controller
                # (flip-target ranking shares the oracle's codehash key)
                publish_reachability(code, summary)
                if summary is not None and getattr(
                        summary, "interesting_points", None):
                    try:
                        from mythril_tpu.adaptive import (
                            get_adaptive_controller,
                        )

                        get_adaptive_controller().register_points(
                            _code_hash_full(code),
                            summary.interesting_points,
                        )
                    except Exception:  # steering never breaks packing
                        log.debug("adaptive point registration failed",
                                  exc_info=True)
                hooked, conc_nop, val_gate = self._hook_info(laser, summary)
                tables.append(
                    CodeTables(
                        code.instruction_list,
                        arena,
                        hooked_opcodes=hooked,
                        code_size=len(getattr(code, "bytecode", b"") or b"")
                        or None,
                        conc_nop_opcodes=conc_nop,
                        value_gate_opcodes=val_gate,
                        static_summary=summary,
                    )
                )
                table_laser.append(laser)
                table_code.append(code)
                table_hash.append(_code_hash_full(code))
            seed_code_idx.append(ci)

        # ------------------------------------------------------------------
        # per-code bucket isolation (large-code frontier): cluster the
        # codes by their own size bucket and dispatch one batch per class,
        # so a creation-heavy outlier pays for its own padded axes instead
        # of inflating every small code's tables.  ``bucket_floor`` may be
        # a list of per-class floors (cooperative driver) — each class
        # picks the smallest floor that covers it.  --no-code-paging keeps
        # the legacy single corpus-wide bucket (the parity baseline).
        # ------------------------------------------------------------------
        floors: List[tuple] = []
        if isinstance(bucket_floor, list):
            floors = [tuple(f) for f in bucket_floor]
            bucket_floor = None
        elif bucket_floor is not None:
            floors = [tuple(bucket_floor)]
            bucket_floor = None
        if getattr(args, "code_paging", True):
            classes = bucket_classes(tables)
        else:
            classes = [(multi_size_bucket(tables), list(range(len(tables))))]
        if len(classes) > 1:
            reg = _get_metrics()
            # the counterfactual is the LEGACY single corpus-wide bucket —
            # no paging, so its instruction axis covers the largest code in
            # full (the r19 tail: one outlier inflating everyone's axes).
            # multi_size_bucket() under paging clamps at the residency
            # budget, which would understate the waste being recovered.
            single = multi_size_bucket(tables)
            single = (
                single[0],
                max(single[1], max(t.full_instr_cap() for t in tables)),
            ) + single[2:]
            single_waste = round(pad_waste_pct(tables, single), 2)
            waste_num = waste_den = 0.0
            for cls_bucket, members in classes:
                cells = cls_bucket[0] * cls_bucket[1]
                waste_num += pad_waste_pct(
                    [tables[i] for i in members], cls_bucket
                ) * cells
                waste_den += cells
                reg.gauge(
                    "frontier.bucket_class_occupancy.%d" % cls_bucket[1]
                ).set(round(100.0 * len(members) / cls_bucket[0], 1))
            total = 0
            for _cls_bucket, members in classes:
                member_set = set(members)
                sub_pairs = [
                    p for p, ci in zip(pairs, seed_code_idx)
                    if ci in member_set
                ]
                total += self._run(sub_pairs, bucket_floor=floors or None)
            # aggregate LAST: each single-class sub-run above writes its
            # own class's figure into the gauges, so the corpus-weighted
            # aggregate must land after the recursion to survive
            reg.gauge("frontier.pad_waste_pct").set(
                round(waste_num / max(waste_den, 1.0), 2)
            )
            reg.gauge("frontier.pad_waste_single_bucket_pct").set(
                single_waste
            )
            reg.gauge("frontier.bucket_classes").set(len(classes))
            return total

        natural_bucket = multi_size_bucket(tables)
        bucket = natural_bucket
        floor = self._pick_floor(floors, natural_bucket)
        if floor is not None:
            bucket = tuple(max(b, f) for b, f in zip(bucket, floor))
        code_cap, instr_cap, addr_cap, loops_cap = bucket
        # coverage planes are indexed by TRUE pc, so their axis covers the
        # whole code even when paged dispatch tables hold only a window
        visit_cap = max(instr_cap, visited_instr_cap(tables))
        _waste = round(pad_waste_pct(tables, bucket), 2)
        _reg = _get_metrics()
        # single class: the class bucket IS the corpus bucket, so the
        # counterfactual equals the actual.  A multi-class dispatch
        # earlier in this analysis latches bucket_classes >= 2 — its
        # corpus-weighted figures are the ones worth keeping, so a later
        # single-class round (e.g. a tail transaction touching one code)
        # must not clobber them.  reset_isolation_gauges() clears the
        # latch at analysis entry.  bucket_classes reads 0 when isolation
        # is off (--no-code-paging), 1 when on but the corpus is uniform.
        if int(_reg.gauge("frontier.bucket_classes").value or 0) <= 1:
            _reg.gauge("frontier.pad_waste_pct").set(_waste)
            _reg.gauge("frontier.pad_waste_single_bucket_pct").set(_waste)
            _reg.gauge("frontier.bucket_classes").set(
                1 if getattr(args, "code_paging", True) else 0
            )
        _reg.gauge(
            "frontier.bucket_class_occupancy.%d" % instr_cap
        ).set(round(100.0 * len(tables) / code_cap, 1))
        # packed-code paging state: per-table resident-window starts plus
        # pending faults the next sync-point repack folds in
        self._page_tables = tables
        self._page_bucket = bucket
        self._page_bases = [0] * len(tables)
        self._page_pending = {}
        self._page_fault_counts = {}
        paged_rows = sum(t.fam.shape[0] for t in tables)
        self._page_resident = sum(
            min(t.fam.shape[0], instr_cap) for t in tables
        )
        _latch_resident_pct(
            round(100.0 * self._page_resident / max(paged_rows, 1), 1)
        )
        program_key = (caps, bucket, visit_cap)
        program_warm = program_key in _WARM_PROGRAMS
        _devplane.install()
        with _otrace.span("frontier.compile", cat="frontier",
                          warm=program_warm, bucket=list(bucket)), \
                _devplane.dispatch_scope(bucket):
            # builds (or returns) the jitted program; the XLA compile
            # itself is paid inside the first dispatch's segment span
            # (warm=False marks it)
            segment = cached_segment(caps, *bucket)
        # marked warm only AFTER a segment actually dispatches (loop below):
        # a run that breaks before its first segment must not tag the still
        # uncompiled program as warm, or the NEXT run's compile-paying first
        # segment would count toward the slow-bail verdict
        import jax

        # tables are uploaded once and reused per segment; a page-fault
        # repack (window move) rebuilds them at the next sync point with
        # IDENTICAL shapes, so no recompile ever rides a repack
        code_dev = CodeDev(
            *[jax.device_put(a) for a in stacked_device_tables(
                tables, bucket, page_bases=self._page_bases)]
        )
        laser0 = lasers[0]
        cfg = CfgScalars(
            max_depth=np.int32(laser0.max_depth),
            loop_bound=np.int32(args.loop_bound or 0),
            row_zero=np.int32(row_zero),
            row_one=np.int32(row_one),
            sel_mode=np.int32(_sel_mode(laser0)),
            k_limit=np.int32(caps.K),
        )

        # seed contexts (also fills the arena with env rows)
        ctxs = [self._seed_ctx(arena, gs, i) for i, gs in enumerate(seeds)]

        # mid-frame seeds (resumed callers, earlier spills) are encoded up
        # front; any the encoder rejects bounce straight back to their host
        # work list (eligibility is a cheap pre-filter, the encoder decides)
        mid_enc: List[Optional[dict]] = []
        bounced = set()
        for i, gs in enumerate(seeds):
            if _is_fresh(gs):
                mid_enc.append(None)
                continue
            with _otrace.span("frontier.mid_encode", cat="frontier", seed=i):
                enc = self._encode_mid(arena, gs)
            mid_enc.append(enc)
            if enc is None:
                FrontierStatistics().mid_encode_failures += 1
                # stamp so _mid_eligible stops re-offering this state at
                # every drain while it sits at the same pc.  The work-list
                # re-append happens at the END of the run: _drain_pairs'
                # exception handler re-appends every pair, so appending here
                # would duplicate the state if the run later failed.
                gs._frontier_park_pc = gs.mstate.pc
                bounced.add(i)

        walker = Walker(seed_lasers, arena,
                        [tables[ci] for ci in seed_code_idx], seeds)
        st = empty_state(caps, loops_cap)
        records: Dict[int, Optional[PathRecord]] = {i: None for i in range(caps.B)}
        seed_queue = [i for i in range(len(seeds)) if i not in bounced]
        ev_seen = np.zeros(caps.B, np.int64)

        from mythril_tpu.frontier import step as step_mod

        beam = _sel_mode(laser0) == step_mod.SEL_BEAM

        statics = [
            1 if getattr(gs.environment, "static", False) else 0
            for gs in seeds
        ]

        # initial fill (adaptive: the steering plan's deficit scheduler
        # orders multi-code injection; FIFO — the parity baseline — with
        # one code, no plan, or --no-adaptive)
        for slot in range(caps.B):
            if not seed_queue:
                break
            si = seed_queue.pop(
                _adaptive_pick(seed_queue, seed_code_idx, table_hash)
            )
            self._inject(st, slot, si, ctxs[si], seed_code_idx[si],
                         _beam_importance(seeds[si]) if beam else 0,
                         static=statics[si])
            if mid_enc[si] is not None:
                self._apply_mid(st, slot, mid_enc[si])
                FrontierStatistics().mid_injections += 1
            records[slot] = PathRecord(seed_idx=si)
            ev_seen[slot] = 0

        # the arena stays device-resident across segments; the host pulls
        # only the newly appended row slices at each harvest
        dev_arena = ArenaDev(
            *[jax.device_put(a) for a in arena.device_arrays()]
        )
        arena_len = arena.length
        # [3, C, I] coverage planes: instruction / taken-edge / fall-edge
        # (see observability/exploration.py for the plane contract).  The
        # instruction axis is the FULL cap (true-pc indexed), independent
        # of the possibly-windowed dispatch-table axis
        visited = jax.device_put(np.zeros((3, code_cap, visit_cap), bool))

        # SPMD over the mesh path axis (SURVEY.md §5.8): with >1 attached
        # device the segment inputs are placed path-sharded (state) /
        # replicated (arena, tables, coverage) and GSPMD partitions the SAME
        # jitted program — the fork-grant prefix sum becomes the only
        # cross-shard collective
        mesh = None
        push_sharded = None
        n_dev = jax.device_count()
        if args.frontier_mesh and n_dev > 1:
            from mythril_tpu.parallel.mesh import (
                make_frontier_mesh,
                path_sharding,
            )

            if caps.B % n_dev:
                # caller-pinned caps the padding above could not touch
                # (checkpoint resume with a fixed width): run single-device,
                # but LOUDLY — the metric makes the fallback visible
                _get_metrics().counter("frontier.mesh_fallbacks").inc()
                log.warning(
                    "frontier: batch width %d not divisible by %d devices; "
                    "falling back to single-device execution",
                    caps.B, n_dev,
                )
            else:
                mesh = make_frontier_mesh(path_size=n_dev)
        self._mesh_shards = n_dev if mesh is not None else 1
        if mesh is not None:
            from jax.sharding import NamedSharding, PartitionSpec

            FrontierStatistics().mesh_devices = n_dev
            repl = NamedSharding(mesh, PartitionSpec())
            # read-mostly inputs placed replicated ONCE; segment outputs keep
            # their shardings, so no per-segment re-placement is needed
            dev_arena = jax.tree.map(
                lambda x: jax.device_put(x, repl), dev_arena
            )
            visited = jax.device_put(visited, repl)
            code_dev = jax.tree.map(
                lambda x: jax.device_put(x, repl), code_dev
            )

            def _path_sharding(x):
                return path_sharding(mesh, x)

            # event buffers start empty every segment: one constant sharded
            # pair reused for the whole run (nothing crosses the link)
            mesh_empty_events = jax.device_put(
                np.full_like(st.events, -1), _path_sharding(st.events)
            )
            mesh_empty_ev_len = jax.device_put(
                np.zeros_like(st.ev_len), _path_sharding(st.ev_len)
            )

            def push_sharded(state: FrontierState) -> FrontierState:
                """Host mirror -> path-sharded device state: each field ships
                straight from host numpy to its shards (no single-device
                staging hop; local-device transfers are cheap, unlike the
                100 ms round trip the packed push_state was anchored on)."""
                fields = {
                    name: jax.device_put(f, _path_sharding(f))
                    for name, f in zip(state._fields, state)
                    if name not in ("events", "ev_len")
                }
                fields["events"] = mesh_empty_events
                fields["ev_len"] = mesh_empty_ev_len
                return FrontierState(**fields)

        if mesh is not None:
            _repl = NamedSharding(mesh, PartitionSpec())
            self._page_placer = lambda a: jax.device_put(a, _repl)
        else:
            self._page_placer = jax.device_put
        executed = 0
        exec_timeout = min(
            laser.execution_timeout or args.execution_timeout
            for laser in lasers
        )
        deadline = t_start + exec_timeout
        narrow_harvests = 0
        max_live = 0
        run_segments = 0
        slow_bailed = False

        width_verdict_valid = True  # False when the run was cut short
        skip_loop = False

        # TTFE fix: a floored bucket shares one compiled program across a
        # cooperative corpus, but a COLD program then pays the big bucket's
        # XLA compile before the first event can harvest.  Run the OPENING
        # dispatch at the program's natural bucket — a small program that
        # compiles fast — harvest it, and only then enter the floored-bucket
        # loop.  Time-to-first-event now rides the small compile; the big
        # compile amortizes over the rest.
        if mesh is None and bucket != natural_bucket and not program_warm:
            nat_cc, _nat_ic, _nat_ac, nat_lc = natural_bucket
            stats = FrontierStatistics()

            # pre-compile the floored big-bucket program in the background
            # while the opening natural-bucket segment runs: a dummy
            # dispatch on an all-empty state (every seed -1, so the segment
            # while_loop condition is false and zero steps execute) pays
            # exactly the XLA compile.  cached_segment only builds the
            # wrapper and jit.lower().compile() does not populate the
            # dispatch cache, so a real dispatch is the reliable warmup.
            # Inputs are shared with the live dispatch safely: the segment
            # donates nothing (_SEGMENT_DONATE_ARGNUMS is empty).
            def _precompile_floored():
                t0 = time.perf_counter()
                # the compile happens on THIS daemon thread: scope it so
                # the device plane attributes it to the floored bucket
                with _devplane.dispatch_scope(bucket):
                    try:
                        out = segment(
                            push_state(empty_state(caps, loops_cap)),
                            dev_arena, arena_len, visited, code_dev, cfg,
                        )
                        np.asarray(out[3])  # force completion
                    except Exception as e:  # pragma: no cover - diagnostics
                        log.debug("floored-bucket precompile failed: %s", e)
                _get_metrics().observe(
                    "frontier.bucket_compile_s", time.perf_counter() - t0
                )

            precompile = threading.Thread(
                target=_precompile_floored,
                name="mythril-bucket-precompile",
                daemon=True,
            )
            precompile.start()
            nat_segment = cached_segment(caps, *natural_bucket)
            nat_code_dev = CodeDev(*[
                jax.device_put(a)
                for a in stacked_device_tables(
                    tables, natural_bucket, page_bases=self._page_bases)
            ])
            # full-cap coverage axis (true-pc indexed), same as the floored
            # planes — the corner copy below is then a straight slice
            nat_visited = jax.device_put(
                np.zeros((3, nat_cc, visit_cap), bool)
            )
            cfg0 = cfg._replace(
                k_limit=np.int32(min(caps.K, 96 << min(stats.segments, 4)))
            )
            st_nat = st._replace(loops=st.loops[:, :nat_lc])
            t_seg = time.perf_counter()
            _fid0 = (_otrace.get_tracer().new_flow_id()
                     if _otrace.get_tracer().enabled else None)
            with _otrace.span(
                "frontier.segment", cat="device", segment=-1,
                warm=(caps, natural_bucket, visit_cap) in _WARM_PROGRAMS,
                opening=True,
                **(
                    {"requests": ",".join(self.request_tags)}
                    if self.request_tags else {}
                ),
            ), _otrace.device_annotation("frontier.segment"), \
                    _devplane.dispatch_scope(natural_bucket):
                if _fid0 is not None:
                    _otrace.get_tracer().flow("s", _fid0, "flow.segment",
                                              cat="device")
                self._fire_request_flows()
                out_state, dev_arena, out_len, n_exec, seg_ml, nat_visited = (
                    nat_segment(push_state(st_nat), dev_arena, arena_len,
                                nat_visited, nat_code_dev, cfg0)
                )
                st_p, arena_len, n_exec_host, seg_ml_host = pull_harvest(
                    out_state, out_len, n_exec, seg_ml
                )
            _frec.beat()
            max_live = max(max_live, seg_ml_host)
            arena.pull_from_device(dev_arena, arena_len)
            executed += n_exec_host
            stats.device_instructions += n_exec_host
            stats.segments += 1
            seg_only = time.perf_counter() - t_seg
            stats.segment_s += seg_only
            _get_metrics().observe("frontier.segment_wall_s", seg_only)
            _devplane.observe_segment(
                seg_only, _devplane.bucket_tag(natural_bucket)
            )
            _get_metrics().counter("frontier.opening_dispatches").inc()
            _WARM_PROGRAMS.add((caps, natural_bucket, visit_cap))
            st = st_p._replace(loops=np.ascontiguousarray(np.pad(
                st_p.loops, ((0, 0), (0, loops_cap - nat_lc))
            )))
            t_har = time.perf_counter()
            with _otrace.span("frontier.harvest", cat="frontier",
                              segment=-1):
                if _fid0 is not None:
                    _otrace.get_tracer().flow("f", _fid0, "flow.segment",
                                              cat="device")
                self._harvest(st, records, walker, ev_seen)
            ev_seen.fill(0)
            har_only = time.perf_counter() - t_har
            stats.harvest_s += har_only
            _get_metrics().observe("frontier.harvest_wall_s", har_only)
            # the opening coverage lives in the natural bucket's corner of
            # the floored bitmap (same code order, smaller caps)
            import jax.numpy as jnp

            visited = visited.at[:, :nat_cc, :].set(
                jnp.asarray(nat_visited)
            )
            live = int(((st.halt == O.H_RUNNING) & (st.seed >= 0)).sum())
            max_live = max(max_live, live)
            if live == 0 and not seed_queue:
                skip_loop = True  # nothing left for the floored program
            else:
                # the floored program dispatches next: join so its compile
                # time lands in bucket_compile_s and the first real segment
                # below measures dispatch, not compile
                precompile.join()

        if not skip_loop and args.pipeline:
            # pipeline and mesh COMPOSE: with a mesh the chained dispatches
            # run as one SPMD program over the path axis (push_fn places the
            # corrections with the exact shardings the in-flight outputs
            # carry, so GSPMD inserts no resharding between segments)
            from mythril_tpu.frontier.pipeline import PipelinedRunner

            runner = PipelinedRunner(
                self, st=st, records=records, walker=walker, arena=arena,
                ev_seen=ev_seen, seeds=seeds, seed_lasers=seed_lasers,
                lasers=lasers, ctxs=ctxs, seed_code_idx=seed_code_idx,
                mid_enc=mid_enc, seed_queue=seed_queue, statics=statics,
                beam=beam, tables=tables, table_code=table_code,
                table_hash=table_hash,
                table_idx=table_idx, segment=segment, code_dev=code_dev,
                cfg=cfg, dev_arena=dev_arena, arena_len=arena_len,
                visited=visited, deadline=deadline,
                program_key=program_key, program_warm=program_warm,
                mesh=mesh, push_fn=push_sharded,
                repack_fn=self._maybe_repack,
            )
            runner.run()
            st = runner.st
            executed = runner.executed + executed
            arena_len = runner.arena_len
            visited = runner.visited
            max_live = max(max_live, runner.max_live)
            slow_bailed = runner.slow_bailed
            width_verdict_valid = runner.width_verdict_valid
            skip_loop = True
        watch = _frec.activity() if not skip_loop else None
        if watch is not None:
            watch.__enter__()
        while not skip_loop:
            if time.perf_counter() > deadline or time_handler.time_remaining() <= 0:
                log.info("frontier: execution timeout; parking live paths")
                self._park_all(st, records, walker, reason="timeout")
                width_verdict_valid = False
                break

            stats = FrontierStatistics()
            t_seg = time.perf_counter()
            # step-limit ramp (dynamic scalar, no recompile): early segments
            # stay short so the first terminals harvest — and their exploits
            # confirm — quickly; later segments run long to amortize the
            # link round trip.  Keyed on the ANALYSIS-wide segment counter
            # (reset per contract by the facade), not a per-drain
            # counter: periodic nested drains must not re-pay truncated
            # segments long after the first exploit confirmed.
            cfg = cfg._replace(
                k_limit=np.int32(min(caps.K, 96 << min(stats.segments, 4)))
            )
            st_dev = push_sharded(st) if mesh is not None else push_state(st)
            _fid = (_otrace.get_tracer().new_flow_id()
                    if _otrace.get_tracer().enabled else None)
            with _otrace.span(
                "frontier.segment", cat="device",
                segment=run_segments, warm=program_warm,
                **(
                    {"requests": ",".join(self.request_tags)}
                    if self.request_tags else {}
                ),
            ), _otrace.device_annotation("frontier.segment"), \
                    _devplane.dispatch_scope(bucket):
                if _fid is not None:
                    _otrace.get_tracer().flow("s", _fid, "flow.segment",
                                              cat="device")
                self._fire_request_flows()
                out_state, dev_arena, out_len, n_exec, seg_max_live, visited = (
                    segment(st_dev, dev_arena, arena_len, visited, code_dev, cfg)
                )
                # pull state to host mirrors (writable: harvest mutates
                # slots): one packed meta transfer (scalars ride along) +
                # one bucket-capped events pull
                st, arena_len_new, n_exec_host, seg_ml_host = pull_harvest(
                    out_state, out_len, n_exec, seg_max_live
                )
            _frec.beat()
            max_live = max(max_live, seg_ml_host)
            arena.pull_from_device(dev_arena, arena_len_new)
            arena_len = arena_len_new
            executed += n_exec_host
            stats.device_instructions += n_exec_host
            stats.segments += 1
            seg_only = time.perf_counter() - t_seg
            stats.segment_s += seg_only
            _get_metrics().observe("frontier.segment_wall_s", seg_only)
            _devplane.observe_segment(seg_only, _devplane.bucket_tag(bucket))
            _WARM_PROGRAMS.add(program_key)  # a segment really dispatched

            t_har = time.perf_counter()
            with _otrace.span("frontier.harvest", cat="frontier",
                              segment=run_segments):
                if _fid is not None:
                    _otrace.get_tracer().flow("f", _fid, "flow.segment",
                                              cat="device")
                self._harvest(st, records, walker, ev_seen)
            # events were fully drained into the path records, and the next
            # segment starts with EMPTY device buffers (push_state rebuilds
            # them; events never cross the link upward) — restart the
            # per-slot seen counters to match
            ev_seen.fill(0)
            # page-fault repack: the synchronous loop is all sync points —
            # fold pending window moves into fresh tables (same shapes, no
            # recompile) before the next dispatch
            new_code_dev = self._maybe_repack()
            if new_code_dev is not None:
                code_dev = new_code_dev
            har_only = time.perf_counter() - t_har
            stats.harvest_s += har_only
            _get_metrics().observe("frontier.harvest_wall_s", har_only)

            # mid-run throughput accounting — BEFORE the exit checks below,
            # so a run's final segment still counts (short explorations
            # split into 1-2 segment drains would otherwise never
            # accumulate a verdict): a run can stay live enough to dodge
            # the narrow bail yet execute fewer instructions per second
            # than the host engine steps (small programs over a high-RTT
            # link).  Measured on SEGMENT wall only (dispatch + transfers)
            # — harvest time is replay/confirmation work the host path
            # pays too.  A run's first segment counts only when the
            # program was already warm (else it may be paying the one-off
            # XLA compile); counters persist across runs per code.
            bail_now = False
            if (run_segments > 0 or program_warm) and not args.frontier_force:
                host_rates = [
                    r for r in (
                        getattr(laser, "host_step_rate", lambda: None)()
                        for laser in lasers
                    ) if r
                ]
                # min over lasers: a multi-code batch may pair a fast-host
                # contract with one whose host alternative is 100x slower
                # (bectoken-style wide-mul terms) — bailing the batch, and
                # blanket-marking its codes, must only happen when the
                # device underruns even the SLOWEST host alternative
                bail_rate = (
                    _SLOW_BAIL_HOST_FACTOR * min(host_rates)
                    if host_rates else _SLOW_BAIL_FLOOR
                )
                code_keys = [_code_key(c) for c in table_code]
                seg_rate = n_exec_host / max(seg_only, 1e-6)
                if seg_rate < bail_rate:
                    counts = [_SLOW_SEGMENTS.get(k, 0) + 1 for k in code_keys]
                    for k, c in zip(code_keys, counts):
                        _SLOW_SEGMENTS[k] = c
                    if (
                        max(counts) >= _SLOW_BAIL_SEGMENTS
                        or seg_rate < _SLOW_BAIL_DECISIVE * bail_rate
                    ):
                        log.info(
                            "frontier: %d instructions in %.2fs (below "
                            "%.0f/s); host engine takes over",
                            n_exec_host, seg_only, bail_rate,
                        )
                        bail_now = True
                else:
                    for k in code_keys:
                        _SLOW_SEGMENTS.pop(k, None)
            run_segments += 1
            if bail_now:
                # BEFORE the refill below: injecting queued seeds just to
                # park them straight back out would be a pure encode/park
                # round trip per free slot
                slow_bailed = True
                width_verdict_valid = False
                self._park_all(st, records, walker, reason="slow-bail")
                break

            # refill free slots with queued seeds; under beam search
            # also refresh live slots' scores (a seed's shared annotation
            # may have gained importance from sibling replays)
            for slot in range(caps.B):
                rec = records[slot]
                if rec is None and seed_queue:
                    si = seed_queue.pop(
                        _adaptive_pick(seed_queue, seed_code_idx, table_hash)
                    )
                    self._inject(st, slot, si, ctxs[si], seed_code_idx[si],
                                 _beam_importance(seeds[si]) if beam else 0,
                                 static=statics[si])
                    if mid_enc[si] is not None:
                        with _otrace.span("frontier.mid_inject",
                                          cat="frontier", seed=si):
                            self._apply_mid(st, slot, mid_enc[si])
                        FrontierStatistics().mid_injections += 1
                    records[slot] = PathRecord(seed_idx=si)
                    ev_seen[slot] = 0
                elif beam and rec is not None:
                    st.score[slot] = _beam_importance(seeds[rec.seed_idx])

            live = int(((st.halt == O.H_RUNNING) & (st.seed >= 0)).sum())
            max_live = max(max_live, live)
            if live == 0 and not seed_queue:
                break
            # --coverage-target: the request contract says stop at the
            # bar (or the all-codes plateau) — spending further segments
            # on saturated code is the waste this controller exists to cut
            if _adaptive_coverage_stop():
                log.info(
                    "frontier: coverage target reached; parking live paths"
                )
                self._park_all(st, records, walker, reason="coverage-target")
                width_verdict_valid = False
                break
            if arena_len + max(live, 1) * caps.R * 2 >= caps.ARENA:
                log.warning("frontier: arena nearly full; parking live paths")
                self._park_all(st, records, walker, reason="arena-full")
                width_verdict_valid = False
                break
            # adaptive bail-out: the device pays off only on wide frontiers
            # (the per-segment dispatch amortizes over live paths); a run
            # that stays narrow hands its paths to the host engine, which
            # steps small work lists faster than segment round trips
            if live < caps.MIN_LIVE:
                narrow_harvests += 1
                if narrow_harvests >= caps.NARROW_BAIL:
                    log.info(
                        "frontier: only %d live paths after %d segments; "
                        "host engine takes over", live, narrow_harvests,
                    )
                    self._park_all(st, records, walker, reason="narrow-bail")
                    break
            else:
                narrow_harvests = 0
        if watch is not None:
            watch.__exit__(None, None, None)

        if slow_bailed:
            # slow: proven slower than host stepping on this link (absolute
            # verdict) — but only for codes whose OWN slow-segment count
            # reached the bail threshold: a mixed batch bails on its worst
            # member's count, and blanket-marking would permanently disable
            # the device for codes that just joined the batch
            for code in table_code:
                key = _code_key(code)
                if _SLOW_SEGMENTS.get(key, 0) >= _SLOW_BAIL_SEGMENTS:
                    if key not in _SLOW_CODES:
                        _get_metrics().counter(
                            "frontier.slow_code_verdicts", persistent=True
                        ).inc()
                    _SLOW_CODES.add(key)
        elif max_live < caps.MIN_LIVE and width_verdict_valid:
            # narrow: stayed under MIN_LIVE (skipped for narrow drains,
            # still admitted by wide seed sets).  A run cut short by
            # timeout/arena pressure proves nothing and marks nothing.
            for code in table_code:
                if _code_key(code) not in _NARROW_CODES:
                    _get_metrics().counter(
                        "frontier.narrow_code_verdicts", persistent=True
                    ).inc()
                _NARROW_CODES.add(_code_key(code))

        visited_host = np.asarray(visited)
        for ci, (laser, code) in enumerate(zip(table_laser, table_code)):
            self._merge_coverage(visited_host[:, ci], tables[ci], code, laser)
        for i in bounced:
            seed_lasers[i].work_list.append(seeds[i])
        # seeds still queued when a break path ended the loop (slow-bail,
        # timeout, arena pressure) never occupied a slot: hand them back to
        # their host work lists or their paths would silently vanish
        for si in seed_queue:
            seed_lasers[si].work_list.append(seeds[si])
        return executed

    @staticmethod
    def _merge_coverage(visited: np.ndarray, tables, code, laser) -> None:
        """Device-executed coverage planes ``[3, I]`` into the coverage
        plugin's bitmap and the exploration ledger (the walker only
        replays hook events, so plugin-side coverage alone would
        underreport frontier runs; edge planes exist only here)."""
        bytecode = getattr(code, "bytecode", None)
        if not bytecode:
            return
        cov = getattr(laser, "coverage_plugin", None)
        if cov is not None:
            cov.record_visited(
                bytecode.hex(), tables.n,
                np.nonzero(visited[0, : tables.n])[0],
            )
        from mythril_tpu.observability.exploration import (
            get_exploration_ledger,
        )
        from mythril_tpu.support.support_utils import get_code_hash

        get_exploration_ledger().record_device_planes(
            get_code_hash(bytecode.hex()), tables.n, _jumpi_count(code),
            visited[:, : tables.n],
        )

    # ------------------------------------------------------------------

    def _harvest(self, st: FrontierState, records, walker: Walker,
                 ev_seen: np.ndarray, pipe=None) -> None:
        """``pipe`` is the PipelinedRunner when the pipelined loop drives
        this harvest: slot mutations are reported to its correction ledger
        (so they ride the next chained dispatch) and feasibility checks go
        to its background pool instead of blocking here.

        The phase work lives in frontier/harvest.py: vectorized event
        ingestion, the laser-affinity replay pool (args.harvest_workers;
        0 = serial), and the deterministic slot-order commit."""
        HarvestExecutor(self).harvest(st, records, walker, ev_seen, pipe)

    def _lineage_constraint_rows(self, rec) -> List[int]:
        """Arena rows of the branch conditions appended along this path
        (parent prefixes up to each fork, then the record's own stream).
        Event decoding is shared with the walker (walker.fork_branch_row)."""
        from mythril_tpu.frontier.walker import fork_branch_row

        rows: List[int] = []
        chain = []
        node, upto = rec, len(rec.events)
        while node is not None:
            chain.append((node, upto))
            upto = node.fork_event_idx
            node = node.parent
        for level, (node, limit) in enumerate(reversed(chain)):
            for k in range(limit):
                ev = node.events[k]
                if int(ev[O.EV_KIND]) != O.E_FORK:
                    continue
                # this path continued past the event (fell through a granted
                # fork, or took the single decided branch)
                row = fork_branch_row(ev, taken=False)
                if row >= 0:
                    rows.append(row)
            # entering the next level means this node granted a fork the
            # child took: the child's side appended the taken condition
            if node is not rec:
                child = chain[len(chain) - 2 - level][0]
                row = fork_branch_row(
                    node.events[child.fork_event_idx], taken=True
                )
                if row >= 0:
                    rows.append(row)
        return rows

    def _lineage_mutated(self, rec, walker: Walker) -> bool:
        from mythril_tpu.plugins.plugins.mutation_pruner import MUTATOR_OPCODES

        mutators = frozenset(MUTATOR_OPCODES)
        names = walker.tables_for(rec).opcode_names
        node, upto = rec, len(rec.events)
        while node is not None:
            for k in range(upto):
                ev = node.events[k]
                if int(ev[O.EV_KIND]) != O.E_HOOK:
                    continue
                pc = int(ev[O.EV_PC])
                if pc < len(names) and names[pc] in mutators:
                    return True
            upto = node.fork_event_idx
            node = node.parent
        return False

    def _prefetch_mutation_checks(self, st: FrontierState, records,
                                  walker: Walker) -> None:
        from mythril_tpu.smt import UGT, symbol_factory
        from mythril_tpu.smt.solver import ProbeConfig, check_satisfiable_batch

        terminal = (O.H_STOP, O.H_RETURN, O.H_SELFDESTRUCT)
        queries, seen = [], set()
        for slot in range(self.caps.B):
            rec = records[slot]
            if rec is None or int(st.halt[slot]) not in terminal:
                continue
            if self._lineage_mutated(rec, walker):
                continue
            seed = walker.seeds[rec.seed_idx]
            value = seed.current_transaction.call_value
            try:
                raws = list(seed.world_state.constraints.get_all_raw())
                raws += [
                    walker.decode_wrapped(r, rec.seed_idx).raw
                    for r in self._lineage_constraint_rows(rec)
                ]
            except Exception as e:
                log.debug("mutation prefetch decode failed: %s", e)
                continue
            raws.append(
                UGT(value, symbol_factory.BitVecVal(0, 256)).raw
            )
            key = frozenset(t.tid for t in raws)
            if key not in seen:
                seen.add(key)
                queries.append(raws)
        if len(queries) >= 2:
            # the hook's exact budget (imported, so they cannot diverge); the
            # call's side effect is the solver memo the hook will hit
            from mythril_tpu.plugins.plugins.mutation_pruner import (
                MUTATION_PROBE_CONFIG,
            )
            from mythril_tpu.querycache import get_query_cache

            qc_hits = get_query_cache().hits_total()
            with _otrace.span(
                "frontier.mutation_prefetch", cat="frontier", n=len(queries)
            ) as sp:
                check_satisfiable_batch(
                    queries, ProbeConfig(**MUTATION_PROBE_CONFIG)
                )
                sp.set(
                    querycache_hits=get_query_cache().hits_total() - qc_hits
                )

    def _prune_running(self, st: FrontierState, records, walker: Walker,
                       ev_seen: np.ndarray, pipe=None) -> None:
        from mythril_tpu.smt.solver import check_satisfiable_batch

        todo = []
        for slot in range(self.caps.B):
            rec = records[slot]
            if rec is None or int(st.halt[slot]) != O.H_RUNNING:
                continue
            n_cons = int(st.cons_len[slot])
            if n_cons <= rec._pruned_at:
                continue
            if pipe is not None and n_cons <= rec._submitted_at:
                continue  # verdict for this lineage depth still pending
            seed = walker.seeds[rec.seed_idx]
            raws = list(seed.world_state.constraints.get_all_raw())
            try:
                raws += [
                    walker.decode_wrapped(int(r), rec.seed_idx).raw
                    for r in st.cons[slot, :n_cons]
                ]
            except Exception as e:
                # cannot prune this slot: treat as satisfiable (sound — the
                # path just keeps running) and don't re-decode every segment
                log.warning("prune decode failed on slot %d: %s", slot, e)
                rec._pruned_at = n_cons
                continue
            todo.append((slot, rec, n_cons, raws))
        if not todo:
            return
        if pipe is not None:
            # pipelined: the path keeps running SPECULATIVELY while the
            # pool solves in the background; an UNSAT verdict rolls it
            # back at a later harvest (pipeline.apply_verdicts).  The key
            # mirrors the solver fast path's canonical identity, so the
            # pool dedups in-flight twins and the worker hits the query
            # cache for everything already decided.
            # abstract pre-filter: one vectorized pass over the whole
            # batch of rows; a proven-UNSAT verdict skips the worker and
            # is published through the pool's normal done-queue so the
            # existing rollback machinery (apply_verdicts) kills the path
            kills = [False] * len(todo)
            if getattr(args, "prefilter", True):
                from mythril_tpu.absdomain import prefilter_batch
                from mythril_tpu.smt.solver import tier_done

                t_tier = time.perf_counter()
                kills = prefilter_batch([raws for _, _, _, raws in todo])
                tier_done("prefilter", t_tier, sum(kills))
            for (slot, rec, n_cons, raws), killed in zip(todo, kills):
                rec._submitted_at = n_cons
                pipe.pool.submit(
                    slot, rec, n_cons, raws,
                    frozenset(t.tid for t in raws),
                    sid=getattr(pipe, "current_sid", -1),
                    verdict=False if killed else None,
                    point="%s:%#x" % (
                        _code_tag(walker.seeds[rec.seed_idx].environment.code),
                        int(st.pc[slot]),
                    ),
                )
            return
        # harvest feasibility is one of the query cache's three entry points
        # (ISSUE/querycache.rst): the batched check below takes the cache's
        # exact/core tiers per set inside _fast_path; the span records how
        # many of this sweep's decisions the cache absorbed
        from mythril_tpu.querycache import get_query_cache

        from mythril_tpu.observability.exploration import (
            VERDICT_CLASS,
            get_exploration_ledger,
        )

        qc_hits = get_query_cache().hits_total()
        statuses: List[str] = []
        t_solve = time.perf_counter()
        with _otrace.span(
            "frontier.prune_check", cat="frontier", n=len(todo)
        ) as sp:
            flags = check_satisfiable_batch(
                [raws for _, _, _, raws in todo], statuses_out=statuses
            )
            sp.set(querycache_hits=get_query_cache().hits_total() - qc_hits)
        # batched solve: attribute the sweep's wall evenly across the
        # program points it decided (a documented approximation — the
        # pipelined pool times each query exactly)
        share = (time.perf_counter() - t_solve) / len(todo)
        led = get_exploration_ledger()
        if len(statuses) < len(todo):  # defensive: fill missing statuses
            statuses = statuses + ["unsat"] * (len(todo) - len(statuses))
        for (slot, rec, n_cons, _), ok, status in zip(todo, flags, statuses):
            led.record_solver_time(
                "%s:%#x" % (
                    _code_tag(walker.seeds[rec.seed_idx].environment.code),
                    int(st.pc[slot]),
                ),
                share,
            )
            if ok:
                rec._pruned_at = n_cons
            else:
                rec.term_class = VERDICT_CLASS.get(status, "solver_unsat")
                led.stamp(rec.term_class)
                records[slot] = None
                clear_slot(st, slot)
                ev_seen[slot] = 0

    def _park_all(self, st: FrontierState, records, walker: Walker,
                  reason: str = "bulk") -> None:
        """Timeout/overflow: hand every live path back to the host engine."""
        stats = FrontierStatistics()
        if reason in ("slow-bail", "narrow-bail"):
            note_host_route(reason)
        if reason in ("timeout", "coverage-target"):
            # the execution budget is gone (or the coverage contract ended
            # the request): the host work list these paths land on will
            # never be drained, so they stop exploring HERE — other park
            # reasons (slow/narrow-bail, drain) genuinely continue
            # host-side and are stamped at their real end
            from mythril_tpu.observability.exploration import (
                get_exploration_ledger,
            )

            led = get_exploration_ledger()
            for slot in range(self.caps.B):
                rec = records[slot]
                if rec is not None and rec.term_class is None:
                    rec.term_class = "budget_exhausted"
                    led.stamp("budget_exhausted")
        for slot in range(self.caps.B):
            rec = records[slot]
            if rec is None:
                continue
            if int(st.halt[slot]) == O.H_RUNNING:
                st.halt[slot] = O.H_PARK
            rec.final = snapshot_slot(st, slot)
            if rec.final["halt"] in (O.H_PENDING_FORK, O.H_PAGE_FAULT):
                rec.final["halt"] = O.H_PARK
            stats.device_paths += 1
            stats.record_bulk_park(reason)
            try:
                walker.finish(rec)
            except Exception as e:  # pragma: no cover
                log.warning("frontier park failed: %s", e, exc_info=True)
            records[slot] = None
            clear_slot(st, slot)
