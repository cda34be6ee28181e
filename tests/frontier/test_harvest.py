"""Sharded harvest executor: vectorized ingestion vs the serial reference,
laser-affinity of the replay pool, delta pulls, and serial-vs-sharded
issue-set parity.

The vectorized decoder and the replay pool are performance rewrites of
engine._harvest's inner loops; every test here pins them to the serial
semantics they replaced — the ingestion test differentially against an
inline reimplementation of the old slot-order rescan loop, the parity
tests end-to-end against ``--harvest-workers 0``.
"""

import threading
from pathlib import Path

import numpy as np
import pytest

from mythril_tpu.frontier import ops as O
from mythril_tpu.frontier.harvest import (
    HarvestExecutor,
    ingest_events,
    shutdown_replay_pool,
)
from mythril_tpu.frontier.records import PathRecord
from mythril_tpu.frontier.state import Caps, empty_state
from mythril_tpu.support.support_args import args as global_args

TESTDATA = Path(__file__).parent.parent / "testdata" / "inputs"


# ---------------------------------------------------------------------------
# vectorized ingestion vs the serial reference
# ---------------------------------------------------------------------------


def _serial_ingest(st, records, ev_seen):
    """The pre-executor engine._harvest step 1, verbatim: slot-order scan
    repeated until no new record appears."""
    B = st.events.shape[0]
    changed = True
    while changed:
        changed = False
        for slot in range(B):
            rec = records[slot]
            if rec is None:
                continue
            n_ev = int(st.ev_len[slot])
            for k in range(int(ev_seen[slot]), n_ev):
                ev = st.events[slot, k].copy()
                ev_idx = len(rec.events)
                rec.events.append(ev)
                if (int(ev[O.EV_KIND]) == O.E_FORK
                        and int(ev[O.EV_EXTRA]) >= 0):
                    child_slot = int(ev[O.EV_EXTRA])
                    child = PathRecord(
                        seed_idx=rec.seed_idx, parent=rec,
                        fork_event_idx=ev_idx,
                    )
                    rec.children_by_event[ev_idx] = child
                    records[child_slot] = child
                    ev_seen[child_slot] = 0
                    changed = True
            ev_seen[slot] = n_ev


def _hook_event(pc):
    ev = np.full(O.EV_W, -1, np.int64)
    ev[O.EV_KIND] = O.E_HOOK
    ev[O.EV_PC] = pc
    return ev


def _fork_event(pc, child_slot):
    ev = np.full(O.EV_W, -1, np.int64)
    ev[O.EV_KIND] = O.E_FORK
    ev[O.EV_PC] = pc
    ev[O.EV_EXTRA] = child_slot
    return ev


def _put_events(st, slot, events):
    for k, ev in enumerate(events):
        st.events[slot, k] = ev
    st.ev_len[slot] = len(events)


def _fixture_state(caps):
    """Slot 0 forks into slot 2 which forks (same segment) into slot 5 —
    the chain the old ``while changed`` rescan existed for — plus an
    unrelated path in slot 1 and a dead single-branch fork row."""
    st = empty_state(caps, 4)
    records = {i: None for i in range(caps.B)}
    records[0] = PathRecord(seed_idx=0)
    records[1] = PathRecord(seed_idx=1)
    for s in (0, 1, 2, 5):
        st.seed[s] = 0 if s != 1 else 1
        st.halt[s] = O.H_RUNNING
    _put_events(st, 0, [_hook_event(3), _fork_event(7, 2), _hook_event(9)])
    _put_events(st, 1, [_hook_event(4), _fork_event(6, -1)])  # single-branch
    _put_events(st, 2, [_hook_event(8), _fork_event(11, 5)])  # child forks
    _put_events(st, 5, [_hook_event(12)])  # grandchild, same segment
    return st, records


def _record_shape(records):
    out = {}
    for slot, rec in records.items():
        if rec is None:
            continue
        out[slot] = {
            "seed": rec.seed_idx,
            "fork_event_idx": rec.fork_event_idx,
            "parent": next(
                (s for s, r in records.items() if r is rec.parent), None
            ),
            "events": [tuple(int(x) for x in ev) for ev in rec.events],
            "children": sorted(rec.children_by_event.keys()),
        }
    return out


def test_fork_chain_ingestion_matches_serial_reference():
    caps = Caps(B=8)
    st_a, rec_a = _fixture_state(caps)
    st_b, rec_b = _fixture_state(caps)
    seen_a = np.zeros(caps.B, np.int64)
    seen_b = np.zeros(caps.B, np.int64)

    ingest_events(st_a, rec_a, seen_a)
    _serial_ingest(st_b, rec_b, seen_b)

    assert _record_shape(rec_a) == _record_shape(rec_b)
    assert np.array_equal(seen_a, seen_b)
    # the chain resolved: grandchild record exists with correct lineage
    assert rec_a[5].parent is rec_a[2]
    assert rec_a[2].parent is rec_a[0]
    assert rec_a[2].fork_event_idx == 1  # second event of slot 0's stream
    assert rec_a[0].children_by_event[1] is rec_a[2]


def test_ingestion_resumes_from_ev_seen():
    """A second harvest of the same segment must only append the unseen
    suffix (the pipelined loop re-enters with nonzero ev_seen)."""
    caps = Caps(B=4)
    st = empty_state(caps, 4)
    records = {i: None for i in range(caps.B)}
    records[0] = PathRecord(seed_idx=0)
    st.seed[0] = 0
    _put_events(st, 0, [_hook_event(1), _hook_event(2), _hook_event(3)])
    ev_seen = np.zeros(caps.B, np.int64)
    st.ev_len[0] = 2
    ingest_events(st, records, ev_seen)
    assert len(records[0].events) == 2 and ev_seen[0] == 2
    st.ev_len[0] = 3
    ingest_events(st, records, ev_seen)
    assert len(records[0].events) == 3 and ev_seen[0] == 3
    assert [int(e[O.EV_PC]) for e in records[0].events] == [1, 2, 3]


# ---------------------------------------------------------------------------
# seed affinity: one worker per laser, slot order within it
# ---------------------------------------------------------------------------


class _Rec(PathRecord):
    """PathRecord plus a slot breadcrumb (the real class has __slots__)."""

    __slots__ = ("_slot",)


class _Laser:
    def __init__(self):
        self.work_list = []
        self.total_states = 0


class _AffinityWalker:
    """Instrumented walker: records which thread replays each record."""

    def __init__(self, lasers, seed_laser):
        self.lasers = [seed_laser[i] for i in range(len(seed_laser))]
        self._all = lasers
        self.by_laser = {id(l): [] for l in lasers}
        self.lock = threading.Lock()
        self.committed = []

    def laser_for(self, rec):
        return self.lasers[rec.seed_idx]

    def replay(self, rec):
        with self.lock:
            self.by_laser[id(self.laser_for(rec))].append(
                (threading.get_ident(), rec._slot)
            )

    def commit(self, rec):
        self.committed.append(rec._slot)


class _FakeEngine:
    def __init__(self, caps):
        self.caps = caps

    def _prune_running(self, st, records, walker, ev_seen, pipe=None):
        pass

    def _prefetch_mutation_checks(self, st, records, walker):
        pass


def test_replay_shards_have_laser_affinity_and_slot_order():
    caps = Caps(B=16)
    lasers = [_Laser(), _Laser(), _Laser()]
    # seeds 0,3 -> laser 0; 1,4 -> laser 1; 2,5 -> laser 2 (interleaved,
    # like a multi-selector corpus batch)
    seed_laser = {i: lasers[i % 3] for i in range(6)}
    walker = _AffinityWalker(lasers, seed_laser)
    st = empty_state(caps, 4)
    records = {i: None for i in range(caps.B)}
    for slot in range(12):
        seed = slot % 6
        rec = _Rec(seed_idx=seed)
        rec._slot = slot
        records[slot] = rec
        st.seed[slot] = seed
        st.halt[slot] = O.H_STOP  # every path finished
    try:
        HarvestExecutor(_FakeEngine(caps), workers=4).harvest(
            st, records, walker, np.zeros(caps.B, np.int64)
        )
    finally:
        shutdown_replay_pool()
    for laser in lasers:
        replays = walker.by_laser[id(laser)]
        assert replays, "every laser received finishing paths"
        threads = {t for t, _ in replays}
        assert len(threads) == 1, (
            f"laser touched by {len(threads)} worker threads"
        )
        slots = [s for _, s in replays]
        assert slots == sorted(slots), "shard must replay in slot order"
    # commit stays on the calling thread, in global slot order
    assert walker.committed == sorted(walker.committed)
    assert len(walker.committed) == 12
    assert all(records[s] is None for s in range(12)), "slots recycled"


def test_serial_escape_hatch_uses_no_pool():
    caps = Caps(B=4)
    lasers = [_Laser()]
    walker = _AffinityWalker(lasers, {0: lasers[0]})
    st = empty_state(caps, 4)
    records = {i: None for i in range(caps.B)}
    rec = _Rec(seed_idx=0)
    rec._slot = 0
    records[0] = rec
    st.seed[0] = 0
    st.halt[0] = O.H_RETURN
    HarvestExecutor(_FakeEngine(caps), workers=0).harvest(
        st, records, walker, np.zeros(caps.B, np.int64)
    )
    (replays,) = walker.by_laser[id(lasers[0])]
    assert replays[0] == threading.get_ident(), "workers=0 replays inline"


# ---------------------------------------------------------------------------
# term interning under concurrent replay
# ---------------------------------------------------------------------------


def test_intern_table_is_race_free_under_threads():
    from mythril_tpu.smt import terms

    results = [[] for _ in range(8)]

    def mint(out):
        for i in range(200):
            x = terms.var("race_x%d" % (i % 10), 256)
            out.append(terms.add(x, terms.const(i % 7, 256)))

    threads = [
        threading.Thread(target=mint, args=(out,)) for out in results
    ]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    # identical (op, args, aux) keys must be the SAME object across threads
    for other in results[1:]:
        for a, b in zip(results[0], other):
            assert a is b, "interning minted duplicate terms under threads"


# ---------------------------------------------------------------------------
# serial vs sharded end-to-end parity (differential, device forced on)
# ---------------------------------------------------------------------------


def _analyze(code: bytes, tx_count: int, modules, harvest_workers: int):
    from mythril_tpu.analysis.module.loader import ModuleLoader
    from mythril_tpu.analysis.security import fire_lasers, reset_callback_modules
    from mythril_tpu.analysis.symbolic import SymExecWrapper

    reset_callback_modules()
    for m in ModuleLoader().get_detection_modules():
        if hasattr(m, "cache"):
            m.cache.clear()
    prev = (global_args.frontier, global_args.frontier_force,
            global_args.frontier_mesh, global_args.harvest_workers)
    global_args.frontier = True
    global_args.frontier_force = True
    global_args.frontier_mesh = False
    global_args.harvest_workers = harvest_workers
    try:
        sym = SymExecWrapper(
            code,
            address=0x0901D12E,
            strategy="dfs",
            transaction_count=tx_count,
            execution_timeout=120,
            modules=modules,
        )
        return fire_lasers(sym, white_list=modules)
    finally:
        (global_args.frontier, global_args.frontier_force,
         global_args.frontier_mesh, global_args.harvest_workers) = prev


def _issue_keys(issues):
    return sorted((i.swc_id, i.address, i.function) for i in issues)


def _frontier_marks():
    """Park stamps + path counts: the harvest-visible side effects the
    sharded executor must reproduce bit-for-bit."""
    from mythril_tpu.frontier.stats import FrontierStatistics

    s = FrontierStatistics()
    return {
        "parks_by_opcode": dict(s.parks_by_opcode.most_common()),
        "parks_by_reason": dict(s.parks_by_reason.most_common()),
        "device_paths": s.device_paths,
        "semantic_parks": s.semantic_parks,
    }


def _run_marked(code, txs, modules, workers):
    from mythril_tpu.observability.metrics import get_registry

    get_registry().reset(prefix="frontier.")
    issues = _analyze(code, txs, modules, workers)
    return _issue_keys(issues), _frontier_marks()


@pytest.mark.slow
def test_harvest_parity_fork_heavy():
    # 8 reconvergent symbolic branches (256 concurrent paths) ending in an
    # unguarded SELFDESTRUCT: every path is a terminal replay, the shape
    # that maximizes replay-pool pressure
    from bench_contracts import wide_contract

    code = wide_contract(8)
    serial_issues, serial_marks = _run_marked(
        code, 1, ["AccidentallyKillable"], 0
    )
    assert any(s == "106" for s, _, _ in serial_issues)
    for workers in (1, 4):
        issues, marks = _run_marked(
            code, 1, ["AccidentallyKillable"], workers
        )
        assert issues == serial_issues, (
            f"workers={workers} changed the issue set"
        )
        assert marks == serial_marks, (
            f"workers={workers} changed park stamps/path counts: "
            f"{marks} != {serial_marks}"
        )


@pytest.mark.slow
def test_harvest_parity_multi_tx_storage_gate():
    # storage-gated selfdestruct: the 2-tx chain exercises park-carrier
    # restore and slot recycling across harvests
    from tests.frontier.test_frontier_engine import DISPATCH

    guarded = DISPATCH + "600054600114601b5733ff5b00"
    code = bytes.fromhex(guarded)
    serial_issues, serial_marks = _run_marked(
        code, 2, ["AccidentallyKillable"], 0
    )
    sharded_issues, sharded_marks = _run_marked(
        code, 2, ["AccidentallyKillable"], 4
    )
    assert sharded_issues == serial_issues
    assert sharded_marks == serial_marks


@pytest.mark.parametrize("name", [
    "killbilly", pytest.param("wide_fork", marks=pytest.mark.slow),
])
def test_sharded_harvest_uses_pool_and_keeps_issue_set(name, monkeypatch):
    """--harvest-workers 4 vs 0: identical issue sets with SWC-106 found,
    the sharded run dispatched replays to the pool, the serial run none."""
    from bench_contracts import wide_contract
    from tests.parity import exploit, parity_run

    if name == "wide_fork":  # 256 fork-chained paths, harvest-bound
        target, txs = wide_contract(8), 1
    else:
        target, txs = exploit(name)
    runs = {
        workers: parity_run(monkeypatch, target, txs,
                            ["AccidentallyKillable"], ("frontier.",),
                            harvest_workers=workers, frontier_mesh=False)
        for workers in (4, 0)
    }
    (sharded, sharded_snap), (serial, serial_snap) = runs[4], runs[0]
    assert any(swc == "106" for swc, _ in sharded), sharded
    assert sharded == serial
    assert sharded_snap["frontier.harvest.sharded_paths"] > 0, sharded_snap
    assert serial_snap["frontier.harvest.sharded_paths"] == 0, serial_snap


# ---------------------------------------------------------------------------
# delta pulls: bit-identical mirror vs the full pull
# ---------------------------------------------------------------------------


def test_delta_pull_matches_full_pull():
    import jax.numpy as jnp

    from mythril_tpu.frontier.step import pull_harvest, push_state

    caps = Caps(B=8)
    st = empty_state(caps, 4)
    for s in range(4):
        st.seed[s] = s
        st.halt[s] = O.H_RUNNING
        st.pc[s] = 10 + s
        st.stack[s, :2] = [100 + s, 200 + s]
        st.stack_len[s] = 2
        st.cons[s, 0] = 7
        st.cons_len[s] = 1
    st.halt[2] = O.H_STOP  # finishing slot: its rows must be re-pulled
    st.events[1, 0, :] = 5
    st.ev_len[1] = 1
    st.cons[3, 1] = 9
    st.cons_len[3] = 2  # grew since the previous pull

    dev = push_state(st)
    dev = dev._replace(
        events=jnp.asarray(st.events), ev_len=jnp.asarray(st.ev_len)
    )
    full = pull_harvest(dev, 17, 55, 3)

    # previous mirror: stale where the device advanced
    prev = empty_state(caps, 4)
    for name, dst, src in zip(prev._fields, prev, full[0]):
        if name != "events":
            dst[...] = src
    prev.cons_len[3] = 1
    prev.cons[3, 1] = -1
    prev.stack[2] = -1
    prev.ev_len[1] = 0

    delta = pull_harvest(dev, 17, 55, 3, prev=prev)
    assert delta[1:] == full[1:]
    for name, a, b in zip(full[0]._fields, full[0], delta[0]):
        assert np.array_equal(np.asarray(a), np.asarray(b)), (
            f"delta pull diverged from full pull in {name}"
        )
