"""Large-code frontier: per-code bucket isolation + packed-code paging.

One creation-heavy outlier used to inflate the corpus-wide
``multi_size_bucket`` so every small code paid the outlier's padded
instruction axis.  Bucket classes give
each size cluster its own compiled segment; codes beyond the residency
budget keep only a hot window device-resident, and a cold jump faults to
the host (``H_PAGE_FAULT``) for a sync-point repack.  The contract under
test everywhere: the issue set is bit-identical with the optimization on
or off — a faulted path degrades to an ordinary host park, and the host
engine is always correct.
"""

from collections import namedtuple

import numpy as np
import pytest

from mythril_tpu.frontier import ops as O
from mythril_tpu.frontier.arena import HostArena
from mythril_tpu.frontier.code import (
    CodeTables,
    _LOOPS_CAP,
    bucket_classes,
    bucket_hint_classes,
    multi_size_bucket,
    pad_waste_pct,
    page_budget,
    stacked_device_tables,
    visited_instr_cap,
)
from mythril_tpu.frontier.engine import FrontierEngine
from mythril_tpu.frontier.state import Caps, empty_state
from mythril_tpu.support.support_args import args

Ins = namedtuple("Ins", "opcode address arg_int")


def _program(n_pops: int, n_pushes: int = 0):
    """n_pushes PUSH1s then n_pops POPs (distinct families, so window
    slicing is observable in the fam table)."""
    out = []
    addr = 0
    for _ in range(n_pushes):
        out.append(Ins("PUSH1", addr, 0))
        addr += 2
    for _ in range(n_pops):
        out.append(Ins("POP", addr, None))
        addr += 1
    return out


@pytest.fixture
def paging_defaults():
    prev = (args.code_paging, args.code_page_budget)
    args.code_paging, args.code_page_budget = True, 2048
    yield
    args.code_paging, args.code_page_budget = prev


# ---------------------------------------------------------------------------
# pad-path units
# ---------------------------------------------------------------------------


def test_size_bucket_caps_at_page_budget(paging_defaults):
    arena = HostArena(4096)
    small = CodeTables(_program(100), arena)
    big = CodeTables(_program(3000), arena)
    assert page_budget() == 2048
    assert small.size_bucket()[0] == 512
    assert not small.is_paged()
    # the outlier's natural axis (8192) caps at the residency budget
    assert big.size_bucket()[0] == 2048
    assert big.full_instr_cap() == 8192
    assert big.is_paged()
    # escape hatch: --no-code-paging restores the unpaged growth
    args.code_paging = False
    assert big.size_bucket()[0] == 8192
    assert not big.is_paged()


def test_padded_tables_window_slices_instruction_axis(paging_defaults):
    arena = HostArena(4096)
    t = CodeTables(_program(5, n_pushes=3), arena)  # PUSH,PUSH,PUSH,POP*5
    cap = 4
    bucket = (cap, t.size_bucket()[1], _LOOPS_CAP)
    resident = t.padded_device_tables(bucket)
    assert list(resident[0]) == [O.F_PUSH] * 3 + [O.F_POP]
    windowed = t.padded_device_tables(bucket, window_base=3)
    assert list(windowed[0]) == [O.F_POP] * 4
    # window past the code end: real rows then the F_STOP pad fill
    tail = t.padded_device_tables(bucket, window_base=6)
    assert list(tail[0]) == [O.F_POP, O.F_POP, O.F_STOP, O.F_STOP]
    # jumpmap is NOT windowed: same byte-address axis either way
    assert np.array_equal(resident[6], windowed[6])


def test_stacked_tables_carry_pbase_column(paging_defaults):
    arena = HostArena(4096)
    tables = [CodeTables(_program(600), arena),
              CodeTables(_program(20), arena)]
    bucket = (8, 512, tables[0].size_bucket()[1], _LOOPS_CAP)
    cols = stacked_device_tables(tables, bucket, page_bases=[128, 0])
    assert len(cols) == 11  # 10 dispatch planes + the pbase column
    pbase = cols[-1]
    assert pbase.dtype == np.int32 and pbase.shape == (8,)
    assert list(pbase[:2]) == [128, 0] and not pbase[2:].any()
    # member 0's window starts at row 128; pad codes dispatch F_STOP
    assert cols[0][0][0] == tables[0].fam[128]
    assert (cols[0][3:] == O.F_STOP).all()


def test_pad_waste_pct_counts_unused_cells():
    arena = HostArena(4096)
    tables = [CodeTables(_program(15), arena),   # 16 rows with implicit STOP
              CodeTables(_program(99), arena)]   # 100 rows
    bucket = (8, 512, 32768, _LOOPS_CAP)
    expected = 100.0 * (1.0 - (16 + 100) / (8 * 512))
    assert pad_waste_pct(tables, bucket) == pytest.approx(expected)
    # a bucket the members fill exactly has no waste
    assert pad_waste_pct(tables, (2, 58, 32768, _LOOPS_CAP)) == pytest.approx(
        100.0 * (1.0 - (16 + 58) / (2 * 58))
    )


# ---------------------------------------------------------------------------
# outlier isolation
# ---------------------------------------------------------------------------


def test_bucket_classes_isolate_outlier(paging_defaults):
    arena = HostArena(8192)
    smalls = [CodeTables(_program(n), arena) for n in (20, 60, 200)]
    outlier = CodeTables(_program(3000), arena)
    tables = smalls + [outlier]

    single = multi_size_bucket(tables)
    classes = bucket_classes(tables)
    assert len(classes) == 2
    (small_bucket, small_members), (big_bucket, big_members) = classes
    # the small class keeps ITS axis — not the outlier's
    assert small_bucket[1] == 512 and small_members == [0, 1, 2]
    assert big_bucket[1] == 2048 and big_members == [3]
    # every member fits its class in every dimension
    for bucket, members in classes:
        assert len(members) <= bucket[0]
        for i in members:
            ic, ac, lc = tables[i].size_bucket()
            assert ic <= bucket[1] and ac <= bucket[2] and lc <= bucket[3]
    # the aggregate (cell-weighted) per-class waste beats the single bucket
    num = den = 0.0
    for bucket, members in classes:
        cells = bucket[0] * bucket[1]
        num += pad_waste_pct([tables[i] for i in members], bucket) * cells
        den += cells
    assert num / den < pad_waste_pct(tables, single)
    # coverage planes still span the WHOLE outlier (true-pc indexed)
    assert visited_instr_cap(tables) == 8192


def test_bucket_hint_classes_mirror_built_tables(paging_defaults):
    arena = HostArena(8192)
    lists = [_program(20), _program(60), _program(3000)]
    hints = bucket_hint_classes(lists)
    built = bucket_classes([CodeTables(pl, arena) for pl in lists])
    assert hints == [bucket for bucket, _members in built]


def test_pick_floor_rejects_partial_covers():
    floors = [(8, 512, 32768, 512), (1, 2048, 32768, 512)]
    # both cover; the smaller [C, instr] plane wins
    assert FrontierEngine._pick_floor(
        floors, (1, 512, 32768, 512)) == (1, 2048, 32768, 512)
    assert FrontierEngine._pick_floor(
        floors, (8, 512, 32768, 512)) == (8, 512, 32768, 512)
    # a floor covering only SOME dimensions would mint a third compiled
    # shape (elementwise max) — it must be skipped, not clamped
    assert FrontierEngine._pick_floor(
        floors, (16, 512, 32768, 512)) is None
    assert FrontierEngine._pick_floor([], (1, 512, 32768, 512)) is None


# ---------------------------------------------------------------------------
# page-fault park / repack
# ---------------------------------------------------------------------------


def _paged_engine(paging_defaults=None):
    """A bare engine with paging state for a 10-instruction code windowed
    to a 4-row axis (no laser, no device: the repack path is host-only)."""
    eng = object.__new__(FrontierEngine)
    arena = HostArena(1024)
    eng._page_tables = [CodeTables(_program(10), arena)]  # 11 rows
    eng._page_bucket = (1, 4, 32768, _LOOPS_CAP)
    eng._page_bases = [0]
    eng._page_pending = {}
    eng._page_fault_counts = {}
    eng._page_placer = lambda a: a
    return eng


def test_note_page_fault_schedules_window_over_pc(paging_defaults):
    eng = _paged_engine()
    assert eng._note_page_fault(0, 9) is True
    # a quarter-axis of context before the fault, clamped into the code
    assert eng._page_pending == {0: min(max(0, 9 - 1), 11 - 4)}
    # out-of-range code ids never repack
    assert eng._note_page_fault(7, 9) is False


def test_note_page_fault_storm_pins_host_side(paging_defaults):
    eng = _paged_engine()
    verdicts = [eng._note_page_fault(0, 5) for _ in range(10)]
    limit = FrontierEngine._PAGE_FAULT_LIMIT
    assert verdicts == [True] * limit + [False] * (10 - limit)


def test_maybe_repack_folds_pending_and_keeps_shapes(paging_defaults):
    eng = _paged_engine()
    assert eng._maybe_repack() is None  # nothing pending: no re-upload
    assert eng._note_page_fault(0, 9)
    code_dev = eng._maybe_repack()
    assert code_dev is not None
    assert eng._page_bases == [7] and eng._page_pending == {}
    assert int(code_dev.pbase[0]) == 7
    # same shapes as the resident stack: the compiled program is untouched
    base = stacked_device_tables(eng._page_tables, eng._page_bucket)
    for fresh, orig in zip(code_dev, base):
        assert np.asarray(fresh).shape == np.asarray(orig).shape
    # window content actually moved: row 0 now holds instruction 7
    assert code_dev.fam[0, 0] == eng._page_tables[0].fam[7]
    assert eng._maybe_repack() is None  # pending drained


def test_device_dispatch_faults_off_window_pc(paging_defaults):
    jax = pytest.importorskip("jax")
    from mythril_tpu.frontier.step import (
        ArenaDev, CfgScalars, CodeDev, cached_segment,
    )

    caps = Caps(B=2, K=1)
    arena = HostArena(caps.ARENA)
    row_zero = arena.const_row(0, 256)
    row_one = arena.const_row(1, 256)
    tables = CodeTables(_program(10), arena)  # POP*10 + implicit STOP
    instr_cap = 4  # window: rows 0..3 resident
    _ic, addr_cap, loops_cap = tables.size_bucket()
    bucket = (1, instr_cap, addr_cap, loops_cap)
    segment = cached_segment(caps, 1, instr_cap, addr_cap, loops_cap)
    code_dev = CodeDev(*[
        jax.device_put(a) for a in stacked_device_tables([tables], bucket)
    ])
    cfg = CfgScalars(
        max_depth=np.int32(128), loop_bound=np.int32(0),
        row_zero=np.int32(row_zero), row_one=np.int32(row_one),
        sel_mode=np.int32(0),
    )
    st = empty_state(caps, loops_cap)
    for slot, pc in enumerate((2, 6)):  # resident / off-window
        st.seed[slot] = 0
        st.halt[slot] = O.H_RUNNING
        st.pc[slot] = pc
        st.stack[slot, 0] = row_one
        st.stack_len[slot] = 1
    dev_arena = ArenaDev(*[jax.device_put(a) for a in arena.device_arrays()])
    visited = jax.device_put(np.zeros((3, 1, 16), bool))
    out, _arena, _alen, _n, _ml, _visited = segment(
        st, dev_arena, arena.length, visited, code_dev, cfg
    )
    halts = np.array(out.halt)
    assert halts[0] == O.H_RUNNING  # resident pc executed its POP
    assert int(np.array(out.pc)[0]) == 3
    assert halts[1] == O.H_PAGE_FAULT  # off-window pc faulted, untouched
    assert int(np.array(out.pc)[1]) == 6
    assert int(np.array(out.stack_len)[1]) == 1  # arity forced to 0: no pops
    assert int(np.array(out.ev_len)[1]) == 0  # faults never emit events


# ---------------------------------------------------------------------------
# paged-vs-resident parity (end to end)
# ---------------------------------------------------------------------------


def _pad_tail_kill(n_pad: int) -> bytes:
    """Selector dispatch to CALLER;SELFDESTRUCT placed BEYOND a straight-
    line pad tail — the deep cold-jump shape of a creation-heavy outlier."""
    sel = 0x41C0E1B5  # kill()
    tail = bytes([0x60, 0x00, 0x50]) * n_pad + bytes([0x00])
    dest = 16 + len(tail)
    head = bytes([
        0x60, 0x00, 0x35, 0x60, 0xE0, 0x1C,
        0x63, (sel >> 24) & 0xFF, (sel >> 16) & 0xFF,
        (sel >> 8) & 0xFF, sel & 0xFF,
        0x14, 0x61, (dest >> 8) & 0xFF, dest & 0xFF, 0x57,
    ])
    return head + tail + bytes([0x5B, 0x33, 0xFF])


@pytest.mark.slow
def test_paged_vs_resident_issue_parity():
    """The whole optimization, end to end: a code big enough to page (at a
    shrunken budget) analyzed with paging ON finds the EXACT issue set of
    the fully-resident run — and finds the deep SELFDESTRUCT exactly once
    (the faulted path re-injects once after the repack; it is not lost and
    not duplicated)."""
    from mythril_tpu.analysis.module.loader import ModuleLoader
    from mythril_tpu.analysis.security import (
        fire_lasers,
        reset_callback_modules,
    )
    from mythril_tpu.analysis.symbolic import SymExecWrapper
    from mythril_tpu.observability.metrics import get_registry

    code = _pad_tail_kill(400)  # ~816 instrs: pages at a 512-row budget

    def analyze():
        # same code + address twice in-process: drop the per-(address,
        # bytecode) detector caches or the second run reports nothing
        reset_callback_modules()
        for module in ModuleLoader().get_detection_modules():
            module.cache.clear()
        sym = SymExecWrapper(
            code, address=0x0901D12E, strategy="bfs",
            transaction_count=1, execution_timeout=120,
            modules=["AccidentallyKillable"],
        )
        issues = fire_lasers(sym, white_list=["AccidentallyKillable"])
        return sorted((i.swc_id, i.address) for i in issues)

    prev = (args.frontier, args.frontier_force, args.code_paging,
            args.code_page_budget, args.probe_backend)
    reg = get_registry()
    try:
        args.probe_backend = "auto"
        args.frontier = True
        args.frontier_force = True
        args.code_paging, args.code_page_budget = True, 512
        faults_before = reg.counter("frontier.page_faults").value
        paged = analyze()
        faults = reg.counter("frontier.page_faults").value - faults_before
        args.code_paging = False
        resident = analyze()
    finally:
        (args.frontier, args.frontier_force, args.code_paging,
         args.code_page_budget, args.probe_backend) = prev
    assert paged == resident, "paging changed the issue set"
    assert [s for s, _ in paged].count("106") == 1, (
        "deep SELFDESTRUCT must surface exactly once"
    )
    assert faults > 0, "the cold-jump target never faulted the window"


def _mixed_batch(name):
    from bench_contracts import GATED, SUICIDE

    # small codes share a batch with outliers big enough to page at the
    # default residency budget: (jobs, transactions)
    return {
        "largecode_mixed": ([("bigkill", _pad_tail_kill(1500)),
                             ("suicide", SUICIDE), ("gated", GATED)], 2),
        "two_outliers": ([("big_a", _pad_tail_kill(1200)),
                          ("big_b", _pad_tail_kill(2400)),
                          ("suicide", SUICIDE)], 1),
    }[name]


@pytest.mark.slow
@pytest.mark.parametrize("name", ["largecode_mixed", "two_outliers"])
def test_mixed_size_batch_splits_classes_and_pages(name, monkeypatch):
    """--no-code-paging parity on mixed-size cooperative batches: identical
    issue sets with SWC-106 found; the off run clusters nothing; the on run
    splits into more than one bucket class, wastes less padding than one
    corpus-wide bucket would, and page-faults on the deep cold jumps."""
    from tests.parity import cooperative_parity_run

    jobs, txs = _mixed_batch(name)
    runs = {
        paged: cooperative_parity_run(
            monkeypatch, jobs, txs, ("frontier.",), timeout=180,
            code_paging=paged, pipeline=True, frontier_mesh=False)
        for paged in (False, True)
    }
    (on, snap, _), (off, off_snap, _) = runs[True], runs[False]
    assert "106" in {swc for _, swc, _, _ in on}, on
    assert on == off
    assert not off_snap["frontier.bucket_classes"], off_snap
    assert snap["frontier.bucket_classes"] > 1, snap
    assert (snap["frontier.pad_waste_pct"]
            < snap["frontier.pad_waste_single_bucket_pct"]), snap
    assert snap["frontier.page_faults"] > 0, snap
