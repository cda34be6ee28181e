"""Device-resident feasibility pre-filter: vectorized abstract SMT.

Before a path-constraint query reaches the feasibility pool or the exact
solver stack, this package evaluates a SOUND abstraction of it — unsigned
intervals plus known-bits, see ``domains.py`` — over the packed constraint
rows of an entire frontier batch at once.  A row whose abstraction is
bottom (some asserted root must-false, or an empty abstract element) has
NO concrete model: the original conjunction is UNSAT and the path dies
without any host round-trip or bit-blasting.  Everything else falls
through to the existing tiers completely unchanged, so recall is
untouched by construction and ``bench.py --prefilter-compare`` asserts
bit-identical issue sets with the filter on and off.

Entry points
------------
``prefilter_batch(rows)``
    One verdict per constraint row; ``True`` means *proven UNSAT*.
``refute(conjuncts)``
    Single-row convenience wrapper (the solver fast path's tier 0.58).

Verdicts are memoized under the same canonical frozenset-of-tids key the
feasibility pool dedups on, so the pipeline gate and the solver gate never
evaluate the same query twice.  ``prefilter.{evaluated,killed,fallthrough}``
counters and the ``prefilter.eval_s`` histogram account every fresh
evaluation; memo hits are free and uncounted.  A batch whose union tape
would pass ``tape.MAX_NODES`` is evaluated in groups of rows whose unions
fit; ``prefilter.packs`` counts the tapes evaluated and
``prefilter.grouped_rows`` the rows that went through groups.
"""

from __future__ import annotations

import threading
import time
from collections import OrderedDict
from typing import List, Optional, Sequence

from mythril_tpu.native.bitblast import UnionSize, Unsupported
from mythril_tpu.smt.terms import Term
from mythril_tpu.support.device import DeviceFailure

__all__ = ["prefilter_batch", "refute", "reset_state"]

# Verdict memo: frozenset of conjunct tids -> proven-UNSAT bool.  Terms are
# interned process-wide, so keys stay valid across analyses; UNSAT is a
# semantic fact and never expires.  Bounded FIFO to cap memory.
_MEMO_CAP = 8192
_memo: "OrderedDict[frozenset, bool]" = OrderedDict()
_memo_lock = threading.Lock()


def _registry():
    from mythril_tpu.observability import get_registry

    return get_registry()


def _counters():
    reg = _registry()
    return (
        reg.counter("prefilter.evaluated"),
        reg.counter("prefilter.killed"),
        reg.counter("prefilter.fallthrough"),
        reg.histogram("prefilter.eval_s"),
    )


def reset_state() -> None:
    """Drop the verdict memo (tests and bench compare modes)."""
    with _memo_lock:
        _memo.clear()


def _memo_get(key: frozenset) -> Optional[bool]:
    with _memo_lock:
        return _memo.get(key)


def _memo_put(key: frozenset, verdict: bool) -> None:
    with _memo_lock:
        _memo[key] = verdict
        while len(_memo) > _MEMO_CAP:
            _memo.popitem(last=False)


def _fits(size: UnionSize, row: Sequence[Term], max_nodes: int) -> bool:
    try:
        return size.add(row) <= max_nodes
    except Unsupported:
        return False


def _row_groups(rows: List[Sequence[Term]]) -> List[List[int]]:
    """Split ``rows`` into groups whose union tapes fit the node budget.

    A batch whose whole union fits is one group, as it always was.  Else
    the rows are taken in the order of their conjuncts, so that rows that
    share a path prefix sit side by side, and a group closes before the
    row that would push its union past ``tape.MAX_NODES`` and at the
    largest row bucket, so each group is one known-bits dispatch.  A row
    over the budget, or with structure the tape cannot hold, ends up alone.
    """
    from mythril_tpu.absdomain import device, tape

    whole = UnionSize()
    if all(_fits(whole, row, tape.MAX_NODES) for row in rows):
        return [list(range(len(rows)))]
    order = sorted(range(len(rows)), key=lambda i: [t.tid for t in rows[i]])
    groups: List[List[int]] = [[]]
    size = UnionSize()
    for i in order:
        full = len(groups[-1]) == device.ROW_BUCKETS[-1]
        if (full or not _fits(size, rows[i], tape.MAX_NODES)) and groups[-1]:
            groups.append([])
            size = UnionSize()
            _fits(size, rows[i], tape.MAX_NODES)
        groups[-1].append(i)
    return groups


def _evaluate_rows(rows: List[Sequence[Term]]) -> List[Optional[bool]]:
    """Pack + evaluate; ``None`` marks fallthrough (unsupported structure)."""
    groups = _row_groups(rows)
    if len(groups) > 1:
        _registry().counter("prefilter.grouped_rows").inc(len(rows))
    out: List[Optional[bool]] = [None] * len(rows)
    for group in groups:
        for i, v in zip(group, _evaluate_group([rows[i] for i in group])):
            out[i] = v
    return out


def _evaluate_group(rows: List[Sequence[Term]]) -> List[Optional[bool]]:
    from mythril_tpu.absdomain import domains, tape

    try:
        pack = tape.pack(rows)
    except Unsupported:
        if len(rows) == 1:
            return [None]
        # one poisoned row must not cost its siblings the pass: halve
        # the group until the poisoned row falls through alone
        mid = len(rows) // 2
        return _evaluate_group(rows[:mid]) + _evaluate_group(rows[mid:])

    _registry().counter("prefilter.packs").inc()
    km, kv, kb_ref = _eval_kb(pack)
    lo, hi, iv_ref = domains.eval_iv_host(pack)
    v = domains.verdicts(pack, lo, hi, km, kv, iv_ref | kb_ref)
    return [bool(x) for x in v]


def _eval_kb(pack):
    """Known-bits pass: device interpreter when warm, host numpy otherwise.
    A device failure raises ``DeviceFailure``; it never degrades to host."""
    from mythril_tpu.absdomain import device, domains

    if device.should_use_device():
        try:
            return device.run_kb(pack)
        except Exception as e:
            raise DeviceFailure(f"absdomain known-bits kernel: {e!r}") from e
    return domains.eval_kb_host(pack)


def prefilter_batch(
    conjunct_sets: Sequence[Sequence[Term]],
) -> List[bool]:
    """One abstract verdict per constraint row; True = proven UNSAT.

    Unsupported structure, oversized tapes, or internal host errors all
    degrade to False (fall through to the exact tiers); only a device
    failure (``DeviceFailure``) raises.
    """
    n = len(conjunct_sets)
    results: List[Optional[bool]] = [None] * n
    keys = [frozenset(t.tid for t in cs) for cs in conjunct_sets]

    fresh_idx: List[int] = []
    fresh_key_pos: dict = {}
    for i, key in enumerate(keys):
        hit = _memo_get(key)
        if hit is not None:
            results[i] = hit
        elif key in fresh_key_pos:
            results[i] = -1  # duplicate within the batch; filled below
        else:
            fresh_key_pos[key] = len(fresh_idx)
            fresh_idx.append(i)

    if fresh_idx:
        c_eval, c_kill, c_fall, h_eval = _counters()
        t0 = time.perf_counter()
        try:
            verdicts = _evaluate_rows([list(conjunct_sets[i]) for i in fresh_idx])
        except DeviceFailure:
            raise
        except Exception:
            verdicts = [None] * len(fresh_idx)
        h_eval.observe(time.perf_counter() - t0)
        c_eval.inc(len(fresh_idx))
        for i, v in zip(fresh_idx, verdicts):
            if v is None:
                c_fall.inc()
                v = False
            elif v:
                c_kill.inc()
            _memo_put(keys[i], v)
            results[i] = v

    for i, key in enumerate(keys):
        if results[i] == -1:
            results[i] = _memo_get(key) or False
    return [bool(r) for r in results]


def refute(conjuncts: Sequence[Term]) -> bool:
    """True iff the abstraction PROVES ``conjuncts`` unsatisfiable."""
    return prefilter_batch([conjuncts])[0]
