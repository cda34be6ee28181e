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
evaluation; memo hits are free and uncounted.
"""

from __future__ import annotations

import threading
import time
from collections import OrderedDict
from typing import List, Optional, Sequence

from mythril_tpu.native.bitblast import Unsupported
from mythril_tpu.smt.terms import Term
from mythril_tpu.support.device import DeviceFailure

__all__ = ["prefilter_batch", "refute", "reset_state"]

# Verdict memo: frozenset of conjunct tids -> proven-UNSAT bool.  Terms are
# interned process-wide, so keys stay valid across analyses; UNSAT is a
# semantic fact and never expires.  Bounded FIFO to cap memory.
_MEMO_CAP = 8192
_memo: "OrderedDict[frozenset, bool]" = OrderedDict()
_memo_lock = threading.Lock()


def _counters():
    from mythril_tpu.observability import get_registry

    reg = get_registry()
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


def _evaluate_rows(rows: List[Sequence[Term]]) -> List[Optional[bool]]:
    """Pack + evaluate; ``None`` marks fallthrough (unsupported structure)."""
    from mythril_tpu.absdomain import domains, tape

    try:
        pack = tape.pack(rows)
    except Unsupported:
        if len(rows) == 1:
            return [None]
        # one poisoned row must not cost its siblings the pass
        out: List[Optional[bool]] = []
        for row in rows:
            out.extend(_evaluate_rows([row]))
        return out

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
