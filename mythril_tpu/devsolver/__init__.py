"""Device-resident SAT tier: decide narrow path conditions off Z3.

Where the absdomain pre-filter (tier 0.58) can only *refute*, this
package *decides*: narrow path-condition shapes — conditions whose free
support fits a configurable bit budget after the pre-filter's known-bits
/ interval narrowing — are bit-blasted to a packed 3-CNF plane
(``blaster.py``) and solved by a batched unit-propagation + bounded-DPLL
search kernel (``kernel.py`` host twin, ``device.py`` jitted twin) with
a three-valued verdict per query:

* **UNSAT** is exact: serialization abstractions only add behaviors and
  narrowing pins are implied by the asserted conjuncts, so an
  exhausted search refutes the original conjunction.
* **SAT** is a *candidate* until proven: the model is rebuilt through
  ``bitblast._rebuild_assignment`` and re-evaluated against the ORIGINAL
  terms with ``concrete_eval`` — an unvalidated model is NEVER trusted;
  validation failure increments ``devsolver.model_validation_failures``
  and the query falls through as UNKNOWN.
* **UNKNOWN** (budget lapse, unsupported structure, admission denial)
  falls through to the exact tiers unchanged.

Soundness is therefore by construction: the tier can answer or abstain,
never misdecide.  ``tests/devsolver/test_pipeline_parity.py`` asserts
identical issue sets with the tier on and off.

Entry points: ``decide_batch(rows)`` / ``decide(conjuncts)`` — both
never raise; ``configure()`` applies analyzer args; ``reset_state()``
drops the verdict memo and per-point admission accounting.
"""

from __future__ import annotations

import threading
import time
from collections import OrderedDict
from typing import List, Optional, Sequence, Tuple

from mythril_tpu.native.bitblast import Unsupported
from mythril_tpu.smt.terms import Term
from mythril_tpu.support.device import DeviceFailure

__all__ = ["decide", "decide_batch", "configure", "reset_state"]

SAT, UNSAT, UNKNOWN = "sat", "unsat", "unknown"

# analyzer-args knobs (configure() overwrites from support_args)
_config = {"bit_budget": 64, "iters": 2048}

# verdict memo: frozenset of conjunct tids -> (status, assignment).  UNSAT
# and validated SAT are semantic facts; UNKNOWN is deterministic for fixed
# budgets (structural rejection / budget lapse), so caching it stops the
# tier re-paying blast cost on hot repeated queries.  Bounded FIFO.
_MEMO_CAP = 8192
_memo: "OrderedDict[frozenset, tuple]" = OrderedDict()
_memo_lock = threading.Lock()


def configure(bit_budget: Optional[int] = None,
              iters: Optional[int] = None) -> None:
    if bit_budget is not None:
        _config["bit_budget"] = int(bit_budget)
    if iters is not None:
        _config["iters"] = int(iters)


def reset_state() -> None:
    """Drop the verdict memo + admission accounting (tests)."""
    from mythril_tpu.devsolver import admission

    with _memo_lock:
        _memo.clear()
    admission.reset_state()


def _counters():
    from mythril_tpu.observability import get_registry

    reg = get_registry()
    return (
        reg.counter("devsolver.admitted"),
        reg.counter("devsolver.decided_sat"),
        reg.counter("devsolver.decided_unsat"),
        reg.counter("devsolver.unknown"),
        reg.counter("devsolver.model_validation_failures"),
        reg.counter("devsolver.kernel_wall_s"),
    )


def _memo_get(key: frozenset):
    with _memo_lock:
        return _memo.get(key)


def _memo_put(key: frozenset, verdict: tuple) -> None:
    with _memo_lock:
        _memo[key] = verdict
        while len(_memo) > _MEMO_CAP:
            _memo.popitem(last=False)


def _validate(conjuncts, blasted, assign_row):
    """Rebuild + validate one SAT candidate; None when it does not hold."""
    from mythril_tpu.native import bitblast
    from mythril_tpu.devsolver import blaster

    try:
        mb = blaster.model_bytes(blasted, assign_row)
        asg, _violations, _kec = bitblast._rebuild_assignment(
            blasted.tape, mb)
        if bitblast._model_validates(conjuncts, asg):
            return asg
    except Exception:
        pass
    return None


def decide_batch(
    conjunct_sets: Sequence[Sequence[Term]],
) -> List[Tuple[str, Optional[object]]]:
    """One (status, model) per row; raises only ``DeviceFailure``.

    Status is ``"sat"`` (model is a validated ``Assignment``),
    ``"unsat"`` (exact), or ``"unknown"`` (fall through — admission
    denied, structure unsupported, budget lapsed, or validation failed).
    """
    from mythril_tpu.devsolver import admission, blaster, device, kernel

    n = len(conjunct_sets)
    results: List[Optional[tuple]] = [None] * n
    keys = [frozenset(t.tid for t in cs) for cs in conjunct_sets]
    c_adm, c_sat, c_unsat, c_unk, c_badmodel, c_wall = _counters()
    point = admission.current_point()

    fresh: List[int] = []
    seen_pos: dict = {}
    for i, key in enumerate(keys):
        hit = _memo_get(key)
        if hit is not None:
            results[i] = hit
        elif key in seen_pos:
            results[i] = ("dup", seen_pos[key])
        else:
            seen_pos[key] = i
            fresh.append(i)

    # blast the admitted fresh rows
    blasted: dict = {}
    for i in list(fresh):
        if not admission.policy.admit(point):
            results[i] = (UNKNOWN, None)
            c_unk.inc()
            fresh.remove(i)
            continue
        c_adm.inc()
        try:
            b = blaster.blast(list(conjunct_sets[i]),
                              bit_budget=_config["bit_budget"])
        except Unsupported:
            results[i] = (UNKNOWN, None)
            _memo_put(keys[i], (UNKNOWN, None))
            c_unk.inc()
            admission.policy.note(point, decided=False)
            fresh.remove(i)
            continue
        except Exception:
            results[i] = (UNKNOWN, None)
            c_unk.inc()
            admission.policy.note(point, decided=False)
            fresh.remove(i)
            continue
        if b.verdict == UNSAT:
            results[i] = (UNSAT, None)
            _memo_put(keys[i], (UNSAT, None))
            c_unsat.inc()
            admission.policy.note(point, decided=True)
            fresh.remove(i)
            continue
        blasted[i] = b

    # packed planes for the survivors, chunked at the kernel's largest
    # query bucket (a wide frontier batch can admit more rows than one
    # plane holds)
    q_cap = kernel.Q_BUCKETS[-1]
    all_idx = sorted(blasted)
    for chunk in range(0, len(all_idx), q_cap):
        idx = all_idx[chunk:chunk + q_cap]
        n_vars = max(blasted[i].n_vars for i in idx)
        plane = kernel.pack_plane(
            [(blasted[i].clauses, blasted[i].dec_vars) for i in idx],
            n_vars)
        t0 = time.perf_counter()
        if device.should_use_device():
            try:
                status, assign = device.run_device(plane, _config["iters"])
            except Exception as e:
                raise DeviceFailure(f"devsolver kernel: {e!r}") from e
        else:
            try:
                status, assign = kernel.run_host(plane, _config["iters"])
            except Exception:
                status, assign = None, None
        c_wall.inc(round(time.perf_counter() - t0, 6))

        for qi, i in enumerate(idx):
            if status is None:
                verdict: tuple = (UNKNOWN, None)
            elif int(status[qi]) == kernel.UNSAT_Q:
                verdict = (UNSAT, None)
            elif int(status[qi]) == kernel.SAT_Q:
                asg = _validate(list(conjunct_sets[i]), blasted[i],
                                assign[qi])
                if asg is None:
                    # on a FULL encoding a model that fails host
                    # validation is a soundness alarm; on a projected,
                    # truncated, or lazily-abstracted one (roots
                    # dropped / subtrees cut / select-congruence
                    # omitted) it is the expected fallthrough
                    if (blasted[i].projected == 0
                            and blasted[i].truncated == 0
                            and not blasted[i].abstracted):
                        c_badmodel.inc()
                    verdict = (UNKNOWN, None)
                else:
                    verdict = (SAT, asg)
            else:
                verdict = (UNKNOWN, None)
            results[i] = verdict
            _memo_put(keys[i], verdict)
            decided = verdict[0] in (SAT, UNSAT)
            admission.policy.note(point, decided=decided)
            if verdict[0] == SAT:
                c_sat.inc()
            elif verdict[0] == UNSAT:
                c_unsat.inc()
            else:
                c_unk.inc()

    out: List[Tuple[str, Optional[object]]] = []
    for i in range(n):
        r = results[i]
        if r is not None and r[0] == "dup":
            r = results[r[1]]
        if r is None:
            r = (UNKNOWN, None)
        out.append(r)
    return out


def decide(conjuncts: Sequence[Term]) -> Tuple[str, Optional[object]]:
    """Single-row convenience wrapper (the solver fast path's tier 0.65)."""
    return decide_batch([conjuncts])[0]
