"""The program's per-analysis accounts, read over a window.

The program closes one account per analysis when its report is built
(``get_registry().accounts(since_ns)``): its interval on the epoch clock,
a snapshot of its analysis-scoped counters, and per issue the solver busy
seconds spent before it was found.  ``window_accounts`` gives the
accounts whose interval lies inside the window, one per analysis the
window ran; a program without accounts, or a count that does not match,
raises ``MissingCounter`` rather than reading 0.  Readers of a metric new
since the accounts ask ``available()`` first and read nothing from a
program that keeps none.
"""

from __future__ import annotations

from typing import Dict, List

from benchmark.counters import MissingCounter


def _registry():
    from mythril_tpu.observability.metrics import get_registry

    return get_registry()


def available() -> bool:
    """Whether the program keeps per-analysis accounts."""
    return hasattr(_registry(), "accounts")


def window_accounts(run) -> List[Dict]:
    """The accounts of the analyses inside ``[run.t0, run.t1]``."""
    reg = _registry()
    if not hasattr(reg, "accounts"):
        raise MissingCounter("the program keeps no per-analysis accounts")
    lo, hi = int(run.t0 * 1e9), int(run.t1 * 1e9)
    accounts = [a for a in reg.accounts(lo) if a["end_ns"] <= hi]
    if len(accounts) != run.analysed:
        raise MissingCounter(f"{len(accounts)} accounts closed in the window, "
                             f"which analysed {run.analysed}")
    return accounts


def metric(account: Dict, name: str) -> float:
    """One counter of an account; ``MissingCounter`` when it lacks it."""
    if name not in account["metrics"]:
        raise MissingCounter(f"the program's accounts hold no counter {name!r}")
    return float(account["metrics"][name])
