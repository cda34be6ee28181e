"""Arithmetic the metric readers share: nearest-rank percentiles over all
requests of a window, a missing or failed request counting as infinite."""

from __future__ import annotations

import math
from typing import Iterable, List, Optional

INF = float("inf")


def percentile(values: Iterable[float], p: float) -> Optional[float]:
    """Nearest-rank ``p``-th percentile; None for no values or an
    infinite one (a missing request in the tail)."""
    xs: List[float] = sorted(values)
    if not xs:
        return None
    v = xs[max(0, math.ceil(p / 100.0 * len(xs)) - 1)]
    return None if v == INF else v


def complete(req) -> bool:
    """Finished, without error, with every expected issue and none that
    its contract does not allow."""
    got = set(req.issues)
    return (req.error is None and req.done is not None
            and set(req.variant.expected) <= got <= req.variant.allowed)


def ttfe(req) -> float:
    if req.error is not None or req.first_planted is None:
        return INF
    return req.first_planted - req.t0


def report_time(req) -> float:
    if not complete(req):
        return INF
    return req.done - req.t0
