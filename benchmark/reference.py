"""What decides ``correct``: every request issued in the window, held to
its contract's expected issue set, exactly.

The reference is the family's construction (``corpus/families``): the
(SWC id, pc) pairs it plants, every one of which must be reported, and
the further true findings it lists as possible, which may be.  The timed path's answer is what the
client received: the jsonv2 report of ``analyze``, or the service's
``done`` event, deduplicated and replayed answers included.  Each number
is a count with the limit 0.
"""

from __future__ import annotations

from typing import Dict, List, Tuple

LIMITS = {"missed_issues": 0, "unexpected_issues": 0, "failed_requests": 0}


def compare(requests) -> Dict[str, Tuple[int, int]]:
    missed = unexpected = failed = 0
    for r in requests:
        want = set(r.variant.expected)
        if r.error is not None or r.done is None:
            failed += 1
            missed += len(want)
            continue
        got = set(r.issues)
        missed += len(want - got)
        unexpected += len(got - r.variant.allowed)
    found = {"missed_issues": missed, "unexpected_issues": unexpected,
             "failed_requests": failed}
    return {k: (v, LIMITS[k]) for k, v in found.items()}


def correct(numbers: Dict[str, Tuple[int, int]], requests: List) -> bool:
    return bool(requests) and all(v <= lim for v, lim in numbers.values())
