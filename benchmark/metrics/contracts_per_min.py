"""Contracts completed with their full issue set, among those issued in
the window, per minute from the window's start to the last one's end:
all the work issued in the window and all the time it took."""

from benchmark.stats import complete


def read(run):
    ok = [r for r in run.requests if complete(r)]
    if not ok:
        return None
    span = max(r.done for r in ok) - run.t0
    return 60.0 * len(ok) / span if span > 0 else None
