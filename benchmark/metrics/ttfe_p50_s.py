"""Median time to first exploit over every request issued in the window:
from the request's due time (serve) or the call's start (analyze) to the
first report of its planted SWC; a request without it counts as missing."""

from benchmark.stats import percentile, ttfe


def read(run):
    return percentile([ttfe(r) for r in run.requests], 50)
