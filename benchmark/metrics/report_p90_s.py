"""90th percentile over every request issued in the window of the time
from its due time or start to its complete report (the ``done`` event, or
the return of ``analyze``); a failed or incomplete report counts as missing."""

from benchmark.stats import percentile, report_time


def read(run):
    return percentile([report_time(r) for r in run.requests], 90)
