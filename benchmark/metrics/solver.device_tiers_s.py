"""Busy seconds per analysed contract in the solver tiers that run on the
device: the tape-VM probe, the known-bits pre-filter and the device SAT
search (``solver.tier.<tier>.busy_s``, from the program's per-analysis
accounts; host wall around each call, device waits included)."""

from benchmark import accounts

TIERS = ("device_probe", "prefilter", "devsolver")


def read(run):
    if not accounts.available():
        return None
    window = accounts.window_accounts(run)
    if not window:
        return None
    return sum(accounts.metric(a, f"solver.tier.{t}.busy_s")
               for a in window for t in TIERS) / len(window)
