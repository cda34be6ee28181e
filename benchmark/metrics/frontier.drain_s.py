"""Seconds per analysed contract from the first pass of the execution
deadline to the report (``frontier.drain_s``, from the program's
per-analysis accounts): the harvest of the paths open at the timeout."""

from benchmark import accounts


def read(run):
    if not accounts.available():
        return None
    window = accounts.window_accounts(run)
    if not window:
        return None
    return sum(accounts.metric(a, "frontier.drain_s") for a in window) / len(window)
