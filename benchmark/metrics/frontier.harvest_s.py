"""Host harvest seconds per analysed contract: the frontier's
``harvest.{ingest,solver,replay,commit}_s`` phases summed over the window."""

PHASES = ("ingest", "solver", "replay", "commit")


def read(run):
    names = [f"frontier.harvest.{p}_s" for p in PHASES]
    if not sum(run.counter(f"{n}#count") for n in names) or not run.analysed:
        return None
    return sum(run.counter(n) for n in names) / run.analysed
