"""Solver busy seconds per analysed contract: ``solver.solver_time_s``,
summed over the solver's threads, so it is busy time and not wall time."""


def read(run):
    busy = run.counter("solver.solver_time_s")
    if not busy or not run.analysed:
        return None
    return busy / run.analysed
