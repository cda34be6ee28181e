"""Seconds from the process's start to the window's opening: imports, JAX
and device initialisation, contract generation, warm-up (compiles or
cache loads) and, for the service, its start-up warm-up."""


def read(run):
    return run.setup_s
