"""Share of the window's requests that the service answered from another
request's analysis: (``dedup_hits`` + ``replay_hits``) / ``requests``."""


def read(run):
    n = run.counter("service.requests")
    if not n:
        return None
    hits = run.counter("service.dedup_hits") + run.counter("service.replay_hits")
    return 100.0 * hits / n
