"""Mean over the window's requests of the service's ``queue_wait`` plus
``batch_wait`` phases (``service/telemetry.py``), in ms."""


def read(run):
    n = run.counter("service.queue_wait_s#count")
    if not n:
        return None
    total = run.counter("service.queue_wait_s") + run.counter("service.batch_wait_s")
    return 1000.0 * total / n
