"""XLA compile seconds inside the window (the program's ``jax.monitoring``
listener, ``device.compile_wall_s_total``).  Warm-up should leave none.
The program registers the counter at its first compile; a process that
loaded every program from the cache has none, and reads 0."""


def read(run):
    return run.counter("device.compile_wall_s_total", default=0.0)
