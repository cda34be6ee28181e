"""One reader per metric, ``<metric name>.py``, found by name.

A reader defines ``read(run) -> float | None``; ``run`` carries the
window's requests, its counters, its reduced trace and its length (see
``harness.Run``).  A reader that finds nothing to read returns None, and
the harness leaves the metric out of the line.
"""

import importlib.util
import os

METRIC_DIR = os.path.dirname(os.path.abspath(__file__))


def load_reader(name: str):
    path = os.path.join(METRIC_DIR, f"{name}.py")
    if not os.path.isfile(path):
        raise KeyError(f"no reader for metric {name!r} ({path})")
    spec = importlib.util.spec_from_file_location(f"benchmark_metric_{name}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read
