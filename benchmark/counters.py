"""The program's own counters, read over a window.

The program zeroes its per-analysis metrics before every analysis (and
the service before every batch), and keeps no cumulative total.
``Tally`` wraps the registry's public ``reset`` so the values it is about
to zero are added up first; ``read()`` then gives, for each watched
metric, everything the process counted since ``start()``: a counter's
value, a histogram's sum.  Values come from the registry's public
``snapshot``.

A watched name that the program never registered is left out of
``read()``, and ``Run.counter`` raises ``MissingCounter`` for it: a metric
whose counter the program renamed or dropped fails the run instead of
reading 0.  The program should keep cumulative counters itself (PERF.md,
Open questions), which would make this wrapper unnecessary.
"""

from __future__ import annotations

import threading
from typing import Dict, Iterable, Tuple

#: every program counter a per-layer metric reads
WATCHED = (
    "frontier.device_instructions",
    "frontier.segments",
    "frontier.harvest.ingest_s",
    "frontier.harvest.solver_s",
    "frontier.harvest.replay_s",
    "frontier.harvest.commit_s",
    "solver.solver_time_s",
    "device.compile_wall_s_total",
    "service.requests",
    "service.dedup_hits",
    "service.replay_hits",
    "service.queue_wait_s",
    "service.batch_wait_s",
)


class MissingCounter(KeyError):
    """A metric reads a counter the program never registered."""


def _reading(snap) -> Tuple[float, float]:
    """(value, observations) of one metric's snapshot: a counter's value,
    a histogram's sum and count."""
    if isinstance(snap, dict):
        return float(snap["sum"]), float(snap["count"])
    return float(snap), 0.0


class Tally:
    def __init__(self, names: Iterable[str] = WATCHED):
        from mythril_tpu.observability.metrics import get_registry

        self.names = tuple(names)
        self.reg = get_registry()
        self._lock = threading.Lock()
        self._swept: Dict[str, float] = {n: 0.0 for n in self.names}
        self._swept_n: Dict[str, float] = {n: 0.0 for n in self.names}
        self._base: Dict[str, Tuple[float, float]] = {}
        self._keeps: Dict[str, bool] = {}
        original = self.reg.reset

        def reset(include_persistent: bool = False, prefix: str = ""):
            with self._lock:
                snap = self.reg.snapshot()
                for name in self.names:
                    if name in snap and name.startswith(prefix) and (
                            include_persistent or not self._persistent(name, snap[name])):
                        v, n = _reading(snap[name])
                        self._swept[name] += v
                        self._swept_n[name] += n
            original(include_persistent=include_persistent, prefix=prefix)

        self.reg.reset = reset

    def _persistent(self, name: str, snap) -> bool:
        """Whether the registry's reset keeps ``name`` (a registered
        metric): its own flag, through the public accessor of its kind."""
        if name not in self._keeps:
            make = self.reg.histogram if isinstance(snap, dict) else self.reg.counter
            self._keeps[name] = bool(make(name).persistent)
        return self._keeps[name]

    def _now(self) -> Dict[str, Tuple[float, float]]:
        snap = self.reg.snapshot()
        out = {}
        for name in self.names:
            if name in snap:
                v, n = _reading(snap[name])
                out[name] = (self._swept[name] + v, self._swept_n[name] + n)
        return out

    def start(self) -> None:
        with self._lock:
            self._base = self._now()

    def read(self) -> Dict[str, float]:
        """Totals since ``start()`` of every watched name the program has
        registered; ``<name>#count`` holds a histogram's number of
        observations."""
        with self._lock:
            now = self._now()
        out = {}
        for name, (v, n) in now.items():
            b, bn = self._base.get(name, (0.0, 0.0))
            out[name], out[f"{name}#count"] = v - b, n - bn
        return out
