"""The one traffic generator: a traffic file's parameters and a seed in,
the requests of a run out.

A traffic file (``traffic/<mix>.json``) holds:

- ``loop``: ``"closed"`` (``clients`` callers, each sends its next request
  when the last one ends) or ``"open"`` (arrivals at ``rate_per_s``,
  whatever the system does);
- ``families``: share of each contract family; ``params``: per-family
  parameters, where a list value is a set of sizes the family cycles;
- ``pool``: 0 for a distinct contract per request, or the number of
  distinct variants that requests are drawn from by ``popularity``
  (``{"law": "zipf", "s": 1.0}``);
- ``tier``: the service tier of every request;
- ``draw_seed`` (optional): the seed the contracts are drawn from, in
  place of the run's.  Every run then holds the same contracts, and the
  run's seed moves only their order (arrivals, Zipf ranks).

Every seed gets the same work: a closed loop sends the families and sizes
in one fixed order, open-loop gaps are the quantiles of the exponential
law in an order the seed picks, and Zipf ranks are quantiles of the law
too.  Without ``draw_seed``, runs with different seeds differ by order and
by the contracts' constants, and the constants alone can move the solver's
work: a window that times one contract wants ``draw_seed``.
"""

from __future__ import annotations

import itertools
import json
import math
import os
import random
from dataclasses import dataclass, field
from typing import Dict, Iterator, List, Optional

from benchmark.corpus import Variant, build_variant

TRAFFIC_DIR = os.path.join(os.path.dirname(os.path.abspath(__file__)), "traffic")
_SEED_MASK = (1 << 64) - 1


def load_traffic(name: str) -> dict:
    path = os.path.join(TRAFFIC_DIR, f"{name}.json")
    if not os.path.isfile(path):
        raise KeyError(f"no traffic mix {name!r} ({path})")
    with open(path) as f:
        traffic = json.load(f)
    if traffic.get("loop") not in ("open", "closed"):
        raise ValueError(f"traffic {name!r}: loop must be open or closed")
    return traffic


@dataclass
class Request:
    """One request of a run, filled in as it goes (times: ``time.time()``)."""

    rid: int
    variant: Variant
    due: Optional[float] = None  # open loop: when it was due to be sent
    start: Optional[float] = None
    first_planted: Optional[float] = None
    done: Optional[float] = None
    error: Optional[str] = None
    issues: List[tuple] = field(default_factory=list)  # (swc, pc)
    deduped: bool = False

    @property
    def t0(self) -> Optional[float]:
        """Where the request's times start: its due time, else its start."""
        return self.due if self.due is not None else self.start


def _apportion(shares: Dict[str, float], total: int) -> Dict[str, int]:
    """Largest-remainder split of ``total`` slots by ``shares``."""
    norm = sum(shares.values())
    raw = {k: total * v / norm for k, v in shares.items()}
    out = {k: int(math.floor(v)) for k, v in raw.items()}
    for k in sorted(raw, key=lambda k: (out[k] - raw[k], k))[:total - sum(out.values())]:
        out[k] += 1
    return out


def block(traffic: dict) -> List[str]:
    """The families of one block of requests, in a fixed order: the
    smallest block (at most 100) that holds every family in its share,
    each family spread evenly through it."""
    shares = traffic["families"]
    total = sum(shares.values())
    for size in range(1, 101):
        counts = _apportion(shares, size)
        if all(abs(counts[k] / size - v / total) < 0.005 for k, v in shares.items()):
            break
    order = list(shares)
    slots = [((j + 0.5) / counts[f], order.index(f), f)
             for f in order for j in range(counts[f])]
    return [f for _, _, f in sorted(slots)]


class Generator:
    """Variants and timings of one run, from a traffic mix and a seed."""

    def __init__(self, traffic: dict, seed: int):
        self.traffic = traffic
        self.seed = seed & _SEED_MASK
        self.draw_seed = int(traffic.get("draw_seed", self.seed)) & _SEED_MASK
        self.rng = random.Random(f"{self.seed}:window")
        self.params = traffic.get("params", {})
        self._size_cycle = {f: itertools.count() for f in traffic["families"]}

    def _params(self, family: str, i: int) -> dict:
        """The family's parameters with each list cycled by ``i``."""
        out = {}
        for key, value in self.params.get(family, {}).items():
            out[key] = value[i % len(value)] if isinstance(value, list) else value
        return out

    def sizes(self, family: str) -> List[dict]:
        """Every size the family takes in this mix (what warm-up covers)."""
        lists = [len(v) for v in self.params.get(family, {}).values()
                 if isinstance(v, list)]
        return [self._params(family, i) for i in range(max(lists, default=1))]

    def variant(self, family: str, index: int, size_index: int) -> Variant:
        rng = random.Random(f"{self.draw_seed}:{family}:{index}")
        return build_variant(family, rng, index, self._params(family, size_index))

    def distinct(self) -> Iterator[Variant]:
        """Endless distinct variants, block after block in the same order
        of families and sizes: a closed loop finishes few requests in a
        window, so only the contracts' constants move with the seed."""
        fams = block(self.traffic)
        while True:
            for fam in fams:
                n = next(self._size_cycle[fam])  # this family's n-th request
                yield self.variant(fam, n, n)

    def pool(self) -> List[Variant]:
        """The pool of distinct variants, rank 1 first.  Families hold
        ranks in a fixed interleave; the seed permutes ranks within a
        family."""
        size = int(self.traffic["pool"])
        counts = _apportion(self.traffic["families"], size)
        fixed = random.Random("pool-layout")
        layout = [f for f in sorted(counts) for _ in range(counts[f])]
        fixed.shuffle(layout)
        members = {f: [self.variant(f, i, i) for i in range(n)] for f, n in counts.items()}
        for f in members:
            self.rng.shuffle(members[f])
        taken = {f: iter(members[f]) for f in members}
        return [next(taken[f]) for f in layout]

    def popular(self, n: int) -> List[Variant]:
        """``n`` requests over the pool: ranks at the quantiles of the
        popularity law, in an order the seed picks."""
        pool = self.pool()
        law = self.traffic.get("popularity", {"law": "zipf", "s": 1.0})
        if law["law"] != "zipf":
            raise ValueError(f"unknown popularity law {law['law']!r}")
        weights = [1.0 / (r ** float(law["s"])) for r in range(1, len(pool) + 1)]
        total = sum(weights)
        cdf = list(itertools.accumulate(w / total for w in weights))
        picks = []
        for j in range(n):
            q = (j + 0.5) / n
            picks.append(pool[min(next(i for i, c in enumerate(cdf) if c >= q), len(pool) - 1)])
        self.rng.shuffle(picks)
        return picks

    def stream(self, n: Optional[int] = None) -> Iterator[Variant]:
        """The requests' variants, in the order they are sent; ``n`` is
        the number of requests where it is known (an open loop)."""
        if int(self.traffic.get("pool", 0)):
            # a closed loop never outruns 4096 requests in a window
            return iter(self.popular(n or 4096))
        return self.distinct()

    def arrivals(self, seconds: float) -> List[float]:
        """Open loop: offsets from the window's start of every arrival in
        it.  Gaps are exponential quantiles at ``rate_per_s``, permuted."""
        rate = float(self.traffic["rate_per_s"])
        n = max(1, int(round(rate * seconds)))
        gaps = [-math.log(1.0 - (j + 0.5) / n) / rate for j in range(n)]
        self.rng.shuffle(gaps)
        # the first arrival opens the window
        times = list(itertools.accumulate([0.0] + gaps[:-1]))
        return [t for t in times if t < seconds]

    def warmup(self) -> List[Variant]:
        """One variant per family, at its largest size, drawn apart from
        the window's: it loads or compiles the programs the window uses,
        without filling its caches with the window's contracts."""
        out = []
        for fam in self.traffic["families"]:
            sizes = self.sizes(fam)
            i = max(range(len(sizes)), key=lambda j: sorted(sizes[j].values()))
            rng = random.Random(f"{self.draw_seed}:warmup:{fam}")
            out.append(build_variant(fam, rng, i, sizes[i]))
        return out
