"""Seeded contract families: one module per family under ``families/``.

Each family module defines ``build(rng, index, params) -> Variant``.  A
variant keeps its family's shape and size bucket; the seed moves
selectors, constants, storage slots and guard order.  ``expected`` is the
reference: every (SWC id, pc) the family puts there by construction, the
complete issue set an analysis at the configuration's guarantees must
report.  ``possible`` lists further reports that are no fault (true
findings an analysis may or may not reach within its budget).  ``planted`` names the SWC ids of the family's headline exploit,
whose first report times the time to first exploit.
The harness finds a family by its file name, so a new family is a new
file.
"""

from __future__ import annotations

import functools
import importlib.util
import os
from dataclasses import dataclass
from typing import Tuple

FAMILY_DIR = os.path.join(os.path.dirname(os.path.abspath(__file__)), "families")


@dataclass(frozen=True)
class Variant:
    family: str
    index: int
    code: bytes
    expected: Tuple[Tuple[str, int], ...]  # (SWC id, pc), sorted
    planted: Tuple[str, ...]  # SWC ids of the headline exploit
    possible: Tuple[Tuple[str, int], ...] = ()  # reports that are no fault

    @property
    def hex(self) -> str:
        return "0x" + self.code.hex()

    @property
    def allowed(self) -> set:
        return set(self.expected) | set(self.possible)


@functools.lru_cache(maxsize=None)
def load_family(name: str):
    path = os.path.join(FAMILY_DIR, f"{name}.py")
    if not os.path.isfile(path):
        raise KeyError(f"no contract family {name!r} ({path})")
    spec = importlib.util.spec_from_file_location(f"benchmark_family_{name}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def build_variant(family: str, rng, index: int, params=None) -> Variant:
    mod = load_family(family)
    code, expected, *possible = mod.build(rng, index, params or {})
    return Variant(family, index, code, tuple(sorted(expected)), tuple(mod.PLANTED),
                   tuple(sorted(possible[0])) if possible else ())
