"""The reduction of a ``jax.profiler`` trace to the benchmark's numbers.

``load`` turns the profiler's ``.xplane.pb`` into a plain dict (device
planes' op and module events, host events), which ``reduce`` works on;
tests feed ``reduce`` a small recorded dict of the same form.

- busy: the union of the intervals in which an XLA op ran on a device,
  averaged over the devices; idle share = 1 - busy / window.
- program time: the device time of the XLA modules whose name starts
  with a given prefix (``jit_segment`` is the frontier's segment program).
- breakdown: the modules that took most device time, and the longest idle
  gaps, each named after the innermost host event open at its middle.
"""

from __future__ import annotations

import glob
import os
import re
from typing import Dict, List, Optional, Tuple

DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
_MODULE_ID = re.compile(r"\(\d+\)$")

Event = Tuple[str, float, float]  # (name, start_ns, duration_ns)


def load(trace_dir: str) -> Dict:
    """The newest ``.xplane.pb`` under ``trace_dir`` as a plain dict:
    ``{"devices": [{"plane", "ops", "modules"}], "host": [events]}``."""
    from jax.profiler import ProfileData

    paths = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                             recursive=True), key=os.path.getmtime)
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    data = ProfileData.from_file(paths[-1])
    devices, host = [], []
    for plane in data.planes:
        if DEVICE_PLANE.match(plane.name):
            entry = {"plane": plane.name, "ops": [], "modules": []}
            for line in plane.lines:
                key = {OPS_LINE: "ops", MODULES_LINE: "modules"}.get(line.name)
                if key:
                    entry[key] = [(e.name, float(e.start_ns), float(e.duration_ns))
                                  for e in line.events]
            devices.append(entry)
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                host.extend((e.name, float(e.start_ns), float(e.duration_ns))
                            for e in line.events if e.duration_ns > 0)
    return {"devices": devices, "host": host}


def union(intervals: List[Tuple[float, float]]) -> List[Tuple[float, float]]:
    out: List[List[float]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def _clip(iv, lo, hi):
    return [(max(s, lo), min(e, hi)) for s, e in iv if min(e, hi) > max(s, lo)]


def module_name(name: str) -> str:
    return _MODULE_ID.sub("", name)


def _host_name(host: List[Event], t: float) -> str:
    """The innermost host event open at ``t``."""
    best: Optional[Event] = None
    for ev in host:
        if ev[1] <= t <= ev[1] + ev[2] and (best is None or ev[2] < best[2]):
            best = ev
    return best[0] if best else "host (no span open)"


def reduce(trace: Dict, window_ns: float, program_prefix: str = "jit_segment",
           top: int = 10) -> Optional[Dict]:
    """Numbers of one traced window ``[0, window_ns]`` (profiler time).
    None when the trace holds no device plane."""
    devices = trace.get("devices") or []
    if not devices:
        return None
    busy, program_ns = [], 0.0
    by_module: Dict[str, float] = {}
    gaps: List[Tuple[float, float]] = []
    for dev in devices:
        ops = dev.get("ops") or dev.get("modules") or []
        iv = union(_clip([(s, s + d) for _, s, d in ops], 0.0, window_ns))
        busy.append(sum(e - s for s, e in iv))
        edges = [0.0] + [x for se in iv for x in se] + [window_ns]
        gaps += [(edges[i], edges[i + 1]) for i in range(0, len(edges), 2)
                 if edges[i + 1] > edges[i]]
        for name, s, d in dev.get("modules") or []:
            clipped = _clip([(s, s + d)], 0.0, window_ns)
            if not clipped:
                continue
            d_in = clipped[0][1] - clipped[0][0]
            key = module_name(name)
            by_module[key] = by_module.get(key, 0.0) + d_in
            if key.startswith(program_prefix):
                program_ns += d_in
    n = len(devices)
    host = trace.get("host") or []
    longest = sorted(gaps, key=lambda g: g[0] - g[1])[:top]
    return {
        "busy_s": sum(busy) / n / 1e9,
        "window_s": window_ns / 1e9,
        "program_s": program_ns / n / 1e9,
        "device_ops": [[k, v / n / 1e9] for k, v in
                       sorted(by_module.items(), key=lambda kv: -kv[1])[:top]],
        "idle_gaps": [[_host_name(host, (s + e) / 2), (e - s) / 1e9]
                      for s, e in longest],
    }
