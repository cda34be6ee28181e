"""One run of one cell: ``python3 benchmark/run.py --workload <cell> --seed <n>
--seconds <s> --trace <0|1>``.

Everything a cell is made of is data found by name: the cell in
``BENCHMARK.json``, its configuration's file (``configs/``), its traffic
mix (``traffic/<mix>.json``), the contract families the mix names
(``corpus/families/``) and a reader per metric (``metrics/``).  The
configuration's ``entry`` picks how the system is driven: ``analyze``
(``entries/analyze.py``) or ``serve`` (``entries/serve.py``).

A run: refuse any device but a TPU (and a ``device_kind`` missing from
``peaks.json``), generate the contracts from the seed, warm up, open the
window, issue requests for ``--seconds``, follow each request issued to
its end, close the window, compare every answer with its contract's
expected issue set, and print the result as the last line.
"""

from __future__ import annotations

import argparse
import json
import os
import shutil
import sys
import tempfile
import time
from dataclasses import dataclass, field
from typing import Dict, List, Optional

from benchmark.counters import MissingCounter

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)


class Refused(Exception):
    """The run cannot measure: wrong device, missing program, bad cell."""


# -- the spec, found by name ---------------------------------------------


def load_spec(root: Optional[str] = None) -> dict:
    with open(os.path.join(root or ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


def resolve(spec: dict, workload: str, root: Optional[str] = None):
    """(cell, config, traffic, end-to-end metrics, per-layer metrics)."""
    from benchmark.loadgen import load_traffic

    cells = {c["name"]: c for c in spec["workloads"]}
    if workload not in cells:
        raise Refused(f"no workload {workload!r} in BENCHMARK.json")
    cell = cells[workload]
    cfg_entry = {c["name"]: c for c in spec["configs"]}[cell["config"]]
    with open(os.path.join(root or ROOT, cfg_entry["file"])) as f:
        config = json.load(f)
    traffic = load_traffic(cell["traffic"])

    def applies(m):
        return workload in m.get("workloads", [workload])

    e2e = [m for m in spec["end_to_end"] if applies(m)]
    per_layer = [m for m in spec["per_layer"] if applies(m)]
    return cell, config, traffic, e2e, per_layer


# -- the device ----------------------------------------------------------


def load_peaks() -> dict:
    with open(os.path.join(HERE, "peaks.json")) as f:
        return json.load(f)


def check_device(chips: int) -> dict:
    """The device JAX found, or Refused: anything but a TPU, fewer chips
    than the cell asks for, or a kind with no row in ``peaks.json``."""
    import jax

    devs = jax.devices()
    d0 = devs[0]
    if d0.platform != "tpu":
        raise Refused(f"JAX found no TPU (platform {d0.platform!r})")
    if len(devs) < chips:
        raise Refused(f"the cell asks for {chips} chips, JAX found {len(devs)}")
    if d0.device_kind not in load_peaks()["devices"]:
        raise Refused(f"device kind {d0.device_kind!r} has no row in peaks.json")
    return {"platform": d0.platform, "kind": d0.device_kind, "count": len(devs)}


def memory_peak_bytes() -> int:
    import jax

    peaks = []
    for d in jax.local_devices():
        try:
            stats = d.memory_stats() or {}
        except Exception:
            stats = {}
        peaks.append(int(stats.get("peak_bytes_in_use", 0)))
    return max(peaks, default=0)


# -- the window ------------------------------------------------------------


class Window:
    """Opens when set-up ends: starts the program's counters and, in a
    traced run, the profiler.  Closes when the last request issued in it
    has ended, and stops the profiler then.

    The profiler starts before the first request and stops after the last
    one has ended, on the thread that opens the window, so no device work
    is in flight at either end: stopped at 20 s from a thread of its own
    while an analysis ran, it crashed a traced run on the chip (my chip
    runs, PR 22, c3 and c6).  Host events are off: with them, stopping a
    trace took 20-213 s (c2, c4), and no program span named an idle gap."""

    def __init__(self, tally, trace_dir: Optional[str]):
        self.tally, self.trace_dir = tally, trace_dir
        self.t0 = self.t1 = None
        self.counters: Dict[str, float] = {}
        self.trace_s = 0.0

    def open(self) -> None:
        self.tally.start()
        if self.trace_dir:
            import jax

            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0  # no Python calls
            opts.host_tracer_level = 0  # no host events
            jax.profiler.start_trace(self.trace_dir, profiler_options=opts)
        self.t0 = time.time()

    def close(self) -> None:
        self.t1 = time.time()
        self.counters = self.tally.read()
        if self.trace_dir:
            import jax

            self.trace_s = time.time() - self.t0
            jax.profiler.stop_trace()


@dataclass
class Run:
    """What the metric readers see."""

    requests: List
    t0: float
    t1: float
    seconds: float
    setup_s: float
    counters: Dict[str, float]
    trace: Optional[dict] = None
    lateness: List[float] = field(default_factory=list)
    served: bool = False  # the service answered the requests

    def counter(self, name: str, default: Optional[float] = None) -> float:
        """A program counter's total over the window; ``MissingCounter``
        when the program never registered it and no ``default`` is given."""
        if name in self.counters:
            return self.counters[name]
        if default is None:
            raise MissingCounter(f"the program registered no counter {name!r}")
        return default

    @property
    def analysed(self) -> int:
        """Analyses the window ran: requests less those answered from
        another request's analysis."""
        if self.served:
            return int(self.counter("service.requests") - self.counter("service.dedup_hits")
                       - self.counter("service.replay_hits"))
        return len(self.requests)


def entry_for(config: dict):
    if config["entry"] == "analyze":
        from benchmark.entries.analyze import AnalyzeEntry
        return AnalyzeEntry
    if config["entry"] == "serve":
        from benchmark.entries.serve import ServeEntry
        return ServeEntry
    raise Refused(f"unknown entry {config['entry']!r}")


def execute(workload: str, seed: int, seconds: float, trace: bool,
            t_start: float, modules: Optional[List[str]] = None,
            device: Optional[dict] = None, record_dir: Optional[str] = None,
            warm: bool = True) -> dict:
    """One run; returns the result dict (the last line).  ``device`` is
    given only by tests, which skip the look for a chip; ``warm`` is off
    only for a later run in a process whose programs are loaded already."""
    from benchmark import reference
    from benchmark.loadgen import Generator
    from benchmark.metrics import load_reader

    spec = load_spec()
    cell, config, traffic, e2e, per_layer = resolve(spec, workload)
    try:
        import mythril_tpu
    except ImportError as e:
        raise Refused(f"the program is not here: {e}")
    if device is None:
        device = check_device(int(cell["chips"]))
    from mythril_tpu.observability import deviceplane

    from benchmark.counters import Tally

    mythril_tpu.arm_compile_cache()
    deviceplane.install()
    tally = Tally()
    gen = Generator(traffic, seed)
    entry = entry_for(config)(config, traffic, modules)
    trace_dir = tempfile.mkdtemp(prefix="bench-trace-") if trace else None
    window = Window(tally, trace_dir)
    try:
        requests = entry.execute(gen.warmup() if warm else [], gen, seconds, window)
        mem = memory_peak_bytes()
        reduced = None
        if trace_dir:
            from benchmark.trace.reduce import load, reduce

            reduced = reduce(load(trace_dir), window.trace_s * 1e9)
    finally:
        if trace_dir:
            shutil.rmtree(trace_dir, ignore_errors=True)
    run = Run(requests, window.t0, window.t1, seconds, window.t0 - t_start,
              window.counters, reduced, getattr(entry, "lateness", []),
              served=config["entry"] == "serve")
    numbers = reference.compare(requests)
    metrics = {}
    for m in (per_layer if trace else e2e):
        value = load_reader(m["name"])(run)
        if value is not None:
            metrics[m["name"]] = {"value": float(value), "unit": m["unit"]}
    device = dict(device, memory_peak_bytes=mem)
    if reduced is not None:
        device.update(busy_s=reduced["busy_s"], window_s=reduced["window_s"])
    result = {
        "correct": reference.correct(numbers, requests),
        "attempted": len(requests),
        "failed": sum(1 for r in requests if r.error is not None or r.done is None),
        "metrics": metrics,
        "device": device,
    }
    if reduced is not None:
        result["breakdown"] = {"device_ops": reduced["device_ops"],
                               "idle_gaps": reduced["idle_gaps"]}
    result["checks"] = {k: {"value": v, "limit": lim} for k, (v, lim) in numbers.items()}
    if run.lateness:  # the open-loop generator's, on a line before the result
        print(f"open-loop lateness: max {max(run.lateness):.6f} s over "
              f"{len(run.lateness)} sends", flush=True)
    if record_dir:
        _record(record_dir, workload, seed, trace, run, result)
    return result


def _record(record_dir, workload, seed, trace, run, result) -> None:
    """The run's file: every request's times and the generator's lateness."""
    os.makedirs(record_dir, exist_ok=True)
    rows = [{"rid": r.rid, "family": r.variant.family, "t0": r.t0 - run.t0,
             "start": None if r.start is None else r.start - run.t0,
             "first_planted": None if r.first_planted is None else r.first_planted - run.t0,
             "done": None if r.done is None else r.done - run.t0,
             "deduped": r.deduped, "error": r.error,
             "missed": sorted(set(r.variant.expected) - set(r.issues)),
             "unexpected": sorted(set(r.issues) - r.variant.allowed)}
            for r in run.requests]
    path = os.path.join(record_dir, f"{workload}.{seed}.trace{int(trace)}.json")
    with open(path, "w") as f:
        json.dump({"result": result, "setup_s": run.setup_s,
                   "window_s": run.t1 - run.t0, "lateness_s": run.lateness,
                   "counters": run.counters, "requests": rows}, f)


def main(argv=None, t_start: Optional[float] = None) -> int:
    t_start = time.time() if t_start is None else t_start
    ap = argparse.ArgumentParser(description="Run one benchmark cell once.")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    try:
        result = execute(args.workload, args.seed, args.seconds, bool(args.trace),
                         t_start, record_dir=os.path.join(ROOT, "bench_runs"))
    except Refused as e:
        print(f"benchmark refused: {e}", file=sys.stderr, flush=True)
        return 2
    for name, check in result["checks"].items():
        print(f"check {name} = {check['value']} (limit {check['limit']})",
              file=sys.stderr, flush=True)
    print(json.dumps(result), flush=True)
    return 0
