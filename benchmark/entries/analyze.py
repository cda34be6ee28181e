"""``myth analyze``, one call per contract, in this process.

Each call goes through ``cli.main`` with ``-o jsonv2``, from a fresh
analysis scope and with the solver's in-process caches cleared, as a new
one-shot process would start (``chip_smoke.py``'s ``analyze``, and
``bench.py``'s ``_clear_caches``).  Only the process start and JAX's
initialisation are paid once, in set-up.
"""

from __future__ import annotations

import contextlib
import io
import json
import sys
import time
from typing import List, Optional, Tuple

from benchmark.loadgen import Request


def run_cli(argv: List[str]) -> Tuple[int, str]:
    """``python -m mythril_tpu <argv>`` in this process: (exit code, stdout)."""
    from mythril_tpu.interfaces import cli

    out = io.StringIO()
    saved = sys.argv
    sys.argv = ["myth", *argv]
    code = 0
    try:
        with contextlib.redirect_stdout(out):
            try:
                cli.main()
            except SystemExit as e:
                code = e.code if isinstance(e.code, int) else 1
    finally:
        sys.argv = saved
    return code, out.getvalue()


def fresh_scope() -> None:
    """What a new one-shot process starts without: detector caches and
    issue lists, per-analysis metrics, the solver's model and query caches."""
    from mythril_tpu.analysis.module.loader import ModuleLoader
    from mythril_tpu.analysis.security import reset_callback_modules
    from mythril_tpu.facade.warm import reset_analysis_scope
    from mythril_tpu.querycache import reset_query_cache
    from mythril_tpu.smt.solver import clear_model_cache
    from mythril_tpu.support.model import _get_model_cached

    reset_analysis_scope()
    reset_callback_modules()
    clear_model_cache()
    _get_model_cached.cache_clear()
    reset_query_cache()
    for module in ModuleLoader().get_detection_modules():
        module.cache.clear()


def warm_interpreters() -> None:
    """Compile, or load, the program's one-time device interpreters (tape
    VM, known-bits pre-filter, device solver) before anything is timed.

    Under ``--probe-backend auto`` the program compiles each in a
    background thread once a query first needs it, and serves queries on
    the host until it is ready.  Left to that, the tape VM compiled inside
    the window (3.7-33.6 s, my chip runs, PR 22, c8) and the window's
    queries switched from host to device at a time set by that race, which
    moved the time to first exploit by seconds from run to run."""
    from mythril_tpu.absdomain import device as absdomain_device
    from mythril_tpu.devsolver import device as devsolver_device
    from mythril_tpu.ops import tape_vm

    for warmup in (tape_vm.warmup, absdomain_device.warmup, devsolver_device.warmup):
        warmup()


def rebase_stamp(wall: float, t0: float, eps: float = 0.05) -> float:
    """bench.py ``_rebase_stamp``: an absolute discovery stamp as seconds
    after ``t0``; a stamp well before ``t0`` was not found by this run."""
    delta = wall - t0
    if delta < -eps:
        return float("nan")
    return max(0.0, delta)


class AnalyzeEntry:
    """A closed loop of one auditor running ``myth analyze``."""

    def __init__(self, config: dict, traffic: dict, modules: Optional[List[str]] = None):
        if traffic["loop"] != "closed" or int(traffic.get("clients", 1)) != 1:
            raise ValueError("the analyze entry runs one auditor: a closed loop, 1 client")
        self.options = list(config["options"])
        if modules is not None:
            self.options += ["-m", ",".join(modules)]

    def analyze(self, req: Request) -> None:
        from mythril_tpu.analysis.report import StartTime

        fresh_scope()
        req.start = time.time()
        rc, out = run_cli(["analyze", "-c", req.variant.hex, "--bin-runtime",
                           *self.options, "-o", "jsonv2"])
        req.done = time.time()
        try:
            report = json.loads(out.strip().splitlines()[-1])[0]
        except (IndexError, ValueError, KeyError) as e:
            req.error = f"analyze exited {rc}: no jsonv2 report ({e!r})"
            return
        if rc != 0:
            req.error = f"analyze exited {rc}"
        base = StartTime().global_start_time
        planted = set(req.variant.planted)
        for issue in report.get("issues", []):
            swc = issue["swcID"].replace("SWC-", "")
            pc = int(issue["locations"][0]["sourceMap"].split(":")[0])
            req.issues.append((swc, pc))
            if swc in planted and (swc, pc) in req.variant.expected:
                at = rebase_stamp(base + issue["extra"]["discoveryTime"] / 1e9, req.start)
                if at == at and (req.first_planted is None
                                 or req.start + at < req.first_planted):
                    req.first_planted = req.start + at

    def warm(self, variants) -> None:
        warm_interpreters()
        for i, v in enumerate(variants):
            req = Request(-1 - i, v)
            self.analyze(req)
            if req.error:
                raise RuntimeError(f"warm-up analysis of {v.family} failed: {req.error}")

    def execute(self, warm, gen, seconds: float, window) -> List[Request]:
        """Warm up, then analyze contracts back to back while the window
        is open; the last one issued runs to its end."""
        self.warm(warm)
        done: List[Request] = []
        window.open()
        end = window.t0 + seconds
        for rid, variant in enumerate(gen.stream()):
            if time.time() >= end:
                break
            req = Request(rid, variant)
            self.analyze(req)
            done.append(req)
        window.close()
        return done
