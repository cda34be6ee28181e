"""Dispatch-RTT calibration: scale device break-evens to the actual link.

The frontier/probe break-even constants are anchors measured at a 100 ms
dispatch round trip: ``device_probe_threshold`` (the DAG-size x candidates
product above which a probe dispatch beats host evaluation) and the
narrow-gate static-JUMPI floor both encode that latency.  Where the round
trip is shorter the same constants under-sell the device; where it is
longer they over-dispatch.

This module measures the real dispatch round trip ONCE on a TPU (tiny
jitted add, median of three timed runs after a warmup) the first time a
device decision is taken, and rescales the defaults linearly in RTT around
the anchor.  User-overridden values are left alone.  The measurement is
reported in the jsonv2 meta (``mythril_execution_info.calibration``).
"""

from __future__ import annotations

import logging
import time
from typing import Dict, Optional

log = logging.getLogger(__name__)

# anchor measured at a 100 ms dispatch round trip
_ANCHOR_RTT_MS = 100.0
_ANCHOR_PROBE_THRESHOLD = 600_000
_ANCHOR_MIN_STATIC_JUMPIS = 8
# observed-width admission gate, anchor measured at a 100 ms dispatch round
# trip: 24 live paths was the empirical floor below which segment fixed
# costs beat the host there (round-5 width study: the 0.3-0.7x rows peak at
# width 5-12, the winning rows at 40+); at a sub-millisecond round trip this
# scales down to the engine default of 8
_ANCHOR_MIN_SEED_WIDTH = 24

_state: Dict = {"done": False, "rtt_ms": None, "applied": {}}


def measure_dispatch_rtt_ms() -> Optional[float]:
    """Median round trip of a tiny device dispatch, in milliseconds.

    Returns None when the initialised backend is not a TPU."""
    from mythril_tpu.support.device import on_tpu

    if not on_tpu():
        return None
    import jax
    import numpy as np

    f = jax.jit(lambda x: x + 1)

    def roundtrip():
        # a FRESH host upload and a FORCED host readback: the round trip a
        # probe dispatch pays, not just the enqueue
        y = f(jax.device_put(np.zeros((8,), np.int32)))
        np.asarray(y)

    roundtrip()  # compile + first-transfer setup outside the timed runs
    samples = []
    for _ in range(3):
        t0 = time.perf_counter()
        roundtrip()
        samples.append((time.perf_counter() - t0) * 1000.0)
    samples.sort()
    return samples[1]


def calibrate() -> Dict:
    """Measure once and rescale un-overridden break-evens; idempotent.

    Returns the telemetry dict (empty when calibration did not run)."""
    if _state["done"]:
        return _state["applied"]
    _state["done"] = True
    rtt = measure_dispatch_rtt_ms()
    _state["rtt_ms"] = rtt
    if rtt is None:
        return {}
    from mythril_tpu.frontier import engine as frontier_engine
    from mythril_tpu.support.support_args import args

    scale = rtt / _ANCHOR_RTT_MS
    applied: Dict = {"dispatch_rtt_ms": round(rtt, 2)}
    if args.device_probe_threshold == _ANCHOR_PROBE_THRESHOLD:
        new_threshold = int(
            min(5_000_000, max(20_000, _ANCHOR_PROBE_THRESHOLD * scale))
        )
        args.device_probe_threshold = new_threshold
        applied["device_probe_threshold"] = new_threshold
    if frontier_engine._MIN_STATIC_JUMPIS == _ANCHOR_MIN_STATIC_JUMPIS:
        new_jumpis = int(min(16, max(2, round(_ANCHOR_MIN_STATIC_JUMPIS * scale))))
        frontier_engine._MIN_STATIC_JUMPIS = new_jumpis
        applied["min_static_jumpis"] = new_jumpis
    if frontier_engine._MIN_SEED_WIDTH == 8:  # engine default, un-overridden
        new_width = int(min(64, max(8, round(_ANCHOR_MIN_SEED_WIDTH * scale))))
        frontier_engine._MIN_SEED_WIDTH = new_width
        applied["min_seed_width"] = new_width
    _state["applied"] = applied
    log.info("device calibration: %s", applied)
    return applied


def telemetry() -> Dict:
    """Calibration info for report meta (without forcing a measurement).

    Empty both when calibration never ran AND when it ran without an
    accelerator (rtt None) — a ``{"dispatch_rtt_ms": null}`` block would be
    noise every consumer has to null-check."""
    if not _state["done"] or _state["rtt_ms"] is None:
        return {}
    out = {"dispatch_rtt_ms": _state["rtt_ms"]}
    out.update({k: v for k, v in _state["applied"].items() if k != "dispatch_rtt_ms"})
    return out
