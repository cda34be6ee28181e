#!/usr/bin/env python
"""Flight-deck acceptance smoke: one pipelined frontier run, full deck armed.

Runs a small forced-frontier analysis (multi-shard when more than one
device is visible — CI forces 8 via XLA_FLAGS) with the span tracer,
heartbeat sampler, and flight recorder all on, exports the artifacts,
then VALIDATES them:

* the Chrome-trace JSON loads and is Perfetto-shaped (``traceEvents``);
* ``process_name``/``thread_name`` metadata names every track that
  recorded an event;
* every flow start ("s") has a matching finish ("f") with the same id,
  in wall-clock order — no dangling dispatch arrows;
* segment-id flow links exist (``flow.segment``) and the pipelined
  spans carry ``segment`` args;
* heartbeat counter tracks ("C" events) are present and the JSONL is
  parseable with monotonic ticks;
* a flight-recorder bundle can be dumped and loads back.

Exit status is nonzero on any violation.  Artifacts land in ``--out``
(default ``flightdeck-smoke/``) for CI to archive.

Usage::

    XLA_FLAGS=--xla_force_host_platform_device_count=8 \
        JAX_PLATFORMS=cpu python scripts/flightdeck_smoke.py --out DIR
"""

from __future__ import annotations

import json
import os
import pathlib
import sys

REPO = pathlib.Path(__file__).resolve().parents[1]
sys.path.insert(0, str(REPO))

FAILURES: list = []


def check(ok: bool, what: str) -> None:
    tag = "ok" if ok else "FAIL"
    print(f"[flightdeck-smoke] {tag}: {what}")
    if not ok:
        FAILURES.append(what)


def run_analysis(out_dir: pathlib.Path) -> dict:
    from bench_contracts import analyze, clear_caches, killbilly_contract
    from mythril_tpu.frontier import engine as _eng
    from mythril_tpu.observability import get_registry, get_tracer
    from mythril_tpu.observability.flightrecorder import (
        arm_flight_recorder,
        disarm_flight_recorder,
        get_flight_recorder,
    )
    from mythril_tpu.observability.heartbeat import get_heartbeat
    from mythril_tpu.support.support_args import args

    tracer = get_tracer()
    tracer.reset()
    tracer.enabled = True
    hb = get_heartbeat()
    hb.reset()
    hb.start(period_s=0.05, out_path=str(out_dir / "heartbeat.jsonl"))
    arm_flight_recorder(str(out_dir / "flight"), watchdog_deadline_s=600.0)

    args.probe_backend = "auto"
    args.frontier = True
    args.frontier_force = True  # tiny contract: bypass the narrow gate
    args.frontier_width = 64
    args.pipeline = True
    args.frontier_mesh = True
    _eng._SLOW_CODES.clear()
    _eng._NARROW_CODES.clear()
    clear_caches()

    try:
        _, issues = analyze(
            killbilly_contract(), 0x0901D12E, 3,
            modules=["AccidentallyKillable"], timeout=300,
        )
        check(any(i.swc_id == "106" for i in issues),
              "recall: the killbilly selfdestruct was found")
        bundle_path = get_flight_recorder().dump("smoke")
        hb.sample_now()
    finally:
        hb.stop()
        disarm_flight_recorder()
        tracer.export_chrome_trace(str(out_dir / "trace.json"))
        tracer.export_jsonl(str(out_dir / "trace.jsonl"))
        (out_dir / "metrics.json").write_text(
            json.dumps(get_registry().snapshot(), indent=1)
        )
        tracer.enabled = False

    import jax

    return {"bundle": bundle_path, "n_devices": jax.device_count()}


def validate_trace(out_dir: pathlib.Path) -> None:
    doc = json.loads((out_dir / "trace.json").read_text())
    events = doc["traceEvents"]
    check(isinstance(events, list) and events, "trace.json loads, has events")

    meta = [e for e in events if e["ph"] == "M"]
    check(any(e["name"] == "process_name" for e in meta),
          "process_name metadata present")
    named = {e["tid"] for e in meta if e["name"] == "thread_name"}
    used = {e["tid"] for e in events if e["ph"] != "M"}
    check(used <= named, "every track that recorded an event is named")
    names = {
        e["args"]["name"] for e in meta if e["name"] == "thread_name"
    }
    check("heartbeat" in names, "heartbeat counter track is named")

    flows = [e for e in events if e["ph"] in ("s", "t", "f")]
    starts = {e["id"] for e in flows if e["ph"] == "s"}
    ends = {e["id"] for e in flows if e["ph"] == "f"}
    check(bool(starts), "flow events present")
    check(starts == ends, f"every flow start has a finish "
          f"(dangling: {sorted(starts ^ ends)[:5]})")
    by_id: dict = {}
    for e in flows:
        by_id.setdefault(e["id"], []).append(e)
    ordered = all(
        all(a["ts"] <= b["ts"] for a, b in zip(evs, evs[1:]))
        for evs in by_id.values()
    )
    check(ordered, "flow endpoints are in wall-clock order")
    check(any(e["name"] == "flow.segment" for e in flows),
          "segment-id dispatch->harvest flow links present")

    seg_spans = [
        e for e in events
        if e["ph"] == "X" and e["name"].startswith("frontier.")
        and (e.get("args") or {}).get("segment") is not None
    ]
    check(bool(seg_spans), "frontier spans carry segment ids")
    counters = [e for e in events if e["ph"] == "C"]
    check(bool(counters), "heartbeat counter events present")


def validate_heartbeat(out_dir: pathlib.Path) -> None:
    lines = [
        json.loads(l)
        for l in (out_dir / "heartbeat.jsonl").read_text().splitlines()
    ]
    check(bool(lines), "heartbeat JSONL has samples")
    ticks = [l["tick"] for l in lines]
    check(ticks == sorted(ticks), "heartbeat ticks are monotonic")
    check(any("pipeline.pool_queue_depth" in l for l in lines),
          "queue depths were sampled from the pipelined runner")


def validate_bundle(bundle_path: str) -> None:
    bundle = json.loads(open(bundle_path).read())
    check(bundle["reason"] == "smoke", "flight bundle loads")
    check(bool(bundle.get("threads")), "bundle has thread stacks")
    check("spans_tail" in bundle, "bundle has a span tail")


def validate_metrics(out_dir: pathlib.Path) -> None:
    snap = json.loads((out_dir / "metrics.json").read_text())
    check(isinstance(snap, dict) and snap, "metrics.json loads")
    check(snap.get("pipeline.segments_pipelined", 0) > 0,
          "the run actually pipelined segments")


def main() -> int:
    out = pathlib.Path(
        sys.argv[sys.argv.index("--out") + 1]
        if "--out" in sys.argv else "flightdeck-smoke"
    )
    out.mkdir(parents=True, exist_ok=True)
    os.environ.setdefault("JAX_PLATFORMS", "cpu")

    info = run_analysis(out)
    print(f"[flightdeck-smoke] devices: {info['n_devices']}")
    validate_trace(out)
    validate_heartbeat(out)
    validate_bundle(info["bundle"])
    validate_metrics(out)

    if FAILURES:
        print(f"[flightdeck-smoke] {len(FAILURES)} FAILURES:", file=sys.stderr)
        for f in FAILURES:
            print(f"  - {f}", file=sys.stderr)
        return 1
    print("[flightdeck-smoke] all checks passed")
    return 0


if __name__ == "__main__":
    sys.exit(main())
