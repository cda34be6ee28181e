"""Worker-pool end-to-end tests: digest parity with the solo path,
crash containment, drain-on-stop with busy workers, and the
cross-process completed-result store.

Each pool test spawns real worker processes (spawn start method, so a
fresh interpreter imports the engine); contracts are tiny and warmup is
off to keep the module inside the tier-1 budget."""

import os
import signal
import time
from pathlib import Path

import pytest

from mythril_tpu.service import (
    AnalysisOptions,
    AnalysisService,
    ServiceConfig,
    issue_digest,
)

REPO = Path(__file__).resolve().parents[2]
KILL_SIMPLE_HEX = (
    REPO / "tests" / "testdata" / "inputs" / "kill_simple.bin-runtime"
).read_text().strip()
CLEAN_HEX = "0x60006000f3"

OPTS = AnalysisOptions(transaction_count=1, execution_timeout=30)


def _config(**overrides):
    base = dict(
        default_options=OPTS,
        max_batch_width=1,  # one flight per job: fan out across workers
        batch_window_s=0.05,
        frontier=False,
        probe=False,
        warmup=False,
    )
    base.update(overrides)
    return ServiceConfig(**base)


def _digests(service, code, name):
    _req, stream, _ = service.submit(code, name=name)
    summary = stream.result(timeout=180)
    return sorted(issue_digest(i) for i in summary["issues"])


def _restarts_now():
    from mythril_tpu.observability.metrics import get_registry

    return get_registry().counter(
        "service.worker_restarts", persistent=True
    ).snapshot() or 0


def test_pool_digests_bit_identical_to_solo(scoped_args):
    solo = AnalysisService(_config()).start()
    try:
        want = {
            "kill": _digests(solo, KILL_SIMPLE_HEX, "kill"),
            "clean": _digests(solo, CLEAN_HEX, "clean"),
        }
    finally:
        assert solo.stop(drain=True, timeout=30) is True
    assert want["kill"] and not want["clean"]

    r0 = _restarts_now()
    pool = AnalysisService(_config(workers=2)).start()
    try:
        assert pool.wait_warm(timeout=600) is True
        assert pool.pooled is True
        got = {
            "kill": _digests(pool, KILL_SIMPLE_HEX, "kill"),
            "clean": _digests(pool, CLEAN_HEX, "clean"),
        }
        stats = pool.stats()
        assert len(stats["workers"]) == 2
    finally:
        assert pool.stop(drain=True, timeout=60) is True
    assert got == want
    assert _restarts_now() == r0, "a pool worker restarted"


def test_worker_crash_errors_only_its_requests(scoped_args):
    r0 = _restarts_now()
    # two transactions widen the execution window so the kill lands
    # while the victim batch is genuinely in flight
    slow = AnalysisOptions(transaction_count=2, execution_timeout=60)
    service = AnalysisService(_config(workers=2)).start()
    try:
        assert service.wait_warm(timeout=600) is True
        _req, victim, _ = service.submit(
            KILL_SIMPLE_HEX, name="victim", options=slow
        )
        # wait for dispatch, then kill that worker process outright
        deadline = time.time() + 60
        pid = None
        while time.time() < deadline:
            busy = [w for w in service.worker_stats()
                    if w["state"] == "busy"]
            if busy:
                pid = busy[0]["pid"]
                break
            time.sleep(0.01)
        assert pid is not None, "victim batch was never dispatched"
        os.kill(pid, signal.SIGKILL)

        events = list(victim.events(timeout=120))
        kinds = [k for k, _ in events]
        # the dead worker's request errors — no silent requeue, so no
        # done event and no issues from a half-run analysis
        assert kinds[-1] == "error"
        assert "died" in events[-1][1]
        assert "done" not in kinds

        # the daemon survives: a follow-up request completes normally
        # on the remaining/respawned workers
        _req2, stream2, _ = service.submit(CLEAN_HEX, name="after")
        assert stream2.result(timeout=180)["issues"] == []
        assert _restarts_now() >= r0 + 1
        assert service.stats()["service.worker_restarts"] >= 1
    finally:
        service.stop(drain=True, timeout=60)


def test_stop_drains_busy_workers(scoped_args):
    service = AnalysisService(_config(workers=2)).start()
    try:
        assert service.wait_warm(timeout=600) is True
        _r1, s1, _ = service.submit(KILL_SIMPLE_HEX, name="d1")
        _r2, s2, _ = service.submit(CLEAN_HEX, name="d2")
    finally:
        # SIGTERM path: drain must let in-flight work finish, not drop it
        assert service.stop(drain=True, timeout=180) is True
    kill_summary = s1.result(timeout=10)
    assert [i["swc_id"] for i in kill_summary["issues"]] == ["106"]
    assert s2.result(timeout=10)["issues"] == []


def test_result_store_replays_across_processes(scoped_args, tmp_path):
    from mythril_tpu.observability.metrics import get_registry
    from mythril_tpu.service.resultstore import ResultStore

    reg = get_registry()
    hits0 = reg.counter(
        "service.result_store_hits", persistent=True
    ).snapshot() or 0
    cache_root = str(tmp_path / "cache")

    first = AnalysisService(_config(cache_root=cache_root)).start()
    try:
        want = _digests(first, KILL_SIMPLE_HEX, "kill")
    finally:
        assert first.stop(drain=True, timeout=30) is True

    # the completed-result store persisted the terminal event log
    store = ResultStore(os.path.join(cache_root, "results"))
    assert len(store) == 1

    # a FRESH daemon over the same cache root replays without analysis:
    # this is the cross-worker/cross-process dedup hit
    second = AnalysisService(_config(cache_root=cache_root)).start()
    try:
        req, stream, deduped = second.submit(KILL_SIMPLE_HEX, name="again")
        assert deduped is True
        summary = stream.result(timeout=10)
        assert sorted(issue_digest(i) for i in summary["issues"]) == want
        hits1 = reg.counter(
            "service.result_store_hits", persistent=True
        ).snapshot() or 0
        assert hits1 >= hits0 + 1
    finally:
        assert second.stop(drain=True, timeout=30) is True


def test_result_store_keeps_only_done_logs(tmp_path):
    from mythril_tpu.service.resultstore import ResultStore

    store = ResultStore(str(tmp_path / "results"))
    key = ("0x" + "ab" * 32, OPTS.key())
    store.put(key, [("issue", {"swc_id": "106"}), ("error", "boom")])
    assert store.get(key) is None  # not a completed result
    done = [("issue", {"swc_id": "106"}), ("done", {"issues": []})]
    store.put(key, done)
    assert store.get(key) == done
    # unknown key misses cleanly
    assert store.get(("0x" + "cd" * 32, OPTS.key())) is None
