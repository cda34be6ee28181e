"""In-process end-to-end service tests: real analyses over tiny
contracts with the host engine (frontier off, warmup off) so each case
stays in the tier-1 budget."""

import threading
from pathlib import Path

import pytest

from mythril_tpu.service import (
    AnalysisOptions,
    AnalysisService,
    ServiceConfig,
    canonical_codehash,
    issue_digest,
)

REPO = Path(__file__).resolve().parents[2]
KILL_SIMPLE_HEX = (
    REPO / "tests" / "testdata" / "inputs" / "kill_simple.bin-runtime"
).read_text().strip()
CLEAN_HEX = "0x60006000f3"  # PUSH1 0; PUSH1 0; RETURN — nothing to report

OPTS = AnalysisOptions(transaction_count=1, execution_timeout=30)


def _config(**overrides):
    base = dict(
        default_options=OPTS,
        max_batch_width=4,
        batch_window_s=0.05,
        frontier=False,
        probe=True,
        warmup=False,
    )
    base.update(overrides)
    return ServiceConfig(**base)


@pytest.fixture
def scoped_args():
    """The service arms the global flag object at start(); snapshot and
    restore it (plus the detector scope) so these tests do not leak
    configuration into the rest of the suite."""
    from mythril_tpu.facade.warm import reset_analysis_scope
    from mythril_tpu.support.support_args import args

    saved = dict(vars(args))
    yield
    vars(args).clear()
    vars(args).update(saved)
    # the service also re-armed the global query cache; point it back
    from mythril_tpu.querycache import configure as configure_query_cache

    configure_query_cache(
        enabled=getattr(args, "query_cache", True),
        cache_dir=getattr(args, "query_cache_dir", None),
    )
    reset_analysis_scope()


def test_submit_streams_issues_then_done(scoped_args):
    service = AnalysisService(_config()).start()
    try:
        _req, stream, deduped = service.submit(
            KILL_SIMPLE_HEX, name="kill", tier="interactive"
        )
        assert deduped is False
        events = list(stream.events(timeout=120))
        kinds = [k for k, _ in events]
        assert kinds[-1] == "done" and "issue" in kinds
        summary = events[-1][1]
        assert [i["swc_id"] for i in summary["issues"]] == ["106"]
        # streamed issues are exactly the authoritative set, earlier
        streamed = [p for k, p in events if k == "issue"]
        assert (
            sorted(issue_digest(i) for i in streamed)
            == sorted(issue_digest(i) for i in summary["issues"])
        )
        # the interactive tier's first evidence came from the host probe
        assert streamed[0].get("provisional") is True
    finally:
        assert service.stop(drain=True, timeout=30) is True


def test_clean_contract_reports_no_issues(scoped_args):
    service = AnalysisService(_config(probe=False)).start()
    try:
        _req, stream, _ = service.submit(CLEAN_HEX, name="clean")
        assert stream.issues(timeout=120) == []
    finally:
        service.stop(drain=True, timeout=30)


def test_duplicate_concurrent_submits_share_one_analysis(scoped_args):
    from mythril_tpu.observability.metrics import get_registry

    reg = get_registry()
    batches0 = reg.counter("service.batches", persistent=True).snapshot()
    dedup0 = reg.counter("service.dedup_hits", persistent=True).snapshot()

    # wide admission window so both submissions land in one flight
    service = AnalysisService(_config(batch_window_s=0.3)).start()
    results = {}
    lock = threading.Lock()

    def _client(cid):
        _req, stream, deduped = service.submit(KILL_SIMPLE_HEX, name=cid)
        summary = stream.result(timeout=120)
        with lock:
            results[cid] = (deduped, summary)

    try:
        threads = [
            threading.Thread(target=_client, args=(f"c{i}",)) for i in range(3)
        ]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=120)
        assert len(results) == 3
        digests = {
            cid: sorted(issue_digest(i) for i in summary["issues"])
            for cid, (_d, summary) in results.items()
        }
        assert len(set(map(tuple, digests.values()))) == 1
        # exactly one analysis ran; every other submission deduped
        assert (
            reg.counter("service.batches", persistent=True).snapshot()
            - batches0
        ) == 1
        assert (
            reg.counter("service.dedup_hits", persistent=True).snapshot()
            - dedup0
        ) == 2
    finally:
        service.stop(drain=True, timeout=30)


def test_per_request_isolation_on_tenant_failure(scoped_args, monkeypatch):
    """One tenant's failure reaches only that tenant; batchmates complete."""
    import mythril_tpu.analysis.cooperative as coop

    boom_hash = canonical_codehash(CLEAN_HEX)
    real = coop.run_cooperative_batch

    def _sabotaged(jobs, **kwargs):
        issues, errors, states = real(jobs, **kwargs)
        if any(name == boom_hash for name, _code in jobs):
            issues.pop(boom_hash, None)
            errors[boom_hash] = "injected tenant failure"
        return issues, errors, states

    monkeypatch.setattr(coop, "run_cooperative_batch", _sabotaged)

    service = AnalysisService(_config(probe=False, batch_window_s=0.3)).start()
    try:
        _r1, ok_stream, _ = service.submit(KILL_SIMPLE_HEX, name="ok")
        _r2, boom_stream, _ = service.submit(CLEAN_HEX, name="boom")
        with pytest.raises(RuntimeError, match="injected tenant failure"):
            boom_stream.result(timeout=120)
        # the co-batched healthy tenant is untouched by the failure
        assert [i["swc_id"] for i in ok_stream.issues(timeout=120)] == ["106"]

        # the failure is NOT cached: resubmitting analyzes afresh
        monkeypatch.setattr(coop, "run_cooperative_batch", real)
        _r3, retry_stream, deduped = service.submit(CLEAN_HEX, name="retry")
        assert deduped is False
        assert retry_stream.issues(timeout=120) == []
    finally:
        service.stop(drain=True, timeout=30)


def test_completed_result_replays_without_reanalysis(scoped_args):
    from mythril_tpu.observability.metrics import get_registry

    reg = get_registry()
    service = AnalysisService(_config(probe=False)).start()
    try:
        _r1, first, _ = service.submit(KILL_SIMPLE_HEX, name="first")
        first_issues = first.issues(timeout=120)

        batches0 = reg.counter("service.batches", persistent=True).snapshot()
        replay0 = reg.counter("service.replay_hits", persistent=True).snapshot()
        _r2, second, deduped = service.submit(KILL_SIMPLE_HEX, name="second")
        assert deduped is True
        assert second.issues(timeout=10) == first_issues
        assert (
            reg.counter("service.batches", persistent=True).snapshot()
            == batches0
        )
        assert (
            reg.counter("service.replay_hits", persistent=True).snapshot()
            - replay0
        ) == 1
    finally:
        service.stop(drain=True, timeout=30)


def test_stop_drains_and_rejects_new_submissions(scoped_args):
    service = AnalysisService(_config(probe=False)).start()
    _req, stream, _ = service.submit(KILL_SIMPLE_HEX, name="inflight")
    assert service.stop(drain=True, timeout=120) is True
    # the in-flight request still got its full result during the drain
    assert [i["swc_id"] for i in stream.issues(timeout=1)] == ["106"]
    with pytest.raises(RuntimeError, match="not accepting"):
        service.submit(KILL_SIMPLE_HEX, name="late")


def test_cache_root_pins_query_cache_only(scoped_args, tmp_path):
    root = tmp_path / "svc-cache"
    service = AnalysisService(
        _config(probe=False, cache_root=str(root))
    ).start()
    try:
        _req, stream, _ = service.submit(KILL_SIMPLE_HEX, name="kill")
        stream.result(timeout=120)
    finally:
        service.stop(drain=True, timeout=30)
    from mythril_tpu.support.support_args import args

    assert args.query_cache_dir == str(root / "querycache")
    # the XLA cache follows JAX_COMPILATION_CACHE_DIR, never --cache-root
    assert not hasattr(args, "compile_cache_dir")
    assert not (root / "xla").exists()
    # the query cache persisted solved queries under the pinned root
    assert (root / "querycache").is_dir()


def test_wait_warm_and_stats(scoped_args):
    service = AnalysisService(_config(warmup=True)).start()
    try:
        assert service.wait_warm(timeout=120) is True
        stats = service.stats()
        assert "service.requests" in stats
        assert stats["service.queue_depth"] == 0
    finally:
        service.stop(drain=True, timeout=30)


def test_failed_warmup_fails_startup(scoped_args, monkeypatch):
    from mythril_tpu.service import worker

    def boom():
        raise RuntimeError("injected warmup failure")

    monkeypatch.setattr(worker, "run_warmup", boom)
    service = AnalysisService(_config(warmup=True)).start()
    try:
        with pytest.raises(RuntimeError, match="warmup failed"):
            service.wait_warm(timeout=60)
        with pytest.raises(RuntimeError, match="not accepting"):
            service.submit(KILL_SIMPLE_HEX, name="late")
    finally:
        service.stop(drain=False, timeout=10)


def test_workers_above_chip_count_refused(scoped_args, monkeypatch):
    from mythril_tpu.support import device

    monkeypatch.setattr(device, "local_tpu_chips", lambda: 1)
    with pytest.raises(RuntimeError, match="exceeds the 1 TPU chip"):
        AnalysisService(_config(workers=2)).start()
