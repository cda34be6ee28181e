"""Service integration: real TCP daemon, concurrent clients, and the
determinism contract — per-request issue sets bit-identical to solo
one-shot runs of the same contracts.  Slow-marked: runs real analyses."""

import threading
from pathlib import Path

import pytest

from bench_contracts import KILLBILLY, etherstore_like, rubixi_like, wide_contract
from mythril_tpu.service import (
    AnalysisOptions,
    AnalysisService,
    ServiceConfig,
    issue_digest,
)
from mythril_tpu.service.client import ServiceClient
from mythril_tpu.service.server import AnalysisServer

pytestmark = pytest.mark.slow

REPO = Path(__file__).resolve().parents[2]
KILL_SIMPLE_HEX = (
    REPO / "tests" / "testdata" / "inputs" / "kill_simple.bin-runtime"
).read_text().strip()
CLEAN_HEX = "0x60006000f3"

OPTS = AnalysisOptions(transaction_count=2, execution_timeout=60)


def _solo_digests(contracts):
    """Ground truth: each contract analyzed alone, one-shot style."""
    from mythril_tpu.analysis.cooperative import run_cooperative_batch
    from mythril_tpu.facade.warm import reset_analysis_scope
    from mythril_tpu.service.codehash import normalize_code

    out = {}
    for name, code in contracts:
        reset_analysis_scope()
        issues_by_name, errors, _states = run_cooperative_batch(
            [(name, normalize_code(code))],
            transaction_count=OPTS.transaction_count,
            execution_timeout=OPTS.execution_timeout,
            isolate_errors=False,
        )
        assert not errors, f"solo run of {name} failed: {errors}"
        out[name] = sorted(issue_digest(i) for i in issues_by_name[name])
    reset_analysis_scope()
    return out


@pytest.fixture
def scoped_args():
    from mythril_tpu.facade.warm import reset_analysis_scope
    from mythril_tpu.support.support_args import args

    saved = dict(vars(args))
    yield
    vars(args).clear()
    vars(args).update(saved)
    from mythril_tpu.querycache import configure as configure_query_cache

    configure_query_cache(
        enabled=getattr(args, "query_cache", True),
        cache_dir=getattr(args, "query_cache_dir", None),
    )
    reset_analysis_scope()


def test_concurrent_clients_bit_identical_to_solo(scoped_args):
    """N>=4 concurrent TCP clients (duplicates by construction) each get
    the solo one-shot issue set, streamed, with dedup and a clean drain."""
    from mythril_tpu.observability.metrics import get_registry
    from mythril_tpu.support.support_args import args

    # persistent counter: earlier tests (crash containment) legitimately
    # error requests, so assert no NEW errors rather than zero ever
    errors0 = get_registry().counter(
        "service.request_errors", persistent=True
    ).snapshot() or 0

    contracts = [
        ("kill", KILL_SIMPLE_HEX),
        ("etherstore", etherstore_like().hex()),
        ("clean", CLEAN_HEX),
    ]

    # ground truth first, same engine configuration as the service
    args.frontier = False
    args.probe_backend = "host"
    args.transaction_count = OPTS.transaction_count
    args.execution_timeout = OPTS.execution_timeout
    solo = _solo_digests(contracts)
    assert [i[0] for i in solo["kill"]] == ["106"]
    assert solo["clean"] == []

    server = AnalysisServer(
        ServiceConfig(
            default_options=OPTS,
            max_batch_width=8,
            batch_window_s=0.3,
            frontier=False,
            probe=True,
            warmup=False,
        ),
        host="127.0.0.1",
        port=0,
    ).start()
    host, port = server.address
    # every contract submitted twice -> 6 clients, dedup by construction
    jobs = [
        (f"c{i}", name, code, "interactive" if i == 0 else "batch")
        for i, (name, code) in enumerate(contracts * 2)
    ]
    results = {}
    lock = threading.Lock()

    def _client(cid, name, code, tier):
        client = ServiceClient(host, port, timeout=600)
        events = list(
            client.submit_stream(code, name=cid, tier=tier)
        )
        with lock:
            results[cid] = (name, events)

    try:
        threads = [
            threading.Thread(target=_client, args=job, daemon=True)
            for job in jobs
        ]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=600)
        assert len(results) == len(jobs), "a client never completed"

        deduped_count = 0
        for cid, (name, events) in results.items():
            kinds = [e["event"] for e in events]
            assert kinds[0] == "accepted", (cid, kinds)
            assert kinds[-1] == "done", (cid, kinds)
            if events[0]["deduped"]:
                deduped_count += 1
            done = events[-1]
            digests = sorted(issue_digest(i) for i in done["issues"])
            # the determinism contract: shared batching, probes and
            # dedup must not change any request's issue set
            assert digests == solo[name], f"{cid} ({name}) diverged"
            # streamed issue events are exactly the authoritative set
            streamed = sorted(
                issue_digest(e) for e in events if e["event"] == "issue"
            )
            assert streamed == digests, (cid, name)
        assert deduped_count >= 3  # second submission of each contract

        stats = ServiceClient(host, port).stats()
        assert stats["service.dedup_hits"] >= 3
        assert stats["service.request_errors"] == errors0
    finally:
        assert server.stop(drain=True, timeout=120) is True


def test_server_ping_and_malformed_request(scoped_args):
    server = AnalysisServer(
        ServiceConfig(
            default_options=OPTS, frontier=False, probe=False, warmup=False
        ),
        host="127.0.0.1",
        port=0,
    ).start()
    host, port = server.address
    try:
        client = ServiceClient(host, port, timeout=30)
        assert client.ping() is True
        # an invalid submission is an error event, not a dead socket
        events = list(client.submit_stream("not-hex", name="bad"))
        assert events[-1]["event"] == "error"
        assert "hex" in events[-1]["error"]
        # and the blocking helper surfaces it as an exception
        with pytest.raises(RuntimeError, match="analysis failed"):
            client.submit("not-hex", name="bad2")
    finally:
        server.stop(drain=True, timeout=30)


def _serve_load_requests():
    """The synthetic traffic: 8 clients cycling over four contracts, so
    every contract is submitted twice (dedup by construction)."""
    contracts = [
        ("killbilly", KILLBILLY),
        ("etherstore", etherstore_like().hex()),
        ("rubixi", rubixi_like().hex()),
        ("wide4", wide_contract(4).hex()),
    ]
    return contracts, [
        (f"client{i}", *contracts[i % len(contracts)],
         "interactive" if i % 4 == 0 else "batch")
        for i in range(8)
    ]


@pytest.mark.parametrize("workers", [1, 2])
def test_serve_load_traffic_matches_solo(scoped_args, workers):
    """Concurrent in-process submitters, the tracer on: every request's
    issue digests equal the solo one-shot run of its contract, and the
    service drains cleanly.  One worker dedups the duplicates; a pool of
    two restarts no worker and every worker reports over the fleet
    fabric."""
    from mythril_tpu.observability.metrics import get_registry
    from mythril_tpu.observability.tracer import get_tracer
    from mythril_tpu.support.support_args import args

    contracts, requests = _serve_load_requests()
    args.frontier = False
    args.probe_backend = "host"
    solo = _solo_digests(contracts)

    reg = get_registry()
    dedup0 = reg.counter("service.dedup_hits", persistent=True).snapshot() or 0
    restarts0 = reg.counter(
        "service.worker_restarts", persistent=True).snapshot() or 0
    tracer = get_tracer()
    tracer.reset()
    tracer.enabled = True
    service = AnalysisService(ServiceConfig(
        default_options=OPTS,
        # a pool caps batch width so admitted work fans out over workers
        max_batch_width=len(requests) if workers == 1 else 4,
        batch_window_s=0.25 if workers == 1 else 0.05,
        frontier=False,
        probe=True,
        warmup=True,
        workers=workers,
        trace=workers > 1,
        flush_interval_s=0.25,
    )).start()
    results = {}
    lock = threading.Lock()

    def _submit(client, cname, code, tier):
        _req, stream, _deduped = service.submit(code, name=client, tier=tier,
                                                tenant=client)
        summary = stream.result(timeout=600)
        with lock:
            results[client] = (
                cname, sorted(issue_digest(i) for i in summary["issues"]))

    try:
        assert service.wait_warm(timeout=600) is True
        threads = [threading.Thread(target=_submit, args=req, daemon=True)
                   for req in requests]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=900)
        fleet = service.fleet.summary() if workers > 1 else None
    finally:
        drained = service.stop(drain=True, timeout=120)
        tracer.enabled = False
    foreign_spans = tracer.summary().get("foreign_spans", 0)
    tracer.reset()

    assert drained is True
    assert len(results) == len(requests), "a request never completed"
    for client, (cname, digests) in results.items():
        assert digests == solo[cname], f"{client} ({cname}) diverged"
    if workers == 1:
        assert (reg.counter("service.dedup_hits", persistent=True).snapshot()
                or 0) > dedup0
    else:
        assert (reg.counter("service.worker_restarts", persistent=True)
                .snapshot() or 0) == restarts0
        assert len(fleet["workers"]) == workers, fleet
        assert foreign_spans > 0
