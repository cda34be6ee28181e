"""Shared runner for the on-vs-off parity tests.

Each optimization keeps an escape hatch (``--no-pipeline``,
``--no-prefilter``, ``--harvest-workers 0`` ...), and its parity test runs
the same contract with the hatch open and shut: the issue sets must be
identical while the optimized run shows, in its counters, that it really
engaged.  These helpers run one side of such a comparison from cold
caches with the device frontier forced on (the contracts are tiny, so the
width gates would otherwise keep them on the host).
"""

from bench_contracts import (
    GATED,
    SUICIDE,
    analyze,
    clear_caches,
    killbilly_contract,
)

# tiny contracts: bypass the width gates so the device frontier really runs
FORCED_FRONTIER = dict(
    probe_backend="auto", frontier=True, frontier_force=True,
    frontier_width=64,
)


def exploit(name):
    """(target, transactions) of a shared exploit shape; each finds
    SWC-106."""
    if name == "killbilly":
        return killbilly_contract(), 3
    return {"suicide": SUICIDE, "gated": GATED}[name], 1


def _cold(reg, prefixes) -> None:
    from mythril_tpu import absdomain, devsolver
    from mythril_tpu.frontier import engine

    clear_caches()
    # verdict memos and the engine's per-code slow/narrow verdicts are
    # process-persistent on purpose; one mode's verdicts must not steer
    # the other mode's run
    absdomain.reset_state()
    devsolver.reset_state()
    engine._SLOW_CODES.clear()
    engine._NARROW_CODES.clear()
    engine._SLOW_SEGMENTS.clear()
    for prefix in prefixes:
        reg.reset(prefix=prefix)


def _snapshot(reg, prefixes) -> dict:
    snap = {}
    for prefix in prefixes:
        snap.update(reg.snapshot(prefix=prefix))
    return snap


def parity_run(monkeypatch, target, tx_count, modules, prefixes, **overrides):
    """One cold analysis of ``target`` under ``FORCED_FRONTIER`` plus
    ``overrides`` (``args`` fields).  Returns the sorted (swc, address)
    issue keys and the registry snapshot under ``prefixes``, which were
    zeroed before the run."""
    from mythril_tpu.observability import get_registry
    from mythril_tpu.support.support_args import args

    for name, value in {**FORCED_FRONTIER, **overrides}.items():
        monkeypatch.setattr(args, name, value)
    reg = get_registry()
    _cold(reg, prefixes)
    _, issues = analyze(target, 0x0901D12E, tx_count, modules=modules,
                        timeout=300)
    return sorted((i.swc_id, i.address) for i in issues), _snapshot(
        reg, prefixes)


def cooperative_parity_run(monkeypatch, jobs, tx_count, prefixes,
                           timeout=120, **overrides):
    """``parity_run`` for a multi-code batch through the cooperative
    scheduler.  Issue keys carry the member name and code hash; also
    returns ``frontier.segments`` dispatched by this run."""
    from mythril_tpu.adaptive import get_adaptive_controller
    from mythril_tpu.analysis.cooperative import analyze_cooperative
    from mythril_tpu.observability import get_registry
    from mythril_tpu.observability.exploration import get_exploration_ledger
    from mythril_tpu.support.support_args import args

    for name, value in {**FORCED_FRONTIER, **overrides}.items():
        monkeypatch.setattr(args, name, value)
    reg = get_registry()
    _cold(reg, prefixes)
    get_exploration_ledger().reset_scope()
    get_adaptive_controller().reset_scope()
    segments = reg.counter("frontier.segments").value
    per_name, _states = analyze_cooperative(
        jobs, transaction_count=tx_count, execution_timeout=timeout
    )
    issues = sorted(
        (name, i.swc_id, i.address, i.bytecode_hash)
        for name, found in per_name.items()
        for i in found
    )
    segments = reg.counter("frontier.segments").value - segments
    return issues, _snapshot(reg, prefixes), segments
