"""Interprocedural refinement over-approximation contract: issue sets
are bit-identical with the interproc layer on and off (the base static
pass stays enabled in both runs), and the reachable coverage
denominator never reports below the raw one."""

import pytest

from bench_contracts import (
    analyze,
    clear_caches,
    etherstore_like,
    killbilly_contract,
    rubixi_like,
)
from mythril_tpu.observability import get_registry
from mythril_tpu.observability.exploration import get_exploration_ledger
from mythril_tpu.staticpass import clear_cache, reset_views
from mythril_tpu.support.support_args import args


def _run(interproc_on: bool, contract=None):
    prev = (args.staticpass, args.staticpass_interproc)
    args.staticpass = True
    args.staticpass_interproc = interproc_on
    try:
        clear_caches()
        clear_cache()
        reset_views()
        get_registry().reset(prefix="staticpass.")
        _, issues = analyze(
            contract if contract is not None else killbilly_contract(),
            0x0901D12E, 2, modules=None, timeout=300,
        )
        return sorted((i.swc_id, i.address, i.title) for i in issues)
    finally:
        args.staticpass, args.staticpass_interproc = prev


def _coverage_monotone():
    for code_hash, d in get_exploration_ledger().coverage().items():
        assert d["instruction_pct_reachable"] >= d["instruction_pct_raw"], code_hash


def test_issue_sets_identical_and_coverage_monotone():
    on_issues = _run(True)
    # with interproc on, every ledger entry must satisfy the defensive
    # guarantee: reachable coverage >= raw coverage
    _coverage_monotone()
    off_issues = _run(False)
    assert on_issues == off_issues
    # the recall issue itself must be present in both
    assert any(swc == "106" for swc, _, _ in on_issues)


@pytest.mark.parametrize("name, swc", [("etherstore", "107"),
                                       ("rubixi", "105")])
def test_real_shapes_identical_with_interproc_on_and_off(name, swc):
    code = {"etherstore": etherstore_like, "rubixi": rubixi_like}[name]()
    on_issues = _run(True, code)
    _coverage_monotone()
    off_issues = _run(False, code)
    assert on_issues == off_issues
    assert any(s == swc for s, _, _ in on_issues), on_issues


def test_reachable_coverage_exceeds_raw_where_code_is_dead():
    """The creation frame carries the runtime body as unreachable data:
    the corrected denominator must report strictly higher coverage there."""
    get_exploration_ledger().reset_scope()
    _run(True)
    coverage = get_exploration_ledger().coverage().values()
    assert any(
        d["instruction_pct_reachable"] > d["instruction_pct_raw"]
        for d in coverage
    ), list(coverage)
