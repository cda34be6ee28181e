"""End-to-end over-approximation contract: identical issue sets on/off.

The whole point of the static pass is that it only removes WORK, never
issues.  This runs the killbilly workload (all 14 modules) with the gate
enabled and disabled and asserts byte-identical findings while the gated
run actually skipped modules and elided hooks.
"""

from bench_contracts import analyze, clear_caches, killbilly_contract
from mythril_tpu.observability import get_registry
from mythril_tpu.staticpass import clear_cache, reset_views
from mythril_tpu.support.support_args import args


def _run(staticpass_on: bool):
    prev = args.staticpass
    args.staticpass = staticpass_on
    try:
        clear_caches()
        clear_cache()
        reset_views()
        get_registry().reset(prefix="staticpass.")
        _, issues = analyze(
            killbilly_contract(), 0x0901D12E, 3, modules=None, timeout=300
        )
        snap = {
            k: v
            for k, v in get_registry().snapshot().items()
            if k.startswith("staticpass.")
        }
        return sorted((i.swc_id, i.address, i.title) for i in issues), snap
    finally:
        args.staticpass = prev


def test_issue_sets_identical_and_gate_nontrivial():
    on_issues, on_snap = _run(True)
    off_issues, off_snap = _run(False)
    assert on_issues == off_issues
    # the recall issue itself must be present in both
    assert any(swc == "106" for swc, _, _ in on_issues)
    # and the gated run must have actually pruned something
    assert on_snap["staticpass.modules_skipped"] > 0
    assert on_snap["staticpass.hooks_elided"] > 0
    assert off_snap.get("staticpass.modules_skipped", 0) == 0
