"""Multi-selector seeding is a pure partition: recall must not change.

seed_message_call under args.multi_selector_seeding splits each symbolic
tx into one seed per function-table entry plus a complement seed.  The
union of the partition is the single-seed state space, so any analysis
must find exactly the same issues either way.
"""

import pytest

from bench_contracts import KILLBILLY, KILLBILLY_CREATION
from mythril_tpu.analysis.security import fire_lasers, reset_callback_modules
from mythril_tpu.analysis.symbolic import SymExecWrapper
from mythril_tpu.frontend.evmcontract import EVMContract
from mythril_tpu.support.support_args import args as global_args


def _analyze(multi_selector: bool):
    reset_callback_modules()
    from mythril_tpu.analysis.module.loader import ModuleLoader

    for m in ModuleLoader().get_detection_modules():
        m.cache.clear()
    old = global_args.multi_selector_seeding
    global_args.multi_selector_seeding = multi_selector
    try:
        contract = EVMContract(
            code=KILLBILLY, creation_code=KILLBILLY_CREATION, name="KillBilly"
        )
        sym = SymExecWrapper(
            contract,
            address=0x0901D12E,
            strategy="bfs",
            transaction_count=3,
            execution_timeout=120,
            modules=["AccidentallyKillable"],
        )
        issues = fire_lasers(sym, white_list=["AccidentallyKillable"])
    finally:
        global_args.multi_selector_seeding = old
    return sorted((i.swc_id, i.address) for i in issues)


def test_multi_selector_seeding_recall_parity():
    single = _analyze(False)
    partitioned = _analyze(True)
    assert single, "killbilly exploit not found at all"
    assert single == partitioned, (
        f"selector partition changed recall: {single} vs {partitioned}"
    )
