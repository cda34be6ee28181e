"""The shared fixture contracts and the recall oracle in ``bench_contracts``.

Tests, the chip smoke and the graft entry all judge a killbilly run with
``check_recall`` and drive the device frontier with ``wide_contract``; a
fixture that silently drifted would make every one of them vacuous.
"""

from types import SimpleNamespace

import pytest

from bench_contracts import (
    _selects,
    check_recall,
    read_runtime,
    wide_contract,
)
from mythril_tpu.frontend.disassembler import Disassembly
from mythril_tpu.staticpass.cfg import StaticCFG
from mythril_tpu.staticpass.tables import InstrTables

ACTIVATE, KILL = 0x0A11CE00, 0x41C0E1B5


@pytest.mark.parametrize("calldata, selector, expected", [
    ("0x0a11ce00", ACTIVATE, True),
    # minimization may shave the selector's trailing zero byte:
    # CALLDATALOAD zero-pads past calldatasize, so it still dispatches
    ("0a11ce", ACTIVATE, True),
    ("0x41c0e1b5" + "00" * 32, KILL, True),
    ("0x0a11ce01", ACTIVATE, False),
])
def test_selects_reads_calldata_like_calldataload(calldata, selector,
                                                 expected):
    assert _selects(calldata, selector) is expected


def _issue(swc, inputs):
    return SimpleNamespace(
        swc_id=swc,
        transaction_sequence={"steps": [{"input": i} for i in inputs]},
    )


def test_check_recall_accepts_activate_then_kill():
    check_recall([_issue("106", ["0x", "0x0a11ce", "0x41c0e1b5"])])


@pytest.mark.parametrize("issues, why", [
    ([], "zero issues"),
    ([_issue("107", ["0x0a11ce00", "0x41c0e1b5"])], "wrong SWC"),
    ([_issue("106", ["0x41c0e1b5"])], "missing activate"),
    ([_issue("106", ["0x41c0e1b5", "0x0a11ce00"])], "not kill"),
])
def test_check_recall_rejects_other_exploits(issues, why):
    with pytest.raises(AssertionError, match=why):
        check_recall(issues)


@pytest.mark.parametrize("n", [1, 4, 10])
def test_wide_contract_branches_all_resolve_to_the_selfdestruct(n):
    code = wide_contract(n)
    assert code.endswith(bytes([0x33, 0xFF]))  # CALLER; SELFDESTRUCT
    cfg = StaticCFG(InstrTables(Disassembly(code).instruction_list))
    # one static JUMPI per branch, every one resolved, nothing dead
    assert cfg.n_resolved == n
    assert cfg.reachable_blocks().all()


def test_read_runtime_strips_prefix_and_newline(tmp_path):
    path = tmp_path / "kill.sol.o"
    path.write_text("0x33ff\n")
    assert read_runtime(path) == bytes([0x33, 0xFF])
