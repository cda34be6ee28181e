"""Module gating: GateView, module_relevant, gate_view_for_contract."""

import pytest

from bench_contracts import KILLBILLY, KILLBILLY_CREATION
from mythril_tpu.frontend.disassembler import Disassembly
from mythril_tpu.frontier import taint
from mythril_tpu.staticpass import (
    GateView,
    filter_modules,
    gate_view_for_contract,
    module_relevant,
    summarize,
)
from mythril_tpu.support.support_args import args


def _killbilly_view() -> GateView:
    code = bytes.fromhex(KILLBILLY)
    s = summarize(Disassembly(code).instruction_list, code_size=len(code))
    return GateView([s], contract_name="killbilly")


class _FakeModule:
    pre_hooks = []
    post_hooks = []

    def __init__(self, required=None, sources=None, sinks=frozenset()):
        self.static_required_ops = required
        self.static_taint_sources = sources or {}
        self.static_taint_sinks = sinks


def test_killbilly_gate_keeps_and_skips_the_right_modules():
    from mythril_tpu.analysis.module.base import EntryPoint
    from mythril_tpu.analysis.module.loader import ModuleLoader

    view = _killbilly_view()
    kept, skipped = filter_modules(
        ModuleLoader().get_detection_modules(EntryPoint.CALLBACK), view
    )
    kept_names = sorted(type(m).__name__ for m in kept)
    # killbilly has SSTORE/SLOAD/JUMPI/SELFDESTRUCT but no CALL family,
    # no arithmetic, no env-dependence sources
    assert "AccidentallyKillable" in kept_names
    assert "Exceptions" in kept_names  # REVERT occurs
    for name in ("TxOrigin", "EtherThief", "IntegerArithmetics",
                 "ArbitraryDelegateCall", "MultipleSends"):
        assert name in view.skipped_modules


def test_occurrence_gate():
    view = _killbilly_view()
    assert module_relevant(_FakeModule(required=frozenset({"SSTORE"})), view)
    assert not module_relevant(_FakeModule(required=frozenset({"CREATE2"})), view)
    # None disables the gate: custom modules are never skipped
    assert module_relevant(_FakeModule(required=None), view)


def test_taint_gate_requires_source_reaching_sink():
    # ORIGIN; PUSH1 6; JUMPI; STOP; INVALID; JUMPDEST(6); STOP
    code = bytes.fromhex("32600657" + "00" + "fe" + "5b00")
    s = summarize(Disassembly(code).instruction_list, code_size=len(code))
    view = GateView([s])
    hits = _FakeModule(
        required=frozenset({"ORIGIN"}),
        sources={"ORIGIN": taint.TAINT_ORIGIN},
        sinks=frozenset({"JUMPI"}),
    )
    assert module_relevant(hits, view)
    # same declaration but the source opcode never occurs
    misses = _FakeModule(
        required=frozenset({"TIMESTAMP"}),
        sources={"TIMESTAMP": taint.TAINT_TIMESTAMP},
        sinks=frozenset({"JUMPI"}),
    )
    assert not module_relevant(misses, view)


def test_filter_modules_without_view_is_identity():
    mods = [_FakeModule()]
    kept, skipped = filter_modules(mods, None)
    assert kept == mods and skipped == []


@pytest.fixture
def _staticpass_enabled():
    prev = args.staticpass
    args.staticpass = True
    yield
    args.staticpass = prev


def test_gate_view_none_when_disabled(_staticpass_enabled):
    args.staticpass = False
    assert gate_view_for_contract(bytes.fromhex(KILLBILLY)) is None


def test_gate_view_none_on_resume(_staticpass_enabled):
    assert (
        gate_view_for_contract(
            bytes.fromhex(KILLBILLY), resume_from="/tmp/ckpt"
        )
        is None
    )


def test_gate_view_none_with_active_dynloader(_staticpass_enabled):
    class _Dyn:
        active = True

    assert (
        gate_view_for_contract(bytes.fromhex(KILLBILLY), dynloader=_Dyn())
        is None
    )


def test_gate_view_none_for_creation_only_contract(_staticpass_enabled):
    from mythril_tpu.frontend.evmcontract import EVMContract

    contract = EVMContract(creation_code=KILLBILLY_CREATION, name="KB")
    assert gate_view_for_contract(contract) is None


def test_gate_view_for_raw_runtime_bytes(_staticpass_enabled):
    view = gate_view_for_contract(bytes.fromhex(KILLBILLY))
    assert view is not None
    assert "SELFDESTRUCT" in view.reachable_opcodes
