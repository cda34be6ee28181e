"""Differential tests: device frontier vs host engine on real analyses.

The host engine is the oracle (VERDICT.md round-1 item 1): the same contract
analyzed with ``args.frontier`` on and off must produce the same issues.
"""

import pytest

from mythril_tpu.analysis.security import fire_lasers, reset_callback_modules
from mythril_tpu.analysis.symbolic import SymExecWrapper
from mythril_tpu.support.support_args import args as global_args


def analyze(code_hex: str, tx_count=1, modules=None, frontier=False):
    reset_callback_modules()
    # the per-(address, bytecode) issue cache deliberately survives module
    # resets (reference base.py:70-95); differential runs re-analyze the
    # same bytecode, so clear it between runs
    from mythril_tpu.analysis.module.loader import ModuleLoader

    for m in ModuleLoader().get_detection_modules():
        if hasattr(m, "cache"):
            m.cache.clear()
    old = global_args.frontier
    old_force = global_args.frontier_force
    global_args.frontier = frontier
    # differential fixtures are deliberately tiny: bypass the a-priori
    # narrow gate so frontier=True really exercises the device
    global_args.frontier_force = frontier
    try:
        sym = SymExecWrapper(
            bytes.fromhex(code_hex),
            address=0x0901D12E,
            strategy="dfs",
            transaction_count=tx_count,
            execution_timeout=60,
            modules=modules,
        )
        return fire_lasers(sym, white_list=modules)
    finally:
        global_args.frontier = old
        global_args.frontier_force = old_force


def issue_keys(issues):
    return sorted(
        (i.swc_id, i.address, i.function, i.severity) for i in issues
    )


# dispatcher prelude: selector(kill()=0x41c0e1b5) -> JUMPDEST at 0x14=20
DISPATCH = "60003560e01c6341c0e1b5146014576000" + "6000fd" + "5b"


@pytest.mark.parametrize("frontier", [False, True])
def test_unprotected_selfdestruct(frontier):
    issues = analyze(
        DISPATCH + "33ff", modules=["AccidentallyKillable"], frontier=frontier
    )
    assert len(issues) == 1
    issue = issues[0]
    assert issue.swc_id == "106"
    assert issue.function == "kill()"
    step = issue.transaction_sequence["steps"][-1]
    assert step["input"].startswith("0x41c0e1b5")


def test_differential_selfdestruct_matches_host():
    host = analyze(DISPATCH + "33ff", modules=["AccidentallyKillable"])
    dev = analyze(
        DISPATCH + "33ff", modules=["AccidentallyKillable"], frontier=True
    )
    assert issue_keys(host) == issue_keys(dev)


def test_differential_clean_contract():
    code = "602a60005500"  # store 42 at slot 0, stop
    assert analyze(code, frontier=True) == []


def test_differential_exception_invalid():
    host = analyze(DISPATCH + "fe", modules=["Exceptions"])
    dev = analyze(DISPATCH + "fe", modules=["Exceptions"], frontier=True)
    assert issue_keys(host) == issue_keys(dev)
    assert len(dev) == 1
    assert dev[0].swc_id == "110"


def test_differential_tx_origin():
    body = "323314601b5700" "5b00"
    host = analyze(DISPATCH + body, modules=["TxOrigin"])
    dev = analyze(DISPATCH + body, modules=["TxOrigin"], frontier=True)
    assert issue_keys(host) == issue_keys(dev)
    assert len(dev) == 1
    assert dev[0].swc_id == "115"


def test_differential_integer_overflow():
    body = "600435" "6001" "01" "6000" "55" "00"
    host = analyze(DISPATCH + body, modules=["IntegerArithmetics"])
    dev = analyze(DISPATCH + body, modules=["IntegerArithmetics"], frontier=True)
    assert issue_keys(host) == issue_keys(dev)
    assert len(dev) >= 1
    assert dev[0].swc_id == "101"


def test_differential_timestamp():
    body = "426064" "11" "601c57" "00" "5b00"
    host = analyze(DISPATCH + body, modules=["PredictableVariables"])
    dev = analyze(
        DISPATCH + body, modules=["PredictableVariables"], frontier=True
    )
    assert issue_keys(host) == issue_keys(dev)


def test_multi_tx_killbilly_exploit():
    """2-tx storage-gated selfdestruct: tx reseeding + storage encode must
    chain through the device frontier."""
    from bench_contracts import check_recall, run_analysis

    old = global_args.frontier
    old_force = global_args.frontier_force
    global_args.frontier = True
    global_args.frontier_force = True
    try:
        _sym, issues, _wall = run_analysis("auto")
    finally:
        global_args.frontier = old
        global_args.frontier_force = old_force
    check_recall(issues)


def test_arena_const_value_full_width():
    """Regression: numpy int32 widths cannot shift 1 << 256 (C long)."""
    import numpy as np

    from mythril_tpu.frontier.arena import HostArena

    arena = HostArena(64)
    row = arena.const_row((1 << 256) - 1, 256)
    assert isinstance(arena.width[row], np.int32) or arena.width.dtype == np.int32
    assert arena.const_value(row) == (1 << 256) - 1


def test_mload_straddling_stored_word_parks():
    """Soundness regression: MLOAD at 16 over a word stored at 0 must not
    read zero on the device (exact-address miss); the path parks and the
    host engine computes the straddled composite, keeping the feasible
    selfdestruct branch alive."""
    # mstore(0, calldataload(0)); jumpi(0x22, mload(16)); stop; jumpdest caller selfdestruct
    body = "600035" "600052" "601051" "602257" "00" "5b33ff"
    host = analyze(DISPATCH + body, modules=["AccidentallyKillable"])
    dev = analyze(DISPATCH + body, modules=["AccidentallyKillable"], frontier=True)
    assert issue_keys(host) == issue_keys(dev)
    assert len(dev) == 1 and dev[0].swc_id == "106"


def test_sha3_straddling_stored_word_parks():
    """Same straddle hazard through the SHA3 word gather.

    The branch guard is ``sha3(16, 32) != keccak(0^32)``: a device that
    wrongly hashes the exact-miss zero word folds the guard to false and
    never reaches the selfdestruct, while the straddled composite (host,
    or a parked path) is satisfiable with nonzero calldata."""
    k0 = "290decd9548b62a8d60345a988386fc84ba6bc95484008f6362f93160ef3e563"
    # mstore(0, calldataload(0)); h = sha3(16, 32);
    # jumpi(0x47, iszero(eq(h, K0)) == 0 ? ... ) -> iszero(eq) as guard
    body = "600035" + "600052" + "6020601020" + "7f" + k0 + "14" + "15" + "604757" + "00" + "5b33ff"
    host = analyze(DISPATCH + body, modules=["AccidentallyKillable"])
    dev = analyze(DISPATCH + body, modules=["AccidentallyKillable"], frontier=True)
    assert issue_keys(host) == issue_keys(dev)
    assert len(dev) == 1 and dev[0].swc_id == "106"


def test_parked_call_body_falls_back_to_host():
    # CALL is not device-executable: the path parks and the host engine
    # finishes it; issues must match the pure-host run
    body = "6000" "6000" "6000" "6000" "6064" "33" "61ffff" "f1" "00"
    host = analyze(DISPATCH + body)
    dev = analyze(DISPATCH + body, frontier=True)
    assert issue_keys(host) == issue_keys(dev)


@pytest.mark.parametrize(
    "fixture,module,swc",
    [
        ("suicide.sol.o", "AccidentallyKillable", "106"),
        ("exceptions.sol.o", "Exceptions", "110"),
        ("origin.sol.o", "TxOrigin", "115"),
        ("ether_send.sol.o", "EtherThief", "105"),
    ],
)
def test_differential_corpus_contracts(fixture, module, swc):
    """Frontier-vs-host issue parity across distinct detectors on real solc
    output (the corpus sweep's recall contracts; solc code is MSTORE/JUMPI
    dense, exercising event-buffer pressure and fork-grant coupling)."""
    import pathlib

    path = pathlib.Path("/root/reference/tests/testdata/inputs") / fixture
    if not path.exists():
        pytest.skip("reference corpus not mounted")
    code = path.read_text().strip().replace("0x", "")
    host = analyze(code, tx_count=2, modules=[module])
    dev = analyze(code, tx_count=2, modules=[module], frontier=True)
    assert issue_keys(host) == issue_keys(dev)
    assert any(i.swc_id == swc for i in dev)


def test_verdict_memos_gate_device_entry():
    """Narrow-marked codes skip narrow drains but a wide seed set still
    goes (width comes from many seeds); SLOW-marked codes (throughput
    bail) are skipped even wide — re-draining just re-pays a proven
    loss."""
    from mythril_tpu.frontier import engine as E

    class _Code:
        def __init__(self, bytecode):
            self.bytecode = bytecode

    class _Env:
        def __init__(self, code):
            self.code = code

    class _GS:
        def __init__(self, code):
            self.environment = _Env(code)

    code = _Code(b"\x60\x00" * 40)
    eng = E.FrontierEngine.__new__(E.FrontierEngine)
    eng.caps = E.Caps(B=64)
    pairs = [(None, _GS(code))]
    wide = [(None, _GS(code)) for _ in range(eng.caps.MIN_LIVE)]
    key = E._code_key(code)
    old_force = E.args.frontier_force
    E.args.frontier_force = False
    try:
        E._NARROW_CODES.add(key)
        assert not eng._device_worthwhile(pairs)
        assert eng._device_worthwhile(wide)  # width bypasses NARROW
        E._NARROW_CODES.discard(key)
        E._SLOW_CODES.add(key)
        assert not eng._device_worthwhile(pairs)
        assert not eng._device_worthwhile(wide)  # SLOW outranks width
        # a mixed batch with an unmarked member still goes
        other = _Code(b"\x60\x01" * 40)
        assert eng._device_worthwhile(wide + [(None, _GS(other))])
    finally:
        E._NARROW_CODES.discard(key)
        E._SLOW_CODES.discard(key)
        E.args.frontier_force = old_force


def test_break_paths_return_queued_seeds_to_work_list():
    """Seeds queued beyond the batch width when a run ends on a break path
    (slow-bail/timeout/arena) must land back on their laser's work list —
    regression for silently vanished exploration states."""
    from mythril_tpu.frontier import engine as E
    from mythril_tpu.frontier.state import Caps

    # a tiny batch (B=2) with 5 eligible fresh seeds and an immediate
    # execution timeout: the loop breaks on the timeout path with seeds
    # still queued
    from mythril_tpu.analysis.symbolic import SymExecWrapper

    reset_callback_modules()
    old = (global_args.frontier, global_args.frontier_force,
           global_args.frontier_mesh)
    global_args.frontier = False
    global_args.frontier_force = True
    # single-device: mesh padding would widen B=2 up to the device count,
    # giving every seed a slot — nothing would queue and this fast contract
    # finishes before the break path this test exists to exercise
    global_args.frontier_mesh = False
    try:
        sym = SymExecWrapper(
            bytes.fromhex(DISPATCH + "33ff"),
            address=0x0901D12E,
            strategy="dfs",
            transaction_count=1,
            execution_timeout=60,
            modules=["AccidentallyKillable"],
            defer_exec=True,
        )
        laser = sym.laser
        from mythril_tpu.core.transaction.symbolic import seed_message_call

        laser.open_states = [sym.deferred_world_state]
        seed_message_call(laser, 0x0901D12E)
        seed = laser.work_list[0]
        import copy as _c

        laser.work_list.extend(_c.copy(seed) for _ in range(4))
        n_before = len(laser.work_list)
        assert n_before == 5

        engine = E.FrontierEngine(laser, Caps(B=2))
        laser.execution_timeout = 0  # loop hits the timeout break instantly
        engine.drain_work_list()
        # every seed must be back (order/form may differ: parked carriers)
        assert len(laser.work_list) == n_before, (
            f"{n_before - len(laser.work_list)} seeds vanished"
        )
    finally:
        (global_args.frontier, global_args.frontier_force,
         global_args.frontier_mesh) = old


def test_host_step_rate_requires_samples():
    """host_step_rate is None until the warmup sample count is reached,
    then reports steps/sec over the accumulated iteration wall."""
    from mythril_tpu.core import svm as svm_mod

    class _L:
        host_step_rate = svm_mod.LaserEVM.host_step_rate
        _host_steps = 0
        _host_step_secs = 0.0

    laser = _L()
    assert laser.host_step_rate() is None
    laser._host_steps = svm_mod._FRONTIER_WARMUP_STEPS
    laser._host_step_secs = float(svm_mod._FRONTIER_WARMUP_STEPS) / 500.0
    assert abs(laser.host_step_rate() - 500.0) < 1e-6
