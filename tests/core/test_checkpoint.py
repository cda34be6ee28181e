"""Checkpoint/resume: frontier snapshots round-trip through disk."""

import pytest

from mythril_tpu.smt import terms
from mythril_tpu.smt.serialize import dump_terms, load_terms


def test_term_roundtrip_restores_sharing():
    x = terms.var("ckx", 256)
    y = terms.var("cky", 256)
    shared = terms.add(x, y)
    roots = [
        terms.eq(shared, terms.const(7, 256)),
        terms.ult(shared, terms.keccak(x)),
        terms.extract(15, 8, y),
    ]
    data = dump_terms(roots)
    # force JSON round-trip (the on-disk representation)
    import json

    data = json.loads(json.dumps(data))
    back = load_terms(data)
    # interning means reloaded roots ARE the original terms
    assert all(a is b for a, b in zip(roots, back))


def test_nested_aux_roundtrip():
    # 'apply' aux is (name, (widths...), out_width): the nested tuple must
    # survive JSON or re-interning raises on the unhashable inner list
    x = terms.var("ckax", 8)
    y = terms.var("ckay", 8)
    f = terms.apply_func("ckf", 256, x, y)
    import json

    data = json.loads(json.dumps(dump_terms([f])))
    assert load_terms(data)[0] is f


def test_world_state_checkpoint_roundtrip(tmp_path):
    from mythril_tpu.core.state.account import Account
    from mythril_tpu.core.state.world_state import WorldState
    from mythril_tpu.frontend.disassembler import Disassembly
    from mythril_tpu.smt import symbol_factory
    from mythril_tpu.support.checkpoint import load_checkpoint, save_checkpoint

    ws = WorldState()
    acct = Account(0xAABB, code=Disassembly("6001600101"), nonce=3)
    ws.put_account(acct)
    acct.set_balance(10**18)
    key = symbol_factory.BitVecVal(5, 256)
    acct.storage[key] = symbol_factory.BitVecVal(42, 256)
    sym = symbol_factory.BitVecSym("slot", 256)
    acct.storage[sym] = symbol_factory.BitVecVal(9, 256)
    ws.constraints.append(
        symbol_factory.BitVecSym("z", 256) == symbol_factory.BitVecVal(1, 256)
    )

    path = str(tmp_path / "ckpt.json")
    save_checkpoint(path, completed_transactions=1, open_states=[ws])
    done, states, _addr = load_checkpoint(path)

    assert done == 1
    assert len(states) == 1
    restored = states[0]
    racct = restored.accounts[0xAABB]
    assert racct.nonce == 3
    assert racct.code.bytecode == bytes.fromhex("6001600101")
    # interning identity: the restored storage array IS the original term,
    # store chain included (reads behave exactly as before the snapshot)
    assert racct.storage._array.raw is acct.storage._array.raw
    assert racct.storage[sym].value == 9
    assert len(restored.constraints) == 1
    # balances array round-trips as the same interned term
    assert restored.balances.raw is ws.balances.raw


def test_resume_continues_analysis(tmp_path):
    """Interrupt after tx 1 of killbilly, resume, and still find the issue."""
    import time

    from mythril_tpu.analysis.security import fire_lasers, reset_callback_modules
    from mythril_tpu.analysis.symbolic import SymExecWrapper
    from mythril_tpu.frontend.evmcontract import EVMContract
    from mythril_tpu.analysis.module.loader import ModuleLoader

    from bench_contracts import KILLBILLY, KILLBILLY_CREATION

    for module in ModuleLoader().get_detection_modules():
        module.cache.clear()
    reset_callback_modules()

    ckpt = str(tmp_path / "frontier.json")
    contract = EVMContract(
        code=KILLBILLY, creation_code=KILLBILLY_CREATION, name="KB"
    )
    # phase 1: run only the first transaction, checkpointing the frontier
    sym = SymExecWrapper(
        contract,
        address=0x0901D12E,
        strategy="bfs",
        transaction_count=1,
        execution_timeout=120,
        modules=["AccidentallyKillable"],
        checkpoint_path=ckpt,
    )
    import os

    assert os.path.exists(ckpt)

    # phase 2: resume from the snapshot and run the remaining transaction
    for module in ModuleLoader().get_detection_modules():
        module.cache.clear()
    reset_callback_modules()
    sym2 = SymExecWrapper(
        contract,
        address=0x0901D12E,
        strategy="bfs",
        transaction_count=2,
        execution_timeout=120,
        modules=["AccidentallyKillable"],
        resume_from=ckpt,
    )
    issues = fire_lasers(sym2, white_list=["AccidentallyKillable"])
    assert issues and issues[0].swc_id == "106"
