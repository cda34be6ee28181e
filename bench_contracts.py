"""Test and smoke contracts, and the helpers that analyze them.

The first part holds the small hand-assembled fixtures the tests, the chip
smoke and the graft entry share (killbilly, the wide fork contract, the
suicide and gated kill shapes) together with the analysis and recall
helpers they drive them through.  The rest assembles the real-shaped
contracts: BECToken's batchTransfer, EtherStore and Rubixi.

BECToken batchTransfer: the image carries no solc and the reference mount ships no compiled
BECToken, so the wide "real-shaped" workload is assembled here instruction
by instruction, mirroring the structures solc 0.4 emits for
``/root/reference/solidity_examples/BECToken.sol``:

  * a selector dispatcher over seven public functions,
  * keccak-addressed mapping storage (``balances[addr]`` at
    ``keccak(addr . slot)`` — MSTOREs + SHA3 over scratch memory, exactly
    solc's layout),
  * SafeMath-checked add/sub on every balance move (BECToken.sol:20-30),
  * owner/paused modifiers (``onlyOwner``/``whenNotPaused``,
    BECToken.sol:176-231),
  * and THE bug: ``batchTransfer`` computes ``amount = cnt * _value``
    UNCHECKED (BECToken.sol:257-259, SWC-101 / CVE-2018-10299) before a
    ``cnt``-bounded loop of checked per-receiver credits reading
    ``_receivers[i]`` straight from calldata.

Width comes from where it comes from in real audits: the dispatcher forks
per function, every require forks, the batch loop forks per iteration on
the symbolic ``cnt``, and multi-tx analysis crosses all of it.
"""

from __future__ import annotations

import time
from pathlib import Path
from typing import Dict, List, Tuple

from mythril_tpu.support.support_utils import keccak256

# ---------------------------------------------------------------------------
# killbilly: a storage-gated selfdestruct that needs a 2-tx chain
# ---------------------------------------------------------------------------

# activate() selector 0x0a11ce00 -> 0x1e, kill() selector 0x41c0e1b5 -> 0x25
DISPATCH = (
    "6000" "35" "60e0" "1c" "80"
    "630a11ce00" "14" "601e" "57"
    "6341c0e1b5" "14" "6025" "57"
    "60006000fd"
)
ACTIVATE = "5b600160005500"  # 0x1e: JUMPDEST; SSTORE(0, 1); STOP
KILL = "5b" "600054" "6001" "14" "6034" "57" "60006000fd" "5b" "33" "ff"
KILLBILLY = DISPATCH + ACTIVATE + KILL
_L = f"{len(KILLBILLY) // 2:02x}"
KILLBILLY_CREATION = f"60{_L}600c60003960{_L}6000f3" + KILLBILLY

# kill() selector -> CALLER; SELFDESTRUCT, anything else reverts
SUICIDE = bytes.fromhex("60003560e01c6341c0e1b51460145760006000fd5b33ff")
# x = calldataload(0); require(x < 10); x == 5 -> selfdestruct (feasible),
# x == 20 -> selfdestruct (infeasible: the branch condition contradicts the
# range pin, the contradiction the abstract pre-filter refutes)
GATED = bytes.fromhex(
    "60003580600a9010600c57005b80600514601c5780601414601c57005b33ff"
)


def wide_contract(n_branches: int) -> bytes:
    """n independent symbolic branches that immediately reconverge (2^n
    surviving paths) followed by an unprotected SELFDESTRUCT — the
    frontier-width shape the batched device interpreter is built for."""
    out = b""
    for k in range(n_branches):
        # PUSH1 k; CALLDATALOAD; PUSH1 1; AND; PUSH2 dest; JUMPI; JUMPDEST
        dest = len(out) + 10
        out += bytes([0x60, k, 0x35, 0x60, 0x01, 0x16,
                      0x61, (dest >> 8) & 0xFF, dest & 0xFF, 0x57, 0x5B])
    return out + bytes([0x33, 0xFF])  # CALLER; SELFDESTRUCT


def killbilly_contract():
    """killbilly with its creation code, as an analysis target."""
    from mythril_tpu.frontend.evmcontract import EVMContract

    return EVMContract(
        code=KILLBILLY, creation_code=KILLBILLY_CREATION, name="KillBilly"
    )


def corpus_dir() -> Path:
    """Directory of compiled corpus contracts (``*.sol.o`` runtime hex).
    The corpus is not vendored: callers skip what is absent."""
    return Path(__file__).parent / "tests" / "integration" / "inputs"


def read_runtime(path: Path) -> bytes:
    return bytes.fromhex(path.read_text().strip().replace("0x", ""))


# ---------------------------------------------------------------------------
# analysis and recall helpers
# ---------------------------------------------------------------------------


def clear_caches() -> None:
    """Make the next analysis cold: detector issue lists and memos, the
    model caches and the in-process query cache (a configured disk store
    stays attached)."""
    from mythril_tpu.analysis.module.loader import ModuleLoader
    from mythril_tpu.analysis.security import reset_callback_modules
    from mythril_tpu.querycache import reset_query_cache
    from mythril_tpu.smt.solver import clear_model_cache
    from mythril_tpu.support.model import _get_model_cached

    reset_callback_modules()
    clear_model_cache()
    _get_model_cached.cache_clear()
    reset_query_cache()
    for module in ModuleLoader().get_detection_modules():
        module.cache.clear()


def analyze(contract, address, tx_count, modules=None, strategy="bfs",
            timeout=60):
    """One full analysis: symbolic execution, then the detection modules."""
    from mythril_tpu.analysis.security import fire_lasers
    from mythril_tpu.analysis.symbolic import SymExecWrapper

    sym = SymExecWrapper(
        contract,
        address=address,
        strategy=strategy,
        transaction_count=tx_count,
        execution_timeout=timeout,
        modules=modules,
    )
    issues = fire_lasers(sym, white_list=modules)
    return sym, issues


def _selects(input_hex: str, selector: int) -> bool:
    """Does this calldata dispatch to ``selector``?  EVM CALLDATALOAD
    zero-pads past calldatasize, so exact minimization may shave trailing
    zero bytes off the selector itself (0x0a11ce00 -> 3-byte calldata)."""
    data = bytes.fromhex(input_hex[2:] if input_hex.startswith("0x") else input_hex)
    padded = (data + b"\x00" * 4)[:4]
    return int.from_bytes(padded, "big") == selector


def check_recall(issues) -> None:
    """killbilly recall: SWC-106 with activate() then kill()."""
    assert issues, "exploit not found: zero issues"
    issue = issues[0]
    assert issue.swc_id == "106", f"wrong SWC id {issue.swc_id}"
    steps = issue.transaction_sequence["steps"]
    inputs = [s["input"] for s in steps]
    assert any(_selects(i, 0x0A11CE00) for i in inputs), "missing activate() tx"
    assert _selects(inputs[-1], 0x41C0E1B5), "final tx is not kill()"


def run_analysis(probe_backend: str):
    """The killbilly analysis (3 transactions, AccidentallyKillable) under
    one probe backend, from cold caches.  Returns (sym, issues, wall_s)."""
    from mythril_tpu.support.support_args import args as global_args

    global_args.probe_backend = probe_backend
    clear_caches()
    t0 = time.time()
    sym, issues = analyze(
        killbilly_contract(), 0x0901D12E, 3, modules=["AccidentallyKillable"],
        timeout=300,
    )
    return sym, issues, time.time() - t0


# ---------------------------------------------------------------------------
# BECToken batchTransfer
# ---------------------------------------------------------------------------


def selector(signature: str) -> int:
    return int.from_bytes(keccak256(signature.encode())[:4], "big")


class Asm:
    """Minimal EVM assembler: opcodes, minimal-width PUSH, label fixups."""

    _OPS = {
        "STOP": 0x00, "ADD": 0x01, "MUL": 0x02, "SUB": 0x03, "DIV": 0x04,
        "LT": 0x10, "GT": 0x11, "EQ": 0x14, "ISZERO": 0x15, "AND": 0x16,
        "SHL": 0x1B, "SHR": 0x1C, "SHA3": 0x20, "ADDRESS": 0x30,
        "CALLER": 0x33, "CALLVALUE": 0x34, "CALLDATALOAD": 0x35,
        "CALLDATASIZE": 0x36, "POP": 0x50, "MLOAD": 0x51, "MSTORE": 0x52,
        "SLOAD": 0x54, "SSTORE": 0x55, "JUMP": 0x56, "JUMPI": 0x57,
        "JUMPDEST": 0x5B, "GAS": 0x5A, "CALL": 0xF1, "RETURN": 0xF3,
        "SELFDESTRUCT": 0xFF, "REVERT": 0xFD, "TIMESTAMP": 0x42,
        "STATICCALL": 0xFA, "ORIGIN": 0x32,
    }

    def __init__(self):
        self.out = bytearray()
        self.labels: Dict[str, int] = {}
        self.fixups: List[Tuple[int, str]] = []

    def op(self, *names: str) -> "Asm":
        for name in names:
            if name.startswith("DUP"):
                self.out.append(0x80 + int(name[3:]) - 1)
            elif name.startswith("SWAP"):
                self.out.append(0x90 + int(name[4:]) - 1)
            else:
                self.out.append(self._OPS[name])
        return self

    def push(self, value: int) -> "Asm":
        data = value.to_bytes(max(1, (value.bit_length() + 7) // 8), "big")
        self.out.append(0x60 + len(data) - 1)
        self.out.extend(data)
        return self

    def push_label(self, name: str) -> "Asm":
        self.out.append(0x61)  # PUSH2
        self.fixups.append((len(self.out), name))
        self.out.extend(b"\x00\x00")
        return self

    def label(self, name: str) -> "Asm":
        assert name not in self.labels, name
        self.labels[name] = len(self.out)
        return self.op("JUMPDEST")

    def jump(self, name: str) -> "Asm":
        return self.push_label(name).op("JUMP")

    def jumpi(self, name: str) -> "Asm":
        return self.push_label(name).op("JUMPI")

    def revert(self) -> "Asm":
        return self.push(0).push(0).op("REVERT")

    def assemble(self) -> bytes:
        for pos, name in self.fixups:
            self.out[pos: pos + 2] = self.labels[name].to_bytes(2, "big")
        return bytes(self.out)


# storage layout (solc order for BECToken's inheritance chain)
SLOT_OWNER = 0
SLOT_PAUSED = 1
SLOT_BALANCES = 2  # mapping(address => uint256)
SLOT_ALLOWED = 3  # approval mapping (flattened to one level here)

SEL_BALANCE_OF = selector("balanceOf(address)")
SEL_TRANSFER = selector("transfer(address,uint256)")
SEL_BATCH_TRANSFER = selector("batchTransfer(address[],uint256)")
SEL_APPROVE = selector("approve(address,uint256)")
SEL_TRANSFER_OWNERSHIP = selector("transferOwnership(address)")
SEL_PAUSE = selector("pause()")
SEL_UNPAUSE = selector("unpause()")


def _mapping_slot(a: Asm, slot: int) -> None:
    """key (on stack) -> storage slot keccak(key . slot), solc's layout:
    MSTORE(0, key); MSTORE(32, slot); SHA3(0, 64)."""
    a.push(0).op("MSTORE")
    a.push(slot).push(32).op("MSTORE")
    a.push(64).push(0).op("SHA3")


def _arg(a: Asm, index: int) -> None:
    """Push calldata argument ``index`` (head slot at 4 + 32*index)."""
    a.push(4 + 32 * index).op("CALLDATALOAD")


def _require(a: Asm, ok_label: str) -> None:
    """Branch on the condition on stack; fall-through reverts."""
    a.jumpi(ok_label)
    a.revert()
    a.label(ok_label)


def _only_owner(a: Asm, tag: str) -> None:
    a.push(SLOT_OWNER).op("SLOAD", "CALLER", "EQ")
    _require(a, f"own_{tag}")


def _when_not_paused(a: Asm, tag: str) -> None:
    a.push(SLOT_PAUSED).op("SLOAD", "ISZERO")
    _require(a, f"np_{tag}")


def _return_one(a: Asm) -> None:
    a.push(1).push(0).op("MSTORE").push(32).push(0).op("RETURN")


def bectoken_like() -> bytes:
    """Assemble the BECToken-shaped runtime (see module docstring)."""
    a = Asm()

    # ---- dispatcher: selector = shr(224, calldataload(0)) ----
    a.push(0).op("CALLDATALOAD").push(0xE0).op("SHR")
    for sel, lbl in (
        (SEL_TRANSFER, "transfer"),
        (SEL_BATCH_TRANSFER, "batch"),
        (SEL_BALANCE_OF, "balanceOf"),
        (SEL_APPROVE, "approve"),
        (SEL_TRANSFER_OWNERSHIP, "transferOwnership"),
        (SEL_PAUSE, "pause"),
        (SEL_UNPAUSE, "unpause"),
    ):
        a.op("DUP1").push(sel).op("EQ").jumpi(lbl)
    a.revert()

    # ---- balanceOf(address) ----
    a.label("balanceOf")
    _arg(a, 0)
    _mapping_slot(a, SLOT_BALANCES)
    a.op("SLOAD").push(0).op("MSTORE").push(32).push(0).op("RETURN")

    # ---- transfer(address to, uint256 value) [whenNotPaused, SafeMath] ----
    a.label("transfer")
    _when_not_paused(a, "transfer")
    # require(to != 0)
    _arg(a, 0)
    a.op("ISZERO", "ISZERO")
    _require(a, "t_to")
    # bal = balances[caller]; require(value <= bal)  (SafeMath sub)
    a.op("CALLER")
    _mapping_slot(a, SLOT_BALANCES)
    a.op("DUP1", "SLOAD")  # [slot_c, bal]
    _arg(a, 1)  # [slot_c, bal, value]
    a.op("DUP2", "DUP2", "GT", "ISZERO")  # value <= bal
    _require(a, "t_bal")
    # balances[caller] = bal - value
    a.op("DUP2", "DUP2", "SWAP1", "SUB")  # [slot_c, bal, value, bal-value]
    a.op("DUP4", "SSTORE")  # [slot_c, bal, value]
    # rb = balances[to]; c = rb + value; require(c >= rb) (SafeMath add)
    _arg(a, 0)
    _mapping_slot(a, SLOT_BALANCES)  # [slot_c, bal, value, slot_to]
    a.op("DUP1", "SLOAD")  # [.., slot_to, rb]
    a.op("DUP3", "DUP2", "ADD")  # [.., slot_to, rb, rb+value]
    a.op("DUP1", "DUP3", "GT", "ISZERO")  # rb <= rb+value
    _require(a, "t_add")
    a.op("SWAP1", "POP", "SWAP1", "SSTORE")  # balances[to] = c
    _return_one(a)

    # ---- batchTransfer(address[] receivers, uint256 value) ----
    # THE BUG (BECToken.sol:255-268): amount = cnt * value, UNCHECKED.
    # TRUE solc dynamic-array layout: the first head word holds the byte
    # OFFSET of the array data region, so the length is read through one
    # level of calldata indirection — ``cnt = calldataload(4 +
    # calldataload(4))`` — and element i at ``ptr + 32 + 32*i``.  This is
    # the CVE-2018-10299 shape as solc emits it (resolved by the solver's
    # dynamic select hints / CDCL Ackermann congruence; ROADMAP.md item 1).
    a.label("batch")
    _when_not_paused(a, "batch")
    # ptr = 4 + calldataload(4)   (array data region)
    a.push(4).op("CALLDATALOAD")
    a.push(4).op("ADD")  # [ptr]
    # cnt = calldataload(ptr)     (array length, via indirection)
    a.op("DUP1", "CALLDATALOAD")  # [ptr, cnt]
    _arg(a, 1)  # [ptr, cnt, value]
    # amount = cnt * value   <-- unchecked multiply, SWC-101
    # (stack indices below are all relative to the top; ptr stays parked
    # at the bottom of the frame until the loop body needs it)
    a.op("DUP2", "DUP2", "MUL")  # [ptr, cnt, value, amount]
    # require(cnt > 0 && cnt <= 20)
    a.op("DUP3")
    a.push(0).op("LT")  # 0 < cnt
    _require(a, "b_cnt0")
    a.push(20).op("DUP4", "GT", "ISZERO")  # cnt <= 20
    _require(a, "b_cnt20")
    # require(value > 0)
    a.op("DUP2")
    a.push(0).op("LT")
    _require(a, "b_val")
    # require(balances[caller] >= amount)
    a.op("CALLER")
    _mapping_slot(a, SLOT_BALANCES)  # [ptr, cnt, value, amount, slot_c]
    a.op("DUP1", "SLOAD")  # [ptr, cnt, value, amount, slot_c, bal]
    a.op("DUP1", "DUP4", "GT", "ISZERO")  # not(amount > bal)
    _require(a, "b_bal")
    # balances[caller] = bal - amount
    a.op("DUP3", "SWAP1", "SUB")  # [ptr, cnt, value, amount, slot_c, bal-amount]
    a.op("SWAP1", "SSTORE")  # [ptr, cnt, value, amount]
    a.op("POP")  # [ptr, cnt, value]
    # for (i = 0; i < cnt; i++) balances[receivers[i]] += value (checked)
    a.push(0)  # [ptr, cnt, value, i]
    a.label("b_loop")
    a.op("DUP1", "DUP4", "GT")  # cnt > i
    a.op("ISZERO").jumpi("b_done")
    # receiver = calldataload(ptr + 32 + 32*i)  (element i of the array)
    a.op("DUP1")
    a.push(32).op("MUL")
    a.push(32).op("ADD")  # [ptr, cnt, value, i, 32+32*i]
    a.op("DUP5", "ADD", "CALLDATALOAD")  # [ptr, cnt, value, i, receiver]
    _mapping_slot(a, SLOT_BALANCES)  # [ptr, cnt, value, i, slot_r]
    a.op("DUP1", "SLOAD")  # [ptr, cnt, value, i, slot_r, rb]
    a.op("DUP4", "DUP2", "ADD")  # [.., slot_r, rb, rb+value]
    a.op("DUP1", "DUP3", "GT", "ISZERO")  # rb <= rb+value (SafeMath add)
    _require(a, "b_add")
    a.op("SWAP1", "POP", "SWAP1", "SSTORE")  # balances[receiver] = sum
    a.push(1).op("ADD")  # i++
    a.jump("b_loop")
    a.label("b_done")
    _return_one(a)

    # ---- approve(address spender, uint256 value) ----
    a.label("approve")
    _when_not_paused(a, "approve")
    _arg(a, 1)  # value
    _arg(a, 0)  # spender
    _mapping_slot(a, SLOT_ALLOWED)
    a.op("SSTORE")
    _return_one(a)

    # ---- transferOwnership(address) [onlyOwner] ----
    a.label("transferOwnership")
    _only_owner(a, "xfer")
    _arg(a, 0)
    a.push(SLOT_OWNER).op("SSTORE")
    _return_one(a)

    # ---- pause() / unpause() [onlyOwner] ----
    a.label("pause")
    _only_owner(a, "pause")
    a.push(1).push(SLOT_PAUSED).op("SSTORE")
    _return_one(a)

    a.label("unpause")
    _only_owner(a, "unpause")
    a.push(0).push(SLOT_PAUSED).op("SSTORE")
    _return_one(a)

    return a.assemble()


# ---------------------------------------------------------------------------
# EtherStore: the canonical reentrancy shape
# (/root/reference/solidity_examples/etherstore.sol, SWC-107)
# ---------------------------------------------------------------------------

ES_SLOT_LIMIT = 0  # withdrawalLimit
ES_SLOT_LASTTIME = 1  # mapping(address => uint256) lastWithdrawTime
ES_SLOT_BALANCES = 2  # mapping(address => uint256) balances

SEL_DEPOSIT = selector("depositFunds()")
SEL_WITHDRAW = selector("withdrawFunds(uint256)")


def etherstore_like() -> bytes:
    """EtherStore's withdrawFunds: three requires, then an external CALL to
    ``msg.sender`` carrying value BEFORE the balance decrement — the
    textbook reentrancy window (etherstore.sol:14-24).  Detected as
    SWC-107 (external call to user-supplied address / state change after
    call)."""
    a = Asm()
    a.push(0).op("CALLDATALOAD").push(0xE0).op("SHR")
    for sel, lbl in ((SEL_DEPOSIT, "deposit"), (SEL_WITHDRAW, "withdraw")):
        a.op("DUP1").push(sel).op("EQ").jumpi(lbl)
    a.revert()

    # ---- depositFunds(): balances[caller] += callvalue ----
    a.label("deposit")
    a.op("CALLER")
    _mapping_slot(a, ES_SLOT_BALANCES)  # [slot_b]
    a.op("DUP1", "SLOAD")  # [slot_b, bal]
    a.op("CALLVALUE", "ADD")  # [slot_b, bal+value]  (0.5.0: unchecked +=)
    a.op("SWAP1", "SSTORE")
    a.op("STOP")

    # ---- withdrawFunds(uint256 amt) ----
    a.label("withdraw")
    _arg(a, 0)  # [amt]
    # require(balances[caller] >= amt)
    a.op("CALLER")
    _mapping_slot(a, ES_SLOT_BALANCES)  # [amt, slot_b]
    a.op("DUP1", "SLOAD")  # [amt, slot_b, bal]
    a.op("DUP3", "GT", "ISZERO")  # not(amt > bal)
    _require(a, "w_bal")  # [amt, slot_b]
    # require(amt <= withdrawalLimit)
    a.push(ES_SLOT_LIMIT).op("SLOAD")  # [amt, slot_b, limit]
    a.op("DUP3", "GT", "ISZERO")  # not(amt > limit)
    _require(a, "w_lim")  # [amt, slot_b]
    # require(now >= lastWithdrawTime[caller] + 1 weeks)
    a.op("CALLER")
    _mapping_slot(a, ES_SLOT_LASTTIME)
    a.op("SLOAD")  # [amt, slot_b, last]
    a.push(604800).op("ADD")  # [amt, slot_b, last+1w]
    a.op("TIMESTAMP", "LT", "ISZERO")  # not(now < last+1w)
    _require(a, "w_time")  # [amt, slot_b]
    # caller.call.value(amt)("") — the reentrancy window
    a.push(0).push(0).push(0).push(0)  # out_sz out_off in_sz in_off
    a.op("DUP6")  # value = amt
    a.op("CALLER", "GAS", "CALL")  # [amt, slot_b, success]
    _require(a, "w_ok")  # [amt, slot_b]
    # balances[caller] -= amt   (STATE CHANGE AFTER THE CALL)
    a.op("DUP1", "SLOAD")  # [amt, slot_b, bal]
    a.op("DUP3", "SWAP1", "SUB")  # [amt, slot_b, bal-amt]
    a.op("DUP2", "SSTORE")  # [amt, slot_b]
    # lastWithdrawTime[caller] = now
    a.op("TIMESTAMP", "CALLER")  # [amt, slot_b, ts, caller]
    _mapping_slot(a, ES_SLOT_LASTTIME)  # [amt, slot_b, ts, slot_t]
    a.op("SSTORE")
    a.op("STOP")
    return a.assemble()


# ---------------------------------------------------------------------------
# Rubixi: the constructor-name ownership takeover
# (/root/reference/solidity_examples/rubixi.sol, SWC-105 via dynamicPyramid)
# ---------------------------------------------------------------------------

RX_SLOT_FEES = 1  # collectedFees
RX_SLOT_CREATOR = 5  # creator

SEL_DYNAMIC_PYRAMID = selector("dynamicPyramid()")
SEL_COLLECT_ALL = selector("collectAllFees()")


def rubixi_like() -> bytes:
    """Rubixi's famous bug: ``dynamicPyramid()`` was the constructor name
    of an earlier revision, left public and unguarded (rubixi.sol:29-31) —
    anyone calls it to become ``creator`` and then drains fees through
    ``collectAllFees`` (rubixi.sol:36-40).  Detected as SWC-105
    (unprotected ether withdrawal: 2-tx takeover then drain)."""
    a = Asm()
    a.push(0).op("CALLDATALOAD").push(0xE0).op("SHR")
    for sel, lbl in (
        (SEL_DYNAMIC_PYRAMID, "pyramid"),
        (SEL_COLLECT_ALL, "collect"),
    ):
        a.op("DUP1").push(sel).op("EQ").jumpi(lbl)
    # fallback: init() — collectedFees += callvalue / 10
    a.push(RX_SLOT_FEES).op("SLOAD")  # [fees]
    a.push(10).op("CALLVALUE", "DIV", "ADD")  # [fees + value/10]
    a.push(RX_SLOT_FEES).op("SSTORE")
    a.op("STOP")

    # ---- dynamicPyramid(): creator = msg.sender  (NO GUARD — the bug) ----
    a.label("pyramid")
    a.op("CALLER")
    a.push(RX_SLOT_CREATOR).op("SSTORE")
    a.op("STOP")

    # ---- collectAllFees() [onlyowner]: creator.transfer(collectedFees) ----
    a.label("collect")
    a.push(RX_SLOT_CREATOR).op("SLOAD", "CALLER", "EQ")
    _require(a, "c_own")
    a.push(RX_SLOT_FEES).op("SLOAD")  # [fees]
    a.op("DUP1")
    a.push(0).op("LT")  # 0 < fees
    _require(a, "c_pos")  # [fees]
    a.push(0).push(0).push(0).push(0)
    a.op("DUP5")  # value = fees
    a.push(RX_SLOT_CREATOR).op("SLOAD")  # to = creator
    a.op("GAS", "CALL")  # [fees, success]
    _require(a, "c_ok")
    a.push(0).push(RX_SLOT_FEES).op("SSTORE")  # collectedFees = 0
    a.op("STOP")
    return a.assemble()
