"""bench_contracts ``bectoken_like``: BECToken's batchTransfer, whose
``amount = cnt * value`` is unchecked (SWC-101, CVE-2018-10299), among
SafeMath transfers, keccak-mapped balances and owner/paused modifiers.
The seed moves the storage slots, the batch bound (10-30), the order of
the dispatcher and the selectors of the owner-only functions."""

from benchmark.corpus.asm import Asm, selector

PLANTED = ("101",)  # the headline exploit

SEL_BALANCE_OF = selector("balanceOf(address)")
SEL_TRANSFER = selector("transfer(address,uint256)")
SEL_BATCH_TRANSFER = selector("batchTransfer(address[],uint256)")
SEL_APPROVE = selector("approve(address,uint256)")
SEL_TRANSFER_OWNERSHIP = selector("transferOwnership(address)")


def build(rng, index, params):
    s_owner, s_paused, s_bal, s_allowed = rng.sample(range(0, 8), 4)
    bound = rng.randint(10, 30)
    entries = [
        (SEL_TRANSFER, "transfer"), (SEL_BATCH_TRANSFER, "batch"),
        (SEL_BALANCE_OF, "balanceOf"), (SEL_APPROVE, "approve"),
        (SEL_TRANSFER_OWNERSHIP, "transferOwnership"),
        (selector(f"pause{rng.randrange(10 ** 6)}()"), "pause"),
        (selector(f"unpause{rng.randrange(10 ** 6)}()"), "unpause"),
    ]
    rng.shuffle(entries)
    a = Asm()

    def only_owner(tag):
        a.push(s_owner).op("SLOAD", "CALLER", "EQ")
        a.require(f"own_{tag}")

    def when_not_paused(tag):
        a.push(s_paused).op("SLOAD", "ISZERO")
        a.require(f"np_{tag}")

    a.dispatch(entries)
    a.revert()

    a.label("balanceOf")
    a.arg(0).mapping_slot(s_bal)
    a.op("SLOAD").push(0).op("MSTORE").push(32).push(0).op("RETURN")

    # transfer(to, value) [whenNotPaused, SafeMath]
    a.label("transfer")
    when_not_paused("transfer")
    a.arg(0).op("ISZERO", "ISZERO")
    a.require("t_to")
    a.op("CALLER").mapping_slot(s_bal)
    a.op("DUP1", "SLOAD")
    a.arg(1)
    a.op("DUP2", "DUP2", "GT", "ISZERO")
    a.require("t_bal")
    a.op("DUP2", "DUP2", "SWAP1", "SUB")
    a.op("DUP4", "SSTORE")
    a.arg(0).mapping_slot(s_bal)
    a.op("DUP1", "SLOAD")
    a.op("DUP3", "DUP2").mark("transfer_add").op("ADD")
    a.op("DUP1", "DUP3", "GT", "ISZERO")
    a.require("t_add")
    a.op("SWAP1", "POP", "SWAP1", "SSTORE")
    a.return_one()

    # batchTransfer(address[] receivers, uint256 value): THE BUG
    a.label("batch")
    when_not_paused("batch")
    a.push(4).op("CALLDATALOAD").push(4).mark("ptr_add").op("ADD")  # [ptr]
    a.op("DUP1", "CALLDATALOAD")  # [ptr, cnt]
    a.arg(1)  # [ptr, cnt, value]
    a.op("DUP2", "DUP2").mark("mul").op("MUL")  # amount = cnt * value, unchecked
    a.op("DUP3").push(0).op("LT")
    a.require("b_cnt0")
    a.push(bound).op("DUP4", "GT", "ISZERO")
    a.require("b_cnt")
    a.op("DUP2").push(0).op("LT")
    a.require("b_val")
    a.op("CALLER").mapping_slot(s_bal)
    a.op("DUP1", "SLOAD")
    a.op("DUP1", "DUP4", "GT", "ISZERO")
    a.require("b_bal")
    a.op("DUP3", "SWAP1", "SUB")
    a.op("SWAP1", "SSTORE")
    a.op("POP")
    a.push(0)
    a.label("b_loop")
    a.op("DUP1", "DUP4", "GT")
    a.op("ISZERO").jumpi("b_done")
    a.op("DUP1").push(32).op("MUL").push(32).op("ADD")
    a.op("DUP5").mark("elem_add").op("ADD", "CALLDATALOAD")  # ptr + 32 + 32*i
    a.mapping_slot(s_bal)
    a.op("DUP1", "SLOAD")
    a.op("DUP4", "DUP2").mark("loop_add").op("ADD")
    a.op("DUP1", "DUP3", "GT", "ISZERO")
    a.require("b_add")
    a.op("SWAP1", "POP", "SWAP1", "SSTORE")
    a.push(1).op("ADD")
    a.jump("b_loop")
    a.label("b_done")
    a.return_one()

    a.label("approve")
    when_not_paused("approve")
    a.arg(1).arg(0).mapping_slot(s_allowed)
    a.op("SSTORE")
    a.return_one()

    a.label("transferOwnership")
    only_owner("xfer")
    a.arg(0).push(s_owner).op("SSTORE")
    a.return_one()

    a.label("pause")
    only_owner("pause")
    a.push(1).push(s_paused).op("SSTORE")
    a.return_one()

    a.label("unpause")
    only_owner("unpause")
    a.push(0).push(s_paused).op("SSTORE")
    a.return_one()
    m = a.marks
    # Mythril's integer module also flags an add whose result feeds a
    # JUMPI (the SafeMath checks) or a calldata offset; found or not within
    # the budget, such a report is no fault
    possible = [("101", m[k]) for k in ("transfer_add", "ptr_add", "elem_add", "loop_add")]
    return a.assemble(), [("101", m["mul"])], possible
