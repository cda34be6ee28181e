"""bench_contracts ``etherstore_like``: EtherStore's withdrawFunds sends
value to msg.sender BEFORE it lowers the balance (SWC-107 at the call and
at the state read after it), and anyone can withdraw (SWC-105).  Its
unchecked ``+=`` on deposit and ``last + period`` can overflow (SWC-101),
and the waiting period hangs on the block timestamp (SWC-116).  The seed moves the storage slots, the waiting
period (1-4 weeks) and the order of the dispatcher."""

from benchmark.corpus.asm import Asm, selector

PLANTED = ("107", "105")  # the headline exploit

SEL_DEPOSIT = selector("depositFunds()")
SEL_WITHDRAW = selector("withdrawFunds(uint256)")


def build(rng, index, params):
    s_limit, s_last, s_bal = rng.sample(range(0, 8), 3)
    period = 604800 * rng.randint(1, 4)
    entries = [(SEL_DEPOSIT, "deposit"), (SEL_WITHDRAW, "withdraw")]
    rng.shuffle(entries)
    a = Asm()
    a.dispatch(entries)
    a.revert()

    a.label("deposit")
    a.op("CALLER").mapping_slot(s_bal)
    a.op("DUP1", "SLOAD")
    a.op("CALLVALUE").mark("dep_add").op("ADD")  # unchecked +=
    a.op("SWAP1", "SSTORE")
    a.op("STOP")

    a.label("withdraw")
    a.arg(0)  # [amt]
    a.op("CALLER").mapping_slot(s_bal)  # [amt, slot_b]
    a.op("DUP1", "SLOAD")
    a.op("DUP3", "GT", "ISZERO")
    a.require("w_bal")
    a.push(s_limit).op("SLOAD")
    a.op("DUP3", "GT", "ISZERO")
    a.require("w_lim")
    a.op("CALLER").mapping_slot(s_last)
    a.op("SLOAD")
    a.push(period).mark("period_add").op("ADD")
    a.op("TIMESTAMP", "LT", "ISZERO").mark("time_guard")
    a.require("w_time")  # JUMPI on the timestamp: PUSH2 dest (3 bytes), JUMPI
    a.push(0).push(0).push(0).push(0)
    a.op("DUP6")
    a.op("CALLER", "GAS").mark("call").op("CALL")
    a.require("w_ok")
    a.op("DUP1").mark("read_after_call").op("SLOAD")
    a.op("DUP3", "SWAP1", "SUB")
    a.op("DUP2", "SSTORE")
    a.op("TIMESTAMP", "CALLER").mapping_slot(s_last)
    a.op("SSTORE")
    a.op("STOP")
    m = a.marks
    return a.assemble(), [
        ("101", m["dep_add"]), ("101", m["period_add"]),
        ("105", m["call"]), ("107", m["call"]),
        ("107", m["read_after_call"]), ("116", m["time_guard"] + 3),
    ]
