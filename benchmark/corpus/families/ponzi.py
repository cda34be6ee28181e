"""bench_contracts ``rubixi_like``: Rubixi's renamed constructor
``dynamicPyramid()`` lets anyone become creator, then drain the fees
(SWC-105), with the fee reset after the external call (SWC-107 at the
call and at that write).  The fallback's unchecked fee sum can overflow
(SWC-101).  The
seed moves the storage slots, the fee divisor (5-20) and the order of
the dispatcher."""

from benchmark.corpus.asm import Asm, selector

PLANTED = ("105", "107")  # the headline exploit

SEL_DYNAMIC_PYRAMID = selector("dynamicPyramid()")
SEL_COLLECT_ALL = selector("collectAllFees()")


def build(rng, index, params):
    s_fees, s_creator = rng.sample(range(0, 8), 2)
    divisor = rng.randint(5, 20)
    entries = [(SEL_DYNAMIC_PYRAMID, "pyramid"), (SEL_COLLECT_ALL, "collect")]
    rng.shuffle(entries)
    a = Asm()
    a.dispatch(entries)
    # fallback: collectedFees += callvalue / divisor
    a.push(s_fees).op("SLOAD")
    a.push(divisor).op("CALLVALUE", "DIV").mark("fee_add").op("ADD")
    a.push(s_fees).op("SSTORE")
    a.op("STOP")

    a.label("pyramid")
    a.op("CALLER").push(s_creator).op("SSTORE", "STOP")

    a.label("collect")
    a.push(s_creator).op("SLOAD", "CALLER", "EQ")
    a.require("c_own")
    a.push(s_fees).op("SLOAD")
    a.op("DUP1").push(0).op("LT")
    a.require("c_pos")
    a.push(0).push(0).push(0).push(0)
    a.op("DUP5")
    a.push(s_creator).op("SLOAD")
    a.op("GAS").mark("call").op("CALL")
    a.require("c_ok")
    a.push(0).push(s_fees).mark("write_after_call").op("SSTORE")
    a.op("STOP")
    m = a.marks
    return a.assemble(), [
        ("101", m["fee_add"]), ("105", m["call"]), ("107", m["call"]),
        ("107", m["write_after_call"]),
    ]
