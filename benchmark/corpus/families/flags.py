"""bench.py ``_wide_contract`` behind a one-function dispatcher: k
independent one-bit calldata guards that reconverge (2^k paths, the
device frontier's shape), then CALLER;SELFDESTRUCT (SWC-106).
``params["k"]`` is the guard count; the seed moves the selector and the
calldata bytes and bits the guards test."""

from benchmark.corpus.asm import Asm, selector

PLANTED = ("106",)  # the headline exploit


def build(rng, index, params):
    k = int(params.get("k", 8))
    offsets = sorted(rng.sample(range(4, 36), k))
    a = Asm()
    a.dispatch([(selector(f"run{rng.randrange(10 ** 6)}(bytes32)"), "run")])
    a.revert()
    a.label("run")
    for i, off in enumerate(offsets):
        # PUSH1 off; CALLDATALOAD; PUSH1 bit; AND; PUSH2 g_i; JUMPI; JUMPDEST
        a.push(off).op("CALLDATALOAD").push(1 << rng.randrange(8)).op("AND")
        a.jumpi(f"g{i}")
        a.label(f"g{i}")
    a.op("CALLER").mark("kill").op("SELFDESTRUCT")
    return a.assemble(), [("106", a.marks["kill"])]
