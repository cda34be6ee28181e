"""bench.py ``_largecode_contract``: kill() jumps past a long straight-line
tail of PUSH1/POP pads to CALLER;SELFDESTRUCT (SWC-106), so the code
outgrows the smallest size bucket and the kill is a cold-page jump.  The
seed moves the selector and the pad count (1400-1600 pairs, one bucket)."""

from benchmark.corpus.asm import Asm, selector

PLANTED = ("106",)  # the headline exploit


def build(rng, index, params):
    lo, hi = params.get("pads", (1400, 1600))
    n_pad = rng.randint(lo, hi)
    a = Asm()
    a.dispatch([(selector(f"kill{rng.randrange(10 ** 6)}()"), "kill")])
    a.op("STOP")
    for _ in range(n_pad):
        a.push(0).op("POP")
    a.op("STOP")
    a.label("kill").op("CALLER").mark("kill").op("SELFDESTRUCT")
    return a.assemble(), [("106", a.marks["kill"])]
