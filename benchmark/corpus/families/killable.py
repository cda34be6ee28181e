"""KillBilly (bench.py ``KILLBILLY``): an unprotected SELFDESTRUCT, SWC-106.

Even variants gate the kill behind an ``activate()`` that sets a storage
flag, as KILLBILLY does; odd variants kill unguarded.  (A runtime-only
analysis starts from symbolic storage, so both are found within ``-t 2``.)  The seed
moves the selectors, the flag's slot and value."""

from benchmark.corpus.asm import Asm, selector

PLANTED = ("106",)  # the headline exploit

NAMES = ("activate", "arm", "enable", "open", "unlock", "start")
KILLS = ("kill", "destroy", "close", "shutdown", "terminate", "retire")


def build(rng, index, params):
    gated = index % 2 == 0
    slot, flag = rng.randrange(0, 16), rng.randrange(1, 256)
    sel_on = selector(f"{rng.choice(NAMES)}{rng.randrange(1000)}()")
    sel_kill = selector(f"{rng.choice(KILLS)}{rng.randrange(1000)}()")
    a = Asm()
    a.dispatch([(sel_on, "on"), (sel_kill, "kill")] if gated else [(sel_kill, "kill")])
    a.revert()
    if gated:
        a.label("on").push(flag).push(slot).op("SSTORE", "STOP")
    a.label("kill")
    if gated:
        a.push(slot).op("SLOAD").push(flag).op("EQ")
        a.require("armed")
    a.op("CALLER").mark("kill").op("SELFDESTRUCT")
    return a.assemble(), [("106", a.marks["kill"])]
