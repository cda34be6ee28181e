"""The readings that ``correct``'s limits are set from: the numbers a cell
compares, on many seeds in one process, for the program as configured and
for the control.

The control is a run of the cell with one guarantee of its configuration
broken, which has to come out as not correct.  The configurations state
"all detection modules" and "a complete issue set"; the control runs the
cell as the benchmark does with one detection module left out (``--drop``),
the step a later PR would take to save that module's callbacks and solver
queries.  The benchmark's own runs never run it; ``tests/benchmark`` keeps
it at a test's size, and on the chip it runs as

    python3 benchmark/readings.py --workload <cell> --seconds <s> --seeds <a,b,c> \
        --control-seeds <d,e,f> --drop <Module>

Every run after the first in the process skips the warm-up: the programs
are loaded already, and readings time nothing.
"""

import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import argparse  # noqa: E402
import json  # noqa: E402


def modules_without(name: str):
    """Every detection module of the program but ``name``."""
    from mythril_tpu.analysis.module.loader import ModuleLoader

    names = [type(m).__name__ for m in ModuleLoader().get_detection_modules()]
    if name not in names:
        raise KeyError(f"no detection module {name!r}: {names}")
    return [n for n in names if n != name]


def main(argv=None) -> int:
    from benchmark.harness import Refused, execute

    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", default="", help="comma-separated: the program as configured")
    ap.add_argument("--control-seeds", default="", help="comma-separated: the control")
    ap.add_argument("--drop", help="the control's detection module left out")
    ap.add_argument("--seconds", type=float, required=True)
    args = ap.parse_args(argv)
    plan = [(int(s), None) for s in args.seeds.split(",") if s]
    if args.control_seeds:
        plan += [(int(s), args.drop) for s in args.control_seeds.split(",")]
    for i, (seed, drop) in enumerate(plan):
        try:
            result = execute(args.workload, seed, args.seconds, False, time.time(),
                             modules=modules_without(drop) if drop else None,
                             warm=i == 0)
        except Refused as e:
            print(f"readings refused: {e}", file=sys.stderr)
            return 2
        print(json.dumps({"control": drop, "workload": args.workload, "seed": seed,
                          "correct": result["correct"], "attempted": result["attempted"],
                          "metrics": result["metrics"], "checks": result["checks"]}),
              flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
