"""Device-only efficiency microbench (engine._run_microbench).

The chained-dispatch subtraction isolates pure segment compute from the
host<->device round trip, so the per-chip instructions/sec number is
measurable whatever the dispatch round trip.  Runs once per process on the
first productive
segment when args.frontier_microbench is set.
"""

from mythril_tpu.analysis.security import fire_lasers, reset_callback_modules
from mythril_tpu.analysis.symbolic import SymExecWrapper
from mythril_tpu.frontier.stats import FrontierStatistics
from mythril_tpu.support.support_args import args as global_args


def _wide_contract(n_branches: int) -> bytes:
    out = b""
    for k in range(n_branches):
        dest = len(out) + 10
        out += bytes([0x60, k, 0x35, 0x60, 0x01, 0x16,
                      0x61, (dest >> 8) & 0xFF, dest & 0xFF, 0x57, 0x5B])
    return out + bytes([0x33, 0xFF])


def test_microbench_records_device_compute():
    old = (
        global_args.frontier,
        global_args.frontier_force,
        global_args.frontier_width,
        global_args.frontier_mesh,
        global_args.frontier_microbench,
    )
    global_args.frontier = True
    global_args.frontier_force = True
    global_args.frontier_width = 64
    global_args.frontier_mesh = False  # single-device path (mesh skips it)
    global_args.frontier_microbench = True
    reset_callback_modules()
    FrontierStatistics().reset()
    try:
        sym = SymExecWrapper(
            _wide_contract(6),
            address=0x0901D12E,
            strategy="bfs",
            transaction_count=1,
            execution_timeout=120,
            modules=["AccidentallyKillable"],
        )
        issues = fire_lasers(sym, white_list=["AccidentallyKillable"])
        assert any(i.swc_id == "106" for i in issues)
        mb = FrontierStatistics().microbench
        assert mb, "microbench never recorded"
        assert mb["segment_compute_s"] > 0
        assert mb["instructions_per_s"] > 0
        assert mb["n_exec_per_segment"] > 0
        assert mb["bytes_pushed_per_segment"] > 0
        assert mb["width"] == 64
        # it must also surface through the stats dict (report meta channel)
        assert FrontierStatistics().as_dict()["microbench"] == mb
    finally:
        (
            global_args.frontier,
            global_args.frontier_force,
            global_args.frontier_width,
            global_args.frontier_mesh,
            global_args.frontier_microbench,
        ) = old