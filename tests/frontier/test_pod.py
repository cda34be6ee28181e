"""Pod-scale sharding tests: slot<->device ownership math, the sync-point
rebalance planner, and the sharded-pipelined vs single-device-pipelined
end-to-end parity (the conftest pins an 8-device virtual CPU mesh)."""

import numpy as np
import pytest

from mythril_tpu.frontier.pipeline import (
    CorrectionLedger,
    choose_free_slot,
    plan_rebalance,
)
from mythril_tpu.parallel.mesh import (
    pad_batch,
    shard_size,
    shard_slots,
    slot_shard,
)
from mythril_tpu.support.support_args import args as global_args


# ---------------------------------------------------------------------------
# slot <-> device ownership math
# ---------------------------------------------------------------------------


def test_pad_batch_rounds_up_to_device_multiple():
    assert pad_batch(64, 8) == 64
    assert pad_batch(65, 8) == 72
    assert pad_batch(1, 8) == 8
    assert pad_batch(7, 1) == 7  # single shard: no padding
    assert pad_batch(0, 8) == 0


def test_shard_size_requires_even_split():
    assert shard_size(64, 8) == 8
    with pytest.raises(AssertionError):
        shard_size(65, 8)


def test_slot_shard_contiguous_blocks():
    # 16 slots over 4 shards: [0..3]->0, [4..7]->1, ...
    assert [slot_shard(s, 16, 4) for s in range(16)] == [
        0, 0, 0, 0, 1, 1, 1, 1, 2, 2, 2, 2, 3, 3, 3, 3
    ]
    np.testing.assert_array_equal(
        shard_slots(16, 4), np.repeat(np.arange(4), 4)
    )


def test_shard_slots_matches_slot_shard():
    B, n = 64, 8
    vec = shard_slots(B, n)
    for s in range(B):
        assert vec[s] == slot_shard(s, B, n)


# ---------------------------------------------------------------------------
# rebalance planner
# ---------------------------------------------------------------------------


def _masks(live_slots, free_slots, B):
    live = np.zeros(B, bool)
    free = np.zeros(B, bool)
    live[list(live_slots)] = True
    free[list(free_slots)] = True
    return live, free


def test_plan_rebalance_spills_hot_shard_to_idle():
    # shard 0 holds 4 live paths, shard 1 is idle with free slots
    live, free = _masks(range(4), range(4, 8), 8)
    moves = plan_rebalance(live, free, 2)
    # youngest (highest-slot) live paths spill first; stops when balanced
    assert moves == [3, 2]


def test_plan_rebalance_balanced_is_noop():
    live, free = _masks([0, 1, 4, 5], [2, 3, 6, 7], 8)
    assert plan_rebalance(live, free, 2) == []


def test_plan_rebalance_no_free_receivers_is_noop():
    # hot shard exists but nobody can receive: all other slots occupied
    live, free = _masks(range(8), [], 8)
    assert plan_rebalance(live, free, 2) == []


def test_plan_rebalance_one_off_imbalance_is_noop():
    # difference of 1 is not worth a sync point
    live, free = _masks([0, 1, 4], [5, 6, 7], 8)
    assert plan_rebalance(live, free, 2) == []


def test_plan_rebalance_respects_max_moves():
    live, free = _masks(range(8), range(8, 16), 16)
    moves = plan_rebalance(live, free, 2, max_moves=2)
    assert moves == [7, 6]


def test_plan_rebalance_single_shard_is_noop():
    live, free = _masks(range(4), range(4, 8), 8)
    assert plan_rebalance(live, free, 1) == []


def test_plan_rebalance_indivisible_batch_is_noop():
    live, free = _masks(range(3), range(3, 7), 7)
    assert plan_rebalance(live, free, 2) == []


def test_choose_free_slot_prefers_idle_shard():
    # shard 0 loaded, shard 1 idle: injection goes to shard 1's first free
    live, free = _masks([0, 1, 2], [3, 4, 5, 6, 7], 8)
    assert choose_free_slot(free, live, 2) == 4


def test_choose_free_slot_single_shard_is_first_free():
    # the pre-pod scan: first free slot regardless of load
    live, free = _masks([0, 1, 2], [3, 4, 5, 6, 7], 8)
    assert choose_free_slot(free, live, 1) == 3


def test_choose_free_slot_no_free_returns_none():
    live, free = _masks(range(8), [], 8)
    assert choose_free_slot(free, live, 2) is None


def test_choose_free_slot_skips_full_idle_shard():
    # shard 1 has fewest live paths but no reclaimable slot (all device-
    # owned); fall through to the next-coolest shard with a free slot
    live, free = _masks([0], [1, 2, 3], 8)
    assert choose_free_slot(free, live, 2) == 1


# ---------------------------------------------------------------------------
# ledger exactly-once under spill + re-inject
# ---------------------------------------------------------------------------


def test_ledger_exactly_once_spill_reinject():
    """A rebalance spill (touch src) + re-injection (touch dst) ride the
    NEXT dispatch exactly once: the first consume carries both slots, the
    second consume is empty."""
    ledger = CorrectionLedger(8)
    host_seed = np.full(8, -1, np.int64)
    host_seed[[0, 1, 2, 3]] = 1  # live paths on shard 0

    ledger.consume_all()  # dispatch 0: full push
    # rebalance at a sync point: spill slot 3 (freed), re-inject into 4
    ledger.touch(3)
    host_seed[3] = -1
    ledger.touch(4)
    host_seed[4] = 1

    mask = ledger.consume(host_seed)
    assert mask[3] and mask[4]
    assert mask.sum() == 2
    # the freed spill source becomes device-owned (fork grants may land)
    assert ledger.device_owned[3]
    assert not ledger.device_owned[4]
    # exactly-once: nothing pends for the next dispatch
    assert ledger.consume(host_seed).sum() == 0

    # pull of dispatch 0: both touched slots are newer than that output,
    # so the host view is carried forward (no stale device overwrite)
    assert set(ledger.on_pull().tolist()) == {3, 4}
    # pull of dispatch 1 (the one that consumed the mask): device is
    # authoritative again, nothing carries
    assert ledger.on_pull().size == 0

    ledger.release_owned()
    assert not ledger.device_owned.any()


# ---------------------------------------------------------------------------
# end-to-end parity: sharded-pipelined vs single-device-pipelined
# ---------------------------------------------------------------------------


def _analyze(code: bytes, tx_count: int, modules, mesh: bool):
    from mythril_tpu.analysis.module.loader import ModuleLoader
    from mythril_tpu.analysis.security import (
        fire_lasers,
        reset_callback_modules,
    )
    from mythril_tpu.analysis.symbolic import SymExecWrapper

    reset_callback_modules()
    for m in ModuleLoader().get_detection_modules():
        if hasattr(m, "cache"):
            m.cache.clear()
    prev = (global_args.frontier, global_args.frontier_force,
            global_args.frontier_mesh, global_args.pipeline)
    global_args.frontier = True
    global_args.frontier_force = True
    global_args.frontier_mesh = mesh
    global_args.pipeline = True
    try:
        sym = SymExecWrapper(
            code,
            address=0x0901D12E,
            strategy="dfs",
            transaction_count=tx_count,
            execution_timeout=120,
            modules=modules,
        )
        return fire_lasers(sym, white_list=modules)
    finally:
        (global_args.frontier, global_args.frontier_force,
         global_args.frontier_mesh, global_args.pipeline) = prev


def _issue_keys(issues):
    return sorted((i.swc_id, i.address, i.function) for i in issues)


@pytest.mark.slow
def test_pod_parity_multi_tx_storage_gate():
    """Sharded-pipelined vs single-device-pipelined on the storage-gated
    selfdestruct (2-tx chain): bit-identical issue sets, and the sharded
    run really ran path-sharded AND pipelined (the composition this PR
    exists for)."""
    import jax

    from mythril_tpu.frontier.stats import FrontierStatistics
    from mythril_tpu.observability.metrics import get_registry
    from tests.frontier.test_frontier_engine import DISPATCH

    n_dev = jax.device_count()
    assert n_dev == 8, "conftest should pin 8 virtual CPU devices"

    guarded = DISPATCH + "600054600114601b5733ff5b00"
    code = bytes.fromhex(guarded)

    get_registry().reset(prefix="pipeline.")
    fstats = FrontierStatistics()
    fstats.mesh_devices = 0
    sharded = _analyze(code, 2, ["AccidentallyKillable"], mesh=True)
    snap = get_registry().snapshot(prefix="pipeline.")
    mesh_devices = fstats.mesh_devices

    single = _analyze(code, 2, ["AccidentallyKillable"], mesh=False)

    assert _issue_keys(sharded) == _issue_keys(single)
    assert len(sharded) == 1
    assert mesh_devices == n_dev, (
        f"sharded run was not path-sharded: mesh_devices={mesh_devices}"
    )
    assert snap.get("pipeline.segments_pipelined", 0) > 0, (
        f"sharded run never chained a dispatch: {snap}"
    )
    assert snap.get("pipeline.mesh_shards", 0) == n_dev


@pytest.mark.parametrize("name", ["suicide", "killbilly"])
def test_mesh_and_pipeline_hatches_keep_issue_set(name, monkeypatch):
    """All four --no-mesh x --no-pipeline combinations find the same
    issues; pipelined runs chain segments and unpipelined runs do not; mesh
    runs shard over every device and the sharded-pipelined run attributes
    delta-pull bytes to every shard."""
    import jax

    from tests.parity import exploit, parity_run

    n_dev = jax.device_count()
    assert n_dev == 8, "conftest should pin 8 virtual CPU devices"
    target, txs = exploit(name)
    runs = {
        (mesh, piped): parity_run(
            monkeypatch, target, txs, ["AccidentallyKillable"],
            ("pipeline.", "frontier."), frontier_mesh=mesh, pipeline=piped)
        for mesh in (True, False) for piped in (True, False)
    }
    ref, _ = runs[(False, True)]
    assert any(swc == "106" for swc, _ in ref), ref
    for (mesh, piped), (issues, snap) in runs.items():
        assert issues == ref, (mesh, piped)
        chained = snap["pipeline.segments_pipelined"]
        assert (chained > 0) if piped else (chained == 0), (mesh, piped, snap)
        assert snap["frontier.mesh_devices"] == (n_dev if mesh else 0), (
            mesh, piped)
    pod = runs[(True, True)][1]
    assert pod["pipeline.delta_pulls"] > 0, pod
    by_shard = pod["pipeline.delta_pull_bytes_by_shard"]
    assert len(by_shard) == n_dev and all(v > 0 for v in by_shard.values()), (
        by_shard)
