"""Device known-bits interpreter must match host numpy bit for bit."""

import numpy as np
import pytest

from mythril_tpu import absdomain
from mythril_tpu.absdomain import device, domains, tape
from mythril_tpu.observability import get_registry
from mythril_tpu.smt import terms
from mythril_tpu.smt.terms import (
    add, band, bnot, const, eq, lnot, mul, ult, ule, var, zext,
)


def _rows():
    x = var("pfdev_x", 256)
    y = var("pfdev_y", 256)
    prod = mul(zext(x, 256), zext(y, 256))
    return [
        [ult(x, const(10, 256)), eq(x, const(20, 256))],
        [ule(x, const(1, 256)), lnot(ult(prod, const(1 << 256, 512)))],
        [eq(band(x, const(0xFF, 256)), const(0x42, 256)),
         ult(add(x, y), const(1 << 128, 256))],
    ]


@pytest.mark.slow
def test_device_matches_host_bit_for_bit():
    pack = tape.pack(_rows())
    h_km, h_kv, h_ref = domains.eval_kb_host(pack)
    device.warmup()
    assert device.interpreter_ready()
    d_km, d_kv, d_ref = device.run_kb(pack)
    np.testing.assert_array_equal(h_km, np.asarray(d_km))
    np.testing.assert_array_equal(h_kv, np.asarray(d_kv))
    np.testing.assert_array_equal(h_ref, np.asarray(d_ref))


@pytest.mark.slow
def test_device_verdicts_match_host():
    pack = tape.pack(_rows())
    lo, hi, iv_ref = domains.eval_iv_host(pack)
    h_km, h_kv, h_ref = domains.eval_kb_host(pack)
    device.warmup()
    d_km, d_kv, d_ref = device.run_kb(pack)
    v_host = domains.verdicts(pack, lo, hi, h_km, h_kv, iv_ref | h_ref)
    v_dev = domains.verdicts(pack, lo, hi, np.asarray(d_km),
                             np.asarray(d_kv), iv_ref | np.asarray(d_ref))
    np.testing.assert_array_equal(v_host, v_dev)
    assert v_host[0] and v_host[1]  # both contradictions still refute


# A chain of k additions onto x: 2k + 1 nodes, then the row's tail.
def _chain(name, k, tail):
    x = var(name, 256)
    acc = x
    for i in range(k):
        acc = add(acc, const(i + 7, 256))
    return tail(x, acc)


def _ult(x, acc):  # 2k + 3 nodes
    return [ult(acc, const(5, 256))]


def _bnot(x, acc):  # 2k + 4 nodes
    return [ult(bnot(acc), const(5, 256))]


def _contra(x, acc):  # 2k + 5 nodes; the pins on x and on the sum disagree
    return [eq(acc, const(1, 256)), eq(x, const(3, 256))]


#: one-row tapes on both sides of the 512-node bucket edge
_ONE_ROW = {511: (254, _ult), 512: (254, _bnot), 513: (255, _ult),
            525: (260, _contra)}


def _count(name):
    return get_registry().counter(name).value or 0


def _assert_parity(pack):
    """Kernel and host pass agree on km, kv, refuted and the verdicts."""
    h_km, h_kv, h_ref = domains.eval_kb_host(pack)
    d_km, d_kv, d_ref = device.run_kb(pack)
    np.testing.assert_array_equal(h_km, d_km)
    np.testing.assert_array_equal(h_kv, d_kv)
    np.testing.assert_array_equal(h_ref, d_ref)
    lo, hi, iv_ref = domains.eval_iv_host(pack)
    v_host = domains.verdicts(pack, lo, hi, h_km, h_kv, iv_ref | h_ref)
    v_dev = domains.verdicts(pack, lo, hi, d_km, d_kv, iv_ref | d_ref)
    np.testing.assert_array_equal(v_host, v_dev)
    return d_ref, v_dev


@pytest.mark.parametrize("nodes", sorted(_ONE_ROW))
def test_one_row_tape_walks_its_own_nodes(nodes):
    k, tail = _ONE_ROW[nodes]
    pack = tape.pack([_chain(f"pfwalk_{nodes}", k, tail)])
    assert pack.n_nodes == nodes
    steps, bucket_steps = _count("prefilter.kb_steps"), _count(
        "prefilter.kb_bucket_steps")
    refuted, verdict = _assert_parity(pack)
    assert _count("prefilter.kb_steps") == steps + nodes
    assert _count("prefilter.kb_bucket_steps") == bucket_steps + (
        512 if nodes <= 512 else 4096)
    assert bool(refuted[0]) == bool(verdict[0]) == (tail is _contra)


def test_grouped_batch_matches_host_group_by_group():
    # the rows share their constants, so the sums alone pass the budget
    rows = [_chain(f"pfgroup_{i}", 260 + i, _contra if i == 3 else _ult)
            for i in range(16)]
    groups = absdomain._row_groups(rows)
    assert len(groups) >= 2
    refuted = {}
    for group in groups:
        pack = tape.pack([rows[i] for i in group])
        assert 512 < pack.n_nodes <= tape.MAX_NODES
        d_ref, _ = _assert_parity(pack)
        refuted.update(zip(group, d_ref.tolist()))
    assert [i for i in sorted(refuted) if refuted[i]] == [3]


def test_node_counts_in_one_bucket_share_one_program(monkeypatch):
    """The trip count is traced: a static one compiles a program per size."""
    monkeypatch.setattr(device, "_jitted", None)
    for nodes in (513, 525):
        k, tail = _ONE_ROW[nodes]
        device.run_kb(tape.pack([_chain(f"pfcache_{nodes}", k, tail)]))
    assert device._get_jitted()._cache_size() == 1
