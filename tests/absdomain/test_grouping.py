"""A batch whose union tape is over the node budget is evaluated as a few
budget-sized groups, with the verdicts each row gets on its own."""

import pytest

from mythril_tpu import absdomain
from mythril_tpu.absdomain import device, tape
from mythril_tpu.native import bitblast
from mythril_tpu.observability import get_registry
from mythril_tpu.smt.terms import (
    apply_func, array_var, band, concat, concat2, const, eq, keccak, select,
    ult, var,
)

_CD = array_var("pfg_calldata", 256, 8)


@pytest.fixture(autouse=True)
def _fresh_memo():
    absdomain.reset_state()
    yield
    absdomain.reset_state()


def _word(offset, size=32):
    """A calldata word: ``size`` byte selects under a concat chain."""
    return concat(*(select(_CD, const(offset + j, 256)) for j in range(size)))


_PREFIX = [ult(_word(4), const(1000, 256))]


def _row(r):
    """The shared prefix plus a word of the row's own.  Every third row is
    refuted by its harvested ranges, every third by known bits alone."""
    w = _word(36 + 32 * r)
    if r % 3 == 0:
        own = [ult(w, const(10, 256)), eq(w, const(20, 256))]
    elif r % 3 == 1:
        own = [eq(band(w, const(1, 256)), const(1, 256)), eq(w, const(0, 256))]
    else:
        own = [ult(w, const(10, 256)), eq(w, const(r % 10, 256))]
    return _PREFIX + own


def _rows(n=48):
    return [_row(r) for r in range(n)]


def _alone(rows):
    """Each row's verdict from a batch of its own."""
    out = []
    for row in rows:
        out.append(absdomain.prefilter_batch([row])[0])
    absdomain.reset_state()
    return out


def _count(name):
    return get_registry().counter(name).value or 0


def _union_nodes(rows):
    union = list({c.tid: c for row in rows for c in row}.values())
    return len(bitblast.serialize(union, lazy_selects=True).records)


def test_batch_is_over_budget_while_each_row_fits():
    rows = _rows()
    assert _union_nodes(rows) > tape.MAX_NODES
    assert max(_union_nodes([row]) for row in rows) < tape.MAX_NODES


def test_grouped_verdicts_equal_row_by_row():
    rows = _rows()
    alone = _alone(rows)
    assert sum(alone) == 32  # two rows in three are refuted
    g0 = _count("prefilter.grouped_rows")
    assert absdomain.prefilter_batch(rows) == alone
    assert _count("prefilter.grouped_rows") == g0 + len(rows)


def test_grouped_verdicts_do_not_depend_on_row_order():
    rows = _rows()
    alone = _alone(rows)
    order = list(range(len(rows)))[::-1]
    assert absdomain.prefilter_batch([rows[i] for i in order]) == [alone[i] for i in order]


def test_poisoned_rows_keep_their_siblings_verdicts():
    # one row too wide for the limbs (found when its group packs), one the
    # serializer cannot express at all (found while grouping)
    a = var("pfg_wide", 512)
    wide = _PREFIX + [eq(concat2(a, a), const(0, 1024))]
    arrays = _PREFIX + [eq(_CD, array_var("pfg_other", 256, 8))]
    rows = _rows()
    alone = _alone(rows)
    batch = rows[:10] + [wide] + rows[10:30] + [arrays] + rows[30:]
    got = absdomain.prefilter_batch(batch)
    assert got[10] is False and got[31] is False
    assert got[:10] + got[11:31] + got[32:] == alone


def test_oversized_row_falls_through_alone():
    # refutable, but its own tape is over the budget
    big = [_word(4 + 32 * k) for k in range(48)]
    over = [ult(big[0], const(10, 256)), eq(big[0], const(20, 256))]
    over += [ult(w, const(1 << 200, 256)) for w in big[1:]]
    assert _union_nodes([over]) > tape.MAX_NODES
    rows = _rows(24)
    alone = _alone(rows)
    f0 = _count("prefilter.fallthrough")
    got = absdomain.prefilter_batch(rows[:12] + [over] + rows[12:])
    assert got[12] is False
    assert got[:12] + got[13:] == alone
    assert _count("prefilter.fallthrough") == f0 + 1


def test_oversized_batch_packs_fewer_tapes_than_rows():
    rows = _rows()
    p0 = _count("prefilter.packs")
    absdomain.prefilter_batch(rows)
    packs = _count("prefilter.packs") - p0
    assert 2 <= packs < len(rows) // 8


def test_rows_that_share_a_prefix_are_grouped_together():
    # two path prefixes of 20 words each, their rows interleaved: taken in
    # the given order every group would carry both prefixes
    prefixes = [[ult(_word(4 + 32 * (20 * p + k)), const(1 << 250, 256))
                 for k in range(20)] for p in range(2)]
    rows = [prefixes[r % 2] + [ult(_word(2000 + 32 * r), const(10, 256))]
            for r in range(40)]
    assert _union_nodes([prefixes[0] + prefixes[1]]) > tape.MAX_NODES // 2
    groups = absdomain._row_groups(rows)
    assert len(groups) == 2
    assert all(len({r % 2 for r in g}) == 1 for g in groups)


def test_groups_cover_every_row_once_within_budget_and_bucket():
    rows = [[ult(_word(4 + 8 * r, 8), const(10, 64))] for r in range(200)]
    assert _union_nodes(rows) > tape.MAX_NODES
    groups = absdomain._row_groups(rows)
    assert sorted(i for g in groups for i in g) == list(range(200))
    assert max(len(g) for g in groups) == device.ROW_BUCKETS[-1]
    assert all(_union_nodes([rows[i] for i in g]) <= tape.MAX_NODES for g in groups)


@pytest.mark.parametrize("shape", ["prefix", "over_a_bucket"])
def test_batch_that_fits_packs_once(shape):
    if shape == "prefix":
        rows = _rows(12)
    else:  # more rows than one dispatch holds: the tape is still one
        rows = [[ult(var("pfg_t%d" % r, 256), const(10, 256))] for r in range(100)]
    assert _union_nodes(rows) <= tape.MAX_NODES
    p0, g0 = _count("prefilter.packs"), _count("prefilter.grouped_rows")
    assert absdomain.prefilter_batch(rows) == _alone(rows)
    assert _count("prefilter.packs") == p0 + 1
    assert _count("prefilter.grouped_rows") == g0


@pytest.mark.parametrize("extra", ["none", "keccak", "apply"])
def test_union_size_counts_what_serialize_emits(extra):
    rows = _rows(6)
    if extra == "keccak":
        rows = [row + [eq(keccak(_word(36 + 32 * r)), var("pfg_h%d" % r, 256))]
                for r, row in enumerate(rows)]
    elif extra == "apply":
        rows = [row + [eq(apply_func("pfg_f", 256, _word(36 + 32 * r), const(r, 8)),
                          const(r, 256))]
                for r, row in enumerate(rows)]
    size = bitblast.UnionSize()
    for k, row in enumerate(rows, 1):
        assert size.add(row) == _union_nodes(rows[:k])


def test_union_size_stays_unsupported_after_a_bad_row():
    size = bitblast.UnionSize()
    size.add(_row(1))
    with pytest.raises(bitblast.Unsupported):
        size.add([eq(_CD, array_var("pfg_other2", 256, 8))])
    with pytest.raises(bitblast.Unsupported):
        size.add(_row(2))
