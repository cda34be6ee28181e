"""The one generator: the same work for every seed, in another order."""

import collections
import threading
import time

import pytest

from benchmark.loadgen import Generator, block, load_traffic

#: the open-loop mix ISSUE.md asks of the scan cells (none is in
#: BENCHMARK.json yet: PERF.md, Open questions)
ZIPF = {"loop": "open", "rate_per_s": 0.4,
        "families": {"token": 30, "vault": 20, "ponzi": 15, "killable": 20,
                     "largecode": 10, "flags": 5},
        "params": {"flags": {"k": 7}},
        "pool": 256, "popularity": {"law": "zipf", "s": 1.0}, "tier": "batch"}
#: a closed loop that cycles sizes
SIZES = {"loop": "closed", "clients": 1, "families": {"flags": 1},
         "params": {"flags": {"k": [7, 8, 9]}}, "pool": 0}


def _gaps(times):
    return sorted(round(y - x, 9) for x, y in zip(times, times[1:]))


def test_open_loop_arrivals_are_one_set_in_a_seeded_order():
    a = Generator(ZIPF, 2 ** 31 + 5).arrivals(51)
    b = Generator(ZIPF, 17).arrivals(51)
    assert len(a) == len(b) == round(ZIPF["rate_per_s"] * 51)
    assert a != b
    assert a[0] == b[0] == 0.0
    assert all(x < 51 for x in a)
    # the same exponential quantiles, permuted (less the one that closes each)
    assert abs(sum(_gaps(a)) - sum(_gaps(b))) < 10.0


def test_families_keep_their_shares_in_every_block():
    fams = block(ZIPF)
    counts = collections.Counter(fams)
    assert len(fams) == 20
    assert counts == {"token": 6, "vault": 4, "ponzi": 3, "killable": 4,
                      "largecode": 2, "flags": 1}
    stream = Generator(dict(ZIPF, pool=0), 9).stream()  # distinct, closed-loop order
    first = collections.Counter(next(stream).family for _ in range(40))
    assert first == {k: 2 * v for k, v in counts.items()}


def test_zipf_pool_has_the_same_popularity_for_every_seed():
    def shape(seed):
        reqs = Generator(ZIPF, seed).popular(120)
        by_code = collections.Counter(v.code for v in reqs)
        return (sorted(by_code.values(), reverse=True),
                collections.Counter(v.family for v in reqs))

    (pop_a, fam_a), (pop_b, fam_b) = shape(1), shape(2 ** 32 + 3)
    assert pop_a == pop_b and fam_a == fam_b
    assert pop_a[0] > 10  # rank 1 dominates under Zipf(1)


def test_closed_loop_sends_one_order_of_sizes_for_every_seed():
    def sizes(traffic, seed):
        stream = Generator(traffic, seed).stream()
        return [len(next(stream).code) for _ in range(6)]

    assert sizes(SIZES, 1) == sizes(SIZES, 2 ** 40 + 123)  # k = 7, 8, 9, 7, 8, 9
    assert sizes(SIZES, 1)[:3] == sorted(sizes(SIZES, 1)[:3])
    wide = sizes(load_traffic("wide"), 2 ** 31 + 9)
    assert wide == [wide[0]] * 6  # k = 7 only


def test_warmup_covers_every_family_at_its_largest_apart_from_the_window():
    g = Generator(ZIPF, 4)
    warm = g.warmup()
    assert [v.family for v in warm] == list(ZIPF["families"])
    assert not {v.code for v in g.pool()} & {v.code for v in warm}
    cycling = Generator(SIZES, 4)
    (flags,) = cycling.warmup()
    assert len(flags.code) == max(len(cycling.variant("flags", i, i).code) for i in range(3))
    wide = Generator(load_traffic("wide"), 4)
    (flags,) = wide.warmup()
    first = next(wide.stream())
    assert len(flags.code) == len(first.code) and flags.code != first.code


@pytest.mark.parametrize("seeds", [(1, 2 ** 31 + 9), (2905551112, 2 ** 40 + 7)])
def test_a_draw_seed_gives_every_run_the_same_contracts(seeds):
    wide = load_traffic("wide")
    drawn = {s: Generator(wide, s) for s in seeds}
    firsts = {next(g.stream()).code for g in drawn.values()}
    warms = {g.warmup()[0].code for g in drawn.values()}
    assert len(firsts) == len(warms) == 1
    # the contracts that seed 2905551111 drew without it (my chip runs, PR 22, c9)
    plain = Generator({k: v for k, v in wide.items() if k != "draw_seed"}, wide["draw_seed"])
    assert firsts == {next(plain.stream()).code}
    assert warms == {plain.warmup()[0].code}
    # the seed still orders what a mix lets it order
    a, b = (Generator(ZIPF | {"draw_seed": 5}, s) for s in seeds)
    assert {v.code for v in a.pool()} == {v.code for v in b.pool()}
    assert [v.code for v in a.pool()] != [v.code for v in b.pool()]


def test_open_loop_records_its_lateness():
    from benchmark.entries.serve import ServeEntry

    class Win:
        def open(self):
            self.t0 = time.time()

        def close(self):
            self.closed = True

    entry = ServeEntry({"options": []}, dict(ZIPF, rate_per_s=20.0))
    seen = []
    lock = threading.Lock()

    def submit(req):
        with lock:
            seen.append(req.rid)

    entry.submit = submit
    reqs = entry._open_loop(Generator(entry.traffic, 3), 0.5, Win())
    assert len(reqs) == len(seen) == 10
    assert len(entry.lateness) == 10 and all(x >= 0 for x in entry.lateness)
    assert all(r.due is not None for r in reqs)
