"""Each contract family plants its SWC ids where it says, the same way for
the same seed, and stays in its shape and size bucket across seeds."""

import random

import pytest

from benchmark.corpus import build_variant

FAMILIES = ("token", "vault", "ponzi", "killable", "largecode", "flags")
# the opcodes each SWC id may sit on
OPCODE = {"101": {0x01, 0x02, 0x03}, "105": {0xF1}, "106": {0xFF},
          "107": {0xF1, 0x54, 0x55}, "116": {0x57}}
PLANTED = {"token": {"101"}, "vault": {"105", "107"}, "ponzi": {"105", "107"},
           "killable": {"106"}, "largecode": {"106"}, "flags": {"106"}}


def _variant(family, seed, index=0, params=None):
    return build_variant(family, random.Random(f"{seed}:{family}"), index, params)


@pytest.mark.parametrize("family", FAMILIES)
def test_family_plants_its_swc_ids(family):
    v = _variant(family, 7)
    assert set(v.planted) == PLANTED[family]
    assert PLANTED[family] <= {swc for swc, _ in v.expected}
    for swc, pc in v.expected + v.possible:
        assert v.code[pc] in OPCODE[swc], (family, swc, pc, hex(v.code[pc]))


@pytest.mark.parametrize("family", FAMILIES)
def test_family_is_deterministic_per_seed(family):
    assert _variant(family, 11) == _variant(family, 11)
    seeds = [_variant(family, s) for s in range(6)]
    assert len({v.code for v in seeds}) > 1  # the seed moves the contract
    # one shape: the same planted SWC ids on every seed
    assert len({tuple(sorted({s for s, _ in v.expected})) for v in seeds}) == 1


@pytest.mark.parametrize("family", FAMILIES)
def test_family_keeps_its_size_bucket(family):
    sizes = [len(_variant(family, s, params={"k": 8}).code) for s in range(8)]
    assert max(sizes) <= 1.2 * min(sizes)


def test_flags_guard_count_sets_the_paths():
    small, large = (_variant("flags", 3, params={"k": k}) for k in (7, 9))
    # one guard is 11 bytes: PUSH1 off CALLDATALOAD PUSH1 bit AND PUSH2 JUMPI JUMPDEST
    assert len(large.code) - len(small.code) == 2 * 11


def test_killable_alternates_gated_and_open():
    gated, open_ = _variant("killable", 5, 0), _variant("killable", 5, 1)
    assert 0x55 in gated.code and 0x55 not in open_.code  # SSTORE of the flag


def test_keccak_matches_known_selectors():
    from benchmark.corpus.asm import keccak256, selector

    assert keccak256(b"").hex().startswith("c5d24601")
    assert selector("transfer(address,uint256)") == 0xA9059CBB
