"""EVM assembler and selector hashing for the seeded contract families.

A copy of ``bench_contracts.Asm`` (plus ``mark``, which records the pc of
the next instruction so a family can name where its planted issue sits)
and of a plain keccak-256, so the corpus imports nothing of the program.
"""

from __future__ import annotations

from typing import Dict, List, Tuple

_MASK64 = (1 << 64) - 1
_ROT = [
    [0, 36, 3, 41, 18],
    [1, 44, 10, 45, 2],
    [62, 6, 43, 15, 61],
    [28, 55, 25, 21, 56],
    [27, 20, 39, 8, 14],
]
_RC = [
    0x0000000000000001, 0x0000000000008082, 0x800000000000808A, 0x8000000080008000,
    0x000000000000808B, 0x0000000080000001, 0x8000000080008081, 0x8000000000008009,
    0x000000000000008A, 0x0000000000000088, 0x0000000080008009, 0x000000008000000A,
    0x000000008000808B, 0x800000000000008B, 0x8000000000008089, 0x8000000000008003,
    0x8000000000008002, 0x8000000000000080, 0x000000000000800A, 0x800000008000000A,
    0x8000000080008081, 0x8000000000008080, 0x0000000080000001, 0x8000000080008008,
]


def _rol(x: int, n: int) -> int:
    n %= 64
    return ((x << n) | (x >> (64 - n))) & _MASK64


def _keccak_f1600(a):
    a = list(a)
    for rnd in range(24):
        c = [a[x] ^ a[x + 5] ^ a[x + 10] ^ a[x + 15] ^ a[x + 20] for x in range(5)]
        d = [c[(x - 1) % 5] ^ _rol(c[(x + 1) % 5], 1) for x in range(5)]
        for i in range(25):
            a[i] ^= d[i % 5]
        b = [0] * 25
        for x in range(5):
            for y in range(5):
                b[y + 5 * ((2 * x + 3 * y) % 5)] = _rol(a[x + 5 * y], _ROT[x][y])
        for x in range(5):
            for y in range(5):
                a[x + 5 * y] = b[x + 5 * y] ^ (
                    (~b[(x + 1) % 5 + 5 * y]) & b[(x + 2) % 5 + 5 * y] & _MASK64)
        a[0] ^= _RC[rnd]
    return a


def keccak256(data: bytes) -> bytes:
    """Ethereum's keccak-256 (rate 136 bytes, domain byte 0x01)."""
    rate = 136
    padded = bytearray(data)
    pad_len = rate - (len(padded) % rate)
    padded += b"\x81" if pad_len == 1 else b"\x01" + b"\x00" * (pad_len - 2) + b"\x80"
    lanes = [0] * 25
    for start in range(0, len(padded), rate):
        block = padded[start:start + rate]
        for i in range(rate // 8):
            lanes[i] ^= int.from_bytes(block[8 * i:8 * i + 8], "little")
        lanes = _keccak_f1600(lanes)
    return b"".join(lanes[i].to_bytes(8, "little") for i in range(4))


def selector(signature: str) -> int:
    return int.from_bytes(keccak256(signature.encode())[:4], "big")


class Asm:
    """Minimal EVM assembler: opcodes, minimal-width PUSH, label fixups."""

    _OPS = {
        "STOP": 0x00, "ADD": 0x01, "MUL": 0x02, "SUB": 0x03, "DIV": 0x04,
        "LT": 0x10, "GT": 0x11, "EQ": 0x14, "ISZERO": 0x15, "AND": 0x16,
        "SHL": 0x1B, "SHR": 0x1C, "SHA3": 0x20, "ADDRESS": 0x30,
        "CALLER": 0x33, "CALLVALUE": 0x34, "CALLDATALOAD": 0x35,
        "CALLDATASIZE": 0x36, "POP": 0x50, "MLOAD": 0x51, "MSTORE": 0x52,
        "SLOAD": 0x54, "SSTORE": 0x55, "JUMP": 0x56, "JUMPI": 0x57,
        "JUMPDEST": 0x5B, "GAS": 0x5A, "CALL": 0xF1, "RETURN": 0xF3,
        "SELFDESTRUCT": 0xFF, "REVERT": 0xFD, "TIMESTAMP": 0x42,
        "STATICCALL": 0xFA, "ORIGIN": 0x32,
    }

    def __init__(self):
        self.out = bytearray()
        self.labels: Dict[str, int] = {}
        self.marks: Dict[str, int] = {}
        self.fixups: List[Tuple[int, str]] = []

    def op(self, *names: str) -> "Asm":
        for name in names:
            if name.startswith("DUP"):
                self.out.append(0x80 + int(name[3:]) - 1)
            elif name.startswith("SWAP"):
                self.out.append(0x90 + int(name[4:]) - 1)
            else:
                self.out.append(self._OPS[name])
        return self

    def push(self, value: int) -> "Asm":
        data = value.to_bytes(max(1, (value.bit_length() + 7) // 8), "big")
        self.out.append(0x60 + len(data) - 1)
        self.out.extend(data)
        return self

    def push_label(self, name: str) -> "Asm":
        self.out.append(0x61)  # PUSH2
        self.fixups.append((len(self.out), name))
        self.out.extend(b"\x00\x00")
        return self

    def label(self, name: str) -> "Asm":
        assert name not in self.labels, name
        self.labels[name] = len(self.out)
        return self.op("JUMPDEST")

    def mark(self, name: str) -> "Asm":
        """Record the pc of the next instruction under ``name``."""
        self.marks[name] = len(self.out)
        return self

    def jump(self, name: str) -> "Asm":
        return self.push_label(name).op("JUMP")

    def jumpi(self, name: str) -> "Asm":
        return self.push_label(name).op("JUMPI")

    def revert(self) -> "Asm":
        return self.push(0).push(0).op("REVERT")

    def assemble(self) -> bytes:
        for pos, name in self.fixups:
            self.out[pos:pos + 2] = self.labels[name].to_bytes(2, "big")
        return bytes(self.out)

    # -- solc idioms shared by the families ---------------------------------

    def dispatch(self, entries) -> "Asm":
        """selector = shr(224, calldataload(0)); jump to each (sel, label)."""
        self.push(0).op("CALLDATALOAD").push(0xE0).op("SHR")
        for sel, lbl in entries:
            self.op("DUP1").push(sel).op("EQ").jumpi(lbl)
        return self

    def mapping_slot(self, slot: int) -> "Asm":
        """key (on stack) -> keccak(key . slot), solc's mapping layout."""
        self.push(0).op("MSTORE")
        self.push(slot).push(32).op("MSTORE")
        return self.push(64).push(0).op("SHA3")

    def arg(self, index: int) -> "Asm":
        return self.push(4 + 32 * index).op("CALLDATALOAD")

    def require(self, ok_label: str) -> "Asm":
        """Branch on the condition on stack; fall-through reverts."""
        self.jumpi(ok_label)
        self.revert()
        return self.label(ok_label)

    def return_one(self) -> "Asm":
        return self.push(1).push(0).op("MSTORE").push(32).push(0).op("RETURN")
