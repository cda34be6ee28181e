"""Pallas TPU kernel for the batched keccak-f[1600] permutation.

This is the hand-scheduled version of ``keccak_jax.keccak_f1600`` (the
SURVEY.md §2.9 "Pallas keccak-f[1600] kernel (batched)" item): the probe
solver hashes thousands of candidate preimages per dispatch, and the
permutation is the dominant cost of every ``keccak`` term.

Layout: the [..., 25, 4]-limb state (25 lanes x four 16-bit limbs held in
uint32, see mythril_tpu/ops/bitvec.py) is transposed to a ``(100, B)`` tile —
rows are lane-major limbs, the batch rides the 128-wide lane dimension of the
VPU — so every theta/rho/pi/chi shuffle is a *static* gather over the leading
(sublane) axis and every xor/shift is an 8x128 vector op.  The 24 rounds run
in a ``fori_loop`` with round constants scalar-prefetched from SMEM, keeping
the whole permutation resident in VMEM with zero HBM round-trips between
rounds.

Numerical contract: bit-identical to ``keccak_jax.keccak_f1600`` (differential
test: tests/ops/test_keccak_pallas.py, in interpreter mode on CPU).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from mythril_tpu.ops.bitvec import LIMB_BITS, LIMB_MASK
from mythril_tpu.ops.keccak_jax import _PI_ROT, _PI_SRC, _RC_LIMBS

# Row index tables for the flattened (100 = lane*4 + limb, B) layout.
# rho+pi as one fused static row gather: out_row[dst*4 + j] combines
# src rows rotated by q limbs plus a sub-limb shift borrowing from the
# previous limb (limbs are < 2^16, so ``prev >> 16`` vanishes when s == 0).
_ROT_Q = _PI_ROT // LIMB_BITS
_ROT_S = _PI_ROT % LIMB_BITS
_RHOPI_MAIN = np.zeros(100, np.int32)
_RHOPI_PREV = np.zeros(100, np.int32)
_RHOPI_SHIFT = np.zeros((100, 1), np.uint32)
for _dst in range(25):
    for _j in range(4):
        _src = _PI_SRC[_dst]
        _q, _s = int(_ROT_Q[_dst]), int(_ROT_S[_dst])
        _RHOPI_MAIN[_dst * 4 + _j] = _src * 4 + (_j - _q) % 4
        _RHOPI_PREV[_dst * 4 + _j] = _src * 4 + (_j - _q - 1) % 4
        _RHOPI_SHIFT[_dst * 4 + _j, 0] = _s

# theta: parity column x feeds lanes x, x+5, ...; d[x] = c[x-1] ^ rotl1(c[x+1])
_THETA_ROWS = np.array(
    [[(x + 5 * y) * 4 + j for y in range(5)] for x in range(5) for j in range(4)],
    np.int32,
)  # [20, 5] rows to xor per parity limb (20 = 5 columns x 4 limbs)
_D_FOR_ROW = np.array(
    [((i // 4) % 5) * 4 + (i % 4) for i in range(100)], np.int32
)  # state row -> d row (d laid out as [20, B], x-major limbs)

# chi: out = b ^ (~b[x+1] & b[x+2]) on the x coordinate
_CHI1_ROWS = np.array(
    [(((i // 4) % 5 + 1) % 5 + 5 * (i // 20)) * 4 + i % 4 for i in range(100)],
    np.int32,
)
_CHI2_ROWS = np.array(
    [(((i // 4) % 5 + 2) % 5 + 5 * (i // 20)) * 4 + i % 4 for i in range(100)],
    np.int32,
)
# d[x] gathers: c rows for x-1 and x+1 (c laid out as [20, B], x-major limbs)
_DM1_ROWS = np.array(
    [((x + 4) % 5) * 4 + j for x in range(5) for j in range(4)], np.int32
)
_DP1_MAIN = np.zeros(20, np.int32)  # rotl1 over the 64-bit lane of c[x+1]
_DP1_PREV = np.zeros(20, np.int32)
for _x in range(5):
    for _j in range(4):
        _DP1_MAIN[_x * 4 + _j] = ((_x + 1) % 5) * 4 + _j  # shift 1 within limb
        _DP1_PREV[_x * 4 + _j] = ((_x + 1) % 5) * 4 + (_j - 1) % 4


def _round_body(r, st, rc_ref):
    """One keccak-f round on the (100, B) uint32 tile.

    All shuffle tables are compile-time Python constants, so every gather is
    written as static row slicing + one concatenate — Pallas kernels cannot
    capture traced index arrays (they would become implicit constants).
    """
    row = [st[i : i + 1, :] for i in range(100)]

    # theta parity: c[x*4+j] = xor over the column's five lanes
    c = []
    for i in range(20):
        acc = row[_THETA_ROWS[i, 0]]
        for y in range(1, 5):
            acc = acc ^ row[_THETA_ROWS[i, y]]
        c.append(acc)
    # d[x] = c[x-1] ^ rotl1(c[x+1])
    d = []
    for i in range(20):
        rot1 = (
            (c[_DP1_MAIN[i]] << 1) | (c[_DP1_PREV[i]] >> (LIMB_BITS - 1))
        ) & LIMB_MASK
        d.append(c[_DM1_ROWS[i]] ^ rot1)
    a = [row[i] ^ d[_D_FOR_ROW[i]] for i in range(100)]

    # rho + pi: per-row static sub-limb shift over the gathered source rows
    b = []
    for i in range(100):
        s = int(_RHOPI_SHIFT[i, 0])
        main, prev = a[_RHOPI_MAIN[i]], a[_RHOPI_PREV[i]]
        b.append(((main << s) | (prev >> (LIMB_BITS - s))) & LIMB_MASK)

    # chi + iota (round constant limbs read from SMEM)
    out = [
        b[i] ^ ((b[_CHI1_ROWS[i]] ^ LIMB_MASK) & b[_CHI2_ROWS[i]])
        for i in range(100)
    ]
    for j in range(4):
        out[j] = out[j] ^ rc_ref[r, j]
    return jnp.concatenate(out, axis=0)


def _kernel(rc_ref, st_ref, out_ref):
    st = st_ref[:]
    st = jax.lax.fori_loop(
        0, 24, lambda r, s: _round_body(r, s, rc_ref), st, unroll=False
    )
    out_ref[:] = st


# lanes per grid step: the round body holds several (100, BT) temporaries in
# VMEM; 1024 lanes keeps the scoped allocation well under the ~16MB limit
# (observed: 4096 lanes in one block exceeds it)
_LANE_TILE = 1024


@functools.partial(jax.jit, static_argnames=("interpret",))
def _permute_tile(tile: jnp.ndarray, interpret: bool = False) -> jnp.ndarray:
    """Run keccak-f[1600] on a (100, B) tile (B a multiple of 128).

    Large batches are tiled along the lane axis with a pallas grid so each
    block's working set stays within scoped VMEM."""
    B = tile.shape[1]
    bt = min(B, _LANE_TILE)
    grid = (B + bt - 1) // bt
    return pl.pallas_call(
        _kernel,
        out_shape=jax.ShapeDtypeStruct(tile.shape, jnp.uint32),
        grid=(grid,),
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.SMEM),
            pl.BlockSpec((100, bt), lambda i: (0, i), memory_space=pltpu.VMEM),
        ],
        out_specs=pl.BlockSpec((100, bt), lambda i: (0, i), memory_space=pltpu.VMEM),
        interpret=interpret,
    )(jnp.asarray(_RC_LIMBS), tile)


def keccak_f1600(state: jnp.ndarray, interpret: bool = False) -> jnp.ndarray:
    """Drop-in for ``keccak_jax.keccak_f1600``: [..., 25, 4] -> [..., 25, 4].

    Flattens the batch onto the 128-lane axis (padded up), permutes in one
    pallas dispatch, and restores the original layout.
    """
    batch_shape = state.shape[:-2]
    flat = state.reshape((-1, 25, 4))
    b = flat.shape[0]
    bp = max(128, ((b + 127) // 128) * 128)
    if bp != b:
        flat = jnp.pad(flat, ((0, bp - b), (0, 0), (0, 0)))
    tile = flat.reshape(bp, 100).T  # rows = lane*4 + limb
    out = _permute_tile(tile, interpret=interpret)
    return out.T[:b].reshape(batch_shape + (25, 4))
