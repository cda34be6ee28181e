"""Batched keccak-256 on device — exact concrete hashing for the probe solver.

The reference cannot hash symbolically, so it axiomatizes keccak as an
uninterpreted function with disjoint-interval range constraints
(mythril/laser/ethereum/function_managers/keccak_function_manager.py:26-34).
This framework instead evaluates ``keccak`` terms *concretely* for every
candidate assignment, on device, in batch — hashing thousands of candidate
preimages per dispatch.  Exactness beats axioms: a probe hit is a real model
with real hash values, so no post-hoc ``_replace_with_actual_sha`` step
(reference: mythril/analysis/solver.py:128-164) is ever needed.

Representation: 64-bit keccak lanes as four 16-bit limbs in uint32
(``[..., 25, 4]`` state), matching ``mythril_tpu/ops/bitvec.py`` — no 64-bit
integers anywhere, so the same arithmetic is valid inside Pallas TPU kernels.
Differentially tested against the host implementation
(mythril_tpu/ops/keccak.py) in tests/ops/test_keccak_jax.py.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from mythril_tpu.ops.bitvec import LIMB_BITS, LIMB_MASK, nlimbs
from mythril_tpu.ops.keccak import _RC, _ROT

RATE_BYTES = 136  # 1088-bit rate for keccak-256

# Round constants as [24, 4] little-endian 16-bit limbs.
_RC_LIMBS = np.array(
    [[(rc >> (16 * i)) & LIMB_MASK for i in range(4)] for rc in _RC], np.uint32
)

# Static lane shuffles for one round, flattened over lane index i = x + 5*y.
# rho+pi: output lane dst = y + 5*((2x+3y)%5) takes input lane x+5y rotated
# by _ROT[x][y]; chi: out[i] = b[i] ^ (~b[i+1 (mod x)] & b[i+2 (mod x)]).
_PI_SRC = np.zeros(25, np.int32)
_PI_ROT = np.zeros(25, np.int32)
for _x in range(5):
    for _y in range(5):
        _PI_SRC[_y + 5 * ((2 * _x + 3 * _y) % 5)] = _x + 5 * _y
        _PI_ROT[_y + 5 * ((2 * _x + 3 * _y) % 5)] = _ROT[_x][_y] % 64
_CHI1 = np.array([(i % 5 + 1) % 5 + 5 * (i // 5) for i in range(25)], np.int32)
_CHI2 = np.array([(i % 5 + 2) % 5 + 5 * (i // 5) for i in range(25)], np.int32)
_MOD5 = np.arange(25, dtype=np.int32) % 5
_XM1 = np.array([(x + 4) % 5 for x in range(5)], np.int32)
_XP1 = np.array([(x + 1) % 5 for x in range(5)], np.int32)
# Per-lane limb gather for the rho rotations: new[j] = old[(j - q) % 4].
_ROT_Q, _ROT_S = _PI_ROT // LIMB_BITS, _PI_ROT % LIMB_BITS
_ROT_JIDX = (np.arange(4)[None, :] - _ROT_Q[:, None]) % 4  # [25, 4]


def _rotl64(lane: jnp.ndarray, r: int) -> jnp.ndarray:
    """Rotate a [..., 4]-limb 64-bit lane left by a static amount."""
    r %= 64
    q, s = divmod(r, LIMB_BITS)
    rolled = jnp.roll(lane, q, axis=-1)
    if s == 0:
        return rolled
    prev = jnp.roll(rolled, 1, axis=-1)
    return ((rolled << s) | (prev >> (LIMB_BITS - s))) & LIMB_MASK


def _rho_rotate(lanes: jnp.ndarray) -> jnp.ndarray:
    """Rotate each of the 25 [..., 25, 4] lanes by its static rho amount.

    Limb rotation is a static gather; the sub-limb shift uses the limb one
    below (limbs are < 2^16, so ``prev >> 16`` is 0 exactly when s == 0)."""
    jidx = jnp.broadcast_to(jnp.asarray(_ROT_JIDX), lanes.shape)
    rolled = jnp.take_along_axis(lanes, jidx, axis=-1)
    prev = jnp.take_along_axis(lanes, (jidx - 1) % 4, axis=-1)
    s = jnp.asarray(_ROT_S[:, None].astype(np.uint32))
    return ((rolled << s) | (prev >> (LIMB_BITS - s))) & LIMB_MASK


def _round(state: jnp.ndarray, rc: jnp.ndarray) -> jnp.ndarray:
    """One keccak-f round on the [..., 25, 4] state (lane index = x + 5*y)."""
    s5 = state.reshape(state.shape[:-2] + (5, 5, 4))  # [..., y, x, limb]
    c = s5[..., 0, :, :]
    for y in range(1, 5):
        c = c ^ s5[..., y, :, :]
    d = jnp.take(c, _XM1, axis=-2) ^ _rotl64(jnp.take(c, _XP1, axis=-2), 1)
    a = state ^ jnp.take(d, _MOD5, axis=-2)
    b = _rho_rotate(jnp.take(a, _PI_SRC, axis=-2))
    chi = b ^ (
        (jnp.take(b, _CHI1, axis=-2) ^ LIMB_MASK) & jnp.take(b, _CHI2, axis=-2)
    )
    return chi.at[..., 0, :].set(chi[..., 0, :] ^ rc)


def keccak_f1600(state: jnp.ndarray) -> jnp.ndarray:
    """Full 24-round permutation of the [..., 25, 4] state.

    On TPU (or with ``args.keccak_backend = "pallas"``) this dispatches to the
    hand-scheduled Pallas kernel (mythril_tpu/ops/keccak_pallas.py); the
    portable path runs the rounds under ``lax.scan`` so the compiled graph
    holds ONE round body — a fully unrolled version takes minutes of XLA
    compile time."""
    from mythril_tpu.support.device import on_tpu
    from mythril_tpu.support.support_args import args

    backend = getattr(args, "keccak_backend", "auto")
    if backend == "pallas" or (backend == "auto" and on_tpu()):
        from mythril_tpu.ops import keccak_pallas

        return keccak_pallas.keccak_f1600(state)
    out, _ = jax.lax.scan(
        lambda st, rc: (_round(st, rc), None), state, jnp.asarray(_RC_LIMBS)
    )
    return out


def _gather_bytes(data: jnp.ndarray, width: int) -> list:
    """Big-endian byte string of a [..., L]-limb bitvector, as a list of
    [...]-shaped uint32 byte tensors (static index shuffle)."""
    n = width // 8
    out = []
    for j in range(n):  # j = 0 is the most significant byte
        k = n - 1 - j  # numeric little-endian byte index
        limb = data[..., k // 2]
        out.append((limb >> (8 * (k % 2))) & 0xFF)
    return out


def keccak256(data: jnp.ndarray, width: int, permute=None) -> jnp.ndarray:
    """keccak-256 of the big-endian byte serialization of a bitvector.

    ``data``: [..., nlimbs(width)] uint32; ``width`` must be a multiple of 8
    (the term layer guarantees byte-width hash inputs).  Returns [..., 16]
    limbs (a 256-bit word).  ``permute`` pins the keccak-f[1600]
    implementation (default: ``keccak_f1600``'s backend choice)."""
    permute = permute or keccak_f1600
    assert width % 8 == 0, "keccak input must be byte-aligned"
    msg = _gather_bytes(data, width)
    n = len(msg)
    zero = jnp.zeros(jnp.shape(data)[:-1], jnp.uint32)
    msg = [jnp.broadcast_to(b, zero.shape).astype(jnp.uint32) for b in msg]

    # keccak (pre-NIST) padding: 0x01 ... 0x80 within the last rate block
    nblocks = n // RATE_BYTES + 1
    padded = msg + [zero] * (nblocks * RATE_BYTES - n)
    padded[n] = padded[n] | 0x01
    padded[nblocks * RATE_BYTES - 1] = padded[nblocks * RATE_BYTES - 1] | 0x80

    state = jnp.zeros(zero.shape + (25, 4), jnp.uint32)
    for blk in range(nblocks):
        block = padded[blk * RATE_BYTES : (blk + 1) * RATE_BYTES]
        # absorb: XOR 17 lanes (8 bytes each, little-endian within the lane)
        lanes = []
        for t in range(17):
            limbs = [
                block[8 * t + 2 * u] | (block[8 * t + 2 * u + 1] << 8)
                for u in range(4)
            ]
            lanes.append(jnp.stack(limbs, axis=-1))
        absorb = jnp.stack(lanes, axis=-2)  # [..., 17, 4]
        state = state.at[..., :17, :].set(state[..., :17, :] ^ absorb)
        state = permute(state)

    # squeeze 32 bytes = lanes 0..3; output word is big-endian bytes
    out_bytes = []  # big-endian byte list, most significant first
    for t in range(4):
        for u in range(8):  # byte u of lane t, little-endian in the lane
            out_bytes.append((state[..., t, u // 2] >> (8 * (u % 2))) & 0xFF)
    # out_bytes[0] is the FIRST digest byte = most significant of the word
    limbs = []
    for i in range(16):  # little-endian 16-bit limbs of the 256-bit word
        b_lo = out_bytes[31 - 2 * i]
        b_hi = out_bytes[31 - (2 * i + 1)]
        limbs.append(b_lo | (b_hi << 8))
    return jnp.stack(limbs, axis=-1)
