"""Tape VM: ONE compiled XLA program evaluates ANY constraint conjunction.

The first device-probe design (mythril_tpu/ops/lowering.py) compiles each
distinct conjunction into its own jitted evaluator.  Engine workloads produce
a fresh conjunction per JUMPI fork, so that design pays an XLA compile —
seconds — for almost every query; measured on
the killbilly benchmark the compile path was ~4s per dispatch, 1000x slower
than the host evaluator.

This module fixes the economics the TPU-native way: the *program* is a
generic term-tape interpreter compiled once per (profile, batch) bucket, and
the *conjunction* is data — opcode/operand/width-mask tensors streamed in
per query.  `lax.scan` walks the tape; `lax.switch` dispatches each step to
one of ~20 vector op kernels from mythril_tpu/ops/bitvec.py operating on the
whole candidate batch at once.  All values live as 256-bit (16xu32-limb)
words zero-extended from their semantic width; narrower-width semantics are
recovered by desugaring (signed compares via sign-bit flips, sext via
conditional OR of the extension mask, ashr/sdiv via 256-bit sign extension)
plus a per-step result mask, so every branch is width-static.

Array reads (select) resolve against per-candidate finite tables exactly as
in lowering.py; keccak terms hash concretely on device via
mythril_tpu/ops/keccak_jax.py (the 32- and 64-byte preimage shapes that EVM
storage-slot hashing produces).  Unsupported structure raises
`TapeUnsupported` and the caller falls back to the per-conjunction path.
"""

from __future__ import annotations

import functools
from typing import Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from mythril_tpu.ops import bitvec as bv
from mythril_tpu.ops.keccak_jax import keccak256
from mythril_tpu.smt import terms
from mythril_tpu.smt.terms import Term
from mythril_tpu.support.device import DeviceWarmup

L = 16  # limbs per word (256 bits as 16x16-bit limbs in u32)

(
    OP_ADD, OP_SUB, OP_MUL, OP_UDIV, OP_UREM, OP_SDIV, OP_SREM, OP_EXP,
    OP_AND, OP_OR, OP_XOR, OP_SHL, OP_LSHR, OP_ASHR,
    OP_EQ, OP_ULT, OP_ITE, OP_SELECT, OP_KECCAK32, OP_KECCAK64,
) = range(20)

N_OPS = 20


class TapeUnsupported(Exception):
    """Conjunction shape the tape VM cannot express; use the fallback path."""


# Profiles: (T steps, V leaf slots, A arrays, K table rows, R roots)
_PROFILES = (
    ("small", 96, 24, 3, 8, 24),
    ("large", 384, 72, 6, 24, 72),
)
_BATCH_BUCKETS = (64, 256)


# ---------------------------------------------------------------------------
# Host-side tape assembly
# ---------------------------------------------------------------------------


class TapeProgram:
    """A conjunction assembled into tape tensors (numpy, device-ready)."""

    def __init__(self, conjuncts: Sequence[Term]):
        self.conjuncts = list(conjuncts)
        self.leaf_vars: List[Term] = []  # creation order == leaf-row order
        self.bv_vars: List[Term] = []
        self.bool_vars: List[Term] = []
        self.array_vars: List[Term] = []
        self._row_of: Dict[int, int] = {}  # term tid -> reg row
        self._const_rows: Dict[int, int] = {}  # value -> leaf row
        self._leaf_consts: List[int] = []  # leaf row -> const value
        self._var_rows: Dict[int, int] = {}  # var tid -> leaf row
        self.ops: List[Tuple[int, int, int, int, int, int]] = []  # op,a0,a1,a2,aux,wmask_width
        self.root_rows: List[int] = []
        self._build()

    # -- leaf management ----------------------------------------------------
    def _const(self, value: int) -> int:
        row = self._const_rows.get(value)
        if row is None:
            row = len(self._leaf_consts)
            self._leaf_consts.append(value)
            self._const_rows[value] = row
        return row

    @property
    def n_leaves(self) -> int:
        return len(self._leaf_consts) + len(self.leaf_vars)

    def _var_row(self, t: Term) -> int:
        row = self._var_rows.get(t.tid)
        if row is None:
            # var leaf rows sit above all const rows; the const count grows
            # while building, so store a placeholder (-1 - ordinal) that
            # finalize resolves once the const pool is complete
            row = -(1 + len(self.leaf_vars))
            self.leaf_vars.append(t)
            if t.sort is terms.BOOL:
                self.bool_vars.append(t)
            else:
                self.bv_vars.append(t)
            self._var_rows[t.tid] = row
        return row

    # -- op emission ---------------------------------------------------------
    def _emit(self, op: int, a0: int, a1: int = 0, a2: int = 0, aux: int = 0,
              width: int = 256) -> int:
        self.ops.append((op, a0, a1, a2, aux, width))
        if len(self.ops) > _PROFILES[-1][1]:
            raise TapeUnsupported("tape too long")
        # computed rows live above ALL leaf rows; encode as offset + big base
        return _STEP_BASE + len(self.ops) - 1

    def _build(self):
        for t in terms.topo_order(self.conjuncts):
            op = t.op
            if op in ("array_var", "const_array", "store"):
                if op == "array_var":
                    self.array_vars.append(t)
                    if len(self.array_vars) > _PROFILES[-1][3]:
                        raise TapeUnsupported("too many arrays")
                continue
            if op == "ite" and terms.is_array_sort(t.sort):
                continue
            if terms.is_bv_sort(t.sort) and t.width > 256:
                # wide terms (keccak preimage concats) are consumed
                # structurally by _lower_keccak; any other consumer will
                # fail the _r lookup and trigger the fallback path
                continue
            self._row_of[t.tid] = self._lower(t)
        for c in self.conjuncts:
            self.root_rows.append(self._row_of[c.tid])
        if len(self.root_rows) > _PROFILES[-1][5]:
            raise TapeUnsupported("too many roots")

    def _r(self, t: Term) -> int:
        row = self._row_of.get(t.tid)
        if row is None:
            raise TapeUnsupported(f"consumer of unlowered term {t.op}")
        return row

    def _lower(self, t: Term) -> int:
        op, a = t.op, t.args
        if op == "const":
            if t.sort is terms.BOOL:
                return self._const(1 if t.aux else 0)
            if t.width > 256:
                raise TapeUnsupported("wide constant")
            return self._const(t.aux)
        if op == "var":
            return self._var_row(t)
        if op == "select":
            return self._lower_select(a[0], self._r(a[1]))
        if op == "keccak":
            return self._lower_keccak(t)
        if op == "apply":
            raise TapeUnsupported("uninterpreted function")

        w = t.width if terms.is_bv_sort(t.sort) else 1

        if op == "and" or op == "or":
            code = OP_AND if op == "and" else OP_OR
            row = self._r(a[0])
            for x in a[1:]:
                row = self._emit(code, row, self._r(x), width=1)
            return row
        if op == "not":
            return self._emit(OP_XOR, self._r(a[0]), self._const(1), width=1)
        if op == "xor" and t.sort is terms.BOOL:
            return self._emit(OP_XOR, self._r(a[0]), self._r(a[1]), width=1)
        if op == "eq":
            if terms.is_array_sort(a[0].sort):
                raise TapeUnsupported("array equality")
            return self._emit(OP_EQ, self._r(a[0]), self._r(a[1]), width=1)
        if op == "ite":
            return self._emit(
                OP_ITE, self._r(a[0]), self._r(a[1]), self._r(a[2]), width=w
            )
        if op == "ult":
            return self._emit(OP_ULT, self._r(a[0]), self._r(a[1]), width=1)
        if op == "ule":
            lt = self._emit(OP_ULT, self._r(a[1]), self._r(a[0]), width=1)
            return self._emit(OP_XOR, lt, self._const(1), width=1)
        if op in ("slt", "sle"):
            wa = a[0].width
            sb = self._const(1 << (wa - 1))
            fa = self._emit(OP_XOR, self._r(a[0]), sb, width=wa)
            fb = self._emit(OP_XOR, self._r(a[1]), sb, width=wa)
            if op == "slt":
                return self._emit(OP_ULT, fa, fb, width=1)
            lt = self._emit(OP_ULT, fb, fa, width=1)
            return self._emit(OP_XOR, lt, self._const(1), width=1)

        if op == "bvnot":
            return self._emit(
                OP_XOR, self._r(a[0]), self._const(terms.mask(-1, w)), width=w
            )
        if op == "bvneg":
            return self._emit(OP_SUB, self._const(0), self._r(a[0]), width=w)
        if op == "zext":
            return self._r(a[0])  # invariant: regs are zero-extended already
        if op == "sext":
            return self._sign_extend(self._r(a[0]), a[0].width, w)
        if op == "extract":
            hi, lo = t.aux
            if lo == 0:
                # masking alone suffices; reuse the operand row via OR 0
                return self._emit(OP_OR, self._r(a[0]), self._const(0), width=w)
            return self._emit(
                OP_LSHR, self._r(a[0]), self._const(lo), width=w
            )
        if op == "concat":
            shifted = self._emit(
                OP_SHL, self._r(a[0]), self._const(a[1].width), width=w
            )
            return self._emit(OP_OR, shifted, self._r(a[1]), width=w)
        if op == "bvashr":
            ext = self._sign_extend(self._r(a[0]), w, 256)
            return self._emit(OP_ASHR, ext, self._r(a[1]), width=w)
        if op in ("bvsdiv", "bvsrem"):
            ea = self._sign_extend(self._r(a[0]), w, 256)
            eb = self._sign_extend(self._r(a[1]), w, 256)
            code = OP_SDIV if op == "bvsdiv" else OP_SREM
            return self._emit(code, ea, eb, width=w)
        simple = {
            "bvadd": OP_ADD, "bvsub": OP_SUB, "bvmul": OP_MUL,
            "bvudiv": OP_UDIV, "bvurem": OP_UREM, "bvexp": OP_EXP,
            "bvand": OP_AND, "bvor": OP_OR, "bvxor": OP_XOR,
            "bvshl": OP_SHL, "bvlshr": OP_LSHR,
        }
        code = simple.get(op)
        if code is None:
            raise TapeUnsupported(f"op {op}")
        return self._emit(code, self._r(a[0]), self._r(a[1]), width=w)

    def _sign_extend(self, row: int, from_w: int, to_w: int) -> int:
        if from_w >= to_w:
            return row
        sign = self._emit(OP_LSHR, row, self._const(from_w - 1), width=1)
        ext_bits = terms.mask(-1, to_w) ^ terms.mask(-1, from_w)
        extended = self._emit(
            OP_OR, row, self._const(ext_bits), width=to_w
        )
        return self._emit(OP_ITE, sign, extended, row, width=to_w)

    def _lower_select(self, arr: Term, idx_row: int) -> int:
        rng_w = arr.sort[2]
        if rng_w > 256 or arr.sort[1] > 256:
            raise TapeUnsupported("wide array sorts")
        if arr.op == "store":
            base, s_idx, s_val = arr.args
            below = self._lower_select(base, idx_row)
            hit = self._emit(OP_EQ, self._r(s_idx), idx_row, width=1)
            return self._emit(
                OP_ITE, hit, self._r(s_val), below, width=rng_w
            )
        if arr.op == "ite":
            c, x, y = arr.args
            then = self._lower_select(x, idx_row)
            els = self._lower_select(y, idx_row)
            return self._emit(
                OP_ITE, self._r(c), then, els, width=rng_w
            )
        if arr.op == "const_array":
            return self._r(arr.args[0])
        if arr.op == "array_var":
            slot = next(
                i for i, av in enumerate(self.array_vars) if av.tid == arr.tid
            )
            return self._emit(OP_SELECT, idx_row, aux=slot, width=rng_w)
        raise TapeUnsupported(f"array op {arr.op}")

    def _lower_keccak(self, t: Term) -> int:
        inp = t.args[0]
        if inp.width == 256:
            return self._emit(OP_KECCAK32, self._r(inp), width=256)
        if inp.width == 512 and inp.op == "concat":
            hi, lo = inp.args
            if hi.width == 256 and lo.width == 256:
                return self._emit(
                    OP_KECCAK64, self._r(lo), self._r(hi), width=256
                )
        raise TapeUnsupported(f"keccak input width {inp.width}")

    # -- finalize into padded tensors ---------------------------------------
    def finalize(self, profile) -> Optional[dict]:
        """Resolve rows against a profile; None if the profile is too small."""
        name, T, V, A, K, R = profile
        n_consts = len(self._leaf_consts)
        if (
            len(self.ops) > T
            or self.n_leaves > V
            or len(self.array_vars) > A
            or len(self.root_rows) > R
        ):
            return None

        def resolve(row: int) -> int:
            if row >= _STEP_BASE:
                return V + (row - _STEP_BASE)
            if row < 0:
                return n_consts + (-row - 1)  # var placeholder
            return row  # const leaf

        op = np.zeros(T, np.int32)
        a0 = np.zeros(T, np.int32)
        a1 = np.zeros(T, np.int32)
        a2 = np.zeros(T, np.int32)
        aux = np.zeros(T, np.int32)
        wmask = np.zeros((T, L), np.uint32)
        for i, (o, x0, x1, x2, ax, w) in enumerate(self.ops):
            op[i] = o
            a0[i] = resolve(x0)
            a1[i] = resolve(x1)
            a2[i] = resolve(x2)
            aux[i] = ax
            wmask[i] = bv.from_ints(terms.mask(-1, w), 256)
        root_rows = np.zeros(R, np.int32)
        root_valid = np.zeros(R, bool)
        for i, row in enumerate(self.root_rows):
            root_rows[i] = resolve(row)
            root_valid[i] = True
        leaf_consts = np.zeros((V, L), np.uint32)
        for i, v in enumerate(self._leaf_consts):
            leaf_consts[i] = bv.from_ints(v, 256)
        return {
            "profile": name,
            "shape": (T, V, A, K, R),
            "op": op, "a0": a0, "a1": a1, "a2": a2, "aux": aux,
            "wmask": wmask, "root_rows": root_rows, "root_valid": root_valid,
            "leaf_consts": leaf_consts, "n_consts": n_consts,
        }


_STEP_BASE = 1 << 20


# ---------------------------------------------------------------------------
# The compiled interpreter (one jit per (profile shape, batch bucket))
# ---------------------------------------------------------------------------


@functools.partial(jax.jit, static_argnames=("T", "V", "A", "K", "R"))
def _run_tape(
    leaf_vals,  # [B, V, L] u32 (consts + var values)
    tab_idx,  # [B, A, K, L] u32
    tab_val,  # [B, A, K, L] u32
    tab_valid,  # [B, A, K] bool
    tab_default,  # [B, A, L] u32
    op, a0, a1, a2, aux,  # [T] i32
    wmask,  # [T, L] u32
    root_rows,  # [R] i32
    root_valid,  # [R] bool
    *, T: int, V: int, A: int, K: int, R: int,
):
    B = leaf_vals.shape[0]
    regs0 = jnp.zeros((V + T, B, L), jnp.uint32)
    regs0 = regs0.at[:V].set(jnp.transpose(leaf_vals, (1, 0, 2)))

    def to_word(flag):  # [B] bool -> [B, L] 0/1 word
        out = jnp.zeros((B, L), jnp.uint32)
        return out.at[:, 0].set(flag.astype(jnp.uint32))

    def br_select(x, y, z, slot):
        t_idx = lax.dynamic_index_in_dim(tab_idx, slot, axis=1, keepdims=False)
        t_val = lax.dynamic_index_in_dim(tab_val, slot, axis=1, keepdims=False)
        t_ok = lax.dynamic_index_in_dim(tab_valid, slot, axis=1, keepdims=False)
        t_def = lax.dynamic_index_in_dim(tab_default, slot, axis=1, keepdims=False)
        hit = (t_idx == x[:, None, :]).all(-1) & t_ok  # [B, K]
        any_hit = hit.any(-1)
        chosen = (t_val * hit[..., None].astype(jnp.uint32)).sum(axis=1)
        return jnp.where(any_hit[:, None], chosen, t_def)

    def br_keccak64(x, y, z, slot):
        # x = low 256 bits, y = high 256 bits; limbs little-endian
        return keccak256(jnp.concatenate([x, y], axis=-1), 512)

    branches = [
        lambda x, y, z, s: bv.add(x, y, 256),
        lambda x, y, z, s: bv.sub(x, y, 256),
        lambda x, y, z, s: bv.mul(x, y, 256),
        lambda x, y, z, s: bv.udiv(x, y, 256),
        lambda x, y, z, s: bv.urem(x, y, 256),
        lambda x, y, z, s: bv.sdiv(x, y, 256),
        lambda x, y, z, s: bv.srem(x, y, 256),
        lambda x, y, z, s: bv.bvexp(x, y, 256),
        lambda x, y, z, s: x & y,
        lambda x, y, z, s: x | y,
        lambda x, y, z, s: x ^ y,
        lambda x, y, z, s: bv.shl(x, y, 256),
        lambda x, y, z, s: bv.lshr(x, y, 256),
        lambda x, y, z, s: bv.ashr(x, y, 256),
        lambda x, y, z, s: to_word(bv.eq(x, y)),
        lambda x, y, z, s: to_word(bv.ult(x, y)),
        lambda x, y, z, s: bv.mux((x != 0).any(-1), y, z),
        br_select,
        lambda x, y, z, s: keccak256(x, 256),
        br_keccak64,
    ]

    def step_wrapper(carry, xs):
        regs, t = carry
        opc, i0, i1, i2, slot, wm = xs
        x = lax.dynamic_index_in_dim(regs, i0, axis=0, keepdims=False)
        y = lax.dynamic_index_in_dim(regs, i1, axis=0, keepdims=False)
        z = lax.dynamic_index_in_dim(regs, i2, axis=0, keepdims=False)
        res = lax.switch(opc, branches, x, y, z, slot)
        res = res & wm[None, :]
        regs = lax.dynamic_update_index_in_dim(regs, res, V + t, axis=0)
        return (regs, t + 1), None

    (regs, _), _ = lax.scan(
        step_wrapper, (regs0, jnp.int32(0)), (op, a0, a1, a2, aux, wmask)
    )
    vals = regs[root_rows]  # [R, B, L] (static gather: root_rows is traced...)
    truth = (vals != 0).any(-1)  # [R, B]
    truth = truth | ~root_valid[:, None]
    return truth.T  # [B, R]


# ---------------------------------------------------------------------------
# Public adapter (mirrors lowering.CompiledConjunction's surface)
# ---------------------------------------------------------------------------


class TapeCompiled:
    """Evaluate a conjunction over candidate batches via the shared VM."""

    def __init__(self, program: TapeProgram, tensors: dict):
        self.program = program
        self.tensors = tensors
        self.conjuncts = program.conjuncts
        self.bv_vars = program.bv_vars
        self.bool_vars = program.bool_vars
        self.array_vars = program.array_vars

    def evaluate_batch(self, assignments) -> np.ndarray:
        args, (T, V, A, K, R) = self.pack_args(assignments)
        truth = _run_tape(*args, T=T, V=V, A=A, K=K, R=R)
        return np.asarray(truth)[: len(assignments), : len(self.conjuncts)]

    def pack_args(self, assignments) -> Tuple[tuple, tuple]:
        """Candidate assignments -> the _run_tape input tensors + shape.

        Exposed separately so callers embedding the interpreter in larger
        jitted programs (driver entry points, mesh-sharded dispatch) can
        build the exact argument tuple the compiled program expects.
        """
        t = self.tensors
        T, V, A, K, R = t["shape"]
        B_real = len(assignments)
        B = next((b for b in _BATCH_BUCKETS if b >= B_real), None)
        if B is None:
            B = ((B_real + 255) // 256) * 256

        # packing is bulk per column (one from_ints call over the whole
        # batch) — per-candidate Python loops were the large-batch bottleneck
        leaf_vals = np.tile(t["leaf_consts"][None], (B, 1, 1))
        n_consts = t["n_consts"]
        n = len(assignments)
        for vi, var in enumerate(self.program.leaf_vars):
            vals = [int(asg.scalars.get(var, 0)) for asg in assignments]
            leaf_vals[:n, n_consts + vi] = bv.from_ints(vals, 256)

        tab_idx = np.zeros((B, A, K, L), np.uint32)
        tab_val = np.zeros((B, A, K, L), np.uint32)
        tab_valid = np.zeros((B, A, K), bool)
        tab_default = np.zeros((B, A, L), np.uint32)
        for ai, av in enumerate(self.program.array_vars):
            keys = sorted(
                {
                    k
                    for asg in assignments
                    for k in getattr(asg.arrays.get(av), "backing", {})
                }
            )[:K]
            arrs = [asg.arrays.get(av) for asg in assignments]
            defaults = [int(a.default) if a is not None else 0 for a in arrs]
            tab_default[:n, ai] = bv.from_ints(defaults, 256)
            if keys:
                tab_idx[:, ai, : len(keys)] = bv.from_ints(keys, 256)[None]
                tab_valid[:n, ai, : len(keys)] = True
                for ki, k in enumerate(keys):
                    vals = [
                        int(a.backing.get(k, d)) if a is not None else 0
                        for a, d in zip(arrs, defaults)
                    ]
                    tab_val[:n, ai, ki] = bv.from_ints(vals, 256)

        args = (
            jnp.asarray(leaf_vals),
            jnp.asarray(tab_idx),
            jnp.asarray(tab_val),
            jnp.asarray(tab_valid),
            jnp.asarray(tab_default),
            jnp.asarray(t["op"]), jnp.asarray(t["a0"]), jnp.asarray(t["a1"]),
            jnp.asarray(t["a2"]), jnp.asarray(t["aux"]),
            jnp.asarray(t["wmask"]),
            jnp.asarray(t["root_rows"]), jnp.asarray(t["root_valid"]),
        )
        return args, (T, V, A, K, R)


def _do_warmup_compiles() -> None:
    from mythril_tpu.smt import terms
    from mythril_tpu.smt.concrete_eval import Assignment

    x = terms.var("__tape_warmup__", 256)
    compiled = compile_tape([terms.ult(x, terms.const(7, 256))])
    asg = Assignment()
    asg.scalars[x] = 1
    # both production batch buckets: is_possible dispatches 48 candidates
    # (-> bucket 64), get_model dispatches 192 (-> bucket 256)
    for b in _BATCH_BUCKETS:
        compiled.evaluate_batch([asg] * b)


#: the one-time interpreter compile (support/device.DeviceWarmup contract).
#: Engine timers (the 10s creation-transaction timeout, reference cli
#: default) must not pay it: a FORCED device backend calls ``warmup()``
#: before timed execution; "auto" calls ``ensure_warming()`` once a query
#: crosses the device break-even and uses the host until
#: ``interpreter_ready()``.  A failed compile raises at the next call.
_WARMUP = DeviceWarmup("tape-vm", _do_warmup_compiles)
warmup = _WARMUP.warmup
ensure_warming = _WARMUP.ensure_warming
interpreter_ready = _WARMUP.ready


_CACHE: Dict[tuple, TapeCompiled] = {}
_CACHE_CAP = 4096


def compile_tape(conjuncts: Sequence[Term]) -> TapeCompiled:
    """Assemble (and cache) the tape for a conjunction.

    Raises TapeUnsupported when the DAG exceeds every profile or contains
    structure the VM cannot express; callers fall back to
    lowering.compile_cached.
    """
    key = tuple(c.tid for c in conjuncts)
    hit = _CACHE.get(key)
    if hit is not None:
        return hit
    program = TapeProgram(conjuncts)
    tensors = None
    for profile in _PROFILES:
        tensors = program.finalize(profile)
        if tensors is not None:
            break
    if tensors is None:
        raise TapeUnsupported("exceeds every profile")
    compiled = TapeCompiled(program, tensors)
    if len(_CACHE) >= _CACHE_CAP:
        _CACHE.clear()
    _CACHE[key] = compiled
    return compiled
