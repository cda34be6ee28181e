"""The jitted device segment: K lockstep instruction steps over the batch.

One call executes up to ``caps.K`` EVM instructions for every live path in
the frontier — the device-side replacement for the host engine's
one-state-at-a-time loop (reference mythril/laser/ethereum/svm.py:261-304,
instructions.py handler dispatch).  Structure per step:

  1. per-path phase (``vmap`` of a ``lax.switch`` over handler families):
     pops/pushes on the tensor stack, constant folding via the 16-bit-limb
     algebra (mythril_tpu/ops/bitvec.py), symbolic results as new arena rows
     (each path owns ``caps.R`` reserved rows per step — no cross-path
     coordination needed), event recording, fork requests;
  2. cross-path phase: grant JUMPI forks into free batch slots by prefix-sum
     rank (masked in-batch duplication — the reference's ``copy.copy`` fork,
     instructions.py:791-823, as a gather), write fork constraints/events.

Under ``vmap`` every switch branch executes for the whole batch and results
are selected — that is the intended SIMD trade: handlers are tiny tensor ops,
and XLA fuses the lot into one kernel per step.
"""

from __future__ import annotations

import time
from functools import lru_cache, partial
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from mythril_tpu.frontier import ops as O
from mythril_tpu.frontier.code import (
    CTX_ADDRESS,
    CTX_BALANCES,
    CTX_SEED,
    CTX_STORAGE,
    CodeTables,
)
from mythril_tpu.frontier.state import Caps, FrontierState
from mythril_tpu.observability import deviceplane as _devplane
from mythril_tpu.observability import tracer as _otrace
from mythril_tpu.ops import bitvec as bv

I32 = jnp.int32


class ArenaDev(NamedTuple):
    op: jnp.ndarray  # [T] i32
    a: jnp.ndarray  # [T] i32
    b: jnp.ndarray  # [T] i32
    c: jnp.ndarray  # [T] i32
    width: jnp.ndarray  # [T] i32
    val: jnp.ndarray  # [T, 16] u32
    isconst: jnp.ndarray  # [T] bool


class NewRows(NamedTuple):
    """R rows a path may write this step."""

    op: jnp.ndarray  # [R]
    a: jnp.ndarray
    b: jnp.ndarray
    c: jnp.ndarray
    width: jnp.ndarray
    val: jnp.ndarray  # [R, 16]
    isconst: jnp.ndarray


class Fork(NamedTuple):
    want: jnp.ndarray  # scalar bool
    target: jnp.ndarray  # instruction index of the taken branch
    dest_row: jnp.ndarray
    word_row: jnp.ndarray
    cond_row: jnp.ndarray  # bool row for the taken constraint
    ncond_row: jnp.ndarray  # bool row for the fall-through constraint


def _memgas(size_bytes):
    w = size_bytes // 32
    return 3 * w + (w * w) // 512


class CodeDev(NamedTuple):
    """Per-instruction dispatch tables as DEVICE INPUTS (padded to a size
    bucket) so one compiled segment program serves every contract — compile
    cost is paid once per (caps, bucket), not once per contract.

    All tables carry a leading [C] code axis and every path indexes them by
    its ``state.code_id`` — one [B] gather per table per step — so paths
    from DIFFERENT contracts (a corpus sweep, inner-call frames) batch into
    a single wide segment (multi-code frontier batching, SURVEY.md §7.3)."""

    fam: jnp.ndarray  # [C, N] i32, padded with F_STOP
    aux: jnp.ndarray  # [C, N] i32
    arity: jnp.ndarray  # [C, N] i32
    gmin: jnp.ndarray  # [C, N] i32
    gmax: jnp.ndarray  # [C, N] i32
    event: jnp.ndarray  # [C, N] bool
    jumpmap: jnp.ndarray  # [C, ADDR_CAP] i32
    loopid: jnp.ndarray  # [C, N] i32 (clamped to the loops cap)
    concskip: jnp.ndarray  # [C, N] bool — hooked-only event suppressible
    # when every popped operand is concrete (module concrete_nop_hooks)
    valgate: jnp.ndarray  # [C, N] bool — MSTORE panic gate (module
    # value_gated_hooks): event only when the stored value is concrete
    # with the solc Panic(uint256) selector in its top 32 bits
    pbase: jnp.ndarray  # [C] i32 resident-window start per code (packed-
    # code paging): every instruction-axis gather subtracts it from the
    # TRUE pc; a pc outside [pbase, pbase + N) dispatches F_PAGEFAULT.
    # All-zero (and N covering the whole code) when paging is off.


class CfgScalars(NamedTuple):
    """Run-config scalars as dynamic inputs (no recompile on change)."""

    max_depth: jnp.ndarray
    loop_bound: jnp.ndarray  # 0 disables the bound
    row_zero: jnp.ndarray  # arena row of const 0
    row_one: jnp.ndarray  # arena row of const 1
    # fork-grant priority under slot scarcity (SEL_*): the batched form of
    # the host search strategies (SURVEY.md §7.2 item 5) — with free slots
    # every fork is granted and the mode is irrelevant
    sel_mode: jnp.ndarray
    # per-segment step limit (<= caps.K), dynamic so the engine can ramp:
    # short early segments harvest terminals quickly (time-to-first-exploit
    # depends on the FIRST tx-end replay), long late segments amortize the
    # link round trip once the frontier is warm
    k_limit: jnp.ndarray = np.int32(1 << 30)  # default: caps.K governs


# fork-grant selection modes (cfg.sel_mode)
SEL_NONE = 0  # slot order (no strategy preference)
SEL_DEEP = 1  # deepest parents first (depth-first flavor)
SEL_SHALLOW = 2  # shallowest parents first (breadth-first flavor)
SEL_COVERAGE = 3  # forks targeting not-yet-visited code first
SEL_BEAM = 4  # highest annotation search_importance first (beam search,
# reference laser/ethereum/strategy/beam.py:7-31; the score column is the
# batched beam_priority)


def build_segment(caps: Caps):
    """Build the jitted segment program (code tables arrive as arguments)."""

    R, STK, MEM, STO, CON, EVT = caps.R, caps.STK, caps.MEM, caps.STO, caps.CON, caps.EVT

    # ------------------------------------------------------------------
    # per-path step
    # ------------------------------------------------------------------

    def path_step(st: FrontierState, ids, arena: ArenaDev, code: CodeDev,
                  cfg: CfgScalars):
        """st: per-path slice (no leading B); ids: [R] reserved arena rows."""
        # per-path code identity: every table read is a SCALAR (cid, idx)
        # gather — [B] elements total under vmap.  Never materialize a
        # per-path table row (code.fam[cid] would broadcast [B, N] per step,
        # the same HBM hazard as closing over the arena in handlers).
        cid = jnp.clip(st.code_id, 0, code.fam.shape[0] - 1)
        max_depth, loop_bound = cfg.max_depth, cfg.loop_bound
        row_zero, row_one = cfg.row_zero, cfg.row_one
        # packed-code paging: table rows hold the resident window
        # [pbase, pbase + N); st.pc stays the TRUE instruction index and
        # every instruction-axis gather uses the window-relative index.
        # A pc outside the window dispatches F_PAGEFAULT (halt for a host
        # repack) — the clamped gathers below then read garbage rows that
        # the fam override keeps unreachable.
        rel = st.pc - code.pbase[cid]
        infault = (rel < 0) | (rel >= code.fam.shape[1])
        pc = jnp.clip(rel, 0, code.fam.shape[1] - 1)
        fam = jnp.where(infault, O.F_PAGEFAULT, code.fam[cid, pc])
        aux = code.aux[cid, pc]
        arity = jnp.where(infault, 0, code.arity[cid, pc])
        running = (st.halt == O.H_RUNNING) & (st.seed >= 0)

        gas_pre = (st.gas_min, st.gas_max)

        # operand rows in pop order (pre-dispatch; underflow handled below)
        def opnd(j):
            idx = jnp.clip(st.stack_len - 1 - j, 0, STK - 1)
            return jnp.where(j < arity, st.stack[idx], -1)

        pops = jnp.stack([opnd(j) for j in range(7)])

        underflow = st.stack_len < arity

        # ------------------------------------------------------------------
        # Hoisted arena/table reads.  CRITICAL for memory: under vmap, the
        # lax.switch batching rule materializes a [B, ...] broadcast of every
        # UNBATCHED array its branches touch, per branch — closing over the
        # arena ([ARENA, 16]) inside handlers costs B x ARENA x 16 x 4 bytes
        # x n_branches of HBM at compile time (observed 16 GB at B=256).
        # Handlers below must therefore only consume these per-path gathers.
        # ------------------------------------------------------------------

        def aisc(r):
            return jnp.where(r >= 0, arena.isconst[jnp.clip(r, 0, None)], False)

        def aval(r):
            return arena.val[jnp.clip(r, 0, None)]

        pop_c = jnp.stack([aisc(pops[j]) for j in range(7)])  # [7] bool
        pop_v = jnp.stack([aval(pops[j]) for j in range(7)])  # [7, 16] u32

        def conc_from(c, v):
            """(is_small_concrete, byte_address) from a popped operand."""
            small = c & (jnp.max(v[2:]) == 0) & (v[1] < 16)  # < 2^20
            return small, (v[0] | (v[1] << 16)).astype(I32)

        ok_addr0, addr0 = conc_from(pop_c[0], pop_v[0])
        ok_addr1, addr1 = conc_from(pop_c[1], pop_v[1])

        def valid_dest(addr):
            a = jnp.clip(addr, 0, code.jumpmap.shape[1] - 1)
            idx = code.jumpmap[cid, a]
            return (addr < code.jumpmap.shape[1]) & (idx >= 0), idx

        valid0, jidx0 = valid_dest(addr0)
        lid_pc = code.loopid[cid, pc]

        rows0 = NewRows(
            op=jnp.zeros(R, I32),
            a=jnp.full(R, -1, I32),
            b=jnp.full(R, -1, I32),
            c=jnp.full(R, -1, I32),
            width=jnp.zeros(R, I32),
            val=jnp.zeros((R, 16), jnp.uint32),
            isconst=jnp.zeros(R, bool),
        )
        no_fork = Fork(
            want=jnp.asarray(False),
            target=jnp.asarray(0, I32),
            dest_row=jnp.asarray(-1, I32),
            word_row=jnp.asarray(-1, I32),
            cond_row=jnp.asarray(-1, I32),
            ncond_row=jnp.asarray(-1, I32),
        )

        # tiny helpers over the per-path slice -------------------------------
        def set_row(rows, k, op, a=-1, b=-1, c=-1, width=256, val=None, isconst=False):
            rows = rows._replace(
                op=rows.op.at[k].set(op),
                a=rows.a.at[k].set(a),
                b=rows.b.at[k].set(b),
                c=rows.c.at[k].set(c),
                width=rows.width.at[k].set(width),
                isconst=rows.isconst.at[k].set(isconst),
            )
            if val is not None:
                rows = rows._replace(val=rows.val.at[k].set(val))
            return rows

        def stack_after_pop(n):
            return st.stack_len - n

        def push1(stack, length, row):
            ok = length < STK
            stack = stack.at[jnp.clip(length, 0, STK - 1)].set(
                jnp.where(ok, row, stack[jnp.clip(length, 0, STK - 1)])
            )
            return stack, length + 1, ok

        class Out(NamedTuple):
            st: FrontierState
            rows: NewRows
            fork: Fork
            res_row: jnp.ndarray  # pushed result row (-1 none)
            ev_ops: jnp.ndarray  # [7] operand rows for the event

        def base_out(st2, rows=rows0, fork=no_fork, res=-1):
            return Out(
                st=st2,
                rows=rows,
                fork=fork,
                res_row=jnp.asarray(res, I32),
                ev_ops=pops,
            )

        def halted(kind):
            return base_out(st._replace(halt=jnp.asarray(kind, I32)))

        def pushed(rows, row, extra_pop=0, res=None):
            """Pop ``arity`` (already accounted) push one row."""
            length = stack_after_pop(arity)
            stack, length, ok = push1(st.stack, length, row)
            st2 = st._replace(stack=stack, stack_len=length)
            out = base_out(st2, rows=rows, res=row if res is None else res)
            return out, ok

        # ----- handlers -----------------------------------------------------

        def h_park(_):
            return halted(O.H_PARK)

        def h_page_fault(_):
            # pc left the resident window: freeze the path exactly where
            # it is (no pc advance, no gas) so the host can repack the
            # window and re-inject at the SAME pc
            return halted(O.H_PAGE_FAULT)

        def h_stop(_):
            return halted(O.H_STOP)

        def h_push_checked(_):
            out, ok = pushed(rows0, aux)
            return jax.tree.map(
                lambda a, b: jnp.where(ok, a, b), out, halted(O.H_PARK)
            )

        def h_dup(_):
            idx = jnp.clip(st.stack_len - aux, 0, STK - 1)
            row = st.stack[idx]
            stack, length, ok = push1(st.stack, st.stack_len, row)
            out = base_out(st._replace(stack=stack, stack_len=length), res=row)
            return jax.tree.map(lambda a, b: jnp.where(ok, a, b), out, halted(O.H_PARK))

        def h_swap(_):
            i = jnp.clip(st.stack_len - 1, 0, STK - 1)
            j = jnp.clip(st.stack_len - 1 - aux, 0, STK - 1)
            a, b = st.stack[i], st.stack[j]
            stack = st.stack.at[i].set(b).at[j].set(a)
            return base_out(st._replace(stack=stack))

        def h_pop(_):
            return base_out(st._replace(stack_len=stack_after_pop(1)))

        # cheap folds only: the division family and EXP stay symbolic on
        # device even for concrete operands (their fold loops would dominate
        # the fused step kernel); the host decode folds them for free
        _BIN_FOLDS = {
            O.A_ADD: lambda x, y: bv.add(x, y, 256),
            O.A_SUB: lambda x, y: bv.sub(x, y, 256),
            O.A_MUL: lambda x, y: bv.mul(x, y, 256),
            O.A_AND: lambda x, y: bv.and_(x, y, 256),
            O.A_OR: lambda x, y: bv.or_(x, y, 256),
            O.A_XOR: lambda x, y: bv.xor(x, y, 256),
            O.A_SHL: lambda x, y: bv.shl(x, y, 256),
            O.A_LSHR: lambda x, y: bv.lshr(x, y, 256),
            O.A_ASHR: lambda x, y: bv.ashr(x, y, 256),
        }

        def h_binop(_):
            code = aux & 0xFF
            swap = (aux & 256) != 0
            p0, p1 = pops[0], pops[1]
            # term operand order: (left, right); shifts pop (shift, value)
            left = jnp.where(swap, p1, p0)
            right = jnp.where(swap, p0, p1)
            foldable = jnp.asarray(False)
            for opc in _BIN_FOLDS:
                foldable = foldable | (code == opc)
            both_const = pop_c[0] & pop_c[1] & foldable
            lv = jnp.where(swap, pop_v[1], pop_v[0])
            rv = jnp.where(swap, pop_v[0], pop_v[1])
            folded = jnp.zeros((16,), jnp.uint32)
            for opc, fn in _BIN_FOLDS.items():
                folded = jnp.where(code == opc, fn(lv, rv), folded)
            rows_c = set_row(rows0, 0, O.A_CONST, val=folded, isconst=True)
            rows_s = set_row(rows0, 0, code, a=left, b=right)
            rows = jax.tree.map(
                lambda a, b: jnp.where(both_const, a, b), rows_c, rows_s
            )
            out, ok = pushed(rows, ids[0])
            return jax.tree.map(lambda a, b: jnp.where(ok, a, b), out, halted(O.H_PARK))

        def h_cmp(_):
            p0, p1 = pops[0], pops[1]
            both_const = pop_c[0] & pop_c[1]
            lv, rv = pop_v[0], pop_v[1]
            t = jnp.asarray(False)
            for opc, fn in (
                (O.A_ULT, lambda: bv.ult(lv, rv)),
                (O.A_UGT, lambda: bv.ult(rv, lv)),
                (O.A_SLT, lambda: bv.slt(lv, rv, 256)),
                (O.A_SGT, lambda: bv.slt(rv, lv, 256)),
                (O.A_EQ, lambda: bv.eq(lv, rv)),
            ):
                t = jnp.where(aux == opc, fn(), t)
            const_row = jnp.where(t, row_one, row_zero)
            # symbolic: cmp bool row + ITE word row
            rows_s = set_row(rows0, 0, aux, a=p0, b=p1, width=0)
            rows_s = set_row(rows_s, 1, O.A_ITEW, a=ids[0], b=row_one, c=row_zero)
            res_row = jnp.where(both_const, const_row, ids[1])
            rows = jax.tree.map(
                lambda a, b: jnp.where(both_const, a, b), rows0, rows_s
            )
            out, ok = pushed(rows, res_row)
            return jax.tree.map(lambda a, b: jnp.where(ok, a, b), out, halted(O.H_PARK))

        def h_iszero(_):
            p0 = pops[0]
            is_c = pop_c[0]
            z = bv.is_zero(pop_v[0])
            const_row = jnp.where(z, row_one, row_zero)
            rows_s = set_row(rows0, 0, O.A_EQZ, a=p0, width=0)
            rows_s = set_row(rows_s, 1, O.A_ITEW, a=ids[0], b=row_one, c=row_zero)
            res_row = jnp.where(is_c, const_row, ids[1])
            rows = jax.tree.map(lambda a, b: jnp.where(is_c, a, b), rows0, rows_s)
            out, ok = pushed(rows, res_row)
            return jax.tree.map(lambda a, b: jnp.where(ok, a, b), out, halted(O.H_PARK))

        def h_not(_):
            p0 = pops[0]
            is_c = pop_c[0]
            rows_c = set_row(rows0, 0, O.A_CONST, val=bv.not_(pop_v[0], 256), isconst=True)
            rows_s = set_row(rows0, 0, O.A_NOT, a=p0)
            rows = jax.tree.map(lambda a, b: jnp.where(is_c, a, b), rows_c, rows_s)
            out, ok = pushed(rows, ids[0])
            return jax.tree.map(lambda a, b: jnp.where(ok, a, b), out, halted(O.H_PARK))

        def h_envpush(_):
            row = st.ctx[aux]
            out, ok = pushed(rows0, row)
            return jax.tree.map(lambda a, b: jnp.where(ok, a, b), out, halted(O.H_PARK))

        def h_calldataload(_):
            rows = set_row(rows0, 0, O.A_CDLOAD, a=pops[0], b=st.ctx[CTX_SEED])
            out, ok = pushed(rows, ids[0])
            return jax.tree.map(lambda a, b: jnp.where(ok, a, b), out, halted(O.H_PARK))

        def h_balance(_):
            rows = set_row(rows0, 0, O.A_SELECT, a=st.ctx[CTX_BALANCES], b=pops[0])
            out, ok = pushed(rows, ids[0])
            return jax.tree.map(lambda a, b: jnp.where(ok, a, b), out, halted(O.H_PARK))

        def h_selfbalance(_):
            rows = set_row(
                rows0, 0, O.A_SELECT, a=st.ctx[CTX_BALANCES], b=st.ctx[CTX_ADDRESS]
            )
            out, ok = pushed(rows, ids[0])
            return jax.tree.map(lambda a, b: jnp.where(ok, a, b), out, halted(O.H_PARK))

        def h_gaspush(_):
            rows = set_row(rows0, 0, O.A_VARF, a=pc)
            out, ok = pushed(rows, ids[0])
            return jax.tree.map(lambda a, b: jnp.where(ok, a, b), out, halted(O.H_PARK))

        def h_msize(_):
            size = st.mem_size.astype(jnp.uint32)
            val = jnp.zeros((16,), jnp.uint32)
            val = val.at[0].set(size & 0xFFFF).at[1].set(size >> 16)
            rows = set_row(rows0, 0, O.A_CONST, val=val, isconst=True)
            out, ok = pushed(rows, ids[0])
            return jax.tree.map(lambda a, b: jnp.where(ok, a, b), out, halted(O.H_PARK))

        # ---- memory ----

        def mem_lookup(addr):
            hit = (st.mem_addr == addr) & (jnp.arange(MEM) < st.mem_len)
            any_hit = jnp.any(hit)
            idx = jnp.argmax(hit)
            return any_hit, st.mem_val[idx]

        def mem_overlap_miss(addr):
            """True when a live entry overlaps [addr, addr+32) but is not an
            exact hit: the 32-byte window would straddle a stored word, which
            the entry model cannot compose — the path must park.  (Stores
            keep live entries mutually disjoint, see h_mstore.)"""
            live = jnp.arange(MEM) < st.mem_len
            near = (jnp.abs(st.mem_addr - addr) < 32) & live
            exact = (st.mem_addr == addr) & live
            return (near & ~exact).any()

        def mem_gas(st2, addr, size):
            new_size = jnp.maximum(st2.mem_size, ((addr + size + 31) // 32) * 32)
            cost = _memgas(new_size) - _memgas(st2.mem_size)
            return st2._replace(
                mem_size=new_size,
                gas_min=st2.gas_min + cost,
                gas_max=st2.gas_max + cost,
            )

        def h_mload(_):
            ok_addr, addr = ok_addr0, addr0
            any_hit, val_row = mem_lookup(addr)
            row = jnp.where(any_hit, val_row, row_zero)
            st2 = mem_gas(st._replace(), addr, 32)
            length = stack_after_pop(1)
            stack, length, ok = push1(st2.stack, length, row)
            out = base_out(st2._replace(stack=stack, stack_len=length), res=row)
            good = ok_addr & ok & ~mem_overlap_miss(addr)
            return jax.tree.map(lambda a, b: jnp.where(good, a, b), out, halted(O.H_PARK))

        def h_mstore(_):
            ok_addr, addr = ok_addr0, addr0
            val_row = pops[1]
            # exact hit -> overwrite; straddling a different entry -> park
            # (keeps live entries mutually disjoint, the invariant the
            # read-side straddle detection relies on)
            live = jnp.arange(MEM) < st.mem_len
            exact = (st.mem_addr == addr) & live
            overlap = mem_overlap_miss(addr)
            any_exact = exact.any()
            idx = jnp.where(any_exact, jnp.argmax(exact), st.mem_len)
            ok_cap = idx < MEM
            mem_addr = st.mem_addr.at[jnp.clip(idx, 0, MEM - 1)].set(addr)
            mem_val = st.mem_val.at[jnp.clip(idx, 0, MEM - 1)].set(val_row)
            st2 = st._replace(
                mem_addr=mem_addr,
                mem_val=mem_val,
                mem_len=jnp.where(any_exact, st.mem_len, st.mem_len + 1),
                stack_len=stack_after_pop(2),
            )
            st2 = mem_gas(st2, addr, 32)
            out = base_out(st2)
            good = ok_addr & ~overlap & ok_cap
            return jax.tree.map(lambda a, b: jnp.where(good, a, b), out, halted(O.H_PARK))

        def h_sha3(_):
            ok_off, off = ok_addr0, addr0
            ok_len, ln = ok_addr1, addr1
            words = (ln + 31) // 32
            good = ok_off & ok_len & (ln > 0) & (ln % 32 == 0) & (words <= 4)
            # gather word rows off, off+32, ...
            w_rows = []
            for w in range(4):
                hit, vr = mem_lookup(off + 32 * w)
                w_rows.append(jnp.where(hit, vr, row_zero))
                # a straddling entry in a word we hash makes the gather wrong
                good = good & jnp.where(
                    w < words, ~mem_overlap_miss(off + 32 * w), True
                )
            # build concat chain: data = w0 for words==1,
            # concat(w0,w1) etc.  rows: up to 3 concats (ids 0..2) + keccak id3
            rows = rows0
            cur = w_rows[0]
            cur_w = jnp.asarray(256, I32)
            for w in range(1, 4):
                need = words > w
                rows = jax.tree.map(
                    lambda a, b: jnp.where(need, a, b),
                    set_row(rows, w - 1, O.A_CONCAT, a=cur, b=w_rows[w],
                            width=cur_w + 256),
                    rows,
                )
                cur = jnp.where(need, ids[w - 1], cur)
                cur_w = jnp.where(need, cur_w + 256, cur_w)
            rows = set_row(rows, 3, O.A_KECCAK, a=cur, width=256)
            sha_gas = 30 + 6 * words
            st2 = mem_gas(
                st._replace(gas_min=st.gas_min + sha_gas, gas_max=st.gas_max + sha_gas),
                off, ln,
            )
            length = stack_after_pop(2)
            stack, length, ok = push1(st2.stack, length, ids[3])
            out = base_out(
                st2._replace(stack=stack, stack_len=length), rows=rows, res=ids[3]
            )
            good = good & ok
            return jax.tree.map(lambda a, b: jnp.where(good, a, b), out, halted(O.H_PARK))

        # ---- storage ----

        def h_sload(_):
            key = pops[0]
            live = jnp.arange(STO) < st.sto_len
            hit = (st.sto_key == key) & live
            any_hit = hit.any()
            hit_val = st.sto_val[jnp.argmax(hit)]
            # miss: select row over current storage array + cache it
            rows = set_row(rows0, 0, O.A_SELECT, a=st.ctx[CTX_STORAGE], b=key)
            res = jnp.where(any_hit, hit_val, ids[0])
            idx = st.sto_len
            ok_cap = any_hit | (idx < STO)
            sto_key = st.sto_key.at[jnp.clip(idx, 0, STO - 1)].set(
                jnp.where(any_hit, st.sto_key[jnp.clip(idx, 0, STO - 1)], key)
            )
            sto_val = st.sto_val.at[jnp.clip(idx, 0, STO - 1)].set(
                jnp.where(any_hit, st.sto_val[jnp.clip(idx, 0, STO - 1)], ids[0])
            )
            st2 = st._replace(
                sto_key=sto_key,
                sto_val=sto_val,
                sto_len=jnp.where(any_hit, st.sto_len, st.sto_len + 1),
            )
            length = stack_after_pop(1)
            stack, length, ok = push1(st2.stack, length, res)
            rows = jax.tree.map(lambda a, b: jnp.where(any_hit, a, b), rows0, rows)
            out = base_out(
                st2._replace(stack=stack, stack_len=length), rows=rows, res=res
            )
            good = ok_cap & ok
            return jax.tree.map(lambda a, b: jnp.where(good, a, b), out, halted(O.H_PARK))

        def h_sstore(_):
            key, val = pops[0], pops[1]
            rows = set_row(rows0, 0, O.A_STORE, a=st.ctx[CTX_STORAGE], b=key, c=val,
                           width=0)
            live = jnp.arange(STO) < st.sto_len
            hit = (st.sto_key == key) & live
            any_hit = hit.any()
            idx = jnp.where(any_hit, jnp.argmax(hit), st.sto_len)
            ok_cap = idx < STO
            sto_key = st.sto_key.at[jnp.clip(idx, 0, STO - 1)].set(key)
            sto_val = st.sto_val.at[jnp.clip(idx, 0, STO - 1)].set(val)
            st2 = st._replace(
                sto_key=sto_key,
                sto_val=sto_val,
                sto_len=jnp.where(any_hit, st.sto_len, st.sto_len + 1),
                ctx=st.ctx.at[CTX_STORAGE].set(ids[0]),
                stack_len=stack_after_pop(2),
            )
            out = base_out(st2, rows=rows)
            return jax.tree.map(lambda a, b: jnp.where(ok_cap, a, b), out, halted(O.H_PARK))

        # ---- control flow ----

        def h_jump(_):
            valid, idx = valid0, jidx0
            good = ok_addr0 & valid
            st2 = st._replace(
                pc=idx,
                depth=st.depth + 1,
                stack_len=stack_after_pop(1),
            )
            out = base_out(st2)
            return jax.tree.map(lambda a, b: jnp.where(good, a, b), out,
                                halted(O.H_INVALID))

        def h_jumpi(_):
            dest_row, word_row = pops[0], pops[1]
            word_const = pop_c[1]
            truth = ~bv.is_zero(pop_v[1])
            valid, idx = valid0, jidx0
            can_take = ok_addr0 & valid

            # constraint rows (allocated regardless; decode folds constants):
            # cond = (word != 0); ncond = Not(cond)   [host jumpi_ parity]
            rows = set_row(rows0, 0, O.A_NE, a=word_row, b=row_zero, width=0)
            rows = set_row(rows, 1, O.A_BNOT, a=ids[0], width=0)
            cond_row, ncond_row = ids[0], ids[1]

            # concrete condition: single branch, no fork
            def concrete_case():
                take = truth & can_take
                dead = truth & ~can_take
                new_pc = jnp.where(take, idx, st.pc + 1)
                app_row = jnp.where(take, cond_row, ncond_row)
                cl = jnp.clip(st.cons_len, 0, CON - 1)
                cons = jnp.where(dead, st.cons, st.cons.at[cl].set(app_row))
                ok_cons = st.cons_len < CON
                st2 = st._replace(
                    pc=new_pc,
                    depth=st.depth + 1,
                    stack_len=stack_after_pop(2),
                    cons=cons,
                    cons_len=jnp.where(dead, st.cons_len, st.cons_len + 1),
                    halt=jnp.where(dead, O.H_INVALID, st.halt),
                )
                ok = ok_cons | dead
                st2 = jax.tree.map(
                    lambda a, b: jnp.where(ok, a, b), st2,
                    st._replace(halt=jnp.asarray(O.H_PARK, I32)),
                )
                return base_out(st2, rows=rows)

            # symbolic condition (host jumpi_:791-823).  If the taken branch
            # is viable the path state is left UNTOUCHED here and the batch
            # phase applies both sides — a denied fork (batch full) must see
            # the pristine pre-JUMPI state so it can re-run later.  If only
            # the fall-through survives, apply it in place.
            def symbolic_case():
                cl = jnp.clip(st.cons_len, 0, CON - 1)
                ok_cons = st.cons_len < CON
                want = can_take & ok_cons

                fall_only = st._replace(
                    pc=st.pc + 1,
                    depth=st.depth + 1,
                    stack_len=stack_after_pop(2),
                    cons=st.cons.at[cl].set(ncond_row),
                    cons_len=st.cons_len + 1,
                )
                fall_only = jax.tree.map(
                    lambda a, b: jnp.where(ok_cons, a, b), fall_only,
                    st._replace(halt=jnp.asarray(O.H_PARK, I32)),
                )
                st2 = jax.tree.map(
                    lambda a, b: jnp.where(can_take, a, b),
                    st._replace(halt=jnp.where(ok_cons, st.halt,
                                               jnp.asarray(O.H_PARK, I32))),
                    fall_only,
                )
                fork = Fork(
                    want=want,
                    target=idx,
                    dest_row=dest_row,
                    word_row=word_row,
                    cond_row=cond_row,
                    ncond_row=ncond_row,
                )
                return base_out(st2, rows=rows, fork=fork)

            return jax.tree.map(
                lambda a, b: jnp.where(word_const, a, b),
                concrete_case(), symbolic_case(),
            )

        def h_jumpdest(_):
            lid = lid_pc
            tracked = lid >= 0  # ids beyond the loops cap are unbounded
            slot = jnp.clip(lid, 0, None)
            count = st.loops[slot] + 1
            loops = jnp.where(tracked, st.loops.at[slot].set(count), st.loops)
            over = tracked & (loop_bound > 0) & (count > loop_bound)
            st2 = st._replace(
                loops=loops, halt=jnp.where(over, O.H_LOOP, st.halt)
            )
            return base_out(st2)

        def h_log(_):
            return base_out(st._replace(stack_len=stack_after_pop(arity)))

        def h_return(_):
            kind = jnp.where(aux == 1, O.H_REVERT, O.H_RETURN)
            return base_out(
                st._replace(halt=kind, stack_len=stack_after_pop(2))
            )

        def h_selfdestruct(_):
            return base_out(
                st._replace(
                    halt=jnp.asarray(O.H_SELFDESTRUCT, I32),
                    stack_len=stack_after_pop(1),
                )
            )

        def h_invalid(_):
            return halted(O.H_INVALID)

        def h_signextend(_):
            b_row, x_row = pops[0], pops[1]
            b_c, x_c = pop_c[0], pop_c[1]
            bval = pop_v[0]
            b_small = (jnp.max(bval[1:]) == 0) & (bval[0] < 31)
            # fold: both concrete
            bits = (8 * (bval[0] + 1)).astype(I32)
            x = pop_v[1]
            mask_c = bv.shl(
                bv.from_ints(1, 256), jnp.full((16,), 0, jnp.uint32).at[0].set(
                    bits.astype(jnp.uint32)), 256,
            )
            mask_m1 = bv.sub(mask_c, bv.from_ints(1, 256), 256)
            low = bv.and_(x, mask_m1, 256)
            # sign bit: bit (bits-1)
            sign_word = bv.lshr(
                x, jnp.zeros((16,), jnp.uint32).at[0].set((bits - 1).astype(jnp.uint32)),
                256,
            )
            neg = (sign_word[0] & 1) == 1
            high = bv.not_(mask_m1, 256)
            folded = jnp.where(neg, bv.or_(low, high, 256), low)
            folded = jnp.where(b_small, folded, x)  # b >= 31 -> x unchanged
            rows_c = set_row(rows0, 0, O.A_CONST, val=folded, isconst=True)
            rows_m = set_row(rows0, 0, O.A_SIGNEXT, a=b_row, b=x_row)
            both = b_c & x_c
            rows = jax.tree.map(lambda a, b2: jnp.where(both, a, b2), rows_c, rows_m)
            out, ok = pushed(rows, ids[0])
            return jax.tree.map(lambda a, b2: jnp.where(ok, a, b2), out, halted(O.H_PARK))

        def h_byte(_):
            i_row, w_row = pops[0], pops[1]
            both = pop_c[0] & pop_c[1]
            iv = pop_v[0]
            small = (jnp.max(iv[1:]) == 0) & (iv[0] < 32)
            # byte index from the big end: byte i = bits [8*(31-i), +8)
            lo_bit = (8 * (31 - jnp.clip(iv[0], 0, 31))).astype(jnp.uint32)
            shifted = bv.lshr(
                pop_v[1], jnp.zeros((16,), jnp.uint32).at[0].set(lo_bit), 256
            )
            folded = jnp.zeros((16,), jnp.uint32).at[0].set(shifted[0] & 0xFF)
            folded = jnp.where(small, folded, jnp.zeros((16,), jnp.uint32))
            rows_c = set_row(rows0, 0, O.A_CONST, val=folded, isconst=True)
            rows_m = set_row(rows0, 0, O.A_BYTE, a=i_row, b=w_row)
            rows = jax.tree.map(lambda a, b2: jnp.where(both, a, b2), rows_c, rows_m)
            out, ok = pushed(rows, ids[0])
            return jax.tree.map(lambda a, b2: jnp.where(ok, a, b2), out, halted(O.H_PARK))

        def h_addmod(_):
            rows = set_row(rows0, 0, aux, a=pops[0], b=pops[1], c=pops[2])
            out, ok = pushed(rows, ids[0])
            return jax.tree.map(lambda a, b2: jnp.where(ok, a, b2), out, halted(O.H_PARK))

        handlers = [
            h_park,  # F_PARK
            h_stop,  # F_STOP
            h_push_checked,  # F_PUSH
            h_dup,  # F_DUP
            h_swap,  # F_SWAP
            h_pop,  # F_POP
            h_binop,  # F_BINOP
            h_cmp,  # F_CMP
            h_iszero,  # F_ISZERO
            h_not,  # F_NOTOP
            h_envpush,  # F_ENVPUSH
            h_calldataload,  # F_CALLDATALOAD
            h_balance,  # F_BALANCE
            h_selfbalance,  # F_SELFBALANCE
            h_sha3,  # F_SHA3
            h_mload,  # F_MLOAD
            h_mstore,  # F_MSTORE
            h_sload,  # F_SLOAD
            h_sstore,  # F_SSTORE
            h_jump,  # F_JUMP
            h_jumpi,  # F_JUMPI
            h_jumpdest,  # F_JUMPDEST
            h_log,  # F_LOG
            h_return,  # F_RETURN
            h_selfdestruct,  # F_SELFDESTRUCT
            h_invalid,  # F_INVALID
            h_gaspush,  # F_GASPUSH
            h_msize,  # F_MSIZE
            h_signextend,  # F_SIGNEXTEND
            h_byte,  # F_BYTEOP
            h_addmod,  # F_ADDMODOP
            h_park,  # F_MSTORE8 (parked in v1)
            h_page_fault,  # F_PAGEFAULT (synthesized by the window check)
        ]

        out = jax.lax.switch(jnp.clip(fam, 0, len(handlers) - 1), handlers, None)

        # STATICCALL write protection: a state-mutating op in a static
        # frame halts as a terminal; its E_TERMINAL replay re-executes the
        # op on the host carrier, whose StateTransition raises the real
        # WriteProtection (instructions.py is_state_mutation_instruction)
        write_viol = (st.static != 0) & (
            (fam == O.F_SSTORE) | (fam == O.F_LOG) | (fam == O.F_SELFDESTRUCT)
        )
        out = jax.tree.map(
            lambda a, b: jnp.where(write_viol, a, b),
            base_out(st._replace(halt=jnp.asarray(O.H_INVALID, I32))), out,
        )

        # underflow: exceptional halt, path dies silently
        # (reference svm.py:289-295 -> _handle_vm_exception -> [])
        out = jax.tree.map(
            lambda a, b: jnp.where(underflow, a, b),
            base_out(st._replace(halt=jnp.asarray(O.H_INVALID, I32))), out,
        )

        st2 = out.st

        # a path waiting on the batch-phase fork decision stays pristine
        pending = out.fork.want

        # pc advance for handlers that didn't move it (host StateTransition)
        terminalish = st2.halt != O.H_RUNNING
        st2 = st2._replace(
            pc=jnp.where(
                pending | terminalish | (st2.pc != st.pc), st2.pc, st2.pc + 1
            )
        )
        # static opcode gas on survivors (host charges after the handler;
        # terminal handlers end the tx first and parked ops re-execute on
        # host; forking paths are charged in the batch phase)
        skip_gas = terminalish | pending
        st2 = st2._replace(
            gas_min=jnp.where(
                skip_gas, st2.gas_min, st2.gas_min + code.gmin[cid, pc]
            ),
            gas_max=jnp.where(
                skip_gas, st2.gas_max, st2.gas_max + code.gmax[cid, pc]
            ),
        )
        # depth cap (host strategy drops deeper states silently)
        st2 = st2._replace(
            halt=jnp.where(
                (st2.depth > max_depth) & (st2.halt == O.H_RUNNING),
                O.H_DEPTH, st2.halt,
            )
        )

        # ---- event emission.  Three shapes:
        #   * hooked / terminal ops: E_HOOK / E_TERMINAL with operand rows;
        #   * non-forking JUMPI (concrete cond or invalid taken dest):
        #     E_FORK with [dest, word, appended-constraint] rows, the decided
        #     next pc in the res slot, extra = -3 when the path died;
        #   * forking JUMPI: emitted by the batch phase (child slot unknown
        #     here); parked ops re-execute fully on host and need no event.
        is_jumpi = fam == O.F_JUMPI
        terminal_halt = (
            (st2.halt == O.H_STOP)
            | (st2.halt == O.H_RETURN)
            | (st2.halt == O.H_REVERT)
            | (st2.halt == O.H_SELFDESTRUCT)
            | (st2.halt == O.H_INVALID)
        )
        kind = jnp.where(
            is_jumpi, O.E_FORK,
            jnp.where(terminal_halt, O.E_TERMINAL, O.E_HOOK),
        )
        # device detector predicate: hooks declared no-op on all-concrete
        # operands (IntegerArithmetics arithmetic, ArbitraryJump JUMP) emit
        # no event when operand concreteness proves the no-op — the walker
        # then never replays them (probe-then-confirm at event granularity)
        all_conc = jnp.asarray(True)
        for j in range(7):
            all_conc = all_conc & ((arity <= j) | pop_c[j])
        # MSTORE panic gate: the declared hook observes ONLY concrete
        # values whose top 32 bits are the solc Panic(uint256) selector
        # 0x4E487B71 (it no-ops on symbolic values too, value.value is
        # None there) — suppress everything else (16-bit limbs: bits
        # 224-239 are limb 14, 240-255 limb 15)
        nonpanic = ~(
            pop_c[1] & (pop_v[1][14] == 0x7B71) & (pop_v[1][15] == 0x4E48)
        )
        emit = (
            code.event[cid, pc]
            & ~infault  # faulted paths re-inject and run the op then
            & ~pending
            & ~underflow
            & ~(code.concskip[cid, pc] & all_conc)
            & ~(code.valgate[cid, pc] & nonpanic)
            & (st2.halt != O.H_PARK)
            & (st2.halt != O.H_PAGE_FAULT)
            & (st2.halt != O.H_DEPTH)
            & (st2.halt != O.H_LOOP)
        )
        died = st2.halt == O.H_INVALID
        last_cons = st2.cons[jnp.clip(st2.cons_len - 1, 0, CON - 1)]
        ev_ops = out.ev_ops.at[2].set(
            jnp.where(is_jumpi & ~died, last_cons, out.ev_ops[2])
        )
        res_slot = jnp.where(is_jumpi, st2.pc, out.res_row)
        extra_slot = jnp.where(is_jumpi & died, -3, -1)
        payload = jnp.concatenate([
            # event pc is the TRUE instruction index (walker contract),
            # not the window-relative gather index
            jnp.stack([kind, st.pc, gas_pre[0], gas_pre[1]]),
            ev_ops,
            jnp.stack([res_slot, extra_slot]),
        ]).astype(I32)
        ev_ok = st2.ev_len < EVT
        el = jnp.clip(st2.ev_len, 0, EVT - 1)
        events = jnp.where(
            emit & ev_ok,
            st2.events.at[el].set(payload),
            st2.events,
        )
        st2 = st2._replace(
            events=events,
            ev_len=jnp.where(emit & ev_ok, st2.ev_len + 1, st2.ev_len),
            # event buffer full: park so the host drains and continues
            halt=jnp.where(
                emit & ~ev_ok & (st2.halt == O.H_RUNNING), O.H_PARK, st2.halt
            ),
        )

        # freeze non-running paths entirely
        final = jax.tree.map(
            lambda new, old: jnp.where(running, new, old), st2, st
        )
        rows_out = jax.tree.map(
            lambda r: jnp.where(
                running, r,
                jnp.zeros_like(r) if r.dtype != bool else jnp.zeros_like(r),
            ),
            out.rows,
        )
        fork_out = jax.tree.map(
            lambda f: jnp.where(running, f, jnp.zeros_like(f)), out.fork
        )
        return final, rows_out, fork_out

    vstep = jax.vmap(path_step, in_axes=(0, 0, None, None, None))

    # ------------------------------------------------------------------
    # whole-batch step: per-path phase + arena scatter + fork grants
    # ------------------------------------------------------------------

    B = caps.B

    def batch_step(carry):
        state, arena, arena_len, t, n_exec, max_live, visited, code, cfg = carry
        running = (state.halt == O.H_RUNNING) & (state.seed >= 0)
        n_live = running.sum().astype(I32)
        n_exec = n_exec + n_live
        # width as seen DURING the segment: a whole exploration that runs
        # wide and completes within one segment must not read as narrow at
        # the (empty) harvest — the engine's narrow-memo uses this
        max_live = jnp.maximum(max_live, n_live)
        state = state._replace(steps=state.steps + running.astype(I32))
        # coverage: mark every live path's (code, pc) on the instruction
        # plane (idle slots drop).  ``visited`` is [3, C, I]: plane 0 =
        # instruction executed, planes 1/2 = JUMPI taken / fall-through
        # edges (marked below once a branch actually resolves)
        cid_live = jnp.clip(state.code_id, 0, visited.shape[1] - 1)
        cid_or_oob = jnp.where(running, cid_live, visited.shape[1])
        pc_or_oob = jnp.clip(state.pc, 0, visited.shape[2] - 1)
        visited = visited.at[0, cid_or_oob, pc_or_oob].set(True, mode="drop")
        # arena rows are reserved for LIVE paths only (prefix-sum block
        # assignment): a wide batch with few live paths must not burn B*R
        # rows per step — that exhausts the arena in ARENA/(B*R) steps.
        # Dead slots get out-of-range ids; their scatters drop.
        live_rank = jnp.cumsum(running.astype(I32)) - 1
        bases = arena_len + live_rank * R
        ids = jnp.where(
            running[:, None],
            bases[:, None] + jnp.arange(R, dtype=I32)[None, :],
            caps.ARENA,
        )
        new_state, rows, fork = vstep(state, ids, arena, code, cfg)

        # edge coverage, inline-resolved JUMPIs: a concrete condition (or
        # fall-only branch) decided inside vstep without wanting a fork.
        # Compare the successor pc against pc+1 to pick the plane; paths
        # that halted at the JUMPI (invalid dest) mark no edge, and
        # fork-wanting paths mark theirs at the grant below.
        fam_here = code.fam[
            cid_live,
            jnp.clip(state.pc - code.pbase[cid_live], 0,
                     code.fam.shape[1] - 1),
        ]
        # a faulted path has new halt H_PAGE_FAULT, so the garbage row a
        # clamped out-of-window gather reads never passes this guard
        inline_jumpi = (
            running & (fam_here == O.F_JUMPI) & ~fork.want
            & (new_state.halt == O.H_RUNNING)
        )
        nf_plane = jnp.where(new_state.pc == state.pc + 1, 2, 1)
        nf_cid = jnp.where(inline_jumpi, cid_live, visited.shape[1])
        visited = visited.at[nf_plane, nf_cid, pc_or_oob].set(
            True, mode="drop"
        )

        # arena scatter (rows are disjoint fresh slots; dead slots drop)
        flat_ids = ids.reshape(-1)
        arena = ArenaDev(
            op=arena.op.at[flat_ids].set(rows.op.reshape(-1), mode="drop"),
            a=arena.a.at[flat_ids].set(rows.a.reshape(-1), mode="drop"),
            b=arena.b.at[flat_ids].set(rows.b.reshape(-1), mode="drop"),
            c=arena.c.at[flat_ids].set(rows.c.reshape(-1), mode="drop"),
            width=arena.width.at[flat_ids].set(rows.width.reshape(-1), mode="drop"),
            val=arena.val.at[flat_ids].set(rows.val.reshape(-1, 16), mode="drop"),
            isconst=arena.isconst.at[flat_ids].set(
                rows.isconst.reshape(-1), mode="drop"
            ),
        )
        arena_len = arena_len + n_live * R

        # ---- fork grants ----
        # a grant REQUIRES room for the parent's E_FORK event: a granted
        # fork whose event is dropped orphans the child (no lineage record
        # on the host).  Full-buffer parents pend at the pristine JUMPI
        # until the next segment's drained buffer.
        buf_ok = new_state.ev_len < EVT
        want = fork.want & buf_ok
        free = new_state.seed < 0
        n_free = free.sum()
        # strategy-scored grants (the batched form of the host search
        # strategies; only matters when forks outnumber free slots): rank
        # wanters by descending score — argsort is stable, so SEL_NONE
        # (score 0) degenerates to the legacy slot order
        target_pc = jnp.clip(fork.target, 0, visited.shape[2] - 1)
        uncovered = ~visited[0, cid_live, target_pc]
        sel = cfg.sel_mode
        score = jnp.where(
            sel == SEL_DEEP, state.depth,
            jnp.where(
                sel == SEL_SHALLOW, -state.depth,
                jnp.where(
                    sel == SEL_COVERAGE,
                    uncovered.astype(I32) * (1 << 20) + state.depth,
                    jnp.where(sel == SEL_BEAM, state.score, 0),
                ),
            ),
        )
        sort_key = jnp.where(want, -score, jnp.iinfo(jnp.int32).max)
        order = jnp.argsort(sort_key)
        rank = jnp.zeros(B, I32).at[order].set(jnp.arange(B, dtype=I32))
        granted = want & (rank < n_free)
        free_list = jnp.argsort(~free)  # free slots first, ascending
        child_slot = jnp.where(
            granted, free_list[jnp.clip(rank, 0, B - 1)], B
        )

        # gather-copy children from parents
        src = jnp.arange(B, dtype=I32)
        parent_ids = jnp.arange(B, dtype=I32)
        src = src.at[child_slot].set(parent_ids, mode="drop")
        forked_into = jnp.zeros(B, bool).at[child_slot].set(granted, mode="drop")
        taken_pc = jnp.zeros(B, I32).at[child_slot].set(fork.target, mode="drop")
        cond_of_child = jnp.zeros(B, I32).at[child_slot].set(
            fork.cond_row, mode="drop"
        )

        ncond_of_parent = fork.ncond_row

        def copy_field(f):
            return jnp.where(
                forked_into.reshape((B,) + (1,) * (f.ndim - 1)), f[src], f
            )

        state2 = jax.tree.map(copy_field, new_state)

        # apply the fork to BOTH sides from the pristine pre-JUMPI state:
        # pops, depth, the JUMPI's static gas, and the branch constraint
        # (parent = fall-through + Not(cond); child = taken + cond)
        touched = granted | forked_into
        # TRUE pc of the JUMPI (branch targets, visited planes) vs the
        # window-relative row index (gas-table gathers): a forking JUMPI
        # just executed, so it is resident by construction
        jumpi_true = jnp.where(forked_into, state.pc[src], state.pc)
        # child slots copied code_id from their parent via copy_field
        cid2 = jnp.clip(state2.code_id, 0, code.fam.shape[0] - 1)
        jumpi_pc = jnp.clip(jumpi_true, 0, visited.shape[2] - 1)
        jumpi_rel = jnp.clip(jumpi_true - code.pbase[cid2], 0,
                             code.fam.shape[1] - 1)
        branch_pc = jnp.where(forked_into, taken_pc, jumpi_true + 1)
        branch_row = jnp.where(forked_into, cond_of_child, ncond_of_parent)
        # edge coverage, granted forks: the child resolves the taken edge,
        # the granting parent the fall-through edge, both at the JUMPI's
        # pc.  Denied/pending forks re-run pristine and mark nothing.
        edge_plane = jnp.where(forked_into, 1, 2)
        edge_cid = jnp.where(touched, cid2, visited.shape[1])
        visited = visited.at[edge_plane, edge_cid, jumpi_pc].set(
            True, mode="drop"
        )
        cl = jnp.clip(state2.cons_len, 0, CON - 1)
        state2 = state2._replace(
            pc=jnp.where(touched, branch_pc, state2.pc),
            depth=jnp.where(touched, state2.depth + 1, state2.depth),
            stack_len=jnp.where(touched, state2.stack_len - 2, state2.stack_len),
            gas_min=jnp.where(
                touched, state2.gas_min + code.gmin[cid2, jumpi_rel],
                state2.gas_min,
            ),
            gas_max=jnp.where(
                touched, state2.gas_max + code.gmax[cid2, jumpi_rel],
                state2.gas_max,
            ),
            cons=jnp.where(
                touched[:, None],
                state2.cons.at[jnp.arange(B), cl].set(branch_row),
                state2.cons,
            ),
            cons_len=jnp.where(touched, state2.cons_len + 1, state2.cons_len),
            events=jnp.where(
                forked_into[:, None, None],
                jnp.full_like(state2.events, -1),
                state2.events,
            ),
            ev_len=jnp.where(forked_into, 0, state2.ev_len),
            # fresh per-path step counter: the parent keeps its count, the
            # child starts at zero (per-laser total_states attribution)
            steps=jnp.where(forked_into, 0, state2.steps),
            halt=jnp.where(forked_into, O.H_RUNNING, state2.halt),
        )

        # a denied fork pends at the pristine JUMPI: the harvest re-runs it
        # once slots have been freed (or spills it to the host engine).  A
        # full event buffer also pends — the harvest drains buffers every
        # segment, so the fork can be granted next segment with a fresh one
        denied = want & ~granted
        state2 = state2._replace(
            halt=jnp.where(
                (fork.want & ~buf_ok) | denied,
                O.H_PENDING_FORK,
                state2.halt,
            )
        )
        emit_fork = granted
        payload = jnp.stack(
            [
                jnp.full(B, O.E_FORK, I32),
                state.pc,  # pc of the JUMPI itself
                state.gas_min,
                state.gas_max,
                fork.dest_row,
                fork.word_row,
                fork.cond_row,
                fork.ncond_row,
                fork.target,  # slot op4: taken-branch instruction index
                jnp.full(B, -1, I32),
                jnp.full(B, -1, I32),
                jnp.full(B, -1, I32),
                jnp.where(granted, child_slot, -1),
            ],
            axis=1,
        )
        el = jnp.clip(state2.ev_len, 0, EVT - 1)
        ev_ok = state2.ev_len < EVT
        state2 = state2._replace(
            events=jnp.where(
                (emit_fork & ev_ok)[:, None, None],
                state2.events.at[jnp.arange(B), el].set(payload),
                state2.events,
            ),
            ev_len=jnp.where(emit_fork & ev_ok, state2.ev_len + 1, state2.ev_len),
            halt=jnp.where(
                emit_fork & ~ev_ok, O.H_PARK, state2.halt
            ),
        )

        return (state2, arena, arena_len, t + 1, n_exec, max_live, visited,
                code, cfg)

    def cond(carry):
        state, _, arena_len, t, _n, _m, _v, _code, cfg = carry
        running = (state.halt == O.H_RUNNING) & (state.seed >= 0)
        room = arena_len + running.sum() * R < caps.ARENA
        k = jnp.minimum(cfg.k_limit, caps.K)
        return (t < k) & running.any() & room

    # NO-INPUT-DONATION INVARIANT: this jit must never donate its inputs.
    # The engine re-pushes state across nested drains and re-dispatches on
    # the same device buffers; donate_argnums would let XLA alias those
    # buffers into the outputs and the second dispatch would read garbage.
    # Kept as an explicit empty tuple + assert so a future "optimization"
    # trips loudly instead of corrupting path state silently.
    _SEGMENT_DONATE_ARGNUMS: tuple = ()
    assert _SEGMENT_DONATE_ARGNUMS == (), (
        "frontier segment must not donate inputs: the engine's re-dispatch "
        "paths reuse the pushed device buffers"
    )

    @partial(jax.jit, donate_argnums=_SEGMENT_DONATE_ARGNUMS)
    def segment(state: FrontierState, arena: ArenaDev, arena_len,
                visited, code: CodeDev, cfg: CfgScalars):
        carry = (state, arena, jnp.asarray(arena_len, I32),
                 jnp.asarray(0, I32), jnp.asarray(0, I32),
                 jnp.asarray(0, I32), visited, code, cfg)
        (state, arena, arena_len, t, n_exec, max_live, visited, _code,
         _cfg) = jax.lax.while_loop(cond, batch_step, carry)
        return state, arena, arena_len, n_exec, max_live, visited

    return segment


# ---------------------------------------------------------------------------
# Packed host pulls.  Every device->host transfer pays a dispatch round trip
# (anchor measured at a 100 ms dispatch round trip), and slicing with fresh
# python bounds triggers an XLA compile per distinct shape — pulling the 20
# FrontierState fields plus 7 arena slices separately cost ~5 s per harvest
# at that anchor (measured on the corpus).
# One jitted concatenation per pull makes it a single fixed-shape dispatch
# and ONE transfer; the host unpacks with numpy views.
# ---------------------------------------------------------------------------

ARENA_CHUNK = 8192  # rows per packed arena pull (22 i32 words per row)

# events are by far the largest state field ([B, EVT, EV_W]: ~2.5 MB at
# B=256, ~10 MB at B=1024) and the harvest drains them COMPLETELY every
# segment, so they are excluded from both packed transfers: the upload
# rebuilds empty buffers on device, and the download pulls only a
# size-bucketed [B, cap, EV_W] slice covering max(ev_len)
_EVENT_BUCKETS = (8, 32, 128)  # plus full EVT as the last resort


@lru_cache(maxsize=16)
def _state_packer(field_shapes: tuple):
    """Packers for the state WITHOUT the events buffer (+2 trailing scalars
    on the pull side: arena_len and n_exec ride the same transfer)."""
    names = [n for n in FrontierState._fields if n != "events"]
    shapes = list(field_shapes)
    sizes = [int(np.prod(s)) for s in shapes]
    bounds = np.cumsum([0] + sizes)
    total = int(bounds[-1])
    ev_index = names.index("ev_len")

    @jax.jit
    def pack_meta(state: FrontierState, arena_len, n_exec, max_live):
        flat = [
            f.reshape(-1)
            for name, f in zip(state._fields, state)
            if name != "events"
        ]
        flat.append(jnp.stack([
            jnp.asarray(arena_len, jnp.int32),
            jnp.asarray(n_exec, jnp.int32),
            jnp.asarray(max_live, jnp.int32),
        ]))
        return jnp.concatenate(flat)

    def unpack_host(buf: np.ndarray, events: np.ndarray):
        fields = {
            names[i]: buf[bounds[i]: bounds[i + 1]].reshape(shapes[i]).copy()
            for i in range(len(shapes))
        }
        fields["events"] = events
        state = FrontierState(**fields)
        return state, int(buf[total]), int(buf[total + 1]), int(buf[total + 2])

    def ev_len_of(buf: np.ndarray) -> np.ndarray:
        return buf[bounds[ev_index]: bounds[ev_index + 1]]

    @jax.jit
    def unpack_dev(buf, events, ev_len) -> FrontierState:
        fields = {
            names[i]: jax.lax.dynamic_slice_in_dim(buf, int(bounds[i]), sizes[i])
            .reshape(shapes[i])
            for i in range(len(shapes))
        }
        fields["events"] = events
        fields["ev_len"] = ev_len
        return FrontierState(**fields)

    return pack_meta, unpack_host, unpack_dev, ev_len_of


@partial(jax.jit, static_argnums=1)
def _pack_events(state: FrontierState, cap: int):
    return state.events[:, :cap, :].reshape(-1)


# Delta pulls pad their dynamic-length index vectors to these row counts so
# the gather programs compile a handful of times, not once per distinct
# dirty-set size (same motivation as _EVENT_BUCKETS; the full batch width is
# the last resort).
_SLOT_BUCKETS = (8, 32, 128)


@jax.jit
def _pack_meta_1d(state: FrontierState, arena_len, n_exec, max_live):
    """Every per-slot [B] field flattened into one transfer (+ the three
    trailing scalars, mirroring pack_meta)."""
    flat = [f for f in state if f.ndim == 1]
    flat.append(jnp.stack([
        jnp.asarray(arena_len, jnp.int32),
        jnp.asarray(n_exec, jnp.int32),
        jnp.asarray(max_live, jnp.int32),
    ]))
    return jnp.concatenate(flat)


@jax.jit
def _gather_rows(state: FrontierState, idx):
    """Rows ``idx`` of every 2-D field, concatenated flat (field order =
    FrontierState declaration order; events is 3-D and excluded)."""
    return jnp.concatenate(
        [f[idx].reshape(-1) for f in state if f.ndim == 2]
    )


@partial(jax.jit, static_argnums=2)
def _gather_events_rows(state: FrontierState, idx, cap: int):
    return state.events[idx, :cap, :].reshape(-1)


def _bucketed(n: int, full: int) -> int:
    return next((b for b in _SLOT_BUCKETS if b >= n and b <= full), full)


def pull_harvest(state: FrontierState, arena_len, n_exec, max_live,
                 prev: FrontierState = None, shards: int = 1):
    """Timed wrapper over :func:`_pull_harvest_impl` — this is the
    frontier's blocking device->host point, so its wall is stamped into
    the device plane's ``frontier.pull_device_s`` series (attributed to
    the dispatching bucket via the caller's dispatch scope)."""
    t0 = time.perf_counter()
    try:
        return _pull_harvest_impl(state, arena_len, n_exec, max_live,
                                  prev=prev, shards=shards)
    finally:
        _devplane.observe_pull(time.perf_counter() - t0)


def _pull_harvest_impl(state: FrontierState, arena_len, n_exec, max_live,
                       prev: FrontierState = None, shards: int = 1):
    """Device->host harvest transfer.

    ``prev=None`` (synchronous loop, sync points, mesh): ONE packed pull of
    every non-event field (+ the arena_len / n_exec / max_live scalars — no
    separate scalar round trips), then one bucket-capped events pull sized
    by max(ev_len).

    ``prev`` set (pipelined steady state, the next dispatch already
    chained): a DELTA pull.  The harvest only ever reads three things from
    a fresh mirror — per-slot scalars (halt/seed/ev_len/... drive every
    decision), the 2-D rows of slots it is about to finish or prune, and
    the new event slices — so the pull ships the [B] scalar plane plus the
    dirty rows only: slots that halted (snapshot_slot reads their
    stack/memory), slots whose constraint list grew (prune reads cons;
    append-only, so an unchanged cons_len means unchanged rows — and a
    recycled slot's mirror length is 0 after clear_slot, so fork-grant
    reuse always miscompares and pulls), and ev_len-dirty event slices.
    Everything else is carried from ``prev`` by copy; those rows are only
    ever read again by a full push, and every sync point full-pulls first
    (the pipeline passes ``prev`` only when a dispatch is chained).
    Against the full pull this drops the per-segment meta transfer from
    every [B, W] plane to ~16*B scalars + the few finishing rows.

    ``shards > 1`` (pipelined mesh run): the pulled bytes are additionally
    attributed per path-shard (slot blocks of B/shards) into the
    ``pipeline.delta_pull_bytes_by_shard`` labeled counter, so a hot shard's
    outsized pull traffic is visible per device.  Gather-pad rows are
    excluded from the attribution (they carry no slot), so the per-shard
    figures sum to slightly less than the raw transfer total."""
    assert all(f.dtype == np.int32 for f in state), (
        "packed state transfer assumes uniform int32 fields"
    )
    if prev is None:
        shapes = tuple(
            f.shape for name, f in zip(state._fields, state)
            if name != "events"
        )
        pack_meta, unpack_host, _d, ev_len_of = _state_packer(shapes)
        buf = np.asarray(pack_meta(state, arena_len, n_exec, max_live))
        max_ev = int(ev_len_of(buf).max()) if buf.size else 0
        B, EVT, EVW = state.events.shape
        cap = next((b for b in _EVENT_BUCKETS if b >= max_ev and b <= EVT),
                   EVT)
        events = np.full((B, EVT, EVW), -1, np.int32)
        if max_ev > 0:
            pulled = np.asarray(_pack_events(state, cap)).reshape(B, cap, EVW)
            events[:, :cap, :] = pulled
        return unpack_host(buf, events)

    from mythril_tpu.observability.metrics import get_registry

    B, EVT, EVW = np.asarray(prev.events).shape
    names_1d = [n for n, f in zip(prev._fields, prev)
                if np.asarray(f).ndim == 1]
    names_2d = [n for n, f in zip(prev._fields, prev)
                if np.asarray(f).ndim == 2]

    buf = np.asarray(_pack_meta_1d(state, arena_len, n_exec, max_live))
    fields = {}
    off = 0
    for n in names_1d:
        fields[n] = buf[off: off + B].copy()
        off += B
    scalars = (int(buf[off]), int(buf[off + 1]), int(buf[off + 2]))
    pulled_bytes = buf.nbytes
    n_sh = max(1, int(shards))
    # [B] planes split evenly over the contiguous slot blocks; row/event
    # gathers attribute by the pulled slot's owning shard
    shard_bytes = np.full(n_sh, buf.nbytes // n_sh, np.int64)

    halt, seed = fields["halt"], fields["seed"]
    ev_len = np.minimum(fields["ev_len"], EVT)
    dirty = (
        ((seed >= 0) & (halt != O.H_RUNNING))
        | (ev_len > 0)
        | (fields["cons_len"] != prev.cons_len)
    )
    idx = np.nonzero(dirty)[0].astype(np.int32)

    for n in names_2d:
        fields[n] = np.asarray(getattr(prev, n)).copy()
    if idx.size:
        cap_n = _bucketed(idx.size, B)
        pad = np.zeros(cap_n, np.int32)
        pad[: idx.size] = idx
        rows = np.asarray(_gather_rows(state, jnp.asarray(pad)))
        pulled_bytes += rows.nbytes
        np.add.at(shard_bytes, idx * n_sh // B, rows.nbytes // cap_n)
        off2 = 0
        for n in names_2d:
            w = fields[n].shape[1]
            block = rows[off2: off2 + cap_n * w].reshape(cap_n, w)
            fields[n][idx] = block[: idx.size]
            off2 += cap_n * w

    events = np.full((B, EVT, EVW), -1, np.int32)
    ev_idx = np.nonzero(ev_len > 0)[0].astype(np.int32)
    if ev_idx.size:
        max_ev = int(ev_len[ev_idx].max())
        cap = next((b for b in _EVENT_BUCKETS if b >= max_ev and b <= EVT),
                   EVT)
        cap_m = _bucketed(ev_idx.size, B)
        pad = np.zeros(cap_m, np.int32)
        pad[: ev_idx.size] = ev_idx
        pulled = np.asarray(
            _gather_events_rows(state, jnp.asarray(pad), cap)
        ).reshape(cap_m, cap, EVW)
        events[ev_idx, :cap, :] = pulled[: ev_idx.size]
        pulled_bytes += pulled.nbytes
        np.add.at(shard_bytes, ev_idx * n_sh // B, pulled.nbytes // cap_m)
    fields["events"] = events

    reg = get_registry()
    reg.counter("pipeline.delta_pulls").inc()
    reg.counter("pipeline.delta_pull_bytes").inc(pulled_bytes)
    if n_sh > 1:
        by_shard = reg.labeled_counter("pipeline.delta_pull_bytes_by_shard")
        for k in range(n_sh):
            by_shard[f"shard{k}"] += int(shard_bytes[k])
    return (FrontierState(**fields), *scalars)


def push_state(state: FrontierState):
    """One packed host->device transfer of the non-event fields; the event
    buffers are rebuilt EMPTY on device (the harvest drains them every
    segment, so nothing needs to cross the link)."""
    assert all(f.dtype == np.int32 for f in state), (
        "packed state transfer assumes uniform int32 fields"
    )
    shapes = tuple(
        f.shape for name, f in zip(state._fields, state) if name != "events"
    )
    _p, _h, unpack_dev, _e = _state_packer(shapes)
    buf = np.concatenate([
        np.asarray(f).reshape(-1)
        for name, f in zip(state._fields, state)
        if name != "events"
    ])
    events = jnp.full(state.events.shape, -1, jnp.int32)
    ev_len = jnp.zeros(state.ev_len.shape, jnp.int32)
    return unpack_dev(jax.device_put(buf), events, ev_len)


@partial(jax.jit, static_argnums=2)
def _pack_arena_chunk(arena: ArenaDev, lo, chunk: int):
    sl = lambda a: jax.lax.dynamic_slice_in_dim(a, lo, chunk)
    val_bits = jax.lax.bitcast_convert_type(sl(arena.val), jnp.int32)
    return jnp.concatenate([
        sl(arena.op), sl(arena.a), sl(arena.b), sl(arena.c), sl(arena.width),
        sl(arena.isconst).astype(jnp.int32), val_bits.reshape(-1),
    ])


def pull_arena_rows(dev_arena: ArenaDev, lo: int, hi: int):
    """Rows [lo, hi) as host numpy columns, chunked at a fixed shape so the
    slice program compiles once (twice for arenas smaller than the chunk).
    Returns (op, a, b, c, width, isconst, val)."""
    cols = [[] for _ in range(7)]
    cap = int(dev_arena.op.shape[0])
    C = min(ARENA_CHUNK, cap)
    pos = lo
    while pos < hi:
        eff = min(pos, max(0, cap - C))  # dynamic_slice clamps
        skip = pos - eff
        take = min(hi - pos, C - skip)
        buf = np.asarray(_pack_arena_chunk(dev_arena, eff, C))
        parts = [
            buf[0:C], buf[C:2 * C], buf[2 * C:3 * C], buf[3 * C:4 * C],
            buf[4 * C:5 * C], buf[5 * C:6 * C],
            buf[6 * C:].view(np.uint32).reshape(C, 16),
        ]
        for out, part in zip(cols, parts):
            out.append(part[skip: skip + take])
        pos += take
    return [np.concatenate(c) if len(c) > 1 else c[0] for c in cols]


# ---------------------------------------------------------------------------
# Pipelined dispatch chaining (frontier/pipeline.py).  A chained dispatch
# consumes the PREVIOUS segment's device outputs directly — no host sync —
# and folds in the host's corrections (slots the last harvest mutated) via a
# per-slot select.  Event buffers are rebuilt EMPTY for every slot at each
# chained dispatch, exactly like push_state does for a full push: the
# harvest drains them completely per segment, and letting them accumulate
# across chained segments would overflow caps.EVT.
# ---------------------------------------------------------------------------


# the non-event fields the correction upload actually merges; events/ev_len
# are rebuilt empty on device, so the correction push's (constant) event
# buffers never enter the merge — and therefore are never donated
_MERGE_FIELDS = tuple(
    n for n in FrontierState._fields if n not in ("events", "ev_len")
)


@lru_cache(maxsize=2)
def _merge_fn(donate: bool):
    """The chained-dispatch correction merge, optionally DONATING the
    correction tuple (argnum 1).  The correction buffers are freshly pushed
    per chain and never read again, so on backends with real buffer
    donation (TPU) XLA aliases them straight into the merged outputs — the
    carried frontier state never double-buffers (SNIPPETS.md [3]).  The
    segment itself still never donates (see _SEGMENT_DONATE_ARGNUMS); the
    previous output cannot be donated either, because pull_harvest reads it
    AFTER the chain is dispatched."""

    @partial(jax.jit, donate_argnums=(1,) if donate else ())
    def merge(prev: FrontierState, corr_fields, mask) -> FrontierState:
        def pick(c, p):
            m = mask.reshape((-1,) + (1,) * (p.ndim - 1))
            return jnp.where(m, c, p)

        fields = dict(zip(_MERGE_FIELDS, corr_fields))
        merged = {
            name: pick(fields[name], p) if name in fields else p
            for name, p in zip(prev._fields, prev)
        }
        merged["events"] = jnp.full_like(prev.events, -1)
        merged["ev_len"] = jnp.zeros_like(prev.ev_len)
        return FrontierState(**merged)

    return merge


def _merge_corrections(prev: FrontierState, corr: FrontierState,
                       mask) -> FrontierState:
    donate = jax.default_backend() != "cpu"  # CPU: donation unimplemented
    corr_fields = tuple(
        f for n, f in zip(corr._fields, corr) if n in _MERGE_FIELDS
    )
    return _merge_fn(donate)(prev, corr_fields, mask)


def chain_dispatch(segment, prev_out, host_state: FrontierState,
                   corr_mask: np.ndarray, code_dev, cfg,
                   arena_override=None, push_fn=None, mask_sharding=None,
                   segment_id: int = -1):
    """Dispatch the next segment on the previous segment's device outputs.

    ``prev_out`` is the 6-tuple a segment call returned (possibly still
    un-materialized futures); ``host_state`` is the host mirror whose rows
    are uploaded for the slots flagged in ``corr_mask``.  The upload is one
    packed push_state transfer — the same cost the synchronous loop pays —
    but the un-flagged slots keep the device's own (possibly further
    advanced) values, so the device never waits for the host.
    ``arena_override`` replaces the chained (dev_arena, arena_len) pair
    after a sync-point host append (re-injection rows).

    Mesh runs pass ``push_fn`` (the engine's path-sharded push) and
    ``mask_sharding`` (the [B] path sharding) so the correction upload and
    its mask land with EXACTLY the shardings the in-flight outputs carry:
    the merge and the chained segment then run as one SPMD program with
    matching in/out shardings across every chained dispatch (SNIPPETS.md
    [1]–[2]) and GSPMD inserts no resharding between them.

    ``segment_id`` is the flight deck's monotonic dispatch id — the key
    that correlates this dispatch with the pull/harvest/replay/solver
    spans it later produces; it only annotates telemetry, never the
    computation."""
    out_state, dev_arena, out_len, _n_exec, _max_live, visited = prev_out
    if arena_override is not None:
        dev_arena, out_len = arena_override
    with _otrace.span("frontier.chain_merge", cat="device",
                      segment=segment_id):
        corr = (push_fn or push_state)(host_state)
        mask = (jax.device_put(corr_mask, mask_sharding)
                if mask_sharding is not None else jax.device_put(corr_mask))
        merged = _merge_corrections(out_state, corr, mask)
        return segment(merged, dev_arena, out_len, visited, code_dev, cfg)


# Host arena rows appended at a pipeline sync point (re-injected spills) are
# shipped as fixed-shape chunks so the update program compiles once.
REINJECT_CHUNK = 256


@jax.jit
def _write_arena_chunk(arena: ArenaDev, lo, op, a, b, c, width, val,
                       isconst) -> ArenaDev:
    def upd(dst, src):
        return jax.lax.dynamic_update_slice_in_dim(dst, src, lo, 0)

    return ArenaDev(
        op=upd(arena.op, op), a=upd(arena.a, a), b=upd(arena.b, b),
        c=upd(arena.c, c), width=upd(arena.width, width),
        val=upd(arena.val, val), isconst=upd(arena.isconst, isconst),
    )


def push_arena_rows(dev_arena: ArenaDev, host_arena, lo: int,
                    hi: int) -> ArenaDev:
    """Write host arena rows [lo, hi) into the device arena.

    ONLY safe at a pipeline sync point (no segment in flight): an in-flight
    segment appends its own rows at the same indices.  Chunks are built from
    the host mirror at a fixed REINJECT_CHUNK shape; rows below ``lo`` that
    fall inside a clamped chunk are rewritten with their (identical) host
    mirror values, rows beyond ``hi`` with the mirror's zero fill — both are
    no-ops for decoding, which never follows references past arena length."""
    cap = int(dev_arena.op.shape[0])
    C = min(REINJECT_CHUNK, cap)
    pos = lo
    while pos < hi:
        eff = min(pos, max(0, cap - C))  # dynamic_update_slice clamps
        dev_arena = _write_arena_chunk(
            dev_arena, eff,
            jnp.asarray(host_arena.op[eff:eff + C]),
            jnp.asarray(host_arena.a[eff:eff + C]),
            jnp.asarray(host_arena.b[eff:eff + C]),
            jnp.asarray(host_arena.c[eff:eff + C]),
            jnp.asarray(host_arena.width[eff:eff + C]),
            jnp.asarray(host_arena.val[eff:eff + C]),
            jnp.asarray(host_arena.isconst[eff:eff + C]),
        )
        pos = eff + C
    return dev_arena


@lru_cache(maxsize=16)
def cached_segment(caps: Caps, code_cap: int, instr_cap: int, addr_cap: int,
                   loops_cap: int):
    """One compiled segment per (caps, size bucket) — shared by every
    contract batch whose stacked tables fit the bucket, and persisted
    across processes by the XLA compilation cache."""
    return build_segment(caps)
