"""Device-resident known-bits interpreter: one jitted scan per bucket.

The known-bits domain is exact uint32 limb arithmetic — precisely the
dtype discipline the packed tape VM already lives by — so it runs on the
accelerator without JAX x64: ``lax.scan`` walks the node records as DATA
(no per-tape retracing) and ``lax.switch`` dispatches each step to the
same xp-agnostic kernels the host pass uses (``domains.KB_KERNELS`` with
``xp = jax.numpy``).  The float64 interval pass stays on host numpy; the
two verdicts are combined in ``absdomain.prefilter_batch``.

Compilation follows the ``ops/tape_vm`` warm-up contract: buckets of
(node, row) shapes are compiled once per process, a background thread owns
the first compile, and callers use the host known-bits pass until
``interpreter_ready()`` — the pre-filter must never ADD latency.
"""

from __future__ import annotations

from typing import Tuple

import numpy as np

from mythril_tpu.absdomain import domains
from mythril_tpu.absdomain.tape import LIMBS, U32, PackedBatch
from mythril_tpu.native.bitblast import OP_VAR
from mythril_tpu.support.device import DeviceWarmup, note_host_route, on_tpu

# (node, row) padding buckets; row chunks above the cap are split by run_kb
NODE_BUCKETS = (512, 4096)
ROW_BUCKETS = (16, 64)


def _jax():
    import jax
    import jax.numpy as jnp
    from jax import lax

    return jax, jnp, lax


_jitted = None


def _get_jitted():
    global _jitted
    if _jitted is not None:
        return _jitted
    jax, jnp, lax = _jax()

    branches = []
    for opc in range(31):
        fn = domains.KB_KERNELS.get(opc, domains._kb_top)
        branches.append(lambda p, A, B, C, _fn=fn: _fn(jnp, p, A, B, C))

    # the function's name is the XLA module's (jit_absdomain_known_bits),
    # which is how a device trace tells this program apart
    def absdomain_known_bits(op, w, x0, x1, a0, a1, a2, wa, wb, wm, cl,
                             okm, okv):
        n, r = okm.shape[0], okm.shape[1]
        km0 = jnp.zeros((n, r, LIMBS), jnp.uint32)
        kv0 = jnp.zeros((n, r, LIMBS), jnp.uint32)
        ref0 = jnp.zeros((r,), bool)

        def step(carry, xs):
            km_all, kv_all, refuted, i = carry
            (s_op, s_w, s_x0, s_x1, s_a0, s_a1, s_a2, s_wa, s_wb,
             s_wm, s_cl, s_okm, s_okv) = xs
            p = domains.NodeParams(
                w=s_w, x0=s_x0, x1=s_x1, wm=s_wm, cl=s_cl, wa=s_wa, wb=s_wb,
            )

            def child(j):
                jj = jnp.maximum(j, 0)
                return (
                    lax.dynamic_index_in_dim(km_all, jj, 0, keepdims=False),
                    lax.dynamic_index_in_dim(kv_all, jj, 0, keepdims=False),
                )

            A, B, C = child(s_a0), child(s_a1), child(s_a2)
            k, v = lax.switch(s_op, branches, p, A, B, C)
            refuted = refuted | ((k & s_okm & (v ^ s_okv)) != 0).any(axis=-1)
            k = k | s_okm
            v = (v | s_okv) & k
            km_all = lax.dynamic_update_index_in_dim(km_all, k, i, axis=0)
            kv_all = lax.dynamic_update_index_in_dim(kv_all, v, i, axis=0)
            return (km_all, kv_all, refuted, i + 1), None

        (km_all, kv_all, refuted, _), _ = lax.scan(
            step, (km0, kv0, ref0, jnp.int32(0)),
            (op, w, x0, x1, a0, a1, a2, wa, wb, wm, cl, okm, okv),
        )
        return km_all, kv_all, refuted

    _jitted = jax.jit(absdomain_known_bits)
    return _jitted


def _bucket(v: int, buckets) -> int:
    for b in buckets:
        if v <= b:
            return b
    return buckets[-1]


def _dense_overrides(pack: PackedBatch, rows) -> Tuple[np.ndarray, np.ndarray]:
    n = pack.n_nodes
    okm = np.zeros((n, len(rows), LIMBS), U32)
    okv = np.zeros((n, len(rows), LIMBS), U32)
    for node, (_lo, _hi, km, kv) in pack.overrides.items():
        okm[node] = km[rows]
        okv[node] = kv[rows]
    return okm, okv


def chunk_args(pack: PackedBatch, rows) -> tuple:
    """The jitted interpreter's inputs for ``rows`` of ``pack``, padded to
    their (node, row) bucket."""
    n, r = pack.n_nodes, len(rows)
    nb = _bucket(n, NODE_BUCKETS)
    rb = _bucket(r, ROW_BUCKETS)

    def pad_nodes(a, fill=0):
        out = np.full((nb,) + a.shape[1:], fill, a.dtype)
        out[:n] = a
        return out

    op = pad_nodes(pack.op, OP_VAR)  # padding nodes are harmless top vars
    w = pad_nodes(pack.w, 1)
    wm = np.zeros((nb, LIMBS), U32)
    wm[:, 0] = 1
    wm[:n] = pack.wm
    okm, okv = _dense_overrides(pack, rows)
    okm_p = np.zeros((nb, rb, LIMBS), U32)
    okv_p = np.zeros((nb, rb, LIMBS), U32)
    okm_p[:n, :r] = okm
    okv_p[:n, :r] = okv

    a0 = pad_nodes(pack.a0, -1)
    a1 = pad_nodes(pack.a1, -1)
    a2 = pad_nodes(pack.a2, -1)
    wa = np.where(a0 >= 0, w[np.maximum(a0, 0)], 0).astype(np.int32)
    wb = np.where(a1 >= 0, w[np.maximum(a1, 0)], 0).astype(np.int32)

    return (op, w, pad_nodes(pack.x0), pad_nodes(pack.x1), a0, a1, a2,
            wa, wb, wm, pad_nodes(pack.c_limbs), okm_p, okv_p)


def _run_chunk(pack: PackedBatch, rows) -> Tuple[np.ndarray, np.ndarray, np.ndarray]:
    n, r = pack.n_nodes, len(rows)
    km, kv, refuted = _get_jitted()(*chunk_args(pack, rows))
    return (np.asarray(km)[:n, :r], np.asarray(kv)[:n, :r],
            np.asarray(refuted)[:r])


def run_kb(pack: PackedBatch) -> Tuple[np.ndarray, np.ndarray, np.ndarray]:
    """Device known-bits pass; bit-identical to ``domains.eval_kb_host``."""
    r = pack.n_rows
    cap = ROW_BUCKETS[-1]
    km = np.zeros((pack.n_nodes, r, LIMBS), U32)
    kv = np.zeros((pack.n_nodes, r, LIMBS), U32)
    refuted = np.zeros(r, bool)
    for start in range(0, r, cap):
        rows = list(range(start, min(start + cap, r)))
        ck, cv, cr = _run_chunk(pack, rows)
        km[:, start:start + len(rows)] = ck
        kv[:, start:start + len(rows)] = cv
        refuted[start:start + len(rows)] = cr
    return km, kv, refuted


# ---------------------------------------------------------------------------
# Warm-up contract (ops/tape_vm idiom)
# ---------------------------------------------------------------------------


def _compile_smallest_bucket() -> None:
    from mythril_tpu.absdomain import tape as _t
    from mythril_tpu.smt import terms

    x = terms.var("_prefilter_warm", 256)
    pack = _t.pack([[terms.eq(x, terms.const(1, 256))]])
    _run_chunk(pack, [0])


_WARMUP = DeviceWarmup("absdomain-kb", _compile_smallest_bucket)
#: compile the smallest bucket synchronously (idempotent)
warmup = _WARMUP.warmup
ensure_warming = _WARMUP.ensure_warming
interpreter_ready = _WARMUP.ready


def should_use_device() -> bool:
    """Offload known-bits only on a real accelerator, once compiled."""
    if not on_tpu():
        return False
    if not interpreter_ready():
        ensure_warming()
        note_host_route("prefilter_warming")
        return False
    return True
