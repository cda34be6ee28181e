"""Device twin of the batched DPLL search kernel.

Runs ``kernel.step`` — the exact same function the host driver loops —
under ``jax.jit`` + ``lax.while_loop`` with ``xp = jax.numpy``.  The
step is pure integer arithmetic whose only scatter is an
order-independent logical-or (``.at[...].max`` on a boolean plane), so
host and device traces are bit-identical by construction, mirroring the
``absdomain/domains.py`` / ``absdomain/device.py`` pair.

Compilation follows the ``absdomain/device.py`` warm-up contract: one
program per (query, clause, variable) bucket triple, the first compile
claimed by a background thread, and ``should_use_device()`` false until
it lands — the device tier must never ADD latency to a query that the
host twin (or the exact tiers) would have answered sooner.
"""

from __future__ import annotations

from typing import Tuple

import numpy as np

from mythril_tpu.devsolver import kernel
from mythril_tpu.devsolver.kernel import RUNNING, UNKNOWN_Q, Plane
from mythril_tpu.support.device import DeviceWarmup, note_host_route, on_tpu

_jitted = None


def _jax():
    import jax
    import jax.numpy as jnp
    from jax import lax

    return jax, jnp, lax


def _get_jitted():
    global _jitted
    if _jitted is not None:
        return _jitted
    jax, jnp, lax = _jax()

    def scatter_or(shape, qi, vi, mask):
        return jnp.zeros(shape, bool).at[qi, vi].max(mask)

    # the function's name is the XLA module's (jit_devsolver_search),
    # which is how a device trace tells this program apart
    def devsolver_search(lits, dec, n_vars_arr, max_iters):
        qb = lits.shape[0]
        vb = n_vars_arr.shape[0]
        d = dec.shape[1]
        assign = jnp.zeros((qb, vb), jnp.int8)
        assign = assign.at[:, 0].set(2).at[:, 1].set(1)
        level = jnp.zeros((qb, vb), jnp.int16)
        dval = jnp.zeros((qb, d), jnp.int8)
        dflip = jnp.zeros((qb, d), jnp.int8)
        depth = jnp.zeros((qb,), jnp.int32)
        status = jnp.zeros((qb,), jnp.int8)

        def cond(carry):
            _a, _l, _dv, _df, _dp, st, it = carry
            return (st == RUNNING).any() & (it < max_iters)

        def body(carry):
            a, l, dv, df, dp, st, it = carry
            a, l, dv, df, dp, st = kernel.step(
                jnp, scatter_or, lits, dec, a, l, dv, df, dp, st)
            return a, l, dv, df, dp, st, it + 1

        assign, level, dval, dflip, depth, status, _ = lax.while_loop(
            cond, body,
            (assign, level, dval, dflip, depth, status, jnp.int32(0)))
        status = jnp.where(status == RUNNING, jnp.int8(UNKNOWN_Q), status)
        return status, assign

    _jitted = jax.jit(devsolver_search)
    return _jitted


def run_device(plane: Plane, max_iters: int
               ) -> Tuple[np.ndarray, np.ndarray]:
    """Jitted twin of ``kernel.run_host``; returns (status[Q], assign)."""
    _jax()  # import check before touching the cache
    # n_vars is carried as a shape (dummy array) so each variable bucket
    # compiles its own program instead of retracing on a python int
    n_vars_arr = np.zeros((plane.n_vars,), np.int8)
    status, assign = _get_jitted()(
        plane.lits, plane.dec, n_vars_arr, np.int32(max_iters))
    return np.asarray(status), np.asarray(assign)


# ---------------------------------------------------------------------------
# Warm-up contract (absdomain/device.py idiom)
# ---------------------------------------------------------------------------


def _compile_smallest_bucket() -> None:
    # smallest buckets: 1 query, 1 real clause, 3 variables
    plane = kernel.pack_plane([([[4]], [2])], n_vars=3)
    run_device(plane, 8)


_WARMUP = DeviceWarmup("devsolver", _compile_smallest_bucket)
#: compile the smallest bucket synchronously (idempotent)
warmup = _WARMUP.warmup
ensure_warming = _WARMUP.ensure_warming
interpreter_ready = _WARMUP.ready


def should_use_device() -> bool:
    """Offload the search only on a real accelerator, once compiled."""
    if not on_tpu():
        return False
    if not interpreter_ready():
        ensure_warming()
        note_host_route("devsolver_warming")
        return False
    return True
