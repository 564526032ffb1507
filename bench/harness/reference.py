"""A plain Jacobi-preconditioned CG, the reference that stands in for the program.

It imports nothing of the program and takes nothing the program made:
only the benchmark's own operator (``stencil.build``) and right-hand
sides. The control (``bench/control.py``) runs it in the precision below
the configuration's, in the program's place, and the run's checks must
then read ``correct`` false.
"""
from __future__ import annotations

from functools import partial

from . import stencil


def pcg(data, b, *, offsets: tuple[int, ...], rtol: float, maxiter: int):
    """Solve ``A x = b`` from ``x0 = 0``; every vector in ``data.dtype``.

    Returns ``(x, iterations, converged)``; stops when ``||r|| <= rtol
    ||b||`` by its own recurrence residual, or after ``maxiter``.
    """
    import jax
    import jax.numpy as jnp

    dt = data.dtype
    b = b.astype(dt)
    inv = (1.0 / data[offsets.index(0)]).astype(dt)
    A = partial(stencil.spmv, data, offsets)
    stop = jnp.asarray(rtol, dt) * jnp.linalg.norm(b)

    def cond(state):
        k, _, r, _, _ = state
        return (k < maxiter) & (jnp.linalg.norm(r) > stop)

    def body(state):
        k, x, r, p, rz = state
        q = A(p)
        alpha = rz / jnp.dot(p, q)
        x = x + alpha * p
        r = r - alpha * q
        z = inv * r
        rz_new = jnp.dot(r, z)
        p = z + (rz_new / rz) * p
        return k + 1, x, r, p, rz_new

    z = inv * b
    state = (jnp.int32(0), jnp.zeros_like(b), b, z, jnp.dot(b, z))
    k, x, r, _, _ = jax.lax.while_loop(cond, body, state)
    return x, k, jnp.linalg.norm(r) <= stop
