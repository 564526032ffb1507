"""Public wrapper for the fused PIPECG iteration core.

Padding contract: the wrapper accepts any length and zero-pads to the
(TILE_ROWS * LANE) tile grid — but ``pad1d`` and the trailing un-pad
slice are emitted ONLY when the inputs are misaligned. The solver's
padded execution path (``core.pipecg``) pads every vector once per
*solve* to this alignment, so inside the iteration hot loop all ten
per-call pads and nine un-pad slices vanish and the only per-iteration
work left is the kernel launch plus free (view-only) reshapes. Callers
that cannot pre-align still get the correct, if slower, pad-per-call
behavior.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from ..common import LANE, as_2d, ceil_to, interpret_default, lane_batched, pad1d, sum_dot_partials
from .kernel import TILE_ROWS, fused_vma_dots_padded

__all__ = ["fused_vma_dots"]


@partial(jax.jit, static_argnames=("interpret",))
def _fused(z, q, s, p, x, r, u, w, n, m, inv_diag, alpha, beta, interpret: bool):
    n_elems = z.shape[0]
    n_pad = ceil_to(n_elems, TILE_ROWS * LANE)
    aligned = n_pad == n_elems  # pre-padded caller: no pads, no un-pad slices
    vecs = tuple(as_2d(pad1d(v, n_pad)) for v in (z, q, s, p, x, r, u, w, n, m))
    inv2 = as_2d(pad1d(inv_diag, n_pad))
    outs = fused_vma_dots_padded(vecs, inv2, alpha, beta, interpret=interpret)
    if aligned:
        news = tuple(o.reshape(-1) for o in outs[:9])
    else:
        news = tuple(o.reshape(-1)[:n_elems] for o in outs[:9])
    dots = sum_dot_partials(outs[9])
    return news + (dots,)


def fused_vma_dots(z, q, s, p, x, r, u, w, n, m, inv_diag, alpha, beta, interpret: bool | None = None):
    """Fused 8-VMA + Jacobi-PC + dot-partials pass (PIPECG lines 10-21).

    Returns (z', q', s', p', x', r', u', w', m', dots) where
    dots = float32 [ (r',u'), (w',u'), (u',u') ].
    """
    if interpret is None:
        interpret = interpret_default()
    run = lane_batched(partial(_fused, interpret=interpret))
    return run(z, q, s, p, x, r, u, w, n, m, inv_diag, alpha, beta)
