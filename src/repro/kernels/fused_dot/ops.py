"""Public wrapper for the fused triple dot product."""
from __future__ import annotations

from functools import partial

import jax

from ..common import LANE, as_2d, ceil_to, interpret_default, pad1d, sum_dot_partials
from .kernel import TILE_ROWS, fused_dots_padded

__all__ = ["fused_dots"]


@partial(jax.jit, static_argnames=("interpret",))
def _fused(r, u, w, interpret: bool):
    n = r.shape[0]
    n_pad = ceil_to(n, TILE_ROWS * LANE)
    r2, u2, w2 = (as_2d(pad1d(v, n_pad)) for v in (r, u, w))
    parts = fused_dots_padded(r2, u2, w2, interpret=interpret)
    return sum_dot_partials(parts)


def fused_dots(r, u, w, interpret: bool | None = None):
    """float32 [ (r,u), (w,u), (u,u) ] in a single memory pass."""
    if interpret is None:
        interpret = interpret_default()
    return _fused(r, u, w, interpret)
