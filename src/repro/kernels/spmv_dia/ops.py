"""Public wrapper for the banded SPMV kernel."""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from ...sparse.formats import DIAMatrix
from ..common import ceil_to, interpret_default, lane_batched, pad1d, row_tile
from .kernel import spmv_dia_padded

__all__ = ["spmv_dia_pallas"]

_DEFAULT_TILE = 4096


@partial(jax.jit, static_argnames=("offsets", "tile", "interpret", "out_dtype"))
def _spmv(data, x, offsets, tile: int, interpret: bool, out_dtype):
    n = x.shape[0]
    n_pad = ceil_to(n, tile)
    xp = pad1d(x, n_pad)
    dp = jnp.pad(data, ((0, 0), (0, n_pad - n)))
    y = spmv_dia_padded(dp, offsets, xp, tile=tile, interpret=interpret, out_dtype=out_dtype)
    return y[:n]


def spmv_dia_pallas(A: DIAMatrix, x: jax.Array, tile: int | None = None,
                    interpret: bool | None = None, out_dtype=None):
    """y = A @ x for a DIA matrix via the Pallas banded kernel.

    ``tile`` must be >= the matrix bandwidth (halo lives in the neighbor
    blocks); it is raised to that and rounded to whole 1-D layout tiles
    (``common.row_tile``). ``out_dtype``
    (default: x.dtype) lets bf16-storage inputs emit the f32-accumulated
    result without a round trip through bf16.
    """
    if interpret is None:
        interpret = interpret_default()
    t = row_tile(A.bandwidth, tile or _DEFAULT_TILE)
    out_dtype = jnp.dtype(out_dtype) if out_dtype is not None else None
    run = lane_batched(partial(_spmv, offsets=A.offsets, tile=t, interpret=interpret,
                               out_dtype=out_dtype))
    return run(A.data, x)
