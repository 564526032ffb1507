"""Public wrapper for the whole-iteration fused PIPECG kernel.

Unlike ``fused_vma``, this wrapper does NOT pad per call: operands must
arrive pre-padded to a multiple of ``tile`` (the solver pads once per
solve — see ``core.pipecg``'s padded execution path). ``trace_count()``
counts how many times the kernel program has been (re)built, the
launch-census hook the benchmarks record.
"""
from __future__ import annotations

from functools import partial

import jax

from ..common import ROW_ALIGN, interpret_default, lane_batched, row_tile, sum_dot_partials
from .kernel import TILE, fused_iter_padded

__all__ = ["fused_iter_step", "fused_iter_tile", "trace_count"]

_TRACES = 0


def trace_count() -> int:
    """Times the fused-iteration kernel program has been traced/built."""
    return _TRACES


def fused_iter_tile(bandwidth: int, tile: int | None = None) -> int:
    """The row-tile the kernel will use: >= bandwidth + 1, whole 1-D
    layout tiles (``common.row_tile``)."""
    return row_tile(bandwidth, tile or TILE)


@partial(jax.jit, static_argnames=("offsets", "tile", "interpret"))
def _step(data, z, q, s, p, x, r, u, w, m, inv_diag, alpha, beta,
          offsets, tile: int, interpret: bool):
    global _TRACES
    _TRACES += 1  # runs at trace time only
    outs = fused_iter_padded(
        data, offsets, (z, q, s, p, x, r, u, w, m), inv_diag, alpha, beta,
        tile=tile, interpret=interpret,
    )
    dots = sum_dot_partials(outs[9])
    return tuple(outs[:9]) + (dots,)


def fused_iter_step(data, offsets, z, q, s, p, x, r, u, w, m, inv_diag,
                    alpha, beta, tile: int, interpret: bool | None = None):
    """One fused PIPECG iteration: SPMV + 8 VMAs + Jacobi PC + dot partials.

    All vector operands and ``data``'s row length must be pre-padded to a
    multiple of ``tile`` (>= bandwidth, ROW_ALIGN-aligned — see
    :func:`fused_iter_tile`). Returns (z', q', s', p', x', r', u', w', m',
    dots) with dots = float32 [ (r',u'), (w',u'), (u',u') ].
    """
    if interpret is None:
        interpret = interpret_default()
    n_pad = z.shape[0]
    if n_pad % tile or tile % ROW_ALIGN:
        raise ValueError(f"operands must be pre-padded: n_pad={n_pad}, tile={tile}")
    run = lane_batched(partial(_step, offsets=offsets, tile=tile, interpret=interpret))
    return run(data, z, q, s, p, x, r, u, w, m, inv_diag, alpha, beta)
