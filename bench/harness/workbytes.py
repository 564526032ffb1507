"""Bytes of HBM traffic that pipelined CG needs, counted from the algorithm.

The count depends only on the operator and the method, never on which
kernel computes them, so a later change that swaps, fuses or removes a
kernel changes no count. It is the least traffic any implementation of
Ghysels & Vanroose's Algorithm 2 with a Jacobi preconditioner moves:

* every stored diagonal is read once per SPMV: one SPMV per iteration
  (``n = A m``), two at the start (``r0 = b - A x0``, ``w0 = A u0``) and
  four per residual replacement (``A x``, ``A u``, ``A p``, ``A q``,
  every ``replace_every`` iterations);
* per iteration, the eight recurrence vectors ``x r u w z q s p`` are each
  read and written once and the inverse diagonal is read once (``m`` and
  ``n`` are temporaries a fused iteration never stores);
* each SPMV outside the iteration reads its input and writes its output.

In a batch the lanes share the operator: its diagonals are read once per
iteration of the batch (the slowest lane's count) for all lanes, while
each lane moves its own vectors for its own iterations.
"""
from __future__ import annotations

import numpy as np

STATE_VECTORS = 8        # x r u w z q s p
VECTORS_PER_ITER = 2 * STATE_VECTORS + 1  # each read + written, inverse diagonal read
INIT_SPMVS = 2
REPLACEMENT_SPMVS = 4


def _outer_spmvs(iterations, replace_every: int):
    replaced = iterations // replace_every if replace_every > 0 else 0 * iterations
    return INIT_SPMVS + REPLACEMENT_SPMVS * replaced


def solve_bytes(n: int, n_diags: int, lane_iterations, replace_every: int,
                itemsize: int = 4) -> int:
    """Bytes one call moves: one right-hand side, or a batch of lanes."""
    its = np.atleast_1d(np.asarray(lane_iterations, dtype=np.int64))
    longest = int(its.max())
    operator = n_diags * (longest + int(_outer_spmvs(np.int64(longest), replace_every)))
    vectors = int(np.sum(VECTORS_PER_ITER * its + 2 * _outer_spmvs(its, replace_every)))
    return itemsize * n * (operator + vectors)
