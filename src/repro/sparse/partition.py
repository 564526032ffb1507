"""Row partitioning + 2-D (local/halo) decomposition — paper §IV-C.

The paper's Hybrid-PIPECG-3 decomposes rows so that nnz is proportional to
measured device throughput (1-D), then splits each part's nnz into
``nnz1`` (columns resident on the device) and ``nnz2`` (columns that arrive
via the m-vector exchange), overlapping SPMV-part-1 with the exchange (2-D).

On the TPU mesh the same structure becomes:

* ``balanced_nnz`` — cut rows so per-shard nnz matches per-device weights
  (uniform weights on a healthy pod; remeasured weights = straggler
  mitigation).
* ``ShardedDIA`` — per-shard banded blocks padded to a common row count so
  they stack into a leading device axis for ``shard_map``; the local/halo
  column split is implicit in the band structure (columns within the shard's
  row range = local block = "nnz1"; boundary strips = "nnz2").

Shards exchange only boundary slabs of width ``bandwidth`` with ring
neighbors (``collective_permute``), and the local SPMV runs while the slabs
are in flight.
"""
from __future__ import annotations

from dataclasses import dataclass
from functools import partial
from typing import Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec

from .formats import DIAMatrix

__all__ = [
    "balanced_rows",
    "balanced_nnz",
    "ShardedDIA",
    "row_sharding",
    "shard_dia",
    "shard_vector",
    "unshard_vector",
    "shard_vectors",
    "unshard_vectors",
    "partition_stats",
]


def balanced_rows(n: int, parts: int) -> np.ndarray:
    """Equal-row boundaries: (parts+1,) with boundaries[0]=0, [-1]=n."""
    base = n // parts
    rem = n % parts
    sizes = np.full(parts, base, dtype=np.int64)
    sizes[:rem] += 1
    return np.concatenate([[0], np.cumsum(sizes)])


def balanced_nnz(row_nnz: np.ndarray, parts: int, weights: np.ndarray | None = None) -> np.ndarray:
    """Cut rows so each part's nnz ~ proportional to its weight.

    This is the paper's performance-model decomposition: ``weights`` are
    relative device speeds (s_dev / sum(s)); uniform if None.
    Returns row boundaries (parts+1,).
    """
    n = len(row_nnz)
    if weights is None:
        weights = np.ones(parts)
    weights = np.asarray(weights, dtype=np.float64)
    weights = weights / weights.sum()
    cum = np.concatenate([[0], np.cumsum(row_nnz, dtype=np.float64)])
    total = cum[-1]
    targets = np.cumsum(weights) * total
    bounds = np.searchsorted(cum, targets[:-1], side="left")
    bounds = np.clip(bounds, 1, n - 1)
    # enforce strictly increasing (each part >= 1 row when possible)
    for i in range(1, len(bounds)):
        if bounds[i] <= bounds[i - 1]:
            bounds[i] = min(bounds[i - 1] + 1, n - 1)
    return np.concatenate([[0], bounds, [n]]).astype(np.int64)


@partial(
    jax.tree_util.register_dataclass,
    data_fields=["data", "rows_valid"],
    meta_fields=["offsets", "n", "rows_max", "boundaries"],
)
@dataclass(frozen=True)
class ShardedDIA:
    """DIA matrix split into P row blocks stacked on a leading device axis.

    ``data[p, j, i]`` = A[boundaries[p]+i, boundaries[p]+i+offsets[j]] for
    i < rows_valid[p]; padded rows are identity (diag=1) so padded vector
    entries stay 0 through the solve.
    """

    data: jax.Array  # (P, n_diags, rows_max)
    rows_valid: jax.Array  # (P,) int32
    offsets: Tuple[int, ...]
    n: int
    rows_max: int
    boundaries: Tuple[int, ...]

    @property
    def n_shards(self) -> int:
        return self.data.shape[0]

    @property
    def bandwidth(self) -> int:
        return max(abs(o) for o in self.offsets)

    def diagonal_sharded(self) -> jax.Array:
        j = self.offsets.index(0)
        return self.data[:, j, :]  # (P, rows_max)


def row_sharding(mesh, ndim: int) -> NamedSharding:
    """Leading (shard) axis split over every mesh axis, the rest whole —
    the layout in which the shard_map'd solver takes its row blocks."""
    names = tuple(mesh.axis_names)
    axis = names[0] if len(names) == 1 else names
    return NamedSharding(mesh, PartitionSpec(axis, *([None] * (ndim - 1))))


def shard_dia(dia: DIAMatrix, boundaries: np.ndarray, mesh=None) -> ShardedDIA:
    """Split a DIA matrix into padded row blocks along ``boundaries``.

    With ``mesh``, each block goes straight from host memory to its own
    device (:func:`row_sharding`); without, the stack lands on the
    default device.
    """
    P = len(boundaries) - 1
    sizes = np.diff(boundaries)
    rows_max = int(sizes.max())
    hw = dia.bandwidth
    if int(sizes.min()) < hw and not (sizes == rows_max).all():
        # equal shards are fine at any bandwidth: the halo SPMV walks
        # ceil(hw/rows) ring hops; only the unequal (performance-model)
        # partition is restricted to single-hop neighbor exchange
        raise ValueError(
            f"smallest shard ({int(sizes.min())}) < bandwidth ({hw}): "
            f"unequal shards support single-hop halo only (use balanced_rows "
            f"for the multi-hop path)"
        )
    k = dia.n_diags
    data_np = np.asarray(dia.data)
    out = np.zeros((P, k, rows_max), dtype=data_np.dtype)
    j0 = dia.offsets.index(0)
    for p in range(P):
        lo, hi = int(boundaries[p]), int(boundaries[p + 1])
        out[p, :, : hi - lo] = data_np[:, lo:hi]
        out[p, j0, hi - lo :] = 1.0  # identity padding rows
    rows_valid = sizes.astype(np.int32)
    if mesh is None:
        data, rows_valid = jnp.asarray(out), jnp.asarray(rows_valid)
    else:
        data = jax.device_put(out, row_sharding(mesh, out.ndim))
        rows_valid = jax.device_put(rows_valid, row_sharding(mesh, 1))
    return ShardedDIA(
        data=data,
        rows_valid=rows_valid,
        offsets=dia.offsets,
        n=dia.n,
        rows_max=rows_max,
        boundaries=tuple(int(b) for b in boundaries),
    )


def shard_vector(x: jax.Array, boundaries) -> jax.Array:
    """(n,) -> (P, rows_max) padded with zeros to match ShardedDIA blocks."""
    boundaries = np.asarray(boundaries)
    P = len(boundaries) - 1
    sizes = np.diff(boundaries)
    rows_max = int(sizes.max())
    out = jnp.zeros((P, rows_max), dtype=x.dtype)
    for p in range(P):
        lo, hi = int(boundaries[p]), int(boundaries[p + 1])
        out = out.at[p, : hi - lo].set(x[lo:hi])
    return out


def shard_vectors(xs: jax.Array, boundaries) -> jax.Array:
    """(k, n) rhs batch -> (P, k, rows_max), the batched-solver layout.

    Shard axis leads (matches ShardedDIA / shard_map in_specs); the rhs
    axis sits between shard and row so each device holds its k local row
    blocks contiguously.
    """
    return jnp.stack([shard_vector(x, boundaries) for x in xs], axis=1)


def unshard_vectors(xs: jax.Array, boundaries) -> jax.Array:
    """(P, k, rows_max) -> (k, n): inverse of shard_vectors."""
    k = xs.shape[1]
    return jnp.stack([unshard_vector(xs[:, j], boundaries) for j in range(k)])


def unshard_vector(xs: jax.Array, boundaries) -> jax.Array:
    boundaries = np.asarray(boundaries)
    P = len(boundaries) - 1
    parts = []
    for p in range(P):
        lo, hi = int(boundaries[p]), int(boundaries[p + 1])
        parts.append(xs[p, : hi - lo])
    return jnp.concatenate(parts)


def partition_stats(dia: DIAMatrix, boundaries: np.ndarray) -> dict:
    """nnz1/nnz2 accounting per shard — the paper's 2-D decomposition view."""
    data = np.asarray(dia.data)
    stats = {"shards": []}
    for p in range(len(boundaries) - 1):
        lo, hi = int(boundaries[p]), int(boundaries[p + 1])
        nnz1 = nnz2 = 0
        for j, o in enumerate(dia.offsets):
            nz = np.count_nonzero(data[j, lo:hi])
            rows = np.arange(lo, hi)
            cols = rows + o
            local = (cols >= lo) & (cols < hi)
            valid = (cols >= 0) & (cols < dia.n) & (data[j, lo:hi] != 0)
            nnz1 += int(np.count_nonzero(local & valid))
            nnz2 += int(np.count_nonzero(~local & valid))
            del nz
        stats["shards"].append({"rows": hi - lo, "nnz_local": nnz1, "nnz_halo": nnz2})
    return stats
