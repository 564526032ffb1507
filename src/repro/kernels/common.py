"""Shared Pallas kernel utilities.

All kernels target TPU (pl.pallas_call + BlockSpec VMEM tiling) and are
validated on CPU with ``interpret=True``. ``interpret_default()`` picks
the mode from the backend: compiled on TPU, interpreted elsewhere.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

LANE = 128        # TPU vector lane width
SUBLANE = 8       # float32 sublane count; (8, 128) is the native f32 tile
ROW_ALIGN = 1024  # TPU lays 1-D f32 arrays out in tiles of 1024 elements


def interpret_default() -> bool:
    """Interpret mode off-TPU (CPU validation); compiled kernels on TPU."""
    return jax.default_backend() != "tpu"


def ceil_to(x: int, m: int) -> int:
    return ((x + m - 1) // m) * m


def row_tile(bandwidth: int, tile: int) -> int:
    """The 1-D row tile of the banded kernels: at least ``tile`` and
    ``bandwidth + 1`` (the halo lives in one neighbor tile), rounded up
    to whole 1-D layout tiles (``ROW_ALIGN``) so the chip accepts the
    block and the in-kernel concatenation of neighbor tiles."""
    return ceil_to(max(tile, bandwidth + 1), ROW_ALIGN)


# Per-tile dot partials leave a kernel as one (SUBLANE, LANE) block per grid
# step — the smallest f32 block the chip accepts — with the partials in
# lanes 0..2 of row 0 and zeros elsewhere.

def dot_partials_block(part: jax.Array) -> jax.Array:
    """(3,) partials -> the (SUBLANE, LANE) block a kernel writes."""
    return jnp.pad(part[None, :], ((0, SUBLANE - 1), (0, LANE - part.shape[0])))


def dot_partials_out(tiles: int):
    """(out_spec, out_shape) of the per-tile dot-partials output."""
    from jax.experimental import pallas as pl

    return (pl.BlockSpec((SUBLANE, LANE), lambda i: (i, 0)),
            jax.ShapeDtypeStruct((SUBLANE * tiles, LANE), jnp.float32))


def sum_dot_partials(parts: jax.Array) -> jax.Array:
    """(SUBLANE * tiles, LANE) partials -> float32 [(r,u), (w,u), (u,u)]."""
    return parts[::SUBLANE, :3].sum(axis=0)


def lane_batched(fn):
    """``fn`` (a kernel wrapper over arrays) made vmappable on the chip.

    Left to itself, ``jax.vmap`` of a ``pallas_call`` adds a squeezed
    batch dimension to every block, and the TPU compiler refuses that for
    1-D vector blocks and scalar operands. Under ``vmap`` this runs
    ``fn`` once per batch lane instead — the same kernel as the unbatched
    call, one launch per right-hand side.
    """
    wrapped = jax.custom_batching.custom_vmap(fn)

    @wrapped.def_vmap
    def _per_lane(axis_size, in_batched, *args):
        lanes = [fn(*(a[i] if bat else a for a, bat in zip(args, in_batched)))
                 for i in range(axis_size)]
        out = jax.tree.map(lambda *xs: jnp.stack(xs), *lanes)
        return out, jax.tree.map(lambda _: True, out)

    return wrapped


def pad1d(x: jax.Array, n_pad: int) -> jax.Array:
    """Zero-pad a 1-D array to length n_pad."""
    n = x.shape[0]
    if n == n_pad:
        return x
    return jnp.pad(x, (0, n_pad - n))


def as_2d(x: jax.Array, lane: int = LANE) -> jax.Array:
    """(n_pad,) -> (n_pad // lane, lane) view for TPU-native tiling."""
    return x.reshape(-1, lane)


# ---------------------------------------------------------------------------
# jaxpr census — count kernel launches (and pad traffic) per program region
# ---------------------------------------------------------------------------

def _sub_jaxprs(params):
    from jax.extend.core import ClosedJaxpr, Jaxpr

    for v in params.values():
        vals = v if isinstance(v, (tuple, list)) else (v,)
        for item in vals:
            if isinstance(item, ClosedJaxpr):
                yield item.jaxpr
            elif isinstance(item, Jaxpr):
                yield item


def count_primitive(jaxpr, name: str, *, into_kernels: bool = True) -> int:
    """Occurrences of primitive ``name`` in a jaxpr, recursing into
    sub-jaxprs (pjit bodies, while cond/body, cond branches, ...).

    ``into_kernels=False`` stops recursion at ``pallas_call`` boundaries:
    ops inside a kernel body run on-chip per tile, so e.g. a ``pad``
    there is not per-iteration HBM traffic and should not count against
    a "no padding in the hot loop" invariant.
    """
    total = 0
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == name:
            total += 1
        if not into_kernels and eqn.primitive.name == "pallas_call":
            continue
        for sub in _sub_jaxprs(eqn.params):
            total += count_primitive(sub, name, into_kernels=into_kernels)
    return total


def while_body_jaxpr(jaxpr):
    """The body jaxpr of the first ``while`` found (recursively), or None.

    For the solver loops this is the per-iteration program region — the
    thing whose kernel-launch count the fusion work drives to 1.
    """
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "while":
            return eqn.params["body_jaxpr"].jaxpr
        for sub in _sub_jaxprs(eqn.params):
            found = while_body_jaxpr(sub)
            if found is not None:
                return found
    return None


def launches_per_iteration(fn, *args, primitive: str = "pallas_call") -> int:
    """Count ``primitive`` occurrences inside ``fn``'s solver-loop body.

    Traces ``fn(*args)`` (no execution) and censuses the first while
    loop's body — i.e. kernel launches per solver iteration. Returns -1
    if the trace contains no while loop.
    """
    closed = jax.make_jaxpr(fn)(*args)
    body = while_body_jaxpr(closed.jaxpr)
    if body is None:
        return -1
    return count_primitive(body, primitive)
