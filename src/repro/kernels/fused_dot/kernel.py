"""Fused triple-dot kernel: gamma=(r,u), delta=(w,u), uu=(u,u) in one pass.

Unfused, the three dots read 6N elements (u three times); fused they read
3N — the same merged-reads idea the paper applies to the CPU side (§V-B.2).
Per-tile partials are emitted as one (8, LANE) block per tile; the wrapper
sums them (exact f32 tree-sum of tile partials).
"""
from __future__ import annotations

import jax.numpy as jnp
from jax.experimental import pallas as pl

from ..common import LANE, dot_partials_block, dot_partials_out

TILE_ROWS = 64


def _kernel(r_ref, u_ref, w_ref, dots_o):
    rf = r_ref[...].astype(jnp.float32)
    uf = u_ref[...].astype(jnp.float32)
    wf = w_ref[...].astype(jnp.float32)
    partial = jnp.stack([jnp.sum(rf * uf), jnp.sum(wf * uf), jnp.sum(uf * uf)])
    dots_o[...] = dot_partials_block(partial)


def fused_dots_padded(r, u, w, *, interpret: bool):
    rows = r.shape[0]
    assert rows % TILE_ROWS == 0
    tiles = rows // TILE_ROWS
    vec_spec = pl.BlockSpec((TILE_ROWS, LANE), lambda i: (i, 0))
    dots_spec, dots_shape = dot_partials_out(tiles)
    fn = pl.pallas_call(
        _kernel,
        grid=(tiles,),
        in_specs=[vec_spec] * 3,
        out_specs=dots_spec,
        out_shape=dots_shape,
        interpret=interpret,
        name="fused_dot",
    )
    return fn(r, u, w)
