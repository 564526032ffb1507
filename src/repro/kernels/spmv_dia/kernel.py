"""Banded (DIA) SPMV kernel — the paper's SPMV hot spot, TPU-adapted.

GPU SPMV in the paper is cuSPARSE CSR. CSR's ragged rows are hostile to the
TPU vector unit, so the TPU-native banded form is used instead: each stencil
diagonal is a dense vector and SPMV is a sum of statically-shifted
elementwise multiplies (pure VPU work, no gathers — this is the
hardware-adaptation noted in DESIGN.md).

Tiling: the grid walks y in 1-D tiles of TILE elements. The x operand is
passed three times with neighbor index maps (left / center / right block),
so every static shift within ``bandwidth <= TILE`` is a static slice of the
concatenated 3-tile window held in VMEM. Diagonal data blocks are (n_diags,
TILE) VMEM tiles. TILE is a multiple of 1024 (``common.row_tile``): the
chip lays 1-D f32 arrays out in 1024-element tiles, and both the block and
the window concatenation must cover whole ones.

Boundary correctness relies on the DIA convention that ``data[j, i] = 0``
whenever column ``i + off[j]`` falls outside [0, n) — clamped neighbor
blocks at the edges are multiplied by those zeros.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


def _kernel(offsets, tile, dat_ref, xl_ref, xc_ref, xr_ref, y_o):
    xwin = jnp.concatenate([xl_ref[...], xc_ref[...], xr_ref[...]])
    acc = jnp.zeros((tile,), jnp.float32)
    for j, o in enumerate(offsets):
        seg = xwin[tile + o: 2 * tile + o]
        acc = acc + dat_ref[j, :].astype(jnp.float32) * seg.astype(jnp.float32)
    y_o[...] = acc.astype(y_o.dtype)


def spmv_dia_padded(data, offsets: tuple[int, ...], x, *, tile: int, interpret: bool,
                    out_dtype=None):
    """data (k, n_pad), x (n_pad,) with n_pad % tile == 0; bandwidth <= tile.

    ``out_dtype`` decouples output from storage precision: the kernel
    always accumulates in f32, so bf16 ``data``/``x`` with
    ``out_dtype=f32`` is the mixed-precision (bf16-storage /
    f32-accumulate) SPMV.
    """
    n_pad = x.shape[0]
    assert n_pad % tile == 0
    tiles = n_pad // tile
    last = tiles - 1

    kern = partial(_kernel, offsets, tile)
    fn = pl.pallas_call(
        kern,
        grid=(tiles,),
        in_specs=[
            pl.BlockSpec((len(offsets), tile), lambda i: (0, i)),
            pl.BlockSpec((tile,), lambda i: (jnp.maximum(i - 1, 0),)),
            pl.BlockSpec((tile,), lambda i: (i,)),
            pl.BlockSpec((tile,), lambda i: (jnp.minimum(i + 1, last),)),
        ],
        out_specs=pl.BlockSpec((tile,), lambda i: (i,)),
        out_shape=jax.ShapeDtypeStruct((n_pad,), out_dtype or x.dtype),
        interpret=interpret,
        name="spmv_dia",
    )
    return fn(data, x, x, x)
