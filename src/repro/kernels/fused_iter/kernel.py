"""Whole-iteration fused PIPECG kernel (Pallas TPU).

Rupp et al. (arXiv 1410.4054) show that pipelined solvers win on
accelerators when the *entire* iteration is fused, not just the SPMV.
This kernel is that step beyond ``fused_vma``: one grid walk over row
tiles computes, per tile,

    SPMV   n = A m           (banded DIA, 3-window shifted reads — the
                              ``spmv_dia`` idiom)
    VMAs   z q s p x r u w   (the 8 recurrences of Alg. 2 lines 10-17)
    PC     m' = inv_diag * w (Jacobi, line 21)
    dots   (r,u) (w,u) (u,u) partials (lines 18-20)

so one PIPECG iteration launches exactly ONE kernel. The SPMV is moved
from the end of iteration i-1 to the start of iteration i — identical
math (n is A m of the *previous* m either way), but now m is a fully
materialized input and the cross-tile halo reads need no intra-kernel
synchronization: tile i reads the (i-1, i, i+1) window of m via three
neighbor-indexed BlockSpecs, exactly like ``spmv_dia``.

Per-element HBM traffic (f32): reads z q s p x r u w m inv + k diag
rows, writes z q s p x r u w m — (10 + k) * 4 B in, 9 * 4 B out, one
round trip per vector per iteration.

Boundary correctness relies on the DIA convention that ``data[j, i] = 0``
whenever column ``i + off[j]`` falls outside [0, n): the zero-padded
tail (n..n_pad) therefore stays zero through every recurrence, which is
what lets the solver loop run entirely on padded views.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from ..common import dot_partials_block, dot_partials_out

TILE = 4096  # 1-D row tile; must be >= matrix bandwidth (halo = 1 tile)


def _kernel(
    offsets, tile,
    alpha_ref, beta_ref,
    dat_ref, ml_ref, mc_ref, mr_ref,
    z_ref, q_ref, s_ref, p_ref, x_ref, r_ref, u_ref, w_ref, inv_ref,
    z_o, q_o, s_o, p_o, x_o, r_o, u_o, w_o, m_o, dots_o,
):
    dtype = z_ref.dtype
    alpha = alpha_ref[0].astype(dtype)
    beta = beta_ref[0].astype(dtype)

    # --- SPMV n = A m on the concatenated 3-tile window (f32 accumulate) ---
    mwin = jnp.concatenate([ml_ref[...], mc_ref[...], mr_ref[...]])
    acc = jnp.zeros((tile,), jnp.float32)
    for j, o in enumerate(offsets):
        seg = mwin[tile + o: 2 * tile + o]
        acc = acc + dat_ref[j, :].astype(jnp.float32) * seg.astype(jnp.float32)
    n_v = acc.astype(dtype)

    # --- the 8 VMAs + Jacobi PC (the pipecg_vma_core recurrence) ---
    m_v = mc_ref[...]
    w_v = w_ref[...]
    u_v = u_ref[...]

    z_v = n_v + beta * z_ref[...]
    q_v = m_v + beta * q_ref[...]
    s_v = w_v + beta * s_ref[...]
    p_v = u_v + beta * p_ref[...]

    x_o[...] = x_ref[...] + alpha * p_v
    r_v = r_ref[...] - alpha * s_v
    u_n = u_v - alpha * q_v
    w_n = w_v - alpha * z_v
    m_n = inv_ref[...] * w_n

    z_o[...] = z_v
    q_o[...] = q_v
    s_o[...] = s_v
    p_o[...] = p_v
    r_o[...] = r_v
    u_o[...] = u_n
    w_o[...] = w_n
    m_o[...] = m_n

    # --- per-tile dot partials on the vectors just produced ---
    rf = r_v.astype(jnp.float32)
    uf = u_n.astype(jnp.float32)
    wf = w_n.astype(jnp.float32)
    part = jnp.stack([jnp.sum(rf * uf), jnp.sum(wf * uf), jnp.sum(uf * uf)])
    dots_o[...] = dot_partials_block(part)


def fused_iter_padded(data, offsets, vecs, inv_diag, alpha, beta, *, tile: int, interpret: bool):
    """One fused PIPECG iteration on padded operands.

    data (k, n_pad) zero-padded DIA diagonals; vecs = (z, q, s, p, x, r,
    u, w, m) each (n_pad,) with n_pad % tile == 0; bandwidth <= tile.
    Returns 9 updated vectors (z q s p x r u w m) + per-tile dot partials
    (SUBLANE * tiles, LANE) — see ``common.sum_dot_partials``.
    """
    n_pad = vecs[0].shape[0]
    assert n_pad % tile == 0, (n_pad, tile)
    tiles = n_pad // tile
    last = tiles - 1
    dtype = vecs[0].dtype

    z, q, s, p, x, r, u, w, m = vecs
    vec_spec = pl.BlockSpec((tile,), lambda i: (i,))
    scalar_spec = pl.BlockSpec((1,), lambda i: (0,))
    dots_spec, dots_shape = dot_partials_out(tiles)
    out_shapes = [jax.ShapeDtypeStruct((n_pad,), dtype) for _ in range(9)] + [dots_shape]
    out_specs = [vec_spec] * 9 + [dots_spec]

    fn = pl.pallas_call(
        partial(_kernel, offsets, tile),
        grid=(tiles,),
        in_specs=[
            scalar_spec,                                            # alpha
            scalar_spec,                                            # beta
            pl.BlockSpec((len(offsets), tile), lambda i: (0, i)),   # diagonals
            pl.BlockSpec((tile,), lambda i: (jnp.maximum(i - 1, 0),)),  # m left
            pl.BlockSpec((tile,), lambda i: (i,)),                      # m center
            pl.BlockSpec((tile,), lambda i: (jnp.minimum(i + 1, last),)),  # m right
        ] + [vec_spec] * 9,
        out_specs=out_specs,
        out_shape=out_shapes,
        interpret=interpret,
        name="fused_iter",
    )
    alpha = jnp.asarray(alpha, jnp.float32).reshape(1)
    beta = jnp.asarray(beta, jnp.float32).reshape(1)
    return fn(alpha, beta, data, m, m, m, z, q, s, p, x, r, u, w, inv_diag)
