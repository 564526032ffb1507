"""Fused PIPECG iteration-core kernel (Pallas TPU).

The paper's §V-B fuses the eight VMA updates and the Jacobi PC into one GPU
kernel so every vector makes a single HBM round trip. This kernel goes one
step further and also emits the three dot-product partials (gamma, delta,
(u,u)) for the tile, because they read exactly the vectors the update just
produced — on TPU that turns the whole iteration core into one
HBM-bandwidth-bound pass:

    reads : z q s p x r u w n m inv_diag   (11 N)
    writes: z q s p x r u w m              (9 N)

versus 8 separate AXPYs + PC + 3 dots = 27 N reads + 9 N writes unfused.

Layout: vectors are zero-padded to a multiple of (TILE_ROWS*LANE) and viewed
as (rows, 128); the grid walks row blocks of ``block_rows(rows)`` rows; per-block
dot partials land in one (8, 128) block per grid step, summed by the wrapper
(padding contributes zeros).

In place: z q s p x r u w m are updated in their own HBM buffers
(``input_output_aliases``, outputs placed in ``pltpu.HBM``), so a solver
loop carries each recurrence vector in one HBM buffer for the whole solve,
XLA inserts no copy to move a result back into the loop carry, and the
kernel streams those vectors through its own block pipeline. Without the
placement XLA may keep the whole loop state in VMEM where it fits (a v5e
does at 104^3), which makes the vector traffic vanish from HBM at some
sizes and not at others. Grid step i reads block i of every input and
writes only block i of each output, so updating in place is safe. A
caller whose operand is still live afterwards gets a copy from XLA, not a
clobbered array.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..common import LANE, dot_partials_block, dot_partials_out

TILE_ROWS = 32  # alignment unit: (32, 128) f32 = 16 KiB
# 20 vector streams (11 in, 9 out), each double-buffered: 512 rows is
# 10 MiB of VMEM, inside v5e's 16 MiB default scoped limit.
MAX_BLOCK_ROWS = 512
# operand index -> output index: the nine updated vectors (alpha, beta
# are operands 0 and 1; n and inv_diag are read only)
_IN_PLACE = {2: 0, 3: 1, 4: 2, 5: 3, 6: 4, 7: 5, 8: 6, 9: 7, 11: 8}


def block_rows(rows: int) -> int:
    """Rows per grid step: the largest multiple of TILE_ROWS that divides
    ``rows`` and is at most MAX_BLOCK_ROWS (few large DMAs per vector)."""
    assert rows % TILE_ROWS == 0, (rows, TILE_ROWS)
    return max(b for b in range(TILE_ROWS, min(rows, MAX_BLOCK_ROWS) + 1, TILE_ROWS)
               if rows % b == 0)


def _kernel(
    alpha_ref, beta_ref,
    z_ref, q_ref, s_ref, p_ref, x_ref, r_ref, u_ref, w_ref, n_ref, m_ref, inv_ref,
    z_o, q_o, s_o, p_o, x_o, r_o, u_o, w_o, m_o, dots_o,
):
    dtype = z_ref.dtype
    alpha = alpha_ref[0].astype(dtype)
    beta = beta_ref[0].astype(dtype)

    n_v = n_ref[...]
    m_v = m_ref[...]
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

    rf = r_v.astype(jnp.float32)
    uf = u_n.astype(jnp.float32)
    wf = w_n.astype(jnp.float32)
    partial = jnp.stack([jnp.sum(rf * uf), jnp.sum(wf * uf), jnp.sum(uf * uf)])
    dots_o[...] = dot_partials_block(partial)


def fused_vma_dots_padded(vecs, inv_diag, alpha, beta, *, interpret: bool):
    """Run the kernel on already-padded 2-D (rows, LANE) views.

    vecs = (z, q, s, p, x, r, u, w, n, m); returns 9 updated views (in the
    HBM buffers of z q s p x r u w m) + per-block dot partials
    (SUBLANE * blocks, LANE).
    """
    rows = vecs[0].shape[0]
    br = block_rows(rows)
    tiles = rows // br
    dtype = vecs[0].dtype

    vec_spec = pl.BlockSpec((br, LANE), lambda i: (i, 0))
    scalar_spec = pl.BlockSpec((1,), lambda i: (0,))

    dots_spec, dots_shape = dot_partials_out(tiles)
    out_shapes = [pltpu.HBM((rows, LANE), dtype) for _ in range(9)] + [dots_shape]
    out_specs = [vec_spec] * 9 + [dots_spec]

    fn = pl.pallas_call(
        _kernel,
        grid=(tiles,),
        in_specs=[scalar_spec, scalar_spec] + [vec_spec] * 11,
        out_specs=out_specs,
        out_shape=out_shapes,
        input_output_aliases=_IN_PLACE,
        interpret=interpret,
        name="fused_vma",
    )
    alpha = jnp.asarray(alpha, jnp.float32).reshape(1)
    beta = jnp.asarray(beta, jnp.float32).reshape(1)
    return fn(alpha, beta, *vecs, inv_diag)
