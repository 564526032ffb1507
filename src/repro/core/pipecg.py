r"""Pipelined PCG — Algorithm 2 of the paper (Ghysels & Vanroose).

Thin single-device front-end over the shared solver loop in
``core.iteration``: the iteration core (jnp, fused-Pallas VMA, or the
whole-iteration ``fused_iter`` kernel), the SPMV engine and the (here:
identity) reduction strategy are injected, so this file holds *no*
iteration math of its own. The distributed solver (``core.distributed``)
wraps the exact same loop in ``shard_map``; its communication-reduced
siblings (``pl2``/``pl3`` depth-l pipelines, hierarchical "h4"
reduction) and the method x reducer selection matrix are documented in
docs/distributed.md.

What this file *does* own is the **padded execution path**: the Pallas
cores want whole row tiles, and padding ten vectors every iteration
would dominate the fused kernel's saving. For DIA operators with an
elementwise (Jacobi/identity) preconditioner, the solve runs entirely on
views zero-padded ONCE — operator diagonals, b, x0, inv_diag — sized so
every kernel tile constraint is met simultaneously; the while-loop body
then contains zero pad/reshape work and the solution is sliced back to n
at the end. (The DIA zero-outside-band convention makes the padded tail
invariant — it stays exactly 0 through every recurrence.) ``SolverPlan``
builds the ``fused_iter`` core once at plan time, pinning the padded
diagonal data on the plan.
"""
from __future__ import annotations

import math
from functools import partial

import jax
import jax.numpy as jnp

from ..kernels.common import LANE, ceil_to, pad1d, row_tile
from ..obs.trace import trace_scope
from ..sparse.formats import DIAMatrix
from ..sparse.spmv import resolve_engine, spmv, spmv_dia, spmv_dia_bf16
from .iteration import get_core, make_fused_iter_core, resolve_core_name, run_pipecg
from .preconditioners import IdentityPC, JacobiPC, apply_pc, identity
from .types import SolveResult

__all__ = ["pipecg", "pin_pipecg_core"]

# default residual-replacement period when the reduced-precision SPMV
# engine is selected and the caller did not choose one (the f32/f64
# safety net arXiv 2501.03743-style reduced-precision CG relies on)
_BF16_REPLACE_EVERY = 50


def _elementwise_pc(M) -> bool:
    return isinstance(M, (JacobiPC, IdentityPC))


def _padded_tile(core_name: str, bandwidth: int, tile: int | None) -> int:
    """The banded-SPMV row tile of this core (>= bandwidth + 1, whole
    1-D layout tiles — ``kernels.common.row_tile``)."""
    if core_name == "fused_iter":
        from ..kernels.fused_iter import fused_iter_tile

        return fused_iter_tile(bandwidth, tile)
    from ..kernels.fused_vma.kernel import TILE_ROWS

    return row_tile(bandwidth, tile or TILE_ROWS * LANE)


def _padded_length(n: int, t: int) -> int:
    """Padded vector length of the pallas core: whole SPMV row tiles AND
    whole fused_vma (TILE_ROWS, LANE) blocks, so neither kernel pads."""
    from ..kernels.fused_vma.kernel import TILE_ROWS

    return ceil_to(n, math.lcm(t, TILE_ROWS * LANE))


def _padded_spmv_fns(Ap: DIAMatrix, spmv_engine: str, t: int):
    """(iteration spmv, replacement spmv) on pre-padded vectors.

    Both keep the padded tail at exactly zero. The replacement SPMV is
    always full precision — when the iteration runs the "bf16" engine it
    is the f32 (f64 under x64) safety net residual replacement re-derives
    vectors through.
    """
    eng = resolve_engine(Ap, spmv_engine)

    def _pallas(v):
        from ..kernels.spmv_dia import spmv_dia_pallas

        return spmv_dia_pallas(Ap, v, tile=t)

    full = _pallas if jax.default_backend() == "tpu" else (lambda v: spmv_dia(Ap, v))
    if eng == "pallas":
        return _pallas, _pallas
    if eng == "bf16":
        return (lambda v: spmv_dia_bf16(Ap, v)), full
    return (lambda v: spmv_dia(Ap, v)), (lambda v: spmv_dia(Ap, v))


@partial(
    jax.jit,
    static_argnames=("maxiter", "core_name", "spmv_engine", "replace_every", "tile", "core_obj"),
)
def _pipecg_impl(
    A, b, M, x0, atol, rtol,
    maxiter: int, core_name: str, spmv_engine: str, replace_every: int,
    tile, core_obj,
):
    # Jacobi fuses into the iteration core; any other PC is applied per
    # iteration by the loop (inv_diag=None -> m = pc_fn(w)).
    inv_diag = M.inv_diag if isinstance(M, JacobiPC) else None
    padded = (
        core_name in ("pallas", "fused_iter")
        and isinstance(A, DIAMatrix)
        and _elementwise_pc(M)
    )

    if not padded:
        i, x, norm, converged, hist = run_pipecg(
            b,
            x0,
            spmv_fn=lambda v: spmv(A, v, engine=spmv_engine),
            pc_fn=lambda r: apply_pc(M, r),
            core=get_core(core_name, A),
            inv_diag=inv_diag,
            atol=atol,
            rtol=rtol,
            maxiter=maxiter,
            replace_every=replace_every,
            replace_spmv_fn=(
                (lambda v: spmv(A, v, engine="auto")) if spmv_engine == "bf16" else None
            ),
        )
        return SolveResult(x=x, iterations=i, residual_norm=norm, converged=converged, history=hist)

    # ---- padded execution: pad once, run the loop pad/reshape-free ----
    n = A.n
    if core_name == "fused_iter":
        core = core_obj if core_obj is not None else make_fused_iter_core(A, tile=tile)
        t, n_pad = core.tile, core.n_pad
    else:
        core = get_core(core_name)
        t = _padded_tile(core_name, A.bandwidth, tile)
        n_pad = _padded_length(n, t)
    with trace_scope("pipecg.pad"):  # once per solve, never in the loop
        Ap = DIAMatrix(jnp.pad(A.data, ((0, 0), (0, n_pad - n))), A.offsets, n_pad)
        bp = pad1d(b, n_pad)
        x0p = pad1d(x0, n_pad)
        inv_p = pad1d(inv_diag, n_pad) if inv_diag is not None else None
        if core_name == "fused_iter" and inv_p is None:
            inv_p = jnp.ones((n_pad,), b.dtype)  # identity PC, fused elementwise
    spmv_fn, replace_fn = _padded_spmv_fns(Ap, spmv_engine, t)

    i, x, norm, converged, hist = run_pipecg(
        bp,
        x0p,
        spmv_fn=spmv_fn,
        pc_fn=(lambda r: inv_p * r) if inv_p is not None else (lambda r: r),
        core=core,
        inv_diag=inv_p,
        atol=atol,
        rtol=rtol,
        maxiter=maxiter,
        replace_every=replace_every,
        replace_spmv_fn=replace_fn,
    )
    return SolveResult(
        x=x[:n], iterations=i, residual_norm=norm, converged=converged, history=hist
    )


def _resolve_config(A, M, engine: str, spmv_engine, replace_every, core):
    """Shared engine/core/spmv/replace resolution for pipecg and plans."""
    core_name = "fused_iter" if core is not None else resolve_core_name(engine, A)
    if core_name == "fused_iter":
        if not isinstance(A, DIAMatrix):
            if engine == "auto":
                core_name = "pallas" if jax.default_backend() == "tpu" else "jnp"
            else:
                raise TypeError(
                    f"engine 'fused_iter' needs a DIAMatrix operator, got {type(A).__name__}"
                )
        elif M is not None and not _elementwise_pc(M):
            if engine == "auto":
                core_name = "pallas" if jax.default_backend() == "tpu" else "jnp"
            else:
                raise ValueError(
                    "engine 'fused_iter' fuses an elementwise preconditioner; "
                    f"use M='jacobi'/'identity', got {type(M).__name__}"
                )
    if spmv_engine is None:
        # fused_iter uses SPMV only at init/replacement -> backend default;
        # engine="pallas"/"auto" runs the whole iteration on kernels
        spmv_engine = "auto" if core_name == "fused_iter" or engine in ("pallas", "auto") else "jnp"
    if replace_every is None:
        replace_every = _BF16_REPLACE_EVERY if spmv_engine == "bf16" else 0
    return core_name, spmv_engine, int(replace_every)


def pin_pipecg_core(A, M, engine: str, spmv_engine=None, replace_every=None, tile=None):
    """Plan-time setup: build (once) the operator-pinned fused core.

    Returns the ``core`` object to thread into :func:`pipecg`, or None
    when the resolved configuration does not use one. Building here —
    rather than inside the solve trace — pins the padded diagonal views
    on the plan, so repeated solves reuse them and the while-loop body
    does zero padding/reshaping.
    """
    core_name, _, _ = _resolve_config(A, M, engine, spmv_engine, replace_every, None)
    if core_name != "fused_iter":
        return None
    return make_fused_iter_core(A, tile=tile)


def pipecg(
    A,
    b,
    M=None,
    x0=None,
    atol: float = 1e-5,
    rtol: float = 0.0,
    maxiter: int = 10000,
    engine: str = "jnp",
    spmv_engine: str | None = None,
    replace_every: int | None = None,
    tile: int | None = None,
    core=None,
) -> SolveResult:
    """Solve SPD ``A x = b`` with Pipelined PCG (Algorithm 2).

    engine="jnp"        — pure-jnp iteration core (oracle).
    engine="pallas"     — fused single-pass Pallas kernel for the 8 VMAs +
                          Jacobi PC + dot partials (paper §V-B, extended to
                          fold the dots); SPMV is a second kernel.
    engine="fused_iter" — the whole iteration (banded SPMV + VMAs + PC +
                          dot partials) as ONE Pallas kernel; requires a
                          DIAMatrix and Jacobi/identity PC.
    engine="auto"       — fused_iter on TPU when its requirements hold,
                          else pallas on TPU, jnp elsewhere.
    spmv_engine         — SPMV dispatch engine ("jnp"/"pallas"/"bf16"/
                          "auto"); defaults to "auto" for fused_iter (init
                          + residual replacement only) and to following
                          ``engine`` otherwise. "bf16" streams the band
                          data in bf16 with f32 accumulation — reduced
                          precision, half the SPMV traffic.
    replace_every       — if > 0, re-derive all auxiliary vectors from
                          their definitions (at full precision) every k
                          iterations. Default: 0, except {bf16} when
                          spmv_engine="bf16" — the residual-replacement
                          safety net reduced-precision runs require.
    tile                — row-tile override for the padded Pallas paths.
    core                — a prebuilt operator-pinned core from
                          :func:`pin_pipecg_core` (plans pass this so
                          padded views are pinned once, not per trace).
    """
    if M is None:
        M = identity()
    if x0 is None:
        x0 = jnp.zeros_like(b)
    core_name, spmv_engine, replace_every = _resolve_config(
        A, M, engine, spmv_engine, replace_every, core
    )
    return _pipecg_impl(
        A, b, M, x0, jnp.float32(atol), jnp.float32(rtol),
        maxiter, core_name, spmv_engine, replace_every, tile, core,
    )


if pipecg.__doc__:
    pipecg.__doc__ = pipecg.__doc__.replace("{bf16}", str(_BF16_REPLACE_EVERY))
