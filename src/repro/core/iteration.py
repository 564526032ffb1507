r"""The canonical PIPECG iteration — one core, many execution strategies.

Every PIPECG execution in this repo (single-device jnp, single-device
fused-Pallas, distributed h1/h2/h3 under ``shard_map``) runs the SAME
recurrence (Ghysels & Vanroose Alg. 2, lines 10-21):

    scalars   beta_i, alpha_i           <- gamma/delta/alpha of it. i-1/i
    VMAs      z,q,s,p (10-13)           <- beta
    VMAs      x,r,u,w (14-17)           <- alpha
    dots      gamma', delta', ||u||^2   (18-20)   \   independent of
    PC        m = M^-1 w                (21)       >  each other ->
    SPMV      n = A m                   (22)      /   overlappable

The dots' results are consumed only at the *next* iteration's scalar
computation — the slack the paper's hybrid methods exploit. What differs
between executions is pure strategy, injected as three callables:

* the **iteration core** (``get_core``): how the 8 VMAs + PC + dot
  partials are evaluated — ``"jnp"`` (XLA fuses what it can),
  ``"pallas"`` (one explicit single-pass TPU kernel, paper §V-B), or
  ``"fused_iter"`` (the SPMV folded in too — ONE kernel per iteration,
  Rupp et al. arXiv 1410.4054).
* the **SPMV strategy** (``spmv_fn``): dense / DIA / BELL on one device
  (``sparse.spmv`` engine dispatch), or all-gather / halo-ppermute row
  blocks inside ``shard_map`` (``core.distributed``).
* the **reduction strategy** (``core.reduce``): identity on one device,
  three separate psums (h1) or one packed psum (h2/h3) on a mesh.

The core x operator selection matrix (see ``sparse.spmv`` for the
orthogonal SPMV-engine axis):

    core          needs                    SPMV per iteration    kernels/iter
    -----------   ----------------------   -------------------   ------------
    "jnp"         any LinearOperator       via spmv_fn           XLA-fused
    "pallas"      any LinearOperator       via spmv_fn           2 (VMA+SPMV)
    "fused_iter"  DIAMatrix, bandwidth     inside the kernel     1
                  <= tile, Jacobi or
                  identity PC
    "auto"        resolves: fused_iter on TPU when its "needs" hold,
                  else pallas on TPU, else jnp.

``"fused_iter"`` cores are built per operator (``register_core`` accepts
factories flagged ``needs_operator``) and carry ``fuses_spmv=True`` —
``run_pipecg`` then drops the carried n vector and the per-iteration
``spmv_fn`` call, since the kernel computes n = A m itself. Such cores
run on padded operands pinned once per solve (``core.pipecg``).

``run_pipecg`` is the single solver loop all of them share; there is
exactly one implementation of the recurrence in the repository
(``pipecg_vma_core``) and both Pallas kernels' oracles delegate to it.
``make_deep_pipecg_core(l)`` builds the communication-reduced sibling
loop (ONE global reduction per *l* iterations — distributed methods
``pl2``/``pl3``); the method x reducer selection matrix lives in
docs/distributed.md.
"""
from __future__ import annotations

from typing import Callable, Optional, Tuple

import jax
import jax.numpy as jnp

from ..obs.trace import trace_scope
from .reduce import Reducer, make_reducer

__all__ = [
    "dot_f32",
    "pipecg_vma_core",
    "vma_core_pallas",
    "make_fused_iter_core",
    "make_deep_pipecg_core",
    "resolve_core_name",
    "get_core",
    "core_names",
    "register_core",
    "run_pipecg",
]


def dot_f32(a: jax.Array, b: jax.Array) -> jax.Array:
    """Dot product accumulated in at-least-float32 (float64 stays float64)."""
    acc = jnp.promote_types(a.dtype, jnp.float32)
    return jnp.sum(a.astype(acc) * b.astype(acc))


# ---------------------------------------------------------------------------
# the iteration core (Alg. 2 lines 10-21 + dot partials)
# ---------------------------------------------------------------------------

def pipecg_vma_core(z, q, s, p, x, r, u, w, n, m, inv_diag, alpha, beta):
    """THE PIPECG recurrence: 8 VMAs + (Jacobi) PC + 3 dot partials.

    ``inv_diag`` is the fused Jacobi inverse diagonal, or None when the
    preconditioner is applied by the caller (m is then returned as w).
    Returns updated vectors plus the (local, unreduced) dot partials
    ``(gamma, delta, ||u||^2)``.
    """
    z = n + beta * z
    q = m + beta * q
    s = w + beta * s
    p = u + beta * p
    x = x + alpha * p
    r = r - alpha * s
    u = u - alpha * q
    w = w - alpha * z
    m = inv_diag * w if inv_diag is not None else w
    return z, q, s, p, x, r, u, w, m, (dot_f32(r, u), dot_f32(w, u), dot_f32(u, u))


def vma_core_pallas(z, q, s, p, x, r, u, w, n, m, inv_diag, alpha, beta):
    """Same contract as :func:`pipecg_vma_core` via the fused Pallas kernel."""
    from ..kernels.fused_vma import fused_vma_dots

    inv = inv_diag if inv_diag is not None else jnp.ones_like(w)
    *vecs, dots = fused_vma_dots(z, q, s, p, x, r, u, w, n, m, inv, alpha, beta)
    return (*vecs, (dots[0], dots[1], dots[2]))


def make_fused_iter_core(A, *, tile: Optional[int] = None,
                         interpret: Optional[bool] = None,
                         data_dtype=None) -> Callable:
    """Build a whole-iteration core for one DIA operator (ONE kernel/iter).

    The returned core fuses the banded SPMV n = A m into the VMA + PC +
    dot-partials pass (``kernels.fused_iter``), so ``run_pipecg`` launches
    a single Pallas kernel per iteration. It operates on *padded* vectors
    of length ``core.n_pad`` (a multiple of ``core.tile``); the padded
    diagonal data is pinned on the core at build time — build once per
    plan, not per solve. ``data_dtype`` (e.g. ``jnp.bfloat16``) stores the
    pinned diagonals in reduced precision while the kernel still
    accumulates in f32 — the mixed-precision band storage of the "bf16"
    SPMV engine, applied to the fused path.

    Attributes: ``fuses_spmv=True`` (run_pipecg drops its per-iteration
    spmv_fn call), ``n_pad``, ``tile``, ``padded_data``, ``offsets``.
    """
    from ..kernels.common import ceil_to, interpret_default
    from ..kernels.fused_iter import fused_iter_step, fused_iter_tile
    from ..sparse.formats import DIAMatrix

    if not isinstance(A, DIAMatrix):
        raise TypeError(
            f"core 'fused_iter' needs a DIAMatrix operator (its SPMV is a "
            f"fused banded kernel), got {type(A).__name__}"
        )
    t = fused_iter_tile(A.bandwidth, tile)
    n_pad = ceil_to(A.n, t)
    dp = jnp.pad(A.data, ((0, 0), (0, n_pad - A.n)))
    if data_dtype is not None:
        dp = dp.astype(data_dtype)
    if interpret is None:
        interpret = interpret_default()
    offsets = A.offsets

    def core(z, q, s, p, x, r, u, w, m, inv_diag, alpha, beta):
        inv = inv_diag if inv_diag is not None else jnp.ones_like(w)
        *vecs, dots = fused_iter_step(
            dp, offsets, z, q, s, p, x, r, u, w, m, inv, alpha, beta,
            tile=t, interpret=interpret,
        )
        return (*vecs, (dots[0], dots[1], dots[2]))

    core.fuses_spmv = True
    core.n_pad = n_pad
    core.tile = t
    core.padded_data = dp
    core.offsets = offsets
    core.interpret = interpret
    return core


make_fused_iter_core.needs_operator = True

_CORES = {
    "jnp": pipecg_vma_core,
    "pallas": vma_core_pallas,
    "fused_iter": make_fused_iter_core,
}


def register_core(name: str, core: Callable, *, overwrite: bool = False) -> None:
    """Register an alternative iteration-core engine (plug-in point).

    ``core`` is either a plain core callable (the ``pipecg_vma_core``
    contract) or, when flagged ``core.needs_operator = True``, a factory
    ``core(A, **kwargs) -> core_fn`` built per operator (the
    ``fused_iter`` pattern). Raises ValueError if ``name`` is already
    registered, unless ``overwrite=True`` — silent replacement hides
    plug-in clashes.
    """
    if name in _CORES and not overwrite:
        raise ValueError(
            f"iteration core {name!r} already registered; pass overwrite=True to replace it"
        )
    _CORES[name] = core


def core_names() -> Tuple[str, ...]:
    return tuple(sorted(_CORES))


def resolve_core_name(engine: str, A=None) -> str:
    """The core name ``get_core`` will build for this engine/operator.

    "auto" prefers, in order: "fused_iter" on TPU when the operator is a
    DIAMatrix whose bandwidth fits the kernel tile (Jacobi/identity PC
    checked by the caller), "pallas" on TPU, else "jnp" — the transparent
    fallback chain for operators the fused kernel cannot take.
    """
    if engine != "auto":
        return engine
    if jax.default_backend() != "tpu":
        return "jnp"
    from ..kernels.fused_iter import TILE
    from ..sparse.formats import DIAMatrix

    if isinstance(A, DIAMatrix) and A.bandwidth < TILE:
        return "fused_iter"
    return "pallas"


def get_core(engine: str, A=None, **factory_kwargs) -> Callable:
    """Resolve an iteration core; operator-built cores take ``A`` (+kwargs)."""
    engine = resolve_core_name(engine, A)
    if engine not in _CORES:
        raise ValueError(f"unknown iteration engine {engine!r}; have {core_names()}")
    core = _CORES[engine]
    if getattr(core, "needs_operator", False):
        return core(A, **factory_kwargs)
    return core


# ---------------------------------------------------------------------------
# the shared solver loop
# ---------------------------------------------------------------------------

def run_pipecg(
    b: jax.Array,
    x0: jax.Array,
    *,
    spmv_fn: Callable[[jax.Array], jax.Array],
    pc_fn: Callable[[jax.Array], jax.Array],
    core: Callable = pipecg_vma_core,
    reducer: Optional[Reducer] = None,
    inv_diag: Optional[jax.Array] = None,
    atol,
    rtol,
    maxiter: int,
    replace_every: int = 0,
    replace_spmv_fn: Optional[Callable[[jax.Array], jax.Array]] = None,
):
    """One PIPECG solve, generic over SPMV / PC / core / reduction strategy.

    Must be called under ``jit`` (or inside ``shard_map``); ``maxiter`` and
    ``replace_every`` are Python ints (static). When ``inv_diag`` is given
    the core fuses the Jacobi PC; otherwise ``pc_fn`` is applied to w each
    iteration. Cores flagged ``fuses_spmv`` (``make_fused_iter_core``)
    compute n = A m inside the kernel: the loop then carries no n vector
    and issues no per-iteration ``spmv_fn`` call — ``spmv_fn`` is still
    used for init and residual replacement. ``replace_spmv_fn`` overrides
    the SPMV used by residual replacement only: the full-precision safety
    net (f32, or f64 under x64) when the iteration SPMV runs reduced
    precision (the "bf16" engine). Returns ``(iterations, x,
    residual_norm, converged, history)`` as raw arrays so callers can
    rewrap (SolveResult / shard_map out_specs).
    """
    if reducer is None:
        reducer = make_reducer("local")
    if replace_spmv_fn is None:
        replace_spmv_fn = spmv_fn
    fused_spmv = bool(getattr(core, "fuses_spmv", False))
    dtype = b.dtype

    # init (Alg. 2 lines 1-3) — trace_scope tags HLO names only (zero
    # primitives added; a no-op context unless repro.obs is enabled)
    with trace_scope("pipecg.init"):
        r0 = b - spmv_fn(x0)
        u0 = pc_fn(r0)
        w0 = spmv_fn(u0)
        gamma0, delta0, nn0 = reducer(dot_f32(r0, u0), dot_f32(w0, u0), dot_f32(u0, u0))
        norm0 = jnp.sqrt(nn0)
        m0 = pc_fn(w0)
        # a fused core computes n = A m itself; carry a width-0 placeholder
        n0 = jnp.zeros((0,), dtype) if fused_spmv else spmv_fn(m0)
    thresh = jnp.maximum(jnp.asarray(atol, norm0.dtype), jnp.asarray(rtol, norm0.dtype) * norm0)
    hist0 = jnp.full((maxiter + 1,), jnp.nan, jnp.float32).at[0].set(norm0.astype(jnp.float32))
    zv = jnp.zeros_like(b)

    def cond(state):
        i = state[0]
        norm = state[-2]
        return (i < maxiter) & (norm > thresh)

    def _replace(x, p):
        # Residual replacement (Cools & Vanroose): re-derive every
        # auxiliary vector from its definition to arrest the recurrence
        # roundoff drift that plain PIPECG accumulates.
        r = b - replace_spmv_fn(x)
        u = pc_fn(r)
        w = replace_spmv_fn(u)
        s = replace_spmv_fn(p)
        q = pc_fn(s)
        z = replace_spmv_fn(q)
        m = pc_fn(w)
        n = jnp.zeros((0,), dtype) if fused_spmv else replace_spmv_fn(m)
        gamma, delta, nn = reducer(dot_f32(r, u), dot_f32(w, u), dot_f32(u, u))
        return r, u, w, s, q, z, m, n, gamma, delta, jnp.sqrt(nn)

    def step(state, replace: bool):
        """One iteration; ``replace`` (static) ends it with a replacement."""
        (i, x, r, u, w, z, q, s, p, m, n,
         gamma, gamma_prev, delta, alpha_prev, norm, hist) = state
        # scalars (lines 5-9) — consume *previous* iteration's reductions
        beta = jnp.where(i > 0, gamma / gamma_prev, 0.0)
        alpha = jnp.where(
            i > 0, gamma / (delta - beta * gamma / alpha_prev), gamma / delta
        )
        # the one canonical core (lines 10-21; +22 when the core fuses it)
        with trace_scope("pipecg.iteration.core"):
            if fused_spmv:
                z, q, s, p, x, r, u, w, m, (g_p, d_p, n_p) = core(
                    z, q, s, p, x, r, u, w, m, inv_diag, alpha.astype(dtype), beta.astype(dtype)
                )
            else:
                z, q, s, p, x, r, u, w, m, (g_p, d_p, n_p) = core(
                    z, q, s, p, x, r, u, w, n, m, inv_diag, alpha.astype(dtype), beta.astype(dtype)
                )
                if inv_diag is None:
                    m = pc_fn(w)  # general (non-fused) preconditioner
        # the reduction(s): results consumed next iteration only
        with trace_scope("pipecg.iteration.reduce"):
            gamma_new, delta_new, uu = reducer(g_p, d_p, n_p)
        if not (fused_spmv or replace):  # a replacement re-derives n itself
            # SPMV (line 22) — independent of the reductions: overlap target
            with trace_scope("pipecg.iteration.spmv"):
                n = spmv_fn(m)
        norm_new = jnp.sqrt(uu)
        if replace:
            with trace_scope("pipecg.residual_replacement"):
                r, u, w, s, q, z, m, n, gamma_new, delta_new, norm_new = _replace(x, p)

        hist = hist.at[i + 1].set(norm_new.astype(jnp.float32))
        return (
            i + 1, x, r, u, w, z, q, s, p, m, n,
            gamma_new, gamma, delta_new, alpha, norm_new, hist,
        )

    if replace_every > 0:
        # Replacement after iterations i with i > 0 and (i + 1) % k == 0.
        # Plain iterations run in inner loops that stop short of each
        # replacement; the outer loop runs the replacing iteration, its
        # stop test the guard. No cond passes vectors through, so each
        # stays in one loop-carried buffer, and under vmap the replacement
        # runs once per period. (A cond around the replacing step also made
        # XLA sink a second copy of a constant operator into its branch.)
        def plain(state):
            i = state[0]
            return cond(state) & ~((i > 0) & (jnp.mod(i + 1, replace_every) == 0))

        def run_plain(state):
            return jax.lax.while_loop(plain, lambda st: step(st, False), state)

        def loop(state):
            return jax.lax.while_loop(cond, lambda st: run_plain(step(st, True)), run_plain(state))
    else:
        def loop(state):
            return jax.lax.while_loop(cond, lambda st: step(st, False), state)

    acc = gamma0.dtype
    state = (
        jnp.int32(0), x0, r0, u0, w0, zv, zv, zv, zv, m0, n0,
        gamma0, jnp.ones((), acc), delta0, jnp.ones((), acc), norm0, hist0,
    )
    out = loop(state)
    i, x, norm, hist = out[0], out[1], out[-2], out[-1]
    return i, x, norm, norm <= thresh, hist


# ---------------------------------------------------------------------------
# depth-l pipelined (communication-reduced) CG — ONE reduction per l steps
# ---------------------------------------------------------------------------

def make_deep_pipecg_core(l: int):
    r"""Build the depth-``l`` pipelined CG solver loop (1 reduction / l its).

    PIPECG hides ONE global reduction behind ONE SPMV; once the reduction
    latency exceeds an SPMV, that slack is spent and strong scaling stalls
    (ROADMAP item 2, after Cornelis/Cools/Vanroose arXiv 1801.04728 and
    Cools et al. arXiv 1905.06850). The depth-``l`` methods attack the
    same bound by *amortization*: the while-loop body advances ``l`` CG
    iterations on extra Krylov-basis recurrences and performs exactly ONE
    packed global reduction — a (2l+1)x(2l+1) Gram matrix psum — per
    body. The jaxpr census over the while body proves it: 1 ``psum`` per
    ``l`` iterations, vs 1 per iteration for pipecg.

    Per outer step on the split-preconditioned operator
    ``At = D^{-1/2} A D^{-1/2}`` (Jacobi/identity only — exactly what the
    distributed methods support; CG on ``At`` generates the same iterates
    as Jacobi-PCG on ``A`` in exact arithmetic):

    * **Z-basis recurrences** — the monomial bases
      ``P_j = At^j p`` (j=0..l) and ``R_j = At^j r`` (j=0..l-1):
      ``2l-1`` SPMVs, no communication beyond the SPMV's own halo.
    * **ONE reduction** — the stacked Gram matrices ``V^T V`` and
      ``V^T D^{-1} V`` of the basis ``V = [P | R]``, reduced through the
      reducer's ``.array`` strategy (``core.reduce``).
    * **l coordinate iterations** — classic CG steps carried as
      length-(2l+1) coordinate vectors; every dot product is a tiny
      ``c^T G c`` form, so no further communication. Per-lane convergence
      masking keeps iteration counts exact (a solve that converges at
      iteration 7 under ``pl3`` reports 7, not 9).
    * **recurrence->vector recovery** + optional full-precision residual
      replacement (``replace_every``), the same safety net ``run_pipecg``
      uses, rounded to outer-step cadence.

    The trade is explicit: reduction *count* drops ``l``-fold while SPMV
    count rises to ``(2l-1)/l`` per iteration — the right exchange when
    the global reduction latency, not local bandwidth, bounds scaling
    (see docs/distributed.md for the selection matrix).

    Returns a loop with the :func:`run_pipecg` signature (so
    ``build_distributed_solver`` swaps it in transparently), tagged
    ``pipeline_depth = l``. Requires an elementwise preconditioner
    passed as ``inv_diag`` (None = identity); ``pc_fn``/``core`` are
    accepted for signature compatibility and must be None/elementwise.
    """
    if l < 1:
        raise ValueError(f"pipeline depth must be >= 1, got {l}")
    m = 2 * l + 1  # basis size: P_0..P_l, R_0..R_{l-1}

    # static shift matrix: coordinates of (At v) from coordinates of v.
    # Columns l (P_l) and 2l (R_{l-1}) are zero — the inner CG steps never
    # apply At to a vector reaching those basis tails (degree argument:
    # p_j uses P_{<=j}, R_{<=j-1} for j < l).
    import numpy as _np

    S_np = _np.zeros((m, m), dtype=_np.float32)
    for j in range(l):
        S_np[j + 1, j] = 1.0
    for j in range(l - 1):
        S_np[l + 2 + j, l + 1 + j] = 1.0

    def run_deep_pipecg(
        b: jax.Array,
        x0: jax.Array,
        *,
        spmv_fn: Callable[[jax.Array], jax.Array],
        pc_fn: Optional[Callable[[jax.Array], jax.Array]] = None,
        core: Optional[Callable] = None,
        reducer: Optional[Reducer] = None,
        inv_diag: Optional[jax.Array] = None,
        atol,
        rtol,
        maxiter: int,
        replace_every: int = 0,
        replace_spmv_fn: Optional[Callable[[jax.Array], jax.Array]] = None,
    ):
        del pc_fn, core  # elementwise PC only; fused via inv_diag
        if reducer is None:
            reducer = make_reducer("local")
        reduce_array = getattr(reducer, "array", None)
        if reduce_array is None:
            raise ValueError(
                "deep-pipeline methods need a reducer with an '.array' "
                "reduction (all core.reduce strategies have one; attach "
                "reducer.array = ... on custom reducers)"
            )
        if replace_spmv_fn is None:
            replace_spmv_fn = spmv_fn
        dtype = b.dtype
        acc = jnp.promote_types(dtype, jnp.float32)
        S = jnp.asarray(S_np, acc)

        # split preconditioning: solve At xt = bt with At = D^-1/2 A D^-1/2
        if inv_diag is not None:
            isd = jnp.sqrt(inv_diag)
            dsq = jnp.where(isd > 0, 1.0 / jnp.where(isd > 0, isd, 1.0), 0.0)
        else:
            isd = dsq = None

        def _split(v):
            return isd * v if isd is not None else v

        def _At(v, raw=spmv_fn):
            return _split(raw(_split(v)))

        with trace_scope("deep_pipecg.init"):
            bt = _split(b)
            xt0 = dsq * x0 if dsq is not None else x0
            rt0 = bt - _At(xt0)
            # convergence metric matches run_pipecg: ||u|| with u = D^-1 r
            # = D^-1/2 rt, i.e. rt^T D^-1 rt — one init-only reduction
            nn_part = dot_f32(rt0, inv_diag * rt0 if inv_diag is not None else rt0)
            norm0 = jnp.sqrt(reducer(nn_part, nn_part, nn_part)[2])
        thresh = jnp.maximum(
            jnp.asarray(atol, norm0.dtype), jnp.asarray(rtol, norm0.dtype) * norm0
        )
        # +1 slack slot: sentinel writes from masked (converged/past-maxiter)
        # inner steps land at maxiter+1 and are sliced off at the end
        hist0 = jnp.full((maxiter + 2,), jnp.nan, jnp.float32).at[0].set(
            norm0.astype(jnp.float32)
        )
        rr_outer = max(1, -(-replace_every // l)) if replace_every > 0 else 0

        def cond(state):
            i = state[0]
            norm = state[-2]
            return (i < maxiter) & (norm > thresh)

        def body(state):
            i, o, xt, rt, p, norm, hist = state

            # --- Z-basis recurrences: 2l-1 SPMVs, zero extra reductions ---
            with trace_scope("deep_pipecg.basis"):
                basis = [p]
                for _ in range(l):
                    basis.append(_At(basis[-1]))
                basis.append(rt)
                for _ in range(l - 1):
                    basis.append(_At(basis[-1]))
                V = jnp.stack(basis)  # (m, R)

            # --- the ONE global reduction per l iterations ---
            with trace_scope("deep_pipecg.gram"):
                Va = V.astype(acc)
                G_loc = Va @ Va.T
                if inv_diag is not None:
                    H_loc = (Va * inv_diag.astype(acc)) @ Va.T
                    G, H = reduce_array(jnp.stack([G_loc, H_loc]))
                else:
                    G = H = reduce_array(G_loc)

            # --- l CG iterations in coordinates (no communication) ---
            with trace_scope("deep_pipecg.coordinate_steps"):
                pc = jnp.zeros((m,), acc).at[0].set(1.0)
                rc = jnp.zeros((m,), acc).at[l + 1].set(1.0)
                xc = jnp.zeros((m,), acc)
                for j in range(l):
                    active = (norm > thresh) & (i < maxiter)
                    sc = S @ pc  # coordinates of At p
                    rr = rc @ (G @ rc)
                    pAp = pc @ (G @ sc)
                    alpha = rr / pAp
                    xc_n = xc + alpha * pc
                    rc_n = rc - alpha * sc
                    beta = (rc_n @ (G @ rc_n)) / rr
                    pc_n = rc_n + beta * pc
                    norm_n = jnp.sqrt(jnp.maximum(rc_n @ (H @ rc_n), 0.0))
                    xc = jnp.where(active, xc_n, xc)
                    rc = jnp.where(active, rc_n, rc)
                    pc = jnp.where(active, pc_n, pc)
                    norm = jnp.where(active, norm_n.astype(norm.dtype), norm)
                    idx = jnp.where(active, i + 1, maxiter + 1)  # sentinel slot
                    hist = hist.at[idx].set(norm_n.astype(jnp.float32))
                    i = i + active.astype(jnp.int32)

            # --- recover the full vectors from their coordinates ---
            with trace_scope("deep_pipecg.recover"):
                xt = xt + (xc.astype(dtype) @ V)
                rt = (rc.astype(dtype) @ V)
                p = (pc.astype(dtype) @ V)

            if rr_outer > 0:
                # Residual replacement at outer-step cadence: re-derive the
                # true (split) residual at full precision to arrest the
                # coordinate-recurrence drift — the deep-pipeline analogue
                # of run_pipecg's replace_every safety net.
                def _replace(args):
                    xt_, rt_ = args
                    with trace_scope("deep_pipecg.residual_replacement"):
                        return xt_, bt - _At(xt_, raw=replace_spmv_fn)

                xt, rt = jax.lax.cond(
                    jnp.mod(o + 1, rr_outer) == 0, _replace, lambda a: a, (xt, rt)
                )

            return (i, o + 1, xt, rt, p, norm, hist)

        state = (jnp.int32(0), jnp.int32(0), xt0, rt0, rt0, norm0, hist0)
        out = jax.lax.while_loop(cond, body, state)
        i, xt, norm, hist = out[0], out[2], out[-2], out[-1]
        x = _split(xt)  # back-transform: x = D^-1/2 xt
        return i, x, norm, norm <= thresh, hist[: maxiter + 1]

    run_deep_pipecg.pipeline_depth = l
    run_deep_pipecg.spmvs_per_iteration = (2 * l - 1) / l
    return run_deep_pipecg
