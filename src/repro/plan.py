"""Plan/execute solver API: ``repro.plan(A, ...) -> SolverPlan``.

PIPECG's economics are pay-setup-once, iterate-many: preconditioner
construction, the performance-model row decomposition, operator sharding
and tracing/compiling the iteration loop are all amortizable across every
right-hand side served against the same operator. This module is the
setup phase — the PETSc ``KSPSetUp`` / scipy ``factorized`` shape:

    p = repro.plan(A, method="h3", shards=8, M="jacobi")   # pay once
    res  = p.solve(b)                # reuses the pinned compiled loop
    many = p.solve_batched(B)        # (k, n) rhs -> ONE vmapped program
    p.describe()                     # method/engine/shard-bounds/reducer

What a plan pins at construction:

* the resolved preconditioner (``jacobi(A)`` is computed exactly once);
* for distributed methods — the perf-model ``decompose`` row boundaries,
  the device mesh, the ``ShardedDIA`` operator handle and the sharded
  inverse diagonal (nothing is re-sharded per solve);
* one jitted solve program per entry point (``solve`` / ``solve_batched``)
  with ``atol``/``rtol``/``x0`` as *traced* arguments, so changing the
  tolerance or warm-start between calls re-traces nothing.

``A`` may be any ``LinearOperator`` (``sparse.operators``): the
materialized ``DIAMatrix``/``BellMatrix``/``CSRMatrix`` formats, a dense
array, or a matrix-free :class:`~repro.sparse.FunctionOperator` (stencils
applied on the fly, Jacobian-vector products). Distributed methods still
require a ``DIAMatrix`` — their halo exchange derives from band offsets.

``repro.solve`` remains the one-shot form: a thin wrapper that fetches a
plan from a keyed cache (operator identity x configuration) and calls
``plan.solve`` — serving loops get plan reuse without holding a handle.
The single-device method registry (``register_solver``) lives here;
registered solver fns must be jit-traceable, since plans pin them inside
one compiled program.
"""
from __future__ import annotations

import hashlib
import inspect
import itertools
import sys as _sys
import time
from collections import OrderedDict
from typing import Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from .obs import metrics as _metrics
from .obs.trace import enabled as _obs_enabled, span as _span, trace_scope as _trace_scope
from .core import chronopoulos_cg, identity, jacobi, pcg, pipecg
from .core.distributed import (
    build_distributed_solver,
    make_solver_mesh,
    method_names,
)
from .core.perfmodel import decompose
from .core.preconditioners import IdentityPC, JacobiPC
from .core.types import SolveResult
from .sparse import balanced_rows, shard_dia, shard_vector, spmv, unshard_vector
from .sparse.partition import row_sharding
from .sparse.formats import DIAMatrix

__all__ = [
    "plan",
    "SolverPlan",
    "register_solver",
    "solver_names",
    "get_plan",
    "operator_fingerprint",
    "plan_cache_stats",
    "clear_plan_cache",
]


def operator_fingerprint(A) -> str:
    """Stable content hash of an operator, for cross-process plan keying.

    Unlike the in-process plan cache (which keys on ``id(A)``), this
    digests the operator's *contents* — type, static metadata, and array
    bytes — so two processes that build the same matrix derive the same
    fingerprint. This is what the serving tier's plan pool
    (``serve.router``) and the warm-start manifests (``serve.warmstart``)
    key on. Operators whose identity lives in Python objects (e.g. a
    matrix-free ``FunctionOperator``'s ``fn``) fall back to an
    ``id:``-prefixed process-local fingerprint: poolable, not
    manifest-portable.
    """
    h = hashlib.sha256()
    h.update(type(A).__name__.encode())
    if isinstance(A, DIAMatrix):
        h.update(repr((A.n, A.offsets, str(A.dtype))).encode())
        h.update(np.asarray(A.data).tobytes())
    elif hasattr(A, "ndim") and not hasattr(A, "matvec"):  # dense array
        arr = np.asarray(A)
        h.update(repr((arr.shape, str(arr.dtype))).encode())
        h.update(arr.tobytes())
    else:
        try:
            leaves, treedef = jax.tree_util.tree_flatten(A)
            td = repr(treedef)
            if "0x" in td:  # object reprs with addresses: not portable
                return f"id:{id(A):x}"
            h.update(td.encode())
            for leaf in leaves:
                h.update(np.asarray(leaf).tobytes())
        except Exception:
            return f"id:{id(A):x}"
    return h.hexdigest()[:16]


def _resolve_pc(M, A):
    if M is None or M == "identity" or M == "none":
        return identity()
    if M == "jacobi":
        return jacobi(A)  # needs A.diagonal(); matrix-free operators must pass diag=
    if isinstance(M, str):
        raise ValueError(f"unknown preconditioner name {M!r} (use 'jacobi'/'identity')")
    return M


def _require_jnp_engine(method: str, engine: str) -> None:
    # honest failure instead of silently running jnp under a "pallas" label
    if engine not in ("auto", "jnp"):
        raise ValueError(
            f"method {method!r} has no {engine!r} backend (the Pallas engines "
            "apply to pipecg and the distributed methods); use engine='jnp'/'auto'"
        )


def _solve_pcg(A, b, *, M, x0, atol, rtol, maxiter, engine):
    _require_jnp_engine("pcg", engine)
    return pcg(A, b, M=M, x0=x0, atol=atol, rtol=rtol, maxiter=maxiter)


def _solve_chronopoulos(A, b, *, M, x0, atol, rtol, maxiter, engine):
    _require_jnp_engine("chronopoulos", engine)
    return chronopoulos_cg(A, b, M=M, x0=x0, atol=atol, rtol=rtol, maxiter=maxiter)


def _solve_pipecg(A, b, *, M, x0, atol, rtol, maxiter, engine,
                  replace_every=None, spmv_engine=None, tile=None, core=None):
    return pipecg(
        A, b, M=M, x0=x0, atol=atol, rtol=rtol, maxiter=maxiter,
        engine=engine, spmv_engine=spmv_engine, replace_every=replace_every,
        tile=tile, core=core,
    )


SolverFn = Callable[..., SolveResult]

_PLAN_IDS = itertools.count(1)  # per-process plan number, the spans' ``plan``

_SOLVERS: Dict[str, SolverFn] = {
    "pcg": _solve_pcg,
    "chronopoulos": _solve_chronopoulos,
    "pipecg": _solve_pipecg,
}


def register_solver(name: str, fn: SolverFn, *, overwrite: bool = False) -> None:
    """Register a solve method: ``fn(A, b, *, M, x0, ...) -> SolveResult``.

    ``fn`` must be jit-traceable — plans pin it inside one compiled
    program. Raises ValueError if ``name`` is already registered, unless
    ``overwrite=True`` — silent replacement hides plug-in clashes.
    """
    if name in _SOLVERS and not overwrite:
        raise ValueError(
            f"solver {name!r} already registered; pass overwrite=True to replace it"
        )
    _SOLVERS[name] = fn


def solver_names() -> Tuple[str, ...]:
    """All method names, each exactly once, sorted."""
    return tuple(sorted(set(_SOLVERS) | set(method_names()) | {"pipecg_distributed"}))


class SolverPlan:
    """A pinned, reusable solver: setup done, only iteration remains.

    Build via :func:`repro.plan`. Thread-compatible for reads; build one
    plan per operator/configuration and fire right-hand sides at it.
    ``trace_count`` exposes how many times a solve program was traced —
    steady-state serving sits at 1 per entry point (the reuse guarantee
    the tests assert).
    """

    def __init__(self, A, *, method="pipecg", engine="auto", M="jacobi",
                 atol=1e-5, rtol=0.0, maxiter=10000, **kwargs):
        if method in method_names():  # "h1"/"h2"/"h3" aliases
            kwargs.setdefault("dist_method", method)
            method = "pipecg_distributed"
        distributed = method == "pipecg_distributed"
        if not distributed and method not in _SOLVERS:
            raise ValueError(f"unknown method {method!r}; have {solver_names()}")

        self.A = A
        self.method = method
        self.engine = engine
        self.atol = float(atol)
        self.rtol = float(rtol)
        self.maxiter = int(maxiter)
        self.n = int(A.shape[0]) if hasattr(A, "shape") else None
        self.distributed = distributed
        self._traces = 0
        self._id = next(_PLAN_IDS)
        self._call_ids = itertools.count(1)  # numbers solve calls, the spans' ``call``
        self._run = None
        self._run_batched = None
        self._run_x0 = None
        self.last_report = None       # SolveReport of the latest solve (obs on)
        self._census_launches = None  # cached launches/iter census (obs on)

        with _span("plan.build", method=method, engine=engine, n=self.n,
                   distributed=distributed):
            with _span("plan.resolve_pc"):
                self.M = _resolve_pc(M, A)
            if distributed:
                self._setup_distributed(kwargs)
            else:
                self._setup_single(kwargs)
        _metrics.counter("plan.builds").inc()

    # -- setup ------------------------------------------------------------

    def _setup_single(self, kwargs):
        fn = _SOLVERS[self.method]
        params = inspect.signature(fn).parameters
        if not any(p.kind is inspect.Parameter.VAR_KEYWORD for p in params.values()):
            unknown = set(kwargs) - set(params)
            if unknown:
                raise TypeError(
                    f"method {self.method!r} does not accept {sorted(unknown)}; "
                    f"it takes {sorted(k for k in params if k not in ('A', 'b'))}"
                )
        self.kwargs = dict(kwargs)
        A, M, engine, maxiter = self.A, self.M, self.engine, self.maxiter
        call_kwargs = dict(kwargs)
        if self.method == "pipecg" and call_kwargs.get("core") is None:
            # plan-time pinning: build the operator-bound fused_iter core
            # (padded diagonal views and all) ONCE here, not per trace —
            # the while-loop body then does zero padding/reshaping and
            # repeated solves reuse the exact same kernel closure
            from .core.pipecg import pin_pipecg_core

            with _span("plan.pin_core"):
                core = pin_pipecg_core(
                    A, M, engine,
                    spmv_engine=call_kwargs.get("spmv_engine"),
                    replace_every=call_kwargs.get("replace_every"),
                    tile=call_kwargs.get("tile"),
                )
            if core is not None:
                call_kwargs["core"] = core
        self._core = call_kwargs.get("core")

        def _inner(b, x0, atol, rtol):
            with self._traced(), _trace_scope(f"solve.{self.method}"):
                return fn(A, b, M=M, x0=x0, atol=atol, rtol=rtol,
                          maxiter=maxiter, engine=engine, **call_kwargs)

        self._inner = _inner
        self._run = jax.jit(_inner)

    def _setup_distributed(self, kwargs):
        dist_method = kwargs.pop("dist_method", "h3")
        shards = kwargs.pop("shards", 1)
        weights = kwargs.pop("weights", None)
        partition = kwargs.pop("partition", "rows")
        mesh = kwargs.pop("mesh", None)
        reducer = kwargs.pop("reducer", None)
        spmv_strategy = kwargs.pop("spmv", None)
        sub = kwargs.pop("sub", None)
        replace_every = int(kwargs.pop("replace_every", 0) or 0)
        if kwargs:
            raise TypeError(
                f"distributed plan does not accept {sorted(kwargs)}; it takes "
                f"['dist_method', 'mesh', 'partition', 'reducer', "
                f"'replace_every', 'shards', 'spmv', 'sub', 'weights']"
            )
        A = self.A
        if not isinstance(A, DIAMatrix):
            raise TypeError(f"distributed solve needs a DIAMatrix, got {type(A).__name__}")
        if shards < 1:
            raise ValueError(f"shards must be >= 1, got {shards}")
        if len(jax.devices()) < shards:
            raise RuntimeError(
                f"need {shards} devices but only {len(jax.devices())} visible; on CPU set "
                f"XLA_FLAGS=--xla_force_host_platform_device_count={shards} before importing jax"
            )
        if partition not in ("rows", "nnz"):
            raise ValueError(f"unknown partition {partition!r} (use 'rows' or 'nnz')")
        if isinstance(self.M, JacobiPC):
            inv_diag = self.M.inv_diag
        elif isinstance(self.M, IdentityPC):
            inv_diag = jnp.ones((A.n,), A.dtype)
        else:
            raise TypeError(
                f"distributed solve supports Jacobi/identity PCs, got {type(self.M).__name__}"
            )
        # ---- the paid-once setup: decomposition, mesh, operator handle ----
        with _span("plan.decompose", shards=int(shards), partition=partition):
            if weights is not None or partition == "nnz":
                bounds = decompose(A, shards, weights=None if weights is None else np.asarray(weights))
            else:
                bounds = balanced_rows(A.n, shards)
        self.dist_method = dist_method
        self.shards = int(shards)
        self.bounds = tuple(int(x) for x in np.asarray(bounds))
        with _span("plan.shard"):
            self.mesh = mesh if mesh is not None else make_solver_mesh(shards, sub=sub)
            # the reusable operator handle, each row block on its own device
            self.sharded = shard_dia(A, bounds, mesh=self.mesh)
        # every knob that changes the compiled program goes in here — this
        # dict is what describe() reports, and the same knobs (as user
        # kwargs) are what _plan_key freezes, so pl2/pl3/h4/sub/replace
        # variants never collide in the plan cache
        self.kwargs = {"dist_method": dist_method, "shards": self.shards,
                       "partition": partition, "reducer": reducer,
                       "spmv": spmv_strategy, "sub": sub,
                       "replace_every": replace_every}

        def _build_runner(nrhs=None):
            with _span("plan.build_solver", dist_method=dist_method,
                       nrhs=0 if nrhs is None else int(nrhs)):
                return build_distributed_solver(
                    self.sharded, mesh=self.mesh, method=dist_method,
                    engine=self.engine, maxiter=self.maxiter,
                    reducer=reducer, spmv=spmv_strategy,
                    replace_every=replace_every, nrhs=nrhs,
                )

        self._build_runner = _build_runner
        self._batched_runners = {}  # (k, with_x0) -> jitted batched program
        runner = _build_runner()
        self.pipeline_depth = runner.pipeline_depth
        self.reducer = runner.reduce_name
        self.spmv_strategy = runner.spmv_name
        mesh = self.mesh
        inv_sh = jax.device_put(shard_vector(inv_diag, bounds), row_sharding(mesh, 2))
        self._inv_sh = inv_sh
        bounds_arr = self.bounds

        def _solve_rhs(rhs, atol, rtol) -> SolveResult:
            b_sh = jax.lax.with_sharding_constraint(
                shard_vector(rhs, bounds_arr), row_sharding(mesh, 2))
            res = runner(b_sh, inv_sh, atol, rtol)
            return SolveResult(
                x=unshard_vector(res.x, bounds_arr), iterations=res.iterations,
                residual_norm=res.residual_norm, converged=res.converged,
                history=res.history,
            )

        def _inner0(b, atol, rtol):
            with self._traced():
                return _solve_rhs(b, atol, rtol)

        def _inner_x0(b, x0, atol, rtol):
            # nonzero warm start: solve the shifted system A d = b - A x0,
            # then x = x0 + d (no host sync, no x0==0 guard needed)
            with self._traced():
                res = _solve_rhs(b - spmv(A, x0), atol, rtol)
            return SolveResult(
                x=x0 + res.x, iterations=res.iterations,
                residual_norm=res.residual_norm, converged=res.converged,
                history=res.history,
            )

        self._run = jax.jit(_inner0)
        self._run_x0 = jax.jit(_inner_x0)

    # -- execution --------------------------------------------------------

    @property
    def trace_count(self) -> int:
        """Times any of this plan's solve programs has been (re)traced."""
        return self._traces

    def _traced(self):
        """Count a (re)trace of a solve program; its ``plan.trace`` span marks
        on the timeline the call that traced (trace time only)."""
        self._traces += 1
        _metrics.counter("plan.traces").inc()
        return _span("plan.trace", plan=self._id)

    def _call_span(self, name: str, **attrs):
        """The root span of one solve call: plan number, call counter, method."""
        return _span(name, plan=self._id, call=next(self._call_ids), method=self.method,
                     **attrs)

    def _tols(self, atol, rtol):
        return (
            jnp.float32(self.atol if atol is None else atol),
            jnp.float32(self.rtol if rtol is None else rtol),
        )

    def _execute(self, b, x0, atol, rtol) -> SolveResult:
        """Prepare the call's arguments, then dispatch the pinned program."""
        with _span("plan.solve.args"):  # tolerance scalars, x0 zeros
            atol, rtol = self._tols(atol, rtol)
            if x0 is None and not self.distributed:
                x0 = jnp.zeros_like(b)
        with _span("plan.solve.dispatch"):
            if not self.distributed:
                return self._run(b, x0, atol, rtol)
            if x0 is None:
                return self._run(b, atol, rtol)
            return self._run_x0(b, x0, atol, rtol)

    def solve(self, b, x0=None, atol: float | None = None, rtol: float | None = None) -> SolveResult:
        """Solve ``A x = b`` with this plan's pinned program.

        ``x0``/``atol``/``rtol`` are per-call and traced — varying them
        between calls does not retrace (``x0=None`` and ``x0=array`` are
        two distinct programs; steady state is still one trace each).

        With observability enabled (``repro.obs.enable()``) the solve is
        synchronized and timed, solve metrics are recorded, and a full
        :class:`~repro.obs.SolveReport` lands on ``self.last_report``.
        Otherwise dispatch stays async, and the call's spans (``plan.solve``
        > ``plan.solve.args``, ``plan.solve.dispatch``) record only while a
        profiler is capturing; the solve-loop jaxpr is the same either way.
        """
        if not _obs_enabled():
            with self._call_span("plan.solve"):
                return self._execute(b, x0, atol, rtol)
        traces_before = self._traces
        with self._call_span("plan.solve", n=self.n) as sp:
            t0 = time.perf_counter()
            res = self._execute(b, x0, atol, rtol)
            jax.block_until_ready(res)
            elapsed = time.perf_counter() - t0
        self._record_solve(res, elapsed, b, sp, cold=self._traces > traces_before)
        return res

    def _record_solve(self, res: SolveResult, elapsed: float, b, sp, *, cold: bool) -> None:
        """Obs-enabled bookkeeping: metrics + SolveReport (host side only)."""
        from .obs.report import plan_launches_per_iteration, solve_report

        if self._census_launches is None:
            # trace-only census, cached per plan: kernel launches per
            # iteration of the pinned loop (the fusion trajectory metric)
            self._census_launches = plan_launches_per_iteration(self, b)
        report = solve_report(
            self, res, elapsed_s=elapsed, launches=self._census_launches, cold_start=cold
        )
        self.last_report = report
        if sp is not None:
            sp.attrs.update(iterations=report.iterations, time_s=elapsed,
                            converged=report.converged, cold_start=cold)
        _metrics.counter("plan.solves").inc()
        if cold:
            # first solve through a fresh program: wall time is trace +
            # compile + solve; keep it out of the steady-state histogram
            _metrics.counter("plan.cold_solves").inc()
            _metrics.histogram("plan.cold_solve_time_s").record(elapsed)
        else:
            _metrics.histogram("plan.solve_time_s").record(elapsed)
        _metrics.histogram("plan.solve_iterations").record(report.iterations)
        if not report.converged:
            _metrics.counter("plan.solves_unconverged").inc()
        if report.rr_events:
            _metrics.counter("plan.rr_events").inc(report.rr_events)

    def solve_batched(self, B, x0=None, atol: float | None = None, rtol: float | None = None) -> SolveResult:
        """Solve a batch of rhs, shape (k, n) -> SolveResult with leading k.

        Single-device methods run as ONE vmapped XLA program (per-lane
        results are exact; wall-clock is set by the slowest rhs).
        Distributed methods also run as ONE program: the solver loop is
        vmapped *inside* the shard_map block, so each global reduction
        carries the whole batch's partials (k-fold useful work per
        reduction — see docs/distributed.md). The batched program is
        built+compiled once per batch size k and cached on the plan.
        With observability enabled the batch is synchronized/timed and
        batch metrics are recorded.
        """
        if not _obs_enabled():
            with self._call_span("plan.solve_batched"):
                return self._execute_batched(B, x0, atol, rtol)
        traces_before = self._traces
        with self._call_span("plan.solve_batched", k=int(B.shape[0]), n=self.n) as sp:
            t0 = time.perf_counter()
            res = self._execute_batched(B, x0, atol, rtol)
            jax.block_until_ready(res)
            elapsed = time.perf_counter() - t0
        from .obs.report import iterations_from_history, plan_launches_per_iteration, solve_report

        cold = self._traces > traces_before
        iters = iterations_from_history(res.history)
        if self._census_launches is None and B.shape[0]:
            self._census_launches = plan_launches_per_iteration(self, B[0])
        self.last_report = solve_report(
            self, res, elapsed_s=elapsed, launches=self._census_launches, cold_start=cold
        )
        if sp is not None:
            sp.attrs.update(time_s=elapsed, cold_start=cold,
                            iterations_max=int(np.max(iters)) if len(iters) else 0)
        _metrics.counter("plan.batched_solves").inc()
        _metrics.counter("plan.batched_rhs").inc(int(B.shape[0]))
        _metrics.histogram("plan.batch_size").record(int(B.shape[0]))
        _metrics.histogram(
            "plan.cold_solve_time_s" if cold else "plan.solve_time_s"
        ).record(elapsed)
        for it in np.asarray(iters).ravel():
            _metrics.histogram("plan.solve_iterations").record(int(it))
        return res

    def _batched_distributed(self, k: int, with_x0: bool):
        """The (k rhs, warm-start?) batched program, built+jitted once per k.

        One shard_map program for the whole batch: the solver loop is
        vmapped INSIDE the block (core.distributed), so every global
        reduction carries k systems' partials — no Python per-rhs loop.
        """
        cached = self._batched_runners.get((k, with_x0))
        if cached is not None:
            return cached
        runner = self._build_runner(nrhs=k)
        A, bounds, inv_sh = self.A, self.bounds, self._inv_sh

        def _solve_rhs_batch(B, atol, rtol) -> SolveResult:
            from .sparse import shard_vectors, unshard_vectors

            B_sh = jax.lax.with_sharding_constraint(
                shard_vectors(B, bounds), row_sharding(self.mesh, 3))
            res = runner(B_sh, inv_sh, atol, rtol)
            return SolveResult(
                x=unshard_vectors(res.x, bounds), iterations=res.iterations,
                residual_norm=res.residual_norm, converged=res.converged,
                history=res.history,
            )

        if with_x0:
            def _inner(B, X0, atol, rtol):
                # warm starts via the shifted systems A d_k = b_k - A x0_k
                with self._traced():
                    res = _solve_rhs_batch(B - jax.vmap(lambda v: spmv(A, v))(X0), atol, rtol)
                return SolveResult(
                    x=X0 + res.x, iterations=res.iterations,
                    residual_norm=res.residual_norm, converged=res.converged,
                    history=res.history,
                )
        else:
            def _inner(B, atol, rtol):
                with self._traced():
                    return _solve_rhs_batch(B, atol, rtol)

        jitted = jax.jit(_inner)
        self._batched_runners[(k, with_x0)] = jitted
        return jitted

    def _batched_single(self):
        if self._run_batched is None:
            self._run_batched = jax.jit(jax.vmap(self._inner, in_axes=(0, 0, None, None)))
        return self._run_batched

    def _execute_batched(self, B, x0, atol, rtol) -> SolveResult:
        with _span("plan.solve_batched.args"):
            atol, rtol = self._tols(atol, rtol)
            if x0 is None and not self.distributed:
                x0 = jnp.zeros_like(B)
        with _span("plan.solve_batched.dispatch"):
            if not self.distributed:
                return self._batched_single()(B, x0, atol, rtol)
            run = self._batched_distributed(int(B.shape[0]), x0 is not None)
            if x0 is None:
                return run(B, atol, rtol)
            return run(B, x0, atol, rtol)

    def lower(self, b) -> "jax.stages.Lowered":
        """The program ``solve(b)`` (1-D ``b``) or ``solve_batched(b)``
        (2-D ``b``, no ``x0``) runs, lowered for inspection — e.g.
        ``plan.lower(b).compile().as_text()``. A program that already ran
        with these shapes is not traced again."""
        atol, rtol = self._tols(None, None)
        if b.ndim == 2:
            if self.distributed:
                return self._batched_distributed(int(b.shape[0]), False).lower(b, atol, rtol)
            return self._batched_single().lower(b, jnp.zeros_like(b), atol, rtol)
        if self.distributed:
            return self._run.lower(b, atol, rtol)
        return self._run.lower(b, jnp.zeros_like(b), atol, rtol)

    def describe(self) -> dict:
        """Introspection: what this plan pinned at setup."""
        d = {
            "method": self.kwargs.get("dist_method", self.method) if self.distributed else self.method,
            "engine": self.engine,
            "n": self.n,
            "dtype": str(getattr(self.A, "dtype", "?")),
            "operator": type(self.A).__name__,
            "preconditioner": type(self.M).__name__,
            "atol": self.atol,
            "rtol": self.rtol,
            "maxiter": self.maxiter,
            "distributed": self.distributed,
            "trace_count": self._traces,
        }
        if self.distributed:
            d.update(
                shards=self.shards,
                shard_bounds=self.bounds,
                rows_per_shard=tuple(int(x) for x in np.diff(self.bounds)),
                reducer=self.reducer,            # override-resolved, not the
                spmv_strategy=self.spmv_strategy,  # method's registered default
                mesh_axes=tuple(self.mesh.axis_names),
                pipeline_depth=self.pipeline_depth,
                sub=self.kwargs.get("sub"),
                replace_every=self.kwargs.get("replace_every", 0),
            )
        else:
            d.update({k: v for k, v in self.kwargs.items() if v is not None})
            if self.method == "pipecg":
                from .core.pipecg import _resolve_config

                try:
                    cn, se, rep = _resolve_config(
                        self.A, self.M, self.engine,
                        self.kwargs.get("spmv_engine"),
                        self.kwargs.get("replace_every"),
                        getattr(self, "_core", None),
                    )
                except (TypeError, ValueError):
                    pass
                else:
                    d.update(core=cn, spmv_engine=se, replace_every=rep)
        return d

    def config(self) -> dict:
        """JSON-able rebuild recipe: ``plan(A, **cfg)`` on an operator with
        the same contents reproduces this plan (same ``describe()``, same
        pool key). This is the manifest-export hook the serving tier's
        cross-process warm start (``serve.warmstart``) serializes; it
        raises for plans whose configuration holds live Python objects
        (custom preconditioner / pinned core / explicit mesh) — those
        cannot be rebuilt from JSON.
        """
        if isinstance(self.M, JacobiPC):
            M = "jacobi"
        elif isinstance(self.M, IdentityPC):
            M = "identity"
        else:
            raise ValueError(
                f"plan with a custom preconditioner object "
                f"({type(self.M).__name__}) is not manifest-serializable; "
                "use M='jacobi'/'identity'"
            )
        cfg = {
            "method": self.method,
            "engine": self.engine,
            "M": M,
            "atol": self.atol,
            "rtol": self.rtol,
            "maxiter": self.maxiter,
        }
        for k, v in self.kwargs.items():
            if v is None:
                continue
            if not isinstance(v, (bool, int, float, str)):
                raise ValueError(
                    f"plan kwarg {k}={type(v).__name__} is not "
                    "manifest-serializable (pass plain scalars/strings)"
                )
            cfg[k] = v
        return cfg

    def __repr__(self) -> str:
        cfg = ", ".join(f"{k}={v!r}" for k, v in self.describe().items())
        return f"SolverPlan({cfg})"


def plan(A, method: str = "pipecg", engine: str = "auto", M="jacobi",
         *, atol: float = 1e-5, rtol: float = 0.0, maxiter: int = 10000,
         **kwargs) -> SolverPlan:
    """Build a reusable :class:`SolverPlan` for ``A`` (see module docstring).

    Keyword arguments mirror ``repro.solve``: ``replace_every``/
    ``spmv_engine``/``tile`` (pipecg — a pipecg plan with
    ``engine="fused_iter"`` builds the whole-iteration fused core and its
    padded operator views once, right here), ``shards``/``weights``/
    ``partition``/``mesh``/``reducer``/``spmv``/``sub``/``replace_every``
    (distributed methods — ``sub`` builds the 2-D hierarchical mesh the
    "h4" reducer needs; see docs/distributed.md for the selection matrix). ``atol``/``rtol`` set
    the plan's *defaults* — ``plan.solve(b, atol=...)`` overrides per
    call without retracing.
    """
    return SolverPlan(A, method=method, engine=engine, M=M,
                      atol=atol, rtol=rtol, maxiter=maxiter, **kwargs)


# ---------------------------------------------------------------------------
# the keyed plan cache behind one-shot ``repro.solve``
# ---------------------------------------------------------------------------

_PLAN_CACHE: "OrderedDict[tuple, SolverPlan]" = OrderedDict()
_PLAN_CACHE_MAX = 16
_CACHE_STATS = {"hits": 0, "misses": 0, "uncachable": 0}


def _freeze(v):
    if v is None or isinstance(v, (bool, int, float, str, bytes)):
        return v
    if isinstance(v, (list, tuple)):
        return tuple(_freeze(x) for x in v)
    if hasattr(v, "ravel"):  # numpy / jax arrays (e.g. weights)
        return ("arr",) + tuple(np.asarray(v).ravel().tolist())
    return ("id", id(v))  # identity-keyed; the plan keeps the object alive


def _plan_key(A, method, engine, M, maxiter, kwargs):
    Mk = M if (M is None or isinstance(M, str)) else ("id", id(M))
    items = tuple((k, _freeze(kwargs[k])) for k in sorted(kwargs))
    key = (id(A), method, engine, Mk, int(maxiter), items)
    try:
        hash(key)
    except TypeError:
        return None
    return key


def get_plan(A, *, method="pipecg", engine="auto", M="jacobi",
             maxiter: int = 10000, **kwargs) -> SolverPlan:
    """Fetch-or-build a cached plan keyed on operator identity x config.

    Identity keys (``id(A)``, ``id(M)``, ...) are safe because the cached
    plan holds strong references to those exact objects — an id cannot be
    reused while its entry lives. A hit is verified with ``is`` against
    the live operator; eviction is LRU at {max} entries.
    """
    key = _plan_key(A, method, engine, M, maxiter, kwargs)
    if key is not None:
        cached = _PLAN_CACHE.get(key)
        if cached is not None and cached.A is A:
            _PLAN_CACHE.move_to_end(key)
            _CACHE_STATS["hits"] += 1
            _metrics.counter("plan_cache.hits").inc()
            return cached
        _CACHE_STATS["misses"] += 1
        _metrics.counter("plan_cache.misses").inc()
    else:
        _CACHE_STATS["uncachable"] += 1
        _metrics.counter("plan_cache.uncachable").inc()
    p = plan(A, method=method, engine=engine, M=M, maxiter=maxiter, **kwargs)
    if key is not None:
        _PLAN_CACHE[key] = p
        while len(_PLAN_CACHE) > _PLAN_CACHE_MAX:
            _PLAN_CACHE.popitem(last=False)
        _metrics.gauge("plan_cache.size").set(len(_PLAN_CACHE))
    return p


if get_plan.__doc__:
    get_plan.__doc__ = get_plan.__doc__.replace("{max}", str(_PLAN_CACHE_MAX))


def plan_cache_stats() -> dict:
    """Hit/miss/uncachable counters + current size of the plan cache."""
    return dict(_CACHE_STATS, size=len(_PLAN_CACHE))


def clear_plan_cache() -> None:
    _PLAN_CACHE.clear()
    for k in _CACHE_STATS:
        _CACHE_STATS[k] = 0


# ``repro.plan`` names both this module and the entry-point function; any
# ``import repro.plan`` sets the package attribute to the module, which
# would otherwise shadow the callable. Making the module itself callable
# (delegating to :func:`plan`) keeps ``repro.plan(A, ...)`` working under
# every import order while ``repro.plan.SolverPlan`` etc. stay reachable.
class _CallableModule(_sys.modules[__name__].__class__):
    __call__ = staticmethod(plan)


_sys.modules[__name__].__class__ = _CallableModule
