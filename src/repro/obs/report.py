"""SolveReport: one solve, every number needed to check a "faster" claim.

The paper's contribution is *measured* — overlap, fusion and the CPU/GPU
decomposition are justified by wall-clock and a performance model — so a
solve result here carries its evidence: the trimmed convergence curve,
iterations-to-tolerance, time-to-solution, kernel launches per iteration
(from the jaxpr census in ``kernels.common``), residual-replacement
events, plan-cache traffic and an environment fingerprint that makes
trajectory points comparable across runs. Bandwidth is not here: a share
of the HBM roofline needs device time from a profiler trace, which the
benchmark under ``bench/`` reads.

``SolverPlan.solve`` builds one of these automatically when observability
is enabled (``plan.last_report``); :func:`solve_report` is the manual
form. :func:`convergence_curve` is the one NaN-trimming implementation —
``SolveResult.history`` is NaN-padded past convergence and has *no* NaN
tail at exactly-maxiter solves, the off-by-one everyone hand-rolling the
slice gets wrong.
"""
from __future__ import annotations

import json
from dataclasses import dataclass, field
from typing import Any, Dict, Optional

import numpy as np

__all__ = [
    "convergence_curve",
    "iterations_from_history",
    "env_fingerprint",
    "plan_launches_per_iteration",
    "SolveReport",
    "solve_report",
]


# ---------------------------------------------------------------------------
# convergence-curve trimming (the one implementation)
# ---------------------------------------------------------------------------

def _trim_row(h: np.ndarray) -> np.ndarray:
    nan = np.isnan(h)
    if not nan.any():
        # exactly-maxiter solve: all maxiter+1 entries are real — the
        # whole row IS the curve (slicing to a "first NaN" here is the
        # classic off-by-one that drops the final residual)
        return h
    return h[: int(np.argmax(nan))]


def convergence_curve(result_or_history):
    """Trim the NaN padding from a solve history.

    Accepts a ``SolveResult`` (or anything with ``.history``) or a raw
    history array. A 1-D history returns one ``np.ndarray`` of length
    ``iterations + 1`` (entry 0 is the initial preconditioned residual
    norm); a 2-D (batched) history returns a list of per-row arrays —
    rows converge at different iterations, so the curves are ragged.
    """
    h = getattr(result_or_history, "history", result_or_history)
    h = np.asarray(h, dtype=np.float64)
    if h.ndim == 1:
        return _trim_row(h)
    if h.ndim == 2:
        return [_trim_row(row) for row in h]
    raise ValueError(f"history must be 1-D or 2-D, got shape {h.shape}")


def iterations_from_history(history):
    """Per-solve iteration counts derived from the NaN tail of history.

    1-D -> int; 2-D (k, maxiter+1) -> int array of shape (k,). Works on
    jax or numpy arrays; the 2-D form is what gives batched bucket solves
    honest *per-rhs* iteration counts (every lane of a vmapped solve
    carries its own NaN tail even though wall-clock is shared).
    """
    h = np.asarray(history, dtype=np.float64)
    valid = (~np.isnan(h)).sum(axis=-1)
    iters = np.maximum(valid - 1, 0)
    if h.ndim == 1:
        return int(iters)
    return iters.astype(np.int64)


# ---------------------------------------------------------------------------
# environment fingerprint (what makes two trajectory points comparable)
# ---------------------------------------------------------------------------

def env_fingerprint() -> Dict[str, Any]:
    """Backend/device/precision identity of this process, for records."""
    import platform

    import jax

    devs = jax.devices()
    return {
        "backend": jax.default_backend(),
        "device_kind": devs[0].device_kind if devs else "none",
        "device_count": len(devs),
        "x64": bool(jax.config.read("jax_enable_x64")),
        "jax_version": jax.__version__,
        "python_version": platform.python_version(),
    }


def comparable_env(a: Dict[str, Any], b: Dict[str, Any]) -> bool:
    """Whether wall-clock numbers from two fingerprints may be compared."""
    keys = ("backend", "device_kind", "x64")
    return all(a.get(k) == b.get(k) for k in keys)


# ---------------------------------------------------------------------------
# launch census
# ---------------------------------------------------------------------------

def plan_launches_per_iteration(plan, b, primitive: str = "pallas_call") -> Optional[int]:
    """Census ``primitive`` occurrences in one iteration of a plan's loop.

    Traces the plan's pinned solve program (no execution) and counts the
    primitive inside the first while-loop body — kernel launches per
    solver iteration. Returns None when the census does not apply (no
    while loop found, or tracing failed for an exotic operator).
    """
    import jax.numpy as jnp

    from ..kernels.common import launches_per_iteration

    atol = jnp.float32(plan.atol)
    rtol = jnp.float32(plan.rtol)
    try:
        if plan.distributed:
            n = launches_per_iteration(plan._run, b, atol, rtol, primitive=primitive)
        else:
            n = launches_per_iteration(
                plan._inner, b, jnp.zeros_like(b), atol, rtol, primitive=primitive
            )
    except Exception:
        return None
    return None if n < 0 else int(n)


# ---------------------------------------------------------------------------
# the report
# ---------------------------------------------------------------------------

@dataclass
class SolveReport:
    """Everything one solve claims, in checkable form."""

    # identity
    method: str
    engine: str
    core: Optional[str]
    operator: str
    n: Optional[int]
    dtype: str
    distributed: bool
    # convergence
    iterations: int
    converged: bool
    residual_norm: float
    curve: np.ndarray  # trimmed, length iterations+1
    # cost
    time_s: Optional[float]
    cold_start: bool  # this solve traced/compiled: wall time is not steady-state
    time_per_iter_s: Optional[float]
    launches_per_iter: Optional[int]
    # numerics safety net
    replace_every: int
    rr_events: int
    # plan economics
    trace_count: int
    plan_cache: Dict[str, int] = field(default_factory=dict)
    # provenance
    env: Dict[str, Any] = field(default_factory=dict)

    def to_dict(self) -> dict:
        d = {k: v for k, v in self.__dict__.items() if k != "curve"}
        d["curve"] = [float(x) for x in np.asarray(self.curve).ravel()]
        return d

    def to_json(self, **kwargs) -> str:
        return json.dumps(self.to_dict(), sort_keys=True, **kwargs)

    def summary(self) -> str:
        """Multi-line human-readable report."""
        lines = [
            f"SolveReport: {self.method}/{self.engine}"
            + (f" core={self.core}" if self.core else "")
            + f"  {self.operator}(n={self.n}, {self.dtype})"
            + ("  [distributed]" if self.distributed else ""),
            f"  convergence : {self.iterations} iters, converged={self.converged}, "
            f"|u|={self.residual_norm:.3e}",
        ]
        if len(self.curve):
            lines.append(
                f"  curve       : {self.curve[0]:.3e} -> {self.curve[-1]:.3e} "
                f"({len(self.curve)} points)"
            )
        if self.time_s is not None:
            per = f", {self.time_per_iter_s*1e6:.1f} us/iter" if self.time_per_iter_s else ""
            cold = "  [cold start: includes trace+compile]" if self.cold_start else ""
            lines.append(f"  time        : {self.time_s*1e3:.3f} ms{per}{cold}")
        if self.launches_per_iter is not None:
            lines.append(f"  launches    : {self.launches_per_iter} kernel(s)/iter (jaxpr census)")
        if self.replace_every:
            lines.append(
                f"  resid-repl  : every {self.replace_every} iters -> {self.rr_events} event(s)"
            )
        lines.append(
            f"  plan        : trace_count={self.trace_count}, cache={self.plan_cache}"
        )
        return "\n".join(lines)


def solve_report(plan, result, *, elapsed_s: Optional[float] = None, b=None,
                 launches: Optional[int] = None, cold_start: bool = False) -> SolveReport:
    """Build a :class:`SolveReport` from a plan and its ``SolveResult``.

    ``elapsed_s`` is the synchronized wall time of the solve if the caller
    measured one (``SolverPlan.solve`` does, when observability is on);
    ``b`` enables the launches-per-iteration census (any rhs of the right
    shape — the census traces, it does not execute); ``launches`` passes
    an already-censused count instead (plans cache theirs). ``cold_start``
    marks a solve whose wall time includes trace/compile: the report keeps
    the honest end-to-end time but refuses to derive per-iteration time
    from it.
    """
    desc = plan.describe()
    iterations = int(np.asarray(result.iterations).max())
    curve = convergence_curve(result)
    if isinstance(curve, list):  # batched result: report the worst lane
        curve = max(curve, key=len)

    core = desc.get("core")
    if launches is None and b is not None:
        launches = plan_launches_per_iteration(plan, b)

    time_per_iter = None
    if elapsed_s is not None and iterations > 0 and not cold_start:
        time_per_iter = elapsed_s / iterations

    replace_every = int(desc.get("replace_every") or 0)
    rr_events = iterations // replace_every if replace_every > 0 else 0

    from ..plan import plan_cache_stats

    return SolveReport(
        method=desc.get("method", plan.method),
        engine=desc.get("engine", "?"),
        core=core,
        operator=desc.get("operator", type(plan.A).__name__),
        n=desc.get("n"),
        dtype=desc.get("dtype", "?"),
        distributed=bool(desc.get("distributed", False)),
        iterations=iterations,
        converged=bool(np.asarray(result.converged).all()),
        residual_norm=float(np.asarray(result.residual_norm).max()),
        curve=curve,
        time_s=elapsed_s,
        cold_start=cold_start,
        time_per_iter_s=time_per_iter,
        launches_per_iter=launches,
        replace_every=replace_every,
        rr_events=rr_events,
        trace_count=plan.trace_count,
        plan_cache=plan_cache_stats(),
        env=env_fingerprint(),
    )
