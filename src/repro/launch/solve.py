"""Solver launcher: ``python -m repro.launch.solve --matrix poisson125:16``

Thin CLI over the plan/execute API: builds one ``repro.plan`` (setup paid
once, printed via ``plan.describe()``), then solves. Single-device or
distributed (--shards N, needs that many devices — on CPU set
XLA_FLAGS=--xla_force_host_platform_device_count=N before launch).
``--rhs K`` serves K right-hand sides through the same plan
(``plan.solve_batched``) to demonstrate the amortization.
"""
from __future__ import annotations

import argparse

import sys

# env hygiene BEFORE the first jax import (repro and repro.launch are
# both lazy, so running `python -m repro.launch.solve` reaches this line
# jax-free); a no-op for every variable the operator already set. Guarded
# so importing this module for build_matrix() from an already-running
# process stays silent.
if "jax" not in sys.modules:
    from .env import apply_env

    apply_env()

import jax.numpy as jnp

from .env import enable_compile_cache

from .. import plan, solver_names
from ..sparse import poisson7, poisson27, poisson125, spmv, synthetic_spd_dia, table1_matrix

GENS = {"poisson7": poisson7, "poisson27": poisson27, "poisson125": poisson125}


def build_matrix(spec: str):
    name, _, arg = spec.partition(":")
    if name in GENS:
        return GENS[name](int(arg or 8))
    if name == "synthetic":
        n, _, nnz = (arg or "1000,9").partition(",")
        return synthetic_spd_dia(int(n), float(nnz or 9))
    return table1_matrix(name, scale=float(arg or 1.0))


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--matrix", default="poisson27:12")
    ap.add_argument("--method", default=None, choices=sorted(set(solver_names())),
                    help="solver method; h1..h4/pl2/pl3 are distributed (set --shards; "
                         "h4 also needs --sub); default: pipecg, or h3 when --shards > 1")
    ap.add_argument("--solver", default=None, help="deprecated alias for --method")
    ap.add_argument("--engine", default="auto",
                    choices=["auto", "jnp", "pallas", "fused_iter"],
                    help="iteration core; fused_iter = whole-iteration kernel (pipecg, DIA)")
    ap.add_argument("--spmv-engine", default=None,
                    choices=["auto", "jnp", "pallas", "segsum", "bf16"],
                    help="SPMV backend (pipecg); bf16 = half-traffic mixed precision")
    ap.add_argument("--shards", type=int, default=1)
    ap.add_argument("--atol", type=float, default=1e-5)
    ap.add_argument("--maxiter", type=int, default=10000)
    ap.add_argument("--replace-every", type=int, default=None,
                    help="residual-replacement period (default: 0, or 50 under bf16)")
    ap.add_argument("--weighted", action="store_true", help="nnz perf-model partition (h3)")
    ap.add_argument("--sub", type=int, default=None,
                    help="reducer sub-axis size: shards devices become a "
                         "(shards/sub, sub) pod mesh (required by h4)")
    ap.add_argument("--rhs", type=int, default=1,
                    help="number of right-hand sides served through the one plan")
    args = ap.parse_args(argv)
    enable_compile_cache()

    A = build_matrix(args.matrix)
    xstar = jnp.ones((A.n,)) / jnp.sqrt(A.n)
    b = spmv(A, xstar)
    print(f"matrix {args.matrix}: N={A.n} nnz/N={A.nnz()/A.n:.1f} bw={A.bandwidth}")

    distributed = ("h1", "h2", "h3", "h4", "pl2", "pl3", "pipecg_distributed")
    method = args.solver or args.method
    kw = {}
    if args.shards > 1:
        if method is None:
            method = "h3"
        elif method not in distributed:
            ap.error(f"--method {method} is single-device; with --shards use one of {distributed}")
        kw = {"shards": args.shards, "partition": "nnz" if args.weighted else "rows"}
        if args.sub is not None:
            kw["sub"] = args.sub
        if args.replace_every is not None:
            kw["replace_every"] = args.replace_every
    else:
        if method is None:
            method = "pipecg"
        elif method in distributed:
            ap.error(f"--method {method} is distributed; set --shards > 1")
        if method == "pipecg":
            kw = {"replace_every": args.replace_every, "spmv_engine": args.spmv_engine}

    # --- the plan/execute split: setup once... ---
    p = plan(A, method=method, engine=args.engine, M="jacobi",
             atol=args.atol, maxiter=args.maxiter, **kw)
    desc = p.describe()
    print("plan:", ", ".join(f"{k}={desc[k]}" for k in sorted(desc) if k != "trace_count"))

    # --- ...then any amount of rhs traffic ---
    res = p.solve(b)
    if args.rhs > 1:
        B = jnp.stack([(k + 1.0) * b for k in range(args.rhs)])
        batch = p.solve_batched(B)
        print(
            f"served {args.rhs} rhs through one plan: "
            f"iters={[int(i) for i in jnp.atleast_1d(batch.iterations)]} "
            f"traces={p.trace_count}"
        )

    err = float(jnp.linalg.norm(res.x - xstar))
    true_res = float(jnp.linalg.norm(b - spmv(A, res.x)))
    print(
        f"method={method} iters={int(res.iterations)} converged={bool(res.converged)} "
        f"|u|={float(res.residual_norm):.2e} |x-x*|={err:.2e} true_res={true_res:.2e}"
    )


if __name__ == "__main__":
    main()
