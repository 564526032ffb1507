"""Paper Fig. 6 / Fig. 7 analogue: solver-method comparison per matrix.

Matrices: synthetic analogues of the paper's SuiteSparse Table I (matched
N and nnz/N; big ones scaled to CPU size) + a 27-pt Poisson. Methods are
rows of the solver registry, executed through the plan/execute API: one
``repro.plan`` per (matrix, method) pins the compiled loop outside the
timed region — the timer sees pure iteration cost, exactly the quantity
the paper's speedups are made of (all variants converge in the same
#iterations, verified in `derived`).

``--tiny`` runs a seconds-scale subset through the same plan path — the
CI smoke mode that keeps the serving workflow exercised on every push —
and ``json_path`` writes ``BENCH_solver_methods.json``: per matrix×method
us/iter, kernel launches/iter (jaxpr census), the structural GB/s model
and the convergence-equivalence iteration counts, all stamped with the
environment fingerprint so ``tools/bench_gate.py`` can tell which columns
are comparable across trajectory points.
"""
from __future__ import annotations

import argparse

import jax.numpy as jnp

import repro
from repro.obs import plan_launches_per_iteration
from repro.sparse import poisson27, spmv, table1_matrix

from .common import bench_record, emit, structural_bytes_per_elem, timeit_call, write_bench_json

MATRICES = [
    ("bcsstk15", lambda: table1_matrix("bcsstk15", scale=1.0)),       # N=3948
    ("gyro", lambda: table1_matrix("gyro", scale=1.0)),               # N=17361
    ("boneS01@10%", lambda: table1_matrix("boneS01", scale=0.1)),     # N~12.7k
    ("offshore@10%", lambda: table1_matrix("offshore", scale=0.1)),   # N~26k
    ("poisson27-20", lambda: poisson27(20)),                          # N=8000
]

TINY_MATRICES = [
    ("poisson27-6", lambda: poisson27(6)),                            # N=216
]

# (method, engine) rows of the solver registry
METHODS = {
    "pcg": ("pcg", "jnp"),
    "chrono": ("chronopoulos", "jnp"),
    "pipecg": ("pipecg", "jnp"),
    "pipecg-fused": ("pipecg", "pallas"),
}


def main(iters_per_solve: int = 40, tiny: bool = False, json_path: str | None = None):
    matrices = TINY_MATRICES if tiny else MATRICES
    if tiny:
        iters_per_solve = min(iters_per_solve, 10)
    record = bench_record(
        "solver_methods",
        iters_per_solve=int(iters_per_solve),
        tiny=bool(tiny),
        matrices={},
    )
    for mname, gen in matrices:
        A = gen()
        xstar = jnp.ones((A.n,)) / jnp.sqrt(A.n)  # deterministic rhs: b = A @ 1/sqrt(n)
        b = spmv(A, xstar)
        # convergence equivalence (the paper's correctness premise)
        its = {
            k: int(repro.solve(A, b, method=k, M="jacobi", atol=1e-5, maxiter=2000).iterations)
            for k in ("pcg", "pipecg")
        }
        n_diags = int(A.data.shape[0])
        mrec = {
            "n": int(A.n),
            "nnz_per_row": float(A.nnz() / A.n),
            "iters_pcg": its["pcg"],
            "iters_pipecg": its["pipecg"],
            "methods": {},
        }
        record["matrices"][mname] = mrec
        for meth, (method, engine) in METHODS.items():
            # plan outside the timed region: the timer sees iteration cost only
            p = repro.plan(A, method=method, engine=engine, M="jacobi",
                           atol=0.0, maxiter=iters_per_solve)
            us = timeit_call(lambda: p.solve(b), warmup=1, iters=3)
            assert p.trace_count == 1, (meth, p.trace_count)  # plan reuse, not re-trace
            us_iter = us / iters_per_solve
            launches = plan_launches_per_iteration(p, b)
            core = p.describe().get("core")
            bpe = structural_bytes_per_elem(core, n_diags) if core else None
            gbs = None if bpe is None else A.n * bpe / (us_iter * 1e-6) / 1e9
            mrec["methods"][meth] = {
                "us_per_iter": us_iter,
                "launches_per_iter": launches,
                "bytes_per_elem": bpe,
                "achieved_gbs": gbs,
            }
            emit(
                f"solver/{mname}/{meth}",
                us_iter,
                f"N={A.n};nnz/N={A.nnz()/A.n:.1f};iters_pcg={its['pcg']};iters_pipecg={its['pipecg']}",
            )
    if json_path:
        write_bench_json(json_path, record)
    return record


if __name__ == "__main__":
    ap = argparse.ArgumentParser()
    ap.add_argument("--iters", type=int, default=40, help="iterations per timed solve")
    ap.add_argument("--tiny", action="store_true",
                    help="seconds-scale CI smoke: tiny matrix, few iterations")
    ap.add_argument("--json", metavar="PATH", default=None,
                    help="write BENCH_solver_methods.json record")
    args = ap.parse_args()
    main(iters_per_solve=args.iters, tiny=args.tiny, json_path=args.json)
