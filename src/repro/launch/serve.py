"""Serving launcher: ``python -m repro.launch.serve --matrix poisson27:8``

The real entrypoint for the async serving tier (docs/serving.md): applies
the env hygiene from ``launch.env`` BEFORE the first jax import (XLA
flags, x64 policy, allocator thresholds; prints the tcmalloc preload line
when applicable), then stands up a :class:`repro.serve.SolverServer`,
pushes a mixed-size workload through it, and reports queue/bucket/
program telemetry.

    # cold start, mixed traffic, assert the two-program steady state
    python -m repro.launch.serve --matrix poisson27:8 --matrix poisson7:12 \
        --requests 48 --max-batch 4 --expect-two-programs

    # save a warm-start manifest, then boot a hot replica from it
    python -m repro.launch.serve --matrix poisson27:8 --save-manifest plans.json
    python -m repro.launch.serve --manifest plans.json --requests 32
"""
from __future__ import annotations

import argparse
import sys

# env hygiene must precede any jax import — keep this module jax-free
# until main() has called apply_env()
from .env import apply_env, enable_compile_cache, tcmalloc_note


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--matrix", action="append", default=None,
                    help="operator spec (repeatable for a multi-plan pool); "
                         "see launch/solve.py (default: poisson27:8)")
    ap.add_argument("--requests", type=int, default=32,
                    help="requests pushed per operator")
    ap.add_argument("--method", default="pipecg")
    ap.add_argument("--engine", default="auto")
    ap.add_argument("--atol", type=float, default=1e-5)
    ap.add_argument("--maxiter", type=int, default=2000)
    ap.add_argument("--max-batch", type=int, default=4)
    ap.add_argument("--max-wait-ms", type=float, default=5.0)
    ap.add_argument("--max-depth", type=int, default=256)
    ap.add_argument("--devices", type=int, default=None,
                    help="virtual host devices (XLA flag; set before jax import)")
    ap.add_argument("--x64", action="store_true", help="enable fp64")
    ap.add_argument("--manifest", default=None,
                    help="warm-start: rebuild + re-trace plans from this manifest")
    ap.add_argument("--save-manifest", default=None,
                    help="write the served plans' manifest here on exit")
    ap.add_argument("--expect-two-programs", action="store_true",
                    help="exit nonzero unless steady state compiled exactly two "
                         "XLA programs (single + bucket) per plan")
    args = ap.parse_args(argv)

    # ---- env BEFORE jax (the whole reason this launcher exists) ----
    applied = apply_env(devices=args.devices, x64=True if args.x64 else None)
    for k, v in applied.items():
        print(f"env: {k}={v}")
    note = tcmalloc_note()
    if note:
        print(f"env note: {note}")

    enable_compile_cache()
    import jax.numpy as jnp

    import repro.obs as obs
    from repro.serve import SolverServer
    from .solve import build_matrix

    obs.enable()

    if args.manifest:
        server = SolverServer.from_manifest(args.manifest)
        # route traffic with each plan's own config — CLI solver defaults
        # must not shadow the manifest, or submits would miss the warm
        # pool and trigger fresh builds
        workload = [(p.A, p.config()) for p in server.plans()]
        warm_traces = {id(p): p.trace_count for p in server.plans()}
        print(f"warm-started {len(server.plans())} plan(s) from {args.manifest}")
    else:
        server = SolverServer(
            max_batch=args.max_batch, max_wait_ms=args.max_wait_ms,
            max_depth=args.max_depth, method=args.method, engine=args.engine,
            atol=args.atol, maxiter=args.maxiter,
        )
        workload = [(build_matrix(s), {})
                    for s in (args.matrix or ["poisson27:8"])]
        warm_traces = None

    # ---- mixed-size workload: singles + partial + full buckets ----
    futures = []
    for A, overrides in workload:
        from repro.sparse import spmv

        xstar = jnp.ones((A.n,)) / jnp.sqrt(A.n)
        b = spmv(A, xstar)
        # prime: one lone request, waited on, so the single-rhs program
        # traces deterministically (later singles may coalesce into buckets)
        futures.append(server.submit(A, b, **overrides))
        futures[-1].result(timeout=300.0)
        group, i = [], 1
        while i < args.requests:
            # cycle bucket sizes 1, cap, cap//2, 3 — singles exercise the
            # pinned single program, the rest coalesce into the bucket one
            for size in (1, args.max_batch, max(args.max_batch // 2, 1), 3):
                k = min(size, args.requests - i)
                if k <= 0:
                    break
                group += server.submit_many(
                    A, [(1.0 + 0.1 * (i + j)) * b for j in range(k)],
                    **overrides,
                )
                i += k
        futures += group
    results = [f.result(timeout=300.0) for f in futures]
    server.shutdown(drain=True)

    # ---- report ----
    waits = sorted(r.queue_wait_s for r in results)
    occ = [r.bucket_occupancy for r in results]
    iters = [r.iterations for r in results]
    p = lambda xs, q: xs[min(int(q * (len(xs) - 1)), len(xs) - 1)] if xs else 0.0
    print(f"served {len(results)} requests over {len(server.plans())} plan(s)")
    print(f"queue wait: p50={p(waits, .5) * 1e3:.2f}ms p95={p(waits, .95) * 1e3:.2f}ms")
    print(f"occupancy: mean={sum(occ) / max(len(occ), 1):.2f}  "
          f"iters: min={min(iters)} max={max(iters)}")
    for plan in server.plans():
        extra = ""
        if warm_traces is not None:
            boot = warm_traces.get(id(plan), 0)
            extra = f" (warm-start: {boot} at boot, " \
                    f"{plan.trace_count - boot} added serving)"
        print(f"plan n={plan.n}: compiled programs (trace_count)="
              f"{plan.trace_count}{extra}")
    rejects = {k: v["value"] for k, v in obs.snapshot().items()
               if k.startswith("serve.rejects.") and v["value"]}
    if rejects:
        print(f"rejections: {rejects}")

    if args.save_manifest:
        server.save_manifest(args.save_manifest)
        print(f"manifest saved: {args.save_manifest}")

    if args.expect_two_programs:
        bad = {p.n: p.trace_count for p in server.plans() if p.trace_count != 2}
        if bad:
            print(f"FAIL: expected exactly 2 compiled programs per plan "
                  f"(single + bucket), got {bad}", file=sys.stderr)
            return 1
        print("steady state OK: exactly 2 compiled programs per plan")
    if warm_traces is not None:
        added = {p.n: p.trace_count - warm_traces.get(id(p), 0)
                 for p in server.plans()
                 if p.trace_count != warm_traces.get(id(p), 0)}
        if added:
            print(f"FAIL: warm-started plans re-traced during serving: {added}",
                  file=sys.stderr)
            return 1
        print("warm start OK: zero new traces while serving")
    return 0


if __name__ == "__main__":
    sys.exit(main())
