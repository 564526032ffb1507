#!/usr/bin/env python3
"""Run the solver's main path on a TPU and check what comes out.

    python chip_smoke.py              # phases A, B and C on one chip
    python chip_smoke.py --chips 4    # the distributed methods on 4 chips

Everything goes through the entry points a user calls: ``repro.plan``
(``solve`` / ``solve_batched``) and ``repro.serve.SolverServer``.

* Phase A — the HPCG-shaped 27-point Poisson problem on HPCG's reference
  local grid, 104^3 (the sample input ``hpcg.dat`` of the reference HPCG,
  "104 104 104"): N = 1,124,864, bandwidth 10,921. ``engine="auto"``
  resolves to the ``pallas`` core (``fused_vma`` + ``spmv_dia`` kernels).
  One right-hand side, then a batch of 4 through the same plan.
* Phase B — the paper's 125-point Poisson problem at side 44: N = 85,184,
  bandwidth 3,962. ``auto`` resolves to ``fused_iter``, the one-kernel
  iteration.
* Phase C — a ``SolverServer`` answers 8 requests for phase A's operator,
  among them a bucket that is not full; after warm-up no new traces.
* ``--chips 4`` — only ``h3`` and ``pl2`` on ``make_solver_mesh(4)`` and
  ``h4`` on ``make_solver_mesh(4, sub=2)``, for ``poisson27(165)``
  (N = 4,492,125, about phase A's rows on each chip), each against
  single-chip ``pipecg`` on the same problem.

Each solve is checked: converged; the true relative residual
``||b - A x|| / ||b||`` (jnp SPMV) at most ``RTOL``; iterations within
``ITER_BAND`` of the same solve with ``engine="jnp"`` on the same chip; the
core that ``plan.describe()`` names; and ``tpu_custom_call`` in the
compiled solve program. The solver stops on the Jacobi-preconditioned
recurrence residual, which in f32 drifts from the true one, so plans solve
to ``0.3 * RTOL`` with residual replacement every ``REPLACE_EVERY``
iterations (without it, f32 PIPECG stalls near a true relative residual
of 2e-5 on these problems), and the true residual is checked against
``RTOL`` itself.

Right-hand sides are drawn from ``--seed``. Any failed check raises, and
the script exits non-zero; on success the last line of standard output is
``{"ok": true, "device": {"platform": ..., "kind": ..., "count": ...}}``.
Without a TPU it exits non-zero before solving anything.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time
from importlib import metadata

RTOL = 1e-5
REPLACE_EVERY = 10
ITER_BAND = 1  # iterations: kernel cores vs engine="jnp"
SIDE_A, SIDE_B, SIDE_4 = 104, 44, 165  # grid sides of phases A, B and --chips 4
PLAN = dict(method="pipecg", M="jacobi", atol=0.0, rtol=0.3 * RTOL,
            replace_every=REPLACE_EVERY)


class SmokeFailure(AssertionError):
    pass


def check(ok: bool, what: str) -> None:
    if not ok:
        raise SmokeFailure(what)


def _version(pkg: str) -> str:
    try:
        return metadata.version(pkg)
    except metadata.PackageNotFoundError:
        return "not installed"


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="1: phases A-C on one chip; 4: the distributed methods only")
    ap.add_argument("--seed", type=int, default=0, help="seed of the right-hand sides")
    args = ap.parse_args(argv)

    sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)), "src"))
    try:
        from repro.launch.env import apply_env, enable_compile_cache
    except ImportError as e:
        print(f"chip_smoke: cannot import the repro package next to this script: {e}",
              file=sys.stderr)
        return 2
    apply_env()
    import jax

    try:
        devices = jax.devices()
    except RuntimeError as e:
        print(f"chip_smoke: JAX found no usable backend: {e}", file=sys.stderr)
        return 1
    dev = devices[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: needs a TPU, but JAX's first device is on platform "
              f"{dev.platform!r} ({dev.device_kind})", file=sys.stderr)
        return 1
    if len(devices) < args.chips:
        print(f"chip_smoke: --chips {args.chips} needs {args.chips} TPU chips, "
              f"found {len(devices)}", file=sys.stderr)
        return 1
    cache = enable_compile_cache()
    print(f"device: {dev.device_kind} x{len(devices)}  jax {jax.__version__}  "
          f"libtpu {_version('libtpu')}  compile cache {cache}", flush=True)

    key = jax.random.PRNGKey(args.seed)
    if args.chips == 4:
        four_chips(key)
    else:
        A = phase_a(jax.random.fold_in(key, 0))
        phase_b(jax.random.fold_in(key, 1))
        phase_c(jax.random.fold_in(key, 2), A)
    print(json.dumps({"ok": True, "device": {"platform": dev.platform,
                                             "kind": dev.device_kind,
                                             "count": len(devices)}}))
    return 0


# ---------------------------------------------------------------------------
# checks shared by the phases
# ---------------------------------------------------------------------------

def _true_rel_residual(A, x, b) -> float:
    import jax.numpy as jnp
    from repro.sparse import spmv

    return float(jnp.linalg.norm(b - spmv(A, x, engine="jnp")) / jnp.linalg.norm(b))


def _check_solution(tag, A, b, x, converged, iters, ref_iters) -> float:
    check(bool(converged), f"{tag}: not converged after {iters} iterations")
    rel = _true_rel_residual(A, x, b)
    check(rel <= RTOL, f"{tag}: true relative residual {rel!r} > {RTOL}")
    check(abs(int(iters) - int(ref_iters)) <= ITER_BAND,
          f"{tag}: {int(iters)} iterations vs {int(ref_iters)} with engine='jnp'")
    return rel


def _check_kernels(tag, plan, b) -> None:
    hlo = plan.lower(b).compile().as_text()
    check("tpu_custom_call" in hlo, f"{tag}: no tpu_custom_call in the compiled solve program")


def _warm_time(fn, *args):
    import jax

    jax.block_until_ready(fn(*args))  # compile (or hit the cache)
    t0 = time.perf_counter()
    out = jax.block_until_ready(fn(*args))
    return out, time.perf_counter() - t0


def _single_phase(tag, key, A, core):
    """One rhs, then a batch of 4, through one auto plan vs a jnp plan."""
    import jax
    import numpy as np

    import repro

    b = jax.random.normal(key, (A.n,), A.dtype)
    B = jax.random.normal(jax.random.fold_in(key, 1), (4, A.n), A.dtype)
    p = repro.plan(A, engine="auto", **PLAN)
    ref = repro.plan(A, engine="jnp", **PLAN)
    check(p.describe()["core"] == core,
          f"{tag}: plan core {p.describe()['core']!r}, expected {core!r}")

    res, t1 = _warm_time(p.solve, b)
    rel = _check_solution(tag, A, b, res.x, res.converged, res.iterations,
                          ref.solve(b).iterations)
    print(f"{tag}: core={core} N={A.n} iterations={int(res.iterations)} "
          f"true_rel_residual={rel!r} warm_solve_s={t1!r}", flush=True)

    resB, t4 = _warm_time(p.solve_batched, B)
    refB = ref.solve_batched(B)
    rels = [_check_solution(f"{tag} batch[{i}]", A, B[i], resB.x[i], resB.converged[i],
                            resB.iterations[i], refB.iterations[i]) for i in range(4)]
    print(f"{tag}: batch of 4 iterations={np.asarray(resB.iterations).tolist()} "
          f"max_true_rel_residual={max(rels)!r} warm_solve_s={t4!r}", flush=True)

    _check_kernels(tag, p, b)
    _check_kernels(f"{tag} batch", p, B)


# ---------------------------------------------------------------------------
# the phases
# ---------------------------------------------------------------------------

def phase_a(key):
    from repro.sparse import poisson27

    A = poisson27(SIDE_A)  # 104: N = 1,124,864, bandwidth 10,921
    s = SIDE_A
    check(A.n == s**3 and A.bandwidth == s * s + s + 1, f"phase A: N={A.n} bw={A.bandwidth}")
    _single_phase(f"phase A (27-point, {s}^3)", key, A, "pallas")
    return A


def phase_b(key):
    from repro.sparse import poisson125

    A = poisson125(SIDE_B)  # 44: N = 85,184, bandwidth 3,962
    s = SIDE_B
    check(A.n == s**3 and A.bandwidth == 2 * (s * s + s + 1),
          f"phase B: N={A.n} bw={A.bandwidth}")
    _single_phase(f"phase B (125-point, {s}^3)", key, A, "fused_iter")


def phase_c(key, A):
    """8 requests through SolverServer; no new traces after warm-up."""
    import jax
    import jax.numpy as jnp

    import repro
    from repro.serve import SolverServer

    tag = f"phase C (SolverServer, 27-point {SIDE_A}^3)"
    max_batch = 4
    bs = jax.random.normal(key, (10, A.n), A.dtype)
    srv = SolverServer(max_batch=max_batch, max_wait_ms=50.0, engine="auto", **PLAN)
    try:
        # warm-up: the single-rhs program, then the bucket program
        srv.submit(A, bs[8]).result()
        for f in srv.submit_many(A, list(bs[:max_batch])):
            f.result()
        (plan,) = srv.plans()
        check(plan.describe()["core"] == "pallas",
              f"{tag}: plan core {plan.describe()['core']!r}, expected 'pallas'")
        warm_traces = plan.trace_count

        # 8 requests: a full bucket, a bucket of 3 (padded to 4), a single
        t0 = time.perf_counter()
        results = []
        for group in (range(0, 4), range(4, 7), range(7, 8)):
            futs = srv.submit_many(A, [bs[i + 1] for i in group])
            results += [f.result() for f in futs]
        served_s = time.perf_counter() - t0
    finally:
        srv.shutdown(drain=True)
    check(plan.trace_count == warm_traces,
          f"{tag}: {plan.trace_count - warm_traces} new trace(s) after warm-up")
    check(sorted({r.bucket_size for r in results}) == [1, 3, 4],
          f"{tag}: bucket sizes {[r.bucket_size for r in results]}")

    ref = repro.plan(A, engine="jnp", **PLAN).solve_batched(bs[1:9])
    rels = [_check_solution(f"{tag} request {i}", A, bs[i + 1], r.x, r.converged,
                            r.iterations, ref.iterations[i])
            for i, r in enumerate(results)]
    _check_kernels(tag, plan, bs[0])
    _check_kernels(f"{tag} bucket", plan, jnp.stack(list(bs[:max_batch])))
    print(f"{tag}: core=pallas N={A.n} requests={len(results)} "
          f"iterations={[r.iterations for r in results]} "
          f"buckets={[r.bucket_size for r in results]} "
          f"max_true_rel_residual={max(rels)!r} traces={plan.trace_count} "
          f"served_s={served_s!r}", flush=True)


def four_chips(key):
    """h3 / h4 / pl2 on 4 chips against single-chip pipecg."""
    import jax

    import repro
    from repro.sparse import poisson27

    A = poisson27(SIDE_4)  # 165: N = 4,492,125
    check(A.n == SIDE_4**3, f"4 chips: N={A.n}")
    b = jax.random.normal(key, (A.n,), A.dtype)
    single = repro.plan(A, engine="auto", **PLAN)
    ref, t1 = _warm_time(single.solve, b)
    ref_iters = int(ref.iterations)
    rel = _check_solution("pipecg, 1 chip", A, b, ref.x, ref.converged, ref_iters,
                          repro.plan(A, engine="jnp", **PLAN).solve(b).iterations)
    print(f"pipecg 1 chip: core={single.describe()['core']} N={A.n} "
          f"iterations={ref_iters} true_rel_residual={rel!r} warm_solve_s={t1!r}",
          flush=True)
    band = max(2, (ref_iters + 9) // 10)  # tests/test_deep_pipeline.py's 10% band

    for method, sub in (("h3", None), ("h4", 2), ("pl2", None)):
        p = repro.plan(A, **{**PLAN, "method": method}, engine="auto", shards=4, sub=sub)
        shard_devs = {s.device for s in p.sharded.data.addressable_shards}
        check(len(shard_devs) == 4, f"{method}: operator shards on {len(shard_devs)} device(s)")
        res, t = _warm_time(p.solve, b)
        tag = f"{method}, 4 chips"
        check(bool(res.converged), f"{tag}: not converged")
        rel = _true_rel_residual(A, res.x, b)
        check(rel <= RTOL, f"{tag}: true relative residual {rel!r} > {RTOL}")
        iters = int(res.iterations)
        check(abs(iters - ref_iters) <= band,
              f"{tag}: {iters} iterations vs {ref_iters} for 1-chip pipecg (band {band})")
        kernels = "tpu_custom_call" in p.lower(b).compile().as_text()
        print(f"{tag}: mesh={dict(p.mesh.shape)} shard_devices={sorted(d.id for d in shard_devs)} "
              f"iterations={iters} true_rel_residual={rel!r} kernels={kernels} "
              f"warm_solve_s={t!r}", flush=True)


if __name__ == "__main__":
    sys.exit(main())
