"""Communication schedules of the distributed methods — h1..h4, pl2, pl3.

The paper's Figures 6-8 compare methods by wall time on a CPU+GPU node; on
the TPU target the distinguishing quantity is the per-iteration collective
schedule, measured exactly from the while-body jaxpr of each method's
shard_map program:

  h1 : 3 separate scalar psums + full-vector all-gather   (most latency)
  h2 : 1 packed psum + full-vector all-gather             (paper's 3N->N)
  h3 : 1 packed psum + 2x bandwidth-wide halo ppermute    (paper's 2-D)
  h4 : 2-stage hierarchical psum (intra-pod + inter-pod)  (2-D mesh)
  pl2: ONE Gram psum per 2 iterations  (depth-2 pipeline)
  pl3: ONE Gram psum per 3 iterations  (depth-3 pipeline)

Emits one CSV row per method and (via ``run.py --json-dir``) a
``BENCH_overlap.json`` record whose ``reductions_per_iter`` /
``ppermutes_per_iter`` / ``allgathers_per_iter`` leaves are gated as
STRUCTURAL by tools/bench_gate.py (any increase fails CI) and whose
``iterations`` leaves get the convergence band — the pl2/pl3
within-10%-of-pipecg acceptance criterion, enforced against the
committed trajectory. ``time_per_iter_us`` rides the timing band.

Runs in the calling process on every device it finds (at least 2): the
chips of a TPU host, or on CPU the virtual devices that
``XLA_FLAGS=--xla_force_host_platform_device_count=8`` in the environment
gives. Fewer than 2 devices is an error, which makes ``benchmarks.run``
exit non-zero.
"""
from __future__ import annotations

import time

TIMED_ITERS = 64


def main(json_path: str | None = None):
    import jax
    import jax.numpy as jnp

    from repro.core import jacobi
    from repro.core.distributed import build_distributed_solver, get_method, make_solver_mesh
    from repro.kernels.common import count_primitive, while_body_jaxpr
    from repro.sparse import balanced_rows, poisson27, shard_dia, shard_vector, spmv

    n_dev = len(jax.devices())
    if n_dev < 2:
        raise RuntimeError(
            f"overlap needs >= 2 devices, found {n_dev} ({jax.devices()[0].platform}); "
            "on CPU set XLA_FLAGS=--xla_force_host_platform_device_count=8 before launch"
        )
    shards = n_dev - n_dev % 2  # the h4 pod mesh needs an even count
    A = poisson27(12)
    xstar = jnp.ones((A.n,)) / jnp.sqrt(A.n)
    b = spmv(A, xstar)
    M = jacobi(A)
    bounds = balanced_rows(A.n, shards)
    As = shard_dia(A, bounds)
    mesh1 = make_solver_mesh(shards)
    mesh2 = make_solver_mesh(shards, sub=shards // 2)
    bsh = shard_vector(b, bounds)
    ish = shard_vector(M.inv_diag, bounds)

    out = {"devices": shards, "n": A.n, "methods": {}}
    for method in ("h1", "h2", "h3", "h4", "pl2", "pl3"):
        mesh = mesh2 if method == "h4" else mesh1
        depth = get_method(method).pipeline_depth
        runner = build_distributed_solver(As, mesh=mesh, method=method,
                                          maxiter=TIMED_ITERS, replace_every=50)
        run = jax.jit(lambda bb, ii, a, r, runner=runner: runner(bb, ii, a, r))

        # structural census on the RR-free program: the steady-state schedule.
        # (residual replacement adds a lax.cond branch whose collectives would
        # be counted statically but execute only every replace_every iters)
        census_runner = build_distributed_solver(As, mesh=mesh, method=method,
                                                 maxiter=TIMED_ITERS)
        closed = jax.make_jaxpr(lambda bb, ii, a, r: census_runner(bb, ii, a, r))(
            bsh, ish, jnp.float32(1e-6), jnp.float32(0.0))
        body = while_body_jaxpr(closed.jaxpr)
        red = count_primitive(body, "psum") / depth
        pp = count_primitive(body, "ppermute") / depth
        ag = count_primitive(body, "all_gather") / depth

        # convergence: iterations to atol on the Poisson problem
        res = run(bsh, ish, jnp.float32(1e-6), jnp.float32(0.0))
        iters = int(jax.block_until_ready(res.iterations))

        # timing: fixed-work solve (atol=0 -> all TIMED_ITERS iterations)
        jax.block_until_ready(run(bsh, ish, jnp.float32(0.0), jnp.float32(0.0)))
        times = []
        for _ in range(3):
            t0 = time.perf_counter()
            jax.block_until_ready(run(bsh, ish, jnp.float32(0.0), jnp.float32(0.0)))
            times.append(time.perf_counter() - t0)
        us_per_iter = sorted(times)[1] / TIMED_ITERS * 1e6

        out["methods"][method] = {
            "pipeline_depth": depth,
            "reductions_per_iter": red,
            "ppermutes_per_iter": pp,
            "allgathers_per_iter": ag,
            "iterations": iters,
            "time_per_iter_us": round(us_per_iter, 1),
        }
        print(f"overlap/{method},{us_per_iter:.1f},"
              f"red/it={red:g};ppermute/it={pp:g};allgather/it={ag:g};iters={iters}",
              flush=True)
    if json_path:
        from .common import bench_record, write_bench_json

        write_bench_json(json_path, bench_record("overlap", **out))
    return out


if __name__ == "__main__":
    main()
