"""Paper §V-B analogue: kernel fusion effect on the iteration core.

The fusion win is an HBM-traffic property, so besides CPU wall time we
report the traffic model that applies on the TPU target: bytes/element of
the unfused (8 AXPYs + PC + 3 dots as separate passes) vs fused (one pass)
iteration core, extracted from the lowered HLO of both variants with the
same census used for the roofline.

``iteration_cores`` extends this to whole-solver granularity: the three
iteration cores (jnp / pallas / fused_iter) timed per PIPECG iteration on
the same operator, with kernel-launches-per-iteration from the jaxpr
census and achieved bandwidth against the roofline HBM peak. Its results
land in ``BENCH_kernels.json`` when a path is given (the CI smoke step
does), seeding the cross-PR benchmark trajectory.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.launch.roofline import analyze_hlo
from repro.kernels import fused_vma_dots, fused_vma_dots_ref
from repro.kernels.common import launches_per_iteration

from .common import (bench_record, emit, hbm_peak, seed_key, structural_bytes_per_elem,
                     timeit_call, write_bench_json)


def iteration_cores(grid: int = 24, maxiter: int = 20, json_path: str | None = None):
    """Time one PIPECG iteration per core on poisson27(grid^3).

    atol=rtol=0 pins the loop at exactly ``maxiter`` iterations, so
    per-iteration time is wall/maxiter with the (shared) init amortized
    out of the comparison. On CPU the Pallas cores run in interpret mode
    — the launch census and traffic model are the TPU-relevant columns
    there; wall time only orders the cores on TPU itself.
    """
    import repro
    from repro.sparse import poisson27

    A = poisson27(grid)
    b = jnp.sin(jnp.arange(A.n, dtype=jnp.float32))
    backend = jax.default_backend()
    peak = hbm_peak(jax.devices()[0].device_kind)  # None off the known chips
    record = bench_record(
        "kernels",
        n=int(A.n),
        n_diags=int(A.data.shape[0]),
        maxiter=int(maxiter),
        backend=backend,
        interpret_kernels=backend != "tpu",
        hbm_peak_gbs=None if peak is None else peak / 1e9,
        cores={},
    )
    for core in ("jnp", "pallas", "fused_iter"):
        p = repro.plan(A, method="pipecg", engine=core, M="jacobi",
                       atol=0.0, rtol=0.0, maxiter=maxiter)

        def run(bb, p=p):
            return p._inner(bb, jnp.zeros_like(bb), jnp.float32(0.0), jnp.float32(0.0))

        launches = launches_per_iteration(run, b)
        us = timeit_call(p.solve, b, warmup=1, iters=3)
        us_iter = us / maxiter
        bpe = structural_bytes_per_elem(core, record["n_diags"])
        gbs = record["n"] * bpe / (us_iter * 1e-6) / 1e9
        record["cores"][core] = {
            "us_per_iter": us_iter,
            "launches_per_iter": launches,
            "bytes_per_elem": bpe,
            "achieved_gbs": gbs,
            "frac_of_hbm_peak": None if peak is None else gbs / (peak / 1e9),
            "trace_count": p.trace_count,
        }
        emit(
            f"kernels/iteration_cores/{core}",
            us_iter,
            f"N={record['n']};launches_per_iter={launches};"
            f"bytes_per_elem={bpe:.0f};achieved={gbs:.2f}GB/s",
        )
    if json_path:
        write_bench_json(json_path, record)
    return record


# one jit per op = one kernel launch per op, like the paper's unoptimized
# scale/daxpy/ddot cublas call sequence (§V-B Fig. 5). A single jit would
# let XLA fuse everything and hide exactly the effect the paper measures.
_axpy = jax.jit(lambda y, x, a: y + a * x)
_scale_add = jax.jit(lambda y, x, a: x + a * y)
_mul = jax.jit(lambda a, b: a * b)
_dot = jax.jit(lambda a, b: jnp.sum(a.astype(jnp.float32) * b.astype(jnp.float32)))


def unfused_calls(z, q, s, p, x, r, u, w, n, m, inv, alpha, beta):
    z = _scale_add(z, n, beta)
    q = _scale_add(q, m, beta)
    s = _scale_add(s, w, beta)
    p = _scale_add(p, u, beta)
    x = _axpy(x, p, alpha)
    r = _axpy(r, s, -alpha)
    u = _axpy(u, q, -alpha)
    w = _axpy(w, z, -alpha)
    m = _mul(inv, w)
    gamma = _dot(r, u)
    delta = _dot(w, u)
    uu = _dot(u, u)
    return z, q, s, p, x, r, u, w, m, jnp.stack([gamma, delta, uu])


def main(n: int = 1 << 20, *, json_path: str | None = None, tiny: bool = False):
    if tiny:
        n = 1 << 16
        iteration_cores(grid=8, maxiter=5, json_path=json_path)
    else:
        iteration_cores(json_path=json_path)
    vecs = [jax.random.normal(seed_key("kernels/vma_core", i), (n,)) for i in range(10)]
    inv = jnp.abs(jax.random.normal(seed_key("kernels/vma_core/inv"), (n,))) + 0.5
    a, b = jnp.float32(0.3), jnp.float32(0.7)

    # the canonical iteration core (core.iteration.pipecg_vma_core) via the
    # kernel oracle, compiled as ONE fused jit
    f_fused_jnp = jax.jit(fused_vma_dots_ref)

    us_u = timeit_call(unfused_calls, *vecs, inv, a, b)
    us_f = timeit_call(f_fused_jnp, *vecs, inv, a, b)
    emit("kernels/vma_core/unfused_calls", us_u, f"N={n};12 separate kernels")
    emit("kernels/vma_core/fused_jnp", us_f, f"N={n};speedup={us_u/us_f:.2f}x")

    # TPU-relevant: HBM traffic of each lowering (bytes per vector element)
    hb_u = 0.0
    hb_u += 4 * analyze_hlo(_scale_add.lower(vecs[0], vecs[8], b).compile().as_text()).hbm_bytes
    hb_u += 4 * analyze_hlo(_axpy.lower(vecs[4], vecs[3], a).compile().as_text()).hbm_bytes
    hb_u += analyze_hlo(_mul.lower(inv, vecs[7]).compile().as_text()).hbm_bytes
    hb_u += 3 * analyze_hlo(_dot.lower(vecs[5], vecs[6]).compile().as_text()).hbm_bytes
    hb_f = analyze_hlo(f_fused_jnp.lower(*vecs, inv, a, b).compile().as_text()).hbm_bytes
    emit("kernels/vma_core/unfused_traffic", hb_u / n, f"bytes_per_elem;total={hb_u/1e6:.0f}MB")
    emit(
        "kernels/vma_core/fused_traffic",
        hb_f / n,
        f"bytes_per_elem;total={hb_f/1e6:.0f}MB;reduction={hb_u/hb_f:.2f}x",
    )

    # The jnp "fused" version still re-reads inputs per output on this
    # backend (single-output kLoop fusions) — which is exactly why the
    # Pallas kernel exists: its BlockSpec tiling streams every operand
    # once per grid step BY CONSTRUCTION: 11 reads + 9 writes = 80 B/elem
    # f32, vs ~157 unfused. That 1.96x is the paper's §V-B win on TPU.
    pallas_bytes = (11 + 9) * 4.0
    emit(
        "kernels/vma_core/pallas_traffic",
        pallas_bytes,
        f"bytes_per_elem;structural;reduction={hb_u/n/pallas_bytes:.2f}x",
    )
    # the Pallas kernel itself (interpret mode on CPU: correctness path, not speed)
    outs = fused_vma_dots(*vecs, inv, a, b)
    jax.block_until_ready(outs)
    emit("kernels/vma_core/pallas_interpret_ok", 0.0, "validated in tests/test_kernels.py")


if __name__ == "__main__":
    main()
