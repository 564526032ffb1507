"""The whole-iteration fused PIPECG kernel + mixed-precision SPMV engine.

Three layers, all on CPU interpret mode:

* kernel parity — ``fused_iter_step`` (one Pallas launch) vs
  ``fused_iter_ref`` (= spmv_dia_ref + the canonical ``pipecg_vma_core``
  recurrence), including cross-tile halos and the padded-tail invariant;
* solver integration — ``engine="fused_iter"`` matches ``engine="jnp"``
  iterates on non-multiple-of-tile sizes for Jacobi and identity PCs,
  launches exactly ONE kernel per iteration (jaxpr census) with zero
  per-iteration padding, and plans pin the core (trace_count stays 1);
* bf16 SPMV engine — tolerance-banded vs f32, "auto"/"segsum" engine
  resolution, and convergence with the residual-replacement safety net
  plans default on for it.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

import repro
from repro.core.iteration import make_fused_iter_core, resolve_core_name
from repro.core.pipecg import pipecg
from repro.core.preconditioners import jacobi
from repro.kernels import fused_iter_ref, fused_iter_step, fused_iter_tile
from repro.kernels.common import (
    ceil_to,
    count_primitive,
    launches_per_iteration,
    pad1d,
    while_body_jaxpr,
)
from repro.sparse import csr_from_dia, poisson27, resolve_engine, spmv_dia, spmv_dia_bf16, synthetic_spd_dia

TILE = 256  # small tile -> multiple grid steps (halo paths) in interpret mode


def _rand(n, seed, dtype=jnp.float32):
    return jax.random.normal(jax.random.PRNGKey(seed), (n,), dtype=dtype)


def _padded_operands(A, tile, dtype=jnp.float32, seed=0):
    t = fused_iter_tile(A.bandwidth, tile)
    n_pad = ceil_to(A.n, t)
    data = jnp.pad(A.data, ((0, 0), (0, n_pad - A.n))).astype(dtype)
    vecs = [pad1d(_rand(A.n, seed + i, dtype), n_pad) for i in range(9)]
    inv = pad1d(1.0 / jnp.asarray(A.diagonal(), dtype), n_pad)
    return t, n_pad, data, vecs, inv


class TestKernelParity:
    # sizes above one 1024-element row tile, so the halo paths run
    @pytest.mark.parametrize("gen", [lambda: poisson27(12), lambda: synthetic_spd_dia(1500, 9.0, seed=4)])
    def test_matches_ref(self, gen):
        A = gen()
        t, n_pad, data, vecs, inv = _padded_operands(A, TILE)
        assert n_pad > t  # multiple tiles: the halo BlockSpecs are exercised
        a, b = jnp.float32(0.3), jnp.float32(0.7)
        outs = fused_iter_step(data, A.offsets, *vecs, inv, a, b, tile=t)
        refs = fused_iter_ref(data, A.offsets, *vecs, inv, a, b)
        for got, want in zip(outs[:9], refs[:9]):
            np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=1e-5, atol=1e-5)
        np.testing.assert_allclose(np.asarray(outs[9]), np.asarray(refs[9]), rtol=1e-4, atol=1e-3)

    def test_padded_tail_stays_zero(self):
        A = poisson27(7)  # n=343: real padding
        t, n_pad, data, vecs, inv = _padded_operands(A, TILE)
        assert n_pad > A.n
        outs = fused_iter_step(data, A.offsets, *vecs, inv, jnp.float32(0.5), jnp.float32(0.25), tile=t)
        for o in outs[:9]:
            np.testing.assert_array_equal(np.asarray(o[A.n :]), 0.0)

    def test_rejects_unpadded(self):
        A = poisson27(7)
        vecs = [_rand(A.n, i) for i in range(9)]
        inv = jnp.ones((A.n,))
        with pytest.raises(ValueError, match="pre-padded"):
            fused_iter_step(A.data, A.offsets, *vecs, inv, 0.3, 0.7,
                            tile=fused_iter_tile(A.bandwidth, TILE))


class TestSolverIntegration:
    def test_jacobi_parity_with_jnp_core(self):
        A = poisson27(7)  # 343: non-multiple of every tile
        b = jnp.sin(jnp.arange(A.n, dtype=jnp.float32))
        M = jacobi(A)
        rj = pipecg(A, b, M=M, atol=1e-6, maxiter=200, engine="jnp")
        rf = pipecg(A, b, M=M, atol=1e-6, maxiter=200, engine="fused_iter")
        assert bool(rf.converged)
        assert int(rf.iterations) == int(rj.iterations)
        np.testing.assert_allclose(np.asarray(rf.x), np.asarray(rj.x), rtol=1e-4, atol=1e-5)

    def test_identity_pc_parity_with_jnp_core(self):
        A = poisson27(6)
        b = jnp.cos(jnp.arange(A.n, dtype=jnp.float32))
        # fixed 20 iterations (atol=rtol=0): compare iterates before f32
        # recurrence noise accumulates in the unpreconditioned run
        rj = pipecg(A, b, M=None, atol=0.0, rtol=0.0, maxiter=20, engine="jnp")
        rf = pipecg(A, b, M=None, atol=0.0, rtol=0.0, maxiter=20, engine="fused_iter")
        np.testing.assert_allclose(np.asarray(rf.x), np.asarray(rj.x), rtol=1e-3, atol=1e-4)

    def test_single_kernel_launch_per_iteration(self):
        A = poisson27(5)
        b = jnp.ones((A.n,), jnp.float32)
        M = jacobi(A)

        def run(engine, **kw):
            def f(bb):
                return pipecg(A, bb, M=M, atol=0.0, rtol=0.0, maxiter=10, engine=engine, **kw).x
            return f

        # the acceptance criterion: ONE pallas_call inside the while body
        assert launches_per_iteration(run("fused_iter"), b) == 1
        # contrast: the two-kernel path (VMA core + Pallas SPMV)
        assert launches_per_iteration(run("pallas", spmv_engine="pallas"), b) == 2
        # and the jnp core stages no kernels at all
        assert launches_per_iteration(run("jnp"), b) == 0

    def test_no_padding_in_hot_loop(self):
        A = poisson27(7)
        b = jnp.ones((A.n,), jnp.float32)
        M = jacobi(A)
        for engine in ("fused_iter", "pallas"):
            def f(bb, engine=engine):
                return pipecg(A, bb, M=M, atol=0.0, rtol=0.0, maxiter=10, engine=engine).x

            body = while_body_jaxpr(jax.make_jaxpr(f)(b).jaxpr)
            assert body is not None
            # on-chip kernel-internal pads are free; HBM-level pads are not
            assert count_primitive(body, "pad", into_kernels=False) == 0

    def test_requires_dia_and_elementwise_pc(self):
        A = poisson27(5)
        b = jnp.ones((A.n,), jnp.float32)
        with pytest.raises(TypeError, match="DIAMatrix"):
            pipecg(csr_from_dia(A), b, engine="fused_iter")
        from repro.core.preconditioners import block_jacobi

        with pytest.raises(ValueError, match="elementwise"):
            pipecg(A, b, M=block_jacobi(A, block=5), engine="fused_iter")

    def test_auto_resolution_on_cpu(self):
        # "auto" never picks a Pallas core off-TPU; explicit names pass through
        A = poisson27(4)
        assert resolve_core_name("auto", A) == "jnp"
        assert resolve_core_name("fused_iter", A) == "fused_iter"

    def test_core_factory_pins_padded_views(self):
        A = poisson27(7)
        core = make_fused_iter_core(A)
        assert core.fuses_spmv
        assert core.n_pad % core.tile == 0
        assert core.padded_data.shape == (A.data.shape[0], core.n_pad)

    def test_plan_pins_core_and_traces_once(self):
        A = poisson27(6)
        b = jnp.sin(jnp.arange(A.n, dtype=jnp.float32))
        p = repro.plan(A, method="pipecg", engine="fused_iter", M="jacobi",
                       atol=1e-6, maxiter=100)
        assert p._core is not None and p._core.fuses_spmv
        r1 = p.solve(b)
        r2 = p.solve(2.0 * b)
        assert p.trace_count == 1  # pinned program reused across rhs
        assert bool(r1.converged) and bool(r2.converged)
        np.testing.assert_allclose(np.asarray(r2.x), 2.0 * np.asarray(r1.x), rtol=1e-4, atol=1e-4)
        d = p.describe()
        assert d["core"] == "fused_iter"


class TestBf16Engine:
    def test_tolerance_band_vs_f32(self):
        A = poisson27(7)
        x = _rand(A.n, 3)
        y32 = np.asarray(spmv_dia(A, x), np.float64)
        y16 = np.asarray(spmv_dia_bf16(A, x), np.float64)
        rel = np.linalg.norm(y32 - y16) / np.linalg.norm(y32)
        assert rel < 2e-2  # bf16 storage error band
        assert rel > 0.0  # actually reduced precision, not a f32 alias
        assert spmv_dia_bf16(A, x).dtype == x.dtype

    def test_resolve_engine(self):
        from repro.sparse import csr_device_from_host

        A = poisson27(4)
        C = csr_device_from_host(csr_from_dia(A))
        assert resolve_engine(A, "bf16") == "bf16"
        if jax.default_backend() != "tpu":
            # satellite fix: CSR "auto" prefers the segsum engine off-TPU
            assert resolve_engine(C, "auto") == "segsum"
            assert resolve_engine(A, "auto") == "jnp"
        assert resolve_engine(C, "nonesuch") == "jnp"  # fallback

    def test_converges_with_residual_replacement(self):
        A = poisson27(7)
        b = jnp.sin(jnp.arange(A.n, dtype=jnp.float32))
        p = repro.plan(A, method="pipecg", engine="jnp", M="jacobi",
                       spmv_engine="bf16", atol=0.0, rtol=1e-2, maxiter=500)
        assert p.describe()["replace_every"] > 0  # safety net defaults ON
        r = p.solve(b)
        assert bool(r.converged)
        # true residual lands in the bf16 band, not just the recurrence one
        true_rel = float(jnp.linalg.norm(b - spmv_dia(A, r.x)) / jnp.linalg.norm(b))
        assert true_rel < 5e-2

    def test_explicit_replace_every_zero_respected(self):
        A = poisson27(5)
        b = jnp.ones((A.n,), jnp.float32)
        p = repro.plan(A, method="pipecg", engine="jnp", M="jacobi",
                       spmv_engine="bf16", replace_every=0, rtol=1e-2, maxiter=200)
        # the explicit 0 overrides the bf16 default — no safety net
        assert p.describe()["replace_every"] == 0
        r = p.solve(b)
        assert bool(jnp.all(jnp.isfinite(r.x)))  # runs; convergence not promised
