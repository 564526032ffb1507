"""The unified solver/operator architecture.

Covers the three strategy axes of the shared iteration core
(``core.iteration.run_pipecg``):

* SPMV engine dispatch — Pallas-vs-jnp parity for DIA and BELL
  (interpret mode on CPU), dense fallback, registry extension;
* the ``repro.solve`` registry — every method converges through one
  entry point, ``engine="pallas"`` runs core + SPMV on the kernels;
* cross-strategy equivalence — single-device ``pipecg`` and distributed
  h1/h2/h3 produce matching iterates because they run the same core.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from conftest import run_multidevice

import repro
from repro.sparse import (
    DIAMatrix,
    bell_from_csr,
    csr_from_dia,
    poisson27,
    register_spmv,
    resolve_engine,
    spmv,
    spmv_engines,
    synthetic_spd_dia,
)


def _system(A):
    xstar = jnp.ones((A.n,)) / jnp.sqrt(A.n)
    return xstar, spmv(A, xstar)


class TestSpmvDispatch:
    """Engine registry: (format, engine) -> kernel, with jnp fallback."""

    @pytest.mark.parametrize("gen", [lambda: poisson27(7), lambda: synthetic_spd_dia(500, 9.0, seed=4)])
    def test_dia_pallas_matches_jnp(self, gen):
        A = gen()
        x = jnp.asarray(np.random.default_rng(0).normal(size=(A.n,)), jnp.float32)
        y_j = np.asarray(spmv(A, x, engine="jnp"), np.float64)
        y_p = np.asarray(spmv(A, x, engine="pallas"), np.float64)
        np.testing.assert_allclose(y_p, y_j, rtol=1e-5, atol=1e-4)

    def test_bell_pallas_matches_jnp(self):
        from repro.kernels import spmv_bell_pallas

        A = bell_from_csr(csr_from_dia(poisson27(6)))
        x = jnp.asarray(np.random.default_rng(1).normal(size=(A.n,)), jnp.float32)
        y_j = np.asarray(spmv(A, x, engine="jnp"), np.float64)
        # the B-ELL kernel does not compile for TPU: BELL runs on jnp on
        # every backend, whichever engine is asked for
        assert resolve_engine(A, "pallas") == resolve_engine(A, "auto") == "jnp"
        y_p = np.asarray(spmv_bell_pallas(A, x), np.float64)  # interpret-mode kernel
        np.testing.assert_allclose(y_p, y_j, rtol=1e-5, atol=1e-4)

    def test_dense_fallback(self):
        A = jnp.eye(16) * 2.0
        x = jnp.arange(16.0)
        # dense has no pallas engine: request must fall back to jnp
        np.testing.assert_allclose(np.asarray(spmv(A, x, engine="pallas")), 2.0 * np.arange(16.0))

    def test_engines_listed(self):
        assert set(spmv_engines(poisson27(4))) == {"jnp", "pallas", "bf16"}
        assert spmv_engines(jnp.eye(4)) == ("jnp",)

    def test_registry_extension(self):
        class TaggedDIA(DIAMatrix):
            pass

        calls = []

        def custom(A, x):
            calls.append(1)
            return x

        register_spmv(TaggedDIA, "custom", custom)
        A = poisson27(4)
        T = TaggedDIA(A.data, A.offsets, A.n)
        x = jnp.ones((A.n,))
        # the custom engine dispatches; MRO still finds DIA's jnp engine
        np.testing.assert_allclose(np.asarray(spmv(T, x, engine="custom")), np.asarray(x))
        assert calls
        np.testing.assert_allclose(
            np.asarray(spmv(T, x, engine="jnp")), np.asarray(spmv(A, x, engine="jnp"))
        )


class TestSolveRegistry:
    @pytest.mark.parametrize("method", ["pcg", "chronopoulos", "pipecg"])
    def test_single_device_methods(self, method):
        A = poisson27(7)
        xstar, b = _system(A)
        res = repro.solve(A, b, method=method, M="jacobi", atol=1e-6, maxiter=500)
        assert bool(res.converged)
        assert float(jnp.linalg.norm(res.x - xstar)) < 1e-4

    def test_pipecg_pallas_engine_converges(self):
        """Acceptance: repro.solve(A, b, method='pipecg', engine='pallas')
        runs the fused VMA core AND the Pallas SPMV through the shared
        core and still converges on a Poisson matrix."""
        A = poisson27(7)
        xstar, b = _system(A)
        res = repro.solve(A, b, method="pipecg", engine="pallas", M="jacobi", atol=1e-6, maxiter=500)
        assert bool(res.converged)
        assert float(jnp.linalg.norm(res.x - xstar)) < 1e-4
        ref = repro.solve(A, b, method="pipecg", engine="jnp", M="jacobi", atol=1e-6, maxiter=500)
        assert abs(int(res.iterations) - int(ref.iterations)) <= 1
        np.testing.assert_allclose(np.asarray(res.x), np.asarray(ref.x), rtol=1e-4, atol=1e-5)

    def test_unknown_method_raises(self):
        A = poisson27(4)
        _, b = _system(A)
        with pytest.raises(ValueError, match="unknown method"):
            repro.solve(A, b, method="does-not-exist")

    def test_register_solver_extension(self):
        from repro.core.types import SolveResult

        def diag_solve(A, b, *, M, x0, atol, rtol, maxiter, engine, **_):
            x = b / A.diagonal()
            z = jnp.zeros(())
            return SolveResult(
                x=x, iterations=jnp.int32(1), residual_norm=z,
                converged=jnp.bool_(True), history=jnp.zeros((maxiter + 1,)),
            )

        repro.register_solver("diag", diag_solve)
        assert "diag" in repro.solver_names()
        A = poisson27(4)
        _, b = _system(A)
        res = repro.solve(A, b, method="diag")
        np.testing.assert_allclose(np.asarray(res.x), np.asarray(b / A.diagonal()))

    def test_solver_engine_batches(self):
        from repro.serve.engine import SolverEngine

        A = poisson27(6)
        eng = SolverEngine(A, method="pipecg", atol=0.0, rtol=1e-5, maxiter=300)
        xs = jnp.stack([jnp.sin(jnp.arange(A.n) * (k + 1) / 7.0) for k in range(3)])
        bs = jnp.stack([spmv(A, x) for x in xs])
        rb = eng.solve_batch(bs)
        assert rb.x.shape == bs.shape
        for k in range(3):
            assert bool(rb.converged[k])
            rel = float(jnp.linalg.norm(bs[k] - spmv(A, rb.x[k])) / jnp.linalg.norm(bs[k]))
            assert rel < 1e-3


_CROSS_STRATEGY = """
import numpy as np, jax, jax.numpy as jnp
import repro
from repro.sparse import poisson27, spmv
assert jax.device_count() == 4, jax.device_count()

A = poisson27(10)
xstar = jnp.ones((A.n,)) / jnp.sqrt(A.n)
b = spmv(A, xstar)
ref = repro.solve(A, b, method="pipecg", engine="jnp", M="jacobi", atol=1e-6, maxiter=500)
h_ref = np.asarray(ref.history)
k_ref = int(ref.iterations)
for method in ("h1", "h2", "h3"):
    res = repro.solve(A, b, method=method, M="jacobi", shards=4, atol=1e-6, maxiter=500)
    assert bool(res.converged), method
    assert abs(int(res.iterations) - k_ref) <= 1, (method, int(res.iterations), k_ref)
    # same core => same residual trajectory (up to psum summation order)
    k = min(int(res.iterations), k_ref)
    np.testing.assert_allclose(np.asarray(res.history)[:k], h_ref[:k], rtol=5e-2)
    err = float(jnp.linalg.norm(res.x - ref.x))
    assert err < 1e-4, (method, err)
print("OK", k_ref)
"""


class TestCrossStrategy:
    def test_distributed_matches_single_device_iterates(self):
        """Single-device pipecg and h1/h2/h3 run the SAME iteration core;
        their residual histories and solutions must coincide."""
        out = run_multidevice(_CROSS_STRATEGY, n_devices=4)
        assert "OK" in out


class TestCompat:
    def test_shim_exports(self):
        # the installed JAX provides these directly; the repo has no shims
        from jax.sharding import AxisType

        assert callable(jax.shard_map)
        assert hasattr(AxisType, "Auto")
        mesh = jax.make_mesh((1,), ("x",), devices=jax.devices()[:1],
                             axis_types=(AxisType.Auto,))
        assert tuple(mesh.axis_names) == ("x",)
