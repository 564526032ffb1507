"""Solver correctness: PCG (Alg 1) vs Chronopoulos vs PIPECG (Alg 2).

The paper's evaluation is speedup-only because PIPECG is algebraically
equivalent to PCG — that equivalence is the correctness gate here: same
solutions, same iteration counts (within finite-precision drift), matching
residual histories.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

try:  # property-based tests are optional: skip them, not the module
    from hypothesis import given, settings, strategies as st

    HAVE_HYPOTHESIS = True
except ImportError:
    HAVE_HYPOTHESIS = False

from repro.core import block_jacobi, chronopoulos_cg, identity, jacobi, pcg, pipecg
from repro.sparse import poisson27, poisson125, spmv, synthetic_spd_dia, table1_matrix


def _system(A):
    xstar = jnp.ones((A.n,)) / jnp.sqrt(A.n)  # paper §VI: exact solution 1/sqrt(N)
    b = spmv(A, xstar)
    return xstar, b


SOLVERS = {"pcg": pcg, "chronopoulos": chronopoulos_cg, "pipecg": pipecg}


class TestConvergence:
    @pytest.mark.parametrize("solver", list(SOLVERS))
    def test_poisson27_jacobi(self, solver):
        A = poisson27(8)
        xstar, b = _system(A)
        res = SOLVERS[solver](A, b, M=jacobi(A), atol=1e-6, maxiter=1000)
        assert bool(res.converged)
        assert float(jnp.linalg.norm(res.x - xstar)) < 1e-4

    @pytest.mark.parametrize("solver", list(SOLVERS))
    def test_poisson125(self, solver):
        A = poisson125(6)
        xstar, b = _system(A)
        res = SOLVERS[solver](A, b, M=jacobi(A), atol=1e-6, maxiter=1000)
        assert bool(res.converged)
        assert float(jnp.linalg.norm(res.x - xstar)) < 1e-4

    def test_identity_pc(self):
        A = poisson27(6)
        xstar, b = _system(A)
        res = pipecg(A, b, M=identity(), atol=1e-6, maxiter=1000)
        assert bool(res.converged)

    def test_block_jacobi_at_least_as_fast(self):
        A = synthetic_spd_dia(256, 9.0, seed=2)
        xstar, b = _system(A)
        rj = pipecg(A, b, M=jacobi(A), atol=1e-6, maxiter=2000)
        rb = pipecg(A, b, M=block_jacobi(A, block=4), atol=1e-6, maxiter=2000)
        assert bool(rb.converged)
        assert int(rb.iterations) <= int(rj.iterations) + 2

    def test_rtol_mode(self):
        A = poisson27(6)
        _, b = _system(A)
        res = pcg(A, b, M=jacobi(A), atol=0.0, rtol=1e-6, maxiter=1000)
        assert bool(res.converged)


class TestEquivalence:
    """PIPECG must track PCG: same math, different schedule."""

    @pytest.mark.parametrize("gen", [lambda: poisson27(7), lambda: synthetic_spd_dia(400, 9.0, seed=11)])
    def test_iteration_counts_match(self, gen):
        A = gen()
        xstar, b = _system(A)
        M = jacobi(A)
        its = {k: int(s(A, b, M=M, atol=1e-6, maxiter=2000).iterations) for k, s in SOLVERS.items()}
        assert max(its.values()) - min(its.values()) <= 2, its

    def test_residual_histories_track(self):
        A = poisson27(7)
        xstar, b = _system(A)
        M = jacobi(A)
        h_pcg = np.asarray(pcg(A, b, M=M, atol=1e-6, maxiter=100).history)
        h_pipe = np.asarray(pipecg(A, b, M=M, atol=1e-6, maxiter=100).history)
        k = min(np.count_nonzero(~np.isnan(h_pcg)), np.count_nonzero(~np.isnan(h_pipe)))
        assert k > 3
        # same convergence trajectory within finite-precision drift
        np.testing.assert_allclose(h_pcg[: k - 1], h_pipe[: k - 1], rtol=0.15)

    def test_solutions_match_f32(self):
        """In float32 PIPECG's recurrence-residual drifts (known finite-
        precision property); solutions must still agree to ~1e-2 and both
        must have small TRUE residuals."""
        A = synthetic_spd_dia(300, 7.0, seed=12)
        xstar, b = _system(A)
        M = jacobi(A)
        xs = {}
        for k, s in SOLVERS.items():
            res = s(A, b, M=M, atol=1e-6, maxiter=3000)
            xs[k] = np.asarray(res.x)
            true_res = float(jnp.linalg.norm(b - spmv(A, res.x)))
            assert true_res < 1e-3, (k, true_res)
        np.testing.assert_allclose(xs["pcg"], xs["pipecg"], rtol=2e-2, atol=1e-4)
        np.testing.assert_allclose(xs["pcg"], xs["chronopoulos"], rtol=2e-2, atol=1e-4)

    def test_residual_replacement_arrests_drift(self):
        """Beyond-paper: with replace_every, long f32 runs at unattainable
        tolerance must NOT diverge (plain PIPECG recurrences do)."""
        A = synthetic_spd_dia(300, 7.0, seed=12)
        xstar, b = _system(A)
        M = jacobi(A)
        plain = pipecg(A, b, M=M, atol=0.0, maxiter=300)
        rr = pipecg(A, b, M=M, atol=0.0, maxiter=300, replace_every=25)
        true_plain = float(jnp.linalg.norm(b - spmv(A, plain.x)))
        true_rr = float(jnp.linalg.norm(b - spmv(A, rr.x)))
        assert true_rr < 5e-4, true_rr
        assert true_rr < true_plain

    def test_solutions_match_f64(self):
        """Under float64 the algebraic equivalence is near-exact."""
        with jax.enable_x64(True):
            A = synthetic_spd_dia(200, 7.0, seed=13, dtype=jnp.float64)
            xstar = jnp.ones((200,), jnp.float64) / jnp.sqrt(200.0)
            b = spmv(A, xstar)
            M = jacobi(A)
            xs = {k: np.asarray(s(A, b, M=M, atol=1e-10, maxiter=3000).x) for k, s in SOLVERS.items()}
        np.testing.assert_allclose(xs["pcg"], xs["pipecg"], rtol=1e-6, atol=1e-9)
        np.testing.assert_allclose(xs["pcg"], xs["chronopoulos"], rtol=1e-6, atol=1e-9)

    def test_pallas_engine_matches_jnp(self):
        A = poisson27(7)
        xstar, b = _system(A)
        M = jacobi(A)
        r1 = pipecg(A, b, M=M, atol=1e-6, maxiter=500, engine="jnp")
        r2 = pipecg(A, b, M=M, atol=1e-6, maxiter=500, engine="pallas")
        assert abs(int(r1.iterations) - int(r2.iterations)) <= 1
        np.testing.assert_allclose(np.asarray(r1.x), np.asarray(r2.x), rtol=1e-4, atol=1e-5)


class TestEdgeCases:
    def test_zero_rhs(self):
        A = poisson27(5)
        b = jnp.zeros((A.n,))
        res = pipecg(A, b, M=jacobi(A), atol=1e-6, maxiter=100)
        assert bool(res.converged)
        assert int(res.iterations) == 0
        assert float(jnp.linalg.norm(res.x)) == 0.0

    def test_maxiter_exhaustion(self):
        A = poisson125(5)
        _, b = _system(A)
        res = pipecg(A, b, M=identity(), atol=1e-30, maxiter=3)
        assert not bool(res.converged)
        assert int(res.iterations) == 3

    def test_warm_start(self):
        A = poisson27(6)
        xstar, b = _system(A)
        res = pipecg(A, b, M=jacobi(A), x0=xstar, atol=1e-6, maxiter=100)
        assert int(res.iterations) <= 1

    def test_history_shape_and_nan_padding(self):
        A = poisson27(5)
        _, b = _system(A)
        res = pcg(A, b, M=jacobi(A), atol=1e-6, maxiter=50)
        h = np.asarray(res.history)
        assert h.shape == (51,)
        k = int(res.iterations)
        assert np.all(np.isnan(h[k + 1 :]))
        assert not np.any(np.isnan(h[: k + 1]))


if HAVE_HYPOTHESIS:

    @st.composite
    def spd_problem(draw):
        n = draw(st.integers(min_value=32, max_value=300))
        nnz = draw(st.floats(min_value=3.0, max_value=15.0))
        seed = draw(st.integers(min_value=0, max_value=2**16))
        return n, nnz, seed

    class TestProperties:
        """Property-based invariants of the solver family (hypothesis)."""

        @settings(max_examples=15, deadline=None)
        @given(spd_problem())
        def test_pipecg_solves_random_spd(self, prob):
            n, nnz, seed = prob
            A = synthetic_spd_dia(n, nnz, seed=seed)
            xstar = jnp.ones((n,)) / jnp.sqrt(n)
            b = spmv(A, xstar)
            # paper's tolerance (1e-5), made scale-relative; residual
            # replacement keeps f32 recurrences honest on adversarial
            # instances
            res = pipecg(A, b, M=jacobi(A), atol=0.0, rtol=1e-5, maxiter=5 * n, replace_every=50)
            assert bool(res.converged)
            true_rel = float(jnp.linalg.norm(b - spmv(A, res.x)) / jnp.linalg.norm(b))
            assert true_rel < 1e-3

        @settings(max_examples=10, deadline=None)
        @given(spd_problem())
        def test_monotone_energy_norm(self, prob):
            """CG minimizes the A-norm of the error over the Krylov space:
            the error must be (weakly) monotone decreasing in the A-norm."""
            n, nnz, seed = prob
            A = synthetic_spd_dia(n, nnz, seed=seed)
            xstar = jnp.ones((n,)) / jnp.sqrt(n)
            b = spmv(A, xstar)
            hist = []
            # run a few manual restarts to sample intermediate errors
            for it in (1, 2, 4, 8, 16):
                res = pcg(A, b, M=jacobi(A), atol=0.0, maxiter=it)
                e = res.x - xstar
                hist.append(float(jnp.dot(e, spmv(A, e))))
            for a, c in zip(hist, hist[1:]):
                assert c <= a * (1 + 1e-3)

        @settings(max_examples=10, deadline=None)
        @given(st.integers(min_value=0, max_value=2**16))
        def test_pcg_pipecg_same_iterations(self, seed):
            A = synthetic_spd_dia(128, 7.0, seed=seed)
            xstar = jnp.ones((128,)) / jnp.sqrt(128.0)
            b = spmv(A, xstar)
            M = jacobi(A)
            i1 = int(pcg(A, b, M=M, atol=1e-6, maxiter=1000).iterations)
            i2 = int(pipecg(A, b, M=M, atol=1e-6, maxiter=1000).iterations)
            assert abs(i1 - i2) <= 2

else:

    class TestProperties:
        @pytest.mark.skip(reason="hypothesis not installed")
        def test_property_based(self):
            pass
