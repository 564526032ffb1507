"""The plan/execute solver API (``repro.plan`` -> ``SolverPlan``).

Covers the reuse guarantees the redesign exists for:

* plan reuse — the second (and eighth) ``plan.solve`` re-traces nothing
  (asserted via the plan's trace counter) and matches a fresh
  ``repro.solve``;
* the keyed plan cache behind one-shot ``repro.solve``;
* distributed plans — sharding/decomposition happen exactly once per
  plan, nonzero ``x0`` is solved via the shifted system;
* the ``LinearOperator`` protocol — matrix-free ``FunctionOperator``
  equivalence with the explicit ``DIAMatrix``;
* the CSR segment-sum SPMV engine and registry hygiene
  (``overwrite=False`` everywhere, ``solver_names`` unique + sorted).
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

import repro
from repro import plan as plan_mod  # callable module: plan_mod(A, ...) builds a plan
from repro.plan import clear_plan_cache, get_plan, plan_cache_stats
from repro.sparse import (
    CSRMatrix,
    DIAMatrix,
    FunctionOperator,
    as_operator,
    csr_device_from_host,
    csr_from_dia,
    poisson27,
    register_spmv,
    spmv,
    spmv_engines,
)


def _system(A):
    xstar = jnp.ones((A.n,)) / jnp.sqrt(A.n)
    return xstar, spmv(A, xstar)


class TestPlanReuse:
    def test_eight_rhs_one_trace_matches_fresh_solve(self):
        """Acceptance: 8 rhs through one plan re-trace nothing after the
        first solve and match per-call repro.solve to 1e-6."""
        clear_plan_cache()
        A = poisson27(6)
        _, b = _system(A)
        p = repro.plan(A, method="pipecg", M="jacobi", maxiter=300)
        for k in range(8):
            bk = (1.0 + 0.25 * k) * b
            res = p.solve(bk, atol=1e-6)
            ref = repro.solve(A, bk, method="pipecg", M="jacobi", atol=1e-6, maxiter=300)
            np.testing.assert_allclose(np.asarray(res.x), np.asarray(ref.x), atol=1e-6)
            assert int(res.iterations) == int(ref.iterations)
        assert p.trace_count == 1

    def test_tolerance_and_x0_are_traced_not_static(self):
        A = poisson27(6)
        xstar, b = _system(A)
        p = repro.plan(A, method="pipecg", M="jacobi", maxiter=300)
        loose = p.solve(b, atol=1e-2)
        tight = p.solve(b, atol=1e-6)
        assert int(loose.iterations) < int(tight.iterations)
        warm = p.solve(b, x0=xstar, atol=1e-6)
        assert int(warm.iterations) <= 1
        p.solve(2 * b, x0=0.5 * xstar, atol=1e-6)
        # single-device plans always pass x0 as an array (zeros when None),
        # so tolerance AND warm-start changes share the ONE traced program
        assert p.trace_count == 1

    def test_solve_batched_one_program(self):
        A = poisson27(6)
        _, b = _system(A)
        p = repro.plan(A, method="pipecg", M="jacobi", maxiter=300)
        B = jnp.stack([b, 2.0 * b, -1.0 * b])
        rb = p.solve_batched(B, atol=1e-6)
        assert rb.x.shape == B.shape
        for k in range(3):
            assert bool(rb.converged[k])
            np.testing.assert_allclose(
                np.asarray(rb.x[k]), np.asarray(p.solve(B[k], atol=1e-6).x), atol=1e-6
            )
        before = p.trace_count
        p.solve_batched(0.5 * B, atol=1e-6)
        assert p.trace_count == before  # batched program traced once, reused

    def test_describe(self):
        A = poisson27(5)
        p = repro.plan(A, method="pipecg", engine="jnp", M="jacobi", maxiter=100)
        d = p.describe()
        assert d["method"] == "pipecg"
        assert d["engine"] == "jnp"
        assert d["n"] == A.n
        assert d["preconditioner"] == "JacobiPC"
        assert not d["distributed"]

    def test_unknown_method_raises(self):
        with pytest.raises(ValueError, match="unknown method"):
            repro.plan(poisson27(4), method="does-not-exist")

    def test_unknown_kwarg_raises(self):
        with pytest.raises(TypeError, match="does not accept"):
            repro.plan(poisson27(4), method="pcg", bogus_option=3)


class TestPlanCache:
    def test_solve_hits_cache(self):
        clear_plan_cache()
        A = poisson27(5)
        _, b = _system(A)
        repro.solve(A, b, method="pipecg", M="jacobi", atol=1e-5, maxiter=200)
        s0 = plan_cache_stats()
        repro.solve(A, 2 * b, method="pipecg", M="jacobi", atol=1e-6, maxiter=200)
        s1 = plan_cache_stats()
        assert s0["misses"] == 1 and s0["hits"] == 0
        assert s1["hits"] == 1 and s1["misses"] == 1  # atol change still hits
        assert get_plan(A, method="pipecg", M="jacobi", maxiter=200) is get_plan(
            A, method="pipecg", M="jacobi", maxiter=200
        )

    def test_config_change_is_a_different_plan(self):
        clear_plan_cache()
        A = poisson27(5)
        p1 = get_plan(A, method="pipecg", M="jacobi", maxiter=200)
        p2 = get_plan(A, method="pipecg", M="jacobi", maxiter=300)  # static: re-plan
        p3 = get_plan(A, method="pcg", engine="jnp", M="jacobi", maxiter=200)
        assert p1 is not p2 and p1 is not p3
        assert get_plan(A, method="pipecg", M="jacobi", maxiter=200) is p1

    def test_operator_identity_keys_the_cache(self):
        clear_plan_cache()
        A1 = poisson27(5)
        A2 = poisson27(5)  # equal values, distinct object -> distinct plan
        assert get_plan(A1, method="pipecg", maxiter=100) is not get_plan(
            A2, method="pipecg", maxiter=100
        )


class TestFunctionOperator:
    def test_matches_explicit_dia(self):
        A = poisson27(6)
        _, b = _system(A)
        op = FunctionOperator(
            fn=lambda v: spmv(A, v), n=A.n, out_dtype=b.dtype, diag=A.diagonal()
        )
        r_op = repro.solve(op, b, method="pipecg", M="jacobi", atol=1e-6, maxiter=300)
        r_dia = repro.solve(A, b, method="pipecg", M="jacobi", atol=1e-6, maxiter=300)
        assert bool(r_op.converged)
        assert int(r_op.iterations) == int(r_dia.iterations)
        np.testing.assert_allclose(np.asarray(r_op.x), np.asarray(r_dia.x), atol=1e-6)

    def test_matrix_free_without_diag_needs_non_jacobi_pc(self):
        A = poisson27(5)
        _, b = _system(A)
        op = FunctionOperator(fn=lambda v: spmv(A, v), n=A.n, out_dtype=b.dtype)
        with pytest.raises(ValueError, match="no diagonal"):
            repro.plan(op, method="pipecg", M="jacobi")
        res = repro.solve(op, b, method="pipecg", M="identity", atol=1e-6, maxiter=300)
        assert bool(res.converged)

    def test_as_operator_wraps_callables(self):
        A = poisson27(5)
        op = as_operator(lambda v: spmv(A, v), n=A.n, diag=A.diagonal())
        assert isinstance(op, FunctionOperator)
        assert op.shape == (A.n, A.n)
        assert as_operator(A) is A
        with pytest.raises(ValueError, match="needs n="):
            as_operator(lambda v: v)

    def test_spmv_protocol_fallback(self):
        A = poisson27(5)
        op = FunctionOperator(fn=lambda v: 2.0 * v, n=A.n)
        x = jnp.arange(float(A.n))
        np.testing.assert_allclose(np.asarray(spmv(op, x)), 2.0 * np.arange(A.n))
        assert spmv_engines(op) == ("jnp",)


class TestDistributedPlan:
    """shards=1 runs the full h3 machinery (shard_map, halo spmv, packed
    psum) on the default single device — multi-device equivalence is
    covered by tests/test_unified_solver.py::TestCrossStrategy."""

    def test_setup_runs_exactly_once_for_eight_rhs(self, monkeypatch):
        clear_plan_cache()
        calls = {"shard": 0, "decomp": 0}
        real_shard, real_decomp = plan_mod.shard_dia, plan_mod.decompose

        def counting_shard(*a, **k):
            calls["shard"] += 1
            return real_shard(*a, **k)

        def counting_decomp(*a, **k):
            calls["decomp"] += 1
            return real_decomp(*a, **k)

        monkeypatch.setattr(plan_mod, "shard_dia", counting_shard)
        monkeypatch.setattr(plan_mod, "decompose", counting_decomp)
        A = poisson27(8)
        _, b = _system(A)
        p = repro.plan(A, method="h3", M="jacobi", shards=1, partition="nnz", maxiter=300)
        assert calls == {"shard": 1, "decomp": 1}
        for k in range(8):
            bk = (1.0 + 0.5 * k) * b
            res = p.solve(bk, atol=1e-6)
            ref = repro.solve(A, bk, method="h3", M="jacobi", shards=1,
                              partition="nnz", atol=1e-6, maxiter=300)
            np.testing.assert_allclose(np.asarray(res.x), np.asarray(ref.x), atol=1e-6)
        # 8 rhs later: still exactly one sharding/decomposition, one trace
        assert calls == {"shard": 2, "decomp": 2}  # +1 for repro.solve's own cached plan
        assert p.trace_count == 1

    def test_distributed_describe(self):
        p = repro.plan(poisson27(8), method="h3", M="jacobi", shards=1, maxiter=100)
        d = p.describe()
        assert d["distributed"] and d["method"] == "h3"
        assert d["reducer"] == "packed" and d["spmv_strategy"] == "halo"
        assert d["shard_bounds"] == (0, 512)

    def test_nonzero_x0_solves_shifted_system(self):
        A = poisson27(8)
        xstar, b = _system(A)
        warm = repro.solve(A, b, method="h3", M="jacobi", shards=1, x0=xstar,
                           atol=1e-6, maxiter=300)
        assert int(warm.iterations) <= 1
        assert float(jnp.linalg.norm(warm.x - xstar)) < 1e-5
        x0 = 0.25 * xstar
        part = repro.solve(A, b, method="h3", M="jacobi", shards=1, x0=x0,
                           atol=1e-6, maxiter=300)
        assert bool(part.converged)
        assert float(jnp.linalg.norm(part.x - xstar)) < 1e-4


class TestCSREngine:
    def _csr(self, A: DIAMatrix) -> CSRMatrix:
        return csr_device_from_host(csr_from_dia(A))

    def test_segment_sum_parity(self):
        A = poisson27(7)
        C = self._csr(A)
        x = jnp.asarray(np.random.default_rng(3).normal(size=(A.n,)), jnp.float32)
        y_dia = np.asarray(spmv(A, x), np.float64)
        y_ref = np.asarray(spmv(C, x, engine="jnp"), np.float64)
        y_seg = np.asarray(spmv(C, x, engine="segsum"), np.float64)
        np.testing.assert_allclose(y_ref, y_dia, rtol=1e-5, atol=1e-5)
        np.testing.assert_allclose(y_seg, y_dia, rtol=1e-5, atol=1e-5)
        assert set(spmv_engines(C)) == {"jnp", "segsum"}

    def test_csr_solves_through_plan(self):
        A = poisson27(6)
        _, b = _system(A)
        C = self._csr(A)
        res = repro.solve(C, b, method="pipecg", M="jacobi", atol=1e-6, maxiter=300)
        ref = repro.solve(A, b, method="pipecg", M="jacobi", atol=1e-6, maxiter=300)
        assert bool(res.converged)
        np.testing.assert_allclose(np.asarray(res.x), np.asarray(ref.x), atol=1e-5)

    def test_csr_diagonal(self):
        A = poisson27(5)
        np.testing.assert_allclose(
            np.asarray(self._csr(A).diagonal()), np.asarray(A.diagonal())
        )


class TestRegistryHygiene:
    def test_solver_names_unique_sorted(self):
        names = repro.solver_names()
        assert list(names) == sorted(set(names))
        assert {"pcg", "pipecg", "h1", "h2", "h3", "pipecg_distributed"} <= set(names)

    def test_register_solver_overwrite_guard(self):
        fn = lambda A, b, **kw: None  # noqa: E731
        repro.register_solver("_plan_test_dummy", fn)
        with pytest.raises(ValueError, match="already registered"):
            repro.register_solver("_plan_test_dummy", fn)
        repro.register_solver("_plan_test_dummy", fn, overwrite=True)

    def test_register_spmv_overwrite_guard(self):
        class _PlanTestMat(DIAMatrix):
            pass

        fn = lambda A, x: x  # noqa: E731
        register_spmv(_PlanTestMat, "custom", fn)
        with pytest.raises(ValueError, match="already registered"):
            register_spmv(_PlanTestMat, "custom", fn)
        register_spmv(_PlanTestMat, "custom", fn, overwrite=True)

    def test_register_reducer_overwrite_guard(self, monkeypatch):
        from repro.core import reduce
        from repro.core.reduce import register_reducer

        # a registry copy for this test: tests in other files enumerate the
        # reducers, and this factory's reducer has no ``.array``
        monkeypatch.setattr(reduce, "_REDUCERS", dict(reduce._REDUCERS))
        factory = lambda axis: (lambda g, d, nn: (g, d, nn))  # noqa: E731
        register_reducer("_plan_test_red", factory)
        with pytest.raises(ValueError, match="already registered"):
            register_reducer("_plan_test_red", factory)
        register_reducer("_plan_test_red", factory, overwrite=True)

    def test_register_dist_method_overwrite_guard(self):
        from repro.core.distributed import DistMethod, register_method

        m = DistMethod(reduce="packed", spmv="halo", equal_shards_only=False)
        register_method("_plan_test_h", m)
        with pytest.raises(ValueError, match="already registered"):
            register_method("_plan_test_h", m)
        register_method("_plan_test_h", m, overwrite=True)


class TestServeEngineCoalescing:
    def test_max_batch_buckets_match_unbatched(self):
        from repro.serve.engine import SolverEngine

        A = poisson27(6)
        _, b = _system(A)
        eng = SolverEngine(A, method="pipecg", atol=1e-6, maxiter=300, max_batch=3)
        B = jnp.stack([(1.0 + 0.5 * k) * b for k in range(7)])  # 3 + 3 + padded 1
        rb = eng.solve_batch(B)
        assert rb.x.shape == B.shape
        for k in range(7):
            assert bool(rb.converged[k])
            np.testing.assert_allclose(
                np.asarray(rb.x[k]), np.asarray(eng.solve(B[k]).x), atol=1e-6
            )
        # all buckets (including the padded remainder) reuse ONE batched trace
        assert eng.plan.trace_count == 2  # 1 single-rhs program + 1 bucket program

    def test_empty_batch_is_a_noop(self):
        from repro.serve.engine import SolverEngine

        A = poisson27(5)
        eng = SolverEngine(A, method="pipecg", maxiter=100, max_batch=3)
        assert eng.solve_batch(jnp.zeros((0, A.n))).x.shape == (0, A.n)
