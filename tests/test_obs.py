"""The observability subsystem (``repro.obs``) and its zero-overhead claim.

What these tests pin down:

* ``convergence_curve`` — the one NaN-trim implementation, including the
  exactly-maxiter history (no NaN tail: the whole row IS the curve) and
  the batched ragged form; ``iterations_from_history`` per-rhs counts;
* **zero overhead while disabled** — every metric value is exactly zero
  after a full plan+solve cycle, no spans are recorded, and the solve
  loop's jaxpr is *byte-identical* with observability on vs off (the
  instrumentation uses ``jax.named_scope``, which adds no primitives —
  asserted both by string equality and by the while-body census);
* **spans follow the profiler** — with observability off, a capturing
  profiler alone makes ``plan.solve`` record its spans, and the written
  trace's host plane carries the same names; the named scopes are on in
  the compiled program either way;
* ``SolveReport`` — curve/launches/cache fields on warm solves,
  cold-start refusal to derive per-iteration numbers, distributed plans;
* plan-cache and trace-count telemetry under repeated and cross-key
  solves;
* serve-tier per-rhs iteration derivation + batch occupancy metrics;
* ``tools/bench_gate.py`` — pass on self-compare, fail on structural /
  timing / missing-key regressions, env-gating of timing comparisons.
"""
import glob
import json
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import repro
from repro import obs
from repro.kernels.common import count_primitive, while_body_jaxpr
from repro.plan import clear_plan_cache, plan_cache_stats
from repro.sparse import poisson27, spmv

REPO = os.path.join(os.path.dirname(__file__), "..")


@pytest.fixture(autouse=True)
def _obs_clean():
    """Every test starts and ends disabled with empty spans/metrics."""
    obs.disable()
    obs.clear_spans()
    obs.reset_metrics()
    yield
    obs.disable()
    obs.clear_spans()
    obs.reset_metrics()


def _system(grid=8):
    A = poisson27(grid)
    xstar = jnp.ones((A.n,)) / jnp.sqrt(A.n)
    return A, xstar, spmv(A, xstar)


# ---------------------------------------------------------------------------
# convergence_curve / iterations_from_history
# ---------------------------------------------------------------------------

class TestConvergenceCurve:
    def test_trims_nan_tail(self):
        h = np.array([1.0, 0.5, 0.1, np.nan, np.nan])
        c = obs.convergence_curve(h)
        np.testing.assert_array_equal(c, [1.0, 0.5, 0.1])

    def test_exactly_maxiter_no_nan_tail(self):
        # all maxiter+1 entries real: slicing at "first NaN" would drop
        # the final residual — the whole row is the curve
        h = np.array([1.0, 0.5, 0.25, 0.1])
        c = obs.convergence_curve(h)
        assert len(c) == 4 and c[-1] == 0.1

    def test_batched_ragged(self):
        h = np.array([
            [1.0, 0.5, 0.1, np.nan],
            [1.0, np.nan, np.nan, np.nan],
            [1.0, 0.9, 0.8, 0.7],          # ran to maxiter
        ])
        curves = obs.convergence_curve(h)
        assert [len(c) for c in curves] == [3, 1, 4]

    def test_accepts_solve_result(self):
        A, xstar, b = _system()
        res = repro.solve(A, b, method="pipecg", M="jacobi", atol=1e-5, maxiter=200)
        c = obs.convergence_curve(res)
        assert len(c) == int(res.iterations) + 1
        assert c[-1] < c[0]  # it converged: the curve went down

    def test_iterations_from_history(self):
        h = np.array([
            [1.0, 0.5, 0.1, np.nan],
            [1.0, np.nan, np.nan, np.nan],
            [1.0, 0.9, 0.8, 0.7],
        ])
        np.testing.assert_array_equal(obs.iterations_from_history(h), [2, 0, 3])
        assert obs.iterations_from_history(h[0]) == 2
        assert isinstance(obs.iterations_from_history(h[0]), int)

    def test_3d_history_rejected(self):
        with pytest.raises(ValueError):
            obs.convergence_curve(np.zeros((2, 2, 2)))


# ---------------------------------------------------------------------------
# zero overhead while disabled
# ---------------------------------------------------------------------------

class TestDisabledIsFree:
    def test_metrics_exactly_zero_after_solves(self):
        A, xstar, b = _system()
        p = repro.plan(A, method="pipecg", M="jacobi", atol=1e-5, maxiter=200)
        p.solve(b)
        p.solve(2.0 * b)
        p.solve_batched(jnp.stack([b, -b]))
        for name, d in obs.snapshot().items():
            if d["kind"] == "histogram":
                assert d["count"] == 0, name
            else:
                assert d["value"] == 0.0, name
        assert obs.span_tree() == ()
        assert p.last_report is None

    def test_span_yields_none_when_disabled(self):
        with obs.span("x", a=1) as sp:
            assert sp is None
        assert obs.span_tree() == ()

    @pytest.mark.parametrize("engine", ["jnp", "pallas"])
    def test_jaxpr_byte_identical_on_off(self, engine):
        # THE zero-overhead proof: the traced solve program is the same
        # string with observability on or off — named_scope adds nothing
        A, xstar, b = _system(6)
        args = (b, jnp.zeros_like(b), jnp.float32(1e-5), jnp.float32(0.0))

        def jaxpr_text():
            p = repro.plan(A, method="pipecg", engine=engine, M="jacobi",
                           atol=1e-5, maxiter=50)
            return str(jax.make_jaxpr(p._inner)(*args))

        off = jaxpr_text()
        obs.enable()
        on = jaxpr_text()
        assert on == off

    def test_while_body_census_identical(self):
        # and the census view of the same fact: zero extra primitives in
        # the iteration body with observability enabled
        A, xstar, b = _system(6)
        args = (b, jnp.zeros_like(b), jnp.float32(1e-5), jnp.float32(0.0))

        def body_counts():
            p = repro.plan(A, method="pipecg", engine="pallas", M="jacobi",
                           atol=1e-5, maxiter=50)
            body = while_body_jaxpr(jax.make_jaxpr(p._inner)(*args).jaxpr)
            return {prim: count_primitive(body, prim)
                    for prim in ("pallas_call", "dot_general", "add", "mul")}

        off = body_counts()
        obs.enable()
        assert body_counts() == off


# ---------------------------------------------------------------------------
# spans follow the profiler; scopes are always on
# ---------------------------------------------------------------------------

def _xplane_host_names(log_dir):
    from jax.profiler import ProfileData

    (path,) = glob.glob(os.path.join(str(log_dir), "plugins", "profile", "*", "*.xplane.pb"))
    data = ProfileData.from_file(path)
    return {e.name for plane in data.planes if plane.name.startswith("/host:")
            for line in plane.lines for e in line.events}


class TestSpansFollowTheProfiler:
    def _plan(self):
        A, xstar, b = _system(6)
        return repro.plan(A, method="pipecg", M="jacobi", atol=1e-5, maxiter=50), b

    def test_no_spans_without_obs_or_profiler(self):
        p, b = self._plan()
        jax.block_until_ready(p.solve(b))
        jax.block_until_ready(p.solve(2.0 * b))
        assert not obs.tracing()
        assert obs.span_tree() == ()

    def test_tracing_is_true_only_while_the_profiler_captures(self, tmp_path):
        assert not obs.tracing()
        with jax.profiler.trace(str(tmp_path)):
            assert obs.tracing() and not obs.enabled()
        assert not obs.tracing()

    def test_warm_solve_records_one_call_of_spans(self, tmp_path):
        p, b = self._plan()
        jax.block_until_ready(p.solve(b))  # cold, before the profiler
        with jax.profiler.trace(str(tmp_path)):
            jax.block_until_ready(p.solve(2.0 * b))
        (root,) = obs.span_tree()
        assert root.name == "plan.solve"
        assert root.attrs == {"plan": p._id, "call": 2, "method": "pipecg"}
        assert [c.name for c in root.children] == ["plan.solve.args", "plan.solve.dispatch"]
        assert all(c.parent_id == root.id for c in root.children)
        assert {s.call_id for s in (root, *root.children)} == {root.id}
        assert root.find("plan.trace") is None  # warm: nothing retraced
        assert root.duration_s >= sum(c.duration_s for c in root.children)
        # the profiler changes no dispatch: no sync, no report, no metrics
        assert p.last_report is None
        assert all(d.get("value", 0.0) == 0.0 and d.get("count", 0) == 0
                   for d in obs.snapshot().values())

    def test_cold_solve_records_the_trace(self, tmp_path):
        p, b = self._plan()
        with jax.profiler.trace(str(tmp_path)):
            jax.block_until_ready(p.solve(b))
        (root,) = obs.span_tree()
        traced = root.find("plan.solve.dispatch").find("plan.trace")
        assert traced is not None and traced.attrs == {"plan": p._id}
        assert traced.call_id == root.id

    def test_written_trace_holds_the_span_names(self, tmp_path):
        p, b = self._plan()
        jax.block_until_ready(p.solve(b))
        with jax.profiler.trace(str(tmp_path)):
            jax.block_until_ready(p.solve(b))
            jax.block_until_ready(p.solve_batched(jnp.stack([b, -b])))
        names = _xplane_host_names(tmp_path)
        assert {"plan.solve", "plan.solve.args", "plan.solve.dispatch",
                "plan.solve_batched", "plan.solve_batched.args",
                "plan.solve_batched.dispatch"} <= names

    def test_batched_solve_records_its_spans(self, tmp_path):
        p, b = self._plan()
        B = jnp.stack([b, -b])
        jax.block_until_ready(p.solve_batched(B))
        with jax.profiler.trace(str(tmp_path)):
            jax.block_until_ready(p.solve_batched(B))
        (root,) = obs.span_tree()
        assert root.name == "plan.solve_batched" and root.attrs["call"] == 2
        assert [c.name for c in root.children] == [
            "plan.solve_batched.args", "plan.solve_batched.dispatch"]

    def test_finished_roots_are_bounded(self):
        from repro.obs.trace import MAX_ROOTS

        obs.enable()
        for i in range(MAX_ROOTS + 3):
            with obs.span("s", i=i):
                pass
        roots = obs.span_tree()
        assert len(roots) == MAX_ROOTS
        assert roots[0].attrs["i"] == 3 and roots[-1].attrs["i"] == MAX_ROOTS + 2

    def test_scopes_name_the_compiled_ops_when_disabled(self):
        p, b = self._plan()
        hlo = p.lower(b).compile().as_text()
        for scope in ("solve.pipecg", "pipecg.init", "pipecg.iteration.spmv"):
            assert scope in hlo, scope


# ---------------------------------------------------------------------------
# spans + metrics while enabled
# ---------------------------------------------------------------------------

class TestEnabled:
    def test_span_tree_nesting_and_attrs(self):
        obs.enable()
        with obs.span("outer", k=1) as sp:
            assert sp is not None and sp.attrs["k"] == 1
            with obs.span("inner"):
                pass
        roots = obs.span_tree()
        assert [r.name for r in roots] == ["outer"]
        assert roots[0].find("inner") is not None
        assert roots[0].duration_s >= roots[0].children[0].duration_s

    def test_plan_build_span_structure(self):
        obs.enable()
        A, xstar, b = _system(6)
        repro.plan(A, method="pipecg", M="jacobi", atol=1e-5, maxiter=50)
        build = next(s for s in obs.span_tree() if s.name == "plan.build")
        assert build.find("plan.resolve_pc") is not None
        assert build.find("plan.pin_core") is not None
        assert obs.snapshot()["plan.builds"]["value"] == 1.0

    def test_metric_kind_clash_raises(self):
        obs.counter("x.same")
        with pytest.raises(TypeError):
            obs.gauge("x.same")

    def test_histogram_stats(self):
        obs.enable()
        h = obs.histogram("h")
        for v in (1.0, 2.0, 3.0, 4.0):
            h.record(v)
        d = h.to_dict()
        assert d["count"] == 4 and d["min"] == 1.0 and d["max"] == 4.0
        assert d["mean"] == 2.5

    def test_dump_sinks(self, tmp_path):
        obs.enable()
        obs.counter("c").inc(3)
        with obs.span("s"):
            pass
        mpath, spath = tmp_path / "m.jsonl", tmp_path / "s.json"
        obs.dump_jsonl(str(mpath))
        obs.dump_spans(str(spath))
        lines = [json.loads(l) for l in mpath.read_text().splitlines()]
        assert any(d["name"] == "c" and d["value"] == 3 for d in lines)
        assert json.loads(spath.read_text())["spans"][0]["name"] == "s"


# ---------------------------------------------------------------------------
# SolveReport
# ---------------------------------------------------------------------------

class TestSolveReport:
    def test_warm_solve_report_fields(self):
        obs.enable()
        A, xstar, b = _system()
        p = repro.plan(A, method="pipecg", engine="pallas", M="jacobi",
                       atol=1e-5, maxiter=200)
        r1 = p.solve(b)
        cold = p.last_report
        assert cold is not None and cold.cold_start
        # cold report keeps honest wall time but refuses derived rates
        assert cold.time_s is not None
        assert cold.time_per_iter_s is None

        p.solve(2.0 * b)
        rep = p.last_report
        assert not rep.cold_start
        assert rep.iterations > 0 and rep.converged
        assert len(rep.curve) == rep.iterations + 1
        # on CPU the SPMV engine resolves to jnp, so the fused VMA kernel
        # is the one pallas_call in the loop body
        assert rep.launches_per_iter == 1
        # bandwidth over host time is not a report field: it needs device time
        for gone in ("est_bytes_per_iter", "achieved_gbs", "frac_of_hbm_peak"):
            assert not hasattr(rep, gone)
        assert rep.env["backend"] == jax.default_backend()
        assert rep.trace_count == p.trace_count
        s = rep.summary()
        assert "launches" in s and "bandwidth" not in s
        d = json.loads(rep.to_json())
        assert d["iterations"] == rep.iterations
        assert len(d["curve"]) == rep.iterations + 1

    def test_rr_events(self):
        obs.enable()
        A, xstar, b = _system()
        p = repro.plan(A, method="pipecg", M="jacobi", atol=1e-12, rtol=0.0,
                       maxiter=40, replace_every=10)
        p.solve(b)
        rep = p.last_report
        assert rep.replace_every == 10
        assert rep.rr_events == rep.iterations // 10

    def test_batched_report_uses_worst_lane(self):
        obs.enable()
        A, xstar, b = _system()
        p = repro.plan(A, method="pipecg", M="jacobi", atol=1e-5, maxiter=200)
        res = p.solve_batched(jnp.stack([b, 1e-8 * b]))
        rep = p.last_report
        iters = obs.iterations_from_history(res.history)
        assert rep.iterations == int(iters.max())
        assert len(rep.curve) == int(iters.max()) + 1

    def test_comparable_env(self):
        e = obs.env_fingerprint()
        assert obs.comparable_env(e, dict(e))
        other = dict(e, device_kind="TPU v4")
        assert not obs.comparable_env(e, other)


# ---------------------------------------------------------------------------
# plan cache + trace count telemetry
# ---------------------------------------------------------------------------

class TestPlanTelemetry:
    def test_repeated_and_cross_key_solves(self):
        obs.enable()
        clear_plan_cache()
        A, xstar, b = _system(6)
        for _ in range(3):
            repro.solve(A, b, method="pipecg", M="jacobi", atol=1e-5, maxiter=100)
        stats = plan_cache_stats()
        assert stats["misses"] == 1 and stats["hits"] == 2
        # a different key (method) is a fresh plan, not a hit
        repro.solve(A, b, method="pcg", M="jacobi", atol=1e-5, maxiter=100)
        stats = plan_cache_stats()
        assert stats["misses"] == 2 and stats["hits"] == 2
        snap = obs.snapshot()
        assert snap["plan_cache.hits"]["value"] == 2.0
        assert snap["plan_cache.misses"]["value"] == 2.0
        assert snap["plan_cache.size"]["value"] == stats["size"]

    def test_trace_count_stays_one_across_solves(self):
        obs.enable()
        A, xstar, b = _system(6)
        p = repro.plan(A, method="pipecg", M="jacobi", atol=1e-5, maxiter=100)
        for i in range(4):
            p.solve(b + float(i))
        assert p.trace_count == 1  # same shapes: the pinned program is reused
        snap = obs.snapshot()
        assert snap["plan.solves"]["value"] == 4.0
        assert snap["plan.cold_solves"]["value"] == 1.0
        assert snap["plan.solve_time_s"]["count"] == 3  # warm solves only


# ---------------------------------------------------------------------------
# serve tier: per-rhs iterations + occupancy metrics
# ---------------------------------------------------------------------------

class TestServeTelemetry:
    def test_per_rhs_iterations_from_history(self):
        from repro.serve.engine import SolverEngine

        A, xstar, b = _system()
        eng = SolverEngine(A, M="jacobi", method="pipecg", atol=1e-5, maxiter=200)
        easy, easier, zero = b, 1e-6 * b, jnp.zeros_like(b)
        out = eng.solve_batch(jnp.stack([easy, easier, zero]))
        iters = np.asarray(out.iterations)
        assert iters.shape == (3,)
        # per-rhs counts, not the shared worst-case stop
        single = [int(eng.solve(v).iterations) for v in (easy, easier, zero)]
        np.testing.assert_array_equal(iters, single)
        assert iters[2] == 0  # zero rhs: converged at iteration 0

    def test_occupancy_metrics(self):
        from repro.serve.engine import SolverEngine

        obs.enable()
        A, xstar, b = _system(6)
        eng = SolverEngine(A, M="jacobi", method="pipecg", atol=1e-5,
                           maxiter=100, max_batch=2)
        eng.solve_batch(jnp.stack([b, 2.0 * b, -b]))  # 2 buckets, 1 padded lane
        snap = obs.snapshot()
        assert snap["serve.requests"]["value"] == 3.0
        assert snap["serve.buckets"]["value"] == 2.0
        assert snap["serve.padded_lanes"]["value"] == 1.0
        occ = snap["serve.batch_occupancy"]
        assert occ["count"] == 2 and occ["min"] == 0.5 and occ["max"] == 1.0
        assert snap["serve.rhs_iterations"]["count"] == 3
        assert "serve.wasted_lane_iterations" in snap


# ---------------------------------------------------------------------------
# bench_gate
# ---------------------------------------------------------------------------

def _run_gate(baseline, current, *extra):
    return subprocess.run(
        [sys.executable, os.path.join(REPO, "tools", "bench_gate.py"),
         "--baseline", str(baseline), "--current", str(current), *extra],
        capture_output=True, text=True,
    )


class TestBenchGate:
    BASE = {
        "bench": "kernels", "schema": 2,
        "env": {"backend": "cpu", "device_kind": "cpu", "x64": False},
        "cores": {
            "fused_iter": {"us_per_iter": 100.0, "launches_per_iter": 1,
                           "bytes_per_elem": 184.0, "achieved_gbs": 2.0},
        },
        "iters_pcg": 10,
    }

    def _write(self, d, rec):
        os.makedirs(d, exist_ok=True)
        with open(os.path.join(d, "BENCH_kernels.json"), "w") as f:
            json.dump(rec, f)

    def test_self_compare_passes(self, tmp_path):
        self._write(tmp_path / "a", self.BASE)
        self._write(tmp_path / "b", self.BASE)
        p = _run_gate(tmp_path / "a", tmp_path / "b")
        assert p.returncode == 0, p.stdout + p.stderr

    def test_structural_regression_fails(self, tmp_path):
        cur = json.loads(json.dumps(self.BASE))
        cur["cores"]["fused_iter"]["launches_per_iter"] = 2
        self._write(tmp_path / "a", self.BASE)
        self._write(tmp_path / "b", cur)
        p = _run_gate(tmp_path / "a", tmp_path / "b")
        assert p.returncode == 1
        assert "structural regression" in p.stderr

    def test_timing_band(self, tmp_path):
        cur = json.loads(json.dumps(self.BASE))
        cur["cores"]["fused_iter"]["us_per_iter"] = 200.0  # 2x: inside 2.5x band
        self._write(tmp_path / "a", self.BASE)
        self._write(tmp_path / "b", cur)
        assert _run_gate(tmp_path / "a", tmp_path / "b",
                         "--time-band", "2.5").returncode == 0
        cur["cores"]["fused_iter"]["us_per_iter"] = 300.0  # 3x: outside
        self._write(tmp_path / "b", cur)
        p = _run_gate(tmp_path / "a", tmp_path / "b", "--time-band", "2.5")
        assert p.returncode == 1 and "timing regression" in p.stderr

    def test_timing_skipped_when_env_differs(self, tmp_path):
        cur = json.loads(json.dumps(self.BASE))
        cur["cores"]["fused_iter"]["us_per_iter"] = 1e6
        cur["env"]["device_kind"] = "TPU v4"
        self._write(tmp_path / "a", self.BASE)
        self._write(tmp_path / "b", cur)
        p = _run_gate(tmp_path / "a", tmp_path / "b")
        assert p.returncode == 0
        assert "env fingerprints differ" in p.stdout

    def test_missing_key_fails(self, tmp_path):
        cur = json.loads(json.dumps(self.BASE))
        del cur["cores"]["fused_iter"]["us_per_iter"]
        self._write(tmp_path / "a", self.BASE)
        self._write(tmp_path / "b", cur)
        p = _run_gate(tmp_path / "a", tmp_path / "b")
        assert p.returncode == 1 and "MISSING in current" in p.stderr

    def test_convergence_band(self, tmp_path):
        cur = json.loads(json.dumps(self.BASE))
        cur["iters_pcg"] = 12  # +20% > 10% band
        self._write(tmp_path / "a", self.BASE)
        self._write(tmp_path / "b", cur)
        p = _run_gate(tmp_path / "a", tmp_path / "b")
        assert p.returncode == 1 and "convergence regression" in p.stderr

    def test_update_refreshes_baseline(self, tmp_path):
        cur = json.loads(json.dumps(self.BASE))
        cur["cores"]["fused_iter"]["launches_per_iter"] = 5
        self._write(tmp_path / "b", cur)
        p = _run_gate(tmp_path / "a", tmp_path / "b", "--update")
        assert p.returncode == 0
        with open(tmp_path / "a" / "BENCH_kernels.json") as f:
            assert json.load(f)["cores"]["fused_iter"]["launches_per_iter"] == 5

    def test_committed_trajectory_gates_itself(self):
        traj = os.path.join(REPO, "benchmarks", "trajectory")
        p = _run_gate(traj, traj)
        assert p.returncode == 0, p.stdout + p.stderr
