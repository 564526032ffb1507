"""The readers of the per-layer metrics that read the program's own names: its
``plan.solve`` spans and its ``spmv_dia`` kernel, on hand-made runs."""
from __future__ import annotations

import dataclasses

import numpy as np
import pytest

from bench_testutil import ROOT  # noqa: F401  (puts bench/ and src/ on the path)

from harness import manifest, runner, stencil, tracing

CELL = "hpcg27_104.single"


def _run(device_ops, traced_calls=3, trace=True, k=1):
    cell = manifest.load_cell(CELL)
    summary = tracing.TraceSummary(window_s=5.0, busy_s=[4.8], collective_exposed_s=[0.0],
                                   module_runs=[300], device_ops=device_ops, idle_gaps=[])
    return runner.RunRecord(
        cell=cell, stencil=stencil.from_config(cell.config["operator"]), rhs_per_call=k,
        setup={}, setup_s=1.0, window_s=20.0, latencies_s=np.full(4, 0.085),
        iterations=np.array([[151], [152], [150], [151]]), device_kind="TPU v5 lite",
        trace=summary if trace else None, traced_calls=traced_calls if trace else None)


def _span(name, ms):
    from repro.obs import Span

    return Span(name=name, t_start=1.0, t_end=1.0 + ms * 1e-3)


# 48.7 ms of spmv_dia per solve, as the first traced runs of the cell read
OPS = [["spmv_dia", 3 * 0.0487], ["copy", 0.05], ["fused_vma", 0.04]]


def test_spmv_dia_roofline_equals_the_hand_count():
    # 3 traced calls of 151, 152, 150 iterations, replace_every 10: 62 SPMVs each
    # outside the loop; each SPMV moves 27 diagonals + x + y of N = 104^3 floats
    spmvs = (151 + 62) + (152 + 62) + (150 + 62)
    want = 100.0 * spmvs * 29 * 104**3 * 4 / (3 * 0.0487 * 819e9)
    got = manifest.load_reader("spmv_dia.roofline")(_run(OPS))
    assert got == pytest.approx(want, rel=1e-12)
    assert 55.0 < got < 85.0


@pytest.mark.parametrize("run", [
    pytest.param(lambda: _run(OPS, trace=False), id="untraced"),
    pytest.param(lambda: _run(OPS, k=4), id="batched"),
    pytest.param(lambda: _run([["_spmv", 2.9], ["_fused", 0.8]]), id="kernel-not-named"),
])
def test_spmv_dia_roofline_reads_nothing_where_it_does_not_apply(run):
    assert manifest.load_reader("spmv_dia.roofline")(run()) is None


def test_host_ms_is_the_mean_of_the_last_traced_calls_plan_solve_spans():
    roots = [_span("plan.build", 90.0)] + [_span("plan.solve", ms) for ms in (2, 3, 4, 5)]
    got = manifest.load_reader("plan.host_ms.solve")(_run(OPS, traced_calls=3), roots=roots)
    assert got == pytest.approx((3 + 4 + 5) / 3)


@pytest.mark.parametrize("run,roots", [
    pytest.param(lambda: _run(OPS, trace=False), 4, id="untraced"),
    pytest.param(lambda: _run(OPS, k=4), 4, id="batched"),
    pytest.param(lambda: _run(OPS, traced_calls=5), 4, id="fewer-spans-than-calls"),
    pytest.param(lambda: _run(OPS, traced_calls=3), 0, id="program-has-no-spans"),
])
def test_host_ms_reads_nothing_where_it_does_not_apply(run, roots):
    spans = [_span("plan.solve", 2.0)] * roots + [_span("plan.build", 1.0)]
    assert manifest.load_reader("plan.host_ms.solve")(run(), roots=spans) is None


def test_host_ms_reads_the_spans_the_program_records_under_the_profiler(tmp_path):
    import jax
    import jax.numpy as jnp

    import repro
    from repro import obs
    from repro.sparse import poisson27

    A = poisson27(6)
    b = jnp.ones((A.n,), jnp.float32)
    plan = repro.plan(A, method="pipecg", M="jacobi", atol=1e-5, maxiter=50)
    jax.block_until_ready(plan.solve(b))
    obs.clear_spans()
    with jax.profiler.trace(str(tmp_path)):
        for _ in range(2):
            jax.block_until_ready(plan.solve(b))
    roots = obs.span_tree()
    obs.clear_spans()
    assert [r.name for r in roots] == ["plan.solve", "plan.solve"]
    run = dataclasses.replace(_run(OPS), traced_calls=2)
    got = manifest.load_reader("plan.host_ms.solve")(run, roots=roots)
    assert got == pytest.approx(1e3 * (roots[0].duration_s + roots[1].duration_s) / 2)
    assert got > 0
