"""The benchmark's harness on CPU: loading by name, trace reduction, byte counts,
the result line, and the refusal to run without a TPU."""
from __future__ import annotations

import json
import os
import shutil
import subprocess
import sys

import numpy as np
import pytest

from bench_testutil import BENCH, ROOT, add_cell, run_small, small_root

from harness import manifest, peaks, runner, stencil, tracing, workbytes

CELLS = ("hpcg27_104.single",)
DATA = BENCH / "tests" / "data"


def _manifest():
    return json.loads((ROOT / "BENCHMARK.json").read_text())


# --- loading by name -------------------------------------------------------


@pytest.mark.parametrize("name", CELLS)
def test_loader_finds_cell_config_and_traffic_by_name(name):
    cell = manifest.load_cell(name)
    entry = {w["name"]: w for w in _manifest()["workloads"]}[name]
    assert (cell.config_name, cell.traffic_name, cell.chips) == (
        entry["config"], entry["traffic"], entry["chips"])
    assert cell.config["operator"]["kind"] == "box_stencil"
    assert cell.traffic["rhs_per_call"] in (1, 4)
    e2e = {m.name for m in cell.end_to_end}
    assert "setup_s" in e2e and len(e2e) >= 2
    assert cell.per_layer


def test_cells_report_the_metrics_their_traffic_calls_for():
    single = manifest.load_cell("hpcg27_104.single")
    assert {m.name for m in single.end_to_end} == {"solve_ms", "solve_p95_ms", "setup_s"}
    assert {m.name for m in single.per_layer} == {
        "iters.solve", "roofline.solve", "device_idle.solve", "setup.import_s", "setup.warm_s"}


def test_every_per_layer_metric_moves_a_metric_its_cells_report():
    m = _manifest()
    e2e = {x["name"]: x for x in m["end_to_end"]}
    for pl in m["per_layer"]:
        moved = e2e[pl["moves"]]
        for cell in pl.get("workloads", [w["name"] for w in m["workloads"]]):
            assert "workloads" not in moved or cell in moved["workloads"], (pl["name"], cell)


@pytest.mark.parametrize("metric", [m["name"] for m in _manifest()["end_to_end"] + _manifest()["per_layer"]])
def test_loader_finds_each_metric_reader_by_name(metric):
    assert callable(manifest.load_reader(metric))


@pytest.mark.parametrize("cfg", [c["name"] for c in _manifest()["configs"]])
def test_config_files_are_where_the_manifest_says(cfg):
    entry = {c["name"]: c for c in _manifest()["configs"]}[cfg]
    assert entry["file"] == f"bench/configs/{cfg}.json"
    data = json.loads((ROOT / entry["file"]).read_text())
    st = stencil.from_config(data["operator"])
    assert data["operator"]["n"] == st.n and data["operator"]["diagonals"] == st.n_diags
    assert set(entry["reduced"]) <= set(data["reduced"])


def test_new_cell_file_is_picked_up_without_editing_existing_files(tmp_path):
    root = small_root(tmp_path)
    before = {p: p.read_bytes() for p in (root / "bench").rglob("*") if p.is_file()}
    add_cell(root, "hpcg27_104.pool4", "hpcg27_104", {"rhs_per_call": 1, "pool_calls": 4})

    cell = manifest.load_cell("hpcg27_104.pool4", root)
    assert cell.traffic["pool_calls"] == 4 and cell.config_name == "hpcg27_104"
    assert {x.name for x in cell.end_to_end} == {"solve_ms", "solve_p95_ms", "setup_s"}
    assert {x.name for x in cell.per_layer} == {
        "iters.solve", "roofline.solve", "device_idle.solve", "setup.import_s", "setup.warm_s"}
    assert all(p.read_bytes() == b for p, b in before.items())
    line = run_small(root, "hpcg27_104.pool4", seconds=0.2)
    assert line["correct"] and line["attempted"] > 0
    assert set(line["metrics"]) == {"solve_ms", "solve_p95_ms", "setup_s"}


def test_batch_traffic_runs_through_solve_batched(tmp_path):
    root = small_root(tmp_path)
    add_cell(root, "hpcg27_104.batch4", "hpcg27_104", {"rhs_per_call": 4, "pool_calls": 4})
    line = run_small(root, "hpcg27_104.batch4", seconds=0.2)
    assert line["correct"] and line["failed"] == 0
    assert line["attempted"] > 0 and line["attempted"] % 4 == 0
    # the per-solve metrics read nothing in a batch: only set-up is left
    assert set(line["metrics"]) == {"setup_s"}


def test_loader_refuses_a_cell_file_that_disagrees_with_the_manifest(tmp_path):
    root = small_root(tmp_path)
    (root / "bench" / "workloads" / "hpcg27_104.single.json").write_text(
        json.dumps({"config": "hpcg27_104", "traffic": "single", "chips": 4}))
    with pytest.raises(manifest.ManifestError, match="chips"):
        manifest.load_cell("hpcg27_104.single", root)
    with pytest.raises(manifest.ManifestError, match="no workload"):
        manifest.load_cell("nope.single", root)


# --- trace reduction --------------------------------------------------------


def _fixture_planes():
    return json.loads((DATA / "trace_small.json").read_text())["planes"]


def test_trace_reduction_on_a_synthetic_trace_one_chip():
    s = tracing.summarize(_fixture_planes(), chips=1)
    # window [1000, 11000] ns; busy = [2000,3000] + [3500,5500] + [7300,10000]
    # (the while container and the op before the window are left out)
    assert s.window_s == pytest.approx(10000e-9)
    assert s.busy_s == [pytest.approx(5700e-9)]
    # the all-reduce [9300,9800] overlaps the fusion from 9600; the
    # collective-permute [8000,9000] lies under _spmv
    assert s.collective_exposed_s == [pytest.approx(300e-9)]
    assert s.module_runs == [2]
    gaps = dict(s.idle_gaps)
    assert gaps[tracing.IN_PROGRAM] == pytest.approx(1600e-9)
    assert gaps["bench.call"] == pytest.approx(1600e-9)
    assert gaps["bench.wait"] == pytest.approx(100e-9)
    assert gaps["bench.next"] == pytest.approx(1000e-9)
    assert tracing.OUTSIDE not in gaps
    assert sum(gaps.values()) == pytest.approx(s.window_s - s.busy_s[0])
    ops = dict(s.device_ops)
    assert ops == pytest.approx({"_spmv": 3000e-9, "fusion": 2400e-9, "all-reduce": 500e-9})
    assert [k for k, _ in s.device_ops] == ["_spmv", "fusion", "all-reduce"]


def test_trace_reduction_averages_over_chips():
    s = tracing.summarize(_fixture_planes(), chips=2)
    assert s.busy_s == [pytest.approx(5700e-9), pytest.approx(4000e-9)]
    assert s.mean_busy_s == pytest.approx(4850e-9)
    assert s.collective_exposed_s == [pytest.approx(300e-9), pytest.approx(0.0)]
    gaps = dict(s.idle_gaps)
    assert gaps[tracing.IN_PROGRAM] == pytest.approx(1000e-9)
    assert gaps["bench.call"] == pytest.approx(1700e-9)
    assert gaps["bench.wait"] == pytest.approx(1550e-9)
    assert gaps["bench.next"] == pytest.approx(900e-9)
    assert dict(s.device_ops)["fusion"] == pytest.approx(3200e-9)


def test_trace_reduction_refuses_a_trace_without_the_window_or_the_chips():
    planes = _fixture_planes()
    with pytest.raises(ValueError, match="TPU planes"):
        tracing.summarize(planes, chips=3)
    no_window = [p for p in planes if not p[0].startswith("/host")]
    with pytest.raises(ValueError, match="bench.window"):
        tracing.summarize(no_window, chips=1)


@pytest.mark.parametrize("name,base,opcode", [
    ("%fusion.75 = f32[4]{0:T(128)S(1)} fusion(f32[4]{0} %a), kind=kLoop", "fusion", "fusion"),
    ("%broadcast.2.clone = f32[8]{0} broadcast(f32[] %c)", "broadcast", "broadcast"),
    ("%_spmv.160 = f32[4534272]{0:T(1024)S(1)} custom-call(f32[27,4534272]{1,0:T(8,128)} %d)",
     "_spmv", "custom-call"),
    ("%while.170 = (s32[4]{0:T(128)}, f32[4,8]{1,0:T(4,128)}) while((s32[4]{0}) %t)", "while", "while"),
    ("copy-done", "copy-done", "copy-done"),
])
def test_op_names_are_classified(name, base, opcode):
    assert tracing.classify(name) == (base, opcode)


# --- algorithmic bytes and peaks --------------------------------------------


def test_bytes_p125_44_single_solve_equal_the_hand_count():
    # N = 44^3 = 85,184, 125 diagonals, 53 iterations, replacement every 10:
    # operator reads 125 x (53 + 2 init + 4 x 5 replacement SPMVs) = 9,375 rows;
    # vectors 17 x 53 (8 read + 8 written + inverse diagonal) + 2 x 22 = 945.
    assert workbytes.solve_bytes(85184, 125, 53, 10) == 4 * 85184 * (9375 + 945)
    assert workbytes.solve_bytes(85184, 125, 53, 10) == 3_516_395_520


def test_bytes_p27s_165_single_solve_equal_the_hand_count():
    # N = 165^3 = 4,492,125, 27 diagonals, 33 iterations: operator
    # 27 x (33 + 2 + 12) = 1,269; vectors 17 x 33 + 2 x 14 = 589.
    assert workbytes.solve_bytes(4492125, 27, 33, 10) == 4 * 4492125 * 1858
    assert workbytes.solve_bytes(4492125, 27, 33, 10) == 33_385_473_000


def test_bytes_hpcg27_104_single_solve_equal_the_hand_count():
    # N = 104^3 = 1,124,864, 27 diagonals, 151 iterations, replacement every 10:
    # operator 27 x (151 + 2 + 4 x 15) = 5,751; vectors 17 x 151 + 2 x 62 = 2,691.
    assert workbytes.solve_bytes(1124864, 27, 151, 10) == 4 * 1124864 * 8442
    assert workbytes.solve_bytes(1124864, 27, 151, 10) == 37_984_407_552


def test_bytes_batch_read_the_operator_once_per_iteration_for_all_lanes():
    # lanes [53, 54, 52, 53]: operator 125 x (54 + 2 + 20) = 9,500;
    # vectors 17 x 212 + 2 x (4 x 22) = 3,780
    assert workbytes.solve_bytes(85184, 125, [53, 54, 52, 53], 10) == 4 * 85184 * 13280


def test_bytes_do_not_depend_on_the_kernel_path():
    # the same operator and method under another engine or layout: the same count
    cfg = json.loads((BENCH / "configs" / "hpcg27_104.json").read_text())
    st = stencil.from_config(cfg["operator"])
    counts = set()
    for solver in ({}, {"engine": "jnp"}, {"method": "h3", "shards": 4}):
        c = dict(cfg, solver={**cfg["solver"], **solver})
        record = runner.RunRecord(
            cell=manifest.Cell("x", "hpcg27_104", "single", 1, c, {}, (), ()), stencil=st,
            rhs_per_call=1, setup={}, setup_s=0.0, window_s=1.0, latencies_s=np.ones(2),
            iterations=np.array([[151], [150]]), device_kind="TPU v5 lite", trace=None,
            traced_calls=2)
        counts.add(record.algorithmic_bytes())
    assert counts == {workbytes.solve_bytes(st.n, 27, 151, 10)
                      + workbytes.solve_bytes(st.n, 27, 150, 10)}


def test_peak_table_knows_v5e_and_refuses_other_devices():
    assert peaks.hbm_bytes_per_s("TPU v5 lite") == 819e9
    with pytest.raises(KeyError, match="no HBM peak"):
        peaks.hbm_bytes_per_s("cpu")


# --- operators and the reference ---------------------------------------------


@pytest.mark.parametrize("side,radius", [(7, 1), (6, 2)])
def test_operator_equals_the_programs_generator(side, radius):
    from repro.sparse import poisson27, poisson125

    st = stencil.Stencil(side=side, radius=radius, sigma=1.0)
    ref = (poisson27 if radius == 1 else poisson125)(side)
    assert st.offsets == tuple(ref.offsets)
    np.testing.assert_array_equal(np.asarray(stencil.build(st)), np.asarray(ref.data))


def test_hpcg_operator_has_26_on_the_diagonal_and_minus_1_off_it():
    st = stencil.from_config({"kind": "box_stencil", "side": 5, "radius": 1, "diagonal": 26.0})
    data = np.asarray(stencil.build(st))
    assert st.n_diags == 27
    np.testing.assert_array_equal(data[st.offsets.index(0)], np.full(st.n, 26.0))
    off = np.delete(data, st.offsets.index(0), axis=0)
    assert set(np.unique(off)) == {-1.0, 0.0}
    # an interior row has all 26 neighbours; a corner row 7
    centre = 2 + 2 * 5 + 2 * 25
    assert (off[:, centre] == -1).sum() == 26 and (off[:, 0] == -1).sum() == 7
    x = np.random.default_rng(5).standard_normal(st.n)
    dense = np.zeros(st.n)
    for j, o in enumerate(st.offsets):
        idx = np.arange(st.n) + o
        ok = (idx >= 0) & (idx < st.n)
        dense[ok] += data[j][ok].astype(np.float64) * x[idx[ok]]
    np.testing.assert_allclose(stencil.apply_f64(st, x), dense, rtol=1e-13, atol=1e-12)


@pytest.mark.parametrize("op", [
    {"kind": "box_stencil", "side": 5, "radius": 1},
    {"kind": "box_stencil", "side": 5, "radius": 1, "sigma": 1.0, "diagonal": 26.0},
])
def test_a_stencil_states_exactly_one_centre(op):
    with pytest.raises(ValueError, match="exactly one"):
        stencil.from_config(op)


@pytest.mark.parametrize("side,radius", [(7, 1), (6, 2)])
def test_reference_operator_equals_the_dia_product(side, radius):
    st = stencil.Stencil(side=side, radius=radius, sigma=1.0)
    data = np.asarray(stencil.build(st), dtype=np.float64)
    x = np.random.default_rng(3).standard_normal(st.n)
    dense = np.zeros(st.n)
    for j, o in enumerate(st.offsets):
        idx = np.arange(st.n) + o
        ok = (idx >= 0) & (idx < st.n)
        dense[ok] += data[j][ok] * x[idx[ok]]
    np.testing.assert_allclose(stencil.apply_f64(st, x), dense, rtol=1e-13, atol=1e-12)
    y32 = np.asarray(stencil.spmv(data.astype(np.float32), st.offsets, x.astype(np.float32)))
    np.testing.assert_allclose(y32, dense, rtol=0, atol=1e-5 * np.abs(dense).max())


# --- the result line and the entry point --------------------------------------


def test_result_line_has_exactly_the_keys(tmp_path):
    root = small_root(tmp_path)
    line = run_small(root, "hpcg27_104.single")
    assert list(line) == ["correct", "attempted", "failed", "metrics", "device", "checks"]
    assert line["correct"] is True and line["failed"] == 0 and line["attempted"] > 0
    assert set(line["metrics"]) == {"solve_ms", "solve_p95_ms", "setup_s"}
    assert all(set(v) == {"value", "unit"} for v in line["metrics"].values())
    assert set(line["device"]) == {"platform", "kind", "count", "memory_peak_bytes"}
    assert set(line["checks"]) == {"max_true_rel_residual", "unconverged"}
    assert all(set(c) == {"value", "limit"} for c in line["checks"].values())


def test_traced_result_line_adds_breakdown_and_busy_time(tmp_path, monkeypatch):
    import jax

    # a CPU run has no TPU planes: reduce the hand-made trace in their place
    monkeypatch.setattr(tracing, "load_xplane", lambda path: _fixture_planes())
    monkeypatch.setattr(tracing, "find_xplane", lambda d: d)
    monkeypatch.setitem(peaks.HBM_BYTES_PER_S, jax.devices()[0].device_kind, 1e12)
    root = small_root(tmp_path)
    line = run_small(root, "hpcg27_104.single", trace=True)
    assert list(line) == ["correct", "attempted", "failed", "metrics", "device", "breakdown",
                          "checks"]
    assert set(line["metrics"]) == {"iters.solve", "roofline.solve", "device_idle.solve",
                                    "setup.import_s", "setup.warm_s"}
    assert line["device"]["busy_s"] == pytest.approx(5700e-9)
    assert line["device"]["window_s"] == pytest.approx(10000e-9)
    assert line["metrics"]["device_idle.solve"]["value"] == pytest.approx(43.0)
    assert set(line["breakdown"]) == {"device_ops", "idle_gaps"}
    assert not (root / "bench_out" / "trace").exists() or not any(
        (root / "bench_out" / "trace").rglob("*.pb"))


def test_run_py_exits_nonzero_without_a_tpu(tmp_path):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("XLA_FLAGS", None)
    proc = subprocess.run(
        [sys.executable, str(BENCH / "run.py"), "--workload", "hpcg27_104.single",
         "--seed", "2147483659", "--seconds", "1", "--trace", "0"],
        capture_output=True, text=True, timeout=300, env=env, cwd=ROOT)
    assert proc.returncode != 0
    assert proc.stdout.strip() == ""
    assert "needs a TPU" in proc.stderr


def test_run_py_exits_nonzero_beside_only_its_own_files(tmp_path):
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path / "BENCHMARK.json")
    shutil.copytree(BENCH, tmp_path / "bench", ignore=shutil.ignore_patterns("__pycache__"))
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run(
        [sys.executable, "bench/run.py", "--workload", "hpcg27_104.single", "--seed", "1",
         "--seconds", "1", "--trace", "0"],
        capture_output=True, text=True, timeout=300, env=env, cwd=tmp_path)
    assert proc.returncode != 0 and proc.stdout.strip() == ""
    assert "cannot import the program" in proc.stderr
