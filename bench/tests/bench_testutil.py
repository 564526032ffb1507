"""Helpers shared by the benchmark's tests: small copies of the cells, run on CPU."""
from __future__ import annotations

import json
import shutil
import sys
import time
from pathlib import Path

BENCH = Path(__file__).resolve().parents[1]
ROOT = BENCH.parent
for p in (str(BENCH), str(ROOT / "src")):
    if p not in sys.path:
        sys.path.insert(0, p)

SMALL_SIDE = {1: 10, 2: 8}  # grid side by stencil radius: N = 1000 and 512


def small_root(tmp: Path) -> Path:
    """A copy of the benchmark's data files with every grid cut to a CPU size."""
    shutil.copy(ROOT / "BENCHMARK.json", tmp / "BENCHMARK.json")
    for sub in ("configs", "workloads", "traffic", "metrics"):
        shutil.copytree(BENCH / sub, tmp / "bench" / sub)
    for path in (tmp / "bench" / "configs").glob("*.json"):
        cfg = json.loads(path.read_text())
        cfg["operator"]["side"] = SMALL_SIDE[cfg["operator"]["radius"]]
        path.write_text(json.dumps(cfg))
    return tmp


def add_cell(root: Path, name: str, config: str, traffic: dict) -> None:
    """Add a one-chip cell ``name`` to the copy at ``root`` by adding files and entries
    only, as a later change would: its traffic file, its cell file and its manifest
    entry."""
    traffic_name = name.split(".", 1)[1]
    (root / "bench" / "traffic" / f"{traffic_name}.json").write_text(json.dumps(traffic))
    (root / "bench" / "workloads" / f"{name}.json").write_text(
        json.dumps({"config": config, "traffic": traffic_name, "chips": 1}))
    m = json.loads((root / "BENCHMARK.json").read_text())
    m["workloads"].append({"name": name, "config": config, "traffic": traffic_name,
                           "chips": 1, "why": "test"})
    (root / "BENCHMARK.json").write_text(json.dumps(m))


def run_small(root: Path, cell_name: str, *, seed=11, seconds=0.3, trace=False,
              solver_factory=None) -> dict:
    """One run of a cell from ``root`` on whatever devices JAX has, no chip check."""
    from harness import manifest, runner

    cell = manifest.load_cell(cell_name, root)
    kw = {} if solver_factory is None else {"solver_factory": solver_factory}
    return runner.run_cell(cell, seed=seed, seconds=seconds, trace=trace,
                           t_start=time.perf_counter(), setup={"imports": 0.0}, root=root, **kw)
