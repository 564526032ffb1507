"""Find a cell, its configuration, traffic mix and metric readers by name.

Everything that belongs to one cell, configuration, traffic mix or metric
sits in a file of its own under ``bench/``, named by the name that
``BENCHMARK.json`` gives it:

* ``workloads/<cell>.json``: the cell's configuration, traffic and chips
  (they must agree with the cell's entry in ``BENCHMARK.json``);
* ``configs/<config>.json``: the operator, the solver settings and the
  limits the check holds the answers to;
* ``traffic/<traffic>.json``: the parameters the one closed-loop generator
  reads;
* ``metrics/<metric>.py``: a reader with ``read(run) -> float | None``.

A new cell, configuration, traffic mix or metric is added by adding files
and entries; no file that is already there changes.
"""
from __future__ import annotations

import importlib.util
import json
from dataclasses import dataclass
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]


class ManifestError(Exception):
    pass


@dataclass(frozen=True)
class Metric:
    name: str
    unit: str


@dataclass(frozen=True)
class Cell:
    name: str
    config_name: str
    traffic_name: str
    chips: int
    config: dict
    traffic: dict
    end_to_end: tuple[Metric, ...]
    per_layer: tuple[Metric, ...]


def _read_json(path: Path) -> dict:
    try:
        with open(path) as f:
            return json.load(f)
    except FileNotFoundError:
        raise ManifestError(f"missing {path}") from None
    except json.JSONDecodeError as e:
        raise ManifestError(f"{path} is not valid JSON: {e}") from None


def _reports(metric: dict, cell: str, by_name: dict) -> bool:
    """Whether ``cell`` reports ``metric`` (``workloads`` key, else its ``moves``)."""
    if "workloads" in metric:
        return cell in metric["workloads"]
    moves = metric.get("moves")
    if moves is None:
        return True
    return moves in by_name and _reports(by_name[moves], cell, by_name)


def load_cell(name: str, root: Path = ROOT) -> Cell:
    root = Path(root)
    manifest = _read_json(root / "BENCHMARK.json")
    bench = root / "bench"
    entries = {w["name"]: w for w in manifest["workloads"]}
    if name not in entries:
        raise ManifestError(f"no workload {name!r} in BENCHMARK.json (have {sorted(entries)})")
    spec = _read_json(bench / "workloads" / f"{name}.json")
    for key in ("config", "traffic", "chips"):
        if spec.get(key) != entries[name].get(key):
            raise ManifestError(
                f"workload {name!r}: {key} is {spec.get(key)!r} in bench/workloads/{name}.json "
                f"but {entries[name].get(key)!r} in BENCHMARK.json")
    e2e = {m["name"]: m for m in manifest["end_to_end"]}
    return Cell(
        name=name,
        config_name=spec["config"],
        traffic_name=spec["traffic"],
        chips=int(spec["chips"]),
        config=_read_json(bench / "configs" / f"{spec['config']}.json"),
        traffic=_read_json(bench / "traffic" / f"{spec['traffic']}.json"),
        end_to_end=tuple(Metric(m["name"], m["unit"])
                         for m in manifest["end_to_end"] if _reports(m, name, e2e)),
        per_layer=tuple(Metric(m["name"], m["unit"])
                        for m in manifest["per_layer"] if _reports(m, name, e2e)),
    )


def load_reader(metric: str, root: Path = ROOT):
    """The ``read`` function of ``bench/metrics/<metric>.py``."""
    path = Path(root) / "bench" / "metrics" / f"{metric}.py"
    if not path.is_file():
        raise ManifestError(f"no reader for metric {metric!r}: missing {path}")
    spec = importlib.util.spec_from_file_location(f"bench_metric_{metric.replace('.', '_')}", path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    if not callable(getattr(module, "read", None)):
        raise ManifestError(f"{path} defines no read(run)")
    return module.read
