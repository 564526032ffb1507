"""Shared benchmark helpers. Every benchmark prints ``name,us_per_call,derived`` CSV.

Sections that support ``--json`` additionally write ``BENCH_<topic>.json``
records through :func:`bench_record`/:func:`write_bench_json`, which stamp
every record with the environment fingerprint (backend, device kind, x64,
JAX version — ``repro.obs.env_fingerprint``) and a schema version, so two
trajectory points are only ever compared when they are comparable
(``tools/bench_gate.py`` enforces this).

Determinism: all synthetic problem data is derived from :func:`seed_key`
— a name-keyed PRNG, not an ambient counter — so re-running a benchmark
reproduces bit-identical inputs and the convergence-iteration columns of
the trajectory are stable across runs and machines.
"""
from __future__ import annotations

import json
import time
import zlib
from typing import Optional

import jax

BENCH_SCHEMA = 2  # bump when record layout changes incompatibly


def seed_key(name: str, i: int = 0):
    """Deterministic PRNGKey for a named benchmark input.

    Keyed on a stable hash of ``name`` (crc32, not Python's salted
    ``hash``) folded with ``i`` — the same (name, i) yields the same data
    in every process, which is what makes trajectory points comparable.
    """
    return jax.random.fold_in(jax.random.PRNGKey(zlib.crc32(name.encode()) & 0x7FFFFFFF), i)


def structural_bytes_per_elem(core: str, n_diags: int, elem_bytes: int = 4) -> Optional[float]:
    """Per-iteration HBM bytes/row each core moves BY CONSTRUCTION.

    jnp        — separate passes: SPMV (band + x + y) + 8 triads
                 (2 reads, 1 write each) + PC (3) + 3 dots (2 reads each).
    pallas     — SPMV kernel (band + x + y) + one fused VMA kernel
                 (11 reads + 9 writes).
    fused_iter — ONE kernel: band + m + 8 state vecs + inv_diag reads,
                 9 vector writes (dot partials are noise).

    Returns None for cores the model does not cover (plug-ins).
    """
    vec = {
        "jnp": (n_diags + 2) + 8 * 3 + 3 + 3 * 2,
        "pallas": (n_diags + 2) + (11 + 9),
        "fused_iter": n_diags + 10 + 9,
    }.get(core)
    return None if vec is None else vec * float(elem_bytes)


# Published HBM bandwidth per chip, keyed by ``jax.Device.device_kind``.
# "TPU v5 lite": 819 GB/s — Google Cloud documentation, "TPU v5e".
HBM_PEAK_BYTES_PER_S = {"TPU v5 lite": 819e9}


def hbm_peak(device_kind: str) -> Optional[float]:
    """Published HBM bytes/s of ``device_kind``; None for a device the
    table does not hold (CPU included) — its share is "not measured"."""
    return HBM_PEAK_BYTES_PER_S.get(device_kind)


def timeit_call(fn, *args, warmup: int = 2, iters: int = 5) -> float:
    """Median wall time (us) of fn(*args) with block_until_ready."""
    for _ in range(warmup):
        r = fn(*args)
        jax.block_until_ready(r)
    times = []
    for _ in range(iters):
        t0 = time.perf_counter()
        r = fn(*args)
        jax.block_until_ready(r)
        times.append(time.perf_counter() - t0)
    times.sort()
    return times[len(times) // 2] * 1e6


def emit(name: str, us: float, derived: str = ""):
    print(f"{name},{us:.1f},{derived}", flush=True)


def bench_record(topic: str, **fields) -> dict:
    """A BENCH_<topic>.json skeleton: topic + schema + env fingerprint."""
    from repro.obs import env_fingerprint

    rec = {"bench": topic, "schema": BENCH_SCHEMA, "env": env_fingerprint()}
    rec.update(fields)
    return rec


def write_bench_json(path: str, record: dict) -> None:
    with open(path, "w") as f:
        json.dump(record, f, indent=2, sort_keys=True)
    emit(f"{record.get('bench', 'bench')}/json", 0.0, path)
