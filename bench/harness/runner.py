"""One run of one cell: set-up, the measured window, the check, the metrics.

The loop is closed: one caller sends a call, waits for its result with
``block_until_ready``, and sends the next, until ``seconds`` have passed;
the call that is in flight when they have passed completes and counts.
The right-hand sides come from a pool made on the device from the seed
during set-up, so the window copies nothing from the host; calls cycle
through the pool in order.

Set-up warms the cell's own shapes and nothing else. ``setup_s`` runs
from process start to the first timed call. A traced run profiles the
calls of the window's first ``TRACE_SECONDS`` (the host span
``bench.window``) and reads its per-layer metrics from them.

After the window the run is checked, by the benchmark's own reference
(``stencil.rel_residual``, float64 on the host): every call's
``converged`` flag, and the true relative residual of every right-hand
side of a sample of calls drawn from the seed. The limits are the
configuration's ``check``.
"""
from __future__ import annotations

import contextlib
import json
import math
import os
import random
import shutil
import sys
import time
from dataclasses import dataclass
from pathlib import Path

import numpy as np

from . import manifest, peaks, stencil, tracing, workbytes

CHECK_CALLS = 16   # calls whose answers are compared with the reference
WARM_CALLS = 2
# A traced run profiles the first TRACE_SECONDS of its window: traced for 20 s
# on a 2x2 TPU v5e host, the profiler kept only about half of chip 0's events.
TRACE_SECONDS = 5.0


def log(msg: str) -> None:
    print(f"bench: {msg}", file=sys.stderr, flush=True)


def use_compile_cache(root: Path) -> None:
    """JAX's persistent compilation cache: ``JAX_COMPILATION_CACHE_DIR`` when set,
    else ``.jax_cache/`` at the root of the checkout (a fixed path: the path is
    part of the cache's key). Every program is cached, however fast it compiled,
    so that a second run of a cell compiles nothing."""
    import jax

    jax.config.update("jax_compilation_cache_dir",
                      os.environ.get("JAX_COMPILATION_CACHE_DIR") or str(Path(root) / ".jax_cache"))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)


def seed_key(seed: int):
    """A PRNG key from any whole number (all 64 bits of it count)."""
    import jax

    seed = int(seed) % 2**64
    return jax.random.fold_in(jax.random.key(seed & 0xFFFFFFFF), np.uint32(seed >> 32))


def make_pool(key, st: stencil.Stencil, rhs_per_call: int, pool_calls: int):
    """``pool_calls`` standard-normal right-hand sides (or batches), made on the device."""
    import jax

    shape = (st.n,) if rhs_per_call == 1 else (rhs_per_call, st.n)

    def gen(k):
        return tuple(jax.random.normal(jax.random.fold_in(k, i), shape, st.dtype)
                     for i in range(pool_calls))

    return jax.block_until_ready(jax.jit(gen)(key))


def program_solver(cell: manifest.Cell, st: stencil.Stencil, data):
    """The system under test: ``repro.plan`` on the operator, as a user calls it."""
    import repro
    from repro.sparse import DIAMatrix

    plan = repro.plan(DIAMatrix(data, st.offsets, st.n), **cell.config["solver"])
    entry = plan.solve_batched if cell.traffic["rhs_per_call"] > 1 else plan.solve

    def solve(b):
        res = entry(b)
        return res.x, res.iterations, res.converged

    return solve


class _CompileCounter:
    """Counts traces and program compiles (or cache loads) the process makes."""

    def __init__(self):
        import jax

        self.traces = 0
        self.programs = 0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def close(self):
        import jax

        jax.monitoring.unregister_event_duration_listener(self._on)

    def _on(self, event, duration, **_):
        if event == "/jax/core/compile/jaxpr_trace_duration":
            self.traces += 1
        elif event == "/jax/core/compile/backend_compile_duration":
            self.programs += 1


@dataclass
class RunRecord:
    """What one run measured; the metric readers read it."""

    cell: manifest.Cell
    stencil: stencil.Stencil
    rhs_per_call: int
    setup: dict
    setup_s: float
    window_s: float
    latencies_s: np.ndarray       # per call
    iterations: np.ndarray        # (calls, rhs_per_call)
    device_kind: str
    trace: tracing.TraceSummary | None
    traced_calls: int | None      # calls inside the profiled part of the window

    @property
    def calls(self) -> int:
        return len(self.latencies_s)

    @property
    def rhs(self) -> int:
        return self.calls * self.rhs_per_call

    def algorithmic_bytes(self) -> int:
        """HBM bytes the traced calls need (``workbytes``), all chips together."""
        every = int(self.cell.config["solver"].get("replace_every") or 0)
        itemsize = np.dtype(self.stencil.dtype).itemsize
        return sum(workbytes.solve_bytes(self.stencil.n, self.stencil.n_diags, row, every,
                                         itemsize) for row in self.iterations[:self.traced_calls])

    def hbm_roofline_pct(self) -> float | None:
        """Algorithmic bytes over (device-busy time x HBM peak), per chip, averaged."""
        if self.trace is None:
            return None
        peak = peaks.hbm_bytes_per_s(self.device_kind)
        per_chip = self.algorithmic_bytes() / len(self.trace.busy_s)
        shares = [per_chip / (busy * peak) for busy in self.trace.busy_s if busy > 0]
        return 100.0 * sum(shares) / len(shares) if shares else None


def run_cell(cell: manifest.Cell, *, seed: int, seconds: float, trace: bool,
             t_start: float, setup: dict, solver_factory=program_solver,
             root: Path = manifest.ROOT) -> dict:
    """Run ``cell`` once; returns the result line (see ``bench/run.py``)."""
    import jax

    counter = _CompileCounter()
    devices = jax.devices()[: cell.chips]
    st = stencil.from_config(cell.config["operator"])
    k = int(cell.traffic["rhs_per_call"])
    pool_calls = int(cell.traffic["pool_calls"])

    t = time.perf_counter()
    data = jax.block_until_ready(stencil.build(st))
    setup["operator"] = time.perf_counter() - t
    t = time.perf_counter()
    pool = make_pool(seed_key(seed), st, k, pool_calls)
    setup["pool"] = time.perf_counter() - t
    t = time.perf_counter()
    solve = solver_factory(cell, st, data)
    for i in range(WARM_CALLS):
        jax.block_until_ready(solve(pool[i % pool_calls]))
    setup["warm"] = time.perf_counter() - t
    setup_s = time.perf_counter() - t_start

    trace_dir = Path(root) / "bench_out" / "trace" / cell.name
    if trace:
        shutil.rmtree(trace_dir, ignore_errors=True)
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        jax.profiler.start_trace(str(trace_dir), profiler_options=opts)
    span = jax.profiler.TraceAnnotation if trace else (lambda name: contextlib.nullcontext())

    rng = random.Random(seed)
    sample = []   # reservoir of (call, pool index, x)
    iters, conv, lat = [], [], []
    traced_calls = None
    traces0, programs0 = counter.traces, counter.programs
    window = span(tracing.WINDOW_SPAN)
    window.__enter__()
    t0 = time.perf_counter()
    deadline = t0 + seconds
    i = 0
    while True:
        b = pool[i % pool_calls]
        ts = time.perf_counter()
        with span("bench.call"):
            x, it, ok = solve(b)
        with span("bench.wait"):
            jax.block_until_ready(x)
        te = time.perf_counter()
        with span("bench.next"):
            lat.append(te - ts)
            iters.append(it)
            conv.append(ok)
            if len(sample) < CHECK_CALLS:
                sample.append((i, i % pool_calls, x))
            else:
                j = rng.randrange(i + 1)
                if j < CHECK_CALLS:
                    sample[j] = (i, i % pool_calls, x)
            i += 1
        if trace and traced_calls is None and te >= min(t0 + TRACE_SECONDS, deadline):
            window.__exit__(None, None, None)
            jax.profiler.stop_trace()
            traced_calls = i
        if te >= deadline:
            break
    window_s = te - t0
    in_window = (counter.traces - traces0, counter.programs - programs0)
    counter.close()

    stats = [d.memory_stats() or {} for d in devices]
    peak_bytes = max((s.get("peak_bytes_in_use", 0) for s in stats), default=0)
    iterations = np.asarray(jax.device_get(iters)).reshape(len(lat), k)
    converged = np.asarray(jax.device_get(conv)).reshape(len(lat), k)
    del iters, conv

    summary = None
    if trace:
        summary = tracing.summarize(tracing.load_xplane(tracing.find_xplane(trace_dir)),
                                    cell.chips)
        shutil.rmtree(trace_dir, ignore_errors=True)

    # --- the check, after the window, on the host ---
    t = time.perf_counter()
    limits = cell.config["check"]
    worst, bad = 0.0, set()
    for call, p, x in sorted(sample, key=lambda s: s[0]):
        xs = np.asarray(x).reshape(k, st.n)
        bs = np.asarray(pool[p]).reshape(k, st.n)
        for lane in range(k):
            rel = stencil.rel_residual(st, xs[lane], bs[lane])
            if not math.isfinite(rel):  # an answer with inf or nan in it
                rel = sys.float_info.max
            worst = max(worst, rel)
            if rel > limits["max_true_rel_residual"]:
                bad.add((call, lane))
    unconverged = int(np.sum(~converged))
    bad |= {(int(c), int(l)) for c, l in zip(*np.nonzero(~converged))}
    check_s = time.perf_counter() - t
    checks = {
        "max_true_rel_residual": {"value": worst, "limit": limits["max_true_rel_residual"]},
        "unconverged": {"value": unconverged, "limit": limits["unconverged"]},
    }
    correct = (len(lat) > 0 and len(sample) > 0
               and all(c["value"] <= c["limit"] for c in checks.values()))

    record = RunRecord(cell=cell, stencil=st, rhs_per_call=k, setup=dict(setup),
                       setup_s=setup_s, window_s=window_s, latencies_s=np.asarray(lat),
                       iterations=iterations, device_kind=devices[0].device_kind,
                       trace=summary, traced_calls=traced_calls)
    metrics = {}
    for m in (cell.per_layer if trace else cell.end_to_end):
        value = manifest.load_reader(m.name, root)(record)
        if value is None:
            log(f"metric {m.name}: nothing to read in this run")
            continue
        metrics[m.name] = {"value": float(value), "unit": m.unit}

    d0 = devices[0]
    device = {"platform": d0.platform, "kind": d0.device_kind, "count": len(jax.devices()),
              "memory_peak_bytes": int(peak_bytes)}
    line = {"correct": bool(correct), "attempted": record.rhs, "failed": len(bad),
            "metrics": metrics, "device": device}
    if summary is not None:
        device["busy_s"] = summary.mean_busy_s
        device["window_s"] = summary.window_s
        line["breakdown"] = {"device_ops": summary.device_ops, "idle_gaps": summary.idle_gaps}

    log("setup_phases_s " + " ".join(f"{k_}={v:.3f}" for k_, v in setup.items()))
    log(f"calls={record.calls} rhs={record.rhs} window_s={window_s:.3f} "
        f"median_call_ms={1e3 * float(np.median(lat)):.4f} "
        f"max_call_ms={1e3 * float(np.max(lat)):.4f} "
        f"iterations {dict(zip(*(v.tolist() for v in np.unique(iterations, return_counts=True))))}")
    log(f"traces_in_window={in_window[0]} programs_compiled_in_window={in_window[1]}")
    if summary is not None:
        log(f"trace: window_s={summary.window_s:.3f} busy_s={summary.busy_s} "
            f"program_runs={summary.module_runs} traced_calls={traced_calls} "
            f"collective_exposed_s={summary.collective_exposed_s}")
    log(f"check: {len(sample)} sampled calls ({len(sample) * k} answers) compared with the "
        f"float64 reference in {check_s:.2f} s; {len(bad)} answers wrong")
    line["checks"] = checks  # last key of the line
    for name, c in checks.items():
        log(f"check {name}={c['value']!r} limit={c['limit']!r} "
            f"{'ok' if c['value'] <= c['limit'] else 'FAILED'}")
    return line


def emit(line: dict) -> None:
    print(json.dumps(line, allow_nan=False), flush=True)
