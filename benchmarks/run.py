"""Benchmark driver: one section per paper table/figure.

Prints ``name,us_per_call,derived`` CSV rows.

  bench_convergence     — convergence equivalence (correctness premise)
  bench_solver_methods  — Fig. 6/7: method comparison across matrices
  bench_kernels         — §V-B: kernel fusion effect (time + HBM traffic)
  bench_overlap         — h1..h4/pl2/pl3 collective schedules + time/iter
                          (needs >= 2 devices; JSON-capable, CI-gated)
  bench_serve           — async serving tier: queue wait p50/p95, bucket
                          occupancy, programs compiled (JSON, CI-gated)
  bench_poisson         — Fig. 8: 125-pt Poisson + perf-model decomposition
  bench_roofline_table  — the 40-cell dry-run roofline (reads experiments/)

CLI (ReFrame-style harness):
  --only SECTION        run one section; repeatable (``--only kernels
                        --only solver_methods``); default is all sections
  --tiny                shrink problem sizes (CI smoke)
  --json-dir DIR        sections that support JSON write
                        ``DIR/BENCH_<section>.json`` records — env-
                        fingerprinted, gate-able by tools/bench_gate.py
  --json PATH           legacy single-file form (kernels section only)
  --obs-dump PATH       run with observability on and write the collected
                        spans + metrics snapshot as one JSON artifact

CI runs ``--tiny --json-dir bench_out --only kernels --only
solver_methods --only overlap --obs-dump bench_out/obs_dump.json`` then gates
``bench_out`` against the committed ``benchmarks/trajectory/`` with
``tools/bench_gate.py`` — a "faster" claim that regresses the trajectory
beyond the noise band fails the build.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import traceback


def main(argv=None) -> None:
    from . import (
        bench_convergence,
        bench_kernels,
        bench_overlap,
        bench_poisson,
        bench_roofline_table,
        bench_serve,
        bench_solver_methods,
    )

    sections = [
        ("convergence", bench_convergence.main, {}),
        ("solver_methods", bench_solver_methods.main, {"json_path": True, "tiny": True}),
        ("kernels", bench_kernels.main, {"json_path": True, "tiny": True}),
        ("overlap", bench_overlap.main, {"json_path": True}),
        ("serve", bench_serve.main, {"json_path": True, "tiny": True}),
        ("poisson", bench_poisson.main, {}),
        ("roofline_table", bench_roofline_table.main, {}),
    ]
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--only", choices=[s[0] for s in sections], action="append",
                    default=None, help="run a single section (repeatable)")
    ap.add_argument("--tiny", action="store_true",
                    help="shrink problem sizes (CI smoke)")
    ap.add_argument("--json", metavar="PATH", default=None,
                    help="legacy: single JSON record path (kernels section)")
    ap.add_argument("--json-dir", metavar="DIR", default=None,
                    help="write BENCH_<section>.json per JSON-capable section")
    ap.add_argument("--obs-dump", metavar="PATH", default=None,
                    help="enable observability; dump spans+metrics JSON here")
    args = ap.parse_args(argv)
    from repro.launch.env import enable_compile_cache

    enable_compile_cache()

    if args.obs_dump:
        from repro.obs import clear_spans, enable, reset_metrics

        enable()
        clear_spans()
        reset_metrics()
    if args.json_dir:
        os.makedirs(args.json_dir, exist_ok=True)

    print("name,us_per_call,derived")
    failed = []
    for name, fn, accepts in sections:
        if args.only is not None and name not in args.only:
            continue
        kwargs = {}
        if accepts.get("json_path"):
            if args.json_dir:
                kwargs["json_path"] = os.path.join(args.json_dir, f"BENCH_{name}.json")
            elif args.json and name == "kernels":
                kwargs["json_path"] = args.json
        if accepts.get("tiny") and args.tiny:
            kwargs["tiny"] = True
        try:
            fn(**kwargs)
        except Exception:
            failed.append(name)
            traceback.print_exc()
            print(f"bench/{name}/FAILED,0,", flush=True)

    if args.obs_dump:
        from repro.obs import snapshot, spans_to_dicts

        with open(args.obs_dump, "w") as f:
            json.dump({"metrics": snapshot(), "spans": spans_to_dicts()}, f, indent=2)
        print(f"bench/obs_dump,0.0,{args.obs_dump}", flush=True)

    if failed:
        sys.exit(1)


if __name__ == "__main__":
    main()
