#!/usr/bin/env python3
"""The control of a cell's check: a run whose answers must read ``correct`` false.

    python3 bench/control.py --workload hpcg27_104.single --seeds 5,6,7 --seconds 3

Runs the cell as ``bench/run.py`` does, on the chip and at the cell's own
size, with the system under test replaced by the benchmark's own plain
Jacobi PCG (``harness/reference.py``), the operator and every vector in
bfloat16, the precision below the configuration's float32.

One process runs every seed; each prints its result line, and the last
line is a JSON summary with the smallest reading of each check. The
benchmark's own runs never run this.
"""
import argparse
import json
import sys
import time
from pathlib import Path

HERE = Path(__file__).resolve().parent
ROOT = HERE.parent


def reference_solver(dtype):
    """A solver factory: the reference PCG in ``dtype`` in the program's place."""

    def factory(cell, st, data):
        from functools import partial

        import jax

        from harness import reference

        d = data.astype(dtype)
        cfg = cell.config["solver"]
        f = partial(reference.pcg, offsets=st.offsets, rtol=cfg["rtol"], maxiter=cfg["maxiter"])
        run = jax.jit(f if cell.traffic["rhs_per_call"] == 1 else jax.vmap(f, in_axes=(None, 0)))

        def solve(b):
            x, it, ok = run(d, b)
            return x.astype(b.dtype), it, ok

        return solve

    return factory


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True, help="comma-separated seeds")
    ap.add_argument("--seconds", type=float, default=3.0)
    args = ap.parse_args(argv)

    sys.path.insert(0, str(HERE))
    sys.path.insert(0, str(ROOT / "src"))
    from harness import manifest, runner

    cell = manifest.load_cell(args.workload, ROOT)
    import jax
    import jax.numpy as jnp

    runner.use_compile_cache(ROOT)
    if jax.devices()[0].platform != "tpu" or len(jax.devices()) < cell.chips:
        print("control: needs a TPU with the cell's chips", file=sys.stderr)
        return 1
    factory = reference_solver(jnp.bfloat16)
    readings = []
    for seed in (int(s) for s in args.seeds.split(",")):
        line = runner.run_cell(cell, seed=seed, seconds=args.seconds, trace=False,
                               t_start=time.perf_counter(), setup={}, solver_factory=factory,
                               root=ROOT)
        runner.emit(line)
        readings.append(line)
    summary = {"control": "reference_bf16", "workload": cell.name,
               "correct": [r["correct"] for r in readings],
               "smallest": {k: min(r["checks"][k]["value"] for r in readings)
                            for k in readings[0]["checks"]}}
    print(json.dumps(summary), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
