#!/usr/bin/env python3
"""One run of one benchmark cell of the pipelined-CG solver, on TPU.

    python3 bench/run.py --workload hpcg27_104.single --seed 7 --seconds 20 --trace 0

``--workload`` names an entry of ``workloads`` in ``BENCHMARK.json``; the
harness finds the cell's files by that name (``bench/harness/manifest.py``).
With ``--trace 0`` the run reports the cell's end-to-end metrics, with
``--trace 1`` its per-layer metrics, read from a profiler trace of the
window's first 5 seconds. The last line of standard output is one JSON object with the
keys ``correct``, ``attempted``, ``failed``, ``metrics``, ``device``
(with ``--trace 1`` also ``breakdown``) and, last, ``checks``: each
number compared with the reference beside its limit. The same checks are
the last lines of standard error.

It exits non-zero, and prints no result, when JAX finds no TPU or fewer
chips than the cell asks for, or when the program under test (``src/``)
is not beside it. JAX's persistent compilation cache lives in
``JAX_COMPILATION_CACHE_DIR`` when that is set, else in ``.jax_cache/`` at
the root of the checkout.
"""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

HERE = Path(__file__).resolve().parent
ROOT = HERE.parent


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    sys.path.insert(0, str(HERE))
    from harness import manifest, runner

    try:
        cell = manifest.load_cell(args.workload, ROOT)
    except manifest.ManifestError as e:
        print(f"bench: {e}", file=sys.stderr)
        return 2

    import jax

    runner.use_compile_cache(ROOT)
    sys.path.insert(0, str(ROOT / "src"))
    try:
        import repro.plan  # noqa: F401  (the program under test)
    except ImportError as e:
        print(f"bench: cannot import the program under test from {ROOT / 'src'}: {e}",
              file=sys.stderr)
        return 2
    try:
        devices = jax.devices()
    except RuntimeError as e:
        print(f"bench: JAX found no usable backend: {e}", file=sys.stderr)
        return 1
    if devices[0].platform != "tpu":
        print(f"bench: needs a TPU; JAX's first device is {devices[0].platform!r} "
              f"({devices[0].device_kind})", file=sys.stderr)
        return 1
    if len(devices) < cell.chips:
        print(f"bench: {cell.name} needs {cell.chips} chips, JAX found {len(devices)}",
              file=sys.stderr)
        return 1

    setup = {"imports": time.perf_counter() - T_START}
    line = runner.run_cell(cell, seed=args.seed, seconds=args.seconds, trace=bool(args.trace),
                           t_start=T_START, setup=setup, root=ROOT)
    runner.emit(line)
    return 0


if __name__ == "__main__":
    sys.exit(main())
