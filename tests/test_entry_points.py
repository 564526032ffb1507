"""Entry points refuse to report what they did not run.

``chip_smoke.py`` exits non-zero off a TPU (naming the platform it found)
and when the package is not next to it; ``benchmarks.run`` exits non-zero
when a section fails, as the overlap bench does with fewer than 2 devices;
a distributed plan places each operator shard on its own device.
"""
import os
import shutil
import subprocess
import sys

from conftest import SUBPROCESS_ENV, run_multidevice

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _run(args, cwd, env=None):
    env = dict(SUBPROCESS_ENV if env is None else env)
    env["JAX_PLATFORMS"] = "cpu"
    return subprocess.run([sys.executable, *args], cwd=cwd, env=env,
                          capture_output=True, text=True, timeout=300)


class TestChipSmoke:
    def test_refuses_cpu(self):
        proc = _run(["chip_smoke.py"], REPO)
        assert proc.returncode != 0
        assert "needs a TPU" in proc.stderr and "'cpu'" in proc.stderr
        assert '"ok"' not in proc.stdout

    def test_refuses_without_package(self, tmp_path):
        shutil.copy(os.path.join(REPO, "chip_smoke.py"), tmp_path)
        env = {k: v for k, v in SUBPROCESS_ENV.items() if k != "PYTHONPATH"}
        proc = _run(["chip_smoke.py"], tmp_path, env)
        assert proc.returncode != 0
        assert '"ok"' not in proc.stdout


class TestBenchmarkRun:
    def test_failed_section_exits_nonzero(self):
        env = dict(SUBPROCESS_ENV, PYTHONPATH=os.path.join(REPO, "src"))
        proc = _run(["-m", "benchmarks.run", "--only", "overlap"], REPO, env)
        assert proc.returncode != 0
        assert "overlap needs >= 2 devices" in proc.stderr
        assert "bench/overlap/FAILED" in proc.stdout


_PLACEMENT = """
import jax, jax.numpy as jnp
import repro
from repro.sparse import poisson27, spmv
A = poisson27(8)
b = spmv(A, jnp.ones((A.n,)))
for method, sub in (("h3", None), ("h4", 2)):
    p = repro.plan(A, method=method, shards=4, sub=sub, atol=0.0, rtol=1e-5)
    devs = {s.device for s in p.sharded.data.addressable_shards}
    assert len(devs) == 4, (method, devs)
    assert {s.device for s in p._inv_sh.addressable_shards} == devs
    assert bool(p.solve(b).converged)
print("OK")
"""


class TestShardPlacement:
    def test_operator_shards_on_distinct_devices(self):
        assert "OK" in run_multidevice(_PLACEMENT, n_devices=4)
