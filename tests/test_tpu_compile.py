"""The main-path kernels compile for a TPU v5e at deployment widths.

Nothing runs: each kernel is lowered and compiled for a chip that is
described (``v5e:2x2``), not attached, at the widths ``chip_smoke.py``
solves — the 27-point stencil on a 104^3 grid (row tile 11264) and the
125-point stencil on a 44^3 grid (row tile 4096). Each compiled program
must contain the kernel (``tpu_custom_call``), named by its package so a
profile of the chip finds it under that name. The persistent compilation
cache is off around these compiles: an entry written for a described chip
cannot be read back without one.

The last tests compile the whole padded ``pallas`` solve: at HPCG's 104^3
shapes they count the copies XLA puts into its loops (each recurrence
vector must live in one buffer for the whole solve), and in a plan's
program they count the copies of the operator it holds as a constant.
"""
import os
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

import repro

from repro.core.pipecg import _padded_length, _padded_tile, _pipecg_impl
from repro.core.preconditioners import JacobiPC
from repro.kernels.common import LANE, ceil_to, sum_dot_partials
from repro.kernels.fused_dot.kernel import TILE_ROWS as DOT_ROWS
from repro.kernels.fused_dot.kernel import fused_dots_padded
from repro.kernels.fused_iter import fused_iter_tile
from repro.kernels.fused_iter.kernel import fused_iter_padded
from repro.kernels.fused_vma.kernel import fused_vma_dots_padded
from repro.kernels.spmv_dia.kernel import spmv_dia_padded
from repro.sparse import poisson27
from repro.sparse.formats import DIAMatrix
from repro.sparse.stencil import stencil_offsets

# (stencil radius, grid side, expected row tile)
CASES = {"27pt-104": (1, 104, 11264), "125pt-44": (2, 44, 4096)}


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        desc = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    before = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield desc
    jax.config.update("jax_enable_compilation_cache", before)
    compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _case(name):
    radius, side, tile = CASES[name]
    offsets = tuple(stencil_offsets(3, side, radius))
    n = side**3
    bw = max(abs(o) for o in offsets)
    return offsets, n, bw, tile


def _assert_kernel(name, fn, *shapes):
    hlo = jax.jit(fn).lower(*shapes).compile().as_text()
    kernels = [line for line in hlo.splitlines() if "tpu_custom_call" in line]
    assert kernels and all(f"%{name}" in line for line in kernels), kernels


def _shape(sharding, shape):
    return jax.ShapeDtypeStruct(shape, jnp.float32, sharding=sharding)


@pytest.mark.parametrize("case", sorted(CASES))
def test_spmv_dia(one_chip, case):
    offsets, n, bw, tile = _case(case)
    assert _padded_tile("pallas", bw, None) == tile
    n_pad = _padded_length(n, tile)
    _assert_kernel("spmv_dia", lambda d, x: spmv_dia_padded(d, offsets, x, tile=tile, interpret=False),
                   _shape(one_chip, (len(offsets), n_pad)), _shape(one_chip, (n_pad,)))


@pytest.mark.parametrize("case", sorted(CASES))
def test_fused_iter(one_chip, case):
    offsets, n, bw, tile = _case(case)
    assert fused_iter_tile(bw) == tile
    n_pad = ceil_to(n, tile)
    vec = _shape(one_chip, (n_pad,))
    scalar = _shape(one_chip, ())

    def step(data, *v):
        return fused_iter_padded(data, offsets, v[:9], v[9], v[10], v[11],
                                 tile=tile, interpret=False)

    _assert_kernel("fused_iter", step, _shape(one_chip, (len(offsets), n_pad)), *[vec] * 10, scalar, scalar)


@pytest.mark.parametrize("case", sorted(CASES))
def test_fused_vma(one_chip, case):
    _, n, bw, tile = _case(case)
    rows = _padded_length(n, _padded_tile("pallas", bw, None)) // LANE
    view = _shape(one_chip, (rows, LANE))
    scalar = _shape(one_chip, ())

    def core(*v):
        return fused_vma_dots_padded(v[:10], v[10], v[11], v[12], interpret=False)

    _assert_kernel("fused_vma", core, *[view] * 11, scalar, scalar)


@pytest.mark.parametrize("case", sorted(CASES))
def test_fused_dot(one_chip, case):
    _, n, _, _ = _case(case)
    view = _shape(one_chip, (ceil_to(n, DOT_ROWS * LANE) // LANE, LANE))
    _assert_kernel("fused_dot", lambda r, u, w: sum_dot_partials(fused_dots_padded(r, u, w, interpret=False)),
                   view, view, view)


def _loop_computations(hlo):
    """{name: instruction lines} of every while body and cond branch."""
    comps, cur = {}, None
    for line in hlo.splitlines():
        head = re.match(r"^(?:ENTRY )?%([\w.\-]+) .*\{$", line)
        if head:
            cur = comps.setdefault(head.group(1), [])
        elif line.startswith("}"):
            cur = None
        elif cur is not None:
            cur.append(line)
    names = []
    for line in hlo.splitlines():
        if " while(" in line:
            names += re.findall(r"body=%([\w.\-]+)", line)
        for branches in re.findall(r"branch_computations=\{([^}]*)\}", line):
            names += [b.strip().lstrip("%") for b in branches.split(",")]
        names += re.findall(r"(?:true|false)_computation=%([\w.\-]+)", line)
    return {name: comps[name] for name in names}


@pytest.mark.parametrize("replace_every", [10, 0])
def test_pallas_solve_loops_copy_no_vectors(one_chip, monkeypatch, replace_every):
    """HPCG's 27-point operator at 104^3, Jacobi, the ``pallas`` core, the
    operator an argument: no while body or cond branch copies a vector,
    and ``fused_vma`` updates its vectors in place, in HBM."""
    offsets, n, bw, tile = _case("27pt-104")
    n_pad = _padded_length(n, tile)
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")  # compiled kernels

    def solve(A, b, M, x0, atol, rtol):
        return _pipecg_impl(A, b, M, x0, atol, rtol, 500, "pallas", "auto", replace_every, None, None)

    vec, scalar = _shape(one_chip, (n,)), _shape(one_chip, ())
    A = DIAMatrix(_shape(one_chip, (len(offsets), n)), offsets, n)
    hlo = jax.jit(solve).lower(A, vec, JacobiPC(vec), vec, scalar, scalar).compile().as_text()

    loops = _loop_computations(hlo)
    assert any("fused_vma" in line for lines in loops.values() for line in lines)
    vector = re.compile(rf"f32\[({n_pad // LANE},{LANE}|{n_pad})\]")
    copies = [line for lines in loops.values() for line in lines
              if re.search(r" (copy|copy-start)\(", line) and vector.search(line.split("=", 1)[1])]
    assert copies == []
    calls = [line for line in hlo.splitlines() if "custom-call(" in line and "%fused_vma" in line]
    assert calls and all("output_to_operand_aliasing" in line for line in calls), calls
    # its nine vector results (the loop's state) in HBM, not VMEM (S(1))
    results = [re.findall(rf"f32\[{n_pad // LANE},{LANE}\]\{{[^}}]*\}}", line.split("custom-call(")[0])
               for line in calls]
    assert all(len(r) == 9 and not any("S(1)" in v for v in r) for r in results), results


def test_plan_program_holds_its_operator_once(one_chip, monkeypatch):
    """A plan closes over its operator, so its compiled solve holds the
    padded diagonals as a constant: once, not again in a loop or branch."""
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")  # compiled kernels
    A = poisson27(48)
    plan = repro.plan(A, method="pipecg", engine="pallas", M="jacobi", atol=0.0, rtol=3e-6,
                      maxiter=2000, replace_every=10)
    vec, scalar = _shape(one_chip, (A.n,)), _shape(one_chip, ())
    hlo = jax.jit(plan._inner).lower(vec, vec, scalar, scalar).compile().as_text()
    assert len(re.findall(rf"= f32\[{A.n_diags},\d+\]\S* constant\(", hlo)) == 1
