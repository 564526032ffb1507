"""The main-path kernels compile for a TPU v5e at deployment widths.

Nothing runs: each kernel is lowered and compiled for a chip that is
described (``v5e:2x2``), not attached, at the widths ``chip_smoke.py``
solves — the 27-point stencil on a 104^3 grid (row tile 11264) and the
125-point stencil on a 44^3 grid (row tile 4096). Each compiled program
must contain the kernel (``tpu_custom_call``), named by its package so a
profile of the chip finds it under that name. The persistent compilation
cache is off around these compiles: an entry written for a described chip
cannot be read back without one.
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.core.pipecg import _padded_length, _padded_tile
from repro.kernels.common import LANE, ceil_to, sum_dot_partials
from repro.kernels.fused_dot.kernel import TILE_ROWS as DOT_ROWS
from repro.kernels.fused_dot.kernel import fused_dots_padded
from repro.kernels.fused_iter import fused_iter_tile
from repro.kernels.fused_iter.kernel import fused_iter_padded
from repro.kernels.fused_vma.kernel import fused_vma_dots_padded
from repro.kernels.spmv_dia.kernel import spmv_dia_padded
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
