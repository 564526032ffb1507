"""The check that decides ``correct``, shown to fail: the control (the reference
in bfloat16 in the program's place) and each fault a cell can have, planted
under the harness, at CPU sizes. The harness's look for a chip is skipped;
everything else of a run is driven as on the chip."""
from __future__ import annotations

import pytest

from bench_testutil import add_cell, run_small, small_root

from harness import runner

import control


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    root = small_root(tmp_path_factory.mktemp("bench"))
    # a batch cell, made from data files alone, to plant the batch's fault in
    add_cell(root, "hpcg27_104.batch4", "hpcg27_104", {"rhs_per_call": 4, "pool_calls": 4})
    return root


def _broken(fault):
    """A solver factory: the program, with ``fault`` planted in its answers."""

    def factory(cell, st, data):
        solve = runner.program_solver(cell, st, data)

        def broken(b):
            x, it, ok = solve(b)
            if fault == "state_unchanged":      # the solve hands back x0
                x = x * 0
            elif fault == "half_batch":         # lanes 2 and 3 never solved
                x = x.at[x.shape[0] // 2:].set(0.0)
            elif fault == "answer_altered":     # one entry changed where it is produced
                x = x.at[..., 0].add(1.0)
            return x, it, ok

        return broken

    return factory


def test_the_program_reads_correct(root):
    line = run_small(root, "hpcg27_104.single")
    assert line["correct"] and line["failed"] == 0


def test_control_reference_in_bfloat16_reads_not_correct(root):
    import jax.numpy as jnp

    line = run_small(root, "hpcg27_104.single", solver_factory=control.reference_solver(jnp.bfloat16))
    assert not line["correct"]
    assert line["checks"]["max_true_rel_residual"]["value"] > 3 * 1e-5


def test_reference_in_float32_reads_correct(root):
    import jax.numpy as jnp

    line = run_small(root, "hpcg27_104.single", solver_factory=control.reference_solver(jnp.float32))
    assert line["correct"]


@pytest.mark.parametrize("fault,cell", [
    ("state_unchanged", "hpcg27_104.single"),
    ("answer_altered", "hpcg27_104.single"),
    ("half_batch", "hpcg27_104.batch4"),
])
def test_a_planted_fault_reads_not_correct(root, fault, cell):
    line = run_small(root, cell, solver_factory=_broken(fault), seconds=0.1)
    assert not line["correct"]
    assert line["failed"] > 0
