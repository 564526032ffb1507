"""Share of the HBM roofline of the ``spmv_dia`` kernel in single solves: the least
bytes its SPMVs move, over its device time (the op named ``spmv_dia`` in the trace)
times the HBM peak of the ``device_kind`` (``harness/peaks.py``).

Each SPMV reads every stored diagonal and its input vector and writes its output,
``(n_diags + 2) * n * itemsize`` bytes, and a solve of ``i`` iterations makes
``i`` SPMVs in its loop and the start-up and replacement ones of
``harness/workbytes.py``."""
import numpy as np

from harness import peaks, workbytes


def read(run):
    if run.trace is None or run.rhs_per_call != 1:
        return None
    device_s = dict(run.trace.device_ops).get("spmv_dia")
    if not device_s:
        return None
    st = run.stencil
    every = int(run.cell.config["solver"].get("replace_every") or 0)
    its = run.iterations[:run.traced_calls, 0].astype(np.int64)
    spmvs = int(np.sum(its + workbytes._outer_spmvs(its, every)))
    per_spmv = (st.n_diags + 2) * st.n * np.dtype(st.dtype).itemsize
    per_chip = spmvs * per_spmv / len(run.trace.busy_s)
    return 100.0 * per_chip / (device_s * peaks.hbm_bytes_per_s(run.device_kind))
