"""Share of the HBM roofline of single solves: the algorithmic bytes of the window's
solves (``harness/workbytes.py``) over device-busy time times the HBM peak of the
``device_kind`` (``harness/peaks.py``); per chip, with its share of the bytes,
averaged over the chips."""


def read(run):
    if run.rhs_per_call != 1:
        return None
    return run.hbm_roofline_pct()
