"""95th percentile of the solves' times, each from the call to ``block_until_ready``."""
import numpy as np


def read(run):
    if run.rhs_per_call != 1 or run.calls == 0:
        return None
    return 1e3 * float(np.percentile(run.latencies_s, 95))
