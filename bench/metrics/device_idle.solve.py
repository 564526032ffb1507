"""Share of the traced window in which no op ran on the device, single solves."""


def read(run):
    if run.trace is None or run.rhs_per_call != 1:
        return None
    return 100.0 * (1.0 - run.trace.mean_busy_s / run.trace.window_s)
