"""Time per solve: the window's length over the solves completed in it (host clock)."""


def read(run):
    if run.rhs_per_call != 1 or run.calls == 0:
        return None
    return 1e3 * run.window_s / run.calls
