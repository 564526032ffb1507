"""Mean iterations per solve, from the program's ``SolveResult.iterations``."""


def read(run):
    if run.rhs_per_call != 1 or run.calls == 0:
        return None
    return float(run.iterations.mean())
