"""Process start to the first timed call: imports, operator, pool, plan, compile, warm-up."""


def read(run):
    return run.setup_s
