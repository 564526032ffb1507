"""Set-up spent building the plan and on the warm-up calls, compile included (host clock)."""


def read(run):
    return run.setup.get("warm")
