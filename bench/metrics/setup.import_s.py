"""Set-up spent importing JAX and the program and bringing up the backend (host clock)."""


def read(run):
    return run.setup.get("imports")
