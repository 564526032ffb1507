"""Host time in the program per single solve: the mean duration of the program's own
``plan.solve`` spans (entry until the call returns its un-ready result), over the calls
of the traced window. The program records them while the profiler captures, so the
window's last ``traced_calls`` roots are exactly the traced calls."""


def read(run, roots=None):
    if run.trace is None or run.rhs_per_call != 1 or not run.traced_calls:
        return None
    if roots is None:
        try:
            from repro import obs
        except ImportError:
            return None
        roots = obs.span_tree()
    spans = [s for s in roots if s.name == "plan.solve"]
    if len(spans) < run.traced_calls:
        return None
    spans = spans[-run.traced_calls:]
    return 1e3 * sum(s.duration_s for s in spans) / len(spans)
