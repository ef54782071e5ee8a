"""Device busy time (ms, from the trace) per request answered in the
traced window."""


def read(run):
    if run.trace is None:
        return None
    t0, t1 = run.window
    n = int(((run.done >= t0) & (run.done <= t1)).sum())
    return 1e3 * run.trace.busy_s / n if n else None
