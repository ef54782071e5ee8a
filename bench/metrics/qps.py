"""Requests answered per second over the whole window (host clock):
answers that reached the client inside the window, over its length."""


def read(run):
    t0, t1 = run.window
    return int(((run.done >= t0) & (run.done <= t1)).sum()) / (t1 - t0)
