"""Set-up seconds: data generation, repository build and warm-up, from
the process's start of work to the window's start (host clock)."""


def read(run):
    return run.setup_s
