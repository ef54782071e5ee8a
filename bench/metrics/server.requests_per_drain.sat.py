"""Mean requests per server drain: each drain is one ``engine.search``
call of the window (the harness's span around it)."""


def read(run):
    n = [s.n for s in run.spans]
    return sum(n) / len(n) if n else None
