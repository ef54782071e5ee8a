"""Mean time (ms) a request waited in the server's queue before its drain
began: the program's ``server.drain`` spans of the window's drains, their
summed waits over their requests."""
from bench import phases


def read(run):
    spans = phases.program_spans(run)
    drains = phases.window_drains(spans, run.window) if spans else {}
    marks = [s.attrs for ss in drains.values() for s in ss
             if s.name == "server.drain"]
    n = sum(a["requests"] for a in marks)
    return sum(a["wait_sum_ms"] for a in marks) / n if n else None
