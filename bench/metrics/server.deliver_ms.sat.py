"""Host ms per window drain in answer delivery: the self time of the
program's ``server.deliver`` spans (the server's booking and each
future's result, which runs the client's callback)."""
from bench import phases


def read(run):
    return phases.self_ms_per_drain(run, "server.deliver")
