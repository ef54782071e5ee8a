"""Host ms per window drain placing each stage-1 group's rows: the self
time of the program's ``engine.results`` spans whose ``part`` is
``handoff`` (a device slice of the winner ids per pipeline row)."""
from bench import phases


def read(run):
    return phases.self_ms_per_drain(run, "engine.results", part="handoff")
