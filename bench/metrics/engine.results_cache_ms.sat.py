"""Host ms per window drain inserting dispatched rows into the result
cache: the self time of the program's ``engine.results`` spans whose
``part`` is ``cache`` (each row split into device slices, then stored)."""
from bench import phases


def read(run):
    return phases.self_ms_per_drain(run, "engine.results", part="cache")
