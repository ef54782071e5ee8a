"""Host ms per window drain in marshalling: the self time of the
program's ``engine.marshal`` spans (payload stacking and padding, query
tree builds, result-cache keys and lookups, uploads)."""
from bench import phases


def read(run):
    return phases.self_ms_per_drain(run, "engine.marshal")
