"""Host ms per window drain in planning: the self time of the program's
``engine.plan`` spans (checking and grouping the requests, and grouping
the pipelines' point stages)."""
from bench import phases


def read(run):
    return phases.self_ms_per_drain(run, "engine.plan")
