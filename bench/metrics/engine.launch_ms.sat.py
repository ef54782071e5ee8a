"""Host ms per window drain in calls into compiled programs: the self
time of the program's ``engine.launch`` spans (a compile inside one is
its own ``jax.compile`` span)."""
from bench import phases


def read(run):
    return phases.self_ms_per_drain(run, "engine.launch")
