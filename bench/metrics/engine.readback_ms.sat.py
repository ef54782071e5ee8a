"""Host ms per window drain blocked on the chip: the self time of the
program's ``engine.readback`` spans (each host copy of a program's
outputs, and of built query trees for their digests)."""
from bench import phases


def read(run):
    return phases.self_ms_per_drain(run, "engine.readback")
