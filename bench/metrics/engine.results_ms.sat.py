"""Host ms per window drain in answer assembly: the self time of the
program's ``engine.results`` spans (row splitting, result-cache
insertion, the answers, the stage-2 re-rank)."""
from bench import phases


def read(run):
    return phases.self_ms_per_drain(run, "engine.results")
