"""Metric readers, one file per metric: ``bench/metrics/<name>.py``.

Each defines ``read(run) -> float | None`` over a :class:`bench.run.Run`
(the window's request records, ``engine.search`` spans, engine and server
counters, the set-up time and, in a traced run, the reduced trace).  A
reader that finds nothing to read returns None and the metric is left
out of the result line.
"""
from __future__ import annotations

import importlib.util
from pathlib import Path

HERE = Path(__file__).resolve().parent


def reader(name: str):
    """The ``read`` function of ``bench/metrics/<name>.py``."""
    path = HERE / f"{name}.py"
    if not path.is_file():
        raise KeyError(f"no reader for metric {name!r} ({path})")
    spec = importlib.util.spec_from_file_location(
        f"bench.metrics._{name.replace('.', '_')}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read
