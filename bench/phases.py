"""The program's own host-phase spans (``repro.engine.spans``), as the
metric readers read them.

The program records its spans while a profiler trace runs, so a traced
run (``--trace 1``) holds the window's spans and an untraced run none.
Each span carries the id of the drain it served; a drain belongs to the
window when its ``engine.execute`` span starts inside it.  "Per drain"
is the mean over those drains; a span's "self" time is its duration less
the part of it that its child spans cover.
"""
from __future__ import annotations

from bench.trace import clip, total, union


def program_spans(run) -> list | None:
    """The program's finished spans for this run: ``run.phases`` where
    the run already holds them, else taken from the program's recorder
    once and kept on the run for the other readers.  None where the
    program records none (a program without the recorder, or an untraced
    run)."""
    got = getattr(run, "phases", None)
    if got is None:
        try:
            from repro.engine import spans
        except ImportError:
            return None
        got, _ = spans.take()
        run.phases = got
    return got or None


def window_drains(spans, window) -> dict:
    """drain id -> its spans, for the drains whose ``engine.execute``
    starts in ``window`` (perf_counter seconds, start included)."""
    lo, hi = window[0] * 1e9, window[1] * 1e9
    ids = {s.drain for s in spans if s.name == "engine.execute"
           and s.drain is not None and lo <= s.start < hi}
    out = {d: [] for d in ids}
    for s in spans:
        if s.drain in out:
            out[s.drain].append(s)
    return out


def self_ns(spans) -> dict:
    """span id -> its duration (ns) less what its children cover."""
    kids: dict = {}
    for s in spans:
        if s.parent is not None:
            kids.setdefault(s.parent, []).append((s.start, s.end))
    return {s.id: (s.end - s.start)
            - total(union(clip(kids.get(s.id, []), s.start, s.end)))
            for s in spans}


def self_ms_per_drain(run, name: str, part: str | None = None
                      ) -> float | None:
    """Mean self time (ms) per window drain of the spans named ``name``
    (with ``part`` given, only those whose ``part`` attribute it is)."""
    spans = program_spans(run)
    drains = window_drains(spans, run.window) if spans else {}
    if not drains:
        return None
    own = self_ns(spans)
    ns = sum(own[s.id] for ss in drains.values() for s in ss
             if s.name == name
             and (part is None or s.attrs.get("part") == part))
    return ns / 1e6 / len(drains)
