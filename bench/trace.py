"""Reduction of a profiler trace to device busy time, the device ops
that took it, and the idle gaps labelled by what the host was doing.

``read_xplane`` takes the device's events (one line of the device plane:
``XLA Ops`` where present) and the harness's ``engine.search``
annotations from an ``.xplane.pb`` file; :func:`reduce` is pure and
works on those lists, so it is tested on a small synthetic trace.

Busy time is the union of the device's op intervals inside the window.
An idle gap is a stretch of the window with no op on any device; its
time is split by host state: inside an ``engine.search`` call
(``engine.search``), no request outstanding at the client
(``client.no_request``), or requests outstanding with no search call
running (``server.queue``: draining, straggler waits, answer delivery).
"""
from __future__ import annotations

import glob
import re
from dataclasses import dataclass

import numpy as np

ANNOTATION = "engine.search"


@dataclass
class DeviceTrace:
    ops: list            # per device: [(name, start_ns, end_ns)] op events
    modules: list        # (name, start_ns, end_ns) program events, all devices
    search_ns: list      # (start_ns, end_ns) engine.search annotations
    planes: list         # (plane, [line names]) of the device planes read


def _events(line):
    return [(e.name, float(e.start_ns), float(e.start_ns + e.duration_ns))
            for e in line.events]


def read_xplane(log_dir: str) -> DeviceTrace:
    """The device events and search annotations of the trace in
    ``log_dir`` (written by ``jax.profiler.start_trace``)."""
    from jax.profiler import ProfileData
    path = sorted(glob.glob(f"{log_dir}/**/*.xplane.pb", recursive=True))[-1]
    pd = ProfileData.from_file(path)
    ops, modules, search, planes = [], [], [], []
    for plane in pd.planes:
        if re.fullmatch(r"/device:(?!CPU)[A-Z]+:\d+", plane.name):
            lines = {line.name: line for line in plane.lines}
            planes.append((plane.name, sorted(lines)))
            op_line = lines.get("XLA Ops") or lines.get("XLA Modules")
            mod_line = lines.get("XLA Modules") or op_line
            ops.append(_events(op_line) if op_line is not None else [])
            if mod_line is not None:
                modules += _events(mod_line)
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                search += [(a, b) for n, a, b in _events(line)
                           if n == ANNOTATION]
    return DeviceTrace(ops, modules, sorted(search), planes)


def union(intervals) -> list:
    """Sorted, merged (start, end) intervals."""
    out = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return [(a, b) for a, b in out]


def clip(intervals, lo: float, hi: float) -> list:
    return [(max(a, lo), min(b, hi)) for a, b in intervals
            if b > lo and a < hi]


def gaps(busy, lo: float, hi: float) -> list:
    """The stretches of [lo, hi] outside the merged ``busy`` intervals."""
    out, t = [], lo
    for a, b in busy:
        if a > t:
            out.append((t, a))
        t = max(t, b)
    if t < hi:
        out.append((t, hi))
    return out


def intersect(xs, ys) -> list:
    """Intersection of two sorted, merged interval lists (linear merge)."""
    out, i, j = [], 0, 0
    while i < len(xs) and j < len(ys):
        a, b = max(xs[i][0], ys[j][0]), min(xs[i][1], ys[j][1])
        if a < b:
            out.append((a, b))
        if xs[i][1] < ys[j][1]:
            i += 1
        else:
            j += 1
    return out


def total(intervals) -> float:
    return sum(b - a for a, b in intervals)


def program_name(event_name: str) -> str:
    """``jit_topk_ia(12)`` -> ``jit_topk_ia``."""
    return re.sub(r"\(\d+\)$", "", event_name).strip()


@dataclass
class Reduced:
    busy_s: float
    window_s: float
    device_ops: list     # [[name, seconds], ...] longest first
    idle_gaps: list      # [[label, seconds], ...] longest first


def reduce(ops, modules, window, searching, no_request,
           top: int = 10) -> Reduced:
    """Busy time, top device programs and labelled idle time.

    ``ops``: per device, (name, start, end) op events; ``modules``:
    (name, start, end) program events; ``window``: (start, end);
    ``searching``/``no_request``: host intervals on the same clock.  Busy
    time is averaged over the devices; a gap has no op on any device.
    Times in ns; results in seconds."""
    lo, hi = window
    per_device = [union(clip([(a, b) for _, a, b in dev], lo, hi))
                  for dev in ops]
    busy_ns = (sum(b - a for dev in per_device for a, b in dev)
               / max(len(per_device), 1))
    busy = union([iv for dev in per_device for iv in dev])
    per = {}
    for name, a, b in modules:
        d = max(0.0, min(b, hi) - max(a, lo))
        if d > 0:
            n = program_name(name)
            per[n] = per.get(n, 0.0) + d
    idle = gaps(busy, lo, hi)
    searching = union(searching)
    outside = intersect(idle, gaps(searching, lo, hi))
    labels = {"engine.search": total(intersect(idle, searching)),
              "client.no_request": total(intersect(outside,
                                                   union(no_request)))}
    labels["server.queue"] = total(outside) - labels["client.no_request"]
    ranked = lambda d: [[k, v / 1e9] for k, v in
                        sorted(d.items(), key=lambda kv: -kv[1])[:top]
                        if v > 0]
    return Reduced(busy_ns / 1e9, (hi - lo) / 1e9, ranked(per),
                   ranked(labels))


def align(search_ns, spans_s) -> float | None:
    """Offset (ns) from the harness's perf_counter seconds to the trace's
    clock, from the ``engine.search`` calls both recorded."""
    n = min(len(search_ns), len(spans_s))
    if n == 0:
        return None
    a = np.asarray([s for s, _ in search_ns[:n]])
    b = np.asarray([s for s, _ in spans_s[:n]]) * 1e9
    return float(np.median(a - b))
