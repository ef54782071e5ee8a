"""Requests from a traffic mix and the run's seed.

A mix file (``bench/traffic/<mix>.json``) lists request kinds; the
stream cycles through them in order, each kind as many times per cycle
as its ``weight`` (default 1).  A kind is one op with its parameters,
or a ``pipeline`` of a dataset stage and a point stage.  Parameters:

* ``box``: half-width of the query box around the request's centre,
  which is drawn uniformly from ``center`` (low, high) on each axis;
* ``points``: the query point set is the first ``points`` points of a
  dataset of the lake drawn uniformly;
* ``k``; ``eps``: ``"cell"`` is the grid-cell width of the lake at the
  configuration's ``theta`` (the paper's default epsilon);
* range_points and nnp target a dataset drawn uniformly.

The mixes are free of repeats by design: each kind draws its query sets
and targets without replacement, and no two GBO requests share a
signature (see :func:`requests`), so no request repeats until its kind
has used every dataset.  A user's query is their own dataset or region;
a repeated query is the result cache's path, which is a cell's of its own
with repeated traffic.  (A repeat inside a drain also splits the drain's
group into a sub-batch whose shape warm-up never compiled: that is a gap
in the program's warm-up, listed in ``PERF.md``.)

The stages of one pipeline share the request's centre and dataset.  The
request builder follows ``repro.launch.serve_search.make_traffic``.
"""
from __future__ import annotations

from dataclasses import dataclass

import numpy as np

from bench.data import Lake

WORD_BITS = 32


@dataclass
class Request:
    kind: str
    spec: dict            # plain parameters, the reference's input
    query: object = None  # the program's Query / Pipeline, built by to_query


def cycle(mix: dict) -> list:
    """The kinds of one round of the stream, in order."""
    out = []
    for kind in mix["kinds"]:
        out += [kind] * int(kind.get("weight", 1))
    return out


def space_bounds(lake: Lake):
    """Bounds of the lake's first two axes (the grid of the signatures)."""
    pts = lake.points[..., :2]
    valid = np.arange(pts.shape[1])[None, :] < lake.lengths[:, None]
    lo = np.where(valid[..., None], pts, np.inf).min(axis=(0, 1))
    hi = np.where(valid[..., None], pts, -np.inf).max(axis=(0, 1))
    return lo.astype(np.float32), hi.astype(np.float32)


def signature(points: np.ndarray, lo, hi, theta: int) -> np.ndarray:
    """z-order signature of a point set as (4**theta / 32,) uint32 words."""
    span = np.maximum(hi - lo, np.float32(1e-30))
    nb = 1 << theta
    g = ((points[:, :2] - lo) / span * np.float32(nb)).astype(np.int64)
    g = np.clip(g, 0, nb - 1)
    cells = np.zeros(len(g), np.int64)
    for bit in range(theta):
        cells |= ((g[:, 0] >> bit) & 1) << (2 * bit)
        cells |= ((g[:, 1] >> bit) & 1) << (2 * bit + 1)
    occ = np.zeros(1 << (2 * theta), np.uint32)
    occ[cells] = 1
    words = occ.reshape(-1, WORD_BITS) << np.arange(WORD_BITS, dtype=np.uint32)
    return np.bitwise_or.reduce(words, axis=1)


def requests(mix: dict, config: dict, lake: Lake, seed: int,
             n: int) -> list:
    """``n`` requests of ``mix`` over ``lake``: one stream, of which a run
    takes its warm-up first and its window after, so the two never share
    a request."""
    rng = np.random.default_rng(seed)
    kinds = cycle(mix)
    lo, hi = space_bounds(lake)
    theta = int(config["theta"])
    eps_cell = float((hi[0] - lo[0]) / (1 << theta))
    c_lo, c_hi = mix.get("center", (20.0, 80.0))
    centres = rng.uniform(c_lo, c_hi, (n, 2)).astype(np.float32)
    # each kind draws its query sets and targets without replacement (a
    # permutation of the lake per kind, then another), so no request of a
    # kind repeats until it has used every dataset; GBO queries skip a
    # dataset whose signature a query of the run already has
    slot = np.arange(n) % len(kinds)
    sources = np.zeros(n, np.int64)
    targets = np.zeros(n, np.int64)
    sigs = {}
    for s in range(len(kinds)):
        at = np.nonzero(slot == s)[0]
        laps = -(-len(at) // len(lake)) + 1
        order = np.concatenate([rng.permutation(len(lake))
                                for _ in range(laps)])
        targets[at] = order[:len(at)]
        if kinds[s]["op"] != "topk_gbo":
            sources[at] = order[:len(at)]
            continue
        seen, pos = set(), 0
        for i in at:
            while True:
                j = order[pos % len(order)]
                pos += 1
                sig = signature(lake.points[j, :lake.lengths[j]], lo, hi,
                                theta)
                if sig.tobytes() not in seen:
                    break
            seen.add(sig.tobytes())
            sources[i], sigs[i] = j, sig
    def stage(kind: dict, i: int) -> dict:
        s = {"op": kind["op"]}
        if "box" in kind:
            s["r_lo"] = centres[i] - np.float32(kind["box"])
            s["r_hi"] = centres[i] + np.float32(kind["box"])
        if "k" in kind:
            s["k"] = int(kind["k"])
        if "points" in kind:
            j = sources[i]
            s["q"] = lake.points[j, :min(int(kind["points"]),
                                         lake.lengths[j])]
        if kind["op"] == "topk_gbo":
            s["q_sig"] = sigs[i]
        if "eps" in kind:
            s["eps"] = eps_cell if kind["eps"] == "cell" else float(
                kind["eps"])
        return s

    out = []
    for i in range(n):
        kind = kinds[i % len(kinds)]
        if kind["op"] == "pipeline":
            spec = {"op": "pipeline", "dataset": stage(kind["dataset"], i),
                    "point": stage(kind["point"], i)}
        else:
            spec = stage(kind, i)
            if kind["op"] in ("range_points", "nnp"):
                spec["ds_id"] = int(targets[i])
        out.append(Request(kind["name"], spec))
    return out


def to_query(spec: dict):
    """The program's declarative request for a spec."""
    from repro.engine import Pipeline, Query
    if spec["op"] == "pipeline":
        return Pipeline(dataset_stage=to_query(spec["dataset"]),
                        point_stage=to_query(spec["point"]))
    return Query(**spec)
