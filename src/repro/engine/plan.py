"""Planner for `QueryEngine.search`: mixed declarative batches -> dispatches.

`execute` compiles a mixed ``list[Query | Pipeline]`` into per-(op,
static-params, query-shape) :class:`DispatchGroup`\\ s and runs each group
through the engine's per-op executor (``engine._exec_<op>``) — so every
group rides the existing bucket ladder, executable cache, and result cache
(cache hits short-circuit per row inside the executor, exactly as they do
for the legacy batch methods).  Results are scattered back into INPUT
order; the number of device dispatches is one per group (plus one per
grouped query-index build), never one per query.

Pipelines run in two stages:

  * **stage 1** — each pipeline's ``dataset_stage`` is planned as an
    ordinary row of its op's dispatch group, so pipeline stage-1 queries
    and standalone queries of the same (op, statics) share ONE dispatch;
  * **stage 2** — the winning dataset ids feed ``range_points`` / ``nnp``
    with the id handoff staying ON DEVICE (the planner slices the ids out
    of the stage-1 dispatch output BEFORE any host materialization; ``-1``
    sentinel winners are clamped to slot 0 for the gather and masked out
    of the result).  Stage-2 rows group across pipelines by (point op,
    statics, built query capacity), so P pipelines with compatible point
    stages cost one dispatch of ``sum(k_p)`` rows.  A joinable stage-2
    (``topk_overlap`` / ``topk_coverage`` — the dataset→dataset pipeline)
    takes the same handoff: winner slots are gathered by id on device and
    exactly re-scored against the stage's query set in one grouped
    dispatch, then re-ranked host-side to the stage's top-k (descending
    score, ties keeping stage-1 rank; sentinel winners score ``-1`` and
    stay sentinels).

Grouping keys are host-side only (op tags, static scalars, array shapes) —
planning never syncs device values.  Per-row payload marshalling is
host-side too: group payloads are stacked in NUMPY and uploaded as ONE
array per operand, and dispatch outputs are materialized once per group
and split into free numpy row views — per-query Python cost stays in the
microseconds instead of paying a device-op round trip per row (jax eager
dispatch overhead is ~100us/op on CPU, which would dwarf small-op
dispatches at batch 64+).
"""
from __future__ import annotations

from collections import OrderedDict
from dataclasses import dataclass, field

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import index as index_lib
from repro.engine import spans
from repro.engine.query import (DATASET_RERANK_OPS, Pipeline, Query,
                                SearchResult)


@dataclass
class DispatchGroup:
    """Rows of one batched dispatch: same op, same statics, same query
    shape signature.  ``rows`` are positions in the caller's input list."""

    op: str
    statics: tuple
    shape_sig: tuple
    rows: list = field(default_factory=list)
    queries: list = field(default_factory=list)


def plan(items, leaf_capacity: int = 16) -> list[DispatchGroup]:
    """Check a mixed batch and group it into stage-1 dispatch groups
    (first-seen order; a Pipeline contributes its ``dataset_stage``
    here)."""
    with spans.span("engine.plan"):
        for it in items:
            if not isinstance(it, (Query, Pipeline)):
                raise TypeError(
                    f"search() takes Query/Pipeline items, got {type(it)!r}")
            # a STANDALONE point query must name its dataset; only a
            # Pipeline's point stage may leave ds_id None (filled from the
            # stage-1 winners) — catch it here with a clear message
            # instead of an opaque asarray failure inside the group
            # marshalling
            if (isinstance(it, Query) and it.op in ("range_points", "nnp")
                    and it.ds_id is None):
                raise ValueError(
                    f"Query(op={it.op!r}) requires ds_id outside a Pipeline "
                    f"point stage")
        groups: "OrderedDict[tuple, DispatchGroup]" = OrderedDict()
        for pos, item in enumerate(items):
            q = item.dataset_stage if isinstance(item, Pipeline) else item
            key = (q.op, q.statics(), q.query_shape_sig(leaf_capacity))
            g = groups.get(key)
            if g is None:
                g = groups[key] = DispatchGroup(q.op, key[1], key[2])
            g.rows.append(pos)
            g.queries.append(q)
        return list(groups.values())


class Results(list):
    """`execute`'s answers in input order, with ``groups``: the dispatch
    groups the call planned (stage-1 op groups + pipeline stage-2
    groups), for callers that book them without reading the engine's
    shared counters."""
    groups = 0


def execute(engine, items) -> Results:
    """Run a mixed batch through the engine; one SearchResult per input."""
    items = list(items)
    with spans.span("engine.execute", requests=len(items)) as call:
        results = Results([None] * len(items))
        stage1: dict = {}          # input pos -> stage-1 SearchResult
        handoffs: dict = {}        # input pos -> device (k,) winner-id row
        groups = plan(items, engine.leaf_capacity)
        for g in groups:
            # subgroups: replica row-blocks this group's rows span (1
            # unless the dispatcher splits rows across replica groups)
            engine.stats.count_group(g.op,
                                     engine._plan_subgroups(len(g.rows)))
            with spans.timed("engine.group", op=g.op, stage=1,
                             rows=len(g.rows)) as gs:
                before = _group_counters(engine) if gs.live else None
                rows, ids_dev = _run_group(engine, g)
                if gs.live:
                    gs.set(**_group_attrs(engine, before, len(g.rows)))
            engine.stats.record_latency(g.op, gs.seconds)
            with spans.span("engine.results", part="handoff") as rs:
                n = 0
                for j, (pos, res) in enumerate(zip(g.rows, rows)):
                    if isinstance(items[pos], Pipeline):
                        stage1[pos] = res
                        handoffs[pos] = ids_dev[j]       # device slice
                        n += 1
                    else:
                        results[pos] = res
                rs.set(rows=n)
        results.groups = len(groups)
        if stage1:
            engine.stats.pipeline_stage1 += len(stage1)
            results.groups += _run_stage2(engine, items, stage1, handoffs,
                                          results)
        call.set(groups=results.groups)
    return results


def _group_counters(engine) -> tuple:
    return engine.stats.cache_misses, engine.stats.result_cache_hits


def _group_attrs(engine, before, rows: int) -> dict:
    """A recorded group's ``bucket`` (of the rows it dispatched, 0 when
    the result cache answered them all), ``cached`` (no program built)
    and ``result_hits`` (rows the result cache answered)."""
    misses, hits = _group_counters(engine)
    hits -= before[1]
    return {"bucket": engine.bucket_for(rows - hits) if rows > hits else 0,
            "cached": misses == before[0], "result_hits": hits}


# ---------------------------------------------------------------------------
# stage 1 / plain groups
# ---------------------------------------------------------------------------


def _stack_boxes(queries, attr):
    """(B, d) operand from per-query host rows: ONE numpy stack, no
    per-row device ops (the executor does the single upload)."""
    return np.stack([np.asarray(getattr(q, attr), np.float32)
                     for q in queries])


def _host(*xs) -> list:
    """Host copies of a group's device outputs (iterating one gives its
    rows as free views): the host waits here for the group's program."""
    with spans.span("engine.readback"):
        return [np.asarray(x) for x in xs]


def _group_q_batch(engine, queries):
    """The group's (B, ...) query-index batch: pre-built rows are stacked
    shape-exactly on the host (the group key guarantees equal
    capacity/depth; one upload per leaf at dispatch), raw point sets go
    through ONE grouped `build_queries` (padded to the group's common
    capacity, exactly like the serving front-end built grouped
    requests)."""
    if queries[0].q_index is not None:
        return jax.tree.map(
            lambda *xs: np.stack([np.asarray(x) for x in xs]),
            *[q.q_index for q in queries])
    return engine.build_queries([np.asarray(q.q) for q in queries])


def _run_group(engine, g: DispatchGroup):
    """Run one dispatch group; returns (per-row SearchResults, device
    top-k id batch or None).  The device id batch is kept UNSPLIT so a
    pipeline's stage-2 handoff can slice it without the ids ever visiting
    the host."""
    op, qs = g.op, g.queries
    if op == "range_search":
        with spans.span("engine.marshal"):
            lo, hi = _stack_boxes(qs, "r_lo"), _stack_boxes(qs, "r_hi")
        masks, = _host(engine._exec_range_search(lo, hi))
        with spans.span("engine.results"):
            return [SearchResult(op=op, mask=m) for m in masks], None
    if op == "topk_ia":
        with spans.span("engine.marshal"):
            lo, hi = _stack_boxes(qs, "r_lo"), _stack_boxes(qs, "r_hi")
        vals, ids = engine._exec_topk_ia(lo, hi, qs[0].k)
        v, i = _host(vals, ids)
        with spans.span("engine.results"):
            return [SearchResult(op=op, vals=a, ids=b)
                    for a, b in zip(v, i)], ids
    if op == "topk_gbo":
        with spans.span("engine.marshal"):
            sigs = np.stack([np.asarray(q.q_sig) for q in qs])
        vals, ids = engine._exec_topk_gbo(sigs, qs[0].k)
        v, i = _host(vals, ids)
        with spans.span("engine.results"):
            return [SearchResult(op=op, vals=a, ids=b)
                    for a, b in zip(v, i)], ids
    if op == "topk_hausdorff_approx":
        with spans.span("engine.marshal"):
            q_batch = _group_q_batch(engine, qs)
        vals, ids, eps_eff = engine._exec_topk_hausdorff_approx(
            q_batch, qs[0].k, qs[0].eps)
        v, i, e = _host(vals, ids, eps_eff)
        with spans.span("engine.results"):
            return [SearchResult(op=op, vals=a, ids=b, extras={"eps_eff": c})
                    for a, b, c in zip(v, i, e)], ids
    if op == "topk_hausdorff":
        with spans.span("engine.marshal"):
            q_batch = _group_q_batch(engine, qs)
        vals, ids, stats = engine._exec_topk_hausdorff(
            q_batch, qs[0].k, qs[0].refine_levels, qs[0].chunk)
        v, i = _host(vals, ids)
        with spans.span("engine.results"):
            return [SearchResult(op=op, vals=a, ids=b, stats=s)
                    for a, b, s in zip(v, i, stats)], ids
    if op == "range_points":
        with spans.span("engine.marshal"):
            ds = np.asarray([q.ds_id for q in qs], np.int32)
            lo, hi = _stack_boxes(qs, "r_lo"), _stack_boxes(qs, "r_hi")
        take, stats = engine._exec_range_points(ds, lo, hi)
        take, = _host(take)
        with spans.span("engine.results"):
            return [SearchResult(op=op, mask=m, stats=s)
                    for m, s in zip(take, stats)], None
    if op == "nnp":
        with spans.span("engine.marshal"):
            ds = np.asarray([q.ds_id for q in qs], np.int32)
            q_batch = _group_q_batch(engine, qs)
        dists, idxs, stats = engine._exec_nnp(ds, q_batch)
        d, i, valid = _host(dists, idxs, q_batch.valid)
        with spans.span("engine.results"):
            return [SearchResult(op=op, vals=a, ids=b, mask=m, stats=s)
                    for a, b, m, s in zip(d, i, valid, stats)], None
    if op in DATASET_RERANK_OPS:
        with spans.span("engine.marshal"):
            pts, val = _stack_pointsets(
                [q.q for q in qs],
                max(q.built_capacity(engine.leaf_capacity) for q in qs))
        vals, ids, stats = engine._exec_topk_join(op, pts, val, qs[0].k)
        v, i = _host(vals, ids)
        with spans.span("engine.results"):
            return [SearchResult(op=op, vals=a, ids=b, stats=s)
                    for a, b, s in zip(v, i, stats)], ids
    raise ValueError(f"unplannable op {op!r}")  # pragma: no cover


def _stack_pointsets(pointsets, cap: int):
    """(B, cap, d) points + (B, cap) validity from raw per-query sets —
    the joinable ops score on the shared grid, so no tree build: ONE
    numpy pad/stack, one upload at dispatch.  Padding rows are invalid
    and park in the grid's overflow cell, so any two groupings of the
    same query produce bit-identical scores."""
    sets = [np.asarray(ps, np.float32) for ps in pointsets]
    pts = np.zeros((len(sets), cap, sets[0].shape[-1]), np.float32)
    val = np.zeros((len(sets), cap), bool)
    for i, s in enumerate(sets):
        pts[i, :s.shape[0]] = s
        val[i, :s.shape[0]] = True
    return pts, val


# ---------------------------------------------------------------------------
# stage 2: pipeline point queries over the stage-1 winners
# ---------------------------------------------------------------------------


def _stage2_key(ps: Query, leaf_capacity: int) -> tuple:
    """Grouping key for a pipeline's point stage — host-side shape math
    only, so multiple pipelines share one stage-2 dispatch whenever their
    built query trees are shape-compatible."""
    if ps.op == "nnp":
        cap = ps.built_capacity(leaf_capacity)
        if ps.q_index is not None:
            depth = ps.q_index.depth
        else:
            depth = index_lib.depth_for(cap, leaf_capacity)
        return (ps.op, ps.statics(), cap, depth)
    if ps.op in DATASET_RERANK_OPS:
        # joinable re-rank rows stack raw padded point sets: the key pins
        # the padded capacity so the group's stack is shape-exact
        return (ps.op, ps.statics(), ps.built_capacity(leaf_capacity))
    return (ps.op, ps.statics())


def _run_stage2(engine, items, stage1, handoffs, results) -> int:
    """Run every pipeline's point stage, grouped; returns the number of
    stage-2 groups."""
    with spans.span("engine.plan"):
        groups: "OrderedDict[tuple, list[int]]" = OrderedDict()
        for pos in stage1:
            groups.setdefault(
                _stage2_key(items[pos].point_stage, engine.leaf_capacity),
                []).append(pos)
    for key, poss in groups.items():
        pop = key[0]
        ks = [items[pos].dataset_stage.k for pos in poss]
        total = int(sum(ks))
        engine.stats.count_group(pop, engine._plan_subgroups(total))
        with spans.timed("engine.group", op=pop, stage=2, rows=total) as gs:
            before = _group_counters(engine) if gs.live else None
            _run_stage2_group(engine, items, stage1, handoffs, results, key,
                              poss, ks)
            if gs.live:
                gs.set(**_group_attrs(engine, before, total))
        engine.stats.record_latency(pop, gs.seconds)
        engine.stats.pipeline_stage2 += len(poss)
    return len(groups)


def _run_stage2_group(engine, items, stage1, handoffs, results, key, poss,
                      ks) -> None:
    pop, total = key[0], int(sum(ks))
    with spans.span("engine.marshal"):
        # winner ids, handed off ON DEVICE (sliced from the stage-1
        # dispatch output): -1 sentinels (k past the valid dataset count)
        # are clamped to slot 0 for the gather and masked out below.
        # One concatenate + one compare + one where for the WHOLE group —
        # per-pipeline eager device ops would cost more than the dispatch
        w_flat = jnp.concatenate([handoffs[pos] for pos in poss])
        valid_flat = w_flat >= 0
        ds_flat = jnp.where(valid_flat, w_flat, 0).astype(jnp.int32)
    valid_np, = _host(valid_flat)
    offs = np.concatenate([[0], np.cumsum(ks)])
    valid_rows = [valid_np[offs[i]:offs[i + 1]] for i in range(len(poss))]
    if pop == "range_points":
        with spans.span("engine.marshal"):
            def _tile_box(pos, k, attr):
                b = np.asarray(getattr(items[pos].point_stage, attr),
                               np.float32)
                return np.broadcast_to(b[None], (k,) + b.shape)

            lo = np.concatenate([_tile_box(pos, k, "r_lo")
                                 for pos, k in zip(poss, ks)])
            hi = np.concatenate([_tile_box(pos, k, "r_hi")
                                 for pos, k in zip(poss, ks)])
        take, stats = engine._exec_range_points(ds_flat, lo, hi)
        take_np, = _host(take)
        with spans.span("engine.results"):
            off = 0
            for pos, k, v in zip(poss, ks, valid_rows):
                results[pos] = SearchResult(
                    op="pipeline",
                    mask=take_np[off:off + k] & v[:, None],
                    stats=stats[off:off + k],
                    extras={"stage1": stage1[pos],
                            "ds_ids": stage1[pos].ids, "valid": v})
                off += k
    elif pop in DATASET_RERANK_OPS:
        # dataset→dataset: exact join score of each winner slot vs the
        # pipeline's query set (one grouped dispatch, ids on device), then
        # a host-side re-rank to the stage's top-k.  Sentinel winners were
        # clamped to slot 0 above; their rows are forced to score -1 here,
        # so a pipeline with ZERO surviving winners degrades to
        # all-sentinel output instead of ranking slot 0
        with spans.span("engine.marshal"):
            pts, val = _stack_pointsets(
                [items[pos].point_stage.q for pos in poss], key[2])
            reps = np.asarray(ks, np.int32)
            pts_rep = jnp.repeat(jnp.asarray(pts), reps, axis=0,
                                 total_repeat_length=total)
            val_rep = jnp.repeat(jnp.asarray(val), reps, axis=0,
                                 total_repeat_length=total)
        scores = engine._exec_join_rerank(pop, ds_flat, pts_rep, val_rep)
        s_np, = _host(scores)
        with spans.span("engine.results"):
            off = 0
            for pos, k, v in zip(poss, ks, valid_rows):
                k2 = items[pos].point_stage.k
                seg = np.where(v, s_np[off:off + k], -1).astype(np.int32)
                win = np.asarray(stage1[pos].ids, np.int32)[:k]
                # descending score; stable sort keeps stage-1 rank on ties
                order = np.argsort(-seg, kind="stable")[:k2]
                vals2 = np.full((k2,), -1, np.int32)
                ids2 = np.full((k2,), -1, np.int32)
                vals2[:len(order)] = seg[order]
                ids2[:len(order)] = np.where(vals2[:len(order)] < 0, -1,
                                             win[order])
                results[pos] = SearchResult(
                    op="pipeline", vals=vals2, ids=ids2, mask=vals2 >= 0,
                    extras={"stage1": stage1[pos],
                            "ds_ids": stage1[pos].ids, "valid": v})
                off += k
    else:  # nnp
        with spans.span("engine.marshal"):
            rows = _stage2_nnp_rows(engine, items, poss)
            reps = np.asarray(ks, np.int32)
            q_flat = jax.tree.map(
                lambda x: jnp.repeat(x, reps, axis=0,
                                     total_repeat_length=total), rows)
        dists, idxs, stats = engine._exec_nnp(ds_flat, q_flat)
        d_np, i_np, qv_np = _host(dists, idxs, q_flat.valid)
        with spans.span("engine.results"):
            off = 0
            for pos, k, v in zip(poss, ks, valid_rows):
                results[pos] = SearchResult(
                    op="pipeline",
                    vals=d_np[off:off + k],
                    ids=i_np[off:off + k],
                    mask=v[:, None] & qv_np[off:off + k],
                    stats=stats[off:off + k],
                    extras={"stage1": stage1[pos],
                            "ds_ids": stage1[pos].ids, "valid": v})
                off += k


def _stage2_nnp_rows(engine, items, poss):
    """One query-index row per pipeline in the group, as a (P, ...) tree.

    Raw point sets are built in ONE grouped `build_queries` call; the
    group key pins the built capacity to what a solo build would produce,
    so each row is bit-identical to the two-call host baseline's build.
    Pre-built rows are stacked directly."""
    raw = [pos for pos in poss if items[pos].point_stage.q_index is None]
    built = None
    if raw:
        built = engine.build_queries(
            [np.asarray(items[pos].point_stage.q) for pos in raw])
    raw_row = {pos: i for i, pos in enumerate(raw)}
    rows = []
    for pos in poss:
        ps = items[pos].point_stage
        if ps.q_index is None:
            rows.append(jax.tree.map(
                lambda x, i=raw_row[pos]: x[i], built))
        else:
            rows.append(jax.tree.map(jnp.asarray, ps.q_index))
    return jax.tree.map(lambda *xs: jnp.stack(xs), *rows)
