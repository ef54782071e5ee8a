"""Engine QPS benchmark: batched multi-query dispatch vs per-query loop.

    PYTHONPATH=src python benchmarks/bench_engine.py [--out BENCH_engine.json]
    REPRO_HOST_DEVICES=8 PYTHONPATH=src \
        python benchmarks/bench_engine.py --sharded   # -> BENCH_engine_sharded.json

For each dataset-granularity op (RangeS, top-k IA, top-k GBO, ApproHaus)
and the point-granularity RangeP, measures queries-per-second of

  * the **per-query-loop baseline**: a Python loop over the seed
    single-query ops (the pre-engine serving shape — one host round trip
    per query), and
  * the **engine batched path** at batch sizes 1 -> 256 (one device
    dispatch per batch via the QueryEngine's cached executables).

With ``--sharded`` the engine is a :class:`ShardedQueryEngine` over a 1-D
``data`` mesh spanning all local devices (set ``REPRO_HOST_DEVICES=N`` to
force N host-platform devices on CPU) and the record lands in
``BENCH_engine_sharded.json``; the record also gains an ``exact_hausdorff``
section — single-query ExactHaus latency AND per-device resident
repository bytes at 1/3/8 shards, showing memory dropping ~1/N now that
the sharded branch-and-bound keeps no replicated repository copy.

Both modes also run the BATCHED ExactHaus sweep (`exact_hausdorff_batched`
section): batch 1..64 query-index batches answered in ONE branch-and-bound
dispatch (shared phase-2 work frontier) vs the per-query dispatch loop
(one engine dispatch per query — the pre-batching serving shape), on a
serving-shaped corpus of its own, AND the MIXED-OP sweep (`mixed_ops`
section): heterogeneous declarative batches — all seven ops plus a
dataset->point pipeline kind — answered with ONE `engine.search` call vs
the per-op grouped-dispatch loop over the same rows (hand grouping + one
engine call per (op, statics) group + host id handoff for pipelines, the
pre-redesign serving shape).  All engines run with the result cache
disabled so repeated timing iterations measure dispatch, not memoization.
``--max-batch`` trims every sweep (the CI bench-smoke step uses it).

Two more sections ride along in both modes: ``bound_phases`` — the fused
all-levels `ops.bound_grid` pass vs the per-level `vmap(frontier_bounds)`
composition it replaced in ExactHaus phases 0/1 (B in {1, 8, 32}) — and
``adaptive_serving`` — the serving front-end's queue-depth-driven batching
window vs the seed's static max-wait window (QPS + p50/p99 at low and
saturating load).

``--join-sweep`` runs the joinable-op mode on its own record
(``BENCH_engine_join.json``): batched ``topk_overlap`` / ``topk_coverage``
QPS at batch 1..32 vs the per-query dispatch loop, the bound-phase pruned
fraction per row, and a PRE-FILLED saturating serving segment mixing
joinable queries with dataset→dataset re-rank pipelines (see
``bench_join_sweep``).

``--replica-sweep`` runs a third mode on its own record
(``BENCH_engine_replica.json``): the ReplicatedQueryEngine over R x D
(replica x data) meshes at fixed D — saturated serving QPS plus the
measured per-replica-group critical path and its device-parallel QPS
projection at R = 1/2/4 (see ``bench_replica_scaling``).

Emits the JSON record with per-op QPS curves plus a summary of the
batch-64 speedup over the baseline and the batch-32 batched-ExactHaus
speedup.
"""
from __future__ import annotations

import argparse
import json
import os
import time
from pathlib import Path

from repro import compile_cache, hostdev

# must happen before the first jax import: force N host-platform devices so
# the sharded mode has something to shard over on CPU-only machines
hostdev.apply()

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import point_search, search, zorder
from repro.core.build import build_repository
from repro.data import synthetic
from repro.engine import QueryEngine, ShardedQueryEngine
from repro.engine.sharded import data_mesh, repo_device_bytes

BATCHES = (1, 2, 4, 8, 16, 32, 64, 128, 256)
EXACT_BATCHES = (1, 2, 4, 8, 16, 32, 64)
MIXED_BATCHES = (1, 2, 4, 8, 16, 32, 64)
EXACT_SHARD_COUNTS = (1, 3, 8)

# ExactHaus batched-QPS corpus: the online serving shape — many small-ish
# datasets, small exemplar queries (distinct from the main op corpus so the
# branch-and-bound sweep isn't dominated by one giant padded point axis)
EXACT_DATASETS = 128
EXACT_N_POINTS = (40, 100)
EXACT_Q_POINTS = 24
EXACT_K = 10
EXACT_CHUNK = 8


def bench_exacthaus_batched(engine_ctor, repeats, *, max_batch=None,
                            seed=1):
    """Batched ExactHaus QPS sweep: batch 1..64 in ONE dispatch each vs
    the per-query dispatch loop (the pre-batching serving shape: one
    engine dispatch per query, as serve_search used to issue).

    Builds its own serving-shaped corpus (EXACT_* constants), constructs
    an engine via `engine_ctor(repo)` (local or sharded; result cache off
    so repeats measure dispatch), and returns the op record with per-batch
    QPS and speedup-vs-loop.  The baseline loop for each row runs the
    SAME b queries as the batched dispatch (per-query branch-and-bound
    work varies across the pool, so a fixed baseline query set would bias
    the ratio — at batch 1 both sides run the identical single dispatch
    and the speedup is ~1 by construction)."""
    lake = synthetic.trajectory_repository(EXACT_DATASETS, seed=seed,
                                           n_points=EXACT_N_POINTS)
    repo, _ = build_repository(lake, leaf_capacity=16, theta=5,
                               remove_outliers=False)
    engine = engine_ctor(repo)
    n_pool = max(EXACT_BATCHES)
    q_sets = [lake[i % len(lake)][:EXACT_Q_POINTS] for i in range(n_pool)]
    q_batch_all = engine.build_queries(q_sets)
    k, chunk = EXACT_K, EXACT_CHUNK

    def q_at(i):
        return jax.tree.map(lambda x: x[i], q_batch_all)

    def q_slice(b):
        return jax.tree.map(lambda x: x[:b], q_batch_all)

    engine.topk_hausdorff(q_at(0), k, chunk=chunk)     # warm bucket 1

    batches = [b for b in EXACT_BATCHES
               if max_batch is None or b <= max_batch]
    rows = []
    for b in batches:
        def loop(b=b):                 # matched set: queries 0..b-1
            out = None
            for i in range(b):
                out = engine.topk_hausdorff(q_at(i), k, chunk=chunk)[0]
            return out

        t_loop = _time_best(loop, repeats=max(2, repeats // 2))
        tb = _time_best(lambda: engine.topk_hausdorff(q_slice(b), k,
                                                      chunk=chunk)[0],
                        repeats=repeats)
        rows.append({
            "batch": b,
            "seconds_per_batch": tb,
            "qps": b / tb,
            "loop_seconds": t_loop,
            "loop_qps": b / t_loop,
            "speedup_vs_loop": t_loop / tb,
        })
    return {
        "corpus": {
            "n_datasets": EXACT_DATASETS, "n_points": EXACT_N_POINTS,
            "query_points": EXACT_Q_POINTS, "k": k, "chunk": chunk,
            "ds_points_padded": int(repo.ds_index.points.shape[1]),
            "query_points_padded": int(q_batch_all.points.shape[1]),
        },
        "batches": rows,
    }


def _block_mixed(outs):
    """Block on every device leaf of a mixed result list (SearchResults
    and raw arrays alike)."""
    leaves = []
    for r in outs:
        if hasattr(r, "op"):
            for x in (r.vals, r.ids, r.mask):
                if x is not None:
                    leaves.append(x)
        else:
            leaves.append(r)
    jax.block_until_ready(leaves)
    return outs


def make_mixed_pool(repo, lake, n: int, k: int, eps, seed: int = 2):
    """A declarative query pool cycling all seven ops plus a pipeline kind
    (top-3 IA datasets -> RangeP inside the winners) — the heterogeneous
    traffic shape the unified search() API exists for."""
    from repro.core import zorder as zorder_lib
    from repro.engine.query import Pipeline, Query

    rng = np.random.default_rng(seed)
    n_ds = len(lake)
    sig_fn = jax.jit(lambda p, v: zorder_lib.signature(
        p, v, repo.space_lo, repo.space_hi, 5))
    pool = []
    for i in range(n):
        c = rng.uniform(10, 90, 2).astype(np.float32)
        lo, hi = c - 4.0, c + 4.0
        kind = i % 8
        if kind == 0:
            pool.append(Query(op="range_search", r_lo=lo, r_hi=hi))
        elif kind == 1:
            pool.append(Query(op="topk_ia", r_lo=lo, r_hi=hi, k=k))
        elif kind == 2:
            q = lake[int(rng.integers(n_ds))]
            sig = np.asarray(sig_fn(jnp.asarray(q),
                                    jnp.ones(len(q), bool)))
            pool.append(Query(op="topk_gbo", q_sig=sig, k=k))
        elif kind == 3:
            q = lake[int(rng.integers(n_ds))][:64]
            pool.append(Query(op="topk_hausdorff_approx", q=q, k=k,
                              eps=eps))
        elif kind == 4:
            q = lake[int(rng.integers(n_ds))][:24]
            pool.append(Query(op="topk_hausdorff", q=q, k=k, chunk=8))
        elif kind == 5:
            pool.append(Query(op="range_points",
                              ds_id=int(rng.integers(n_ds)),
                              r_lo=lo, r_hi=hi))
        elif kind == 6:
            q = lake[int(rng.integers(n_ds))][:64]
            pool.append(Query(op="nnp", ds_id=int(rng.integers(n_ds)),
                              q=q))
        else:
            pool.append(Pipeline(
                Query(op="topk_ia", r_lo=c - 10.0, r_hi=c + 10.0, k=3),
                Query(op="range_points", r_lo=lo, r_hi=hi)))
    return pool


def bench_mixed_ops(engine, repo, lake, k, eps, repeats, *,
                    max_batch=None):
    """Mixed-op QPS sweep: ONE declarative `engine.search` call for a
    heterogeneous batch vs the per-op grouped-dispatch loop (group the
    same rows by (op, statics) by hand, one engine call per group, with
    the HOST id handoff for pipelines — the pre-redesign serving shape).
    Both sides run the SAME query rows per batch size, on the same engine
    with the result cache off, so the ratio isolates the single-entry
    planning win (shared drains, no per-op Python passes, device-side
    pipeline handoff)."""
    from collections import OrderedDict

    from repro.engine.query import Pipeline

    batches = [b for b in MIXED_BATCHES
               if max_batch is None or b <= max_batch]
    pool = make_mixed_pool(repo, lake, max(batches), k, eps)

    def grouped(items):
        out = []
        groups: "OrderedDict[tuple, list]" = OrderedDict()
        for it in items:
            if isinstance(it, Pipeline):
                key = ("pipeline", it.dataset_stage.op,
                       it.dataset_stage.statics())
            else:
                key = (it.op,) + it.statics()
            groups.setdefault(key, []).append(it)
        for key, its in groups.items():
            if key[0] == "pipeline":
                # two-call host baseline: ids leave the device per request
                s1 = engine.search([it.dataset_stage for it in its])
                for it, r1 in zip(its, s1):
                    ids = np.asarray(r1.ids)
                    safe = np.where(ids >= 0, ids, 0)
                    kk = len(ids)
                    ps = it.point_stage
                    out.append(engine.range_points(
                        safe, np.broadcast_to(ps.r_lo, (kk, 2)),
                        np.broadcast_to(ps.r_hi, (kk, 2))))
            else:
                out.extend(engine.search(its))
        return out

    rows = []
    for b in batches:
        items = pool[:b]
        # 5 best-of trials: the mixed/grouped ratio is near 1 by
        # construction (same dispatch groups), so scheduler noise on small
        # shared CPUs — especially under an 8-forced-device host mesh —
        # needs more trials than the coarser sweeps to not flip the sign
        t_mixed = _time_best(lambda: _block_mixed(engine.search(items)),
                             repeats=repeats, trials=5)
        t_grouped = _time_best(lambda: _block_mixed(grouped(items)),
                               repeats=repeats, trials=5)
        rows.append({
            "batch": b,
            "seconds_per_batch": t_mixed,
            "qps": b / t_mixed,
            "grouped_seconds": t_grouped,
            "grouped_qps": b / t_grouped,
            "speedup_vs_grouped": t_grouped / t_mixed,
        })
    return {"kinds": 8, "pipeline_every": 8, "batches": rows}


BOUND_PHASE_BATCHES = (1, 8, 32)


def bench_bound_phases(repo, q_batch_all, repeats, *, max_batch=None):
    """Fused bound-phase microbenchmark: ONE `ops.bound_grid` dispatch for
    every tree level's (B, S) frontier bounds vs the pre-fusion
    composition — one jitted `vmap(frontier_bounds)` dispatch PER level
    (the exact pass ExactHaus phases 0/1 used to issue, kept here as the
    baseline).  The record also carries the composition hand-fused under
    one jit (`legacy_onejit_seconds`) so the dispatch-overhead share of
    the win stays visible.

    Outputs are asserted numerically equal first (rtol 1e-5; the residual
    is XLA's shape-dependent FMA contraction, ~1 ulp, and the row records
    the observed max relative deviation), then timed."""
    from repro.core.search import _frontier_bound_all_levels, frontier_bounds

    max_level = min(q_batch_all.depth, repo.ds_index.depth, 3)
    fused = jax.jit(
        lambda q: _frontier_bound_all_levels(q, repo.ds_index, max_level))
    per_level = jax.jit(
        jax.vmap(frontier_bounds, in_axes=(0, None, None, None)),
        static_argnums=(2, 3))

    def legacy(q):
        LBs, UBs = [], []
        for l in range(max_level + 1):
            LB, UB = per_level(q, repo.ds_index, l, l)
            LBs.append(LB)
            UBs.append(UB)
        return jnp.stack(LBs), jnp.stack(UBs)

    def legacy_onejit_fn(q):
        bounds = jax.vmap(frontier_bounds, in_axes=(0, None, None, None))
        LBs, UBs = [], []
        for l in range(max_level + 1):
            LB, UB = bounds(q, repo.ds_index, l, l)
            LBs.append(LB)
            UBs.append(UB)
        return jnp.stack(LBs), jnp.stack(UBs)

    legacy_onejit = jax.jit(legacy_onejit_fn)

    rows = []
    for b in BOUND_PHASE_BATCHES:
        if max_batch is not None and b > max_batch:
            continue
        q = jax.tree.map(lambda x: x[:b], q_batch_all)
        f = jax.block_until_ready(fused(q))
        g = jax.block_until_ready(legacy(q))
        max_rel = 0.0
        for a, c in zip(jax.tree.leaves(f), jax.tree.leaves(g)):
            a, c = np.asarray(a), np.asarray(c)
            np.testing.assert_allclose(a, c, rtol=1e-5)
            denom = np.maximum(np.abs(c), np.float32(1e-30))
            max_rel = max(max_rel, float(np.max(np.abs(a - c) / denom)))
        t_fused = _time_best(lambda: fused(q), repeats=repeats)
        t_legacy = _time_best(lambda: legacy(q), repeats=repeats)
        t_onejit = _time_best(lambda: legacy_onejit(q), repeats=repeats)
        rows.append({
            "batch": b,
            "fused_seconds": t_fused,
            "legacy_seconds": t_legacy,
            "legacy_onejit_seconds": t_onejit,
            "speedup_vs_legacy": t_legacy / t_fused,
            "speedup_vs_legacy_onejit": t_onejit / t_fused,
            "max_rel_deviation": max_rel,
        })
    return {
        "levels": max_level + 1,
        "n_slots": int(repo.ds_index.radii.shape[0]),
        "batches": rows,
    }


def bench_adaptive_serving(engine, repo, lake, k, eps, *,
                           max_batch=None, trials=3, seed=3):
    """Serving A/B: queue-depth-driven adaptive batching window vs the
    seed's fixed max-wait window, same engine, same mixed traffic.

    Two load points per mode: **low** (requests paced at 3x the static
    mode's measured per-request service time — the window policy IS the
    latency here) and
    **saturating** (the whole request pool sits in the queue BEFORE the
    dispatcher starts — batches must fill from queue depth alone; filling
    the queue first removes the submitter-vs-dispatcher thread race,
    which would otherwise measure Python thread scheduling instead of
    the batching policy).  Trials alternate static/adaptive servers so
    machine drift cancels out of the ratio; each (mode, load) keeps its
    best-QPS trial's record (QPS + p50/p99 ms from the server's
    per-request latency log).  Two untimed warm passes precede the trials
    so compile cost never lands in a row."""
    from repro.launch.serve_search import Request, SearchServer
    from repro.engine.query import Pipeline

    server_batch = 16 if max_batch is None else min(16, max_batch)
    n_requests = 6 * server_batch
    # saturating trials cycle the pool 4x: a longer timed window shrinks
    # the relative scheduler noise on what is otherwise a ~tie (under a
    # deep queue both policies fill every batch instantly)
    sat_rounds = 4
    pool = make_mixed_pool(repo, lake, n_requests, k, eps, seed=seed)

    def _row(server, dt, n):
        return {
            "qps": n / dt,
            "p50_ms": server.stats.p50_ms,
            "p99_ms": server.stats.p99_ms,
            "mean_batch": server.stats.mean_batch,
        }

    def run_paced(adaptive, gap_s):
        server = SearchServer(engine, max_batch=server_batch,
                              max_wait_ms=2.0, adaptive=adaptive).start()
        try:
            t0 = time.perf_counter()
            futures = []
            for i, q in enumerate(pool):
                # pace submissions against the trial clock (not sleep
                # accumulation) so the offered load stays what it claims
                lag = t0 + i * gap_s - time.perf_counter()
                if lag > 0:
                    time.sleep(lag)
                futures.append(server.submit_query(q))
            for f in futures:
                f.result(timeout=600)
            return _row(server, time.perf_counter() - t0, n_requests)
        finally:
            server.stop()

    def run_saturating(adaptive):
        # pre-fill the queue, THEN start the dispatcher: queue depth is
        # the whole trial's requests at t0, so every drain sees genuine
        # saturation
        server = SearchServer(engine, max_batch=server_batch,
                              max_wait_ms=2.0, adaptive=adaptive)
        reqs = []
        for q in pool * sat_rounds:
            op = "pipeline" if isinstance(q, Pipeline) else q.op
            req = Request(op, q)
            reqs.append(req)
            server._queue.put(req)
        t0 = time.perf_counter()
        server.start()
        try:
            for req in reqs:
                req.future.result(timeout=600)
            return _row(server, time.perf_counter() - t0, len(reqs))
        finally:
            server.stop()

    rec = {"n_requests": n_requests,
           "n_requests_saturating": n_requests * sat_rounds,
           "max_batch": server_batch, "loads": {}}
    # warm every dispatch group once off the measured path (shared engine:
    # both modes then time steady-state dispatch, not compilation), and
    # measure the per-request service time that paces the low-load trials
    # from the STATIC run — the seed policy defines the load scale, and
    # unlike the adaptive run its throughput doesn't include the
    # depth-scaled overfill win (pacing off the faster adaptive rate
    # would quietly turn "low" load into near-saturation)
    run_saturating(True)
    # best of two: the first static pass may still compile its own
    # (smaller) per-drain bucket shapes on the shared engine, and a
    # one-off slow pass here would mis-scale every low-load trial
    service_s = 1.0 / max(run_saturating(False)["qps"],
                          run_saturating(False)["qps"])
    # interleave the modes trial-by-trial (fresh server each, shared warm
    # engine) so machine drift lands on both sides of the ratio equally;
    # best-of-trials per (load, mode) like the other serving-shaped sweeps
    runs: dict = {}
    for _ in range(trials):
        for mode, adaptive in (("static", False), ("adaptive", True)):
            runs.setdefault(("saturating", mode), []).append(
                run_saturating(adaptive))
            # low-load trials are short and pacer-dominated, so the
            # policy signal is small against scheduler noise — sample
            # twice per round (best-of keeps the cleanest run per mode)
            for _ in range(2):
                runs.setdefault(("low", mode), []).append(
                    run_paced(adaptive, 3.0 * service_s))
    for (load, mode), rows in runs.items():
        rec["loads"].setdefault(load, {})[mode] = max(
            rows, key=lambda r: r["qps"])
    for load, row in rec["loads"].items():
        row["adaptive_qps_ratio"] = (row["adaptive"]["qps"]
                                     / row["static"]["qps"])
    return rec


def bench_replica_scaling(repo, lake, k, eps, *, repeats, max_batch=None,
                          data_shards=2, replica_counts=(1, 2, 4)):
    """Replica-parallel serving sweep at fixed repository bytes per device:
    R replica groups x D data shards, R in `replica_counts`, D fixed.

    Two throughput signals per R, both recorded:

      * ``qps_serving`` — honest end-to-end saturated serving QPS: the
        whole mixed pool sits in the server queue BEFORE the dispatcher
        starts (queue depth alone fills the batches), one
        ``engine.search`` per drain on the ReplicatedQueryEngine.  On a
        machine whose host "devices" time-slice fewer physical cores than
        R x D (CI, laptops — see ``host_cores``), replica groups serialize
        and this number DROPS with R; on real hardware each group owns its
        devices and it tracks the projection below.
      * ``qps_projected_parallel`` — B / t_group(R), where t_group(R) is
        MEASURED wall time of one replica group's program: a 1 x D
        sharded engine answering ``pool[:B//R]`` in one search() call.
        By the bit-identity construction that IS the program each group
        runs (the pool cycles its 8 kinds round-robin, so a 1/R prefix
        reproduces each group's per-dispatch row mix).  With groups on
        disjoint devices the slowest group bounds the batch -> QPS =
        B / t_group.  Monotonically increasing in R because t_group grows
        with rows (fixed per-dispatch overhead amortizes).

    The per-device repository bytes column is the point of fixing D: it
    stays constant across the sweep — replicas buy throughput, not memory.
    """
    from repro.engine import ReplicatedQueryEngine
    from repro.engine.query import Pipeline
    from repro.launch.serve_search import Request, SearchServer

    n_dev = jax.device_count()
    counts = [r for r in replica_counts if r * data_shards <= n_dev]
    server_batch = 16 if max_batch is None else min(16, max_batch)
    # B rows per measured group dispatch: divisible by every R and by the
    # pool's 8 kinds so each 1/R prefix keeps the full round-robin mix
    b_rows = 64 if max_batch is None else max(8, max_batch)
    sat_rounds = 4
    pool = make_mixed_pool(repo, lake, b_rows, k, eps, seed=3)

    def run_saturating(engine):
        server = SearchServer(engine, max_batch=server_batch,
                              max_wait_ms=2.0, adaptive=True)
        reqs = []
        for q in pool * sat_rounds:
            op = "pipeline" if isinstance(q, Pipeline) else q.op
            req = Request(op, q)
            reqs.append(req)
            server._queue.put(req)
        t0 = time.perf_counter()
        server.start()
        try:
            for req in reqs:
                req.future.result(timeout=600)
            dt = time.perf_counter() - t0
            return {"qps": len(reqs) / dt,
                    "p50_ms": server.stats.p50_ms,
                    "p99_ms": server.stats.p99_ms,
                    "mean_batch": server.stats.mean_batch}
        finally:
            server.stop()

    # one replica group's program: a 1 x D engine on a 1/R row prefix
    group_eng = ShardedQueryEngine(repo, mesh=data_mesh(data_shards),
                                   result_cache_size=0)
    ds_arrays = (group_eng.repo.ds_index, group_eng.repo.ds_sigs,
                 group_eng.repo.ds_valid)

    rows = []
    for r in counts:
        engine = ReplicatedQueryEngine(repo, n_replicas=r,
                                       n_data=data_shards,
                                       result_cache_size=0)
        run_saturating(engine)                       # warm every drain shape
        serving = max((run_saturating(engine) for _ in range(2)),
                      key=lambda x: x["qps"])
        g_rows = b_rows // r
        t_group = _time_best(
            lambda n=g_rows: _block_mixed(group_eng.search(pool[:n])),
            repeats=repeats)
        per_dev = repo_device_bytes(
            (engine.repo.ds_index, engine.repo.ds_sigs, engine.repo.ds_valid))
        rows.append({
            "replicas": r,
            "data_shards": data_shards,
            "devices": r * data_shards,
            "serving": serving,
            "group_rows": g_rows,
            "group_seconds_per_batch": t_group,
            "qps_projected_parallel": b_rows / t_group,
            "per_device_repo_bytes": max(per_dev.values()),
        })

    # idle-devices baseline: the 1 x D sharded engine serving the same
    # traffic with the other devices unused — what replicas improve on
    baseline_eng = ShardedQueryEngine(repo, mesh=data_mesh(data_shards),
                                      result_cache_size=0)
    run_saturating(baseline_eng)
    baseline = run_saturating(baseline_eng)

    proj = [row["qps_projected_parallel"] for row in rows]
    return {
        "method": ("qps_serving is the end-to-end pre-filled-queue drain on "
                   "the replicated engine (time-sliced on hosts with fewer "
                   "cores than devices); qps_projected_parallel = "
                   "batch_rows / measured wall time of one replica group's "
                   "program (a 1xD engine on the group's row share), the "
                   "device-parallel throughput bound"),
        "host_cores": os.cpu_count(),
        "batch_rows": b_rows,
        "n_requests_saturating": b_rows * sat_rounds,
        "baseline_1xD_idle_devices": baseline,
        "sweep": rows,
        "replica_qps_monotonic": all(a <= b for a, b in zip(proj, proj[1:])),
    }


def bench_mutation_sweep(lake, k, *, repeats, max_batch=None):
    """Live-repository serving under churn: closed-loop mixed-query QPS
    on a LiveRepository with NO mutations (baseline) vs the SAME load
    while a churn thread streams ingest / replace / delete BURSTS
    through the server's mutation lane (the two-stage pipeline: each
    burst's prepare overlaps the in-flight query segment and the whole
    burst publishes as ONE coalesced epoch at its stream position).

    Both phases use the same closed-loop feeder — a bounded in-flight
    window of queries, so drains stay saturated without pre-filling the
    whole phase (a pre-filled queue would push every mutation behind
    ALL queries and nothing would interleave).  Each phase runs on a
    FRESH server with fresh ``ServerStats``, so per-phase mean_batch
    actually shows the segment splits churn causes.

    The mutation stream keeps the safe id discipline: replaces rotate
    over original ids (always live), deletes only ever target slots the
    stream itself ingested (and only after their publish resolved) — so
    every point query in the pool stays valid no matter how the bursts
    interleave with the drains.

    Also records the mutation lane itself: per-publish latency
    percentiles, coalescing and prepare-overlap counters, bytes
    uploaded (placement accounting: single-dataset payloads only —
    never a full re-upload), epoch movement, and tier growth.
    """
    import threading
    from collections import deque

    from repro.engine import LiveRepository
    from repro.engine.query import Pipeline
    from repro.launch.serve_search import Request, SearchServer

    live = LiveRepository(lake, leaf_capacity=16, theta=5,
                          remove_outliers=False, result_cache_size=0)
    eps = float(zorder.default_epsilon(live.repo.space_lo,
                                       live.repo.space_hi, 5))
    # deeper drains than the query-only serving bench: under churn every
    # mutation run SPLITS its drain into separate engine calls, so the
    # per-call planning/dispatch overhead amortizes over the drain depth
    # — depth 32 keeps post-split segments as large as the query-only
    # bench's whole drains
    server_batch = 32 if max_batch is None else min(32, max_batch)
    b_rows = 64 if max_batch is None else max(8, max_batch)
    # 6 pool rounds per measured phase: long enough that one drain of
    # warm-up jitter can't move the phase QPS by more than a few percent
    sat_rounds = 6
    burst = 8
    window = 4 * server_batch
    pool = make_mixed_pool(live.repo, lake, b_rows, k, eps, seed=3)
    rng = np.random.default_rng(11)
    payloads = [(lake[int(rng.integers(len(lake)))]
                 + rng.normal(0, 0.5, 2).astype(np.float32))
                for _ in range(8)]
    counts = {"applied": 0, "payload": 0}
    own: list = []                          # slots the churn ingested

    def churn(server, stop):
        i = counts["applied"]
        while not stop.is_set():
            futs = []
            for _ in range(burst):          # one back-to-back burst
                kind = i % 3
                if kind == 1:
                    futs.append(server.submit_mutation(
                        "replace", ds_id=int(i // 3) % len(lake),
                        points=payloads[(i + 1) % len(payloads)]))
                    counts["payload"] += 1
                elif kind == 2 and own:
                    futs.append(server.submit_mutation(
                        "delete", ds_id=own.pop(0)))
                else:
                    futs.append(server.submit_mutation(
                        "ingest", points=payloads[i % len(payloads)]))
                    counts["payload"] += 1
                i += 1
            for f in futs:
                out = f.result(timeout=600)
                counts["applied"] += 1
                if isinstance(out, int) and out not in range(len(lake)):
                    own.append(out)         # a fresh ingest slot

    def run_phase(mutate: bool):
        server = SearchServer(live=live, max_batch=server_batch,
                              max_wait_ms=2.0, adaptive=True)
        n_total = len(pool) * sat_rounds
        server.start()
        stop = threading.Event()
        thread = None
        if mutate:
            thread = threading.Thread(target=churn, args=(server, stop),
                                      daemon=True)
        inflight: deque = deque()
        reqs = 0
        t0 = time.perf_counter()
        if thread is not None:
            thread.start()
        try:
            for n in range(n_total):
                q = pool[n % len(pool)]
                op = "pipeline" if isinstance(q, Pipeline) else q.op
                req = Request(op, q)
                server._queue.put(req)
                inflight.append(req)
                reqs += 1
                if len(inflight) >= window:
                    inflight.popleft().future.result(timeout=600)
            while inflight:
                inflight.popleft().future.result(timeout=600)
            dt = time.perf_counter() - t0
        finally:
            # join BEFORE stopping: the last burst's futures must still
            # be served, or its submitted-but-unapplied mutations would
            # skew the placement accounting
            stop.set()
            if thread is not None:
                thread.join(timeout=120)
            server.stop()
        return {"qps": reqs / dt,
                "p50_ms": server.stats.p50_ms,
                "p99_ms": server.stats.p99_ms,
                "mean_batch": server.stats.mean_batch,
                "mutations_in_phase": server.stats.mutations}

    # warm both lanes off the measured path: the query drains compile
    # their bucket shapes; one ingest/replace/delete probe compiles the
    # row-build stages, the group-of-1 updater, AND the tier growth
    # (128 datasets fill the initial ladder tier exactly, so the first
    # ingest doubles it here, not mid-measurement); coalesced groups of
    # {2, 4, 8} compile the batched publish buckets the bursts will hit
    run_phase(mutate=False)
    wid = live.ingest(payloads[0])
    live.replace(wid, payloads[1])
    live.delete(wid)
    for width in (2, 4, 8):
        group = live.prepare_group(
            [("ingest", None, payloads[i % len(payloads)])
             for i in range(width)])
        sids = live.publish_group(group)
        live.publish_group(live.prepare_group(
            [("delete", sid, None) for sid in sids]))
    live.bytes_uploaded = 0
    epoch0, layout0 = live.epoch, getattr(live.engine.dispatch,
                                          "repo_epoch", 0)
    estats = live.engine.stats
    pub0 = len(estats.publish_seconds)
    mc0 = estats.mutations_coalesced
    ov0 = estats.prepare_overlap_seconds

    baseline = max((run_phase(mutate=False) for _ in range(2)),
                   key=lambda r: r["qps"])
    under = max((run_phase(mutate=True) for _ in range(2)),
                key=lambda r: r["qps"])

    pub_ms = sorted(1e3 * x for x in estats.publish_seconds[pub0:])
    pct = lambda p: pub_ms[min(len(pub_ms) - 1,          # noqa: E731
                               int(p * (len(pub_ms) - 1)))] if pub_ms else 0.0
    geom = live.geometry
    per_mutation = geom.point_capacity * (4 * geom.dim + 1)
    return {
        "method": ("closed-loop mixed serving (bounded in-flight query "
                   "window) on a LiveRepository; 'under_mutation' repeats "
                   "the load while a churn thread submits back-to-back "
                   "8-mutation bursts through the server lane — each "
                   "burst prepares concurrently with the in-flight "
                   "segment and publishes as one coalesced epoch; "
                   "mutation latency is per-PUBLISH wall time"),
        "n_requests": b_rows * sat_rounds,
        "in_flight_window": window,
        "burst": burst,
        "baseline": baseline,
        "under_mutation": under,
        "qps_ratio_under_mutation": under["qps"] / baseline["qps"],
        "mutations_applied": counts["applied"],
        "mutations_coalesced": estats.mutations_coalesced - mc0,
        "publishes": len(pub_ms),
        "mutation_mean_ms": (sum(pub_ms) / len(pub_ms)) if pub_ms else 0.0,
        "mutation_p50_ms": pct(0.50),
        "mutation_p99_ms": pct(0.99),
        "prepare_overlap_seconds": estats.prepare_overlap_seconds - ov0,
        "epoch_delta": live.epoch - epoch0,
        "layout_epoch_delta": getattr(live.engine.dispatch, "repo_epoch", 0)
                              - layout0,
        "bytes_uploaded": live.bytes_uploaded,
        "bytes_per_payload_mutation": per_mutation,
        # placement accounting: every upload is ONE padded dataset row
        # (ingest/replace); deletes and growth upload nothing
        "no_full_reupload": live.bytes_uploaded
                            == counts["payload"] * per_mutation,
        "slots": live.n_slots,
        "live_datasets": len(live.live_ids),
    }


JOIN_BATCHES = (1, 2, 4, 8, 16, 32)
JOIN_Q_POINTS = 64
JOIN_CHUNK = 16


def bench_join_sweep(repo, lake, k, *, repeats, max_batch=None):
    """Joinable dataset search: batched QPS + bound-phase pruning.

    For each joinable op (``topk_overlap`` / ``topk_coverage``), batch
    1..32 query point sets answered as ONE `engine.search` call each
    (bound phase + shared-order chunked refine in a single dispatch),
    against the per-query dispatch loop baseline.  Every row also
    records the refine-loop work actually done: the mean bound-phase
    pruned fraction (1 - exact evaluations / valid slots) — the Eq.-4
    bound family earning its keep on the joinable ops.

    A serving segment rides along: a PRE-FILLED saturating queue (the
    whole burst visible to the first drain — in-flight feeding would
    measure the feeder) of joinable queries mixed with dataset→dataset
    pipeline requests (top-k IA winners re-ranked by overlap), drained
    through `SearchServer` / the single mixed `engine.search` path.
    """
    from repro.engine import Pipeline, Query
    from repro.launch.serve_search import Request, SearchServer, _to_query

    batches = [b for b in JOIN_BATCHES
               if max_batch is None or b <= max_batch]
    n_pool = max(batches)
    engine = QueryEngine(repo, result_cache_size=0,
                         default_chunk=JOIN_CHUNK)
    n_valid = int(np.asarray(repo.ds_valid).sum())
    qsets = [np.asarray(lake[i % len(lake)][:JOIN_Q_POINTS], np.float32)
             for i in range(n_pool)]

    rec = {
        "method": ("engine.search batches of B joinable queries (one "
                   "bound+refine dispatch) vs a per-query dispatch "
                   "loop; pruned fraction = 1 - exact evaluations / "
                   f"valid slots, refine chunk {JOIN_CHUNK}"),
        "k": k,
        "n_valid": n_valid,
        "chunk": JOIN_CHUNK,
        "ops": {},
    }
    for op in ("topk_overlap", "topk_coverage"):
        def one(i, op=op):
            return engine.search([Query(op=op, q=qsets[i % n_pool], k=k)])

        n_base = min(n_pool, 8)
        t = _time(lambda: [one(i) for i in range(n_base)],
                  repeats=max(2, repeats // 2))
        baseline_qps = n_base / t

        rows = []
        for b in batches:
            qs = [Query(op=op, q=qsets[i], k=k) for i in range(b)]
            res_box = {}

            def run(qs=qs, res_box=res_box):
                res_box["res"] = engine.search(qs)
                return res_box["res"][0].vals

            tb = _time_best(run, repeats=repeats)
            stats = [r.stats for r in res_box["res"]]
            pruned = sum(s.pruned_fraction for s in stats) / len(stats)
            rows.append({
                "batch": b,
                "seconds_per_batch": tb,
                "qps": b / tb,
                "speedup_vs_loop": (b / tb) / baseline_qps,
                "pruned_fraction": pruned,
                "evaluated_mean": (sum(s.exact_evaluations for s in stats)
                                   / len(stats)),
            })
        rec["ops"][op] = {
            "baseline_qps": baseline_qps,
            "baseline_loop_size": n_base,
            "batches": rows,
        }

    # serving segment: pre-filled saturating queue of joinable +
    # dataset→dataset pipeline requests through the mixed search() drain
    n_req = 4 * max(batches)
    reqs = []
    for i in range(n_req):
        q = qsets[i % n_pool]
        kind = i % 3
        if kind == 0:
            reqs.append(("topk_overlap", dict(q=q, k=k)))
        elif kind == 1:
            reqs.append(("topk_coverage", dict(q=q, k=k)))
        else:
            c = q.mean(axis=0)
            reqs.append(("pipeline", dict(
                dataset=dict(op="topk_ia", r_lo=c - 10.0, r_hi=c + 10.0,
                             k=min(8, n_valid)),
                point=dict(op="topk_overlap", q=q, k=min(3, k)))))
    serve_engine = QueryEngine(repo, result_cache_size=0,
                               default_chunk=JOIN_CHUNK)

    def serve_once():
        server = SearchServer(serve_engine, max_batch=max(batches),
                              max_wait_ms=2.0, adaptive=True)
        items = [Request(op, _to_query(op, p)) for op, p in reqs]
        for r in items:
            server._queue.put(r)
        t0 = time.perf_counter()
        server.start()
        try:
            for r in items:
                r.future.result(timeout=600)
            dt = time.perf_counter() - t0
        finally:
            server.stop()
        return {"qps": n_req / dt, "p50_ms": server.stats.p50_ms,
                "p99_ms": server.stats.p99_ms,
                "mean_batch": server.stats.mean_batch}

    serve_once()                             # warm the bucket ladder
    rec["serving"] = max((serve_once() for _ in range(2)),
                         key=lambda r: r["qps"])
    rec["serving"]["n_requests"] = n_req
    rec["serving"]["mix"] = ("1/3 topk_overlap, 1/3 topk_coverage, "
                             "1/3 IA->overlap rerank pipeline")
    return rec


def bench_exacthaus(repo, qi, k, repeats):
    """Sharded ExactHaus: single-query latency + per-device resident
    repository bytes at 1/3/8 shards (clipped to the available devices).

    The memory column is the point of the row: the dispatcher keeps NO
    replicated repository copy, so the per-device dataset bytes drop
    ~1/N with the shard count while the upper tree stays replicated.
    Includes the unsharded LocalDispatcher pipeline as the reference.
    """
    le = QueryEngine(repo, result_cache_size=0)
    t = _time(lambda: le.topk_hausdorff(qi, k)[0], repeats=repeats)
    rec = {
        "k": k,
        "local": {
            "seconds_per_query": t,
            "qps": 1.0 / t,
            "per_device_repo_bytes": max(repo_device_bytes(le.repo).values()),
        },
        "rows": [],
    }
    for s in EXACT_SHARD_COUNTS:
        if s > jax.device_count():
            print(f"[bench_engine] exacthaus: skipping {s} shards "
                  f"({jax.device_count()} devices available)")
            continue
        e = ShardedQueryEngine(repo, mesh=data_mesh(s),
                               result_cache_size=0)
        last = {}

        def run(e=e, last=last):
            vals, _, last["stats"] = e.topk_hausdorff(qi, k)
            return vals

        t = _time(run, repeats=repeats)
        stats = last["stats"]
        per_dev = repo_device_bytes(e.dispatch.repo)
        total = sum(x.nbytes for x in jax.tree.leaves(e.dispatch.repo))
        rec["rows"].append({
            "shards": s,
            "seconds_per_query": t,
            "qps": 1.0 / t,
            "per_device_repo_bytes": max(per_dev.values()),
            "total_repo_bytes": total,
            "exact_evaluations": stats.exact_evaluations,
        })
    return rec


def _time(fn, *, repeats: int, warmup: int = 2) -> float:
    for _ in range(warmup):
        jax.block_until_ready(fn())
    t0 = time.perf_counter()
    for _ in range(repeats):
        out = fn()
    jax.block_until_ready(out)
    return (time.perf_counter() - t0) / repeats


def _time_best(fn, *, repeats: int, trials: int = 3) -> float:
    """Best-of-`trials` mean timing — robust to scheduler noise spikes on
    small shared CPUs (one descheduled trial can't poison a committed
    row)."""
    return min(_time(fn, repeats=repeats) for _ in range(trials))


def _query_pool(repo, datasets, n: int, seed: int = 0):
    rng = np.random.default_rng(seed)
    lo = rng.uniform(0, 80, (n, 2)).astype(np.float32)
    hi = lo + rng.uniform(2, 20, (n, 2)).astype(np.float32)
    sig_fn = jax.jit(lambda p, v: zorder.signature(
        p, v, repo.space_lo, repo.space_hi, 5))
    sigs = []
    for i in range(n):
        q = datasets[i % len(datasets)]
        sigs.append(np.asarray(sig_fn(jnp.asarray(q),
                                      jnp.ones(len(q), bool))))
    return lo, hi, np.stack(sigs)


def bench_op(name, baseline_one, engine_batch, pool_size, *, repeats=8):
    """QPS for per-query loop vs engine batches; returns the op's record."""
    # baseline: Python loop, one op call per query (seed serving shape)
    n_base = min(pool_size, 32)

    def loop():
        out = None
        for i in range(n_base):
            out = baseline_one(i)
        return out

    t = _time(loop, repeats=max(2, repeats // 2))
    baseline_qps = n_base / t

    rows = []
    for b in BATCHES:
        tb = _time(lambda: engine_batch(b), repeats=repeats)
        rows.append({
            "batch": b,
            "seconds_per_batch": tb,
            "qps": b / tb,
            "speedup_vs_loop": (b / tb) / baseline_qps,
        })
    return {
        "baseline_qps": baseline_qps,
        "baseline_loop_size": n_base,
        "batches": rows,
    }


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--out", default=None,
                    help="output json (default BENCH_engine.json, or "
                         "BENCH_engine_sharded.json with --sharded)")
    ap.add_argument("--datasets", type=int, default=128)
    ap.add_argument("--repeats", type=int, default=8)
    ap.add_argument("--max-batch", type=int, default=None,
                    help="trim every batch sweep to <= this size (CI "
                         "bench-smoke uses a tiny cap so the scripts "
                         "stay cheap but can't rot)")
    ap.add_argument("--sharded", action="store_true",
                    help="benchmark the ShardedQueryEngine over a 1-D data "
                         "mesh spanning all local devices")
    ap.add_argument("--replica-sweep", action="store_true",
                    help="run ONLY the replica-parallel serving sweep "
                         "(ReplicatedQueryEngine at R x 2 for R in 1/2/4; "
                         "force 8 host devices with REPRO_HOST_DEVICES=8) "
                         "-> BENCH_engine_replica.json")
    ap.add_argument("--mutation-sweep", action="store_true",
                    help="run ONLY the live-repository churn benchmark "
                         "(saturated mixed serving with and without a "
                         "background ingest/replace/delete stream) "
                         "-> BENCH_engine_live.json")
    ap.add_argument("--join-sweep", action="store_true",
                    help="run ONLY the joinable-op benchmark (batched "
                         "overlap/coverage QPS + bound-phase pruned "
                         "fraction + a pre-filled mixed serving segment) "
                         "-> BENCH_engine_join.json")
    args = ap.parse_args(argv)
    compile_cache.enable()
    if args.max_batch is not None:
        global BATCHES
        BATCHES = tuple(b for b in BATCHES if b <= args.max_batch)
    if args.out is None:
        args.out = ("BENCH_engine_live.json" if args.mutation_sweep
                    else "BENCH_engine_join.json" if args.join_sweep
                    else "BENCH_engine_replica.json" if args.replica_sweep
                    else "BENCH_engine_sharded.json" if args.sharded
                    else "BENCH_engine.json")

    lake = synthetic.trajectory_repository(args.datasets, seed=0,
                                           n_points=(100, 400))
    if args.mutation_sweep:
        rec = {
            "bench": "engine_live",
            "n_datasets": args.datasets,
            "n_devices": jax.device_count(),
            "mutation_sweep": bench_mutation_sweep(
                lake, 10, repeats=max(2, args.repeats // 2),
                max_batch=args.max_batch),
        }
        ms = rec["mutation_sweep"]
        summary = {
            "qps_baseline": round(ms["baseline"]["qps"], 1),
            "qps_under_mutation": round(ms["under_mutation"]["qps"], 1),
            "qps_ratio_under_mutation":
                round(ms["qps_ratio_under_mutation"], 3),
            "p99_ms_under_mutation": round(ms["under_mutation"]["p99_ms"], 1),
            "mutation_p50_ms": round(ms["mutation_p50_ms"], 1),
            "mutation_p99_ms": round(ms["mutation_p99_ms"], 1),
            "mutations_applied": ms["mutations_applied"],
            "mutations_coalesced": ms["mutations_coalesced"],
            "prepare_overlap_seconds":
                round(ms["prepare_overlap_seconds"], 3),
            "no_full_reupload": ms["no_full_reupload"],
        }
        rec["summary"] = summary
        Path(args.out).write_text(json.dumps(rec, indent=2))
        print(json.dumps(summary, indent=2))
        return rec
    repo, info = build_repository(lake, leaf_capacity=16, theta=5,
                                  remove_outliers=False)

    if args.join_sweep:
        rec = {
            "bench": "engine_join",
            "n_datasets": args.datasets,
            "n_devices": jax.device_count(),
            # k=5: the 10th-best join score of a 64-point trajectory probe
            # is typically 0 (few walks cross it), which pins tau at 0 and
            # disables pruning entirely; at k=5 tau is positive and the
            # bound phase actually earns its keep
            "join_sweep": bench_join_sweep(
                repo, lake, 5, repeats=max(2, args.repeats // 2),
                max_batch=args.max_batch),
        }
        js = rec["join_sweep"]
        top = {op: js["ops"][op]["batches"][-1] for op in js["ops"]}
        summary = {
            "n_valid": js["n_valid"],
            "qps_top_batch": {op: round(row["qps"], 1)
                              for op, row in top.items()},
            "speedup_top_batch": {op: round(row["speedup_vs_loop"], 2)
                                  for op, row in top.items()},
            "pruned_fraction": {op: round(row["pruned_fraction"], 3)
                                for op, row in top.items()},
            "serving_qps": round(js["serving"]["qps"], 1),
            "serving_mean_batch": round(js["serving"]["mean_batch"], 2),
        }
        rec["summary"] = summary
        Path(args.out).write_text(json.dumps(rec, indent=2))
        print(json.dumps(summary, indent=2))
        return rec

    if args.replica_sweep:
        eps = float(zorder.default_epsilon(repo.space_lo, repo.space_hi, 5))
        rec = {
            "bench": "engine_replica",
            "n_datasets": args.datasets,
            "n_devices": jax.device_count(),
            "replica_scaling": bench_replica_scaling(
                repo, lake, 10, eps, repeats=max(2, args.repeats // 2),
                max_batch=args.max_batch),
        }
        summary = {
            "replica_qps_monotonic":
                rec["replica_scaling"]["replica_qps_monotonic"],
            "qps_projected": {
                str(row["replicas"]): round(row["qps_projected_parallel"], 1)
                for row in rec["replica_scaling"]["sweep"]},
            "qps_serving": {
                str(row["replicas"]): round(row["serving"]["qps"], 1)
                for row in rec["replica_scaling"]["sweep"]},
        }
        rec["summary"] = summary
        Path(args.out).write_text(json.dumps(rec, indent=2))
        print(json.dumps(summary, indent=2))
        return rec
    # result cache OFF: the sweeps repeat identical inputs to time
    # dispatch, which the result LRU would short-circuit
    if args.sharded:
        engine = ShardedQueryEngine(repo, result_cache_size=0)
        print(f"[bench_engine] sharded: {engine.dispatch.n_shards} shard(s) "
              f"x {engine.dispatch.shard_slots} dataset slots")
    else:
        engine = QueryEngine(repo, result_cache_size=0)
    n_pool = max(BATCHES)
    lo, hi, sigs = _query_pool(repo, lake, n_pool)
    lo_j, hi_j, sigs_j = jnp.asarray(lo), jnp.asarray(hi), jnp.asarray(sigs)
    eps = float(zorder.default_epsilon(repo.space_lo, repo.space_hi, 5))
    k = 10

    # small exemplar queries for ApproHaus (the serving shape: Q ~ 64 pts)
    q_sets = [lake[i % len(lake)][:64] for i in range(n_pool)]
    q_batch_all = engine.build_queries(q_sets)

    def q_slice(b):
        return jax.tree.map(lambda x: x[:b], q_batch_all)

    ds_ids = np.arange(n_pool, dtype=np.int32) % args.datasets

    ops = {}

    ops["range_search"] = bench_op(
        "range_search",
        lambda i: search.range_search(repo, lo_j[i], hi_j[i])[0],
        lambda b: engine.range_search(lo[:b], hi[:b]),
        n_pool, repeats=args.repeats,
    )
    ops["topk_ia"] = bench_op(
        "topk_ia",
        lambda i: search.topk_ia(repo, lo_j[i], hi_j[i], k)[0],
        lambda b: engine.topk_ia(lo[:b], hi[:b], k),
        n_pool, repeats=args.repeats,
    )
    ops["topk_gbo"] = bench_op(
        "topk_gbo",
        lambda i: search.topk_gbo(repo, sigs_j[i], k)[0],
        lambda b: engine.topk_gbo(sigs[:b], k),
        n_pool, repeats=args.repeats,
    )
    ops["topk_hausdorff_approx"] = bench_op(
        "topk_hausdorff_approx",
        lambda i: search.topk_hausdorff_approx(
            repo, jax.tree.map(lambda x: x[i], q_batch_all), k, eps)[0],
        lambda b: engine.topk_hausdorff_approx(q_slice(b), k, eps),
        n_pool, repeats=max(2, args.repeats // 2),
    )
    ops["range_points"] = bench_op(
        "range_points",
        lambda i: point_search.range_points(
            jax.tree.map(lambda x: x[int(ds_ids[i])], repo.ds_index),
            lo_j[i], hi_j[i])[0],
        lambda b: engine.range_points(ds_ids[:b], lo[:b], hi[:b]),
        n_pool, repeats=args.repeats,
    )

    exact = None
    if args.sharded:
        # single-query ExactHaus across shard counts: latency + per-device
        # resident repository memory (the scale-out win of the sharded
        # branch-and-bound; no replicated copy remains)
        qi = jax.tree.map(lambda x: x[0], q_batch_all)
        exact = bench_exacthaus(repo, qi, k, max(2, args.repeats // 2))

    # batched ExactHaus QPS sweep (both modes): one shared phase-2 work
    # frontier per dispatch vs the per-query dispatch loop
    if args.sharded:
        exact_ctor = lambda r: ShardedQueryEngine(r, result_cache_size=0)
    else:
        exact_ctor = lambda r: QueryEngine(r, result_cache_size=0)
    exact_batched = bench_exacthaus_batched(
        exact_ctor, max(2, args.repeats // 2), max_batch=args.max_batch)

    # mixed-op declarative batches through the unified search() entry
    # point vs the per-op grouped-dispatch loop, on the main corpus
    mixed = bench_mixed_ops(engine, repo, lake, k, eps,
                            max(2, args.repeats // 2),
                            max_batch=args.max_batch)

    # fused all-levels bound pass vs the per-level composition (the
    # ExactHaus phase-0/1 hot path), on the main corpus query batch
    bound_phases = bench_bound_phases(repo, q_batch_all, args.repeats,
                                      max_batch=args.max_batch)

    # serving A/B: adaptive queue-depth window vs the static max-wait
    # window, mixed traffic at low and saturating load
    serving = bench_adaptive_serving(engine, repo, lake, k, eps,
                                     max_batch=args.max_batch,
                                     trials=max(7, args.repeats // 2))

    def speedup_at(rec_op, b):
        """(actual_batch, speedup) for the largest swept batch <= b — the
        key is NAMED with the actual batch so a --max-batch smoke record
        can never be misread as a full-size speedup."""
        rows = [r for r in rec_op["batches"] if r["batch"] <= b]
        return (rows[-1]["batch"], rows[-1]["speedup_vs_loop"]) if rows \
            else (None, None)

    summary = {}
    for name, rec_op in ops.items():
        b, s = speedup_at(rec_op, 64)
        summary[f"{name}_speedup_at_{b}"] = s
    b, s = speedup_at(exact_batched, 32)
    summary[f"exact_hausdorff_batched_speedup_at_{b}"] = s
    mrows = [r for r in mixed["batches"] if r["batch"] <= 32]
    if mrows:
        summary[f"mixed_ops_speedup_at_{mrows[-1]['batch']}"] = \
            mrows[-1]["speedup_vs_grouped"]
    brows = [r for r in bound_phases["batches"] if r["batch"] <= 32]
    if brows:
        summary[f"bound_phases_speedup_at_{brows[-1]['batch']}"] = \
            brows[-1]["speedup_vs_legacy"]
    for load, row in serving["loads"].items():
        summary[f"adaptive_qps_ratio_{load}"] = row["adaptive_qps_ratio"]
    if exact is not None and exact["rows"]:
        base_bytes = exact["rows"][0]["per_device_repo_bytes"]
        summary["exacthaus_per_device_mem_ratio_max_shards"] = (
            exact["rows"][-1]["per_device_repo_bytes"] / base_bytes)
    rec = {
        "bench": "engine_qps_sharded" if args.sharded else "engine_qps",
        "backend": jax.default_backend(),
        "n_devices": jax.device_count(),
        "sharded": bool(args.sharded),
        "mesh": (
            {"axis": engine.dispatch.axis,
             "n_shards": engine.dispatch.n_shards,
             "shard_slots": engine.dispatch.shard_slots}
            if args.sharded else None
        ),
        "n_datasets": args.datasets,
        "n_slots": info["n_slots"],
        "k": k,
        "ops": ops,
        "exact_hausdorff": exact,
        "exact_hausdorff_batched": exact_batched,
        "mixed_ops": mixed,
        "bound_phases": bound_phases,
        "adaptive_serving": serving,
        "summary": summary,
        "engine_stats": {
            "dispatches": engine.stats.dispatches,
            "cache_hits": engine.stats.cache_hits,
            "cache_misses": engine.stats.cache_misses,
        },
    }
    Path(args.out).write_text(json.dumps(rec, indent=2))
    print(json.dumps(summary, indent=2))
    print(f"wrote {args.out}")
    return rec


if __name__ == "__main__":
    main()
