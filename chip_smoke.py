#!/usr/bin/env python3
"""Bring-up check: the search server's normal path on one TPU, checked.

    python chip_smoke.py                # one chip
    python chip_smoke.py --four-chips   # the multi-chip serving paths

One chip: builds a T-Drive-scale repository (the public release's 10,357
taxi trajectories, 100-1000 2-D points each, 16,384 padded slots) through
``build_repository``, forces each Pallas kernel against its jnp oracle
(widths in ``kernel_phase``), serves a warmed-up burst of mixed traffic
(all nine ops and the three pipeline kinds) through ``SearchServer`` over
a ``QueryEngine``, and runs a short live lane (ingest / delete / replace
interleaved with queries) through ``LiveRepository``.  Sampled answers are
compared with the plain references: the per-query seed ops,
``search.topk_hausdorff_host``, ``join_search.topk_join_host`` and, for the
live lane's final epoch, ``repo_mutate.build_frozen``.  Ids, masks and
integer scores must be equal; float values must agree to the jit-vs-eager
tolerance of the test suite (rtol = atol = 1e-5).

Four chips: the same 10,357-trajectory lake as one ``LiveRepository`` on
a 4-chip ``data_mesh``; one coalesced group of mutations, after which the
mesh repository must equal ``build_frozen`` of its contents bit for bit.
That oracle repository then serves one 256-request burst through
``ShardedQueryEngine`` on the 4-chip ``data_mesh`` and
``ReplicatedQueryEngine`` on a 2x2 ``replica_mesh``, each compared with
the single-device ``QueryEngine``; per-device residency and placement are
checked.

Any failed phase raises, so the script exits nonzero; on success the last
line of stdout is one JSON object naming the device.  It refuses to run
(exit 1, no result line) where JAX finds no TPU.
"""
from __future__ import annotations

import argparse
import json
import sys
import tempfile
import time
from collections import defaultdict
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

TDRIVE_DATASETS = 10_357     # trajectories in the public T-Drive release
N_REQUESTS = 256             # one burst: ~21 requests of each of 12 kinds
SAMPLES = 2                  # reference-checked requests per kind
# The one-chip live lane is cut to the first 1,024 of the 10,357
# trajectories: it builds every dataset through batch-of-1 stage
# dispatches (core/repo_mutate.init_live) and its oracle does so again,
# about 3 minutes more at full size on a v5e.  The full lake's live path
# runs under --four-chips.
LIVE_DATASETS = 1024
MUTATE_EVERY = 8
N_MUTATIONS = 8
RTOL = ATOL = 1e-5           # jit-vs-eager tolerance of tests/test_engine.py
LEAF, THETA = 16, 5


class SmokeFailure(RuntimeError):
    pass


def check(ok: bool, what: str) -> None:
    if not ok:
        raise SmokeFailure(what)


def log(msg: str) -> None:
    print(f"[chip_smoke] {msg}", flush=True)


# -- compile accounting -------------------------------------------------------


class CompileLog:
    """Backend-compile seconds and persistent-cache hits, from JAX's own
    monitoring events (a cache hit books its retrieval time instead)."""

    def __init__(self):
        import jax
        from jax._src import dispatch
        self.seconds = 0.0
        self.hits = 0
        self.misses = 0
        self._event = dispatch.BACKEND_COMPILE_EVENT
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._count)

    def _duration(self, event, seconds, **_):
        if event == self._event:
            self.seconds += seconds

    def _count(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.hits += 1
        elif event == "/jax/compilation_cache/cache_misses":
            self.misses += 1

    def snapshot(self):
        return self.seconds, self.hits, self.misses

    def report(self, phase: str, since) -> None:
        s0, h0, m0 = since
        log(f"compile {phase}: {self.seconds - s0:.3f} s backend compile, "
            f"persistent cache {self.hits - h0} hits / "
            f"{self.misses - m0} misses")


# -- result comparison --------------------------------------------------------


class Matches:
    """Per-kind verdicts: every compared array must pass its rule; the
    bitwise flag records whether floats were also bit-identical."""

    def __init__(self):
        self.n = defaultdict(int)
        self.bitwise = defaultdict(lambda: True)
        self.failures = []

    def exact(self, kind, got, want, what):
        import numpy as np
        got, want = np.asarray(got), np.asarray(want)
        ok = got.shape == want.shape and np.array_equal(got, want)
        if not ok:
            self.failures.append(f"{kind}: {what} differ")
            self.bitwise[kind] = False

    def close(self, kind, got, want, what):
        import numpy as np
        got = np.asarray(got, np.float32)
        want = np.asarray(want, np.float32)
        if got.shape != want.shape or not np.allclose(
                got, want, rtol=RTOL, atol=ATOL):
            self.failures.append(f"{kind}: {what} outside tolerance")
        if not np.array_equal(got, want):
            self.bitwise[kind] = False

    def report(self, label: str) -> None:
        for kind in sorted(self.n):
            log(f"match {label} {kind}: {self.n[kind]} compared, "
                f"bitwise={self.bitwise[kind]}")
        check(not self.failures, f"{label}: " + "; ".join(self.failures))


def kind_of(op: str, payload: dict) -> str:
    if op == "pipeline":
        return f"pipeline:{payload['dataset']['op']}>{payload['point']['op']}"
    return op


# -- phases -------------------------------------------------------------------


def tdrive_lake(n: int):
    from repro.data import synthetic
    return synthetic.REPOSITORIES["tdrive"](n)


def build_tdrive(n_datasets: int, compiles: CompileLog):
    import jax
    from repro.core.build import build_repository
    from repro.engine import repo_device_bytes

    t0 = time.perf_counter()
    lake = tdrive_lake(n_datasets)
    t_gen = time.perf_counter() - t0
    c0 = compiles.snapshot()
    t0 = time.perf_counter()
    repo, info = build_repository(lake, leaf_capacity=LEAF, theta=THETA)
    jax.block_until_ready(repo)
    t_build = time.perf_counter() - t0
    nbytes = repo_device_bytes(repo)
    log(f"repository: {info['n_datasets']} datasets, {info['n_slots']} "
        f"slots, {LEAF << info['bottom_depth']} points per slot; generated "
        f"in {t_gen:.3f} s, built in {t_build:.3f} s")
    log("resident bytes: " + ", ".join(
        f"{d}: {b}" for d, b in sorted(nbytes.items(), key=str)))
    compiles.report("build", c0)
    check(info["n_datasets"] == n_datasets, "dataset count")
    return lake, repo, info


def kernel_phase(compiles: CompileLog) -> None:
    """Each Pallas entry point forced on against its jnp oracle on the same
    inputs, at the routing-default tiles of ``autotune.DEFAULTS``: point
    kernels on 1024-point 2-D datasets; the ExactHaus pair grid on 4
    queries x a 32-candidate chunk of 1024-point datasets; bound matrices
    256 x 256 nodes; the fused bound grid over the 15 nodes of levels 0..3
    at 256 queries (its routing threshold) x 2,048 slots, and over the 63
    nodes of levels 0..5 (rolled-loop reductions) at 8 x 512; signatures
    of theta=5 (32 words), 256 x 512."""
    import jax.numpy as jnp
    import numpy as np
    from repro.kernels import ops

    c0 = compiles.snapshot()
    rng = np.random.default_rng(0)
    pts = lambda *s: jnp.asarray(rng.uniform(0, 100, s).astype(np.float32))
    mask = lambda *s: jnp.asarray(rng.random(s) > 0.1)
    q, d, qv, dv = pts(1024, 2), pts(1024, 2), mask(1024), mask(1024)
    gq, gd = pts(4, 1024, 2), pts(4, 32, 1024, 2)
    gqv, gdv = mask(4, 1024), mask(4, 32, 1024)

    def bound_tree(b, s, n):
        return (pts(b, n, 2), pts(b, n), mask(b, n),
                pts(s, n, 2), pts(s, n), mask(s, n))

    levels = ((0, 1), (1, 3), (3, 7), (7, 15), (15, 31), (31, 63))
    grid15, grid63 = bound_tree(256, 2048, 15), bound_tree(8, 512, 63)
    sa = jnp.asarray(rng.integers(0, 2**32, (256, 32), dtype=np.uint32))
    sb = jnp.asarray(rng.integers(0, 2**32, (512, 32), dtype=np.uint32))
    cases = {
        "directed_hausdorff": lambda k: ops.directed_hausdorff(
            q, d, qv, dv, use_kernel=k),
        "hausdorff_grid": lambda k: ops.directed_hausdorff_grid(
            gq, gd, gqv, gdv, use_kernel=k),
        "nn_distance": lambda k: ops.nn_distance(q, d, qv, dv, use_kernel=k),
        "bound_matrices": lambda k: ops.bound_matrices(
            grid15[0][:, 0], grid15[1][:, 0], grid15[3][:256, 0],
            grid15[4][:256, 0], use_kernel=k),
        "bound_grid": lambda k: ops.bound_grid(*grid15, levels=levels[:4],
                                               use_kernel=k),
        "bound_grid:63": lambda k: ops.bound_grid(*grid63, levels=levels,
                                                  use_kernel=k),
        "set_intersect": lambda k: ops.set_intersect_counts(
            sa, sb, use_kernel=k),
    }
    m = Matches()
    for name, run in cases.items():
        got, want = run(True), run(False)
        got = got if isinstance(got, tuple) else (got,)
        want = want if isinstance(want, tuple) else (want,)
        m.n[name] += 1
        for i, (g, w) in enumerate(zip(got, want)):
            if np.issubdtype(np.asarray(w).dtype, np.floating):
                m.close(name, g, w, f"output {i}")
            else:
                m.exact(name, g, w, f"output {i}")
    compiles.report("kernels", c0)
    m.report("kernel-vs-oracle")


#: compiled serving program -> the op it answers (LocalDispatcher names its
#: programs after their op; ExactHaus runs search's own jitted pipeline)
OP_PROGRAMS = {f"jit_{op}": op for op in (
    "range_search", "topk_ia", "topk_gbo", "topk_hausdorff_approx",
    "range_points", "nnp", "topk_overlap", "topk_coverage",
    "join_rerank_overlap")}
OP_PROGRAMS["jit__topk_hausdorff_device_batched"] = "topk_hausdorff"


def pallas_report(dump_dir: str) -> None:
    """Which serving programs carry a Mosaic kernel (``tpu_custom_call``):
    every program the serving phase compiled, from its lowered IR."""
    seen = {}
    for f in sorted(Path(dump_dir).glob("jax_ir*_compile.mlir")):
        name = f.name.split("_", 2)[2].rsplit("_compile.mlir", 1)[0]
        seen[name] = seen.get(name, False) or (
            "tpu_custom_call" in f.read_text())
    for name, op in sorted(OP_PROGRAMS.items(), key=lambda x: x[1]):
        check(name in seen, f"no compiled program {name} for {op}")
        log(f"pallas {op}: tpu_custom_call={seen[name]} ({name})")
    log(f"pallas: {sum(seen.values())} of {len(seen)} compiled programs "
        f"carry a Mosaic kernel")


def serve_burst(engine, traffic, compiles: CompileLog, label: str):
    """Warm up on a pre-filled burst (as ``serve_search.main`` does), then
    serve the same traffic through the client API; returns the answers."""
    from repro.launch.serve_search import (MUTATION_OPS, SearchServer,
                                           ServerStats, serve_prefilled)
    server = SearchServer(engine, max_batch=64)
    try:
        c0 = compiles.snapshot()
        t0 = time.perf_counter()
        serve_prefilled(server, traffic)
        log(f"{label} warmup: {time.perf_counter() - t0:.3f} s")
        compiles.report(f"{label} warmup", c0)
        engine._result_cache.clear()
        server.stats = ServerStats()        # the burst's own latencies
        c0 = compiles.snapshot()
        t0 = time.perf_counter()
        futures = [server.submit(op, **payload) for op, payload in traffic
                   if op not in MUTATION_OPS]
        answers = [f.result(timeout=600) for f in futures]
        dt = time.perf_counter() - t0
    finally:
        server.stop()
    log(f"{label} burst: {len(answers)} requests in {dt:.3f} s, "
        f"{server.stats.batches} dispatch groups, p50 "
        f"{server.stats.p50_ms:.3f} ms / p99 {server.stats.p99_ms:.3f} ms "
        f"(host clock)")
    compiles.report(f"{label} burst", c0)
    return answers


def check_against_references(repo, traffic, answers, m: Matches) -> None:
    """Sampled answers vs the plain references over ``repo``."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from repro.core import join_search, point_search, search
    from repro.engine import QueryEngine

    builder = QueryEngine(repo, result_cache_size=0)

    def q_index(points):
        return jax.tree.map(lambda x: x[0], builder.build_queries([points]))

    def slot(ds_id):
        return jax.tree.map(lambda x: x[int(ds_id)], repo.ds_index)

    picked = defaultdict(list)
    for (op, payload), ans in zip(traffic, answers):
        kind = kind_of(op, payload)
        if len(picked[kind]) < SAMPLES:
            picked[kind].append((payload, ans))

    # one brute-force joinable pass per mode scores every slot for every
    # sampled joinable query (top-k ops and the re-rank pipeline alike)
    join_q = {"overlap": [], "coverage": []}
    join_row = {}
    for kind, rows in picked.items():
        for n, (payload, _) in enumerate(rows):
            if kind in ("topk_overlap", "topk_coverage"):
                mode, q = kind[5:], payload["q"]
            elif kind == "pipeline:topk_ia>topk_overlap":
                mode, q = "overlap", payload["point"]["q"]
            else:
                continue
            join_row[kind, n] = (mode, len(join_q[mode]))
            join_q[mode].append(np.asarray(q))
    t0 = time.perf_counter()
    full = {mode: join_search.topk_join_host(repo, qs, repo.n_slots, mode)
            for mode, qs in join_q.items() if qs}
    log(f"joinable host oracle: {time.perf_counter() - t0:.3f} s")

    def join_full(kind, n):
        mode, row = join_row[kind, n]
        return full[mode][0][row], full[mode][1][row]

    def seed_ia(lo, hi, k):
        return search.topk_ia(repo, jnp.asarray(lo), jnp.asarray(hi), k)

    t0 = time.perf_counter()
    for kind, rows in sorted(picked.items()):
        for n, (payload, ans) in enumerate(rows):
            m.n[kind] += 1
            if kind == "range_search":
                want, _ = search.range_search(
                    repo, jnp.asarray(payload["r_lo"]),
                    jnp.asarray(payload["r_hi"]))
                m.exact(kind, ans, want, "mask")
            elif kind == "topk_ia":
                v, i = seed_ia(payload["q_lo"], payload["q_hi"], payload["k"])
                m.exact(kind, ans[1], i, "ids")
                m.close(kind, ans[0], v, "vals")
            elif kind == "topk_gbo":
                v, i = search.topk_gbo(repo, jnp.asarray(payload["q_sig"]),
                                       payload["k"])
                m.exact(kind, ans[1], i, "ids")
                m.exact(kind, ans[0], v, "counts")
            elif kind == "topk_hausdorff_approx":
                v, i, (_, _, eps) = search.topk_hausdorff_approx(
                    repo, q_index(payload["q"]), payload["k"],
                    payload["eps"])
                m.exact(kind, ans[1], i, "ids")
                m.close(kind, ans[0], v, "vals")
                m.close(kind, ans[2], eps, "eps_eff")
            elif kind == "topk_hausdorff":
                v, i, _ = search.topk_hausdorff_host(
                    repo, q_index(payload["q"]), payload["k"])
                m.exact(kind, ans[1], i, "ids")
                m.close(kind, ans[0], v, "vals")
            elif kind == "range_points":
                want, _ = point_search.range_points(
                    slot(payload["ds_id"]), jnp.asarray(payload["r_lo"]),
                    jnp.asarray(payload["r_hi"]))
                m.exact(kind, ans, want, "take mask")
            elif kind == "nnp":
                dist, idx, _ = point_search.nnp_pruned(
                    q_index(payload["q"]), slot(payload["ds_id"]))
                m.exact(kind, ans[1], idx, "NN ids")
                m.close(kind, ans[0], dist, "NN dists")
            elif kind in ("topk_overlap", "topk_coverage"):
                vals, ids = join_full(kind, n)
                k = payload["k"]
                m.exact(kind, ans[1], ids[:k], "ids")
                m.exact(kind, ans[0], vals[:k], "scores")
            elif kind == "pipeline:topk_ia>range_points":
                st, pt = payload["dataset"], payload["point"]
                v, ids = seed_ia(st["r_lo"], st["r_hi"], st["k"])
                m.exact(kind, ans.extras["ds_ids"], ids, "stage-1 ids")
                m.close(kind, ans.extras["stage1"].vals, v, "stage-1 vals")
                for t, ds_id in enumerate(np.asarray(ids)):
                    want = (point_search.range_points(
                        slot(ds_id), jnp.asarray(pt["r_lo"]),
                        jnp.asarray(pt["r_hi"]))[0] if ds_id >= 0
                        else np.zeros_like(np.asarray(ans.mask[t])))
                    m.exact(kind, ans.mask[t], want, "stage-2 take mask")
            elif kind == "pipeline:topk_hausdorff_approx>nnp":
                st = payload["dataset"]
                qi = q_index(st["q"])
                v, ids, _ = search.topk_hausdorff_approx(repo, qi, st["k"],
                                                         st["eps"])
                m.exact(kind, ans.extras["ds_ids"], ids, "stage-1 ids")
                m.close(kind, ans.extras["stage1"].vals, v, "stage-1 vals")
                pq = q_index(payload["point"]["q"])
                for t, ds_id in enumerate(np.asarray(ids)):
                    if ds_id < 0:
                        continue
                    dist, idx, _ = point_search.nnp_pruned(pq, slot(ds_id))
                    m.exact(kind, ans.ids[t], idx, "stage-2 NN ids")
                    m.close(kind, ans.vals[t], dist, "stage-2 NN dists")
            elif kind == "pipeline:topk_ia>topk_overlap":
                st, k2 = payload["dataset"], payload["point"]["k"]
                _, ids1 = seed_ia(st["r_lo"], st["r_hi"], st["k"])
                m.exact(kind, ans.extras["ds_ids"], ids1, "stage-1 ids")
                vals, ids = join_full(kind, n)
                score = {int(i): int(v) for v, i in zip(vals, ids) if i >= 0}
                ids1 = np.asarray(ids1)
                sc = np.array([score.get(int(d), 0) if d >= 0 else -1
                               for d in ids1], np.int32)
                order = np.argsort(-sc, kind="stable")[:k2]
                want_v = np.where(sc[order] < 0, -1, sc[order])
                want_i = np.where(want_v < 0, -1, ids1[order])
                m.exact(kind, ans.vals, want_v.astype(np.int32), "scores")
                m.exact(kind, ans.ids, want_i.astype(np.int32), "ids")
            else:
                raise SmokeFailure(f"no reference for request kind {kind}")
    log(f"references: {time.perf_counter() - t0:.3f} s")


def repos_equal(live_repo, frozen, n_slots: int, m: Matches, kind: str):
    """Bitwise pytree equality over the logical slot region plus the whole
    upper tree (a mesh may pad the slot axis with zero rows)."""
    import jax
    import numpy as np
    la, lb = jax.tree.leaves(live_repo), jax.tree.leaves(frozen)
    check(len(la) == len(lb), f"{kind}: repository pytrees differ")
    m.n[kind] += 1
    for n, (x, y) in enumerate(zip(la, lb)):
        x, y = np.asarray(x), np.asarray(y)
        if x.shape != y.shape:
            check(x.shape[1:] == y.shape[1:]
                  and min(x.shape[0], y.shape[0]) >= n_slots,
                  f"{kind}: leaf {n} shape {x.shape} vs {y.shape}")
            x, y = x[:n_slots], y[:n_slots]
        if not np.array_equal(x, y, equal_nan=x.dtype.kind == "f"):
            m.failures.append(f"{kind}: repository leaf {n} differs")
            m.bitwise[kind] = False


def compare_results(kind_rows, got, want, m: Matches, what: str):
    """Engine-vs-engine: ids, masks and integers equal, floats close."""
    import numpy as np
    for kind, a, b in zip(kind_rows, got, want):
        m.n[kind] += 1
        for name in ("vals", "ids", "mask"):
            x, y = getattr(a, name), getattr(b, name)
            if x is None and y is None:
                continue
            if (x is None) != (y is None):
                m.failures.append(f"{kind}: {name} missing on one side")
                continue
            if np.issubdtype(np.asarray(y).dtype, np.floating):
                m.close(kind, x, y, f"{what} {name}")
            else:
                m.exact(kind, x, y, f"{what} {name}")


def live_lane(compiles: CompileLog, lake) -> None:
    from repro.engine import LiveRepository, QueryEngine
    from repro.launch.serve_search import (MUTATION_OPS, SearchServer,
                                           _to_query, make_traffic)

    c0 = compiles.snapshot()
    t0 = time.perf_counter()
    live = LiveRepository(lake, leaf_capacity=LEAF, theta=THETA)
    log(f"live lane: {len(lake)} datasets, {live.n_slots} slots, "
        f"initialized in {time.perf_counter() - t0:.3f} s")
    traffic = make_traffic(live.repo, lake, MUTATE_EVERY * N_MUTATIONS + 1,
                           seed=3, mutate_every=MUTATE_EVERY)
    n_mut = sum(op in MUTATION_OPS for op, _ in traffic)
    server = SearchServer(live=live, max_batch=64)
    server.start()
    try:
        t0 = time.perf_counter()
        futures = [(server.submit_mutation(op, **p) if op in MUTATION_OPS
                    else server.submit(op, **p)) for op, p in traffic]
        for f in futures:
            f.result(timeout=600)
        dt = time.perf_counter() - t0
    finally:
        server.stop()
    log(f"live lane: {len(traffic) - n_mut} queries and {n_mut} mutations "
        f"({server.stats.mutations} applied) in {dt:.3f} s; epoch "
        f"{live.epoch}, {len(live.live_ids)} live datasets")
    check(n_mut == N_MUTATIONS and server.stats.mutations == n_mut,
          "live lane: every mutation published")
    check(live.epoch >= 1, "live lane: epoch advanced")

    m = Matches()
    t0 = time.perf_counter()
    frozen = live.frozen_repository()
    repos_equal(live.repo, frozen, live.n_slots, m, "live:repository")
    cold = QueryEngine(frozen, leaf_capacity=LEAF)
    queries = [(kind_of(op, p), _to_query(op, p)) for op, p in traffic
               if op not in MUTATION_OPS]
    kinds = [k for k, _ in queries]
    compare_results(["live:" + k for k in kinds],
                    live.search([q for _, q in queries]),
                    cold.search([q for _, q in queries]), m, "final epoch")
    log(f"live lane oracle: {time.perf_counter() - t0:.3f} s")
    compiles.report("live lane", c0)
    m.report("live-vs-build_frozen")


def one_chip(compiles: CompileLog, n_datasets: int = TDRIVE_DATASETS,
             n_requests: int = N_REQUESTS) -> None:
    import jax
    from repro.engine import QueryEngine
    from repro.launch.serve_search import make_traffic

    lake, repo, _ = build_tdrive(n_datasets, compiles)
    kernel_phase(compiles)

    traffic = make_traffic(repo, lake, n_requests, seed=1)
    engine = QueryEngine(repo)
    with tempfile.TemporaryDirectory() as dump:
        jax.config.update("jax_dump_ir_to", dump)
        try:
            answers = serve_burst(engine, traffic, compiles, "serve")
        finally:
            jax.config.update("jax_dump_ir_to", "")
        pallas_report(dump)
    kinds = {kind_of(op, p) for op, p in traffic}
    log(f"served kinds: {len(kinds)} ({', '.join(sorted(kinds))})")
    check(len(kinds) == 12, "all nine ops and three pipeline kinds served")

    m = Matches()
    check_against_references(repo, traffic, answers, m)
    m.report("serve-vs-reference")
    live_lane(compiles, lake[:LIVE_DATASETS])


def placement_check(repo, n_devices: int, label: str) -> None:
    """Every placed leaf spans the whole mesh (none on one device)."""
    import jax
    leaves = jax.tree_util.tree_leaves_with_path(repo)
    lone = [jax.tree_util.keystr(p) for p, x in leaves
            if len(x.sharding.device_set) != n_devices]
    check(not lone, f"{label}: leaves off the mesh: {lone}")
    log(f"{label}: all {len(leaves)} repository leaves span "
        f"{n_devices} devices")


def residency(repo, label: str, whole: int, share: float) -> None:
    from repro.engine import repo_device_bytes
    per = repo_device_bytes(repo)
    log(f"{label} resident bytes: " + ", ".join(
        f"{d}: {b}" for d, b in sorted(per.items(), key=str))
        + f" (single device holds {whole})")
    for d, b in per.items():
        check(0.8 * share * whole <= b <= 1.2 * share * whole,
              f"{label}: {d} holds {b} bytes, expected ~{share} of {whole}")


def four_chips(compiles: CompileLog, n_datasets: int = TDRIVE_DATASETS,
               n_requests: int = N_REQUESTS) -> None:
    import jax
    from repro.engine import (LiveRepository, QueryEngine, data_mesh,
                              repo_device_bytes)
    from repro.engine.replicated import ReplicatedQueryEngine
    from repro.engine.sharded import ShardedQueryEngine
    from repro.launch.serve_search import make_traffic

    n_dev = len(jax.devices())
    check(n_dev >= 4, f"--four-chips needs 4 devices, JAX found {n_dev}")

    # the mesh live lane holds the whole lake; one coalesced group of
    # mutations, then the cold build_frozen oracle of its final contents
    c0 = compiles.snapshot()
    t0 = time.perf_counter()
    lake = tdrive_lake(n_datasets + 4)
    lake, extra = lake[:n_datasets], lake[n_datasets:]
    live = LiveRepository(lake, leaf_capacity=LEAF, theta=THETA,
                          mesh=data_mesh(4))
    log(f"mesh live repository: {n_datasets} datasets, {live.n_slots} "
        f"slots, built in {time.perf_counter() - t0:.3f} s")
    compiles.report("mesh live build", c0)
    c0 = compiles.snapshot()
    t0 = time.perf_counter()
    group = live.prepare_group([("ingest", None, extra[0]),
                                ("delete", 3, None),
                                ("replace", 5, extra[1]),
                                ("ingest", None, extra[2]),
                                ("delete", 7, None),
                                ("replace", 11, extra[3])])
    outcomes = live.publish_group(group)
    check(not any(isinstance(o, Exception) for o in outcomes),
          f"mesh live group: {outcomes}")
    log(f"mesh live group: 6 mutations published as epoch {live.epoch} "
        f"({live.stats.mutations_coalesced} coalesced) in "
        f"{time.perf_counter() - t0:.3f} s")
    check(live.epoch == 1, "one coalesced publish")
    t0 = time.perf_counter()
    repo = live.frozen_repository()
    jax.block_until_ready(repo)
    log(f"build_frozen oracle: {time.perf_counter() - t0:.3f} s")
    compiles.report("mesh live group + oracle", c0)
    whole = sum(repo_device_bytes(repo).values())
    residency(live.repo, "mesh live 1x4", whole, 0.25)
    placement_check(live.repo, 4, "mesh live 1x4")
    m = Matches()
    repos_equal(live.repo, repo, live.n_slots, m, "mesh-live:repository")
    m.report("mesh-live-vs-build_frozen")
    del live

    # the serving paths, over the oracle's repository (single device)
    traffic = make_traffic(repo, lake, n_requests, seed=1)
    kinds = [kind_of(op, p) for op, p in traffic]
    base = serve_results(QueryEngine(repo), traffic, compiles,
                         "single-device")

    sharded = ShardedQueryEngine(repo, mesh=data_mesh(4))
    residency(sharded.repo, "sharded 1x4", whole, 0.25)
    placement_check(sharded.repo, 4, "sharded 1x4")
    m = Matches()
    compare_results(["sharded:" + k for k in kinds],
                    serve_results(sharded, traffic, compiles, "sharded 1x4"),
                    base, m, "sharded")
    m.report("sharded-vs-single")
    del sharded

    replicated = ReplicatedQueryEngine(repo, n_replicas=2, n_data=2)
    residency(replicated.repo, "replicated 2x2", whole, 0.5)
    placement_check(replicated.repo, 4, "replicated 2x2")
    m = Matches()
    compare_results(["replicated:" + k for k in kinds],
                    serve_results(replicated, traffic, compiles,
                                  "replicated 2x2"),
                    base, m, "replicated")
    m.report("replicated-vs-single")


def serve_results(engine, traffic, compiles: CompileLog, label: str):
    """Serve ``traffic`` once through ``SearchServer`` and return uniform
    ``SearchResult`` rows (the server's per-op response shapes differ)."""
    from repro.launch.serve_search import SearchServer, serve_prefilled

    c0 = compiles.snapshot()
    t0 = time.perf_counter()
    server = SearchServer(engine, max_batch=64)
    try:
        answers = serve_prefilled(server, traffic)
    finally:
        server.stop()
    log(f"{label}: {len(answers)} requests in "
        f"{time.perf_counter() - t0:.3f} s (compiles included)")
    compiles.report(label, c0)
    return [as_result(op, ans) for (op, _), ans in zip(traffic, answers)]


def as_result(op: str, ans):
    """A server response back as the fields ``SearchResult`` carries."""
    from types import SimpleNamespace
    if op == "pipeline":
        return ans
    if op in ("range_search", "range_points"):
        return SimpleNamespace(vals=None, ids=None, mask=ans)
    return SimpleNamespace(vals=ans[0], ids=ans[1], mask=None)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the multi-chip serving paths (needs 4 "
                         "TPU chips)")
    args = ap.parse_args(argv)

    import jax
    devices = jax.devices()
    if devices[0].platform != "tpu":
        print(f"[chip_smoke] needs a TPU; JAX found {devices[0].platform!r}",
              file=sys.stderr)
        return 1

    from repro import compile_cache
    log(f"device: {devices[0].device_kind} x {len(devices)}")
    log(f"compile cache: {compile_cache.enable()}")
    compiles = CompileLog()
    t0 = time.perf_counter()
    if args.four_chips:
        four_chips(compiles)
    else:
        one_chip(compiles)
    log(f"total: {time.perf_counter() - t0:.3f} s, backend compile "
        f"{compiles.seconds:.3f} s, persistent cache {compiles.hits} hits / "
        f"{compiles.misses} misses")
    print(json.dumps({"ok": True, "device": {
        "platform": devices[0].platform, "kind": devices[0].device_kind,
        "count": len(devices)}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
