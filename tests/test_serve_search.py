"""Serving front-end (continuous micro-batching) behaviors.

Direct coverage for `repro.launch.serve_search.SearchServer`: mixed op
types in one queue drain, request -> response id mapping under grouping,
the queue-timeout flush (partial batches must not stall), and stop()
failing still-queued requests instead of hanging their futures.
"""
import numpy as np
import pytest

from conftest import make_clustered_datasets
from repro.core import zorder
from repro.core.build import build_repository
from repro.engine import Pipeline, Query, QueryEngine
from repro.launch.serve_search import OPS, Request, SearchServer, make_traffic

THETA = 5
K = 4


@pytest.fixture(scope="module")
def env():
    datasets = make_clustered_datasets(17, seed=4, n_points=(20, 60))
    repo, _ = build_repository(datasets, leaf_capacity=16, theta=THETA,
                               remove_outliers=False)
    return datasets, repo


def test_mixed_ops_one_drain(env):
    """A burst covering all seven op types PLUS pipeline requests is
    answered correctly and grouped: one dispatch group per compatible
    (op, statics) set — the whole mixed drain is ONE engine.search call,
    not one call per request."""
    import jax

    datasets, repo = env
    engine = QueryEngine(repo)
    server = SearchServer(engine, max_batch=64, max_wait_ms=250.0).start()
    try:
        traffic = make_traffic(repo, datasets, 27, seed=3)  # >= 2 of each kind
        assert {op for op, _ in traffic} == set(OPS)
        futures = [server.submit(op, **p) for op, p in traffic]
        results = [f.result(timeout=600) for f in futures]
        assert len(results) == 27
        assert server.stats.requests == 27
        # grouping: far fewer dispatch groups than requests (14 groups if
        # the whole burst landed in one drain — 11 stage-1 op/static groups
        # + 3 pipeline stage-2 groups; allow a few straggler drains)
        assert server.stats.batches <= 27
        assert server.stats.mean_batch > 1.0
        assert engine.stats.pipeline_stage1 == engine.stats.pipeline_stage2 \
            == 6
        # spot-check each op type against a direct engine call
        for (op, payload), res in zip(traffic, results):
            if op == "range_search":
                want = engine.range_search(payload["r_lo"][None],
                                           payload["r_hi"][None])[0]
                np.testing.assert_array_equal(np.asarray(res),
                                              np.asarray(want))
            elif op == "topk_gbo":
                vals, ids = engine.topk_gbo(payload["q_sig"][None],
                                            payload["k"])
                np.testing.assert_array_equal(np.asarray(res[0]),
                                              np.asarray(vals[0]))
                np.testing.assert_array_equal(np.asarray(res[1]),
                                              np.asarray(ids[0]))
            elif op == "topk_hausdorff":
                # ExactHaus responses carry (vals, ids, SearchStats) — the
                # engine no longer discards the stats; top-k values/ids
                # are padding-invariant, so a solo rebuild must agree
                q_batch = engine.build_queries([payload["q"]])
                qi = jax.tree.map(lambda x: x[0], q_batch)
                vals, ids, stats = engine.topk_hausdorff(qi, payload["k"])
                np.testing.assert_array_equal(np.asarray(res[0]),
                                              np.asarray(vals))
                np.testing.assert_array_equal(np.asarray(res[1]),
                                              np.asarray(ids))
                assert res[2].exact_evaluations > 0
                assert 0.0 <= res[2].pruned_fraction <= 1.0
            elif op == "pipeline":
                # pipeline responses are the full SearchResult: stage-2
                # rows over the k winners + the stage-1 result, equal to
                # the two-call host baseline
                stage1 = res.extras["stage1"]
                ds = payload["dataset"]
                pt = payload["point"]
                if ds["op"] == "topk_ia":
                    want_v, want_i = engine.topk_ia(
                        ds["r_lo"][None], ds["r_hi"][None], ds["k"])
                    np.testing.assert_array_equal(
                        np.asarray(stage1.vals), np.asarray(want_v[0]))
                    np.testing.assert_array_equal(
                        np.asarray(stage1.ids), np.asarray(want_i[0]))
                if ds["op"] == "topk_ia" and pt["op"] == "range_points":
                    ids = np.asarray(stage1.ids)
                    valid = ids >= 0
                    k = ds["k"]
                    want = engine.range_points(
                        np.where(valid, ids, 0),
                        np.broadcast_to(pt["r_lo"], (k, 2)),
                        np.broadcast_to(pt["r_hi"], (k, 2)))
                    got = np.asarray(res.mask)
                    np.testing.assert_array_equal(
                        got[valid], np.asarray(want)[valid])
                    assert not got[~valid].any()
                elif pt["op"] in ("topk_overlap", "topk_coverage"):
                    # dataset→dataset rerank kind: equal to the same
                    # Pipeline answered by a direct engine call
                    want = engine.search([Pipeline(
                        Query(**ds), Query(**pt))])[0]
                    np.testing.assert_array_equal(
                        np.asarray(res.vals), np.asarray(want.vals))
                    np.testing.assert_array_equal(
                        np.asarray(res.ids), np.asarray(want.ids))
    finally:
        server.stop()


def test_request_response_id_mapping(env):
    """Each future must receive ITS query's rows even though requests are
    grouped and answered as one batch — distinct queries, per-request
    verification against single-query engine calls."""
    datasets, repo = env
    engine = QueryEngine(repo)
    server = SearchServer(QueryEngine(repo), max_batch=16,
                          max_wait_ms=100.0).start()
    try:
        rng = np.random.default_rng(7)
        lo = rng.uniform(-60, 40, (9, 2)).astype(np.float32)
        hi = lo + rng.uniform(5, 40, (9, 2)).astype(np.float32)
        futures = [server.submit("topk_ia", q_lo=lo[i], q_hi=hi[i], k=K)
                   for i in range(9)]
        got = [f.result(timeout=600) for f in futures]
        for i, (v, j) in enumerate(got):
            want_v, want_j = engine.topk_ia(lo[i][None], hi[i][None], K)
            np.testing.assert_array_equal(np.asarray(v),
                                          np.asarray(want_v[0]))
            np.testing.assert_array_equal(np.asarray(j),
                                          np.asarray(want_j[0]))
    finally:
        server.stop()


def test_queue_timeout_flush(env):
    """A partial batch (far below max_batch) must flush after max_wait and
    resolve its futures — the server never waits for a full batch."""
    datasets, repo = env
    server = SearchServer(QueryEngine(repo), max_batch=1024,
                          max_wait_ms=5.0).start()
    try:
        rng = np.random.default_rng(11)
        lo = rng.uniform(-60, 40, (3, 2)).astype(np.float32)
        hi = lo + 5.0
        futures = [server.submit("range_search", r_lo=lo[i], r_hi=hi[i])
                   for i in range(3)]
        for f in futures:
            f.result(timeout=120)        # completing at all proves the flush
        assert server.stats.requests == 3
        assert server.stats.batches >= 1
    finally:
        server.stop()


def test_adaptive_drain_and_latency_stats(env):
    """The adaptive (queue-depth-driven) policy must answer every request
    correctly, book the engine's per-op dispatch latency EWMA (what sizes
    the straggler window), and expose latency percentiles."""
    datasets, repo = env
    engine = QueryEngine(repo)
    server = SearchServer(engine, max_batch=16, max_wait_ms=100.0,
                          adaptive=True).start()
    try:
        rng = np.random.default_rng(23)
        lo = rng.uniform(-60, 40, (6, 2)).astype(np.float32)
        hi = lo + 8.0
        futures = [server.submit("range_search", r_lo=lo[i], r_hi=hi[i])
                   for i in range(6)]
        got = [f.result(timeout=600) for f in futures]
        direct = QueryEngine(repo)
        for i, res in enumerate(got):
            want = direct.range_search(lo[i][None], hi[i][None])[0]
            np.testing.assert_array_equal(np.asarray(res),
                                          np.asarray(want))
        assert server.stats.requests == 6
        assert server.stats.p99_ms >= server.stats.p50_ms >= 0.0
        assert engine.stats.latency_ewma["range_search"] > 0.0
        # a lone straggler after the EWMAs exist exercises the sized
        # window path and still resolves promptly
        lone = server.submit("range_search", r_lo=lo[0], r_hi=hi[0])
        np.testing.assert_array_equal(
            np.asarray(lone.result(timeout=600)), np.asarray(got[0]))
    finally:
        server.stop()


def test_depth_scaled_drain_bound(env):
    """Under deep backlog (queue deeper than max_batch) the adaptive
    drain grows to OVERFILL x max_batch so dispatch overhead amortises
    over more requests; the static policy keeps the fixed bound.  Calls
    _drain directly on an unstarted, pre-filled server — no dispatcher
    thread, fully deterministic."""
    datasets, repo = env
    engine = QueryEngine(repo)
    from repro.launch.serve_search import Request

    def prefill(adaptive, n):
        server = SearchServer(engine, max_batch=8, max_wait_ms=2.0,
                              adaptive=adaptive)
        for _ in range(n):
            server._queue.put(Request("range_search", None))
        return server

    deep = prefill(True, 3 * 8)
    assert len(deep._drain()) == 3 * 8      # whole backlog, one drain
    assert SearchServer.OVERFILL * 8 >= 3 * 8
    over = prefill(True, 5 * 8)             # backlog beyond OVERFILL
    assert len(over._drain()) == SearchServer.OVERFILL * 8
    shallow = prefill(True, 4)              # no overfill below max_batch
    assert len(shallow._drain()) == 4
    static = prefill(False, 3 * 8)
    assert len(static._drain()) == 8        # seed policy: fixed bound


def test_submit_unknown_op_and_stopped_server(env):
    datasets, repo = env
    server = SearchServer(QueryEngine(repo), max_batch=8)
    with pytest.raises(RuntimeError):
        server.submit("range_search", r_lo=np.zeros(2), r_hi=np.ones(2))
    server.start()
    with pytest.raises(ValueError):
        server.submit("not_an_op")
    server.stop()


def test_poisoned_request_isolated(env):
    """A malformed request sharing a drain with healthy ones must fail
    ONLY its own future: the server falls back to per-request execution
    when the mixed engine call raises."""
    datasets, repo = env
    server = SearchServer(QueryEngine(repo), max_batch=16,
                          max_wait_ms=200.0).start()
    try:
        rng = np.random.default_rng(13)
        lo = rng.uniform(-60, 40, (2, 2)).astype(np.float32)
        hi = lo + 5.0
        good1 = server.submit("topk_ia", q_lo=lo[0], q_hi=hi[0], k=K)
        # same (op, k) group, wrong box rank: poisons the group stack
        bad = server.submit("topk_ia", q_lo=np.zeros(3, np.float32),
                            q_hi=np.ones(3, np.float32), k=K)
        good2 = server.submit("range_search", r_lo=lo[1], r_hi=hi[1])
        v, j = good1.result(timeout=600)
        assert np.asarray(v).shape == (K,)
        assert np.asarray(good2.result(timeout=600)).shape[0] > 0
        with pytest.raises(Exception):
            bad.result(timeout=600)
        # the dispatcher thread survived the poisoned drain: a fresh
        # request after the failure still resolves
        after = server.submit("topk_ia", q_lo=lo[1], q_hi=hi[1], k=K)
        v2, _ = after.result(timeout=600)
        assert np.asarray(v2).shape == (K,)
    finally:
        server.stop()


def test_stop_fails_queued_requests(env):
    """Requests still queued when the server stops get an exception, not a
    forever-pending future."""
    datasets, repo = env
    server = SearchServer(QueryEngine(repo), max_batch=8).start()
    server.stop()                        # dispatcher fully exited
    req = Request("range_search", dict(r_lo=np.zeros(2), r_hi=np.ones(2)))
    server._queue.put(req)               # lands after the dispatcher died
    server.stop()                        # second stop drains + fails it
    assert req.future.done()
    with pytest.raises(RuntimeError):
        req.future.result(timeout=0)


# -- live serving: the mutation lane ----------------------------------------


def _segment_queries(ds_id, probe_lo, probe_hi):
    """Three fixed queries reused verbatim across segments, so a stale
    cached row from an earlier epoch would be SERVED (not just possible)
    if epoch keying were broken: dataset discovery, top-k, and a point
    probe into ``ds_id`` (box tight around the ORIGINAL content, so a
    replace that moves the points visibly changes the mask)."""
    lo = np.float32([20, 20])
    return [
        ("range_search", dict(r_lo=lo, r_hi=lo + 40.0)),
        ("topk_ia", dict(q_lo=np.float32([-60, -60]),
                         q_hi=np.float32([60, 60]), k=3)),
        ("range_points", dict(ds_id=ds_id, r_lo=probe_lo, r_hi=probe_hi)),
    ]


def _res_np(res):
    return [np.asarray(x) for x in (res if isinstance(res, tuple) else (res,))]


def _assert_same(got, want_engine, traffic):
    """Each legacy response equals the same legacy call on a cold engine."""
    for (op, payload), res in zip(traffic, got):
        if op == "range_search":
            want = want_engine.range_search(payload["r_lo"][None],
                                            payload["r_hi"][None])[0]
        elif op == "topk_ia":
            want = want_engine.topk_ia(payload["q_lo"][None],
                                       payload["q_hi"][None], payload["k"])
            want = (want[0][0], want[1][0])
        else:                                       # range_points
            want = want_engine.range_points(
                np.int32([payload["ds_id"]]), payload["r_lo"][None],
                payload["r_hi"][None])[0]
        for x, y in zip(_res_np(res), _res_np(want)):
            np.testing.assert_array_equal(x, np.asarray(y))


def test_live_interleaved_mutation_drain():
    """Mutations submitted MID-BURST take effect exactly at their stream
    position: the whole interleaved burst is pre-filled before the
    dispatcher starts, so one drain sees [queries, replace, same queries,
    ingest, delete, same queries] — each segment's answers must be
    bit-identical to a cold engine over the frozen equivalent of the
    repository AT THAT POINT (the middle segment repeats the first
    segment's payloads verbatim, so a cached epoch-0 row being re-served
    after the replace would be caught, and the replaced dataset's point
    probe must visibly change)."""
    from repro.core import repo_mutate
    from repro.engine import LiveRepository
    from repro.launch.serve_search import Mutation, _to_query

    datasets = make_clustered_datasets(10, seed=5, n_points=(20, 50))
    live = LiveRepository(datasets, leaf_capacity=16, theta=THETA,
                          result_cache_size=64)
    n_slots = live.n_slots
    new0 = (datasets[0] + np.float32(30.0))        # visibly moved
    fresh = (datasets[3] + np.float32(7.0))
    ingest_slot = min(set(range(n_slots)) - live.live_ids)

    traffic = _segment_queries(
        ds_id=0, probe_lo=datasets[0].min(0) - np.float32(1.0),
        probe_hi=datasets[0].max(0) + np.float32(1.0))
    reqs = [[Request(op, _to_query(op, p)) for op, p in traffic]
            for _ in range(3)]
    muts = [Mutation("replace", ds_id=0, points=new0),
            Mutation("ingest", points=fresh),
            Mutation("delete", ds_id=1)]
    server = SearchServer(live=live, max_batch=64, max_wait_ms=250.0)
    for item in (*reqs[0], muts[0], *reqs[1], muts[1], muts[2], *reqs[2]):
        server._queue.put(item)
    server.start()
    try:
        got = [[r.future.result(timeout=600) for r in seg] for seg in reqs]
        assert muts[0].future.result(timeout=600) == 0
        assert muts[1].future.result(timeout=600) == ingest_slot
        assert muts[2].future.result(timeout=600) is None
    finally:
        server.stop()

    # the adjacent ingest+delete COALESCE into one publish (one epoch);
    # the replace, separated by queries, publishes alone — so 3 applied
    # mutations produce 2 data epochs and exactly 1 coalesced mutation
    assert live.epoch == 2
    assert server.stats.mutations == 3
    assert server.stats.mutation_latencies[0] >= 0.0
    assert live.engine.stats.mutations_coalesced == 1
    assert len(live.engine.stats.publish_seconds) == 2

    # frozen equivalents of the repository at each segment's position
    slots0 = list(datasets) + [None] * (n_slots - len(datasets))
    slots1 = [new0] + slots0[1:]
    cold0 = QueryEngine(repo_mutate.build_frozen(slots0, live.geometry),
                        leaf_capacity=16)
    cold1 = QueryEngine(repo_mutate.build_frozen(slots1, live.geometry),
                        leaf_capacity=16)
    cold2 = QueryEngine(live.frozen_repository(), leaf_capacity=16)
    _assert_same(got[0], cold0, traffic)
    _assert_same(got[1], cold1, traffic)
    _assert_same(got[2], cold2, traffic)
    # the replace was actually visible: the point probe into ds 0 must
    # differ between the first two segments (same payload, new content)
    assert not np.array_equal(np.asarray(got[0][2]), np.asarray(got[1][2]))


def test_live_poisoned_row_fallback_and_lane_errors():
    """A poisoned query sharing a drain with healthy queries AND a
    mutation on a LIVE engine fails only its own future: the mutation
    still publishes, healthy futures resolve with post-mutation-correct
    results, and the dispatcher survives.  Plus the lane's error
    contract: no live repo -> RuntimeError, unknown mutation -> ValueError."""
    from repro.engine import LiveRepository

    datasets = make_clustered_datasets(8, seed=9, n_points=(20, 40))
    live = LiveRepository(datasets, leaf_capacity=16, theta=THETA)
    # a TIGHT cluster: sparse bases get fully dropped by outlier removal
    # (their MBR refines to empty), which would make the mask probe moot
    fresh = (datasets[4] + np.float32(4.0))
    ingest_slot = min(set(range(live.n_slots)) - live.live_ids)
    server = SearchServer(live=live, max_batch=16, max_wait_ms=200.0).start()
    try:
        with pytest.raises(ValueError):
            server.submit_mutation("compact")
        lo = np.float32([-200, -200])      # covers the whole [0,100]^2 lake
        good1 = server.submit("topk_ia", q_lo=lo, q_hi=-lo, k=3)
        bad = server.submit("topk_ia", q_lo=np.zeros(3, np.float32),
                            q_hi=np.ones(3, np.float32), k=3)
        mfut = server.submit_mutation("ingest", points=fresh)
        good2 = server.submit("range_search", r_lo=lo, r_hi=-lo)
        assert np.asarray(good1.result(timeout=600)[0]).shape == (3,)
        with pytest.raises(Exception):
            bad.result(timeout=600)
        assert mfut.result(timeout=600) == ingest_slot
        mask = np.asarray(good2.result(timeout=600))
        # a mutation whose apply raises fails ITS future, nothing else
        bad_mut = server.submit_mutation("delete", ds_id=999)
        with pytest.raises(KeyError):
            bad_mut.result(timeout=600)
        # dispatcher survived; post-mutation answers match a cold engine
        after = server.submit("range_search", r_lo=lo, r_hi=-lo)
        cold = QueryEngine(live.frozen_repository(), leaf_capacity=16)
        want = cold.range_search(lo[None], (-lo)[None])[0]
        np.testing.assert_array_equal(np.asarray(after.result(timeout=600)),
                                      np.asarray(want))
        if ingest_slot < mask.shape[0]:
            assert mask[ingest_slot]       # good2 saw the ingested dataset
    finally:
        server.stop()


def test_mutation_lane_needs_live(env):
    datasets, repo = env
    server = SearchServer(QueryEngine(repo), max_batch=8).start()
    try:
        with pytest.raises(RuntimeError):
            server.submit_mutation("ingest", points=datasets[0])
    finally:
        server.stop()


# -- injectable clock (deterministic drain-bound / latency tests) -----------


class _FakeClock:
    """Virtual time: every call returns the current instant, then
    advances by ``step`` (0 = pinned)."""

    def __init__(self, t=0.0, step=0.0):
        self.t, self.step = t, step

    def __call__(self):
        now = self.t
        self.t += self.step
        return now


def test_clock_injected_static_drain_deadline(env):
    """The static drain's deadline reads the INJECTED clock: with virtual
    time jumping past max_wait between queue reads, a pre-filled partial
    batch drains and exits immediately — no real sleeping against a
    5-second window (the old sleep-based timing assumption)."""
    import time as _time

    datasets, repo = env
    clk = _FakeClock(t=100.0, step=10.0)           # step >> max_wait
    server = SearchServer(QueryEngine(repo), max_batch=64,
                          max_wait_ms=5000.0, adaptive=False, clock=clk)
    for _ in range(3):
        server._queue.put(Request("range_search", None, t_submit=clk()))
    t0 = _time.perf_counter()
    batch = server._drain()
    elapsed = _time.perf_counter() - t0
    assert len(batch) == 3                 # instantly-available rows taken
    assert elapsed < 2.0                   # virtual deadline, real exit
    assert clk.t > 100.0                   # the drain consulted the clock


def test_clock_injected_latency_accounting(env):
    """With a PINNED injected clock, submit->resolve latency is exactly
    0.0 for every request — latency stats become deterministic instead
    of sleep-calibrated."""
    datasets, repo = env
    clk = _FakeClock(t=50.0, step=0.0)
    server = SearchServer(QueryEngine(repo), max_batch=8, max_wait_ms=20.0,
                          adaptive=False, clock=clk).start()
    try:
        lo = np.float32([-10, -10])
        futures = [server.submit("range_search", r_lo=lo, r_hi=-lo)
                   for _ in range(3)]
        for f in futures:
            f.result(timeout=600)
    finally:
        server.stop()
    assert server.stats.latencies == [0.0, 0.0, 0.0]
    assert server.stats.p99_ms == server.stats.p50_ms == 0.0


def check_replicated_serving():
    """SearchServer over a ReplicatedQueryEngine (2 x 4 mesh): a mixed
    burst pre-filled BEFORE the dispatcher starts drains as ONE batch ->
    one engine.search call, every future gets the same legacy response
    shapes as the single-device server, answers are bit-identical to
    direct local-engine calls, and a poisoned request sharing a drain
    fails only its own future (per-request fallback works on the replica
    dispatch path too)."""
    import jax

    from repro.engine import ReplicatedQueryEngine

    datasets = make_clustered_datasets(17, seed=4, n_points=(20, 60))
    repo, _ = build_repository(datasets, leaf_capacity=16, theta=THETA,
                               remove_outliers=False)
    local = QueryEngine(repo)
    engine = ReplicatedQueryEngine(repo, n_replicas=2, n_data=4)
    server = SearchServer(engine, max_batch=64, max_wait_ms=250.0)
    traffic = make_traffic(repo, datasets, 27, seed=3)   # >= 2 of each kind
    assert {op for op, _ in traffic} == set(OPS)
    # pre-fill the queue so the whole burst is visible to the FIRST drain
    from repro.launch.serve_search import _to_query
    reqs = [Request(op, _to_query(op, p)) for op, p in traffic]
    for r in reqs:
        server._queue.put(r)
    server.start()
    try:
        results = [r.future.result(timeout=600) for r in reqs]
        # one drain, one search(): exactly the single-drain group count (11
        # stage-1 op/static groups + 3 pipeline stage-2 groups) — a split
        # drain would re-plan its groups and book more
        assert server.stats.batches == 14
        assert server.stats.batch_size_sum == 27
        s = engine.stats
        assert s.cache_hits + s.cache_misses == s.dispatches
        assert s.plan_groups <= s.replica_subgroups <= s.plan_groups * 2
        # legacy response shapes + bit-identity vs the local engine
        for (op, payload), res in zip(traffic, results):
            if op == "range_search":
                want = local.range_search(payload["r_lo"][None],
                                          payload["r_hi"][None])[0]
                np.testing.assert_array_equal(np.asarray(res),
                                              np.asarray(want))
            elif op == "topk_ia":
                vals, ids = local.topk_ia(payload["q_lo"][None],
                                          payload["q_hi"][None],
                                          payload["k"])
                np.testing.assert_array_equal(np.asarray(res[0]),
                                              np.asarray(vals[0]))
                np.testing.assert_array_equal(np.asarray(res[1]),
                                              np.asarray(ids[0]))
            elif op == "topk_hausdorff":
                q_batch = local.build_queries([payload["q"]])
                qi = jax.tree.map(lambda x: x[0], q_batch)
                vals, ids, _ = local.topk_hausdorff(qi, payload["k"])
                np.testing.assert_array_equal(np.asarray(res[0]),
                                              np.asarray(vals))
                np.testing.assert_array_equal(np.asarray(res[1]),
                                              np.asarray(ids))
                assert res[2].exact_evaluations > 0
            elif op == "pipeline":
                assert res.op == "pipeline"
                assert res.extras["stage1"] is not None
        # poisoned request isolated on the replica path: wrong box rank
        # poisons its group; the server falls back per-request and only
        # the bad future fails
        rng = np.random.default_rng(13)
        lo = rng.uniform(-60, 40, (2, 2)).astype(np.float32)
        hi = lo + 5.0
        good = server.submit("topk_ia", q_lo=lo[0], q_hi=hi[0], k=K)
        bad = server.submit("topk_ia", q_lo=np.zeros(3, np.float32),
                            q_hi=np.ones(3, np.float32), k=K)
        v, j = good.result(timeout=600)
        assert np.asarray(v).shape == (K,)
        import pytest as _pytest
        with _pytest.raises(Exception):
            bad.result(timeout=600)
        after = server.submit("range_search", r_lo=lo[1], r_hi=hi[1])
        assert np.asarray(after.result(timeout=600)).ndim == 1
    finally:
        server.stop()
    print("REPLICATED_SERVING_OK")


def test_replicated_serving():
    from conftest import dispatch_device_check
    dispatch_device_check("test_serve_search", "check_replicated_serving")
