"""The program's host-phase spans (`repro.engine.spans`): recorded while
a profiler trace runs, one tree per served drain, nothing recorded or
annotated without a trace, compiles booked, the kept list bounded, and
the profiler twins on the stamps' clock."""
import glob

import jax
import numpy as np
import pytest

from conftest import make_clustered_datasets
from repro.core.build import build_repository
from repro.engine import Pipeline, Query, QueryEngine, spans
from repro.launch.serve_search import (Request, SearchServer, _to_query,
                                       make_traffic)

LEAVES = ("engine.marshal", "engine.launch", "engine.readback",
          "engine.results")
#: the round robin of `make_traffic` without its two NNP kinds (the ten
#: kinds of the benchmark's mixed10 traffic)
MIXED10 = (0, 1, 2, 3, 4, 5, 7, 9, 10, 11)


@pytest.fixture(scope="module")
def env():
    datasets = make_clustered_datasets(17, seed=4, n_points=(20, 60))
    repo, _ = build_repository(datasets, leaf_capacity=16, theta=5,
                               remove_outliers=False)
    return datasets, repo


@pytest.fixture
def recorder(tmp_path):
    """A profiler trace, and with it the recorder, on for the test; the
    kept spans emptied before and after."""
    spans.take()
    jax.profiler.start_trace(str(tmp_path))
    try:
        yield tmp_path
    finally:
        jax.profiler.stop_trace()
        spans.take()


def _serve(repo, items):
    """Answer ``(op, payload)`` items as drains of a started server; the
    first drain takes every item enqueued before the start."""
    engine = QueryEngine(repo)
    server = SearchServer(engine, max_batch=64, max_wait_ms=250.0)
    reqs = [Request(op, _to_query(op, p)) for op, p in items]
    for r in reqs:
        server._queue.put(r)
    server.start()
    try:
        answers = [r.future.result(timeout=600) for r in reqs]
        again = server.submit("range_search", r_lo=np.float32([-10, -10]),
                              r_hi=np.float32([10, 10]))
        again.result(timeout=600)
    finally:
        server.stop()
    return server, answers


def test_mixed_drain_is_one_tree_per_drain(env, recorder):
    datasets, repo = env
    traffic = make_traffic(repo, datasets, 24, seed=3)
    items = [t for i, t in enumerate(traffic) if i % 12 in MIXED10]
    spans.take()                            # the traffic's own compiles
    server, answers = _serve(repo, items)
    assert len(answers) == len(items) == 20
    got, dropped = spans.take()
    assert dropped == 0
    assert not {s.name for s in got} & {"engine.search"}
    by_id = {s.id: s for s in got}
    drains = {}
    for s in got:
        # a compile outside any drain (the client building a request) is
        # booked with no drain id
        assert s.drain is not None or s.name == "jax.compile"
        if s.drain is not None:
            drains.setdefault(s.drain, []).append(s)
    assert len(drains) == 2
    for drain, ss in drains.items():
        names = [s.name for s in ss]
        assert names.count("server.drain") == 1
        assert names.count("engine.execute") == 1
        assert names.count("server.deliver") == 1
        for s in ss:
            assert s.start <= s.end
            if s.parent is not None:
                p = by_id[s.parent]
                assert p.drain == drain
                assert p.start <= s.start and s.end <= p.end
        groups = [s for s in ss if s.name == "engine.group"]
        execute, = [s for s in ss if s.name == "engine.execute"]
        assert execute.attrs["groups"] == len(groups)
        for g in groups:
            assert g.parent == execute.id
            kids = {s.name for s in ss if s.parent == g.id}
            assert set(LEAVES) <= kids, (g.attrs, kids)
            assert g.attrs["rows"] >= 1 and g.attrs["bucket"] >= 1
    first = min(drains)
    drain, = [s for s in drains[first] if s.name == "server.drain"]
    assert drain.attrs["requests"] == len(items)
    assert drain.attrs["wait_max_ms"] >= drain.attrs["wait_sum_ms"] / 20 > 0
    groups = [s for s in drains[first] if s.name == "engine.group"]
    # both pipeline kinds reach stage 2; the planner's count is the
    # server's
    assert {g.attrs["stage"] for g in groups} == {1, 2}
    assert server.stats.batches == len(groups) + 1
    # every stage-1 group inserts the rows it dispatched into the result
    # cache (a row twinned inside the group rides its twin's); its rows
    # are placed after it, where the pipelines' ids are handed off
    execute, = [s for s in drains[first] if s.name == "engine.execute"]
    results = [s for s in drains[first] if s.name == "engine.results"]
    cache = [s for s in results if s.attrs.get("part") == "cache"]
    handoff = [s for s in results if s.attrs.get("part") == "handoff"]
    for g in groups:
        ins = [s for s in cache if s.parent == g.id]
        if g.attrs["stage"] == 1:
            assert len(ins) == 1
            assert 1 <= ins[0].attrs["rows"] <= g.attrs["rows"]
    assert {by_id[s.parent].name for s in cache} == {"engine.group"}
    assert all(s.parent == execute.id for s in handoff)
    assert len(handoff) == sum(g.attrs["stage"] == 1 for g in groups)
    assert sum(s.attrs["rows"] for s in handoff) == sum(
        isinstance(_to_query(op, p), Pipeline) for op, p in items)
    # each group's latency, as the straggler window reads it, is the
    # group's own span: the placement after it is not part of it
    booked = server.engine.stats.op_seconds
    timed = {}
    for ss in drains.values():
        for g in ss:
            if g.name == "engine.group":
                op = g.attrs["op"]
                timed[op] = timed.get(op, 0.0) + (g.end - g.start) / 1e9
    assert booked == pytest.approx(timed, rel=1e-9)


def _host_marks(log_dir, names) -> list:
    """(name, start_ns) of the profiler's host events with these names."""
    path = sorted(glob.glob(f"{log_dir}/**/*.xplane.pb", recursive=True))[-1]
    out = []
    for plane in jax.profiler.ProfileData.from_file(path).planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                out += [(e.name, e.start_ns) for e in line.events
                        if e.name in names]
    return out


def _range_batch(n):
    rng = np.random.default_rng(n)
    lo = rng.uniform(-60, 40, (n, 2)).astype(np.float32)
    return [Query(op="range_search", r_lo=lo[i], r_hi=lo[i] + 5.0)
            for i in range(n)]


def test_off_records_and_annotates_nothing(env, recorder):
    """Without a profiler trace the recorder is off: one shared no-op
    span, nothing kept, nothing annotated; the spans of the trace that
    ran before stay as they were."""
    _, repo = env
    engine = QueryEngine(repo)
    engine.search(_range_batch(3))          # under the fixture's trace
    jax.profiler.stop_trace()
    try:
        assert spans.span("engine.plan") is spans.span("engine.marshal")
        assert not spans.span("engine.plan").live
        engine.search(_range_batch(3))
        got, dropped = spans.take()
        engine.search(_range_batch(2))
        assert spans.take() == ([], 0)
    finally:
        jax.profiler.start_trace(str(recorder / "again"))
    assert dropped == 0 and got
    assert {s.name for s in got} >= {"engine.execute", *LEAVES}
    # one engine call's tree: everything recorded came from the traced one
    assert [s.name for s in got].count("engine.execute") == 1
    names = {s.name for s in got}
    assert len(_host_marks(str(recorder), names)) == len(
        [s for s in got if s.name != "jax.compile"])


def test_a_new_trace_starts_the_list_again(env, tmp_path):
    """The kept spans are the latest trace's: spans of an earlier trace
    that nothing took are dropped when the next trace records."""
    _, repo = env
    engine = QueryEngine(repo)
    engine.search(_range_batch(2))          # compiled before the traces
    spans.take()
    for run in ("first", "second"):
        jax.profiler.start_trace(str(tmp_path / run))
        try:
            engine.search(_range_batch(2 if run == "first" else 4))
        finally:
            jax.profiler.stop_trace()
        engine.search(_range_batch(3))      # off: no trace runs
    got, dropped = spans.take()
    assert dropped == 0
    execute, = [s for s in got if s.name == "engine.execute"]
    assert execute.attrs["requests"] == 4


def test_the_kept_list_is_bounded(recorder, monkeypatch):
    monkeypatch.setattr(spans, "CAPACITY", 10)
    spans.next_drain()
    for _ in range(7):
        with spans.span("engine.group"):
            with spans.span("engine.marshal"):
                pass
    got, dropped = spans.take()
    assert (len(got), dropped) == (10, 4)
    assert spans.take() == ([], 0)


def test_fresh_bucket_records_one_compile(env, recorder):
    """A bucket met for the first time compiles its program inside the
    launch: one ``jax.compile`` span there, of the compile's seconds."""
    from jax.experimental.compilation_cache import compilation_cache
    _, repo = env
    engine = QueryEngine(repo, buckets=(5,))
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    spans.take()
    try:
        engine.search(_range_batch(5))
    finally:
        jax.config.update("jax_enable_compilation_cache", True)
        compilation_cache.reset_cache()
    got, _ = spans.take()
    compiles = [s for s in got if s.name == "jax.compile"]
    launch, = [s for s in got if s.name == "engine.launch"]
    under_launch = [s for s in compiles if s.parent == launch.id]
    assert len(under_launch) == 1
    c = under_launch[0]
    assert launch.start <= c.start <= c.end <= launch.end
    assert c.end - c.start == pytest.approx(c.attrs["seconds"] * 1e9, abs=1)


def test_twins_follow_the_stamps(env, tmp_path):
    """Under the profiler every span has an annotation twin of its name;
    after one offset each twin starts within 50 us of its stamp."""
    _, repo = env
    engine = QueryEngine(repo)
    engine.search(_range_batch(4))          # compiled before the trace
    spans.take()
    jax.profiler.start_trace(str(tmp_path))
    try:
        for n in (4, 4, 2):
            engine.search(_range_batch(n))
    finally:
        jax.profiler.stop_trace()
    got, _ = spans.take()
    got = [s for s in got if s.name != "jax.compile"]
    assert len(got) > 20
    marks = _host_marks(str(tmp_path), {s.name for s in got})
    assert len(marks) == len(got)
    stamps = sorted(s.start for s in got)
    twins = sorted(t for _, t in marks)
    d = np.asarray(twins, np.float64) - np.asarray(stamps, np.float64)
    assert np.abs(d - np.median(d)).max() < 50_000


def test_threads_keep_their_own_trees(recorder):
    """Many threads opening nested spans at once, with a short switch
    interval: no span is lost, ids are unique, and every parent is an
    enclosing span of the same thread and drain."""
    import sys
    import threading

    n_threads, n_spans = 32, 300
    before = sys.getswitchinterval()
    sys.setswitchinterval(1e-6)

    def work():
        spans.next_drain()
        for _ in range(n_spans):
            with spans.span("engine.group"):
                with spans.span("engine.marshal"):
                    pass

    threads = [threading.Thread(target=work) for _ in range(n_threads)]
    try:
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=120)
    finally:
        sys.setswitchinterval(before)
    assert not any(t.is_alive() for t in threads)
    got, dropped = spans.take()
    assert dropped == 0 and len(got) == 2 * n_threads * n_spans
    by_id = {s.id: s for s in got}
    assert len(by_id) == len(got)
    assert len({s.drain for s in got}) == n_threads
    for s in got:
        if s.name == "engine.marshal":
            p = by_id[s.parent]
            assert p.name == "engine.group" and p.drain == s.drain
            assert p.start <= s.start <= s.end <= p.end
        else:
            assert s.parent is None
