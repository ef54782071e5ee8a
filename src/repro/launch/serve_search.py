"""Search serving front-end: request queue + continuous micro-batching.

    PYTHONPATH=src python -m repro.launch.serve_search [--requests 256 ...]
    REPRO_HOST_DEVICES=8 PYTHONPATH=src \
        python -m repro.launch.serve_search --sharded   # data-sharded engine
    REPRO_HOST_DEVICES=8 PYTHONPATH=src \
        python -m repro.launch.serve_search --replicas 2   # 2 x 4 replica mesh

The production shape for the paper's *online* multi-granularity search:
clients submit single queries (mixed types — RangeS / top-k IA / top-k
GBO / ApproHaus / ExactHaus / joinable overlap & coverage at dataset
granularity, RangeP / NNP at point granularity, plus two-stage
dataset→point and dataset→dataset PIPELINES) into a queue; a
dispatcher thread drains the queue continuously and hands the WHOLE mixed
drain to ``QueryEngine.search`` as one declarative batch.  The engine's
planner does the grouping the server used to do by hand — compatible
requests (same op, same static params) share one device dispatch, cache
hits short-circuit per row, and pipeline stage-1 queries ride the same
groups as standalone queries.  Under load the batch grows toward
`max_batch` on its own — classic continuous batching — so throughput
scales with traffic while the executable cache keeps compile cost
amortized across the bucket ladder.

``submit(op=..., **payload)`` is kept as a thin shim that constructs the
:class:`~repro.engine.query.Query` / :class:`~repro.engine.query.Pipeline`
at submission time; clients holding ready-made spec objects can enqueue
them directly with ``submit_query``.

LIVE serving (``--live`` / ``SearchServer(live=...)``): the server fronts
a :class:`~repro.engine.live.LiveRepository` and accepts a MUTATION lane
on the same queue — ``submit_mutation("ingest"|"delete"|"replace", ...)``
enqueues next to queries, so mutations take effect exactly at their
submission point in the stream: the dispatcher splits each drain into
query segments at mutation boundaries, serves each segment as one
declarative batch, and applies the mutations in order between segments.
Every query answered after a mutation sees the post-mutation epoch
(bit-identical to a cold engine over the frozen equivalent — asserted in
tests/test_serve_search.py); in-flight segments keep the consistent
pre-mutation snapshot.

The dispatcher's notion of time is injectable (``clock=``): latency
accounting and the static drain deadline read ``self.clock()``, so tests
drive deterministic virtual time instead of sleeping.
"""
from __future__ import annotations

import argparse
import queue
import threading
import time
from concurrent.futures import Future, ThreadPoolExecutor
from dataclasses import dataclass, field
from typing import Any

from repro import hostdev

# before the first jax import: let --sharded shard over N forced host
# devices on CPU-only machines (no-op unless REPRO_HOST_DEVICES is set)
hostdev.apply()

import jax
import numpy as np

from repro.core.repo_index import Repository
from repro.engine import Pipeline, Query, QueryEngine, SearchResult, spans

# ops the submit() shim knows how to wrap into a Query/Pipeline; the
# engine's planner handles the grouping, so ANY mix of these may share one
# queue drain (and pipeline stage-1 rows share dispatches with standalone
# queries of the same op)
OPS = (
    "range_search", "topk_ia", "topk_gbo", "topk_hausdorff_approx",
    "topk_hausdorff", "range_points", "nnp", "topk_overlap",
    "topk_coverage", "pipeline",
)


def _to_query(op: str, payload: dict):
    """The submit() shim: legacy (op, payload) -> declarative spec."""
    if op == "pipeline":
        dataset = payload["dataset"]
        point = payload["point"]
        return Pipeline(
            dataset_stage=(dataset if isinstance(dataset, Query)
                           else _to_query(dataset["op"], dataset)),
            point_stage=(point if isinstance(point, Query)
                         else _to_query(point["op"], point)))
    if op == "range_search":
        return Query(op=op, r_lo=payload["r_lo"], r_hi=payload["r_hi"])
    if op == "topk_ia":
        # legacy payload naming: q_lo/q_hi; pipeline specs may say r_lo
        lo = payload.get("q_lo", payload.get("r_lo"))
        hi = payload.get("q_hi", payload.get("r_hi"))
        return Query(op=op, r_lo=lo, r_hi=hi, k=payload["k"])
    if op == "topk_gbo":
        return Query(op=op, q_sig=payload["q_sig"], k=payload["k"])
    if op == "topk_hausdorff_approx":
        return Query(op=op, q=payload["q"], k=payload["k"],
                     eps=payload["eps"])
    if op == "topk_hausdorff":
        return Query(op=op, q=payload["q"], k=payload["k"])
    if op == "range_points":
        return Query(op=op, ds_id=payload.get("ds_id"),
                     r_lo=payload["r_lo"], r_hi=payload["r_hi"])
    if op == "nnp":
        return Query(op=op, ds_id=payload.get("ds_id"), q=payload["q"])
    if op == "topk_overlap" or op == "topk_coverage":
        return Query(op=op, q=payload["q"], k=payload["k"])
    raise ValueError(f"unknown op {op!r}; serving ops: {OPS}")


def _legacy_result(res: SearchResult):
    """Shape a SearchResult like the pre-redesign per-op responses, so
    existing clients keep unpacking what they always unpacked.  Pipeline
    responses are new: they hand back the full SearchResult (stage-2
    rows + ``extras['stage1']``)."""
    if res.op == "range_search" or res.op == "range_points":
        return res.mask
    if res.op == "topk_ia" or res.op == "topk_gbo":
        return (res.vals, res.ids)
    if res.op == "topk_hausdorff_approx":
        return (res.vals, res.ids, res.extras["eps_eff"])
    if res.op == "topk_hausdorff":
        return (res.vals, res.ids, res.stats)
    if res.op == "topk_overlap" or res.op == "topk_coverage":
        return (res.vals, res.ids, res.stats)
    if res.op == "nnp":
        return (res.vals, res.ids)
    return res                              # pipeline: the full result


#: mutation kinds the live lane accepts (LiveRepository methods)
MUTATION_OPS = ("ingest", "delete", "replace")


@dataclass
class Request:
    op: str
    query: Any                              # Query | Pipeline
    future: Future = field(default_factory=Future)
    t_submit: float = field(default_factory=time.perf_counter)


@dataclass
class Mutation:
    """One mutation riding the request queue: applied IN ORDER at its
    position in the stream (queries drained before it see the old epoch,
    queries after it the new one)."""
    op: str                                 # ingest | delete | replace
    ds_id: int | None = None
    points: Any = None
    future: Future = field(default_factory=Future)
    t_submit: float = field(default_factory=time.perf_counter)


@dataclass
class ServerStats:
    requests: int = 0
    batches: int = 0                        # dispatch groups planned
    batch_size_sum: int = 0
    latency_sum: float = 0.0
    latencies: list = field(default_factory=list)   # per-request seconds
    mutations: int = 0                      # mutation-lane ops applied
    mutation_latency_sum: float = 0.0
    mutation_latencies: list = field(default_factory=list)

    @property
    def mean_batch(self) -> float:
        return self.batch_size_sum / max(self.batches, 1)

    @property
    def mean_latency_ms(self) -> float:
        return 1e3 * self.latency_sum / max(self.requests, 1)

    def record(self, seconds: float) -> None:
        """Book one answered request's submit->result latency."""
        self.requests += 1
        self.latency_sum += seconds
        self.latencies.append(seconds)

    def record_mutation(self, seconds: float) -> None:
        """Book one applied mutation's submit->publish latency (kept out
        of the QUERY latency distribution: mutations are a different
        SLO)."""
        self.mutations += 1
        self.mutation_latency_sum += seconds
        self.mutation_latencies.append(seconds)

    @property
    def mean_mutation_ms(self) -> float:
        return 1e3 * self.mutation_latency_sum / max(self.mutations, 1)

    def percentile_ms(self, p: float) -> float:
        """p-th percentile of per-request latency, in ms (0 if empty)."""
        if not self.latencies:
            return 0.0
        return 1e3 * float(np.percentile(np.asarray(self.latencies), p))

    @property
    def p50_ms(self) -> float:
        return self.percentile_ms(50.0)

    @property
    def p99_ms(self) -> float:
        return self.percentile_ms(99.0)


class SearchServer:
    """Continuous micro-batching dispatcher over a QueryEngine.

    Two batching policies:

    * **adaptive** (default) — queue-depth-driven: the dispatcher
      greedily takes every request ALREADY enqueued (no waiting while
      there is work to batch); when the queue runs dry it waits one
      straggler window, and every arrival renews that budget, so the
      batch keeps filling while traffic flows and ships the moment one
      full window passes with nothing new.  The window is
      ``min(max_wait, 0.5 x EWMA dispatch latency)`` of the ops in the
      partial batch (fed by :meth:`EngineStats.record_latency`): folding
      a straggler into this batch saves about one dispatch's EWMA, so
      waiting longer than a fraction of it costs more latency than it
      saves.  Under saturating load the windows renew until the batch
      fills; at low load a lone request waits at most one window —
      typically far less than the static ``max_wait`` deadline for
      cheap ops.  When the backlog is deeper than ``max_batch`` the
      drain bound itself scales with queue depth (up to
      ``OVERFILL x max_batch``): a deep queue means dispatch overhead
      dominates, so amortising it over a larger drain raises saturated
      throughput without hurting the (already queue-dominated) tail.
    * **static** (``adaptive=False``) — the seed policy: after the first
      request, keep blocking up to a fixed ``max_wait`` deadline while
      the batch fills.  Kept for A/B measurement
      (``bench_engine --serving`` and ``--static-window`` here).
    """

    #: adaptive drains may grow to this multiple of ``max_batch`` when
    #: the queue is already deeper than ``max_batch`` (bounds worst-case
    #: host memory for one drain at OVERFILL x max_batch requests)
    OVERFILL = 4

    def __init__(
        self,
        engine: QueryEngine | None = None,
        *,
        live=None,
        max_batch: int = 64,
        max_wait_ms: float = 2.0,
        adaptive: bool = True,
        clock=time.perf_counter,
    ):
        if engine is None:
            if live is None:
                raise ValueError("SearchServer needs an engine or a live "
                                 "repository")
            engine = live.engine
        elif live is not None and live.engine is not engine:
            raise ValueError("live.engine and engine disagree — pass one")
        self.engine = engine
        self.live = live
        self.max_batch = max_batch
        self.max_wait = max_wait_ms / 1e3
        self.adaptive = adaptive
        self.clock = clock
        self.stats = ServerStats()
        self._queue: "queue.Queue[Request | Mutation | None]" = queue.Queue()
        self._thread = threading.Thread(target=self._loop, daemon=True)
        self._running = False
        # lazy 1-worker pool for the prepare stage of the NEXT mutation
        # run — overlapped with the current query segment (the segment
        # serves the immutable pre-mutation snapshot, so the concurrent
        # row builds are invisible to it)
        self._prep_pool: ThreadPoolExecutor | None = None
        self._segment_span = (0.0, 0.0)
        # first request seen past a mutation run: carried to the next
        # drain so a publish always lands at a drain TAIL and never
        # splits one query segment into two engine calls (stream order
        # is untouched — drain boundaries are free choices)
        self._carry: Request | Mutation | None = None

    # -- client API --------------------------------------------------------

    def submit(self, op: str, **payload: Any) -> Future:
        """Enqueue one query; returns a Future with the op's result.

        Thin shim: the legacy (op, **payload) call is converted to a
        declarative Query/Pipeline HERE (validation included), then
        enqueued like any other spec."""
        if op not in OPS:
            raise ValueError(f"unknown op {op!r}; serving ops: {OPS}")
        if not self._running:
            raise RuntimeError("server is not running (start() it first)")
        return self.submit_query(_to_query(op, payload), op=op)

    def submit_query(self, query, *, op: str | None = None) -> Future:
        """Enqueue a ready-made Query/Pipeline spec."""
        if not isinstance(query, (Query, Pipeline)):
            raise TypeError(f"submit_query takes Query/Pipeline, "
                            f"got {type(query)!r}")
        if not self._running:
            raise RuntimeError("server is not running (start() it first)")
        if op is None:
            op = "pipeline" if isinstance(query, Pipeline) else query.op
        req = Request(op, query, t_submit=self.clock())
        self._queue.put(req)
        if not self._running and not req.future.done():
            # lost the race with a concurrent stop(): its drain may have
            # already passed our request, so fail the future ourselves
            try:
                req.future.set_exception(
                    RuntimeError("server stopped before request ran"))
            except Exception:           # drain got there first
                pass
        return req.future

    def submit_mutation(self, op: str, *, ds_id: int | None = None,
                        points=None) -> Future:
        """Enqueue one live-repository mutation on the request queue.

        Returns a Future resolving to the slot id (ingest/replace) or
        None (delete) once the mutation is PUBLISHED — every query
        submitted after this call that drains behind it is answered at
        the post-mutation epoch."""
        if self.live is None:
            raise RuntimeError("mutation lane needs a live repository "
                               "(SearchServer(live=...))")
        if op not in MUTATION_OPS:
            raise ValueError(f"unknown mutation {op!r}; mutation ops: "
                             f"{MUTATION_OPS}")
        if not self._running:
            raise RuntimeError("server is not running (start() it first)")
        mut = Mutation(op, ds_id=ds_id, points=points,
                       t_submit=self.clock())
        self._queue.put(mut)
        return mut.future

    def start(self) -> "SearchServer":
        self._running = True
        self._thread.start()
        return self

    def stop(self) -> None:
        self._running = False
        self._queue.put(None)          # wake the dispatcher
        self._thread.join(timeout=30)
        # fail anything still queued (or carried between drains) so no
        # client Future hangs forever
        if self._carry is not None and not self._carry.future.done():
            self._carry.future.set_exception(
                RuntimeError("server stopped before request ran"))
        self._carry = None
        while True:
            try:
                req = self._queue.get_nowait()
            except queue.Empty:
                break
            if req is not None and not req.future.done():
                req.future.set_exception(
                    RuntimeError("server stopped before request ran"))
        if self._prep_pool is not None:
            self._prep_pool.shutdown(wait=True)
            self._prep_pool = None

    # -- dispatcher --------------------------------------------------------

    def _straggler_window(self, batch: list[Request]) -> float:
        """Adaptive wait budget once the queue runs dry: half the EWMA
        dispatch latency of the ops already in the batch (capped by
        max_wait) — the break-even point between folding a straggler
        into this dispatch and shipping without it.  Before any latency
        has been measured, fall back to the static window."""
        ew = self.engine.stats.latency_ewma
        vals = [ew[r.op] for r in batch if r.op in ew]
        if not vals:
            vals = list(ew.values())
        if not vals:
            return self.max_wait
        return min(self.max_wait, 0.5 * max(vals))

    def _drain(self) -> list[Request]:
        """Block for the first request, then fill the batch —
        queue-depth-driven when adaptive (greedy takes, dry-queue
        straggler windows that renew on every arrival, and a drain
        bound that scales to OVERFILL x max_batch under deep backlog),
        fixed max_wait deadline up to max_batch when static (the seed
        policy).

        A drain closes at the first mutation->query transition (the
        query is carried to the next drain): each drain is then at most
        one query segment plus one tail run of mutations, so the
        per-segment planning/dispatch floor is paid once per drain —
        splitting a segment in two costs ~a full extra group floor,
        which under churn was most of the serving collapse.

        The batch forming, from the first item taken, is the
        ``server.drain`` span, which starts the drain's id."""
        if self._carry is not None:
            first = self._carry
            self._carry = None
        else:
            try:
                first = self._queue.get(timeout=0.1)
            except queue.Empty:
                return []
            if first is None:
                return []
        spans.next_drain()
        with spans.span("server.drain") as sp:
            t0 = self.clock() if sp.live else 0.0
            batch = self._fill([first])
            if sp.live:
                waits = [max(0.0, t0 - r.t_submit) for r in batch
                         if isinstance(r, Request)]
                sp.set(requests=len(waits),
                       wait_sum_ms=1e3 * sum(waits),
                       wait_max_ms=1e3 * max(waits, default=0.0))
        return batch

    def _fill(self, batch: list) -> list:
        """:meth:`_drain` past its first item."""
        if self.adaptive:
            # depth-scaled bound: when the backlog already exceeds
            # max_batch, per-drain overhead (planning plus one engine
            # dispatch per group) dominates per-request work, so fold
            # up to OVERFILL x max_batch queued requests into this
            # drain.  The planner groups compatible rows into shared
            # dispatches and the bucket ladder pads row counts anyway,
            # so the larger drain amortises fixed costs without
            # triggering new compilation.
            limit = self.max_batch
            if self._queue.qsize() > self.max_batch:
                limit = self.OVERFILL * self.max_batch
            waited = False
            while len(batch) < limit:
                try:
                    req = self._queue.get_nowait()
                except queue.Empty:
                    if waited:
                        break
                    waited = True
                    try:
                        req = self._queue.get(
                            timeout=self._straggler_window(batch))
                    except queue.Empty:
                        break
                if req is None:
                    break
                if (isinstance(batch[-1], Mutation)
                        and not isinstance(req, Mutation)):
                    self._carry = req
                    break
                batch.append(req)
                # every arrival renews the straggler budget: the batch
                # keeps growing while traffic flows and ships the moment
                # one full window passes with no arrival (total wait is
                # bounded by max_batch renewals of <= max_wait each)
                waited = False
            # absorb a contiguous run of mutations sitting just past
            # the drain bound (the first non-mutation after them is
            # carried): their publish then rides THIS drain's tail and
            # their prepare overlaps THIS drain's query segment,
            # instead of opening the next drain with nothing to hide
            # the row builds under
            if not isinstance(batch[-1], Mutation) and self._carry is None:
                while True:
                    try:
                        req = self._queue.get_nowait()
                    except queue.Empty:
                        break
                    if req is None:
                        break
                    if isinstance(req, Mutation):
                        batch.append(req)
                        continue
                    self._carry = req
                    break
            return batch
        deadline = self.clock() + self.max_wait
        while len(batch) < self.max_batch:
            timeout = deadline - self.clock()
            try:
                req = self._queue.get(timeout=max(timeout, 0.0))
            except queue.Empty:
                break
            if req is None:
                break
            if (isinstance(batch[-1], Mutation)
                    and not isinstance(req, Mutation)):
                self._carry = req
                break
            batch.append(req)
        return batch

    def _prepare_ahead(self, muts: list[Mutation]):
        """Kick off the prepare stage (row builds + payload uploads) of
        the next mutation run on the side pool, to overlap with the
        query segment the dispatcher is about to serve.  Safe because
        prepare touches nothing a query observes, and the previous
        group's publish already happened (runs are consumed in stream
        order within one drain)."""
        if self._prep_pool is None:
            self._prep_pool = ThreadPoolExecutor(
                max_workers=1, thread_name_prefix="mutation-prepare")

        def work():
            t0 = self.clock()
            group = self.live.prepare_group(
                [(m.op, m.ds_id, m.points) for m in muts])
            return group, t0, self.clock()

        return self._prep_pool.submit(work)

    def _publish_run(self, muts: list[Mutation], prepared) -> None:
        """Install one coalesced run of consecutive mutations: join (or
        run inline) its prepare, book the wall time it hid under the
        preceding query segment, publish the whole group as ONE epoch,
        and resolve every mutation future from the per-item outcomes."""
        if prepared is not None:
            group, tp0, tp1 = prepared.result()
            s0, s1 = self._segment_span
            self.engine.stats.prepare_overlap_seconds += max(
                0.0, min(tp1, s1) - max(tp0, s0))
        else:
            group = self.live.prepare_group(
                [(m.op, m.ds_id, m.points) for m in muts])
        try:
            outcomes = self.live.publish_group(group)
        except Exception as e:
            for m in muts:
                if not m.future.done():
                    m.future.set_exception(e)
            return
        now = self.clock()
        for m, out in zip(muts, outcomes):
            if isinstance(out, Exception):
                if not m.future.done():
                    m.future.set_exception(out)
            else:
                self.stats.record_mutation(now - m.t_submit)
                m.future.set_result(out)

    def _serve_segment(self, segment: list[Request]) -> None:
        """One declarative engine call for a (sub-)drain of queries: the
        planner groups compatible rows into shared dispatches and
        returns per-request results in input order."""
        # dispatch groups as the planner counted them for THIS call
        # (stage-1 op groups + pipeline stage-2 groups; the engine's
        # shared counters would also count a client calling it from
        # another thread); a stand-in engine's plain list books one
        try:
            results = self.engine.search([r.query for r in segment])
            groups = getattr(results, "groups", 1)
        except Exception:
            # a poisoned row fails the whole mixed call; isolate by
            # re-running per request so every healthy future still
            # resolves and only the bad rows carry the exception
            # (the executable cache makes the re-runs cheap)
            results, groups = [], 0
            for r in segment:
                try:
                    out = self.engine.search([r.query])
                    groups += getattr(out, "groups", 1)
                    results.append(out[0])
                except Exception as e:
                    results.append(e)
        with spans.span("server.deliver"):
            now = self.clock()
            self.stats.batches += groups
            self.stats.batch_size_sum += len(segment)
            for req, res in zip(segment, results):
                self.stats.record(now - req.t_submit)
                if isinstance(res, Exception):
                    if not req.future.done():
                        req.future.set_exception(res)
                else:
                    req.future.set_result(_legacy_result(res))

    def _loop(self) -> None:
        while self._running:
            batch = self._drain()
            if not batch:
                continue
            # partition the drain into alternating runs of queries and
            # mutations: each query run is one declarative engine call
            # against the epoch current at ITS point in the stream, and
            # each MUTATION run coalesces into one prepared group whose
            # prepare stage overlaps the query segment just before it
            # (late-bound dispatch keeps that segment on the immutable
            # pre-publish snapshot) and whose publish is a single epoch
            # at the run's stream position
            runs: list[tuple[bool, list]] = []
            for item in batch:
                is_mut = isinstance(item, Mutation)
                if runs and runs[-1][0] == is_mut:
                    runs[-1][1].append(item)
                else:
                    runs.append((is_mut, [item]))
            prepared = None
            for i, (is_mut, items) in enumerate(runs):
                if is_mut:
                    self._publish_run(items, prepared)
                    prepared = None
                    continue
                if i + 1 < len(runs) and runs[i + 1][0]:
                    prepared = self._prepare_ahead(runs[i + 1][1])
                t0 = self.clock()
                self._serve_segment(items)
                self._segment_span = (t0, self.clock())


# ---------------------------------------------------------------------------
# demo / load driver
# ---------------------------------------------------------------------------


def make_traffic(repo: Repository, datasets, n_requests: int, seed: int = 0,
                 mutate_every: int = 0):
    """Pre-build a mixed stream of (op, payload) requests covering all
    nine serving ops PLUS three pipeline kinds (top-k IA -> RangeP inside
    the winners, ApproHaus -> NNP inside the winners — the paper's
    dataset->point workflow — and top-k IA -> topk_overlap re-rank, the
    joinable dataset->dataset workflow), so a drain exercises genuinely
    heterogeneous declarative batches.  Payload construction (signatures
    etc.) happens here, off the submission path, like a real client would
    send ready-made queries.

    ``mutate_every > 0`` adds a MUTATION LANE for live serving: every
    mutate_every-th stream position becomes an ingest / delete / replace
    (round-robin) with a SAFE id discipline — deletes only ever target
    the reserved ids [0, n_ds//4), each at most once; replaces rotate
    over [n_ds//4, n_ds//2) (always live); ingests are fresh jittered
    copies, so they only ever land in freed or new slots.  Point-query
    ds_ids then avoid the delete-reserved range, so every query in the
    stream is valid whenever it drains relative to the mutations."""
    from repro.core import zorder

    rng = np.random.default_rng(seed)
    n_ds = len(datasets)
    eps = float(zorder.default_epsilon(repo.space_lo, repo.space_hi, 5))
    del_pool = list(range(n_ds // 4)) if mutate_every else []
    rep_pool = list(range(n_ds // 4, n_ds // 2)) if mutate_every else []

    def q_id():
        # with a mutation lane, never reference a deletable id
        if mutate_every and n_ds // 4 < n_ds:
            return int(rng.integers(n_ds // 4, n_ds))
        return int(rng.integers(n_ds))

    def jittered():
        base = datasets[int(rng.integers(n_ds))]
        return (base + rng.normal(0, 0.5, base.shape)).astype(np.float32)

    out = []
    n_mut = 0
    for i in range(n_requests):
        if mutate_every and i and i % mutate_every == 0:
            kind = n_mut % 3
            n_mut += 1
            if kind == 1 and del_pool:
                out.append(("delete", dict(ds_id=del_pool.pop(0))))
            elif kind == 2 and rep_pool:
                sid = rep_pool[n_mut % len(rep_pool)]
                out.append(("replace", dict(ds_id=sid, points=jittered())))
            else:
                out.append(("ingest", dict(points=jittered())))
            continue
        c = rng.uniform(20, 80, 2).astype(np.float32)
        lo, hi = c - 2.0, c + 2.0
        kind = i % 12
        if kind == 0:
            out.append(("range_search", dict(r_lo=lo, r_hi=hi)))
        elif kind == 1:
            out.append(("topk_ia", dict(q_lo=lo, q_hi=hi, k=5)))
        elif kind == 2:
            q = datasets[int(rng.integers(n_ds))]
            sig = np.asarray(zorder.signature(
                jax.numpy.asarray(q), jax.numpy.ones(len(q), bool),
                repo.space_lo, repo.space_hi, 5))
            out.append(("topk_gbo", dict(q_sig=sig, k=5)))
        elif kind == 3:
            q = datasets[int(rng.integers(n_ds))][:64]
            out.append(("topk_hausdorff_approx", dict(q=q, k=5, eps=eps)))
        elif kind == 4:
            q = datasets[int(rng.integers(n_ds))][:64]
            out.append(("topk_hausdorff", dict(q=q, k=5)))
        elif kind == 5:
            out.append(("range_points", dict(
                ds_id=q_id(), r_lo=lo, r_hi=hi)))
        elif kind == 6:
            q = datasets[int(rng.integers(n_ds))][:64]
            out.append(("nnp", dict(ds_id=q_id(), q=q)))
        elif kind == 7:
            # dataset->point pipeline: top-3 IA datasets, then RangeP
            # inside each winner (ids never leave the device)
            wide_lo, wide_hi = c - 10.0, c + 10.0
            out.append(("pipeline", dict(
                dataset=dict(op="topk_ia", r_lo=wide_lo, r_hi=wide_hi, k=3),
                point=dict(op="range_points", r_lo=lo, r_hi=hi))))
        elif kind == 8:
            q = datasets[int(rng.integers(n_ds))][:32]
            out.append(("pipeline", dict(
                dataset=dict(op="topk_hausdorff_approx", q=q, k=3, eps=eps),
                point=dict(op="nnp", q=q))))
        elif kind == 9:
            q = datasets[int(rng.integers(n_ds))][:64]
            out.append(("topk_overlap", dict(q=q, k=5)))
        elif kind == 10:
            q = datasets[int(rng.integers(n_ds))][:64]
            out.append(("topk_coverage", dict(q=q, k=5)))
        else:
            # dataset->dataset pipeline: top-5 IA winners re-ranked by
            # grid-cell overlap with the query set (id handoff on device)
            q = datasets[int(rng.integers(n_ds))][:64]
            wide_lo, wide_hi = c - 10.0, c + 10.0
            out.append(("pipeline", dict(
                dataset=dict(op="topk_ia", r_lo=wide_lo, r_hi=wide_hi, k=5),
                point=dict(op="topk_overlap", q=q, k=3))))
    return out


def serve_prefilled(server: SearchServer, traffic) -> list:
    """Answer a burst of ``(op, payload)`` queries that is enqueued BEFORE
    the dispatcher starts (this starts it): the first drain is then
    full-depth, so the burst compiles exactly the bucket and payload shapes
    a measured burst of the same traffic will hit.  Returns the results in
    traffic order."""
    reqs = [Request(op, _to_query(op, p)) for op, p in traffic]
    for req in reqs:
        server._queue.put(req)
    server.start()
    return [req.future.result(timeout=600) for req in reqs]


def main(argv=None):
    from repro import compile_cache
    from repro.core.build import build_repository
    from repro.data import synthetic

    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--requests", type=int, default=256)
    ap.add_argument("--datasets", type=int, default=64)
    ap.add_argument("--max-batch", type=int, default=64)
    ap.add_argument("--max-wait-ms", type=float, default=2.0)
    ap.add_argument("--static-window", action="store_true",
                    help="use the fixed max-wait batching window instead "
                         "of the queue-depth-driven adaptive policy")
    ap.add_argument("--sharded", action="store_true",
                    help="serve from a ShardedQueryEngine with the resident "
                         "repository sharded over a 1-D data mesh spanning "
                         "all local devices")
    ap.add_argument("--replicas", type=int, default=0, metavar="R",
                    help="serve from a ReplicatedQueryEngine over an R x D "
                         "(replica x data) mesh: the repository is sharded "
                         "over D devices per group and replicated across R "
                         "groups, each drain's rows split over the groups")
    ap.add_argument("--data-shards", type=int, default=None, metavar="D",
                    help="data-axis extent per replica group (default: all "
                         "remaining local devices / R)")
    ap.add_argument("--live", action="store_true",
                    help="serve from a mutable LiveRepository (composes "
                         "with --sharded/--replicas) and open the "
                         "mutation lane")
    ap.add_argument("--mutate-every", type=int, default=0, metavar="N",
                    help="with --live: make every N-th request of the "
                         "measured stream an ingest/delete/replace "
                         "mutation (0 = queries only)")
    args = ap.parse_args(argv)
    if args.mutate_every and not args.live:
        ap.error("--mutate-every requires --live")
    compile_cache.enable()

    lake = synthetic.trajectory_repository(args.datasets, seed=0)
    live = None
    if args.live:
        from repro.engine import LiveRepository, data_mesh, replica_mesh
        mesh = None
        if args.replicas:
            mesh = replica_mesh(args.replicas, args.data_shards)
        elif args.sharded:
            mesh = data_mesh()
        live = LiveRepository(lake, leaf_capacity=16, theta=5, mesh=mesh)
        engine = live.engine
        repo = live.repo
        print(f"[serve_search] live repository: {live.n_slots} slots "
              f"({len(live.live_ids)} live), "
              f"{'mesh ' + str(tuple(mesh.shape.values())) if mesh else 'local'}"
              f" dispatch, mutation lane open")
    else:
        repo, _ = build_repository(lake, leaf_capacity=16, theta=5)
    if args.live:
        pass
    elif args.replicas:
        from repro.engine.replicated import ReplicatedQueryEngine
        engine = ReplicatedQueryEngine(repo, n_replicas=args.replicas,
                                       n_data=args.data_shards)
        print(f"[serve_search] replicated engine: "
              f"{engine.dispatch.n_replicas} replica group(s) x "
              f"{engine.dispatch.n_shards} data shard(s) "
              f"({engine.dispatch.n_replicas * engine.dispatch.n_shards} "
              f"devices), {engine.dispatch.shard_slots} dataset slots "
              f"per shard")
    elif args.sharded:
        from repro.engine.sharded import ShardedQueryEngine
        engine = ShardedQueryEngine(repo)
        print(f"[serve_search] sharded engine: "
              f"{engine.dispatch.n_shards} shard(s) x "
              f"{engine.dispatch.shard_slots} dataset slots on the "
              f"'{engine.dispatch.axis}' axis")
    else:
        engine = QueryEngine(repo)
    server = SearchServer(engine, live=live, max_batch=args.max_batch,
                          max_wait_ms=args.max_wait_ms,
                          adaptive=not args.static_window)

    # warmup: run the QUERY traffic once, pre-filled BEFORE the
    # dispatcher starts so the warm drains are full-depth and aligned
    # with the measured burst — compiling exactly the bucket shapes AND
    # payload shapes (pipeline queries embed variable-length datasets,
    # which trace per length) the measurement will hit.  Query-only even
    # under --mutate-every: warmup must not consume the one-shot delete
    # budget or move the epoch before measurement.  The result cache is
    # dropped afterwards so measured dispatches re-execute; only the
    # compiled executables carry over.
    serve_prefilled(server, make_traffic(repo, lake, args.requests))
    if live is not None and args.mutate_every:
        # warm the MUTATION path too: an ingest (which may trigger a
        # tier growth — compiling the growth executables here, outside
        # the measured window), a replace and a delete compile the
        # row-build stages and the group-of-1 updater; then coalesced
        # groups of sizes {2, 4} compile the BATCHED publish buckets, so
        # the first churn burst in the measured window pays no compile
        # time.  Every probe slot is deleted again so the measured
        # stream starts from the live set its id discipline expects.
        probe = (lake[0] + np.float32(0.25)).astype(np.float32)
        wid = live.ingest(probe)
        live.replace(wid, probe)
        live.delete(wid)
        for width in (2, 4):
            group = live.prepare_group(
                [("ingest", None, probe + np.float32(i))
                 for i in range(width)])
            sids = live.publish_group(group)
            cleanup = live.prepare_group(
                [("delete", sid, None) for sid in sids])
            live.publish_group(cleanup)
        live.bytes_uploaded = 0        # report the measured window only
    engine._result_cache.clear()
    server.stats = ServerStats()       # report the measured window only

    traffic = make_traffic(repo, lake, args.requests,
                           mutate_every=args.mutate_every)
    i0 = engine.stats.epoch_invalidations
    h0, m0 = engine.stats.cache_hits, engine.stats.cache_misses
    p_n0 = len(engine.stats.publish_seconds)
    mc0 = engine.stats.mutations_coalesced
    ov0 = engine.stats.prepare_overlap_seconds
    t0 = time.perf_counter()
    futures = [
        (server.submit_mutation(op, **payload) if op in MUTATION_OPS
         else server.submit(op, **payload))
        for op, payload in traffic
    ]
    for f in futures:
        f.result(timeout=600)
    dt = time.perf_counter() - t0
    server.stop()

    print(f"[serve_search] {args.requests} mixed requests in {dt*1e3:.1f} ms "
          f"-> {args.requests/dt:.1f} QPS")
    print(f"[serve_search] dispatch groups: {server.stats.batches}, "
          f"mean requests/group {server.stats.mean_batch:.1f}, "
          f"mean latency {server.stats.mean_latency_ms:.1f} ms "
          f"(p50 {server.stats.p50_ms:.1f} / p99 {server.stats.p99_ms:.1f}, "
          f"{'adaptive' if server.adaptive else 'static'} window)")
    print(f"[serve_search] engine dispatches: {engine.stats.dispatches}, "
          f"cache hits/misses: {engine.stats.cache_hits}/"
          f"{engine.stats.cache_misses} "
          f"(measured window: {engine.stats.cache_hits - h0}/"
          f"{engine.stats.cache_misses - m0}), pipelines: "
          f"{engine.stats.pipeline_stage1}")
    if live is not None:
        pub = np.asarray(engine.stats.publish_seconds[p_n0:], np.float64)
        pub_p50 = 1e3 * float(np.percentile(pub, 50)) if pub.size else 0.0
        pub_p99 = 1e3 * float(np.percentile(pub, 99)) if pub.size else 0.0
        print(f"[serve_search] mutation lane: {server.stats.mutations} "
              f"applied, mean {server.stats.mean_mutation_ms:.1f} ms; "
              f"epoch {live.epoch} "
              f"(layout {getattr(live.engine.dispatch, 'repo_epoch', 0)}), "
              f"{engine.stats.epoch_invalidations - i0} cached rows retired, "
              f"{live.bytes_uploaded} bytes uploaded, "
              f"{live.n_slots} slots ({len(live.live_ids)} live)")
        print(f"[serve_search] publish pipeline: {pub.size} publishes "
              f"(p50 {pub_p50:.1f} / p99 {pub_p99:.1f} ms), "
              f"{engine.stats.mutations_coalesced - mc0} coalesced, "
              f"{engine.stats.prepare_overlap_seconds - ov0:.3f} s of "
              f"prepare hidden under serving")
    return server.stats


if __name__ == "__main__":
    main()
