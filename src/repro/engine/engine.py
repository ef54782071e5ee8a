"""QueryEngine: the batched multi-query execution engine.

The engine owns a resident :class:`Repository` and turns ragged streams of
incoming queries into fixed-shape device work:

  * **shape bucketing** — a batch of B queries is padded (by replicating the
    first row) up to the smallest configured bucket >= B, so the number of
    distinct compiled shapes is bounded by the bucket ladder, not by the
    traffic;
  * **executable cache** — one jitted executable per (op, bucket, k) key,
    built lazily on first use and reused for every later batch that lands
    in the same bucket (every dispatch records a hit or a miss, so
    `stats.cache_hits + stats.cache_misses == stats.dispatches`);
  * **single dispatch** — every op lowers to exactly one device computation
    per batch; no per-query Python loop, no per-chunk host sync.

Every dataset-granularity op — ExactHaus included — is a first-class
batched op: `topk_hausdorff` accepts a (B, ...) query-index batch and
answers it with ONE device dispatch (shared phase-2 work frontier, see
`core/search.py`), riding the same bucket ladder and executable cache as
the rest.

In front of the dispatch path sits a small **result cache** (LRU, keyed by
(op, k, query content digest)): repeated queries short-circuit BEFORE
bucketing, so only the rows that miss form the dispatched batch.  Hits and
misses are booked in `EngineStats.result_cache_hits` / `.result_cache_
misses` — distinct from the executable-cache counters, which keep counting
compiled-program reuse per dispatch.  ``result_cache_size=0`` disables the
cache entirely (the benchmarks do this so repeats measure dispatch, not
memoization).

Dispatch is **pluggable**: the engine delegates the construction of every
device callable to a dispatcher object.  :class:`LocalDispatcher` (the
default) closes each executable over the single-device repository and the
vmapped forms in :mod:`repro.engine.batched_ops`;
:class:`repro.engine.sharded.ShardedDispatcher` (selected by passing
``mesh=``) places the repository's dataset slots across a mesh axis and
merges per-shard results on device.  Bucketing, the executable cache, the
result cache, query construction, and :class:`EngineStats` are shared
between the two — sharded and unsharded engines differ ONLY in the
callables they cache.

Query point sets are themselves bucketed: `build_queries` pads a ragged
list of point sets to a power-of-two point capacity and builds all their
ball-tree indexes in one vmapped build.

The public entry point is the DECLARATIVE one: :meth:`QueryEngine.search`
takes a mixed ``list[Query | Pipeline]`` (see :mod:`repro.engine.query`),
compiles it into per-(op, statics, query-shape) dispatch groups
(:mod:`repro.engine.plan`), and returns one uniform :class:`SearchResult`
per input, in input order.  The per-op batch methods (``range_search``,
``topk_ia``, ...) are kept as DEPRECATED wrappers that construct Query
rows and delegate to ``search()`` — same results, same stats accounting,
one extra split/stack per batch.
"""
from __future__ import annotations

import hashlib
from collections import OrderedDict
from dataclasses import dataclass, field
from functools import partial
from typing import Sequence

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import index as index_lib
from repro.core import join_search, point_search, search
from repro.core.build import pad_batch
from repro.core.index import DatasetIndex
from repro.core.repo_index import Repository
from repro.engine import batched_ops, spans
from repro.engine import plan as plan_lib
from repro.kernels import autotune
from repro.engine.query import Pipeline, Query, SearchResult  # noqa: F401

Array = jax.Array

DEFAULT_BUCKETS = (1, 2, 4, 8, 16, 32, 64, 128, 256)
DEFAULT_RESULT_CACHE = 256


def _digest(*parts) -> bytes:
    """Content digest of query-side payload arrays (result-cache key)."""
    h = hashlib.blake2b(digest_size=16)
    for p in parts:
        a = np.asarray(p)
        h.update(str(a.dtype).encode())
        h.update(str(a.shape).encode())
        h.update(a.tobytes())
    return h.digest()


def _take_rows(x, sel):
    """Row subset for a miss sub-batch (sel is None = all rows)."""
    return x if sel is None else x[np.asarray(sel)]


def _take_tree_rows(tree, sel):
    if sel is None:
        return tree
    idx = np.asarray(sel)
    return jax.tree.map(lambda x: x[idx], tree)


def _split_tuple(raw):
    """Per-row entries of a tuple-of-arrays dispatch output — device-array
    slices, so splitting for the cache never syncs to the host."""
    n = raw[0].shape[0]
    return [tuple(a[i] for a in raw) for i in range(n)]


def _join_tuple(rows):
    return tuple(jnp.stack([r[c] for r in rows])
                 for c in range(len(rows[0])))


@dataclass
class EngineStats:
    """Cumulative engine observability counters.

    Every dispatch is recorded through :meth:`count`, which also books the
    executable-cache outcome — the invariant
    ``cache_hits + cache_misses == dispatches`` holds at all times and is
    asserted in tests.  ``per_op`` keeps the same breakdown per op name.

    The RESULT cache keeps its own counters (:meth:`count_result_cache`),
    distinct from the executable-cache ones: ``result_cache_hits`` counts
    query rows answered from memoized results (no dispatch at all), while
    ``cache_hits``/``cache_misses`` keep describing compiled-executable
    reuse for the dispatches that do run.  Under a live repository,
    entries cached at a RETIRED epoch are purged eagerly on every epoch
    install and counted in ``epoch_invalidations`` — a repeat of the same
    query after a mutation forms a fresh key and is booked as a result-
    cache MISS (then a dispatch), never a silent eviction, so the
    ``cache_hits + cache_misses == dispatches`` invariant is undisturbed
    by mutations.

    The PLANNER books its own counters on top (:meth:`count_group`):
    ``plan_groups`` / ``group_counts[op]`` count the dispatch groups a
    ``search()`` call compiled (one group = one batched dispatch path, op
    groups and pipeline stage-2 groups alike), and ``pipeline_stage1`` /
    ``pipeline_stage2`` count pipeline queries whose respective stage
    executed.  None of these touch the executable-cache invariant.
    """
    queries: int = 0                 # client queries ANSWERED (ops only)
    dispatches: int = 0
    cache_hits: int = 0
    cache_misses: int = 0
    padded_queries: int = 0          # bucket padding overhead actually paid
    result_cache_hits: int = 0       # query rows served from the result LRU
    result_cache_misses: int = 0     # query rows that had to dispatch
    epoch_invalidations: int = 0     # result rows retired by a repo epoch
    mutations_coalesced: int = 0     # mutations that shared another's publish
    prepare_overlap_seconds: float = 0.0   # prepare time hidden under serving
    publish_seconds: list = field(default_factory=list)  # per-publish wall s
    plan_groups: int = 0             # dispatch groups compiled by search()
    replica_subgroups: int = 0       # replica row-blocks those groups spanned
    pipeline_stage1: int = 0         # pipelines whose dataset stage ran
    pipeline_stage2: int = 0         # pipelines whose point stage ran
    group_counts: dict = field(default_factory=dict)   # op -> groups
    per_op: dict = field(default_factory=dict)
    latency_ewma: dict = field(default_factory=dict)   # op -> EWMA seconds
    op_seconds: dict = field(default_factory=dict)     # op -> total seconds

    #: EWMA smoothing for per-op dispatch latency (seconds).  0.2 keeps
    #: roughly the last ~10 dispatches' worth of signal — stable enough
    #: for the adaptive server's straggler window, fresh enough to track
    #: a shift in traffic shape within a few batches.
    EWMA_ALPHA = 0.2

    def count(self, op: str, batch: int, bucket: int, *,
              cached: bool, internal: bool = False) -> None:
        """Record ONE dispatch.  ``internal=True`` (build_queries) books the
        dispatch and its cache outcome but keeps `queries`/`padded_queries`
        counting only answered client queries — a query that flows through
        build_queries AND an op must not be double-counted.  The per-op
        breakdown still records the batch under the internal op's name."""
        if not internal:
            self.queries += batch
            self.padded_queries += bucket - batch
        self.dispatches += 1
        if cached:
            self.cache_hits += 1
        else:
            self.cache_misses += 1
        per = self.per_op.setdefault(
            op, {"queries": 0, "dispatches": 0, "hits": 0, "misses": 0})
        per["queries"] += batch
        per["dispatches"] += 1
        per["hits" if cached else "misses"] += 1

    def count_result_cache(self, op: str, hits: int, misses: int) -> None:
        """Record one result-cache lookup pass over a query batch: `hits`
        rows were served from the LRU, `misses` rows went on to dispatch.
        Kept strictly separate from the executable-cache counters.

        Cache-hit rows ARE answered client queries, so they count toward
        ``queries``/``per_op[op]['queries']`` here; the miss rows are
        counted by :meth:`count` when their dispatch runs — each answered
        row is counted exactly once either way."""
        self.result_cache_hits += hits
        self.result_cache_misses += misses
        self.queries += hits
        per = self.per_op.setdefault(
            op, {"queries": 0, "dispatches": 0, "hits": 0, "misses": 0})
        per["queries"] += hits
        per["result_hits"] = per.get("result_hits", 0) + hits
        per["result_misses"] = per.get("result_misses", 0) + misses

    def record_publish(self, seconds: float, coalesced: int = 0) -> None:
        """Book one mutation PUBLISH (the batched slot write + upper-tree
        rebuild + atomic swap installing a group of prepared mutations):
        its wall time joins the publish latency distribution, and
        ``coalesced`` counts the mutations beyond the first that shared
        this publish (group size - 1; a lone mutation books 0)."""
        self.publish_seconds.append(seconds)
        self.mutations_coalesced += coalesced

    def publish_percentile_ms(self, p: float) -> float:
        """p-th percentile of per-publish wall time, in ms (0 if no
        publish has been recorded)."""
        if not self.publish_seconds:
            return 0.0
        import numpy as _np
        return 1e3 * float(_np.percentile(
            _np.asarray(self.publish_seconds), p))

    @property
    def publish_p50_ms(self) -> float:
        return self.publish_percentile_ms(50.0)

    @property
    def publish_p99_ms(self) -> float:
        return self.publish_percentile_ms(99.0)

    def record_latency(self, op: str, seconds: float) -> None:
        """Book one dispatch group's wall-clock latency: cumulative
        ``op_seconds[op]`` plus an EWMA (``latency_ewma[op]``) that the
        adaptive server reads to size its straggler window.  First sample
        seeds the EWMA directly."""
        self.op_seconds[op] = self.op_seconds.get(op, 0.0) + seconds
        prev = self.latency_ewma.get(op)
        self.latency_ewma[op] = (
            seconds if prev is None
            else prev + self.EWMA_ALPHA * (seconds - prev))

    def count_group(self, op: str, subgroups: int = 1) -> None:
        """Record ONE dispatch group compiled by the planner (an op group
        of a mixed batch, or a pipeline stage-2 group booked under its
        point op's name).  Kept in ``group_counts`` — NOT inside
        ``per_op`` — so the per-op hit/miss/dispatch breakdown stays
        exactly the executable-dispatch accounting.

        ``subgroups`` is the number of replica row-blocks the group's
        planned rows span (1 on local/1-D-sharded dispatch; up to R under
        a :class:`~repro.engine.replicated.ReplicatedDispatcher` — a
        planning-level metric, booked whether or not the rows later hit
        the result cache): ``plan_groups`` keeps counting compiled
        groups, while ``group_counts[op]`` and ``replica_subgroups``
        account for the sub-groups, so ``replica_subgroups >=
        plan_groups`` always."""
        self.plan_groups += 1
        self.replica_subgroups += subgroups
        self.group_counts[op] = self.group_counts.get(op, 0) + subgroups

    def _fold_stats(self, op: str, stats, fields: tuple) -> None:
        """Shared fold for one dispatch's per-query stats (a single stats
        value or a sequence from one batched dispatch): each named counter
        field accumulates as a sum across the batch, ``pruned_fraction``
        records the latest dispatch's mean across its queries."""
        batch = list(stats) if isinstance(stats, (list, tuple)) else [stats]
        if not batch:
            return
        per = self.per_op.setdefault(
            op, {"queries": 0, "dispatches": 0, "hits": 0, "misses": 0})
        for name in fields:
            per[name] = (per.get(name, 0)
                         + sum(getattr(s, name) for s in batch))
        per["pruned_fraction"] = (
            sum(s.pruned_fraction for s in batch) / len(batch))

    def record_point_search(self, op: str, stats) -> None:
        """Fold one point-granularity dispatch's per-query
        :class:`~repro.core.point_search.PointStats` into the per-op
        breakdown — the point-op sibling of :meth:`record_search`
        (RangeP books leaf-slab pruning, NNP the Eq. 4 pair-grid
        pruning)."""
        self._fold_stats(op, stats, ("nodes_evaluated", "leaves_scanned"))

    def record_search(self, op: str, stats) -> None:
        """Fold one dispatch's :class:`~repro.core.search.SearchStats` into
        the per-op breakdown.  ExactHaus books these on every dispatch
        (the engine never discards its SearchStats)."""
        self._fold_stats(op, stats, ("nodes_evaluated",
                                     "candidates_after_bounds",
                                     "exact_evaluations"))


class LocalDispatcher:
    """Single-device dispatch: one jitted executable per op over the
    resident repository.

    Each ``build_*`` returns a callable taking only the query-side
    operands; the repository rides along as a LATE-BOUND leading jit
    argument — the callable reads ``self.repo`` at call time (not a
    closed-over constant, so XLA never bakes the arrays in, and not a
    bind-time `partial`, so a live mutation that swaps ``self.repo`` for
    a same-shape successor takes effect on the very next dispatch with
    the SAME compiled executable).  The attribute swap is atomic, so a
    dispatch sees either the whole old repository or the whole new one —
    never a torn mix.

    ``repo_epoch`` is the LAYOUT epoch: bumped by a live repository only
    when the slot-array shapes change (capacity-tier growth), and folded
    into every executable-cache key, so executables whose build closed
    over the old slot count are retired rather than re-served.
    """

    name = "local"
    #: layout epoch — bumped on slot-shape changes (live tier growth);
    #: part of every executable-cache key like `autotune.epoch()`
    repo_epoch = 0

    def __init__(self, repo: Repository):
        self.repo = repo
        self.n_slots = repo.n_slots

    def _bind(self, impl, name: str):
        def program(*args, **kw):
            return impl(*args, **kw)

        # the compiled program is named after its op (``jit_<name>`` in IR
        # dumps and device traces); a bare partial would lower as
        # ``jit__unknown``
        program.__name__ = program.__qualname__ = name
        jitted = jax.jit(program)

        def call(*args, **kw):
            return jitted(self.repo, *args, **kw)

        return call

    def build_range_search(self):
        return self._bind(batched_ops.range_search_batched, "range_search")

    def build_topk_ia(self, k: int):
        return self._bind(partial(batched_ops.topk_ia_batched, k=k),
                          "topk_ia")

    def build_topk_gbo(self, k: int):
        return self._bind(partial(batched_ops.topk_gbo_batched, k=k),
                          "topk_gbo")

    def build_topk_hausdorff_approx(self, k: int):
        return self._bind(
            partial(batched_ops.topk_hausdorff_approx_batched, k=k),
            "topk_hausdorff_approx")

    def build_topk_hausdorff(self, k: int, refine_levels: int, chunk: int):
        # batched end-to-end: (B, ...) query batch -> one device dispatch
        # (search._topk_hausdorff_device_batched is already jitted); late
        # repo binding like every other op
        def call(q_batch):
            return batched_ops.topk_hausdorff_batched(
                self.repo, q_batch, k=k, refine_levels=refine_levels,
                chunk=chunk)

        return call

    def build_range_points(self):
        return self._bind(batched_ops.range_points_batched, "range_points")

    def build_nnp(self):
        return self._bind(batched_ops.nnp_pruned_batched, "nnp")

    def build_topk_overlap(self, k: int, chunk: int):
        return self._bind(partial(batched_ops.topk_join_batched, k=k,
                                  mode="overlap", chunk=chunk),
                          "topk_overlap")

    def build_topk_coverage(self, k: int, chunk: int):
        return self._bind(partial(batched_ops.topk_join_batched, k=k,
                                  mode="coverage", chunk=chunk),
                          "topk_coverage")

    def build_join_rerank(self, mode: str):
        # dataset→dataset pipeline stage 2: row-wise exact join score of
        # stage-1 winner slots (gathered by id on device) vs the query row
        def impl(repo, ds_ids, q_pts, q_val):
            d_pts = repo.ds_index.points[ds_ids]
            d_val = repo.ds_index.valid[ds_ids]
            return join_search.pair_scores(repo, d_pts, d_val,
                                           q_pts, q_val, mode)

        return self._bind(impl, f"join_rerank_{mode}")


class QueryEngine:
    """Batched search over a resident repository (see module docstring).

    Passing ``mesh=`` (a `jax.sharding.Mesh`) selects the sharded dispatch
    path: dataset slots are placed across ``shard_spec`` (a mesh axis name,
    default ``"data"``) and per-shard results are merged on device —
    bit-identical to the local path (asserted in
    tests/test_engine_sharded.py).  A mesh that also carries a
    ``replica_spec`` axis (default ``"replica"``; build one with
    :func:`~repro.engine.replicated.replica_mesh`) selects the
    REPLICA-PARALLEL dispatcher instead: the slot shards replicate across
    replica groups and each group serves its own slice of every batch's
    rows — still bit-identical (tests/test_engine_replicated.py).
    """

    def __init__(
        self,
        repo: Repository,
        *,
        buckets: Sequence[int] = DEFAULT_BUCKETS,
        leaf_capacity: int = 16,
        mesh=None,
        shard_spec: str = "data",
        replica_spec: str = "replica",
        dispatcher=None,
        result_cache_size: int = DEFAULT_RESULT_CACHE,
        default_chunk: int = 32,
    ):
        self.buckets = tuple(sorted(buckets))
        self.leaf_capacity = leaf_capacity
        self.default_chunk = default_chunk
        self.stats = EngineStats()
        self._executables: dict = {}
        self.result_cache_size = result_cache_size
        self._result_cache: OrderedDict = OrderedDict()
        self._n_valid = int(repo.ds_valid.sum())
        # live-repository versioning: the DATA epoch (bumped on every
        # mutation; part of every dataset-op result-cache key) and the
        # per-slot epochs (point-op keys carry their target slot's epoch,
        # so mutations of OTHER datasets never invalidate them)
        self._repo_epoch = 0
        self._slot_epochs = None
        if dispatcher is None:
            if mesh is not None:
                # a mesh carrying a replica axis selects replica-parallel
                # dispatch (query rows split across replica groups);
                # otherwise the 1-D data-sharded path
                if replica_spec in getattr(mesh, "axis_names", ()):
                    from repro.engine.replicated import ReplicatedDispatcher
                    dispatcher = ReplicatedDispatcher(
                        repo, mesh, axis=shard_spec,
                        replica_axis=replica_spec)
                else:
                    from repro.engine.sharded import ShardedDispatcher
                    dispatcher = ShardedDispatcher(repo, mesh,
                                                   axis=shard_spec)
            else:
                dispatcher = LocalDispatcher(repo)
        self.dispatch = dispatcher
        # hold the dispatcher's PLACED repository (the sharded copy under a
        # ShardedDispatcher) rather than the builder's, so the engine never
        # pins an extra replicated copy once the caller drops theirs
        self.repo = getattr(dispatcher, "repo", repo)

    # -- autotuning --------------------------------------------------------

    def tune(self, **kw):
        """One-time measured sweep of the kernel dispatch constants for
        THIS engine's repository shapes (see :mod:`repro.engine.tune`).
        Installs per-(backend, shape-bucket) routing verdicts in the
        process-global autotune table — gated on bitwise identity with the
        ref path, so tuned routing never shifts a result — and picks the
        fastest ExactHaus refinement ``chunk`` as ``self.default_chunk``.
        Returns the tuner's report dict."""
        from repro.engine.tune import tune_engine
        return tune_engine(self, **kw)

    # -- bucketing ---------------------------------------------------------

    def bucket_for(self, batch: int) -> int:
        for b in self.buckets:
            if b >= batch:
                return b
        b = self.buckets[-1]
        while b < batch:          # beyond the ladder: grow geometrically
            b *= 2
        return b

    def _plan_subgroups(self, batch: int) -> int:
        """Replica row-blocks a `batch`-row dispatch group spans under
        this engine's dispatcher (1 unless the dispatcher splits rows
        across replica groups) — the planner feeds this to
        :meth:`EngineStats.count_group`."""
        f = getattr(self.dispatch, "row_subgroups", None)
        return 1 if f is None else f(batch, self.bucket_for(batch))

    @staticmethod
    def _pad_rows(x: Array, bucket: int) -> Array:
        """Pad a (B, ...) array to (bucket, ...) by replicating row 0 —
        padding rows recompute a real query, so no masking is needed and
        results for them are simply sliced off."""
        b = x.shape[0]
        if b == bucket:
            return x
        reps = jnp.broadcast_to(x[:1], (bucket - b,) + x.shape[1:])
        return jnp.concatenate([x, reps], axis=0)

    def _pad_tree(self, tree, bucket: int):
        return jax.tree.map(lambda x: self._pad_rows(x, bucket), tree)

    # -- executable cache --------------------------------------------------

    def _executable(self, key, build):
        """Cache lookup; returns (fn, cached) so the dispatch path can book
        the hit/miss through `stats.count` uniformly for every op.

        The autotune table epoch is part of every key: executables close
        over routing decisions made at build time (kernel vs ref, tile
        sizes), so a `tune()` that installs new configs must NOT keep
        serving stale compilations — the epoch bump retires them.  The
        dispatcher's LAYOUT epoch rides along for the same reason: builds
        close over slot-count constants, so a live capacity-tier growth
        must retire them too (data-only mutations leave both epochs alone
        and keep every executable)."""
        key = (autotune.epoch(),
               getattr(self.dispatch, "repo_epoch", 0)) + tuple(key)
        fn = self._executables.get(key)
        cached = fn is not None
        if not cached:
            fn = build()
            self._executables[key] = fn
        return fn, cached

    # -- repository epochs (live mutations) -------------------------------

    @property
    def repo_epoch(self) -> int:
        """The DATA epoch of the resident repository (0 forever on a
        frozen engine; bumped by :class:`~repro.engine.live.LiveRepository`
        on every published mutation)."""
        return self._repo_epoch

    def slot_epoch(self, ds_id) -> int:
        """Per-slot mutation epoch of dataset ``ds_id`` (0 on a frozen
        engine) — the component point-op result keys carry, so caches for
        UNTOUCHED datasets survive mutations elsewhere."""
        se = self._slot_epochs
        return 0 if se is None else int(se[int(ds_id)])

    def set_repo_epoch(self, epoch: int, slot_epochs=None,
                       touched=None) -> None:
        """Install a new repository epoch after a live mutation.

        ``epoch`` must be monotonically increasing; ``slot_epochs`` (an
        int array indexed by slot) replaces the per-slot epoch table.
        Result-cache entries keyed at retired epochs are purged EAGERLY
        and booked in ``stats.epoch_invalidations`` — they are retired
        versions, not capacity evictions, and the counter makes the
        distinction observable.  Executables are NOT touched: data
        mutations reuse every compiled program (the layout epoch on the
        dispatcher handles shape changes separately).

        ``touched`` (optional) is the exact set of slots this publish
        wrote: invalidation is then PRECISE for point-granularity rows —
        only entries keyed on a touched slot are even inspected, so
        entries for untouched slots survive a publish without a per-key
        epoch probe (a coalesced N-mutation publish makes ONE such sweep,
        not N).  Dataset-granularity rows always retire on a data-epoch
        move: any slot write can change a whole-repository answer."""
        if epoch < self._repo_epoch:
            raise ValueError(
                f"repository epoch must be monotone: {epoch} < "
                f"{self._repo_epoch}")
        self._repo_epoch = int(epoch)
        if slot_epochs is not None:
            self._slot_epochs = slot_epochs
        stale = []
        for key in list(self._result_cache):
            if key[0] in ("range_points", "nnp"):
                # (op, ds_id, slot_epoch, ...)
                if touched is not None and key[1] not in touched:
                    continue               # precise retention: untouched
                if key[2] != self.slot_epoch(key[1]):
                    stale.append(key)
            elif key[1] != self._repo_epoch:
                # (op, repo_epoch, ...)
                stale.append(key)
        for key in stale:
            self._result_cache.pop(key, None)
        self.stats.epoch_invalidations += len(stale)

    # -- result cache ------------------------------------------------------

    def _cache_insert(self, keys, rows) -> None:
        for key, row in zip(keys, rows):
            self._result_cache[key] = row           # inserts at MRU end
        while len(self._result_cache) > self.result_cache_size:
            self._result_cache.popitem(last=False)

    def _serve_cached(self, op: str, keys, dispatch, split, join):
        """Serve per-query result rows through the result cache (LRU).

        ``keys`` holds one hashable content key per query row;
        ``dispatch(sel)`` runs the op for row positions ``sel`` (or ALL
        rows when ``sel is None``) as one batch; ``split(raw)`` slices a
        dispatch output into per-row entries (device-array slices — lazy,
        no host sync); ``join(rows)`` reassembles rows into the op's
        output shape.

        Repeated queries short-circuit BEFORE bucketing: only DISTINCT
        miss rows form the dispatched sub-batch (duplicate rows inside one
        batch ride their twin's dispatch and are booked as cache hits, so
        ``result_cache_misses`` counts exactly the rows that went through
        a dispatch).  The common cold case — every row a distinct miss —
        returns the dispatch output UNCHANGED, so a no-repeat workload
        pays only the key digests."""
        with spans.span("engine.marshal"):
            out_rows = [None] * len(keys)
            miss: list = []
            hits = 0
            for i, key in enumerate(keys):
                row = self._result_cache.get(key)
                if row is None:
                    miss.append(i)
                else:
                    self._result_cache.move_to_end(key)
                    out_rows[i] = row
                    hits += 1
            uniq_pos: dict = {}        # key -> row index in the sub-batch
            uniq: list = []
            for i in miss:
                if keys[i] not in uniq_pos:
                    uniq_pos[keys[i]] = len(uniq)
                    uniq.append(i)
            self.stats.count_result_cache(
                op, hits + (len(miss) - len(uniq)), len(uniq))
        if not hits and len(uniq) == len(keys):    # all-distinct cold batch
            raw = dispatch(None)
            with spans.span("engine.results", part="cache", rows=len(keys)):
                self._cache_insert(keys, split(raw))
            return raw
        raw = dispatch(uniq) if uniq else None
        with spans.span("engine.results", part="cache", rows=len(uniq)):
            if uniq:
                rows = split(raw)
                self._cache_insert([keys[i] for i in uniq], rows)
                for i in miss:
                    out_rows[i] = rows[uniq_pos[keys[i]]]
            return join(out_rows)

    # -- query construction ------------------------------------------------

    def build_queries(
        self, pointsets: Sequence[np.ndarray]
    ) -> DatasetIndex:
        """Index a ragged list of query point sets as one (B, ...) batch.

        Point counts are bucketed to the next power of two (so repeated
        traffic reuses executables) and the B tree builds run as one
        vmapped dispatch.  Queries are replicated (never sharded): both
        dispatch paths consume the same batched query index.
        """
        n_max = max(int(p.shape[0]) for p in pointsets)
        n_bucket = self.leaf_capacity
        while n_bucket < n_max:
            n_bucket *= 2
        depth = index_lib.depth_for(n_bucket, self.leaf_capacity)
        pts, val, depth = pad_batch(pointsets, self.leaf_capacity, depth)
        bucket = self.bucket_for(len(pointsets))
        pts = self._pad_rows(pts, bucket)
        val = self._pad_rows(val, bucket)
        build, cached = self._executable(
            ("build", bucket, pts.shape[1], depth),
            lambda: jax.jit(partial(index_lib.build_index_batch,
                                    depth=depth)),
        )
        q_batch = build(pts, val)
        self.stats.count("build_queries", len(pointsets), bucket,
                         cached=cached, internal=True)
        return jax.tree.map(lambda x: x[: len(pointsets)], q_batch)

    # -- declarative entry point ------------------------------------------

    def search(self, queries: Sequence) -> list:
        """THE unified entry point: answer a mixed declarative batch.

        ``queries`` is a list of :class:`~repro.engine.query.Query` and/or
        :class:`~repro.engine.query.Pipeline` values covering any mix of
        the seven ops.  The planner (:mod:`repro.engine.plan`) compiles
        the batch into per-(op, statics, query-shape) dispatch groups —
        each group one batched dispatch over the bucket ladder, executable
        cache, and result cache (cache hits short-circuit per row) — runs
        pipeline dataset stages inside those groups, then feeds the
        winning dataset ids to the point stages with the id handoff
        staying on device.  Returns one
        :class:`~repro.engine.query.SearchResult` per input, in INPUT
        order, as a list whose ``groups`` is the number of dispatch groups
        the call planned (:class:`~repro.engine.plan.Results`).
        """
        return plan_lib.execute(self, queries)

    # -- per-op group executors (one batched dispatch path each) ----------

    def _exec_range_search(self, r_lo, r_hi):
        """RangeS for B query boxes -> dataset masks (B, B_pad)."""
        with spans.span("engine.marshal"):
            r_lo = jnp.atleast_2d(jnp.asarray(r_lo, jnp.float32))
            r_hi = jnp.atleast_2d(jnp.asarray(r_hi, jnp.float32))
            if self.result_cache_size:
                lo_np, hi_np = np.asarray(r_lo), np.asarray(r_hi)
                keys = [("range_search", self._repo_epoch,
                         _digest(lo_np[i], hi_np[i]))
                        for i in range(lo_np.shape[0])]
        if not self.result_cache_size:
            return self._range_search_dispatch(r_lo, r_hi)
        return self._serve_cached(
            "range_search", keys,
            lambda sel: self._range_search_dispatch(
                _take_rows(r_lo, sel), _take_rows(r_hi, sel)),
            split=lambda masks: [masks[i] for i in range(masks.shape[0])],
            join=jnp.stack)

    def _range_search_dispatch(self, r_lo, r_hi):
        B = r_lo.shape[0]
        bucket = self.bucket_for(B)
        with spans.span("engine.marshal"):
            fn, cached = self._executable(
                ("range_search", bucket), self.dispatch.build_range_search)
            args = (self._pad_rows(r_lo, bucket),
                    self._pad_rows(r_hi, bucket))
        with spans.span("engine.launch"):
            masks, _ = fn(*args)
        self.stats.count("range_search", B, bucket, cached=cached)
        return masks[:B]

    def _exec_topk_ia(self, q_lo, q_hi, k: int):
        """Top-k IA for B query boxes -> (vals, ids) each (B, k)."""
        with spans.span("engine.marshal"):
            q_lo = jnp.atleast_2d(jnp.asarray(q_lo, jnp.float32))
            q_hi = jnp.atleast_2d(jnp.asarray(q_hi, jnp.float32))
            if self.result_cache_size:
                lo_np, hi_np = np.asarray(q_lo), np.asarray(q_hi)
                keys = [("topk_ia", self._repo_epoch, k,
                         _digest(lo_np[i], hi_np[i]))
                        for i in range(lo_np.shape[0])]
        if not self.result_cache_size:
            return self._topk_ia_dispatch(q_lo, q_hi, k)
        return self._serve_cached(
            "topk_ia", keys,
            lambda sel: self._topk_ia_dispatch(
                _take_rows(q_lo, sel), _take_rows(q_hi, sel), k),
            split=_split_tuple, join=_join_tuple)

    def _topk_ia_dispatch(self, q_lo, q_hi, k: int):
        B = q_lo.shape[0]
        bucket = self.bucket_for(B)
        with spans.span("engine.marshal"):
            fn, cached = self._executable(
                ("topk_ia", bucket, k),
                lambda: self.dispatch.build_topk_ia(k))
            args = (self._pad_rows(q_lo, bucket),
                    self._pad_rows(q_hi, bucket))
        with spans.span("engine.launch"):
            vals, ids = fn(*args)
        self.stats.count("topk_ia", B, bucket, cached=cached)
        return vals[:B], ids[:B]

    def _exec_topk_gbo(self, q_sigs, k: int):
        """Top-k GBO for B query signatures -> (vals, ids) each (B, k)."""
        with spans.span("engine.marshal"):
            q_sigs = jnp.asarray(q_sigs)
            if q_sigs.ndim == 1:
                q_sigs = q_sigs[None, :]
            if self.result_cache_size:
                sigs_np = np.asarray(q_sigs)
                keys = [("topk_gbo", self._repo_epoch, k, _digest(sigs_np[i]))
                        for i in range(sigs_np.shape[0])]
        if not self.result_cache_size:
            return self._topk_gbo_dispatch(q_sigs, k)
        return self._serve_cached(
            "topk_gbo", keys,
            lambda sel: self._topk_gbo_dispatch(_take_rows(q_sigs, sel), k),
            split=_split_tuple, join=_join_tuple)

    def _topk_gbo_dispatch(self, q_sigs, k: int):
        B = q_sigs.shape[0]
        bucket = self.bucket_for(B)
        with spans.span("engine.marshal"):
            fn, cached = self._executable(
                ("topk_gbo", bucket, k),
                lambda: self.dispatch.build_topk_gbo(k))
            padded = self._pad_rows(q_sigs, bucket)
        with spans.span("engine.launch"):
            vals, ids = fn(padded)
        self.stats.count("topk_gbo", B, bucket, cached=cached)
        return vals[:B], ids[:B]

    def _exec_topk_join(self, op: str, q_pts, q_val, k: int):
        """Joinable top-k (``topk_overlap`` / ``topk_coverage``) for B raw
        query point sets -> (vals (B, k), ids (B, k), list[SearchStats]).

        Scores are exact integers, so cached rows replay bit-identically;
        keys carry the repository epoch (the bound phase reads resident
        coarse signatures and the refine reads resident points, so ANY
        published mutation may change a row) — `set_repo_epoch` retires
        them wholesale like every dataset-granularity op."""
        with spans.span("engine.marshal"):
            q_pts = jnp.asarray(q_pts, jnp.float32)
            q_val = jnp.asarray(q_val, bool)
            if self.result_cache_size:
                pts_np, val_np = np.asarray(q_pts), np.asarray(q_val)
                keys = [(op, self._repo_epoch, k,
                         _digest(pts_np[i], val_np[i]))
                        for i in range(pts_np.shape[0])]
        if not self.result_cache_size:
            return self._topk_join_dispatch(op, q_pts, q_val, k)
        return self._serve_cached(
            op, keys,
            lambda sel: self._topk_join_dispatch(
                op, _take_rows(q_pts, sel), _take_rows(q_val, sel), k),
            split=lambda raw: [(raw[0][i], raw[1][i], raw[2][i])
                               for i in range(len(raw[2]))],
            join=lambda rows: (jnp.stack([r[0] for r in rows]),
                               jnp.stack([r[1] for r in rows]),
                               [r[2] for r in rows]))

    def _topk_join_dispatch(self, op: str, q_pts, q_val, k: int):
        B = q_pts.shape[0]
        bucket = self.bucket_for(B)
        chunk = self.default_chunk
        key = (op, bucket, q_pts.shape[1], k, chunk)
        with spans.span("engine.marshal"):
            fn, cached = self._executable(
                key, lambda: getattr(self.dispatch, "build_" + op)(k, chunk))
            args = (self._pad_rows(q_pts, bucket),
                    self._pad_rows(q_val, bucket))
        with spans.span("engine.launch"):
            vals, ids, nodes, cand_after, evaluated = fn(*args)
        self.stats.count(op, B, bucket, cached=cached)
        with spans.span("engine.readback"):
            stats = join_search.join_stats_host(
                self._n_valid, evaluated[:B], nodes[:B], cand_after[:B])
        self.stats.record_search(op, stats)
        return vals[:B], ids[:B], stats

    def _exec_join_rerank(self, op: str, ds_ids, q_pts, q_val):
        """Stage-2 dataset→dataset scoring: row-wise exact join score of
        winner slot `ds_ids[t]` vs query row t -> (T,) int32 on device.

        Like the point-stage executors, the device-resident id handoff
        path skips the result cache (host keys would force a mid-pipeline
        sync); the executable rides the bucket ladder as usual."""
        mode = "overlap" if op == "topk_overlap" else "coverage"
        T = ds_ids.shape[0]
        bucket = self.bucket_for(T)
        key = ("join_rerank", mode, bucket, q_pts.shape[1])
        with spans.span("engine.marshal"):
            fn, cached = self._executable(
                key, lambda: self.dispatch.build_join_rerank(mode))
            args = (
                self._pad_rows(jnp.asarray(ds_ids, jnp.int32), bucket),
                self._pad_rows(jnp.asarray(q_pts, jnp.float32), bucket),
                self._pad_rows(jnp.asarray(q_val, bool), bucket))
        with spans.span("engine.launch"):
            scores = fn(*args)
        # stage-2 rows count like the point-stage dispatches do (one row
        # per stage-1 winner), keeping hits+misses == dispatches intact
        self.stats.count(op, T, bucket, cached=cached)
        return scores[:T]

    def _exec_topk_hausdorff_approx(self, q_batch: DatasetIndex, k: int,
                                    eps):
        """ApproHaus for a (B, ...) query-index batch -> (vals, ids,
        eps_eff)."""
        if not self.result_cache_size:
            return self._topk_hausdorff_approx_dispatch(q_batch, k, eps)
        with spans.span("engine.readback"):
            pts, val = np.asarray(q_batch.points), np.asarray(q_batch.valid)
        with spans.span("engine.marshal"):
            # depth is part of the key: (points, valid, depth) fully
            # determine a DatasetIndex built by this codebase (node stats
            # are derived from them), so same points under a different
            # tree never collide
            keys = [("approx_haus", self._repo_epoch, k, float(eps),
                     q_batch.depth,
                     _digest(pts[i], val[i])) for i in range(pts.shape[0])]
        return self._serve_cached(
            "topk_hausdorff_approx", keys,
            lambda sel: self._topk_hausdorff_approx_dispatch(
                _take_tree_rows(q_batch, sel), k, eps),
            split=_split_tuple, join=_join_tuple)

    def _topk_hausdorff_approx_dispatch(self, q_batch, k: int, eps):
        B = q_batch.points.shape[0]
        bucket = self.bucket_for(B)
        key = ("approx_haus", bucket, q_batch.points.shape[1], k)
        with spans.span("engine.marshal"):
            fn, cached = self._executable(
                key, lambda: self.dispatch.build_topk_hausdorff_approx(k))
            padded = self._pad_tree(q_batch, bucket)
            eps = jnp.float32(eps)
        with spans.span("engine.launch"):
            vals, ids, eps_eff = fn(padded, eps=eps)
        self.stats.count("topk_hausdorff_approx", B, bucket, cached=cached)
        return vals[:B], ids[:B], eps_eff[:B]

    def _exec_topk_hausdorff(self, q_batch: DatasetIndex, k: int,
                             refine_levels: int = 3,
                             chunk: int | None = None):
        """ExactHaus for a (B, ...) query-index batch: ONE device dispatch
        (shared phase-2 work frontier; per-shard loops + batched tau
        all-reduce under a ShardedDispatcher) -> (vals (B, k), ids (B, k),
        list[SearchStats]).

        ``chunk=None`` (the default) resolves to the engine's tuned
        ``default_chunk`` BEFORE any cache key is formed — chunk only
        chunks the refinement sweep (vals/ids are bit-identical under any
        chunk; the `evaluated` counter granularity changes), so retuning
        it between calls is always safe."""
        if chunk is None:
            chunk = self.default_chunk
        if not self.result_cache_size:
            return self._topk_hausdorff_dispatch(
                q_batch, k, refine_levels, chunk)
        with spans.span("engine.readback"):
            pts, val = np.asarray(q_batch.points), np.asarray(q_batch.valid)
        with spans.span("engine.marshal"):
            # depth in the key for the same reason as ApproHaus (a
            # different tree over the same points changes the SearchStats)
            keys = [("exact_haus", self._repo_epoch, k, refine_levels,
                     chunk, q_batch.depth,
                     _digest(pts[i], val[i])) for i in range(pts.shape[0])]
        return self._serve_cached(
            "topk_hausdorff", keys,
            lambda sel: self._topk_hausdorff_dispatch(
                _take_tree_rows(q_batch, sel), k, refine_levels, chunk),
            split=lambda raw: [(raw[0][i], raw[1][i], raw[2][i])
                               for i in range(len(raw[2]))],
            join=lambda rows: (jnp.stack([r[0] for r in rows]),
                               jnp.stack([r[1] for r in rows]),
                               [r[2] for r in rows]))

    def _topk_hausdorff_dispatch(self, q_batch, k: int, refine_levels: int,
                                 chunk: int):
        """One batched ExactHaus device dispatch + per-query SearchStats."""
        B = q_batch.points.shape[0]
        bucket = self.bucket_for(B)
        key = ("exact_haus", bucket, q_batch.points.shape[1], k,
               refine_levels, chunk)
        with spans.span("engine.marshal"):
            fn, cached = self._executable(
                key, lambda: self.dispatch.build_topk_hausdorff(
                    k, refine_levels, chunk))
            padded = self._pad_tree(q_batch, bucket)
        with spans.span("engine.launch"):
            vals, ids, nodes, cand_after, evaluated = fn(padded)
        self.stats.count("topk_hausdorff", B, bucket, cached=cached)
        with spans.span("engine.readback"):
            nodes = np.asarray(nodes)
            cand_after = np.asarray(cand_after)
            evaluated = np.asarray(evaluated)
        with spans.span("engine.results"):
            stats = [
                search.SearchStats(
                    int(nodes[i]), int(cand_after[i]), int(evaluated[i]),
                    1.0 - int(evaluated[i]) / max(self._n_valid, 1),
                )
                for i in range(B)
            ]
        self.stats.record_search("topk_hausdorff", stats)
        return vals[:B], ids[:B], stats

    def _exec_range_points(self, ds_ids, r_lo, r_hi):
        """RangeP for B (dataset id, box) requests -> (take masks
        (B, n_pad), list[PointStats]).

        Point ops ride the result cache too, but ONLY when ``ds_ids``
        arrives host-resident (the planner's op-group path and the legacy
        shims): pipeline stage 2 hands winning ids over ON DEVICE, and
        forming host cache keys there would force a sync in the middle of
        the pipeline — so that path dispatches directly.  Keys carry the
        target slot's mutation epoch, so a live mutation of dataset j
        retires exactly the entries that touched j.  Cached rows keep
        their PointStats; :meth:`EngineStats.record_point_search` books
        only the rows that actually dispatched."""
        if self.result_cache_size and not isinstance(ds_ids, jax.Array):
            with spans.span("engine.marshal"):
                ids_np = np.atleast_1d(np.asarray(ds_ids, np.int32))
                lo_np = np.atleast_2d(np.asarray(r_lo, np.float32))
                hi_np = np.atleast_2d(np.asarray(r_hi, np.float32))
                keys = [("range_points", int(ids_np[i]),
                         self.slot_epoch(ids_np[i]),
                         _digest(lo_np[i], hi_np[i]))
                        for i in range(ids_np.shape[0])]
            return self._serve_cached(
                "range_points", keys,
                lambda sel: self._range_points_dispatch(
                    _take_rows(ids_np, sel), _take_rows(lo_np, sel),
                    _take_rows(hi_np, sel)),
                split=lambda raw: [(raw[0][i], raw[1][i])
                                   for i in range(len(raw[1]))],
                join=lambda rows: (jnp.stack([r[0] for r in rows]),
                                   [r[1] for r in rows]))
        return self._range_points_dispatch(ds_ids, r_lo, r_hi)

    def _range_points_dispatch(self, ds_ids, r_lo, r_hi):
        """One batched RangeP dispatch; the traversal's scanned-leaf mask
        is no longer discarded: per-query leaf pruning stats are computed
        from it (device-side sums, one tiny transfer) and folded into
        ``EngineStats`` via :meth:`EngineStats.record_point_search`."""
        with spans.span("engine.marshal"):
            ds_ids = jnp.atleast_1d(jnp.asarray(ds_ids, jnp.int32))
            r_lo = jnp.atleast_2d(jnp.asarray(r_lo, jnp.float32))
            r_hi = jnp.atleast_2d(jnp.asarray(r_hi, jnp.float32))
            B = ds_ids.shape[0]
            bucket = self.bucket_for(B)
            fn, cached = self._executable(
                ("range_points", bucket), self.dispatch.build_range_points)
            args = (self._pad_rows(ds_ids, bucket),
                    self._pad_rows(r_lo, bucket),
                    self._pad_rows(r_hi, bucket))
        with spans.span("engine.launch"):
            take, scanned = fn(*args)
        self.stats.count("range_points", B, bucket, cached=cached)
        n_leaves = int(scanned.shape[1])
        with spans.span("engine.readback"):
            sc = np.asarray(jnp.sum(scanned[:B], axis=1))
        with spans.span("engine.results"):
            stats = [
                point_search.PointStats(
                    n_leaves, int(sc[i]),
                    float(1.0 - int(sc[i]) / max(n_leaves, 1)))
                for i in range(B)
            ]
        self.stats.record_point_search("range_points", stats)
        return take[:B], stats

    def _exec_nnp(self, ds_ids, q_batch: DatasetIndex):
        """Tree-pruned NNP for B (query, dataset id) requests ->
        (dists (B, nq), idx (B, nq), list[PointStats]).

        Same host-gated result caching as RangeP (see
        :meth:`_exec_range_points`): cacheable only when the ids arrive
        host-resident; the on-device stage-2 handoff dispatches
        directly."""
        if self.result_cache_size and not isinstance(ds_ids, jax.Array):
            with spans.span("engine.readback"):
                pts = np.asarray(q_batch.points)
                val = np.asarray(q_batch.valid)
            with spans.span("engine.marshal"):
                ids_np = np.atleast_1d(np.asarray(ds_ids, np.int32))
                keys = [("nnp", int(ids_np[i]), self.slot_epoch(ids_np[i]),
                         q_batch.depth, _digest(pts[i], val[i]))
                        for i in range(ids_np.shape[0])]
            return self._serve_cached(
                "nnp", keys,
                lambda sel: self._nnp_dispatch(
                    _take_rows(ids_np, sel), _take_tree_rows(q_batch, sel)),
                split=lambda raw: [(raw[0][i], raw[1][i], raw[2][i])
                                   for i in range(len(raw[2]))],
                join=lambda rows: (jnp.stack([r[0] for r in rows]),
                                   jnp.stack([r[1] for r in rows]),
                                   [r[2] for r in rows]))
        return self._nnp_dispatch(ds_ids, q_batch)

    def _nnp_dispatch(self, ds_ids, q_batch: DatasetIndex):
        """One batched NNP dispatch through
        `core/point_search.nnp_pruned_core` (the Eq. 4 pair-grid prune)
        on BOTH dispatchers; the surviving ``pair_live`` mask is surfaced
        as per-query PointStats — the same counters the host `nnp_pruned`
        reports — instead of being thrown away."""
        with spans.span("engine.marshal"):
            ds_ids = jnp.atleast_1d(jnp.asarray(ds_ids, jnp.int32))
            B = ds_ids.shape[0]
            bucket = self.bucket_for(B)
            fn, cached = self._executable(
                ("nnp", bucket, q_batch.points.shape[1]),
                self.dispatch.build_nnp)
            args = (self._pad_rows(ds_ids, bucket),
                    self._pad_tree(q_batch, bucket))
        with spans.span("engine.launch"):
            dists, idxs, pair_live = fn(*args)
        self.stats.count("nnp", B, bucket, cached=cached)
        pairs = int(pair_live.shape[1] * pair_live.shape[2])
        with spans.span("engine.readback"):
            live = np.asarray(jnp.sum(pair_live[:B], axis=(1, 2)))
        with spans.span("engine.results"):
            stats = [
                point_search.PointStats(
                    pairs, int(live[i]),
                    float(1.0 - int(live[i]) / max(pairs, 1)))
                for i in range(B)
            ]
        self.stats.record_point_search("nnp", stats)
        return dists[:B], idxs[:B], stats

    # -- legacy per-op batch methods (deprecated shims over search()) -----

    @staticmethod
    def _host_tree_rows(tree):
        """Split a (B, ...) index batch into host-array rows (ONE device
        sync for the whole tree, then free np views) for Query
        construction in the legacy shims."""
        np_tree = jax.tree.map(np.asarray, tree)
        B = np_tree.points.shape[0]
        return [jax.tree.map(lambda x, i=i: x[i], np_tree)
                for i in range(B)]

    def range_search(self, r_lo, r_hi):
        """DEPRECATED shim (use `search`): RangeS for B query boxes ->
        dataset masks (B, B_pad)."""
        lo = np.atleast_2d(np.asarray(r_lo, np.float32))
        hi = np.atleast_2d(np.asarray(r_hi, np.float32))
        res = self.search([Query(op="range_search", r_lo=lo[i], r_hi=hi[i])
                           for i in range(lo.shape[0])])
        return jnp.asarray(np.stack([r.mask for r in res]))

    def topk_ia(self, q_lo, q_hi, k: int):
        """DEPRECATED shim (use `search`): top-k IA for B query boxes ->
        (vals, ids) each (B, k)."""
        lo = np.atleast_2d(np.asarray(q_lo, np.float32))
        hi = np.atleast_2d(np.asarray(q_hi, np.float32))
        res = self.search([Query(op="topk_ia", r_lo=lo[i], r_hi=hi[i], k=k)
                           for i in range(lo.shape[0])])
        return (jnp.asarray(np.stack([r.vals for r in res])),
                jnp.asarray(np.stack([r.ids for r in res])))

    def topk_gbo(self, q_sigs, k: int):
        """DEPRECATED shim (use `search`): top-k GBO for B query
        signatures -> (vals, ids) each (B, k)."""
        sigs = np.asarray(q_sigs)
        if sigs.ndim == 1:
            sigs = sigs[None, :]
        res = self.search([Query(op="topk_gbo", q_sig=sigs[i], k=k)
                           for i in range(sigs.shape[0])])
        return (jnp.asarray(np.stack([r.vals for r in res])),
                jnp.asarray(np.stack([r.ids for r in res])))

    def topk_overlap(self, pointsets, k: int):
        """Convenience shim (use `search`): joinable top-k by grid-cell
        overlap for B raw query point sets -> (vals (B, k), ids (B, k),
        list[SearchStats])."""
        return self._join_shim("topk_overlap", pointsets, k)

    def topk_coverage(self, pointsets, k: int):
        """Convenience shim (use `search`): joinable top-k by grid-cell
        coverage (query points inside cells the winner occupies) ->
        (vals (B, k), ids (B, k), list[SearchStats])."""
        return self._join_shim("topk_coverage", pointsets, k)

    def _join_shim(self, op: str, pointsets, k: int):
        res = self.search([Query(op=op, q=np.asarray(ps, np.float32), k=k)
                           for ps in pointsets])
        return (jnp.asarray(np.stack([r.vals for r in res])),
                jnp.asarray(np.stack([r.ids for r in res])),
                [r.stats for r in res])

    def topk_hausdorff_approx(self, q_batch: DatasetIndex, k: int, eps):
        """DEPRECATED shim (use `search`): ApproHaus for a (B, ...)
        query-index batch -> (vals, ids, eps_eff)."""
        res = self.search([
            Query(op="topk_hausdorff_approx", q_index=row, k=k, eps=eps)
            for row in self._host_tree_rows(q_batch)])
        return (jnp.asarray(np.stack([r.vals for r in res])),
                jnp.asarray(np.stack([r.ids for r in res])),
                jnp.asarray(np.stack([r.extras["eps_eff"] for r in res])))

    def topk_hausdorff(self, q_batch: DatasetIndex, k: int, *,
                       refine_levels: int = 3, chunk: int = 32):
        """DEPRECATED shim (use `search`): ExactHaus — the device-resident
        branch-and-bound pipeline for a (B, ...) query-index batch OR a
        single query index.

        A batch costs ONE device dispatch (shared phase-2 work frontier;
        per-shard loops + batched tau all-reduce under a
        ShardedDispatcher), bucketed through the same shape ladder as
        every other op.  Per-query (vals, ids) are bit-identical to the
        solo pipeline and `topk_hausdorff_host`.

        Returns (vals (B, k), ids (B, k), list[SearchStats]) for a batch,
        or (vals (k,), ids (k,), SearchStats) for a single query; the
        stats are also folded into ``self.stats`` (summed counters, mean
        pruned fraction per dispatch).
        """
        single = q_batch.points.ndim == 2
        if single:
            q_batch = jax.tree.map(lambda x: x[None], q_batch)
        res = self.search([
            Query(op="topk_hausdorff", q_index=row, k=k,
                  refine_levels=refine_levels, chunk=chunk)
            for row in self._host_tree_rows(q_batch)])
        vals = jnp.asarray(np.stack([r.vals for r in res]))
        ids = jnp.asarray(np.stack([r.ids for r in res]))
        stats = [r.stats for r in res]
        if single:
            return vals[0], ids[0], stats[0]
        return vals, ids, stats

    def range_points(self, ds_ids, r_lo, r_hi):
        """DEPRECATED shim (use `search`): RangeP for B (dataset id, box)
        requests -> take masks (B, n_pad)."""
        ds = np.atleast_1d(np.asarray(ds_ids, np.int32))
        lo = np.atleast_2d(np.asarray(r_lo, np.float32))
        hi = np.atleast_2d(np.asarray(r_hi, np.float32))
        res = self.search([
            Query(op="range_points", ds_id=int(ds[i]), r_lo=lo[i],
                  r_hi=hi[i])
            for i in range(ds.shape[0])])
        return jnp.asarray(np.stack([r.mask for r in res]))

    def nnp(self, ds_ids, q_batch: DatasetIndex):
        """DEPRECATED shim (use `search`): tree-pruned NNP for B (query,
        dataset id) requests -> (dists (B, nq), idx (B, nq))."""
        ds = np.atleast_1d(np.asarray(ds_ids, np.int32))
        rows = self._host_tree_rows(q_batch)
        res = self.search([
            Query(op="nnp", ds_id=int(ds[i]), q_index=rows[i])
            for i in range(ds.shape[0])])
        return (jnp.asarray(np.stack([r.vals for r in res])),
                jnp.asarray(np.stack([r.ids for r in res])))
