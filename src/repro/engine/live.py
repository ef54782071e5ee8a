"""LiveRepository: online ingest / delete / replace under serving traffic.

Every engine path so far serves a FROZEN :class:`Repository` built once at
startup.  This module makes the repository a live catalog:

  * ``ingest(points) -> ds_id`` — build the new dataset's bottom tree and
    z-order signature ON DEVICE under the pinned cold-build geometry
    (:mod:`repro.core.repo_mutate`), scatter it into a free slot, and
    rebuild the tiny upper tree — one jitted executable reused for every
    mutation, no full rebuild, no repository re-upload (only the new
    dataset's padded points cross the host->device boundary);
  * ``delete(ds_id)`` — zero the slot (bit-identical to a never-filled
    slot) and return it to the free list; ``replace(ds_id, points)`` is
    an in-place ingest into the same slot;
  * slot capacity is TIERED like the engine's bucket ladder: when ingest
    outruns the free list, the slot count doubles (zeros appended on
    device, shard-aligned) and the dispatcher's layout epoch retires the
    executables whose builds closed over the old slot count.

Versioning is EPOCH-BASED, two levels:

  * the engine's DATA epoch bumps on every published mutation and is part
    of every dataset-op result-cache key, so a query cached at epoch N is
    never served at epoch N+1 (the purged entries are booked in
    ``stats.epoch_invalidations``, and the identical re-query books a
    result-cache MISS — the hits+misses==dispatches invariant is
    untouched);
  * per-slot epochs version point-granularity results: a RangeP/NNP
    entry keyed on dataset j survives mutations of every OTHER dataset;
  * the dispatcher's LAYOUT epoch (executable-cache keys) bumps only on
    tier growth — data mutations swap ``dispatcher.repo`` atomically and
    keep every compiled executable (same shapes, same shardings).

The correctness bar is BIT-IDENTITY: after any mutation sequence, the
resident repository — and every op's results — must equal a cold engine
built by :func:`repro.core.repo_mutate.build_frozen` from the current
slot contents (``frozen_repository()``; asserted op-by-op in
tests/test_live_repository.py and for random interleavings in
tests/test_mutation_properties.py, on local, sharded, and replicated
dispatchers).

Mutations never tear in-flight queries: the slot update is a functional
(non-donating) device computation, so a dispatch that already read the
old repository keeps consistent old buffers, and the publish step is a
single Python attribute swap.  Mutation calls themselves are serialized
by a lock; queries never take it.

Every mutation runs as a TWO-STAGE pipeline:

  * **prepare** (:meth:`LiveRepository.prepare_group`) — validation, slot
    reservation, and the host-side jitted row-stage builds + padded
    payload upload.  Prepare touches nothing a query can observe, so a
    serving front-end may run it CONCURRENTLY with an in-flight query
    segment against the immutable pre-mutation snapshot (late-bound
    dispatchers make this safe).  A prepare that fails mid-group aborts
    cleanly: its reserved slot returns to the free list, the other items
    stay publishable (:meth:`abort_group` abandons a whole group).
  * **publish** (:meth:`LiveRepository.publish_group`) — the cheap
    install: ONE batched owner-write dispatch + ONE upper-tree rebuild
    for the whole group (:func:`repro.core.repo_mutate.update_slots`),
    then the atomic repo swap.  A run of N consecutive mutations with no
    intervening queries COALESCES into one publish and bumps the data
    epoch ONCE — semantics-preserving because every query is still
    answered at the epoch of its stream position (no query can observe
    the intermediate states a serial apply would have materialized).

``ingest``/``delete``/``replace`` are the group-of-1 form of the same
pipeline — one mutation, one publish, one epoch bump, exactly the
pre-pipeline semantics.

The joinable ops (``topk_overlap`` / ``topk_coverage``) need nothing
special here: their result-cache keys carry the data epoch like every
other dataset op, their coarse bounds read the same upper tree the
publish step rebuilds, and their exact refine gathers slot points through
``repo.ds_index`` — so a joinable query after any mutation sequence is
bit-identical to the cold frozen build (asserted at every epoch in
tests/test_join_search.py).
"""
from __future__ import annotations

import heapq
import threading
import time
from dataclasses import dataclass, field
from typing import Sequence

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import repo_mutate
from repro.core.repo_index import Repository
from repro.engine.engine import QueryEngine

__all__ = ["LiveRepository", "PreparedGroup", "PreparedMutation"]


@dataclass
class PreparedMutation:
    """One mutation after its prepare stage: the target slot (reserved
    for ingest), the built batch-of-1 row + signature (zero row for
    delete), or the error its prepare raised (in which case every
    reservation was already returned — never half-reserved)."""
    op: str
    slot: int | None = None
    points: np.ndarray | None = None    # host copy (slot-data ground truth)
    row: object | None = None           # batch-of-1 DatasetIndex
    sig: object | None = None           # (1, W) signature words
    valid: bool = False
    error: Exception | None = None


@dataclass
class PreparedGroup:
    """An ordered run of prepared mutations awaiting one coalesced
    publish (or :meth:`LiveRepository.abort_group`)."""
    items: list = field(default_factory=list)
    published: bool = False
    aborted: bool = False


class LiveRepository:
    """A mutable, versioned repository serving through a QueryEngine.

    ``mesh=None`` serves locally; a 1-D mesh selects sharded dispatch and
    a (replica x data) mesh replica-parallel dispatch — mutation works
    identically on all three (the slot updater's outputs are pinned to
    the dispatcher's placement, so only TOUCHED state moves between
    devices).

    ``point_capacity`` reserves bottom-tree headroom for datasets larger
    than any initial one (the bottom depth is pinned; an oversize ingest
    raises).  ``slot_headroom`` pre-doubles slot capacity that many
    times.  ``clock`` injects the timebase for publish-latency
    accounting (tests drive it with virtual time).  Remaining engine
    knobs (buckets, result_cache_size, ...) pass through to
    :class:`~repro.engine.engine.QueryEngine`.
    """

    def __init__(
        self,
        datasets: Sequence[np.ndarray],
        *,
        mesh=None,
        leaf_capacity: int = 16,
        repo_leaf_capacity: int | None = None,
        theta: int = 5,
        remove_outliers: bool = True,
        point_capacity: int | None = None,
        slot_headroom: int = 0,
        clock=time.perf_counter,
        **engine_kwargs,
    ):
        self._clock = clock
        repo, geom = repo_mutate.init_live(
            datasets,
            leaf_capacity=leaf_capacity,
            repo_leaf_capacity=repo_leaf_capacity,
            theta=theta,
            remove_outliers=remove_outliers,
            point_capacity=point_capacity,
            slot_headroom=slot_headroom,
        )
        self.geometry = geom
        self.engine = QueryEngine(repo, leaf_capacity=leaf_capacity,
                                  mesh=mesh, **engine_kwargs)
        B = len(datasets)
        #: DATA epoch of the published repository (monotone, starts at 0)
        self.epoch = 0
        #: per-slot epoch: the data epoch at which the slot last changed
        self.slot_epochs = np.zeros(geom.n_slots, np.int64)
        #: host->device bytes moved by mutations (ingest/replace payloads
        #: only — delete and tier growth upload NOTHING; the acceptance
        #: check that single-dataset mutations never re-upload the
        #: repository reads this)
        self.bytes_uploaded = 0
        self.mutations = 0
        self._live: set = set(range(B))
        self._free: list = list(range(B, geom.n_slots))
        heapq.heapify(self._free)
        # host copies of current slot contents — the ground truth the
        # frozen oracle rebuilds from (and the source for `replace`-style
        # serving tools); one small np array per live dataset
        self._slot_data = {j: np.asarray(ds, np.float32)
                           for j, ds in enumerate(datasets)}
        self._lock = threading.Lock()
        # direct ingest/delete/replace serialize through this OUTER lock
        # (each is a group-of-1 prepare+publish, preserving the exact
        # pre-pipeline semantics); the inner ``_lock`` guards free-list /
        # live-set / publish internals so a serving front-end can overlap
        # prepare_group with an in-flight query segment
        self._api_lock = threading.Lock()
        zr, zs = repo_mutate.zero_slot_row(geom)
        # batch-of-1 zero row: deletes coalesce into the same batched
        # scatter as ingests/replaces
        self._zero_row1 = (jax.tree.map(lambda x: x[None], zr), zs[None])
        #: tiers reserved VIRTUALLY by prepare (free list extended past
        #: the current slot count) and not yet materialized by a publish
        self._grows_pending = 0
        # batched slot-write executables keyed by padded group size;
        # cleared on tier growth (they close over the slot count)
        self._updaters: dict = {}
        self.engine.set_repo_epoch(0, self.slot_epochs)

    # -- views -------------------------------------------------------------

    @property
    def repo(self) -> Repository:
        """The currently published (placed) repository."""
        return self.engine.dispatch.repo

    @property
    def stats(self):
        return self.engine.stats

    @property
    def live_ids(self) -> set:
        return set(self._live)

    @property
    def n_slots(self) -> int:
        return self.geometry.n_slots

    def search(self, queries):
        """Serve a declarative batch against the current epoch (see
        :meth:`QueryEngine.search`)."""
        return self.engine.search(queries)

    def slot_datasets(self) -> list:
        """Current slot contents, ``None`` for holes — exactly the input
        :func:`~repro.core.repo_mutate.build_frozen` expects."""
        return [self._slot_data.get(j) for j in range(self.geometry.n_slots)]

    def frozen_repository(self) -> Repository:
        """The cold-built oracle equivalent to the current live state —
        bit-identical to :attr:`repo` (modulo shard padding/placement) by
        construction; tests assert it."""
        return repo_mutate.build_frozen(self.slot_datasets(), self.geometry)

    # -- mutations ---------------------------------------------------------

    #: rows per device dispatch inside one publish — larger groups chunk
    #: (bounds the executable-variant count; padded buckets are powers
    #: of two, so the updater cache holds at most log2(MAX_GROUP)+1
    #: entries per tier)
    MAX_GROUP = 16

    def ingest(self, points) -> int:
        """Add a dataset; returns its slot id (stable until deleted).
        Grows the slot tier first if the free list is empty."""
        return self._apply_one("ingest", None, points)

    def delete(self, ds_id: int) -> None:
        """Remove a dataset: its slot is zeroed (bit-identical to a
        never-filled slot) and returned to the free list."""
        self._apply_one("delete", int(ds_id), None)

    def replace(self, ds_id: int, points) -> None:
        """Swap a live dataset's contents in place — a new VERSION under
        the same id: the slot keeps its id, its per-slot epoch bumps, and
        every cached result that touched it is retired."""
        self._apply_one("replace", int(ds_id), points)

    def _apply_one(self, op, ds_id, points):
        with self._api_lock:
            group = self.prepare_group([(op, ds_id, points)])
            item = group.items[0]
            if item.error is not None:
                group.published = True      # nothing reserved to return
                raise item.error
            return self.publish_group(group)[0]

    # -- prepare stage -----------------------------------------------------

    def prepare_group(self, specs) -> PreparedGroup:
        """Prepare a run of mutations ``[(op, ds_id, points), ...]`` —
        validation, slot reservation, and the jitted row builds + padded
        payload uploads — WITHOUT publishing anything.  Queries served
        while this runs still see the pre-mutation snapshot unchanged.

        Items validate against a group-local view of the live set
        (pending ingests visible, pending deletes excluded), so the
        outcome of each item matches a sequential apply of the group.  A
        failing item records its error (its reservation returned
        immediately) and does NOT poison the rest of the group; the
        caller sees the error in :meth:`publish_group`'s outcomes."""
        items = []
        with self._lock:
            view_live = set(self._live)
        for op, ds_id, points in specs:
            try:
                if op == "ingest":
                    items.append(self._prepare_ingest(points, view_live))
                elif op == "replace":
                    items.append(
                        self._prepare_replace(int(ds_id), points, view_live))
                elif op == "delete":
                    items.append(self._prepare_delete(int(ds_id), view_live))
                else:
                    raise ValueError(f"unknown mutation op {op!r}")
            except Exception as e:  # noqa: BLE001 — recorded per item
                items.append(PreparedMutation(op, error=e))
        return PreparedGroup(items)

    def _prepare_ingest(self, points, view_live):
        # reserve FIRST so concurrent prepares in the same group never
        # collide, then validate/build; ANY failure past the reservation
        # runs the abort path (slot back on the free list — never
        # half-reserved, tested by the abort-path suite)
        with self._lock:
            slot = self._reserve_slot()
        try:
            pts = self._check_points(points)
            row, sig = self._build_payload(pts)
        except Exception:
            with self._lock:
                heapq.heappush(self._free, slot)
            raise
        view_live.add(slot)
        return PreparedMutation("ingest", slot=slot, points=pts,
                                row=row, sig=sig, valid=True)

    def _prepare_replace(self, ds_id, points, view_live):
        if ds_id not in view_live:
            raise KeyError(f"dataset id {ds_id} is not live")
        pts = self._check_points(points)
        row, sig = self._build_payload(pts)
        return PreparedMutation("replace", slot=ds_id, points=pts,
                                row=row, sig=sig, valid=True)

    def _prepare_delete(self, ds_id, view_live):
        if ds_id not in view_live:
            raise KeyError(f"dataset id {ds_id} is not live")
        view_live.discard(ds_id)
        row, sig = self._zero_row1
        return PreparedMutation("delete", slot=ds_id,
                                row=row, sig=sig, valid=False)

    def _build_payload(self, pts):
        geom = self.geometry
        # the canonical batch-of-1 row pipeline — the same shared
        # executables the frozen oracle uses (bit-identity by
        # construction, see core/repo_mutate); the ONLY host->device
        # traffic a mutation pays is this one padded payload
        rows, sigs = repo_mutate.build_row(pts, geom)
        with self._lock:
            self.bytes_uploaded += geom.point_capacity * (4 * geom.dim + 1)
        return rows, sigs

    def _reserve_slot(self) -> int:
        """Pop a free slot (caller holds ``_lock``).  An empty free list
        extends VIRTUALLY into the next tier — ids past the current slot
        count — deferring the actual growth (its device work, layout
        epoch, and data epoch) to the publish stage."""
        if not self._free:
            base = self.geometry.n_slots << self._grows_pending
            self._grows_pending += 1
            for s in range(base, 2 * base):
                heapq.heappush(self._free, s)
        return heapq.heappop(self._free)

    def abort_group(self, group: PreparedGroup) -> None:
        """Abandon a prepared, unpublished group: every ingest
        reservation returns to the free list (subsequent ingests reuse
        the slots) and the group is marked consumed."""
        if group.published or group.aborted:
            raise RuntimeError("group already consumed")
        group.aborted = True
        with self._lock:
            for p in group.items:
                if p.error is None and p.op == "ingest":
                    heapq.heappush(self._free, p.slot)
                    p.error = RuntimeError("prepare aborted")

    # -- publish stage -----------------------------------------------------

    def publish_group(self, group: PreparedGroup):
        """Install a prepared group as ONE coalesced publish: one batched
        owner-write dispatch + one upper-tree rebuild for the whole run
        (chunked at :attr:`MAX_GROUP`), the data epoch bumped once per
        chunk.  Returns per-item outcomes in stream order: the slot id
        for ingest, the dataset id for replace, ``None`` for delete, or
        the item's prepare-stage exception."""
        if group.published or group.aborted:
            raise RuntimeError("group already consumed")
        group.published = True
        outcomes: list = [p.error for p in group.items]
        applied = [(i, p) for i, p in enumerate(group.items)
                   if p.error is None]
        with self._lock:
            for lo in range(0, len(applied), self.MAX_GROUP):
                self._publish_chunk(
                    [p for _, p in applied[lo:lo + self.MAX_GROUP]])
        for i, p in applied:
            outcomes[i] = None if p.op == "delete" else p.slot
        return outcomes

    def _publish_chunk(self, chunk) -> None:
        """One coalesced install (caller holds ``_lock``): materialize
        any tier growth the prepare stage reserved virtually, dedup the
        chunk's writes by slot (last write wins — stream order), pad to
        the power-of-two bucket by REPEATING the last write (duplicate
        scatter indices with identical payloads are deterministic), run
        the one batched updater, then apply host bookkeeping in stream
        order and publish the successor epoch."""
        t0 = self._clock()
        top = max(p.slot for p in chunk)
        while top >= self.geometry.n_slots:
            self._grow(push_free=False)
        last: dict = {}
        for p in chunk:                      # dict preserves insertion,
            last[p.slot] = p                 # value is the LAST write
        writes = list(last.values())
        bucket = 1
        while bucket < len(writes):
            bucket *= 2
        writes = writes + [writes[-1]] * (bucket - len(writes))
        slots = jnp.asarray([p.slot for p in writes], jnp.int32)
        rows = jax.tree.map(lambda *xs: jnp.concatenate(xs, axis=0),
                            *[p.row for p in writes])
        sigs = jnp.concatenate([p.sig for p in writes], axis=0)
        valids = jnp.asarray([p.valid for p in writes], bool)
        new_repo = self._updater_for(bucket)(self.repo, slots, rows,
                                             sigs, valids)
        for p in chunk:
            if p.op == "delete":
                self._live.discard(p.slot)
                self._slot_data.pop(p.slot, None)
                heapq.heappush(self._free, p.slot)
            else:
                self._live.add(p.slot)
                self._slot_data[p.slot] = p.points
        self.mutations += len(chunk)
        self._publish(new_repo, touched=tuple(last))
        self.engine.stats.record_publish(self._clock() - t0,
                                         coalesced=len(chunk) - 1)

    # -- internals ---------------------------------------------------------

    def _check_points(self, points) -> np.ndarray:
        points = np.asarray(points, np.float32)
        geom = self.geometry
        if points.ndim != 2 or points.shape[1] != geom.dim:
            raise ValueError(f"expected (n, {geom.dim}) points, got "
                             f"{points.shape}")
        if points.shape[0] == 0:
            raise ValueError("cannot ingest an empty dataset")
        if points.shape[0] > geom.point_capacity:
            raise ValueError(
                f"dataset with {points.shape[0]} points exceeds the pinned "
                f"point capacity {geom.point_capacity}; rebuild the live "
                f"repository with point_capacity >= {points.shape[0]}")
        return points

    def _check_live(self, ds_id: int) -> None:
        if ds_id not in self._live:
            raise KeyError(f"dataset id {ds_id} is not live")

    def _rep_sharding(self):
        from jax.sharding import NamedSharding, PartitionSpec
        return NamedSharding(self.engine.dispatch.mesh, PartitionSpec())

    def _finish(self, repo, ds_index, ds_sigs, ds_valid, roots, geom):
        """Second mutation stage, shared by every dispatcher: the upper
        tree from single-device root summaries through the ONE cached
        stage executable the frozen oracle also calls — bit-identity with
        the cold build by construction (the same compiled program on
        bitwise-equal inputs), where re-deriving the tree inside the
        fused/shard_map stage can drift a node radius by one ulp at some
        slot counts (reduction codegen is shape- and context-dependent).
        Roots are O(n_slots) summaries — the hop to the default device
        and the replicated placement of the finished tree move no slot
        bodies."""
        dev0 = jax.devices()[0]
        tree = repo_mutate._stage_upper(geom.upper_depth)(
            *(jax.device_put(r, dev0) for r in roots))
        if getattr(self.engine.dispatch, "specs", None) is not None:
            tree = jax.device_put(tree, self._rep_sharding())
        return Repository(ds_index=ds_index, ds_sigs=ds_sigs,
                          ds_valid=ds_valid, repo=tree,
                          space_lo=repo.space_lo, space_hi=repo.space_hi)

    def _updater_for(self, bucket: int):
        fn = self._updaters.get(bucket)
        if fn is None:
            fn = self._make_updater(bucket)
            self._updaters[bucket] = fn
        return fn

    def _make_updater(self, bucket: int):
        """The batched slot-write executable for the CURRENT tier and one
        padded group size: ``bucket`` (slot, row, sig, valid) writes land
        in ONE dispatch — dynamic slot + validity operands, so any mix of
        ingest/delete/replace on any slots reuses it.  Inputs are NOT
        donated (in-flight queries keep the old buffers).  It returns the
        updated slot arrays plus the per-slot ROOT summaries; `_finish`
        turns those into the upper tree.

        Local dispatch is a plain jitted scatter (slots are pre-deduped,
        so the batched scatter is bitwise equal to ``bucket`` sequential
        single-row scatters — pure data movement).  On a mesh the writes
        run inside an EXPLICIT shard_map as a STATIC unroll of owner
        writes — the owner shard folds each (replicated) row into its
        local slice, later writes winning, and the roots are all-gathered
        once (tiny: one summary row per slot, not the slot bodies), so
        only the touched shards' slices change and nothing moves through
        the host.  shard_map rather than the SPMD partitioner is
        load-bearing: jit-of-scatter on a (replica x data) mesh lets the
        partitioner psum the replicated row operand over the replica
        axis, silently DOUBLING every slot (the same hazard
        `ShardedDispatcher._smap` documents for concat)."""
        geom = self.geometry
        disp = self.engine.dispatch
        specs = getattr(disp, "specs", None)
        B_pad = geom.n_slots

        def roots_of(ds_index, ds_sigs, ds_valid):
            return (ds_index.centers[:B_pad, 0, :],
                    ds_index.radii[:B_pad, 0],
                    ds_index.box_lo[:B_pad, 0, :],
                    ds_index.box_hi[:B_pad, 0, :],
                    ds_sigs[:B_pad], ds_valid[:B_pad])

        if specs is None:
            def scatter(repo, slots, rows, sigs, valids):
                ds_index, ds_sigs, ds_valid = repo_mutate.scatter_slots(
                    repo, slots, rows, sigs, valids)
                return (ds_index, ds_sigs, ds_valid,
                        roots_of(ds_index, ds_sigs, ds_valid))
            stage = jax.jit(scatter)
        else:
            from jax.sharding import PartitionSpec as P
            axis = disp.axis

            def local(repo_s, slots, rows, sigs, valids):
                shard = repo_s.ds_valid.shape[0]
                me = jax.lax.axis_index(axis)
                ds_index = repo_s.ds_index
                ds_sigs = repo_s.ds_sigs
                ds_valid = repo_s.ds_valid
                for i in range(bucket):
                    lid = slots[i] - me * shard
                    owns = (lid >= 0) & (lid < shard)
                    lidc = jnp.clip(lid, 0, shard - 1)

                    def wr(a, r):
                        return a.at[lidc].set(jnp.where(owns, r, a[lidc]))

                    ds_index = jax.tree.map(
                        wr, ds_index, jax.tree.map(lambda x: x[i], rows))
                    ds_sigs = wr(ds_sigs, sigs[i])
                    ds_valid = wr(ds_valid, valids[i])

                def gat(x):
                    # physical slot order == shard-major order, so the
                    # tiled gather reassembles global slot order; [:B_pad]
                    # trims the shard-alignment padding
                    return jax.lax.all_gather(x, axis, tiled=True)[:B_pad]

                roots = (gat(ds_index.centers[:, 0, :]),
                         gat(ds_index.radii[:, 0]),
                         gat(ds_index.box_lo[:, 0, :]),
                         gat(ds_index.box_hi[:, 0, :]),
                         gat(ds_sigs), gat(ds_valid))
                return ds_index, ds_sigs, ds_valid, roots

            stage = jax.jit(jax.shard_map(
                local, mesh=disp.mesh,
                in_specs=(specs, P(), P(), P(), P()),
                out_specs=(specs.ds_index, specs.ds_sigs, specs.ds_valid,
                           (P(), P(), P(), P(), P(), P())),
                check_vma=False))

        def fn(repo, slots, rows, sigs, valids):
            ds_index, ds_sigs, ds_valid, roots = stage(repo, slots, rows,
                                                       sigs, valids)
            return self._finish(repo, ds_index, ds_sigs, ds_valid, roots,
                                geom)

        return fn

    def _grow(self, push_free: bool = True) -> None:
        """Double the slot tier: zeros appended ON DEVICE (shard-aligned,
        no host upload), dispatcher layout constants refreshed, layout
        epoch bumped (executables closing over the old slot count are
        retired), and the grown state published as its own data epoch —
        dataset-op result rows change width with the slot axis, so they
        must retire too (per-slot point-op entries survive: no slot's
        contents changed).  ``push_free=False`` materializes a tier the
        prepare stage already reserved virtually (its ids are on the
        free list or held by prepared ingests)."""
        old_n = self.geometry.n_slots
        geom = self.geometry.grown()
        disp = self.engine.dispatch
        n_shards = int(getattr(disp, "n_shards", 1))
        n_phys = -(-geom.n_slots // n_shards) * n_shards
        if getattr(disp, "specs", None) is None:
            ds_index, ds_sigs, ds_valid = jax.jit(
                lambda repo: repo_mutate.pad_slots(repo, n_phys))(self.repo)
            B_pad = geom.n_slots
            roots = (ds_index.centers[:B_pad, 0, :],
                     ds_index.radii[:B_pad, 0],
                     ds_index.box_lo[:B_pad, 0, :],
                     ds_index.box_hi[:B_pad, 0, :],
                     ds_sigs[:B_pad], ds_valid[:B_pad])
            grown = self._finish(self.repo, ds_index, ds_sigs, ds_valid,
                                 roots, geom)
        else:
            grown = self._grow_sharded(geom, n_phys)
        self.geometry = geom
        self.slot_epochs = np.concatenate(
            [self.slot_epochs, np.zeros(geom.n_slots - old_n, np.int64)])
        if push_free:
            for s in range(old_n, geom.n_slots):
                heapq.heappush(self._free, s)
        else:
            self._grows_pending = max(0, self._grows_pending - 1)
        disp.n_slots = geom.n_slots
        if hasattr(disp, "shard_slots"):
            disp.n_slots_sharded = n_phys
            disp.shard_slots = n_phys // n_shards
        disp.repo_epoch = getattr(disp, "repo_epoch", 0) + 1
        self._updaters = {}
        self._publish(grown, touched=())

    def _grow_sharded(self, geom, n_phys: int) -> Repository:
        """Tier growth on a mesh, as an explicit shard_map (the
        jit-of-concat partitioner path psum-doubles replicated state on a
        (replica x data) mesh — see `_make_updater`).  Growth must keep
        the GLOBAL slot order (logical slot j at physical row j), so
        per-shard local zero-padding is wrong — each shard all-gathers
        the old slot arrays, appends the zero tier, and slices out its
        own re-balanced chunk.  Device-to-device only; nothing crosses
        the host boundary."""
        disp = self.engine.dispatch
        specs = disp.specs
        axis = disp.axis
        shard_new = n_phys // int(disp.n_shards)
        B_pad = geom.n_slots

        from jax.sharding import PartitionSpec as P

        def local(repo_s):
            me = jax.lax.axis_index(axis)

            def full(x):
                f = jax.lax.all_gather(x, axis, tiled=True)
                z = jnp.zeros((n_phys - f.shape[0],) + f.shape[1:], f.dtype)
                return jnp.concatenate([f, z], axis=0)

            def loc(x):
                return jax.lax.dynamic_slice_in_dim(
                    x, me * shard_new, shard_new, 0)

            fi = jax.tree.map(full, repo_s.ds_index)
            fs = full(repo_s.ds_sigs)
            fv = full(repo_s.ds_valid)
            roots = (fi.centers[:B_pad, 0, :], fi.radii[:B_pad, 0],
                     fi.box_lo[:B_pad, 0, :], fi.box_hi[:B_pad, 0, :],
                     fs[:B_pad], fv[:B_pad])
            return jax.tree.map(loc, fi), loc(fs), loc(fv), roots

        sm = jax.jit(jax.shard_map(
            local, mesh=disp.mesh, in_specs=(specs,),
            out_specs=(specs.ds_index, specs.ds_sigs, specs.ds_valid,
                       (P(), P(), P(), P(), P(), P())),
            check_vma=False))

        ds_index, ds_sigs, ds_valid, roots = sm(self.repo)
        return self._finish(self.repo, ds_index, ds_sigs, ds_valid, roots,
                            geom)

    def _publish(self, new_repo: Repository, touched) -> None:
        """Atomically install the successor repository and its epoch.

        The dispatcher attribute swap is the linearization point: every
        later dispatch reads the new repository (late-bound executables),
        every in-flight one keeps the old buffers.  Then the engine's
        epoch install purges retired result rows (booked as
        ``epoch_invalidations``) so no future lookup can hit them."""
        disp = self.engine.dispatch
        disp.repo = new_repo
        self.engine.repo = new_repo
        self.engine._n_valid = len(self._live)
        self.epoch += 1
        for s in touched:
            self.slot_epochs[s] = self.epoch
        # `touched` makes the sweep precise: point-op entries for
        # untouched slots survive the publish (one sweep per coalesced
        # group, not per mutation)
        self.engine.set_repo_epoch(self.epoch, self.slot_epochs,
                                   touched=touched)
