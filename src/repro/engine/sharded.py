"""ShardedQueryEngine: the batched engine over a repository sharded on the
``data`` mesh axis.

The paper's "pruning in batch" bound pass is embarrassingly parallel across
dataset slots, so the scale-out unit is the SLOT: `shard_repository` pads
the resident :class:`Repository`'s dataset-axis arrays (`ds_index`,
`ds_sigs`, `ds_valid`) to a multiple of the shard count and places them
with a `NamedSharding` over the chosen mesh axis — each device owns a
contiguous slice of dataset slots; the upper repository tree and the space
bounds are tiny and stay replicated.  Every op then runs the same batched
score pass per shard inside `shard_map` and merges on device:

  * ``topk_ia`` / ``topk_gbo`` / ``topk_hausdorff_approx`` — local top-k
    per shard, then the O(k) all-gather merge from
    :mod:`repro.engine.merge` (network cost independent of repository
    size);
  * ``range_search`` — per-shard mask over the local slots; the global
    mask is the disjoint union (concatenation) of the shard masks, so no
    collective is needed at all;
  * ``range_points`` / ``nnp`` — every shard evaluates the batch against
    its local gather of the requested dataset rows and masks rows it does
    not own; the owner-exclusive contributions are combined with a `psum`
    (adding zeros is exact, so this is the running-min merge with the
    minimum taken over exactly one finite contribution).

Bit-identity with the unsharded :class:`~repro.engine.engine.QueryEngine`
(asserted per-op in tests/test_engine_sharded.py) follows from three facts:

  1. every per-slot score is computed by the same arithmetic on the same
     rows (slicing the slot axis changes no values);
  2. `jax.lax.top_k` breaks ties toward the smallest index, and per-shard
     lists concatenated in shard order enumerate equal values in ascending
     global id — the same order the global top_k uses (see merge.py);
  3. for ``range_search``, the upper-tree traversal can never reject a
     dataset whose own MBR overlaps the query box (every ancestor box
     contains each descendant's MBR and box overlap is monotone under
     containment, and ancestors of a valid slot have counts > 0), so the
     traversal mask equals the per-slot root test `hit & valid` that the
     shards evaluate.

ApproHaus needs two scalars that the seed op derives from the WHOLE
repository — the Lemma 1 dataset-side stopping level and the effective
epsilon's dataset radius term — so the shard pass reduces them with
`pmin`/`pmax` collectives before scoring (boolean AND of the per-shard
level checks, max of the per-shard frontier radii; both are exact).

ExactHaus (`topk_hausdorff`) is genuinely sharded end to end — no
replicated repository copy, so resident repository bytes per device are
~1/N:

  * phases 0/1 (Eq. 4 bound passes) run per shard on the local slot slice
    for the WHOLE (B, ...) query batch in one vmapped pass; each query's
    batch-prune threshold tau (kth-smallest upper bound) is the one
    repository-global quantity and is reduced with the O(k)
    `global_kth_smallest` gather (`core/distributed.py`, batched over the
    query axis), the same collective pattern as `sharded_topk_bounds`;
  * phase 2 runs ONE `lax.while_loop` per shard for the whole batch, over
    each query's OWN ascending-lower-bound candidate order on that
    shard's slots (a shared (query, candidate-chunk) work frontier);
    after every chunk of exact `directed_hausdorff_grid` evaluations the
    per-query taus are all-reduced again (k smallest finite exacts per
    shard -> gather -> kth), so every shard prunes with each query's
    global threshold while it scans.  The loop's per-query continue flags
    (any shard still has work for that query) are psum-reduced into the
    carry so the while cond stays collective-free and replicated;
  * the final top-k is the same O(k) all-gather merge as IA/GBO, batched
    over queries.

Tie-order contract (documented in `search._phase2_exact_loop`, asserted
against the host oracle in tests): per-shard chunking changes WHICH
extra candidates beyond the kth Hausdorff value get exact-evaluated (the
`evaluated` stat), but never the returned set — tau always upper-bounds
the true kth value, so a chunk skipped under either schedule lies
strictly outside the top-k, ties included; values and ids are
bit-identical to `topk_hausdorff_host`.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.core import geometry, join_search, point_search, search
from repro.core.repo_index import Repository
from repro.engine import batched_ops, merge
from repro.engine.engine import (DEFAULT_BUCKETS, DEFAULT_RESULT_CACHE,
                                 QueryEngine)
from repro.kernels import ops as kernel_ops

Array = jax.Array
BIG = search.BIG


def data_mesh(n_devices: int | None = None, axis: str = "data") -> Mesh:
    """A 1-D mesh over the first `n_devices` local devices (all by default)
    with a single repository-sharding axis.  An explicit request larger
    than the platform provides is an error, never a silent smaller mesh."""
    devs = jax.devices()
    if n_devices is not None:
        if n_devices > len(devs):
            raise ValueError(
                f"data_mesh: {n_devices} devices requested but only "
                f"{len(devs)} available (on CPU, force more with "
                f"REPRO_HOST_DEVICES / --xla_force_host_platform_"
                f"device_count before jax initializes)")
        devs = devs[:n_devices]
    return Mesh(np.asarray(devs), (axis,))


def shard_repository(
    repo: Repository, mesh: Mesh, axis: str = "data"
) -> tuple[Repository, Repository, int]:
    """Place a Repository's dataset-slot axis across a mesh axis.

    Pads the slot axis to a multiple of the shard count with empty slots
    (zeros: counts == 0 and valid == False, so they are masked exactly like
    the builder's own padding) and device_puts each dataset-axis array with
    `NamedSharding(mesh, P(axis))`; the upper tree and space bounds are
    replicated.  Returns (sharded repository, matching PartitionSpec pytree
    for shard_map in_specs, padded slot count).
    """
    n_shards = int(mesh.shape[axis])
    n_slots = repo.n_slots
    n_padded = ((n_slots + n_shards - 1) // n_shards) * n_shards

    def pad_slots(x):
        if n_padded == n_slots:
            return x
        pad = jnp.zeros((n_padded - n_slots,) + x.shape[1:], x.dtype)
        return jnp.concatenate([x, pad], axis=0)

    def place(x, spec):
        sharding = NamedSharding(mesh, spec)
        if jax.process_count() > 1:
            # multi-host groundwork: assemble the global array from
            # process-local buffers so no single host ever has to device_put
            # the whole repository (each process here still holds the full
            # builder output, the documented fully-replicated input case of
            # make_array_from_process_local_data; a true multi-host loader
            # would hand each process only its slot slice)
            return jax.make_array_from_process_local_data(
                sharding, np.asarray(x), x.shape)
        return jax.device_put(x, sharding)

    sharded = Repository(
        ds_index=jax.tree.map(lambda x: place(pad_slots(x), P(axis)),
                              repo.ds_index),
        ds_sigs=place(pad_slots(repo.ds_sigs), P(axis)),
        ds_valid=place(pad_slots(repo.ds_valid), P(axis)),
        repo=jax.tree.map(lambda x: place(x, P()), repo.repo),
        space_lo=place(repo.space_lo, P()),
        space_hi=place(repo.space_hi, P()),
    )
    specs = Repository(
        ds_index=jax.tree.map(lambda _: P(axis), repo.ds_index),
        ds_sigs=P(axis),
        ds_valid=P(axis),
        repo=jax.tree.map(lambda _: P(), repo.repo),
        space_lo=P(),
        space_hi=P(),
    )
    return sharded, specs, n_padded


def repo_device_bytes(repo: Repository) -> dict:
    """Resident repository bytes per device, from the placed buffers.

    Sums `addressable_shards[*].data.nbytes` over every array leaf, so
    sharded leaves contribute 1/N per device while replicated leaves (the
    upper tree, space bounds) count fully on each — the number a device's
    memory actually pays.  Works on sharded and single-device repositories
    alike (the regression tests and `bench_engine --sharded` use it to
    prove ExactHaus no longer needs a replicated copy).
    """
    out: dict = {}
    for leaf in jax.tree.leaves(repo):
        for sh in leaf.addressable_shards:
            out[sh.device] = out.get(sh.device, 0) + sh.data.nbytes
    return out


class ShardedDispatcher:
    """Builds the sharded device callables the QueryEngine caches.

    Same call contracts as :class:`~repro.engine.engine.LocalDispatcher`:
    each ``build_*`` returns a callable over the query-side operands with
    the (sharded) repository bound as the leading jit argument.

    The QUERY-ROW placement is parameterized by ``row_axis``: every
    query-side operand and per-row output uses the spec ``P(row_axis,
    ...)``.  The base class keeps ``row_axis = None`` (rows replicated on
    every shard — the 1-D data mesh), while
    :class:`~repro.engine.replicated.ReplicatedDispatcher` sets it to the
    ``replica`` axis of a 2-D mesh so each replica group serves its own
    row slice.  When rows are split, :meth:`_smap` pads the leading row
    axis to a multiple of the replica count by replicating row 0 (the same
    trick as the engine's bucket padding — per-row computations are
    independent, so pad rows change nothing and are sliced off) and cuts
    the row-spec'd outputs back.
    """

    name = "sharded"
    #: mesh axis the query-row (leading batch) axis is partitioned over in
    #: every spec; None keeps rows replicated (the base 1-D behavior)
    row_axis: str | None = None
    #: layout epoch — bumped by a live repository when the slot-array
    #: shapes change (tier growth); part of every executable-cache key.
    #: The sharded builds additionally close over `n_slots`/`shard_slots`
    #: constants, so retiring them on growth is REQUIRED, not just tidy.
    repo_epoch = 0

    def __init__(self, repo: Repository, mesh: Mesh, axis: str = "data"):
        if not isinstance(axis, str):      # accept a PartitionSpec-ish spec
            axis = tuple(axis)[0]
        self.mesh = mesh
        self.axis = axis
        self.n_shards = int(mesh.shape[axis])
        self.n_slots = repo.n_slots
        # the sharded placement is the ONLY repository copy this dispatcher
        # retains — every op (ExactHaus included) runs on the shard slices,
        # so per-device resident bytes are ~total/N (asserted in tests)
        self.repo, self.specs, self.n_slots_sharded = shard_repository(
            repo, mesh, axis)
        self.shard_slots = self.n_slots_sharded // self.n_shards

    # -- helpers -----------------------------------------------------------

    @property
    def _rows(self):
        """Spec of a query-side operand / per-row output: partitioned on
        the row axis when one is configured (P(None) == replicated)."""
        return P(self.row_axis)

    def _smap(self, fn, in_specs, out_specs):
        sm = jax.shard_map(fn, mesh=self.mesh, in_specs=in_specs,
                           out_specs=out_specs, check_vma=False)
        if self.row_axis is None:
            return sm
        n_rep = int(self.mesh.shape[self.row_axis])

        def row_split(spec):
            return len(spec) > 0 and spec[0] == self.row_axis

        def pad(x):
            # pad rows to a multiple of n_rep by repeating row 0 (rows are
            # independent, so pad rows never perturb real ones).  A single
            # gather, NOT concatenate: under jit, XLA's partitioner
            # mis-reshards a concat whose per-replica block is one operand
            # (each shard comes out psum-reduced over the other mesh axis).
            m = -x.shape[0] % n_rep
            if not m:
                return x
            idx = np.concatenate([np.arange(x.shape[0]), np.zeros(m, np.int64)])
            return jnp.take(x, jnp.asarray(idx), axis=0)

        # NOTE: PartitionSpec subclasses tuple — a bare P(...) out_specs is
        # ONE output, not a tuple of per-output specs
        single = isinstance(out_specs, P) or not isinstance(out_specs, tuple)
        o_specs = (out_specs,) if single else out_specs

        def wrapped(repo_s, *args):
            rows = None
            ins = []
            for a, spec in zip(args, in_specs[1:]):
                if row_split(spec):
                    if rows is None:
                        rows = jax.tree.leaves(a)[0].shape[0]
                    a = jax.tree.map(pad, a)
                ins.append(a)
            out = sm(repo_s, *ins)
            if rows is None:
                return out
            outs = (out,) if single else out
            cut = tuple(
                jax.tree.map(lambda x: x[:rows], o) if row_split(spec)
                else o
                for o, spec in zip(outs, o_specs))
            return cut[0] if single else cut

        return wrapped

    def _bind(self, impl):
        """jit with the sharded repository as the LATE-BOUND leading
        operand (an operand, not a closed-over constant, so XLA never
        inlines it; read from ``self.repo`` at call time, so a live
        mutation's atomic placed-repository swap takes effect on the next
        dispatch without recompiling — same shapes + same shardings hit
        the same executable)."""
        jitted = jax.jit(impl)

        def call(*args, **kw):
            return jitted(self.repo, *args, **kw)

        return call

    def _owner_select(self, repo_loc, ds_ids):
        """Per-request (owner mask, local gather of the requested dataset
        rows).  Non-owner shards gather a clamped row and compute masked-out
        garbage; only the owner's result survives the psum merge."""
        shard = repo_loc.ds_valid.shape[0]
        me = jax.lax.axis_index(self.axis)
        mine = (ds_ids // shard) == me
        lid = jnp.clip(ds_ids - me * shard, 0, shard - 1)
        d_sel = jax.tree.map(lambda x: x[lid], repo_loc.ds_index)
        return mine, d_sel

    # -- dataset granularity ----------------------------------------------

    def build_range_search(self):
        axis, n = self.axis, self.n_slots

        def local(repo_loc, r_lo, r_hi):
            # per-slot root test == the upper-tree traversal mask (ancestor
            # boxes contain descendant MBRs; see module docstring)
            _, _, lo, hi = repo_loc.roots()
            hit = geometry.box_overlaps(
                lo[None, :, :], hi[None, :, :],
                r_lo[:, None, :], r_hi[:, None, :])
            return hit & repo_loc.ds_valid[None, :]

        sm = self._smap(local, in_specs=(self.specs, self._rows, self._rows),
                        out_specs=P(self.row_axis, axis))

        def impl(repo_s, r_lo, r_hi):
            masks = sm(repo_s, r_lo, r_hi)
            return masks[:, :n], None

        return self._bind(impl)

    def build_topk_ia(self, k: int):
        axis = self.axis

        def local(repo_loc, q_lo, q_hi):
            _, _, lo, hi = repo_loc.roots()
            ia = geometry.intersect_area(
                lo[None, :, :], hi[None, :, :],
                q_lo[:, None, :], q_hi[:, None, :])
            ia = jnp.where(repo_loc.ds_valid[None, :], ia, -1.0)
            return merge.shard_topk(ia, k, axis)

        sm = self._smap(local, in_specs=(self.specs, self._rows, self._rows),
                        out_specs=(self._rows, self._rows))

        def impl(repo_s, q_lo, q_hi):
            vals, ids = sm(repo_s, q_lo, q_hi)
            return vals, merge.sentinel_ids(vals, ids)

        return self._bind(impl)

    def build_topk_gbo(self, k: int):
        axis = self.axis

        def local(repo_loc, q_sigs):
            counts = kernel_ops.set_intersect_counts(q_sigs, repo_loc.ds_sigs)
            counts = jnp.where(repo_loc.ds_valid[None, :], counts, -1)
            return merge.shard_topk(counts, k, axis)

        sm = self._smap(local, in_specs=(self.specs, self._rows),
                        out_specs=(self._rows, self._rows))

        def impl(repo_s, q_sigs):
            vals, ids = sm(repo_s, q_sigs)
            return vals, merge.sentinel_ids(vals, ids)

        return self._bind(impl)

    def build_topk_hausdorff_approx(self, k: int):
        axis = self.axis

        def local(repo_loc, q_batch, eps):
            dq = q_batch.depth
            dd = repo_loc.ds_index.depth
            n_lq = 1 << dq
            n_ld = 1 << dd

            # Lemma 1 dataset-side stopping level from the WHOLE repository:
            # AND the per-shard level-ok bits (padded slots have counts == 0
            # and drop out of the check exactly like builder padding)
            oks = batched_ops._levels_ok(
                repo_loc.ds_index.radii, repo_loc.ds_index.counts, dd, eps)
            oks = jax.lax.pmin(oks.astype(jnp.int32), axis).astype(bool)
            ld = jnp.where(jnp.any(oks), jnp.argmax(oks), dd)
            ld = ld.astype(jnp.int32)

            od, rd, cd, dmask = batched_ops._gather_frontier(
                repo_loc.ds_index.centers, repo_loc.ds_index.radii,
                repo_loc.ds_index.counts, ld, n_ld)
            d_ok = (cd > 0) & dmask[None, :]
            # global eps_eff radius term: max of the per-shard maxima (exact)
            r_d = jax.lax.pmax(jnp.max(jnp.where(d_ok, rd, 0.0)), axis)
            base = jax.lax.axis_index(axis) * repo_loc.ds_valid.shape[0]

            def per_query(q_centers, q_radii, q_counts):
                lq = batched_ops._level_for_eps(q_radii, q_counts, dq, eps)
                oq, rq, cq, qmask = batched_ops._gather_frontier(
                    q_centers, q_radii, q_counts, lq, n_lq)
                q_ok = (cq > 0) & qmask

                def one(od_i, ok_i):
                    cdm = geometry.pairwise_dist_exact(oq, od_i)
                    cdm = jnp.where(ok_i[None, :], cdm, BIG)
                    row = jnp.min(cdm, axis=1)
                    return jnp.max(jnp.where(q_ok, row, -BIG))

                vals = jax.vmap(one)(od, d_ok)
                vals = jnp.where(repo_loc.ds_valid, vals, BIG)
                neg, gids = merge.local_topk(-vals, k, base)
                r_q = jnp.max(jnp.where(q_ok, rq, 0.0))
                eps_eff = jnp.maximum(jnp.asarray(eps, r_q.dtype),
                                      jnp.maximum(r_q, r_d))
                return neg, gids, eps_eff

            neg, gids, eps_eff = jax.vmap(per_query)(
                q_batch.centers, q_batch.radii, q_batch.counts)
            neg, ids = merge.all_gather_topk(neg, gids, k, axis)
            return -neg, ids, eps_eff

        # eps is a replicated SCALAR (rank 0): its spec must stay P()
        sm = self._smap(local, in_specs=(self.specs, self._rows, P()),
                        out_specs=(self._rows, self._rows, self._rows))

        def impl(repo_s, q_batch, eps):
            return sm(repo_s, q_batch, eps)

        return self._bind(impl)

    def build_topk_hausdorff(self, k: int, refine_levels: int, chunk: int):
        """Sharded BATCHED ExactHaus: per-shard bound phases and ONE
        per-shard phase-2 while_loop for the whole (B, ...) query batch,
        with each query's tau all-reduced after every chunk (the schedule
        from the module docstring, batched over queries), then the O(k)
        all-gather top-k merge per query.  Per-query values and ids are
        bit-identical to the single-device pipeline and the host oracle;
        only the `evaluated` stat is schedule-dependent."""
        axis = self.axis
        n_total = self.n_slots
        shard = self.shard_slots

        def local(repo_loc, q_batch):
            LB, tau, cand, nodes, cand_after = search._hausdorff_bound_phases(
                repo_loc, q_batch, k, refine_levels, axis=axis,
                n_slots_total=n_total)
            exact_vals, evaluated = search._phase2_exact_loop(
                LB, cand, tau, q_batch, repo_loc.ds_index, k, chunk,
                axis=axis)
            vals = jnp.where(repo_loc.ds_valid[None, :], exact_vals, BIG)
            # shard-padded slots carry BIG like invalid ones and lose every
            # smallest-index tie, so k <= n_slots never surfaces a pad id
            base = jax.lax.axis_index(axis) * shard
            neg, gids = merge.local_topk(-vals, k, base)
            neg, ids = merge.all_gather_topk(neg, gids, k, axis)
            return -neg, ids, nodes, cand_after, evaluated

        sm = self._smap(local, in_specs=(self.specs, self._rows),
                        out_specs=(self._rows,) * 5)

        def impl(repo_s, q_batch):
            return sm(repo_s, q_batch)

        return self._bind(impl)

    def _build_topk_join(self, k: int, mode: str, chunk: int):
        """Sharded joinable top-k: per-shard bound phase over the local
        slot slice, the shared-order chunked refine with each query's
        integer τ all-reduced after every chunk (collective cond, so all
        shards iterate together), then the O(k) all-gather top-k merge.
        Scores are exact ints, so values/ids are bit-identical to the
        local dispatcher and the host oracle under ANY shard count; only
        the `evaluated` stat is schedule-dependent (the ExactHaus
        contract).  Shard-padded slots are invalid (ds_valid False), carry
        UB -1, and are never evaluated."""
        axis = self.axis
        n_total = self.n_slots
        shard = self.shard_slots

        def local(repo_loc, q_pts, q_val):
            exact, nodes, cand_after, evaluated = join_search.topk_join_scores(
                repo_loc, q_pts, q_val, k, mode, chunk, axis=axis,
                n_slots_total=n_total)
            base = jax.lax.axis_index(axis) * shard
            vals, gids = merge.local_topk(exact, k, base)
            vals, ids = merge.all_gather_topk(vals, gids, k, axis)
            return (vals, merge.sentinel_ids(vals, ids), nodes, cand_after,
                    evaluated)

        sm = self._smap(local, in_specs=(self.specs, self._rows, self._rows),
                        out_specs=(self._rows,) * 5)

        def impl(repo_s, q_pts, q_val):
            return sm(repo_s, q_pts, q_val)

        return self._bind(impl)

    def build_topk_overlap(self, k: int, chunk: int):
        return self._build_topk_join(k, "overlap", chunk)

    def build_topk_coverage(self, k: int, chunk: int):
        return self._build_topk_join(k, "coverage", chunk)

    # -- point granularity -------------------------------------------------

    def build_range_points(self):
        axis = self.axis

        def local(repo_loc, ds_ids, r_lo, r_hi):
            mine, d_sel = self._owner_select(repo_loc, ds_ids)
            take, scanned = jax.vmap(point_search.range_points_core)(
                d_sel, r_lo, r_hi)
            take = (take & mine[:, None]).astype(jnp.int32)
            scanned = (scanned & mine[:, None]).astype(jnp.int32)
            take = jax.lax.psum(take, axis).astype(bool)
            scanned = jax.lax.psum(scanned, axis).astype(bool)
            return take, scanned

        sm = self._smap(local,
                        in_specs=(self.specs, self._rows, self._rows,
                                  self._rows),
                        out_specs=(self._rows, self._rows))

        def impl(repo_s, ds_ids, r_lo, r_hi):
            return sm(repo_s, ds_ids, r_lo, r_hi)

        return self._bind(impl)

    def build_nnp(self):
        axis = self.axis

        def local(repo_loc, ds_ids, q_batch):
            mine, d_sel = self._owner_select(repo_loc, ds_ids)
            dists, idxs, pair_live = jax.vmap(point_search.nnp_pruned_core)(
                q_batch, d_sel)
            # owner-exclusive merge: + 0.0 and + 0 are exact, so the psum
            # reproduces the owner's values bit-for-bit; the Eq. 4
            # pair_live prune mask rides along the same way so the engine
            # can book the pruned fraction (PointStats)
            dists = jax.lax.psum(jnp.where(mine[:, None], dists, 0.0), axis)
            idxs = jax.lax.psum(jnp.where(mine[:, None], idxs, 0), axis)
            pair_live = jax.lax.psum(
                jnp.where(mine[:, None, None], pair_live, 0
                          ).astype(jnp.int32), axis).astype(bool)
            return dists, idxs, pair_live

        sm = self._smap(local, in_specs=(self.specs, self._rows, self._rows),
                        out_specs=(self._rows, self._rows, self._rows))

        def impl(repo_s, ds_ids, q_batch):
            return sm(repo_s, ds_ids, q_batch)

        return self._bind(impl)

    def build_join_rerank(self, mode: str):
        """Dataset→dataset pipeline stage 2, sharded: each winner slot's
        points live on exactly one shard, so the row-wise exact join score
        merges owner-exclusively (+0 is exact for ints, same pattern as
        NNP/RangeP)."""
        axis = self.axis

        def local(repo_loc, ds_ids, q_pts, q_val):
            mine, d_sel = self._owner_select(repo_loc, ds_ids)
            sc = join_search.pair_scores(repo_loc, d_sel.points, d_sel.valid,
                                         q_pts, q_val, mode)
            return jax.lax.psum(jnp.where(mine, sc, 0), axis)

        sm = self._smap(local, in_specs=(self.specs, self._rows, self._rows,
                                         self._rows),
                        out_specs=self._rows)

        def impl(repo_s, ds_ids, q_pts, q_val):
            return sm(repo_s, ds_ids, q_pts, q_val)

        return self._bind(impl)


class ShardedQueryEngine(QueryEngine):
    """QueryEngine whose resident repository is sharded over a mesh axis.

    Same bucket ladder, executable cache, query construction, and
    :class:`~repro.engine.engine.EngineStats`; only dispatch differs.  With
    no ``mesh`` given, shards over ALL local devices on a 1-D ``data``
    mesh (a 1-device mesh degenerates to the local layout, so the class is
    safe to use unconditionally).
    """

    def __init__(
        self,
        repo: Repository,
        *,
        mesh: Mesh | None = None,
        shard_spec: str = "data",
        buckets=DEFAULT_BUCKETS,
        leaf_capacity: int = 16,
        result_cache_size: int = DEFAULT_RESULT_CACHE,
    ):
        if mesh is None:
            mesh = data_mesh(axis=shard_spec)
        super().__init__(repo, buckets=buckets, leaf_capacity=leaf_capacity,
                         mesh=mesh, shard_spec=shard_spec,
                         result_cache_size=result_cache_size)
