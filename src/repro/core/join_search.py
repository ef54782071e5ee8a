"""Joinable dataset search: grid-cell overlap / coverage over the repository.

The resemblance ops (Hausdorff / IA / GBO) rank repository datasets by how
*similar* they are to the query; the companion joinable-search formulation
(arXiv 2311.13383) ranks them by how well they *join* with it on a shared
spatial grid:

  overlap(Q, D)  = |cells(Q) ∩ cells(D)|      distinct grid cells occupied
                                              by both datasets
  coverage(Q, D) = |{p ∈ Q : cell(p) ∈ cells(D)}|
                                              query points landing in cells
                                              D occupies

Both are exact **integers**, which buys the bit-identity bar for free: any
schedule (local / sharded / replicated, kernel or reference popcount path)
produces the same counts, so prune decisions and final rankings agree
everywhere without a float guard.

Join resolution vs stored resolution
------------------------------------
Scores are defined on a *fine* grid at ``theta_f = theta_c + FINE_DELTA``
where ``theta_c`` is the resolution of the resident coarse signatures
(derived from their word count, so it tracks whatever the repository was
built with).  Each coarse cell tiles into ``R2 = 4**FINE_DELTA`` fine
cells.  Fine signatures are never stored — they are built on the fly from
resident points, which is exactly what makes the bound phase matter.

Bounds (the Eq.-4 shape, adapted to set counts)
-----------------------------------------------
From the resident coarse signature of a slot D we get sound upper bounds
without touching D's points:

  UB_overlap(Q, D)  = min(R2 · |coarse(Q) ∩ coarse(D)|, |fine(Q)|)
      every common fine cell lies inside a common coarse cell, and each
      coarse cell contains at most R2 fine cells;
  UB_coverage(Q, D) = Σ_c hist_c(Q)[c] · occ(D)[c]
      (# query points in D-occupied *coarse* cells — every covered point's
      fine cell sits inside a D-occupied coarse cell).

The same bounds evaluated on the upper tree's OR-union node signatures
bound every descendant slot (unions only grow popcounts), giving the
multi-level frontier accounting reported as ``nodes_evaluated``; the
per-slot bound is uniformly tighter, so it is the one that drives the
actual pruning.

Refine (shared-order chunked loop)
----------------------------------
Exact scoring processes slots in ONE shared order — descending
max-over-the-batch UB — in chunks: each chunk's fine signatures are built
once from resident points and scored against the whole query batch as a
dense (B, chunk) popcount block (the set-intersect kernel path).  Each
query maintains τ_b = k-th largest exact score seen so far (globally
reduced when sharded); a slot is pruned iff UB < τ, and the loop stops
when no query's remaining suffix-max UB reaches its τ.

Soundness: τ is the k-th largest of an evaluated *subset*, hence ≤ the
true k-th value, so a pruned slot (score ≤ UB < τ) is strictly below the
k-th and can never enter the top-k even under smallest-index tie-breaks;
conversely every true top-k member has UB ≥ score ≥ τ at all times and is
always evaluated.  Results are therefore schedule-independent; only the
``exact_evaluations`` counter (and the pruned fraction derived from it)
depends on chunking/sharding, same contract as ExactHaus.

Coverage rides the popcount kernel via **bit-plane decomposition**: the
per-cell point-count histogram of Q is sliced into P = ceil(log2(n+1))
bit planes packed like signatures, and

  coverage = Σ_p 2^p · |plane_p(Q) ∩ occ(D)|

so one (B·P, S) set-intersect matrix answers the whole batch.
"""
from __future__ import annotations

import math
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import distributed, zorder
from repro.core.repo_index import Repository
from repro.core.search import SearchStats
from repro.kernels import ops

#: fine grid refinement below the stored coarse resolution:
#: theta_f = theta_c + FINE_DELTA, R2 = 4**FINE_DELTA fine cells per coarse
FINE_DELTA = 2

MODES = ("overlap", "coverage")


def theta_of_words(n_words: int) -> int:
    """Grid resolution theta whose signature packs into `n_words` uint32."""
    return int(math.log2(n_words * zorder.WORD_BITS)) // 2


def join_thetas(repo: Repository) -> tuple[int, int]:
    """(coarse, fine) grid resolutions for joinable scoring on `repo`."""
    theta_c = theta_of_words(repo.ds_sigs.shape[-1])
    return theta_c, theta_c + FINE_DELTA


def num_planes(n_points: int) -> int:
    """Bit planes needed for per-cell counts of an n-point histogram."""
    return max(1, int(n_points).bit_length())


def hist_planes(points, valid, lo, hi, theta: int, n_planes: int):
    """Per-cell point-count histogram packed as bit planes.

    Returns (n_planes, W) uint32 where word-bit (p, c) is bit p of the
    number of valid points quantized into grid cell c — i.e. plane_p of
    the histogram, packed exactly like a z-order signature so popcount
    machinery applies unchanged.
    """
    n_cells = zorder.num_cells(theta)
    w = zorder.num_words(theta)
    ids = zorder.cell_ids(points, lo, hi, theta)
    ids = jnp.where(valid, ids, n_cells)        # park padding in overflow
    hist = jnp.zeros((n_cells + 1,), jnp.int32).at[ids].add(1)[:n_cells]
    bits = (hist[None, :] >> jnp.arange(n_planes, dtype=jnp.int32)[:, None]) & 1
    bits = bits.astype(jnp.uint32).reshape(n_planes, w, zorder.WORD_BITS)
    shifts = jnp.arange(zorder.WORD_BITS, dtype=jnp.uint32)
    return jax.lax.reduce(bits << shifts[None, None, :], np.uint32(0),
                          jax.lax.bitwise_or, (2,))


def _plane_dot(planes, sigs):
    """Σ_p 2^p · popcount(plane_p ∧ sig) — pure-jnp small-matrix form.

    planes (B, P, W) vs sigs (N, W) -> (B, N) int32.  Used for the upper
    tree's per-level node bounds, where N is tiny; the (B, S) slot-matrix
    passes go through :func:`repro.kernels.ops.plane_weighted_intersect`
    instead so they ride the set-intersect kernel routing.
    """
    cnt = jax.lax.population_count(
        planes[:, :, None, :] & sigs[None, None, :, :])
    cnt = cnt.astype(jnp.int32).sum(axis=-1)                   # (B, P, N)
    weights = jnp.left_shift(jnp.int32(1),
                             jnp.arange(planes.shape[1], dtype=jnp.int32))
    return (cnt * weights[None, :, None]).sum(axis=1)


def query_features(q_pts, q_val, lo, hi, theta_c: int, theta_f: int,
                   mode: str):
    """Per-query grid features: coarse/fine signatures (+ planes for
    coverage).  Returns a dict of batched arrays."""
    sig_c = jax.vmap(lambda p, v: zorder.signature(p, v, lo, hi, theta_c))
    sig_f = jax.vmap(lambda p, v: zorder.signature(p, v, lo, hi, theta_f))
    feats = {"csig": sig_c(q_pts, q_val), "fsig": sig_f(q_pts, q_val)}
    feats["fcnt"] = zorder.sig_count(feats["fsig"]).astype(jnp.int32)
    if mode == "coverage":
        n_p = num_planes(q_pts.shape[-2])
        feats["cplanes"] = jax.vmap(
            lambda p, v: hist_planes(p, v, lo, hi, theta_c, n_p))(q_pts, q_val)
        feats["fplanes"] = jax.vmap(
            lambda p, v: hist_planes(p, v, lo, hi, theta_f, n_p))(q_pts, q_val)
    return feats


def _slot_bounds(repo, feats, mode: str, r2: int):
    """Per-slot upper bounds from resident coarse signatures: (B, S) int32
    with -1 in invalid (padding / deleted / shard-pad) slots."""
    if mode == "overlap":
        ub = ops.set_intersect_counts(feats["csig"], repo.ds_sigs) * r2
        ub = jnp.minimum(ub, feats["fcnt"][:, None])
    else:
        ub = ops.plane_weighted_intersect(feats["cplanes"], repo.ds_sigs)
    return jnp.where(repo.ds_valid[None, :], ub, -1)


def _node_frontier(repo, feats, tau, mode: str, r2: int):
    """Eq.-4-style multi-level accounting: per-query count of upper-tree
    nodes a bound-driven frontier descent at threshold τ would expand.
    The upper tree is replicated on every shard, so this is collective-free
    and identical across dispatchers."""
    up = repo.repo
    floor = jnp.maximum(tau, 0)[:, None]
    active = jnp.ones((tau.shape[0], 1), bool)
    nodes = jnp.zeros(tau.shape, jnp.int32)
    for level in range(up.depth + 1):
        sl = up.level_slice(level)
        sg = up.sigs[sl]
        if mode == "overlap":
            ubn = zorder.sig_intersect_count(
                feats["csig"][:, None, :], sg[None, :, :]) * r2
            ubn = jnp.minimum(ubn, feats["fcnt"][:, None])
        else:
            ubn = _plane_dot(feats["cplanes"], sg)
        live = active & (ubn >= floor) & (up.counts[sl] > 0)[None, :]
        nodes = nodes + live.sum(axis=-1).astype(jnp.int32)
        if level < up.depth:
            active = jnp.repeat(live, 2, axis=1)
    return nodes


def slot_fine_sigs(points, valid, lo, hi, theta_f: int):
    """Fine signatures for a batch of resident slot point sets."""
    return jax.vmap(
        lambda p, v: zorder.signature(p, v, lo, hi, theta_f))(points, valid)


def topk_join_scores(repo, q_pts, q_val, k: int, mode: str, chunk: int,
                     *, axis=None, n_slots_total=None):
    """Bound phase + shared-order chunked exact refine over the (local
    slice of the) repository.

    Returns ``(exact, nodes, cand_after, evaluated)``:
      exact       (B, S) int32 — exact join score, or -1 where the slot is
                  invalid or was pruned by the bounds (pruned slots are
                  provably outside every query's top-k, see module doc);
      nodes       (B,) multi-level frontier accounting at τ_final;
      cand_after  (B,) slots whose UB survives τ_final (globally summed
                  when `axis` is set);
      evaluated   (B,) exact evaluations actually performed (global).

    With ``axis`` set the caller runs this inside shard_map over the slot
    axis; τ and the continue flag are reduced collectively so every shard
    runs the same number of iterations.
    """
    assert mode in MODES, mode
    lo, hi = repo.space_lo, repo.space_hi
    theta_c, theta_f = join_thetas(repo)
    r2 = 1 << (2 * FINE_DELTA)
    B = q_pts.shape[0]
    S = repo.n_slots
    feats = query_features(q_pts, q_val, lo, hi, theta_c, theta_f, mode)

    ub = _slot_bounds(repo, feats, mode, r2)                   # (B, S)

    # one shared processing order for the whole batch (descending
    # max-over-queries UB): each chunk's fine signatures are then built
    # ONCE from resident points and scored against every query
    order = jnp.argsort(-jnp.max(ub, axis=0), stable=True)
    n_chunks = max(1, -(-S // chunk))
    s_pad = n_chunks * chunk
    order_p = jnp.pad(order, (0, s_pad - S))
    ub_sorted = jnp.where((jnp.arange(s_pad) < S)[None, :],
                          jnp.take(ub, order_p, axis=1), -1)
    chunk_max = ub_sorted.reshape(B, n_chunks, chunk).max(axis=-1)
    # suffix max over chunks: the best UB any later slot can offer
    suff = jnp.flip(jax.lax.cummax(jnp.flip(chunk_max, axis=-1), axis=1),
                    axis=-1)                                   # (B, n_chunks)

    ds_pts, ds_val = repo.ds_index.points, repo.ds_index.valid
    k_eff = min(k, S)

    def tau_update(exact, tau_c):
        fin = exact >= 0
        if axis is None:
            kth = jax.lax.top_k(exact, k_eff)[0][..., k_eff - 1]
            n_fin = fin.sum(axis=-1)
        else:
            kth = -distributed.global_kth_smallest(-exact, k, axis)
            n_fin = jax.lax.psum(fin.sum(axis=-1).astype(jnp.int32), axis)
        # only a FULL top-k of true scores may raise τ (k-th largest of an
        # evaluated subset ≤ true k-th value, so pruning stays sound);
        # with fewer than k evaluated the -1 fill would leak in
        return jnp.maximum(tau_c, jnp.where(n_fin >= k, kth, -1))

    def need(pos, tau_c):
        sm = jax.lax.dynamic_slice_in_dim(
            suff, jnp.minimum(pos, n_chunks - 1), 1, axis=1)[:, 0]
        # valid slots always have UB >= 0, so flooring τ at 0 both skips
        # invalid-only suffixes and keeps every unpruned valid slot
        return (pos < n_chunks) & (sm >= jnp.maximum(tau_c, 0))

    def reduce_any(g):
        flag = jnp.any(g)
        if axis is None:
            return flag
        return jax.lax.psum(flag.astype(jnp.int32), axis) > 0

    def body(carry):
        _, pos, exact, tau_c, evaluated = carry
        nb = need(pos, tau_c)                                  # (B,)
        go = jnp.any(nb)
        idx = pos * chunk + jnp.arange(chunk)
        ids = jnp.take(order_p, idx, mode="clip")
        sigs = slot_fine_sigs(ds_pts[ids], ds_val[ids], lo, hi, theta_f)
        if mode == "overlap":
            sc = ops.set_intersect_counts(feats["fsig"], sigs)
        else:
            sc = ops.plane_weighted_intersect(feats["fplanes"], sigs)
        live = ((idx < S) & jnp.take(repo.ds_valid, ids, mode="clip")
                )[None, :] & nb[:, None] & go
        sc = jnp.where(live, sc, -1)
        exact = exact.at[:, ids].max(sc)       # clipped dup ids carry -1
        evaluated = evaluated + live.sum(axis=-1).astype(jnp.int32)
        pos = jnp.where(go, pos + 1, pos)
        tau_c = tau_update(exact, tau_c)
        return (reduce_any(need(pos, tau_c)), pos, exact, tau_c, evaluated)

    tau0 = jnp.full((B,), -1, jnp.int32)
    init = (reduce_any(need(jnp.int32(0), tau0)), jnp.int32(0),
            jnp.full((B, S), -1, jnp.int32), tau0,
            jnp.zeros((B,), jnp.int32))
    if axis is not None:
        # same XLA CPU hazard as ExactHaus phase 2: without the barrier the
        # loop-entry computation fuses across the shard_map boundary and
        # miscompiles at some shard counts
        init = jax.lax.optimization_barrier(init)
    _, _, exact, tau_f, evaluated = jax.lax.while_loop(
        lambda c: c[0], body, init)

    cand = ((ub >= jnp.maximum(tau_f, 0)[:, None]) & (ub >= 0)
            ).sum(axis=-1).astype(jnp.int32)
    if axis is not None:
        cand = jax.lax.psum(cand, axis)
        evaluated = jax.lax.psum(evaluated, axis)
    nodes = _node_frontier(repo, feats, tau_f, mode, r2)
    return exact, nodes, cand, evaluated


def pair_scores(repo, d_points, d_valid, q_pts, q_val, mode: str):
    """Row-wise exact join score between query row t and slot points row t.

    Used by the dataset→dataset pipeline stage: stage-1 winner slots are
    gathered on device and re-scored against the pipeline's own query set.
    Returns (T,) int32 (≥ 0; the caller masks sentinel rows)."""
    assert mode in MODES, mode
    lo, hi = repo.space_lo, repo.space_hi
    _, theta_f = join_thetas(repo)
    d_sigs = slot_fine_sigs(d_points, d_valid, lo, hi, theta_f)
    if mode == "overlap":
        q_sigs = jax.vmap(
            lambda p, v: zorder.signature(p, v, lo, hi, theta_f))(q_pts, q_val)
        return zorder.sig_intersect_count(q_sigs, d_sigs)
    n_p = num_planes(q_pts.shape[-2])
    planes = jax.vmap(
        lambda p, v: hist_planes(p, v, lo, hi, theta_f, n_p))(q_pts, q_val)
    cnt = jax.lax.population_count(planes & d_sigs[:, None, :])
    cnt = cnt.astype(jnp.int32).sum(axis=-1)                   # (T, P)
    weights = jnp.left_shift(jnp.int32(1), jnp.arange(n_p, dtype=jnp.int32))
    return (cnt * weights[None, :]).sum(axis=-1)


# ---------------------------------------------------------------------------
# host oracle
# ---------------------------------------------------------------------------


def _host_cells(points, valid, lo, hi, theta: int):
    """Fine-grid cell id per valid point (host numpy array)."""
    ids = np.asarray(zorder.cell_ids(jnp.asarray(points), lo, hi, theta))
    return ids[np.asarray(valid)]


def topk_join_host(repo: Repository, pointsets, k: int, mode: str):
    """Brute-force joinable top-k oracle over the resident repository.

    Scores every valid slot with plain Python set arithmetic on the shared
    grid assignment, ranks descending with ties toward the smaller slot id
    (the `lax.top_k` rule), and sentinels rows past the valid supply.
    Returns (vals (B, k), ids (B, k)) int32 numpy arrays.
    """
    assert mode in MODES, mode
    lo, hi = repo.space_lo, repo.space_hi
    _, theta_f = join_thetas(repo)
    d_pts = np.asarray(repo.ds_index.points)
    d_val = np.asarray(repo.ds_index.valid)
    slot_valid = np.asarray(repo.ds_valid)
    S = d_pts.shape[0]
    # one cell-id call for every slot: per-slot calls cost a device
    # round trip each, which dominates at repository scale on a chip
    cells = np.asarray(zorder.cell_ids(jnp.asarray(d_pts), lo, hi, theta_f))
    d_cells = [set(cells[s][d_val[s]].tolist()) if slot_valid[s] else set()
               for s in range(S)]

    vals = np.full((len(pointsets), k), -1, np.int32)
    ids = np.full((len(pointsets), k), -1, np.int32)
    for b, q in enumerate(pointsets):
        q = np.asarray(q, np.float32)
        qc = _host_cells(q, np.ones(len(q), bool), lo, hi, theta_f)
        qc = qc.tolist()
        q_cells = set(qc)
        scores = np.full((S,), -1, np.int64)
        for s in range(S):
            if not slot_valid[s]:
                continue
            if mode == "overlap":
                scores[s] = len(q_cells & d_cells[s])
            else:
                scores[s] = sum(c in d_cells[s] for c in qc)
        top = np.argsort(-scores, kind="stable")[:k]
        t = len(top)
        vals[b, :t] = scores[top]
        ids[b, :t] = np.where(vals[b, :t] < 0, -1, top)
    return vals, ids


def join_stats_host(n_valid: int, evaluated, nodes, cand):
    """Fold device counters into per-query SearchStats rows (the ExactHaus
    shape: pruned fraction = share of valid slots never exact-scored)."""
    out = []
    for e, n, c in zip(np.asarray(evaluated), np.asarray(nodes),
                       np.asarray(cand)):
        out.append(SearchStats(
            nodes_evaluated=int(n),
            candidates_after_bounds=int(c),
            exact_evaluations=int(e),
            pruned_fraction=float(1.0 - int(e) / max(n_valid, 1)),
        ))
    return out
