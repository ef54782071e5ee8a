"""Distributed Spadas (DESIGN.md sec. 4): how the paper's search scales out.

Two parallel dimensions, matching the production mesh axes:

  * repository sharding over the ``data`` (and ``pod``) axis — each shard
    owns a slice of dataset slots, runs the identical batched bound pass,
    and the global top-k is an O(k) all-gather merge;
  * point sharding over the ``model`` axis for giant pairwise ops — the
    ring Hausdorff/NNP: Q rows stay resident, D shards rotate around the
    axis via collective_permute, each hop updating the running per-row min
    (the same communication shape as ring attention, so compute/comm
    overlap is native).

Every function here is written with `jax.shard_map` so the collective
schedule is explicit and shows up in the dry-run HLO for the roofline.
"""
from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.core import geometry
from repro.kernels import ops

Array = jax.Array
BIG = 3.4e38


# ---------------------------------------------------------------------------
# repository-sharded bound pass + top-k merge
# ---------------------------------------------------------------------------


def global_kth_smallest(x: Array, k: int, axis: str) -> Array:
    """kth-smallest over a vector sharded on ``axis`` — O(k) per shard.

    Inside shard_map only.  Each shard contributes its min(k, shard_slots)
    smallest entries; the union of those lists always contains the global k
    smallest (at most k - 1 values can precede any of them, globally or
    per shard), so sorting the all-gathered S * min(k, shard) candidates
    and indexing position k - 1 (clamped) selects exactly the element
    `jnp.sort(global_x)[min(k - 1, n - 1)]` would — the VALUE is the same
    float bit pattern because no arithmetic touches it, only selection.
    This is the tau reduction of sharded ExactHaus (phases 0/1 and the
    per-chunk phase-2 tightening) and mirrors the loc_ub gather in
    :func:`sharded_topk_bounds`.
    """
    k_loc = min(k, x.shape[-1])
    small = -jax.lax.top_k(-x, k_loc)[0]          # ascending k_loc smallest
    small = jax.lax.all_gather(small, axis, axis=small.ndim - 1, tiled=True)
    return jnp.sort(small)[..., min(k - 1, small.shape[-1] - 1)]


def sharded_topk_bounds(
    mesh: Mesh,
    axis: str | tuple[str, ...],
    q_center: Array,
    q_radius: Array,
    ds_centers: Array,
    ds_radii: Array,
    ds_valid: Array,
    k: int,
):
    """Phase-0 ExactHaus bound pass, repository sharded over ``axis``.

    ds_* are (B, ...) arrays sharded on their leading dim.  Returns global
    (tau, lb, ub): tau = kth-smallest UB across ALL shards (the batch-prune
    threshold), lb/ub the per-slot bounds (still sharded).
    """
    axes = (axis,) if isinstance(axis, str) else tuple(axis)

    def local(qc, qr, dc, dr, dv):
        cd = jnp.sqrt(jnp.sum((dc - qc[None, :]) ** 2, axis=-1))
        lb = jnp.maximum(cd - dr, 0.0)
        ub = jnp.sqrt(cd * cd + dr * dr) + qr
        lb = jnp.where(dv, lb, BIG)
        ub = jnp.where(dv, ub, BIG)
        # local k smallest upper bounds -> O(k) gather instead of O(B)
        loc_ub = -jax.lax.top_k(-ub, k)[0]
        all_ub = loc_ub
        for ax in axes:
            all_ub = jax.lax.all_gather(all_ub, ax, tiled=True)
        tau = jnp.sort(all_ub)[k - 1]
        return tau, lb, ub

    spec_b = P(axes)
    spec_bd = P(axes, None)
    return jax.shard_map(
        local,
        mesh=mesh,
        in_specs=(P(), P(), spec_bd, spec_b, spec_b),
        out_specs=(P(), spec_b, spec_b),
        check_vma=False,  # tau is replicated by the all_gather merge
    )(q_center, q_radius, ds_centers, ds_radii, ds_valid)


# ---------------------------------------------------------------------------
# ring Hausdorff over the model axis
# ---------------------------------------------------------------------------


def ring_hausdorff(
    mesh: Mesh,
    axis: str,
    q: Array,
    q_valid: Array,
    d: Array,
    d_valid: Array,
    *,
    use_kernel: bool = False,
):
    """Directed Hausdorff H(Q -> D) with BOTH point sets sharded on ``axis``.

    Q rows stay put; D shards rotate around the ring.  Per-hop compute is
    the streaming min kernel on the local (Q-shard x D-shard) tile, so the
    collective_permute of the next D shard overlaps with it.  Ends with an
    all-reduce max over the axis.
    """
    n_dev = mesh.shape[axis]

    def local(q_s, qv_s, d_s, dv_s):
        def hop(i, carry):
            mins, d_cur, dv_cur = carry
            d2 = geometry.sq_dist_matrix(q_s, d_cur)
            d2 = jnp.where(dv_cur[None, :], d2, BIG)
            mins = jnp.minimum(mins, jnp.min(d2, axis=1))
            perm = [(j, (j + 1) % n_dev) for j in range(n_dev)]
            d_nxt = jax.lax.ppermute(d_cur, axis, perm)
            dv_nxt = jax.lax.ppermute(dv_cur, axis, perm)
            return mins, d_nxt, dv_nxt

        mins0 = jax.lax.pvary(jnp.full((q_s.shape[0],), BIG, jnp.float32), axis)
        mins, _, _ = jax.lax.fori_loop(0, n_dev, hop, (mins0, d_s, dv_s))
        nn = jnp.sqrt(jnp.minimum(mins, BIG))
        local_h = jnp.max(jnp.where(qv_s, nn, -BIG))
        return jax.lax.pmax(local_h, axis)

    return jax.shard_map(
        local,
        mesh=mesh,
        in_specs=(P(axis, None), P(axis), P(axis, None), P(axis)),
        out_specs=P(),
    )(q, q_valid, d, d_valid)


def ring_nn_distance(
    mesh: Mesh,
    axis: str,
    q: Array,
    q_valid: Array,
    d: Array,
    d_valid: Array,
):
    """Ring NNP: per-Q-row global NN distance + index, both sets sharded."""
    n_dev = mesh.shape[axis]
    shard_d = d.shape[0] // n_dev

    def local(q_s, qv_s, d_s, dv_s):
        my = jax.lax.axis_index(axis)

        def hop(i, carry):
            mins, args, d_cur, dv_cur = carry
            owner = (my + i) % n_dev  # whose shard we currently hold
            d2 = geometry.sq_dist_matrix(q_s, d_cur)
            d2 = jnp.where(dv_cur[None, :], d2, BIG)
            tmin = jnp.min(d2, axis=1)
            targ = jnp.argmin(d2, axis=1).astype(jnp.int32) + owner * shard_d
            better = tmin < mins
            mins = jnp.where(better, tmin, mins)
            args = jnp.where(better, targ, args)
            perm = [(j, (j + 1) % n_dev) for j in range(n_dev)]
            d_nxt = jax.lax.ppermute(d_cur, axis, perm)
            dv_nxt = jax.lax.ppermute(dv_cur, axis, perm)
            return mins, args, d_nxt, dv_nxt

        mins0 = jax.lax.pvary(jnp.full((q_s.shape[0],), BIG, jnp.float32), axis)
        args0 = jax.lax.pvary(jnp.full((q_s.shape[0],), -1, jnp.int32), axis)
        mins, args, _, _ = jax.lax.fori_loop(
            0, n_dev, hop, (mins0, args0, d_s, dv_s)
        )
        dist = jnp.sqrt(jnp.minimum(mins, BIG))
        dist = jnp.where(qv_s, dist, 0.0)
        args = jnp.where(qv_s, args, -1)
        return dist, args

    return jax.shard_map(
        local,
        mesh=mesh,
        in_specs=(P(axis, None), P(axis), P(axis, None), P(axis)),
        out_specs=(P(axis), P(axis)),
    )(q, q_valid, d, d_valid)


# ---------------------------------------------------------------------------
# sharded GBO (bitset popcount) over the data axis
# ---------------------------------------------------------------------------


def sharded_topk_gbo(
    mesh: Mesh,
    axis: str | tuple[str, ...],
    q_sig: Array,
    ds_sigs: Array,
    ds_valid: Array,
    k: int,
):
    """Top-k GBO with signatures sharded over the repository axis.

    Local popcount(AND) + local top-k, then an O(k) all-gather merge of
    (value, global id) pairs."""
    axes = (axis,) if isinstance(axis, str) else tuple(axis)

    def local(qs, sg, dv):
        counts = jax.lax.population_count(qs[None, :] & sg).astype(jnp.int32)
        counts = counts.sum(axis=-1)
        counts = jnp.where(dv, counts, -1)
        shard = sg.shape[0]
        vals, ids = jax.lax.top_k(counts, k)
        idx = jax.lax.axis_index(axes[0])
        for ax in axes[1:]:
            idx = idx * mesh.shape[ax] + jax.lax.axis_index(ax)
        gids = ids + idx * shard
        for ax in axes:
            vals = jax.lax.all_gather(vals, ax, tiled=True)
            gids = jax.lax.all_gather(gids, ax, tiled=True)
        top, pos = jax.lax.top_k(vals, k)
        return top, gids[pos]

    spec = P(axes)
    return jax.shard_map(
        local,
        mesh=mesh,
        in_specs=(P(), P(axes, None), spec),
        out_specs=(P(), P()),
        check_vma=False,  # top-k is replicated by the all_gather merge
    )(q_sig, ds_sigs, ds_valid)
