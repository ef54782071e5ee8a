"""Public wrappers around the Pallas kernels.

Each public op is a PLAIN-PYTHON wrapper that resolves its routing
(kernel vs ref oracle, tile sizes) through :mod:`repro.kernels.autotune`
and then calls an inner jitted implementation with the resolved constants
as explicit static arguments.  Keeping the decision outside the jit
boundary means tuned constants are never baked into a traced program —
the autotuner's :func:`autotune.epoch` plus the engine's executable-cache
keys guarantee a table update re-routes every subsequent dispatch.

The inner impls handle padding to tile multiples, coordinate-dim padding,
and the TPU/interpret switch: on the ``tpu`` backend the kernels compile
through Mosaic; on ``cpu`` (the test suite runs with ``JAX_PLATFORMS=cpu``)
they run with interpret=True, which executes the kernel body via XLA ops —
the correctness path; any other backend is an error.  ``use_kernel=False``
pins the pure-jnp oracle in ref.py; ``use_kernel=True`` forces the kernel
at any size (the padding helpers round tiny inputs up to one tile).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels import autotune
from repro.kernels import bound_matrix as _bm
from repro.kernels import hausdorff as _haus
from repro.kernels import nn_distance as _nn
from repro.kernels import ref
from repro.kernels import set_intersect as _si

Array = jax.Array

BIG = ref.BIG


def _interpret() -> bool:
    """Pallas mode for the default backend: compiled kernels on ``tpu``,
    the interpreter on ``cpu`` (tests and local runs), and an error
    anywhere else — no backend falls back to the interpreter silently."""
    backend = jax.default_backend()
    if backend == "tpu":
        return False
    if backend == "cpu":
        return True
    raise RuntimeError(f"Pallas kernels need the 'tpu' backend (or 'cpu' "
                       f"in interpret mode), not {backend!r}")


def _pad_rows(x: Array, mult: int, fill=0.0) -> Array:
    n = x.shape[0]
    target = max(mult, ((n + mult - 1) // mult) * mult)
    if target == n:
        return x
    pad = [(0, target - n)] + [(0, 0)] * (x.ndim - 1)
    return jnp.pad(x, pad, constant_values=fill)


def _pad_coords(x: Array, width: int) -> Array:
    d = x.shape[-1]
    if d >= width:
        return x
    return jnp.pad(x, [(0, 0)] * (x.ndim - 1) + [(0, width - d)])


def directed_hausdorff(
    q: Array, d: Array, q_valid: Array, d_valid: Array,
    *, tq: int | None = None, td: int | None = None,
    use_kernel: bool | None = None,
) -> Array:
    """H(Q -> D), masked.  Kernel path streams D tiles (no HBM matrix)."""
    cfg = autotune.resolve("directed_hausdorff", (q.shape[0], d.shape[0]),
                           tq=tq, td=td, use_kernel=use_kernel)
    return _directed_hausdorff(q, d, q_valid, d_valid, tq=cfg.tq, td=cfg.td,
                               use_kernel=cfg.use_kernel)


@functools.partial(jax.jit, static_argnames=("tq", "td", "use_kernel"))
def _directed_hausdorff(
    q: Array, d: Array, q_valid: Array, d_valid: Array,
    *, tq: int, td: int, use_kernel: bool,
) -> Array:
    if not use_kernel:
        return ref.directed_hausdorff(q, d, q_valid, d_valid)
    n_coords = q.shape[-1]
    width = max(8, n_coords)
    qp = _pad_rows(_pad_coords(q, width), tq)
    dp = _pad_rows(_pad_coords(d, width), td)
    dv = _pad_rows(d_valid, td, fill=False)
    mins = _haus.min_sq_dists(qp, dp, dv, n_coords=n_coords, tq=tq, td=td,
                              interpret=_interpret())
    nnd = jnp.sqrt(jnp.minimum(mins[: q.shape[0]], BIG))
    nnd = jnp.where(q_valid, nnd, -BIG)
    return jnp.max(nnd)


def nn_distance(
    q: Array, d: Array, q_valid: Array, d_valid: Array,
    *, tq: int | None = None, td: int | None = None,
    use_kernel: bool | None = None,
):
    """Per-Q-point NN distance + D index (NNP hot loop)."""
    cfg = autotune.resolve("nn_distance", (q.shape[0], d.shape[0]),
                           tq=tq, td=td, use_kernel=use_kernel)
    return _nn_distance(q, d, q_valid, d_valid, tq=cfg.tq, td=cfg.td,
                        use_kernel=cfg.use_kernel)


@functools.partial(jax.jit, static_argnames=("tq", "td", "use_kernel"))
def _nn_distance(
    q: Array, d: Array, q_valid: Array, d_valid: Array,
    *, tq: int, td: int, use_kernel: bool,
):
    if not use_kernel:
        return ref.nn_distance(q, d, q_valid, d_valid)
    n_coords = q.shape[-1]
    width = max(8, n_coords)
    qp = _pad_rows(_pad_coords(q, width), tq)
    dp = _pad_rows(_pad_coords(d, width), td)
    dv = _pad_rows(d_valid, td, fill=False)
    d2, idx = _nn.nn_sq_dists(qp, dp, dv, n_coords=n_coords, tq=tq, td=td,
                              interpret=_interpret())
    d2 = d2[: q.shape[0]]
    idx = idx[: q.shape[0]]
    dist = jnp.sqrt(jnp.minimum(d2, BIG))
    dist = jnp.where(q_valid, dist, 0.0)
    idx = jnp.where(q_valid, idx, -1)
    return dist, idx


def directed_hausdorff_grid(
    q: Array, ds: Array, q_valid: Array, ds_valid: Array, *,
    tile: int | None = None, tq: int | None = None, td: int | None = None,
    use_kernel: bool | None = None,
) -> Array:
    """H(Q_b -> D_{b,j}) over a (B, C) query x candidate-chunk grid.

    q (B, nq, d) queries against ds (B, C, nd, d) per-query candidate
    stacks -> (B, C).  The hot path of batched ExactHaus phase 2: one
    fused evaluation for every (query, chunk-slot) pair in the shared
    work frontier.

    Kernel-sized shapes route to ONE Pallas pair-grid launch
    (`hausdorff.min_sq_dists_grid`: grid = (B*C, Q-tiles, D-tiles)),
    bitwise equal per pair to the per-pair streaming kernel and to the
    jitted per-pair op.  Below the thresholds the D point axis is
    streamed in ``tile``-wide slabs with a running minimum (non-multiple
    nd is padded with invalid columns), so the intermediate is
    (B, C, nq, tile) instead of the full (B, C, nq, nd) matrix.  Bitwise
    equal to `ref.directed_hausdorff` per pair on both paths: the
    per-entry arithmetic is `ref.unrolled_sq_dists` on each slab/tile,
    and fp min/max are exactly associative, so the reassociation changes
    no bits (asserted by the ExactHaus bit-identity suites).
    """
    cfg = autotune.resolve("hausdorff_grid", (q.shape[1], ds.shape[2]),
                           tq=tq, td=td, tile=tile, use_kernel=use_kernel)
    return _directed_hausdorff_grid(q, ds, q_valid, ds_valid, tile=cfg.tile,
                                    tq=cfg.tq, td=cfg.td,
                                    use_kernel=cfg.use_kernel)


@functools.partial(jax.jit,
                   static_argnames=("tile", "tq", "td", "use_kernel"))
def _directed_hausdorff_grid(
    q: Array, ds: Array, q_valid: Array, ds_valid: Array, *,
    tile: int, tq: int, td: int, use_kernel: bool,
) -> Array:
    B, C, nd, n_coords = ds.shape
    nq = q.shape[1]

    if use_kernel:
        width = max(8, n_coords)
        qp = _pad_coords(q, width)
        qp = jnp.pad(qp, ((0, 0), (0, -nq % tq), (0, 0)))
        dp = _pad_coords(ds, width)
        dp = jnp.pad(dp, ((0, 0), (0, 0), (0, -nd % td), (0, 0)))
        dv = jnp.pad(ds_valid, ((0, 0), (0, 0), (0, -nd % td)))
        mins = _haus.min_sq_dists_grid(qp, dp, dv, n_coords=n_coords,
                                       tq=tq, td=td,
                                       interpret=_interpret())[:, :, :nq]
        mins = jnp.minimum(mins, ref.BIG)
    else:
        if nd % tile:
            if nd < tile:
                tile = nd
            else:
                # pad to a tile multiple with invalid columns (masked to
                # BIG inside the slab, so the running min is unchanged)
                # rather than abandoning streaming for the full matrix
                ds = jnp.pad(ds, ((0, 0), (0, 0), (0, -nd % tile), (0, 0)))
                ds_valid = jnp.pad(ds_valid,
                                   ((0, 0), (0, 0), (0, -nd % tile)))
                nd = ds.shape[2]
        n_tiles = nd // tile

        def slab_mins(dp, dv):
            # (B, C, nq, tile) masked squared distances -> (B, C, nq) mins
            d2 = ref.unrolled_sq_dists(q[:, None, :, None, :],
                                       dp[:, :, None, :, :])
            d2 = jnp.where(dv[:, :, None, :], d2, ref.BIG)
            return jnp.min(d2, axis=-1)

        if n_tiles == 1:
            mins = slab_mins(ds, ds_valid)
        else:
            def body(t, acc):
                dp = jax.lax.dynamic_slice_in_dim(ds, t * tile, tile,
                                                  axis=2)
                dv = jax.lax.dynamic_slice_in_dim(ds_valid, t * tile, tile,
                                                  axis=2)
                return jnp.minimum(acc, slab_mins(dp, dv))

            mins = jax.lax.fori_loop(
                0, n_tiles, body,
                jnp.full((B, C, nq), ref.BIG, jnp.float32))
    nnd = jnp.sqrt(mins)
    nnd = jnp.where(q_valid[:, None, :], nnd, -ref.BIG)
    return jnp.max(nnd, axis=-1)


def nn_distance_batched(
    qs: Array, ds: Array, qs_valid: Array, ds_valid: Array,
    *, tq: int | None = None, td: int | None = None,
    use_kernel: bool | None = None,
):
    """Per-point NN for B (query, dataset) pairs: (B, nq) dists + ids."""
    cfg = autotune.resolve("nn_distance", (qs.shape[1], ds.shape[1]),
                           tq=tq, td=td, use_kernel=use_kernel)
    return _nn_distance_batched(qs, ds, qs_valid, ds_valid, tq=cfg.tq,
                                td=cfg.td, use_kernel=cfg.use_kernel)


@functools.partial(jax.jit, static_argnames=("tq", "td", "use_kernel"))
def _nn_distance_batched(
    qs: Array, ds: Array, qs_valid: Array, ds_valid: Array,
    *, tq: int, td: int, use_kernel: bool,
):
    return jax.vmap(
        lambda q, d, qv, dv: _nn_distance(q, d, qv, dv, tq=tq, td=td,
                                          use_kernel=use_kernel)
    )(qs, ds, qs_valid, ds_valid)


def bound_matrices(
    oq: Array, rq: Array, od: Array, rd: Array,
    *, tn: int | None = None, tm: int | None = None,
    use_kernel: bool | None = None,
):
    """Eq. 4 (lb, ub) matrices over two node frontiers."""
    cfg = autotune.resolve("bound_matrices", (oq.shape[0], od.shape[0]),
                           tq=tn, td=tm, use_kernel=use_kernel)
    return _bound_matrices(oq, rq, od, rd, tn=cfg.tq, tm=cfg.td,
                           use_kernel=cfg.use_kernel)


@functools.partial(jax.jit, static_argnames=("tn", "tm", "use_kernel"))
def _bound_matrices(
    oq: Array, rq: Array, od: Array, rd: Array,
    *, tn: int, tm: int, use_kernel: bool,
):
    if not use_kernel:
        return ref.bound_matrix(oq, rq, od, rd)
    n_coords = oq.shape[-1]
    width = max(8, n_coords)
    nq, nd = oq.shape[0], od.shape[0]
    oqp = _pad_rows(_pad_coords(oq, width), tn)
    odp = _pad_rows(_pad_coords(od, width), tm)
    rqp = _pad_rows(rq, tn)
    rdp = _pad_rows(rd, tm)
    lb, ub = _bm.bound_matrices(oqp, rqp, odp, rdp, n_coords=n_coords,
                                tn=tn, tm=tm, interpret=_interpret())
    return lb[:nq, :nd], ub[:nq, :nd]


def bound_grid(
    oq: Array, rq: Array, q_ok: Array, od: Array, rd: Array, d_ok: Array,
    *, levels: tuple, tb: int | None = None, ts: int | None = None,
    use_kernel: bool | None = None,
):
    """Fused multi-level (B, S) frontier bounds — Eq. 4 plus the min/max
    frontier collapse for EVERY tree level in one op.

    oq (B, N, dim) / rq, q_ok (B, N): batched query-tree node
    centers/radii/occupancy over the contiguous node range [0, N);
    od (S, N, dim) / rd, d_ok (S, N): the corpus trees.  ``levels`` is a
    static tuple of per-level (start, stop) node slices.  Returns
    (LB, UB), each (len(levels), B, S) — LB[l, b, s] is exactly the
    scalar `frontier_bounds` reduces level l of pair (b, s) to.

    Kernel-sized batches route to ONE Pallas launch over (B-tiles,
    S-tiles) computing all levels per tile (`bound_matrix.bound_grid`);
    otherwise the fused jnp oracle `ref.frontier_bound_levels` runs.
    Routing stability: every ExactHaus path (host oracle, local batched,
    sharded) calls THIS op at the same shapes, so they route together and
    stay mutually bit-identical (asserted by the equivalence suites);
    kernel-vs-ref bitwise equality is additionally asserted at verified
    shapes and gated per shape bucket by the engine tuner.
    """
    cfg = autotune.resolve("bound_grid", (oq.shape[0], od.shape[0]),
                           tq=tb, td=ts, use_kernel=use_kernel)
    return _bound_grid(oq, rq, q_ok, od, rd, d_ok, levels=tuple(levels),
                       tb=cfg.tq, ts=cfg.td, use_kernel=cfg.use_kernel)


@functools.partial(jax.jit,
                   static_argnames=("levels", "tb", "ts", "use_kernel"))
def _bound_grid(
    oq: Array, rq: Array, q_ok: Array, od: Array, rd: Array, d_ok: Array,
    *, levels: tuple, tb: int, ts: int, use_kernel: bool,
):
    if not use_kernel:
        return ref.frontier_bound_levels(oq, rq, q_ok, od, rd, d_ok, levels)
    n_coords = oq.shape[-1]
    width = max(8, n_coords)
    B, S = oq.shape[0], od.shape[0]
    oqp = _pad_rows(_pad_coords(oq, width), tb)
    rqp = _pad_rows(rq, tb)
    qop = _pad_rows(q_ok, tb, fill=False)
    odp = _pad_rows(_pad_coords(od, width), ts)
    rdp = _pad_rows(rd, ts)
    dop = _pad_rows(d_ok, ts, fill=False)
    lb, ub = _bm.bound_grid(oqp, rqp, qop, odp, rdp, dop,
                            levels=levels, n_coords=n_coords, tb=tb, ts=ts,
                            interpret=_interpret())
    return lb[:, :B, :S], ub[:, :B, :S]


def set_intersect_counts(
    sa: Array, sb: Array, *, ta: int | None = None, tb: int | None = None,
    use_kernel: bool | None = None,
) -> Array:
    """GBO count matrix between signature stacks (na, W) x (nb, W)."""
    cfg = autotune.resolve("set_intersect", (sa.shape[0], sb.shape[0]),
                           tq=ta, td=tb, use_kernel=use_kernel)
    return _set_intersect_counts(sa, sb, ta=cfg.tq, tb=cfg.td,
                                 use_kernel=cfg.use_kernel)


@functools.partial(jax.jit, static_argnames=("ta", "tb", "use_kernel"))
def _set_intersect_counts(
    sa: Array, sb: Array, *, ta: int, tb: int, use_kernel: bool,
) -> Array:
    if not use_kernel:
        return ref.set_intersect_count(sa, sb)
    na, nb = sa.shape[0], sb.shape[0]
    sap = _pad_rows(sa, ta)
    sbp = _pad_rows(sb, tb)
    out = _si.intersect_counts(sap, sbp, ta=ta, tb=tb, interpret=_interpret())
    return out[:na, :nb]


def plane_weighted_intersect(
    planes: Array, sigs: Array, *, ta: int | None = None,
    tb: int | None = None, use_kernel: bool | None = None,
) -> Array:
    """Weighted popcount matrix for histogram bit planes: given per-row
    count histograms sliced into bit planes (B, P, W) and signatures
    (S, W), returns (B, S) int32 of sum_p 2^p * |plane_p AND sig| — i.e.
    the joinable *coverage* form (points-in-occupied-cells) expressed so
    the whole batch rides ONE (B*P, S) set-intersect dispatch through the
    same autotune routing as GBO."""
    b, p, w = planes.shape
    cnt = set_intersect_counts(planes.reshape(b * p, w), sigs,
                               ta=ta, tb=tb, use_kernel=use_kernel)
    cnt = cnt.reshape(b, p, sigs.shape[0])
    weights = jnp.left_shift(jnp.int32(1), jnp.arange(p, dtype=jnp.int32))
    return jnp.sum(cnt * weights[None, :, None], axis=1, dtype=jnp.int32)
