"""Pallas kernel for the paper's fast bound estimation (Eq. 4).

Computes the (LB, UB) Hausdorff bound matrices between two node frontiers
from ONE center-distance evaluation per node pair — the paper's O(1)-bound
insight is what turns the whole frontier into a single dense tile sweep
(DESIGN.md sec. 2).  Tiles are (TN, TM); both outputs share the sweep.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels.hausdorff import tile_sq_dists

BIG = 3.4e38  # python float: baked into the kernel, not a captured const

TN = 256
TM = 256

# fused (B, S) bound grid: query-batch x corpus-slot tiles
TB = 8
TS = 128
# node-range reductions of up to this many steps are unrolled in the
# bound-grid kernel; longer ones loop
UNROLL = 16


def _bound_kernel(oq_ref, rq_ref, od_ref, rd_ref, lb_ref, ub_ref, *, n_coords: int):
    """One (TN, TM) tile.  rq_ref is a (TN, 1) sublane column and rd_ref a
    (1, TM) lane row: Mosaic refuses 1-D blocks whose tiling differs from
    XLA's."""
    # ref.unrolled_sq_dists' exact accumulation (first square, then adds
    # in coordinate order) so the tile stays bitwise equal to the oracle
    acc = tile_sq_dists(oq_ref[...], od_ref[...], n_coords)
    cd = jnp.sqrt(acc)
    rd = rd_ref[...]
    # square rd at its own (1, TM) shape BEFORE broadcasting, exactly like
    # ref.bound_matrix's (rd * rd)[None, :] — fusing the square into the
    # broadcast add invites an FMA contraction the oracle doesn't have
    rd2 = rd * rd
    lb_ref[...] = jnp.maximum(cd - rd, 0.0)
    ub_ref[...] = jnp.sqrt(acc + rd2) + rq_ref[...]


def bound_matrices(
    oq: jax.Array,
    rq: jax.Array,
    od: jax.Array,
    rd: jax.Array,
    *,
    n_coords: int,
    tn: int = TN,
    tm: int = TM,
    interpret: bool = False,
):
    """Eq. 4 (lb, ub) matrices, each (nq, nd) f32.  Shapes pre-padded."""
    nq = oq.shape[0]
    nd = od.shape[0]
    grid = (nq // tn, nd // tm)
    kernel = functools.partial(_bound_kernel, n_coords=n_coords)
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((tn, oq.shape[1]), lambda i, j: (i, 0)),
            pl.BlockSpec((tn, 1), lambda i, j: (i, 0)),
            pl.BlockSpec((tm, od.shape[1]), lambda i, j: (j, 0)),
            pl.BlockSpec((1, tm), lambda i, j: (0, j)),
        ],
        out_specs=[
            pl.BlockSpec((tn, tm), lambda i, j: (i, j)),
            pl.BlockSpec((tn, tm), lambda i, j: (i, j)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((nq, nd), jnp.float32),
            jax.ShapeDtypeStruct((nq, nd), jnp.float32),
        ],
        interpret=interpret,
    )(oq, rq.reshape(nq, 1), od, rd.reshape(1, nd))


def _fold(a: int, b: int, fn, combine):
    """combine(...combine(fn(a), fn(a + 1))..., fn(b - 1)) over node ids
    [a, b), as one ``fori_loop``: fully unrolled up to UNROLL steps (Mosaic
    takes only a full unroll or none).  Starting from fn(a) (no identity
    element) keeps the reduction bitwise equal to a jnp.min / jnp.max over
    the range."""
    return jax.lax.fori_loop(a + 1, b, lambda i, acc: combine(acc, fn(i)),
                             fn(a), unroll=b - a - 1 <= UNROLL)


def _bound_grid_kernel(oq_ref, rq_ref, qok_ref, od_ref, rd_ref, dok_ref,
                       lb_ref, ub_ref, *, levels: tuple, n_coords: int):
    """One (query-tile, slot-tile) step of the fused multi-level bound
    reduction: every tree level's (LB, UB) frontier values.

    oq_ref (N, TB, W) / rq_ref, qok_ref (N, TB, 1): query-tree tile,
        node-major, so node i is a (TB, W) block and its radius and
        occupancy (TB, 1) sublane columns;
    od_ref (N, W, TS) / rd_ref, dok_ref (N, 1, TS): corpus tile, node-
        and coordinate-major, so each coordinate of node j is a (1, TS)
        lane row;
    lb_ref, ub_ref (L, TB, TS): per-level reduced bounds for this tile.

    Every (query node i, corpus node j) pair of a level is one dense
    (TB, TS) slab — query rows on sublanes, corpus slots on lanes — so
    nothing is padded out to (8, 128) tiles of the tiny node axes and the
    step fits scoped VMEM at any node count.  Only pairs inside the same
    level slice [a, b) are evaluated: those are the only ones a level
    reduces.  Per-element arithmetic matches `ref.frontier_bound_levels`
    exactly (coordinate-unrolled squares, same add order, rd squared at
    its own shape), and fp min/max are exactly associative — kernel-vs-ref
    bitwise equality holds wherever XLA makes the same FMA-contraction
    choice for the two program shapes (shape-dependent on CPU; tests
    assert it at verified shapes and the engine tuner gates kernel
    routing on it per shape bucket).
    """
    def pair(i, j):
        q = oq_ref[i]                                  # (TB, W)
        d = od_ref[j]                                  # (W, TS)
        acc = None
        for c in range(n_coords):
            diff = q[:, c:c + 1] - d[c:c + 1, :]
            sq = diff * diff
            acc = sq if acc is None else acc + sq
        rd = rd_ref[j]                                 # (1, TS)
        # square rd at its own (1, TS) shape before broadcasting, exactly
        # like ref.frontier_bound_levels (see _bound_kernel for why)
        rd2 = rd * rd
        lb = jnp.maximum(jnp.sqrt(acc) - rd, 0.0)
        ub = jnp.sqrt(acc + rd2) + rq_ref[i]
        dok = dok_ref[j]
        return jnp.where(dok, lb, BIG), jnp.where(dok, ub, BIG)

    def tmin(x, y):
        return jnp.minimum(x[0], y[0]), jnp.minimum(x[1], y[1])

    def tmax(x, y):
        return jnp.maximum(x[0], y[0]), jnp.maximum(x[1], y[1])

    for l, (a, b) in enumerate(levels):
        def row(i, a=a, b=b):
            row_lb, row_ub = _fold(a, b, lambda j: pair(i, j), tmin)
            ok = qok_ref[i]                            # (TB, 1)
            return (jnp.where(ok, row_lb, -BIG),
                    jnp.where(ok, row_ub, -BIG))

        lb_ref[l], ub_ref[l] = _fold(a, b, row, tmax)


def bound_grid(
    oq: jax.Array,
    rq: jax.Array,
    q_ok: jax.Array,
    od: jax.Array,
    rd: jax.Array,
    d_ok: jax.Array,
    *,
    levels: tuple,
    n_coords: int,
    tb: int = TB,
    ts: int = TS,
    interpret: bool = False,
):
    """Fused multi-level (B, S) frontier bounds: the kernel counterpart of
    `ref.frontier_bound_levels`.

    oq (B, N, W) / rq, q_ok (B, N) x od (S, N, W) / rd, d_ok (S, N) ->
    (LB, UB) each (len(levels), B, S) f32.  B % tb == 0 and S % ts == 0
    (ops.py pads; padded rows carry q_ok/d_ok = False).  The operands are
    transposed node-major here (see `_bound_grid_kernel` for the layout).
    """
    B, N = rq.shape
    S = rd.shape[0]
    W = oq.shape[-1]
    L = len(levels)
    grid = (B // tb, S // ts)
    kernel = functools.partial(_bound_grid_kernel, levels=tuple(levels),
                               n_coords=n_coords)
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((N, tb, W), lambda i, j: (0, i, 0)),
            pl.BlockSpec((N, tb, 1), lambda i, j: (0, i, 0)),
            pl.BlockSpec((N, tb, 1), lambda i, j: (0, i, 0)),
            pl.BlockSpec((N, W, ts), lambda i, j: (0, 0, j)),
            pl.BlockSpec((N, 1, ts), lambda i, j: (0, 0, j)),
            pl.BlockSpec((N, 1, ts), lambda i, j: (0, 0, j)),
        ],
        out_specs=[
            pl.BlockSpec((L, tb, ts), lambda i, j: (0, i, j)),
            pl.BlockSpec((L, tb, ts), lambda i, j: (0, i, j)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((L, B, S), jnp.float32),
            jax.ShapeDtypeStruct((L, B, S), jnp.float32),
        ],
        interpret=interpret,
    )(jnp.transpose(oq, (1, 0, 2)), rq.T[:, :, None], q_ok.T[:, :, None],
      jnp.transpose(od, (1, 2, 0)), rd.T[:, None, :], d_ok.T[:, None, :])
