"""Pallas TPU kernel for the directed Hausdorff hot spot (paper Sec. VI-A.2).

Scheme (DESIGN.md sec. 6): flash-attention-style streaming reduction.
The grid is (Q-tiles, D-tiles); for each Q tile we keep a running per-row
nearest-neighbor distance in the output block (VMEM-resident across the
D-tile sweep, because the output BlockSpec maps every j to the same block).
The |Q| x |D| distance matrix only ever exists one (TQ, TD) tile at a time
in VMEM/VREGs — it is never materialized in HBM.

Layout: points are (n, COORD_PAD) with the coordinate dim padded to a small
static width; the squared distance uses the broadcast-subtract form, unrolled
over coordinates (exact, no |x|^2-2xy cancellation), which is VPU-friendly
since the (TQ, TD) tile is the vectorized shape.

The final max over Q rows happens in the jit wrapper (ops.py) — it is O(nq)
and fuses into the surrounding graph.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

BIG = 3.4e38  # python float: baked into the kernel, not a captured const

# default tile sizes: (TQ, TD) fp32 tile = 256*512*4B = 512 KiB << 16 MiB VMEM
TQ = 256
TD = 512
COORD_PAD = 8


def tile_sq_dists(q, d, n_coords: int):
    """(TQ, TD) squared distances of one tile pair in
    ref.unrolled_sq_dists' exact accumulation (first square, then adds in
    coordinate order — no zero init), so the tile arithmetic compiles to
    the identical contraction as the jnp oracle and routing never changes
    bits."""
    acc = None
    for c in range(n_coords):  # static unroll over true coord count
        diff = q[:, c][:, None] - d[:, c][None, :]
        sq = diff * diff
        acc = sq if acc is None else acc + sq
    return acc


def _min_dist_kernel(q_ref, d_ref, dvalid_ref, o_ref, *, n_coords: int):
    """One (Q-tile, D-tile) step: update running per-Q-row min distance.

    q_ref      (TQ, COORD_PAD) f32 : Q tile
    d_ref      (TD, COORD_PAD) f32 : D tile
    dvalid_ref (1, TD)         bool: D slot validity
    o_ref      (TQ, 1)         f32 : running min of SQUARED distances

    Validity and the output are 2-D blocks (a lane row and a sublane
    column): Mosaic refuses 1-D blocks whose tiling differs from XLA's.
    """
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        o_ref[...] = jnp.full(o_ref.shape, BIG, jnp.float32)

    acc = tile_sq_dists(q_ref[...], d_ref[...], n_coords)
    acc = jnp.where(dvalid_ref[...], acc, BIG)
    o_ref[...] = jnp.minimum(o_ref[...], jnp.min(acc, axis=1, keepdims=True))


def min_sq_dists(
    q: jax.Array,
    d: jax.Array,
    d_valid: jax.Array,
    *,
    n_coords: int,
    tq: int = TQ,
    td: int = TD,
    interpret: bool = False,
) -> jax.Array:
    """Per-Q-row min squared distance to any valid D row.

    q (nq, COORD_PAD), d (nd, COORD_PAD), d_valid (nd,) -> (nq,) f32.
    nq % tq == 0 and nd % td == 0 (ops.py pads).
    """
    nq = q.shape[0]
    nd = d.shape[0]
    grid = (nq // tq, nd // td)
    kernel = functools.partial(_min_dist_kernel, n_coords=n_coords)
    out = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((tq, q.shape[1]), lambda i, j: (i, 0)),
            pl.BlockSpec((td, d.shape[1]), lambda i, j: (j, 0)),
            pl.BlockSpec((1, td), lambda i, j: (0, j)),
        ],
        out_specs=pl.BlockSpec((tq, 1), lambda i, j: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((nq, 1), jnp.float32),
        interpret=interpret,
    )(q, d, d_valid.reshape(1, nd))
    return out[:, 0]


def _min_dist_grid_kernel(q_ref, d_ref, dvalid_ref, o_ref, *, n_coords: int):
    """One (pair, Q-tile, D-tile) step of the (B, C) pair-grid evaluator.

    q_ref      (1, TQ, W)  f32 : Q tile of pair bc = b * C + c
    d_ref      (1, TD, W)  f32 : D tile of that pair
    dvalid_ref (1, 1, TD)  bool
    o_ref      (1, TQ, 1)  f32 : running per-Q-row min SQUARED distance

    Same flash-attention-style running reduction as `_min_dist_kernel`,
    but the pair index is a grid axis — the whole (B, C) frontier is ONE
    kernel launch instead of a vmap of per-pair launches.  The D-tile
    axis is the fastest grid dimension, so the output block persists in
    VMEM across the k sweep and is initialized at k == 0.
    """
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _init():
        o_ref[...] = jnp.full(o_ref.shape, BIG, jnp.float32)

    acc = tile_sq_dists(q_ref[0], d_ref[0], n_coords)
    acc = jnp.where(dvalid_ref[0], acc, BIG)
    o_ref[0] = jnp.minimum(o_ref[0], jnp.min(acc, axis=1, keepdims=True))


def min_sq_dists_grid(
    q: jax.Array,
    ds: jax.Array,
    ds_valid: jax.Array,
    *,
    n_coords: int,
    tq: int = TQ,
    td: int = TD,
    interpret: bool = False,
) -> jax.Array:
    """Per-Q-row min squared distance for every (query, chunk-slot) pair.

    q (B, nq, W), ds (B, C, nd, W), ds_valid (B, C, nd) -> (B, C, nq) f32.
    nq % tq == 0 and nd % td == 0 (ops.py pads).  One grid over
    (B*C pairs, Q tiles, D tiles); bitwise equal to running
    `min_sq_dists` per pair (identical tile arithmetic, exact min
    reassociation).  The pair axes are flattened so every block's last
    two dims are a full dim or an (8, 128)-aligned tile.
    """
    B, C, nd, W = ds.shape
    nq = q.shape[1]
    grid = (B * C, nq // tq, nd // td)
    kernel = functools.partial(_min_dist_grid_kernel, n_coords=n_coords)
    out = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, tq, q.shape[-1]),
                         lambda bc, i, k: (bc // C, i, 0)),
            pl.BlockSpec((1, td, W), lambda bc, i, k: (bc, k, 0)),
            pl.BlockSpec((1, 1, td), lambda bc, i, k: (bc, 0, k)),
        ],
        out_specs=pl.BlockSpec((1, tq, 1), lambda bc, i, k: (bc, i, 0)),
        out_shape=jax.ShapeDtypeStruct((B * C, nq, 1), jnp.float32),
        interpret=interpret,
    )(q, ds.reshape(B * C, nd, W), ds_valid.reshape(B * C, 1, nd))
    return out.reshape(B, C, nq)
