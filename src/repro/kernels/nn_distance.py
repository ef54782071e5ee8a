"""Pallas kernel for NNP (paper Sec. VI-B.2): per-query-point nearest
neighbor distance AND index over a streamed point set.

Same streaming scheme as hausdorff.py with a second output carrying the
running argmin (global D row index, built from the tile offset + iota).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels.hausdorff import tile_sq_dists

BIG = 3.4e38  # python float: baked into the kernel, not a captured const

TQ = 256
TD = 512


def _nn_kernel(q_ref, d_ref, dvalid_ref, dist_ref, idx_ref, *, n_coords: int, td: int):
    """One (Q-tile, D-tile) step: running per-Q-row min SQUARED distance
    and its global D row.  Validity is a (1, TD) lane row and both outputs
    are (TQ, 1) sublane columns, as in `hausdorff._min_dist_kernel`."""
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        dist_ref[...] = jnp.full(dist_ref.shape, BIG, jnp.float32)
        idx_ref[...] = jnp.full(idx_ref.shape, -1, jnp.int32)

    # ref.unrolled_sq_dists' exact accumulation (see hausdorff.py) so the
    # kernel stays bitwise equal to the ref oracle across routing changes
    acc = tile_sq_dists(q_ref[...], d_ref[...], n_coords)
    acc = jnp.where(dvalid_ref[...], acc, BIG)
    tile_min = jnp.min(acc, axis=1, keepdims=True)
    tile_arg = (jnp.argmin(acc, axis=1, keepdims=True).astype(jnp.int32)
                + j * td)
    better = tile_min < dist_ref[...]
    dist_ref[...] = jnp.where(better, tile_min, dist_ref[...])
    idx_ref[...] = jnp.where(better, tile_arg, idx_ref[...])


def nn_sq_dists(
    q: jax.Array,
    d: jax.Array,
    d_valid: jax.Array,
    *,
    n_coords: int,
    tq: int = TQ,
    td: int = TD,
    interpret: bool = False,
):
    """(nq,) min squared distance + (nq,) argmin D row index."""
    nq = q.shape[0]
    nd = d.shape[0]
    grid = (nq // tq, nd // td)
    kernel = functools.partial(_nn_kernel, n_coords=n_coords, td=td)
    dist, idx = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((tq, q.shape[1]), lambda i, j: (i, 0)),
            pl.BlockSpec((td, d.shape[1]), lambda i, j: (j, 0)),
            pl.BlockSpec((1, td), lambda i, j: (0, j)),
        ],
        out_specs=[
            pl.BlockSpec((tq, 1), lambda i, j: (i, 0)),
            pl.BlockSpec((tq, 1), lambda i, j: (i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((nq, 1), jnp.float32),
            jax.ShapeDtypeStruct((nq, 1), jnp.int32),
        ],
        interpret=interpret,
    )(q, d, d_valid.reshape(1, nd))
    return dist[:, 0], idx[:, 0]
