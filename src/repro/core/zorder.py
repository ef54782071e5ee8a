"""z-order signatures (Defs. 4/5/7) as fixed-width bitsets.

TPU adaptation (DESIGN.md sec. 2): the paper stores a sorted variable-length
integer set per dataset; we store a fixed-width bitset over the 4^theta grid
cells so that
  * GBO (Def. 7)  = popcount(AND)            (one VPU op per word)
  * node signature union (Def. 16) = OR
Both are static-shape and vectorize over the whole repository.

Cell ids use the standard Morton interleave of the two leading spatial
coordinates quantized to 2^theta bins each, exactly as Def. 4 prescribes.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

Array = jax.Array

WORD_BITS = 32


def num_cells(theta: int) -> int:
    return 1 << (2 * theta)


def num_words(theta: int) -> int:
    return max(1, num_cells(theta) // WORD_BITS)


def _part1by1(x: Array) -> Array:
    """Spread the low 16 bits of x so there is a 0 between each bit."""
    x = x.astype(jnp.uint32) & jnp.uint32(0x0000FFFF)
    x = (x | (x << 8)) & jnp.uint32(0x00FF00FF)
    x = (x | (x << 4)) & jnp.uint32(0x0F0F0F0F)
    x = (x | (x << 2)) & jnp.uint32(0x33333333)
    x = (x | (x << 1)) & jnp.uint32(0x55555555)
    return x


def morton2(ix: Array, iy: Array) -> Array:
    """Interleave two <=16-bit integer grids into a Morton code (uint32)."""
    return _part1by1(ix) | (_part1by1(iy) << 1)


def quantize(points: Array, lo: Array, hi: Array, theta: int) -> Array:
    """Map points (..., d>=2) into integer grid coords on [lo, hi] (2,)."""
    span = jnp.maximum(hi - lo, 1e-30)
    nbins = (1 << theta) - 1
    g = (points[..., :2] - lo) / span * (nbins + 1)
    g = jnp.clip(g.astype(jnp.int32), 0, nbins)
    return g


def cell_ids(points: Array, lo: Array, hi: Array, theta: int) -> Array:
    """Morton cell id per point (Def. 4), in [0, 4^theta)."""
    g = quantize(points, lo, hi, theta)
    return morton2(g[..., 0], g[..., 1]).astype(jnp.int32)


def signature(points: Array, valid: Array, lo: Array, hi: Array, theta: int) -> Array:
    """z-order signature (Def. 5) as a (W,) uint32 bitset.

    points: (n, d), valid: (n,) bool.  Invalid points contribute nothing.
    """
    n_cells = num_cells(theta)
    ids = cell_ids(points, lo, hi, theta)
    ids = jnp.where(valid, ids, n_cells)  # park invalid in an overflow cell
    occ = jnp.zeros((n_cells + 1,), jnp.uint32).at[ids].max(jnp.uint32(1))
    occ = occ[:n_cells]
    w = num_words(theta)
    occ = occ.reshape(w, WORD_BITS)
    shifts = jnp.arange(WORD_BITS, dtype=jnp.uint32)
    return jnp.bitwise_or.reduce(occ << shifts, axis=1)


def sig_union(a: Array, b: Array) -> Array:
    return a | b


def sig_intersect_count(a: Array, b: Array) -> Array:
    """GBO (Def. 7): |z(A) AND z(B)| via popcount.  Broadcasts over leading
    dims; reduces the trailing word axis."""
    return jax.lax.population_count(a & b).astype(jnp.int32).sum(axis=-1)


def sig_count(a: Array) -> Array:
    return jax.lax.population_count(a).astype(jnp.int32).sum(axis=-1)


def default_epsilon(lo: Array, hi: Array, theta: int) -> Array:
    """Paper Eq. 8: cell width of the x-extent at resolution theta."""
    return (hi[0] - lo[0]) / (1 << theta)
