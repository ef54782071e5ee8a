"""Plain reference of the search semantics, and the comparison that
decides ``correct``.

It imports nothing of the program.  From the raw lake it builds what the
paper's definitions ask for: each dataset's balanced tree order (split on
the widest axis at the median, level by level), the parameter-free outlier
removal over the pooled leaf radii (Eq. 3), the cleaned datasets' MBRs,
grid cells and signatures.  Every answer is then found by brute force over
all datasets: intersecting areas, signature overlaps, directed Hausdorff
distances H(Q -> D), grid overlap and coverage, points in a box, nearest
neighbours.  Arithmetic runs through ``jax.numpy`` on the default device
in ``dtype``: float32 is the reference; bfloat16 is the control, the
precision below the configuration's, which the comparison must refuse.

Program answers arrive in the program's own layout (point masks and ids
in tree order); :func:`semantic` maps them to original point indices with
the reference's own tree order, so a control answer and a program answer
are judged by the same code (:func:`compare`).

The numbers compared, each with a limit in the configuration file:

* ``value_gap``: widest relative gap of a float answer (IA areas,
  Hausdorff distances, NN distances) from the reference, both of the value
  returned and of the reference's value of the returned id against the
  reference's value at that rank, so an exact tie may resolve either way;
* ``count_gap``: the same for integer answers (GBO, overlap, coverage and
  the re-rank), as an absolute count;
* ``mask_diff``: entries of a dataset or point mask that differ, plus NN
  ids that name no point of the cleaned dataset;
* ``approx_ratio``: ApproHaus error over its guarantee (Lemma 1): the
  value within ``r_q + r_d`` of the exact distance and each rank within
  twice that, where the radii are at most max(eps, largest leaf radius);
* ``missing``: requests that never answered or raised.
"""
from __future__ import annotations

import math
from dataclasses import dataclass
from functools import partial

import numpy as np

#: a malformed answer (duplicate or out-of-range ids) reads as this gap
BAD = 1.0
BAD_COUNT = 1 << 20


def _jnp():
    import jax
    import jax.numpy as jnp
    return jax, jnp


def depth_for(n: int, leaf: int) -> int:
    return max(0, math.ceil(math.log2(max(1, n) / leaf)))


# -- tree order and outlier removal ------------------------------------------


def _tree_perm_block(points, valid, depth: int):
    """(B, n_pad) tree order of a block of padded datasets."""
    jax, jnp = _jnp()
    big = jnp.array(jnp.inf, points.dtype)

    def one(p0, v0):
        n_pad, d = p0.shape
        perm = jnp.argsort(~v0, stable=True)
        for level in range(depth):
            seg = n_pad >> level
            p = p0[perm].reshape(1 << level, seg, d)
            v = v0[perm].reshape(1 << level, seg)
            lo = jnp.min(jnp.where(v[..., None], p, big), axis=1)
            hi = jnp.max(jnp.where(v[..., None], p, -big), axis=1)
            width = jnp.where(jnp.isfinite(lo) & jnp.isfinite(hi), hi - lo,
                              -big)
            axis = jnp.argmax(width, axis=-1)
            keys = jnp.take_along_axis(p, axis[:, None, None], axis=-1)[..., 0]
            keys = jnp.where(v, keys, big)
            order = jnp.argsort(keys, axis=-1, stable=True)
            perm = jnp.take_along_axis(perm.reshape(1 << level, seg), order,
                                       axis=-1).reshape(-1)
        return perm

    return jax.vmap(one)(points, valid)


def _leaf_stats(points, valid, leaf: int):
    """Leaf radii and counts of tree-ordered datasets, and every point's
    distance to its leaf centre."""
    _, jnp = _jnp()
    b, n_pad, d = points.shape
    p = points.reshape(b, n_pad // leaf, leaf, d)
    v = valid.reshape(b, n_pad // leaf, leaf)
    w = v.astype(points.dtype)
    cnt = w.sum(axis=-1)
    o = (p * w[..., None]).sum(axis=2) / jnp.maximum(cnt, 1)[..., None]
    d2 = jnp.sum((p - o[:, :, None, :]) ** 2, axis=-1)
    r = jnp.sqrt(jnp.max(jnp.where(v, d2, 0), axis=-1))
    r = jnp.where(cnt == 0, 0, r)
    return r, cnt, jnp.sqrt(d2).reshape(b, n_pad)


def _order_block(points, valid, depth: int, leaf: int):
    """Tree order of a block, the tree-ordered points and validity, and
    their leaf statistics, in one program."""
    _, jnp = _jnp()
    perm = _tree_perm_block(points, valid, depth)
    p = jnp.take_along_axis(points, perm[..., None], axis=1)
    v = jnp.take_along_axis(valid, perm, axis=1)
    return (perm, p.astype(jnp.float32), v) + _leaf_stats(p, v, leaf)


def _knee_gaps(radii: np.ndarray):
    """Eq. 3's gap statistic over the pooled leaf radii of live leaves,
    sorted descending: (phi, gap)."""
    phi = -np.sort(-radii)
    m = max(len(phi), 2)
    dt = phi.dtype
    first, last = phi[0], phi[min(m - 1, len(phi) - 1)]
    i = np.arange(len(phi)).astype(dt)
    gap = first - i * (first - last) / dt.type(m) - phi
    gap[0] = -np.inf
    return phi, gap


def kneedle(radii: np.ndarray):
    """Eq. 3 over the pooled leaf radii of live leaves: the knee radius."""
    phi, gap = _knee_gaps(radii)
    pos = int(np.argmax(gap))
    return phi[max(pos - 1, 0)]


#: a knee whose gap lies within this many float32 spacings of the largest
#: gap is one Eq. 3 may pick: the gap's maximum is flat, and float32
#: rounding on another device can put any of these first
KNEE_ULPS = 32
#: a point whose distance to its leaf centre, or whose leaf radius, lies
#: within this many float32 spacings of the lake's largest coordinate of
#: an admissible knee may fall on either side of it on another device
JITTER_ULPS = 16
#: the datasets another threshold moves are recomputed in lakes of a
#: multiple of this many
SUB_PAD = 256


def knees(radii: np.ndarray) -> np.ndarray:
    """Every knee radius Eq. 3 may pick under float32 rounding: those
    whose gap is within ``KNEE_ULPS`` spacings of the largest."""
    phi, gap = _knee_gaps(radii)
    top = gap.max()
    pos = np.nonzero(gap >= top - KNEE_ULPS * np.spacing(top))[0]
    return np.unique(phi[np.maximum(pos - 1, 0)])


@dataclass
class TreeSet:
    """Datasets in tree order: ``perm[b, t]`` is the original index of
    the point at tree position t (positions past the length are padding)."""
    perm: np.ndarray      # (B, n_pad) int
    points: np.ndarray    # (B, n_pad, d) tree-ordered
    valid: np.ndarray     # (B, n_pad) live after outlier removal
    leaf_r: np.ndarray    # (B, n_leaves) leaf radii after removal


def ordered(points: np.ndarray, lengths: np.ndarray, leaf: int, dtype,
            block: int = 8192):
    """Tree order of every dataset: the order, the tree-ordered points and
    their validity, the leaf radii and counts, and every point's distance
    to its leaf centre."""
    _, jnp = _jnp()
    b = len(lengths)
    depth = depth_for(int(lengths.max()), leaf)
    n_pad = leaf << depth
    pts = np.zeros((b, n_pad, points.shape[-1]), np.float32)
    n = min(n_pad, points.shape[1])
    pts[:, :n] = points[:, :n]
    val = np.arange(n_pad)[None, :] < lengths[:, None]
    order = _jitted(_order_block, depth=depth, leaf=leaf)
    parts = [[np.asarray(x) for x in order(jnp.asarray(pts[s:s + block],
                                                       dtype),
                                           jnp.asarray(val[s:s + block]))]
             for s in range(0, b, block)]
    return tuple(np.concatenate(x) for x in zip(*parts))


def leaf_radii(points: np.ndarray, valid: np.ndarray, leaf: int, dtype,
               block: int = 8192) -> np.ndarray:
    """Leaf radii of tree-ordered datasets."""
    _, jnp = _jnp()
    stats = _jitted(_leaf_stats, leaf=leaf)
    return np.concatenate([
        np.asarray(stats(jnp.asarray(points[s:s + block], dtype),
                         jnp.asarray(valid[s:s + block]))[0])
        for s in range(0, len(points), block)]).astype(np.float32)


def critical(radii: np.ndarray, dist: np.ndarray, leaf: int) -> np.ndarray:
    """Per point, the largest outlier threshold that drops it: Eq. 3 drops
    a point of a leaf wider than r' that lies farther than r' from the
    leaf's centre, so a point is kept exactly when r' >= min(leaf radius,
    distance)."""
    return np.minimum(np.repeat(radii, leaf, axis=1), dist)


def tree_sets(points: np.ndarray, lengths: np.ndarray, leaf: int, dtype,
              block: int = 8192) -> TreeSet:
    """Tree order of every point set, with no outlier removal (the
    query's own tree)."""
    perm, tp, tv, radii, _, _ = ordered(points, lengths, leaf, dtype, block)
    return TreeSet(perm, tp, tv, radii.astype(np.float32))


_JITS = {}


def _jitted(fn, **static):
    """One jitted function per (fn, static arguments), reused by every
    call (a fresh ``jax.jit`` would trace again each time)."""
    jax, _ = _jnp()
    key = (fn, tuple(sorted(static.items())))
    if key not in _JITS:
        _JITS[key] = jax.jit(partial(fn, **static))
    return _JITS[key]


# -- the reference lake ------------------------------------------------------


def _cells(points, lo, hi, theta: int):
    """Morton cell id per point on the 2**theta grid over [lo, hi]."""
    _, jnp = _jnp()
    span = jnp.maximum(hi - lo, jnp.asarray(1e-30, hi.dtype))
    nb = 1 << theta
    g = ((points[..., :2] - lo) / span * nb).astype(jnp.int32)
    g = jnp.clip(g, 0, nb - 1)
    cell = jnp.zeros(g.shape[:-1], jnp.int32)
    for bit in range(theta):
        cell |= ((g[..., 0] >> bit) & 1) << (2 * bit)
        cell |= ((g[..., 1] >> bit) & 1) << (2 * bit + 1)
    return cell


def _occupancy(points, valid, lo, hi, theta: int):
    """(B, 4**theta + 1) bool: the grid cells each dataset occupies, and a
    last column that is never set (padding of a cell list points at it)."""
    _, jnp = _jnp()
    n_cells = 1 << (2 * theta)
    c = jnp.where(valid, _cells(points, lo, hi, theta), n_cells)
    rows = jnp.arange(points.shape[0])[:, None]
    occ = jnp.zeros((points.shape[0], n_cells + 1), bool)
    return occ.at[rows, c].set(True).at[:, n_cells].set(False)


def _count_cells(occ, cells):
    """Per dataset: how many of ``cells`` (padded with the empty column)
    it occupies."""
    _, jnp = _jnp()
    return jnp.sum(occ[:, cells], axis=1)


def _overlap_bits(occ, bits):
    """Per dataset: occupied cells that are also set in ``bits``."""
    _, jnp = _jnp()
    return jnp.sum(occ[:, :-1] & bits[None], axis=1)


def _ia(lo, hi, ql, qh):
    _, jnp = _jnp()
    side = jnp.maximum(jnp.minimum(hi, qh) - jnp.maximum(lo, ql), 0)
    return (side[:, 0] * side[:, 1]).astype(jnp.float32)


def _nn(q, qv, p, v):
    """NN of each query point among the dataset's live points: (dist,
    tree position)."""
    _, jnp = _jnp()
    d2 = jnp.sum((q[:, None, :] - p[None, :, :]) ** 2, axis=-1)
    d2 = jnp.where(v[None], d2, jnp.inf)
    return (jnp.sqrt(jnp.min(d2, axis=1)).astype(jnp.float32),
            jnp.argmin(d2, axis=1))


def _pad_rows(x, n: int, fill=0):
    x = np.asarray(x)
    out = np.full((n,) + x.shape[1:], fill, x.dtype)
    out[:len(x)] = x
    return out


#: query point sets are padded to this many points for the fixed-shape
#: reference programs (the mixes' query sets have at most 64)
Q_PAD = 64


def _hausdorff_block(q, qv, p, v):
    """Directed Hausdorff H(q -> each dataset of the block): (S,)."""
    _, jnp = _jnp()
    big = jnp.asarray(jnp.inf, p.dtype)
    d2 = ((q[:, None, None, 0] - p[None, :, :, 0]) ** 2
          + (q[:, None, None, 1] - p[None, :, :, 1]) ** 2)
    nn = jnp.min(jnp.where(v[None], d2, big), axis=-1)
    return jnp.sqrt(jnp.max(jnp.where(qv[:, None], nn, -big), axis=0))


class RefLake:
    """The reference's own view of a lake, at ``dtype``, cleaned at the
    knee :func:`kneedle` picks.  :meth:`versions` adds the lake as every
    other admissible knee leaves it."""

    def __init__(self, points: np.ndarray, lengths: np.ndarray,
                 config: dict, dtype=None):
        _, jnp = _jnp()
        dtype = jnp.float32 if dtype is None else dtype
        leaf = int(config["leaf_capacity"])
        lengths = np.asarray(lengths)
        perm, tp, raw, radii, counts, dist = ordered(points, lengths, leaf,
                                                     dtype)
        self.knee = kneedle(radii[counts > 0])
        self._knees = knees(radii[counts > 0])
        self._raw = raw
        self._kappa = critical(radii, dist, leaf)
        self._jitter = float(JITTER_ULPS * np.spacing(
            np.float32(np.abs(points).max())))
        valid = raw & (self._kappa <= self.knee)
        self._setup(TreeSet(perm, tp, valid,
                            leaf_radii(tp, valid, leaf, dtype)),
                    lengths, leaf, int(config["theta"]), dtype)

    @classmethod
    def of_sets(cls, sets: TreeSet, lengths, leaf: int, theta: int, dtype,
                space=None) -> "RefLake":
        """A lake of datasets already in tree order and cleaned; ``space``
        fixes the grid's bounds where it is given."""
        self = cls.__new__(cls)
        self._setup(sets, np.asarray(lengths), leaf, theta, dtype, space)
        return self

    def _setup(self, sets, lengths, leaf, theta, dtype, space=None):
        self.dtype, self.leaf, self.theta = dtype, leaf, theta
        self.lengths = lengths
        self.n = len(lengths)
        self.sets = sets
        live = sets.valid[..., None]
        self.lo = np.where(live, sets.points, np.inf).min(axis=1)
        self.hi = np.where(live, sets.points, -np.inf).max(axis=1)
        if space is None:
            space = (self.lo[:, :2].min(axis=0), self.hi[:, :2].max(axis=0))
        self.space_lo, self.space_hi = space
        self._dev = None
        self._blocked = None
        self._occ = {}
        self._memo = {}

    def cached(self, name: str, *args):
        """``getattr(self, name)(*args)``, computed once until
        :meth:`forget`."""
        key = (name,) + tuple(
            a if isinstance(a, str) else (np.shape(a), np.asarray(a).tobytes())
            for a in args)
        if key not in self._memo:
            self._memo[key] = getattr(self, name)(*args)
        return self._memo[key]

    def forget(self) -> None:
        self._memo.clear()

    def thresholds(self) -> list:
        """One outlier threshold for each distinct lake that an admissible
        knee leaves, give or take ``JITTER_ULPS``: :attr:`knee` first."""
        kappa = np.sort(self._kappa[self._raw])
        cand = [self.knee]
        for k in self._knees:
            lo, hi = k - self._jitter, k + self._jitter
            cand += [lo, *kappa[(kappa >= lo) & (kappa <= hi)], hi]
        out, seen = [], set()
        for t in cand:
            kept = int(np.searchsorted(kappa, t, side="right"))
            if kept not in seen:
                seen.add(kept)
                out.append(float(t))
        return out

    def versions(self) -> list:
        """The lake at each of :meth:`thresholds`, this one first.  Only
        the datasets some threshold cleans otherwise are recomputed, unless
        a threshold moves the bounds of the grid."""
        ts = self.thresholds()
        out = [Version(self, None, np.zeros(0, np.int64), ts[0])]
        if len(ts) == 1:
            return out
        moved = (self._raw & (self._kappa > min(ts))
                 & (self._kappa <= max(ts)))
        ids = np.nonzero(moved.any(axis=1))[0]
        others = np.ones(self.n, bool)
        others[ids] = False
        # the moved datasets, padded with empty ones to a multiple of
        # SUB_PAD, so that their programs' shapes recur from seed to seed
        pad = -len(ids) % SUB_PAD
        rows = np.concatenate([ids, np.full(pad, ids[0])])
        lengths = self.lengths[rows].copy()
        lengths[len(ids):] = 0
        for t in ts[1:]:
            at = ids
            valid = self._raw[rows] & (self._kappa[rows] <= t)
            valid[len(ids):] = False
            pts = self.sets.points[rows]
            sub = RefLake.of_sets(
                TreeSet(self.sets.perm[rows], pts, valid,
                        leaf_radii(pts, valid, self.leaf, self.dtype)),
                lengths, self.leaf, self.theta, self.dtype,
                (self.space_lo, self.space_hi))
            lo = np.minimum(self.lo[others, :2].min(axis=0),
                            sub.lo[:, :2].min(axis=0))
            hi = np.maximum(self.hi[others, :2].max(axis=0),
                            sub.hi[:, :2].max(axis=0))
            if not (np.array_equal(lo, self.space_lo)
                    and np.array_equal(hi, self.space_hi)):
                # the grid moves with this threshold: the whole lake anew
                at = np.arange(self.n)
                valid = self._raw & (self._kappa <= t)
                sub = RefLake.of_sets(
                    TreeSet(self.sets.perm, self.sets.points, valid,
                            leaf_radii(self.sets.points, valid, self.leaf,
                                       self.dtype)),
                    self.lengths, self.leaf, self.theta, self.dtype)
            out.append(Version(self, sub, at, t))
        return out

    def _device(self):
        _, jnp = _jnp()
        if self._dev is None:
            self._dev = (jnp.asarray(self.sets.points, self.dtype),
                         jnp.asarray(self.sets.valid),
                         jnp.asarray(self.space_lo, self.dtype),
                         jnp.asarray(self.space_hi, self.dtype))
        return self._dev

    def occupancy(self, theta: int):
        if theta not in self._occ:
            p, v, lo, hi = self._device()
            self._occ[theta] = _jitted(_occupancy, theta=theta)(p, v, lo, hi)
        return self._occ[theta]

    def _count(self, theta: int, cells) -> np.ndarray:
        _, jnp = _jnp()
        n_cells = 1 << (2 * theta)
        cells = _pad_rows(cells, max(Q_PAD, len(cells)), n_cells)
        return np.asarray(_jitted(_count_cells)(self.occupancy(theta),
                                                jnp.asarray(cells)))

    # -- scores over every dataset (reference answers) ---------------------

    def ia(self, r_lo, r_hi) -> np.ndarray:
        _, jnp = _jnp()
        t = self.dtype
        return np.asarray(_jitted(_ia)(
            jnp.asarray(self.lo, t), jnp.asarray(self.hi, t),
            jnp.asarray(r_lo, t), jnp.asarray(r_hi, t)))

    def range_mask(self, r_lo, r_hi) -> np.ndarray:
        lo = _round(self.lo, self.dtype)
        hi = _round(self.hi, self.dtype)
        return np.all((lo <= r_hi) & (r_lo <= hi), axis=-1)

    def gbo(self, q_sig) -> np.ndarray:
        _, jnp = _jnp()
        bits = np.unpackbits(np.asarray(q_sig, np.uint32).view(np.uint8),
                             bitorder="little").astype(bool)
        return np.asarray(_jitted(_overlap_bits)(
            self.occupancy(self.theta), jnp.asarray(bits)))

    def join(self, q, mode: str) -> np.ndarray:
        """Grid overlap / coverage of q with every dataset, on the fine
        grid two levels below the signatures' (``theta + 2``)."""
        _, jnp = _jnp()
        theta_f = self.theta + 2
        _, _, lo, hi = self._device()
        qq = _pad_rows(np.asarray(q, np.float32), Q_PAD)
        cells = np.asarray(_jitted(_cells, theta=theta_f)(
            jnp.asarray(qq, self.dtype), lo, hi))[:len(q)]
        if mode == "overlap":
            cells = np.unique(cells)
        return self._count(theta_f, cells)

    def _blocks(self, block: int = 256):
        """The tree-ordered lake in blocks of ``block`` datasets (padded
        with empty datasets), made once."""
        if self._blocked is None:
            _, jnp = _jnp()
            ts = self.sets
            s, n_pad, d = ts.points.shape
            nb = -(-s // block)
            pp = np.zeros((nb * block, n_pad, d), np.float32)
            vv = np.zeros((nb * block, n_pad), bool)
            pp[:s], vv[:s] = ts.points, ts.valid
            self._blocked = (
                jnp.asarray(pp.reshape(nb, block, n_pad, d), self.dtype),
                jnp.asarray(vv.reshape(nb, block, n_pad)))
        return self._blocked

    def hausdorff(self, q) -> np.ndarray:
        """H(q -> D) for every dataset D."""
        _, jnp = _jnp()
        pp, vv = self._blocks()
        qq = jnp.asarray(_pad_rows(np.asarray(q, np.float32), Q_PAD),
                         self.dtype)
        qv = jnp.asarray(np.arange(Q_PAD) < len(q))
        h = _jitted(_haus_map)(qq, qv, pp, vv)
        return np.asarray(h)[:self.n]

    def points_in(self, ds: int, r_lo, r_hi) -> np.ndarray:
        """Original indices of ds's live points inside the box."""
        ts = self.sets
        p = _round(ts.points[ds], self.dtype)
        inside = np.all((p >= r_lo) & (p <= r_hi), axis=-1) & ts.valid[ds]
        return np.sort(ts.perm[ds][inside])

    def nn(self, q, ds: int):
        """Per query point (original order): NN distance in ds's live
        points and the NN's original index."""
        _, jnp = _jnp()
        ts = self.sets
        n = len(q)
        dist, arg = _jitted(_nn)(
            jnp.asarray(_pad_rows(np.asarray(q, np.float32), Q_PAD),
                        self.dtype),
            jnp.asarray(np.arange(Q_PAD) < n),
            jnp.asarray(ts.points[ds], self.dtype),
            jnp.asarray(ts.valid[ds]))
        return np.asarray(dist)[:n], ts.perm[ds][np.asarray(arg)[:n]]

    def point(self, ds: int, orig: int) -> np.ndarray:
        """Coordinates of ds's original point ``orig`` and whether it is
        live (kept by the outlier removal)."""
        ts = self.sets
        t = np.nonzero(ts.perm[ds] == orig)[0]
        if len(t) == 0 or orig >= self.lengths[ds]:
            return None, False
        return ts.points[ds, t[0]], bool(ts.valid[ds, t[0]])

    def eps_bound(self, q, eps: float) -> float:
        """Largest node radius ApproHaus can stop at: eps, or a leaf
        radius of the cleaned lake or of the query's tree."""
        q = np.asarray(q, np.float32)
        qs = tree_sets(q[None], np.array([len(q)]), self.leaf, self.dtype)
        return float(max(eps, self.sets.leaf_r.max(), qs.leaf_r.max()))


def _round(x, dtype):
    """``x`` rounded to ``dtype`` (no work at float32)."""
    _, jnp = _jnp()
    if jnp.dtype(dtype) == jnp.float32:
        return np.asarray(x, np.float32)
    return np.asarray(jnp.asarray(x, dtype).astype(jnp.float32))


def _haus_map(q, qv, pp, vv):
    jax, jnp = _jnp()
    return jax.lax.map(lambda xs: _hausdorff_block(q, qv, *xs),
                       (pp, vv)).reshape(-1).astype(jnp.float32)


class Version:
    """The lake as one outlier threshold leaves it: the reference's lake
    with the datasets ``ids`` taken from ``sub`` (a lake of those datasets
    alone, cleaned at ``threshold``).  It answers as a :class:`RefLake`
    does, and reads the base lake's answers from its cache."""

    def __init__(self, base: RefLake, sub, ids: np.ndarray,
                 threshold: float):
        self.base, self.sub, self.ids = base, sub, ids
        self.threshold = float(threshold)
        self.n = base.n
        self._at = {int(d): j for j, d in enumerate(ids)}

    def _whole(self, name: str, *args) -> np.ndarray:
        out = np.array(self.base.cached(name, *args), copy=True)
        if self.sub is not None:
            out[self.ids] = getattr(self.sub, name)(*args)[:len(self.ids)]
        return out

    def _one(self, ds: int):
        j = self._at.get(int(ds))
        return (self.base, ds) if j is None else (self.sub, j)

    def ia(self, r_lo, r_hi):
        return self._whole("ia", r_lo, r_hi)

    def range_mask(self, r_lo, r_hi):
        return self._whole("range_mask", r_lo, r_hi)

    def gbo(self, q_sig):
        return self._whole("gbo", q_sig)

    def join(self, q, mode: str):
        return self._whole("join", q, mode)

    def hausdorff(self, q):
        return self._whole("hausdorff", q)

    def points_in(self, ds: int, r_lo, r_hi):
        lake, i = self._one(ds)
        return lake.points_in(i, r_lo, r_hi)

    def nn(self, q, ds: int):
        lake, i = self._one(ds)
        return lake.nn(q, i)

    def point(self, ds: int, orig: int):
        lake, i = self._one(ds)
        return lake.point(i, orig)

    def eps_bound(self, q, eps: float) -> float:
        bound = self.base.cached("eps_bound", q, eps)
        if self.sub is not None:
            bound = max(bound, float(self.sub.sets.leaf_r.max()))
        return bound


#: the compared numbers that have a limit of their own in the answers
LIMITED = ("value_gap", "count_gap", "mask_diff", "approx_ratio")


def over_limits(g: "Gaps", limits: dict) -> int:
    """How many of ``LIMITED`` exceed their limits in ``g``."""
    return sum(getattr(g, k) > limits[k] for k in LIMITED)


class Judge:
    """Compares answers with every version of the lake.  Eq. 3's knee is
    decided by float32 rounding where gaps all but tie, so the program may
    clean at another admissible threshold than the reference's: a run
    stands on the first version, the reference's own knee first, on which
    every answer is within its limits."""

    def __init__(self, ref: RefLake):
        self.ref = ref
        self.versions = ref.versions()
        self.seen = []    # (key, spec, answer)

    def add(self, key, spec: dict, got) -> None:
        self.seen.append((key, spec, got))

    def gaps(self, v: int, limits: dict | None = None):
        """Each answer's gaps on version ``v``; with ``limits``, None at
        the first answer over one of them."""
        out = []
        for _, spec, got in self.seen:
            g = compare(self.versions[v], spec, got)
            if limits is not None and over_limits(g, limits):
                return None
            out.append(g)
        return out

    def verdict(self, limits: dict):
        """(index of the version judged on, each answer's gaps on it).
        Where no version holds every answer within its limits, the one
        with the fewest numbers over a limit, the first of those."""
        for v in range(len(self.versions)):
            per = self.gaps(v, limits)
            if per is not None:
                break
        else:
            every = [self.gaps(v) for v in range(len(self.versions))]
            over = [over_limits(total(per), limits) for per in every]
            v = int(np.argmin(over))
            per = every[v]
        self.ref.forget()
        return v, per


def total(gaps: list) -> "Gaps":
    g = Gaps()
    for x in gaps:
        g.merge(x)
    return g


# -- answers in the reference's terms ----------------------------------------


def _topk(scores: np.ndarray, k: int, higher: bool):
    order = np.argsort(-scores if higher else scores, kind="stable")[:k]
    return scores[order], order


def describe(spec: dict) -> dict:
    """A request's parameters with its query points summarised, for a
    log line."""
    out = {}
    for key, v in spec.items():
        if isinstance(v, dict):
            out[key] = describe(v)
        elif key == "q":
            q = np.asarray(v)
            out[key] = f"{len(q)} points from {q[0].tolist()}"
        elif key == "q_sig":
            bits = np.unpackbits(np.asarray(v).view(np.uint8))
            out[key] = f"{int(bits.sum())} cells"
        else:
            out[key] = np.asarray(v).tolist()
    return out


def answer(ref: RefLake, spec: dict):
    """The reference's own answer to one request (at its dtype), in the
    semantic form :func:`compare` takes."""
    op = spec["op"]
    if op == "pipeline":
        st, pt = spec["dataset"], spec["point"]
        s1 = answer(ref, st)
        ids = [int(i) for i in s1[1]]
        if pt["op"] == "range_points":
            s2 = [ref.points_in(i, pt["r_lo"], pt["r_hi"]) for i in ids]
        elif pt["op"] == "nnp":
            s2 = [ref.nn(pt["q"], i) for i in ids]
        else:
            sc = ref.join(pt["q"], pt["op"][5:])[ids]
            order = np.argsort(-sc, kind="stable")[:pt["k"]]
            s2 = (sc[order], np.asarray(ids)[order])
        return (s1, s2)
    if op == "range_search":
        return ref.range_mask(spec["r_lo"], spec["r_hi"])
    if op == "topk_ia":
        return _topk(ref.ia(spec["r_lo"], spec["r_hi"]), spec["k"], True)
    if op == "topk_gbo":
        return _topk(ref.gbo(spec["q_sig"]), spec["k"], True)
    if op in ("topk_overlap", "topk_coverage"):
        return _topk(ref.join(spec["q"], op[5:]), spec["k"], True)
    if op in ("topk_hausdorff", "topk_hausdorff_approx"):
        return _topk(ref.hausdorff(spec["q"]), spec["k"], False)
    if op == "range_points":
        return ref.points_in(spec["ds_id"], spec["r_lo"], spec["r_hi"])
    if op == "nnp":
        return ref.nn(spec["q"], spec["ds_id"])
    raise ValueError(f"no reference for op {op!r}")


def semantic(ref: RefLake, spec: dict, res):
    """A program response (``SearchServer``'s per-op shape) in the
    reference's terms: original point indices, query points in their
    original order."""
    op = spec["op"]
    if op == "pipeline":
        st, pt = spec["dataset"], spec["point"]
        s1 = res.extras["stage1"]
        ids = np.asarray(res.extras["ds_ids"])
        stage1 = (np.asarray(s1.vals), ids)
        if pt["op"] == "range_points":
            s2 = [_point_set(ref, int(i), m) for i, m in zip(ids,
                                                            res.mask)]
        elif pt["op"] == "nnp":
            s2 = [_nn_answer(ref, pt["q"], int(i), d, x)
                  for i, d, x in zip(ids, res.vals, res.ids)]
        else:
            s2 = (np.asarray(res.vals), np.asarray(res.ids))
        return (stage1, s2)
    if op == "range_search":
        return np.asarray(res)[:ref.n]
    if op == "range_points":
        return _point_set(ref, spec["ds_id"], np.asarray(res))
    if op == "nnp":
        return _nn_answer(ref, spec["q"], spec["ds_id"], res[0], res[1])
    return (np.asarray(res[0]), np.asarray(res[1]))


def _point_set(ref: RefLake, ds: int, mask):
    if ds < 0:
        return np.zeros(0, np.int64)
    perm = ref.sets.perm[ds]
    m = np.asarray(mask, bool)
    return np.sort(perm[:len(m)][m])


def _nn_answer(ref: RefLake, q, ds: int, dists, ids):
    """Tree-ordered NN answers back in the query's original order."""
    q = np.asarray(q, np.float32)
    qs = tree_sets(q[None], np.array([len(q)]), ref.leaf, ref.dtype)
    qperm = qs.perm[0]
    dist = np.full(len(q), np.nan, np.float32)
    nn = np.full(len(q), -1, np.int64)
    perm = ref.sets.perm[ds] if ds >= 0 else None
    for t, i in enumerate(qperm[:len(np.asarray(dists))]):
        if i >= len(q):
            continue
        dist[i] = dists[t]
        j = int(ids[t])
        nn[i] = perm[j] if perm is not None and 0 <= j < len(perm) else -1
    return dist, nn


# -- comparison ---------------------------------------------------------------


def _rel(a, b) -> float:
    a, b = float(a), float(b)
    if a == b:
        return 0.0
    if not (math.isfinite(a) and math.isfinite(b)):
        return BAD
    return abs(a - b) / max(abs(a), abs(b))


@dataclass
class Gaps:
    value_gap: float = 0.0
    count_gap: int = 0
    mask_diff: int = 0
    approx_ratio: float = 0.0

    def merge(self, o: "Gaps") -> None:
        self.value_gap = max(self.value_gap, o.value_gap)
        self.count_gap = max(self.count_gap, o.count_gap)
        self.mask_diff = self.mask_diff + o.mask_diff
        self.approx_ratio = max(self.approx_ratio, o.approx_ratio)


def _ids_ok(ids, n: int) -> bool:
    ids = np.asarray(ids)
    return bool(np.all((ids >= 0) & (ids < n))
                and len(np.unique(ids)) == len(ids))


def topk_gaps(vals, ids, scores, higher: bool, integer: bool) -> Gaps:
    g = Gaps()
    k = len(ids)
    if not _ids_ok(ids, len(scores)):
        if integer:
            g.count_gap = BAD_COUNT
        else:
            g.value_gap = BAD
        return g
    ref_sorted, _ = _topk(scores, k, higher)
    for r in range(k):
        got, mine, want = vals[r], scores[ids[r]], ref_sorted[r]
        if integer:
            g.count_gap = max(g.count_gap, abs(int(got) - int(mine)),
                              abs(int(mine) - int(want)))
        else:
            g.value_gap = max(g.value_gap, _rel(got, mine), _rel(mine, want))
    return g


def mask_gaps(got, want) -> Gaps:
    got, want = np.asarray(got, bool), np.asarray(want, bool)
    if got.shape != want.shape:
        return Gaps(mask_diff=max(got.size, want.size))
    return Gaps(mask_diff=int(np.sum(got != want)))


def set_gaps(got, want) -> Gaps:
    return Gaps(mask_diff=len(np.setxor1d(got, want)))


def nn_gaps(ref: RefLake, q, ds: int, got, want) -> Gaps:
    g = Gaps()
    dist, nn = got
    rdist, _ = want
    q = np.asarray(q, np.float32)
    for i in range(len(q)):
        g.value_gap = max(g.value_gap, _rel(dist[i], rdist[i]))
        p, live = ref.point(ds, int(nn[i])) if nn[i] >= 0 else (None, False)
        if not live:
            g.mask_diff += 1
            continue
        d = np.sqrt(np.sum((q[i] - p) ** 2, dtype=np.float32))
        g.value_gap = max(g.value_gap, _rel(d, rdist[i]))
    return g


def approx_gaps(vals, ids, h: np.ndarray, bound: float) -> Gaps:
    """Lemma 1: |a - H| <= E with E = r_q + r_d <= 2 * bound, and so
    H(id at rank r) <= H(rank r) + 2E."""
    if not _ids_ok(ids, len(h)):
        return Gaps(approx_ratio=BAD_COUNT)
    e = 2.0 * bound
    h_sorted = np.sort(h)
    ratio = 0.0
    for r, i in enumerate(ids):
        ratio = max(ratio, abs(float(vals[r]) - float(h[i])) / e,
                    (float(h[i]) - float(h_sorted[r])) / (2 * e))
    return Gaps(approx_ratio=ratio)


def compare(ref: RefLake, spec: dict, got) -> Gaps:
    """Gaps of one semantic answer ``got`` (program's or control's) from
    the reference ``ref``."""
    op = spec["op"]
    if op == "pipeline":
        st, pt = spec["dataset"], spec["point"]
        (v1, ids), s2 = got
        g = compare(ref, st, (v1, ids))
        if not _ids_ok(ids, ref.n):
            return g
        if pt["op"] == "range_points":
            for i, pts in zip(ids, s2):
                g.merge(set_gaps(pts, ref.points_in(int(i), pt["r_lo"],
                                                    pt["r_hi"])))
        elif pt["op"] == "nnp":
            for i, ans in zip(ids, s2):
                g.merge(nn_gaps(ref, pt["q"], int(i), ans,
                                ref.nn(pt["q"], int(i))))
        else:
            sc = ref.join(pt["q"], pt["op"][5:])[np.asarray(ids)]
            order = np.argsort(-sc, kind="stable")[:pt["k"]]
            want_v, want_i = sc[order], np.asarray(ids)[order]
            v2, i2 = s2
            for r in range(len(want_i)):
                gap = abs(int(v2[r]) - int(want_v[r]))
                if int(i2[r]) != int(want_i[r]):
                    gap = max(gap, 1)
                g.count_gap = max(g.count_gap, gap)
        return g
    if op == "range_search":
        return mask_gaps(got, ref.range_mask(spec["r_lo"], spec["r_hi"]))
    if op == "range_points":
        return set_gaps(got, ref.points_in(spec["ds_id"], spec["r_lo"],
                                           spec["r_hi"]))
    if op == "nnp":
        return nn_gaps(ref, spec["q"], spec["ds_id"], got,
                       ref.nn(spec["q"], spec["ds_id"]))
    vals, ids = got
    if op == "topk_ia":
        return topk_gaps(vals, ids, ref.ia(spec["r_lo"], spec["r_hi"]),
                         True, False)
    if op == "topk_gbo":
        return topk_gaps(vals, ids, ref.gbo(spec["q_sig"]), True, True)
    if op in ("topk_overlap", "topk_coverage"):
        return topk_gaps(vals, ids, ref.join(spec["q"], op[5:]), True, True)
    if op == "topk_hausdorff":
        return topk_gaps(vals, ids, ref.hausdorff(spec["q"]), False, False)
    if op == "topk_hausdorff_approx":
        return approx_gaps(vals, ids, ref.hausdorff(spec["q"]),
                           ref.eps_bound(spec["q"], spec["eps"]))
    raise ValueError(f"no comparison for op {op!r}")
