"""The data lake of a configuration, made from the run's seed.

A vectorised copy of the walks of ``repro.data.synthetic``'s
``trajectory_repository`` (a uniform start in the square, normal steps
with a normal per-trajectory drift, clipped to the square), drawn in bulk
instead of trajectory by trajectory.  The lengths follow the
configuration's stated figures instead of that generator's uniform range:
a log-normal of spread ``points_sigma`` restricted to ``[points_min,
points_max]``, its location set so that the mean length is
``points_mean``; each trajectory then keeps its first ``points_kept``
points where the configuration cuts them.  Every seed gets the same
lengths (the distribution's quantiles at ``(i + 1/2) / n``), in another
order, so every seed's lake holds the same number of points.  It is kept
here so that a change to the program cannot change the benchmark's data.
"""
from __future__ import annotations

from dataclasses import dataclass

import numpy as np


@dataclass
class Lake:
    points: np.ndarray    # (B, n_max, d) float32; rows past lengths[i] are 0
    lengths: np.ndarray   # (B,) int64

    def __len__(self) -> int:
        return len(self.lengths)

    def datasets(self) -> list:
        """Per-dataset (n_i, d) views, the program's input form."""
        return [self.points[i, :n] for i, n in enumerate(self.lengths)]


def length_quantiles(config: dict) -> np.ndarray:
    """The configuration's trajectory lengths, sorted: one per dataset."""
    from scipy.special import ndtr, ndtri
    n = int(config["datasets"])
    lo, hi = int(config["points_min"]), int(config["points_max"])
    sigma, mean = float(config["points_sigma"]), float(config["points_mean"])
    u = (np.arange(n) + 0.5) / n

    def at(mu: float) -> np.ndarray:
        a = ndtr((np.log(lo) - mu) / sigma)
        b = ndtr((np.log(hi) - mu) / sigma)
        z = ndtri(a + u * (b - a))
        return np.clip(np.rint(np.exp(mu + sigma * z)), lo, hi).astype(
            np.int64)

    # the mean grows with mu: bisect for the configuration's mean
    mu_lo, mu_hi = np.log(lo), np.log(hi)
    for _ in range(60):
        mid = 0.5 * (mu_lo + mu_hi)
        if at(mid).mean() < mean:
            mu_lo = mid
        else:
            mu_hi = mid
    return at(mu_hi)


def trajectories(config: dict, seed: int) -> Lake:
    """Random-walk trajectories sized by ``config``; same seed, same lake."""
    rng = np.random.default_rng(seed)
    b, d = int(config["datasets"]), int(config["dims"])
    space, step = float(config["space"]), float(config["step"])
    kept = int(config["points_kept"])
    lengths = rng.permutation(np.minimum(length_quantiles(config), kept))
    n_max = int(lengths.max())
    start = rng.uniform(0, space, (b, 1, d)).astype(np.float32)
    drift = rng.normal(scale=step * 0.2, size=(b, 1, d)).astype(np.float32)
    walk = rng.standard_normal((b, n_max, d), dtype=np.float32)
    walk *= np.float32(step)
    walk += drift
    np.cumsum(walk, axis=1, out=walk)
    walk += start
    np.clip(walk, 0, space, out=walk)
    walk[np.arange(n_max)[None, :] >= lengths[:, None]] = 0
    return Lake(walk, lengths)
