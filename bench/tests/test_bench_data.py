"""The lake from the seed: the configuration's lengths for every seed."""
import numpy as np

from bench import data, spec


def test_every_seed_gets_the_configured_lengths():
    cfg = spec.load_config("tdrive")
    q = data.length_quantiles(cfg)
    assert len(q) == cfg["datasets"] and np.all(np.diff(q) >= 0)
    assert q.min() >= cfg["points_min"] and q.max() <= cfg["points_max"]
    assert abs(q.mean() - cfg["points_mean"]) < 1.0
    # right-skewed: the median lies below the mean
    assert np.median(q) < q.mean()


def test_same_seed_same_lake_and_same_work_for_every_seed():
    cfg = dict(spec.load_config("tdrive"), datasets=40)
    a = data.trajectories(cfg, seed=2**31 + 17)
    b = data.trajectories(cfg, seed=2**31 + 17)
    c = data.trajectories(cfg, seed=5)
    np.testing.assert_array_equal(a.points, b.points)
    np.testing.assert_array_equal(a.lengths, b.lengths)
    assert not np.array_equal(a.lengths, c.lengths)
    np.testing.assert_array_equal(np.sort(a.lengths), np.sort(c.lengths))
    assert a.lengths.max() <= cfg["points_kept"] == a.points.shape[1]
    pts = a.points
    live = np.arange(pts.shape[1])[None, :] < a.lengths[:, None]
    assert np.all(pts[~live] == 0)
    assert np.all((pts[live] >= 0) & (pts[live] <= cfg["space"]))
