"""The plain reference against the engine, through the server, on a tiny
lake: every kind of the mixes."""
import numpy as np
import pytest

from bench import data, reference, spec, traffic


def served(cfg, mix, seed, per_kind):
    """Answers of the program to ``per_kind`` requests of every kind."""
    from repro.core.build import build_repository
    from repro.engine import QueryEngine
    from repro.launch.serve_search import SearchServer
    lake = data.trajectories(cfg, seed)
    reqs = traffic.requests(mix, cfg, lake, seed,
                            per_kind * len(traffic.cycle(mix)))
    repo, _ = build_repository(lake.datasets(), leaf_capacity=16, theta=5)
    server = SearchServer(QueryEngine(repo), max_batch=64).start()
    try:
        futs = [server.submit_query(traffic.to_query(r.spec)) for r in reqs]
        answers = [f.result(timeout=600) for f in futs]
    finally:
        server.stop()
    return lake, reqs, answers


@pytest.mark.parametrize("config,mix", [("tdrive", "mixed12-closed256"),
                                        ("tdrive", "mixed10-closed256")])
def test_reference_agrees_with_the_engine(config, mix):
    cfg = dict(spec.load_config(config), datasets=96)
    m = spec.load_mix(mix)
    lake, reqs, answers = served(cfg, m, seed=2**31 + 5, per_kind=2)
    ref = reference.RefLake(lake.points, lake.lengths, cfg)
    kinds = set()
    for r, a in zip(reqs, answers):
        g = reference.compare(ref, r.spec, reference.semantic(ref, r.spec, a))
        kinds.add(r.kind)
        assert g.count_gap == 0 and g.mask_diff == 0, (r.kind, g)
        assert g.value_gap <= 1e-6, (r.kind, g)
        assert g.approx_ratio <= 1.0, (r.kind, g)
    assert kinds == {k["name"] for k in m["kinds"]}


def test_outlier_removal_matches_the_program():
    """Tree order and outlier removal, on a lake with planted GPS
    failures, equal the program's resident index point for point."""
    from repro.core.build import build_repository
    cfg = dict(spec.load_config("tdrive"), datasets=40)
    lake = data.trajectories(cfg, 3)
    for b in range(0, 40, 4):
        lake.points[b, 5] = (99.5, 99.5) if lake.points[b, 5, 0] < 50 else (
            0.5, 0.5)
    ref = reference.RefLake(lake.points, lake.lengths, cfg)
    repo, _ = build_repository(lake.datasets(), leaf_capacity=16, theta=5)
    valid = np.asarray(repo.ds_index.valid)[:40]
    np.testing.assert_array_equal(np.asarray(repo.ds_index.points)[:40][valid],
                                  ref.sets.points[ref.sets.valid])
    np.testing.assert_array_equal(valid, ref.sets.valid)
    assert ref.sets.valid.sum() < lake.lengths.sum()


def lake_at(ref, threshold):
    """The reference's lake cleaned at ``threshold`` instead of its knee."""
    sets = ref.sets
    valid = ref._raw & (ref._kappa <= threshold)
    return reference.RefLake.of_sets(
        reference.TreeSet(sets.perm, sets.points, valid,
                          reference.leaf_radii(sets.points, valid, ref.leaf,
                                               ref.dtype)),
        ref.lengths, ref.leaf, ref.theta, ref.dtype)


def test_a_lake_cleaned_at_another_admissible_knee_is_judged_on_it(
        monkeypatch):
    """Where Eq. 3's gaps all but tie, another device may clean at another
    knee: its answers are judged on the lake that knee leaves, and refused
    where that knee is not admissible."""
    cfg = dict(spec.load_config("tdrive"), datasets=64)
    lake = data.trajectories(cfg, 7)
    base = reference.RefLake(lake.points, lake.lengths, cfg)
    kept = np.sort(base._kappa[base._raw & (base._kappa <= base.knee)])
    other = float(kept[-6])          # five more points drop at this knee
    program = lake_at(base, other)
    moved = base.sets.valid & ~program.sets.valid
    assert moved.sum() == 5
    reqs = [{"op": "range_points", "ds_id": int(d), "r_lo": np.zeros(2),
             "r_hi": np.full(2, 100.0)} for d in np.nonzero(moved.any(1))[0]]

    def verdict(ref):
        judge = reference.Judge(ref)
        for s in reqs:
            judge.add(s["ds_id"], s, reference.answer(program, s))
        v, per_answer = judge.verdict(cfg["limits"])
        return v, reference.total(per_answer)

    v, gaps = verdict(base)
    assert (v, gaps.mask_diff) == (0, 5)
    monkeypatch.setattr(reference, "knees",
                        lambda radii: np.array([other, base.knee]))
    ref = reference.RefLake(lake.points, lake.lengths, cfg)
    v, gaps = verdict(ref)
    assert v > 0 and gaps.mask_diff == 0
    assert ref.versions()[v].threshold <= other + ref._jitter
