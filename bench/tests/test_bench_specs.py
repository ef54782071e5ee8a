"""Cells, configurations, mixes and metric readers are found by name."""
import json
from pathlib import Path

import pytest

from bench import spec, traffic
from bench.metrics import reader


def test_new_files_are_found_by_name(tmp_path: Path):
    (tmp_path / "bench" / "configs").mkdir(parents=True)
    (tmp_path / "bench" / "traffic").mkdir(parents=True)
    cfg = {"datasets": 8, "theta": 5}
    mix = {"loop": "closed", "clients": 4,
           "kinds": [{"name": "a", "op": "range_search", "box": 1.0},
                     {"name": "b", "op": "topk_ia", "box": 1.0, "k": 3,
                      "weight": 2}]}
    (tmp_path / "bench/configs/lake-x.json").write_text(json.dumps(cfg))
    (tmp_path / "bench/traffic/mix-y.json").write_text(json.dumps(mix))
    bench = {"workloads": [{"name": "cell-z", "config": "lake-x",
                            "traffic": "mix-y", "chips": 1}],
             "end_to_end": [{"name": "qps", "workloads": ["cell-z"]},
                            {"name": "p95_ms", "workloads": ["other"]},
                            {"name": "setup_s"}],
             "per_layer": [{"name": "engine.search_ms.sat",
                            "workloads": ["cell-z"]}]}
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bench))
    cell = spec.load_cell("cell-z", root=tmp_path)
    assert cell.config == cfg and cell.mix == mix and cell.chips == 1
    assert [m["name"] for m in cell.end_to_end] == ["qps", "setup_s"]
    assert [m["name"] for m in cell.per_layer] == ["engine.search_ms.sat"]
    assert [k["name"] for k in traffic.cycle(mix)] == ["a", "b", "b"]
    with pytest.raises(KeyError):
        spec.load_cell("absent", root=tmp_path)


def test_every_cell_and_metric_of_the_benchmark_resolves():
    bench = spec.load_json(spec.ROOT / "BENCHMARK.json")
    for w in bench["workloads"]:
        cell = spec.load_cell(w["name"])
        assert set(cell.config["limits"]) == {
            "value_gap", "count_gap", "mask_diff", "approx_ratio",
            "missing"}
        assert cell.mix["loop"] == "closed"
    for m in bench["end_to_end"] + bench["per_layer"]:
        assert callable(reader(m["name"]))
