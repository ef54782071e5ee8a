"""Cells, configurations and traffic mixes, found by name.

A cell is one ``workloads`` entry of ``BENCHMARK.json`` at the checkout
root.  It names a configuration (``bench/configs/<config>.json``), a
traffic mix (``bench/traffic/<traffic>.json``) and the chips it needs.
Per-layer metrics are readers in ``bench/metrics/<name>.py``.  Nothing
here knows a cell by name: a new cell is a new entry and new files.
"""
from __future__ import annotations

import json
from dataclasses import dataclass
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]


@dataclass(frozen=True)
class Cell:
    name: str
    chips: int
    config: dict
    mix: dict
    end_to_end: tuple      # metric entries of BENCHMARK.json for this cell
    per_layer: tuple


def _applies(metric: dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def load_json(path: Path) -> dict:
    with open(path) as f:
        return json.load(f)


def load_config(name: str, root: Path = ROOT) -> dict:
    return load_json(root / "bench" / "configs" / f"{name}.json")


def load_mix(name: str, root: Path = ROOT) -> dict:
    return load_json(root / "bench" / "traffic" / f"{name}.json")


def load_cell(name: str, root: Path = ROOT) -> Cell:
    """The cell ``name`` of ``root/BENCHMARK.json`` with its files read."""
    bench = load_json(root / "BENCHMARK.json")
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise KeyError(f"no workload {name!r} in BENCHMARK.json "
                       f"(have: {', '.join(sorted(cells))})")
    w = cells[name]
    return Cell(
        name=name,
        chips=int(w["chips"]),
        config=load_config(w["config"], root),
        mix=load_mix(w["traffic"], root),
        end_to_end=tuple(m for m in bench["end_to_end"] if _applies(m, name)),
        per_layer=tuple(m for m in bench["per_layer"] if _applies(m, name)),
    )
