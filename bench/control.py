"""The control of the comparison that decides ``correct``.

    python3 -m bench.control --workload <cell> --seeds <n> [<n> ...]

The plain reference, computed in bfloat16 (the precision below the
configuration's float32), is put in the program's place: for each seed it
answers the cell's requests over the cell's lake at full size, and its
answers are judged by the same comparison as the program's
(``reference.Judge``, over every admissible lake).  The
smallest reading over the seeds of each compared number is that number's
upper reading; the benchmark's own runs never run this.  The last line of
standard output is one JSON object with each seed's readings.
"""
from __future__ import annotations

import argparse
import json
import sys
import time

import numpy as np

from bench import data, reference, spec, traffic


def readings(cell: spec.Cell, seed: int, per_kind: int | None = None,
             dtype=None) -> dict:
    """The compared numbers of the control on one seed: ``per_kind``
    requests of each kind (default: the mix's ``check_per_kind``) answered
    by the reference at ``dtype`` (default bfloat16)."""
    import jax.numpy as jnp
    cfg, mix = cell.config, cell.mix
    per = int(mix["check_per_kind"]) if per_kind is None else per_kind
    lake = data.trajectories(cfg, seed)
    kinds = traffic.cycle(mix)
    reqs = traffic.requests(mix, cfg, lake, seed, per * len(kinds))
    ref = reference.RefLake(lake.points, lake.lengths, cfg)
    low = reference.RefLake(lake.points, lake.lengths, cfg,
                            dtype=jnp.bfloat16 if dtype is None else dtype)
    judge = reference.Judge(ref)
    for r in reqs:
        judge.add(r.kind, r.spec, reference.answer(low, r.spec))
    _, per_answer = judge.verdict(cfg["limits"])
    gaps = reference.total(per_answer)
    per_kind_gaps = {}
    for (kind, _, _), g in zip(judge.seen, per_answer):
        per_kind_gaps.setdefault(kind, reference.Gaps()).merge(g)
    return {"value_gap": gaps.value_gap, "count_gap": gaps.count_gap,
            "mask_diff": gaps.mask_diff, "approx_ratio": gaps.approx_ratio,
            "kinds": {k: vars(g) for k, g in sorted(per_kind_gaps.items())}}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    args = ap.parse_args(argv)
    cell = spec.load_cell(args.workload)
    out = {}
    for seed in args.seeds:
        t = time.perf_counter()
        out[seed] = readings(cell, seed)
        print(f"[control] seed {seed}: {json.dumps(out[seed])} "
              f"({time.perf_counter() - t:.1f} s)", file=sys.stderr,
              flush=True)
    upper = {k: min(out[s][k] for s in out)
             for k in ("value_gap", "count_gap", "mask_diff", "approx_ratio")}
    print(json.dumps({"seeds": out, "upper": upper},
                     default=lambda x: x.item() if isinstance(x, np.generic)
                     else str(x)))
    return 0


if __name__ == "__main__":
    sys.exit(main())
