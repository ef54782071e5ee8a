"""The program's NNP answers on the chip, at the default and at the
highest matmul precision, against the plain reference.

    python3 -m bench.witness_nnp --seeds <n> [<n> ...]

A second witness for a fault of the program, not part of any run: the
``tdrive`` lake of each seed is built with the program's
``build_repository``, and the NNP kinds of ``mixed12-closed256`` (``nnp``
and the ApproHaus -> NNP pipeline, 48 requests each) go through one
``QueryEngine.search`` call with the result cache off, once at JAX's
default matmul precision and once at ``highest``.  Each answer is judged
by :func:`bench.reference.compare`.  One JSON line per seed and
precision: per kind, the requests, those whose relative NN-distance gap
is over 1e-5, and the widest gap.
"""
from __future__ import annotations

import argparse
import json
import sys

import numpy as np

from bench import data, reference, spec, traffic

sys.path.insert(0, str(spec.ROOT / "src"))

KINDS = ("nnp", "pipeline:topk_hausdorff_approx>nnp")
PER_KIND = 48


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    args = ap.parse_args(argv)
    import jax
    from bench.run import enable_compile_cache
    from repro.core.build import build_repository
    from repro.engine import QueryEngine

    enable_compile_cache()
    cfg = spec.load_config("tdrive")
    mix = spec.load_mix("mixed12-closed256")
    n_kinds = len(traffic.cycle(mix))
    for seed in args.seeds:
        lake = data.trajectories(cfg, seed)
        reqs = [r for r in traffic.requests(mix, cfg, lake, seed,
                                            n_kinds * PER_KIND)
                if r.kind in KINDS]
        repo, _ = build_repository(lake.datasets(),
                                   leaf_capacity=int(cfg["leaf_capacity"]),
                                   theta=int(cfg["theta"]))
        ref = reference.RefLake(lake.points, lake.lengths, cfg)
        for precision in ("default", "highest"):
            jax.config.update("jax_default_matmul_precision",
                              None if precision == "default" else "highest")
            engine = QueryEngine(repo, result_cache_size=0)
            answers = engine.search([traffic.to_query(r.spec) for r in reqs])
            gaps = {k: [] for k in KINDS}
            for r, a in zip(reqs, answers):
                # the server's per-op shape: (vals, ids) for a plain op
                a = a if r.spec["op"] == "pipeline" else (a.vals, a.ids)
                g = reference.compare(ref, r.spec,
                                      reference.semantic(ref, r.spec, a))
                gaps[r.kind].append(g.value_gap)
            out = {k: {"n": len(v), "over_1e-5": int(np.sum(np.asarray(v)
                                                            > 1e-5)),
                       "max_gap": float(max(v))} for k, v in gaps.items()}
            print(json.dumps({"seed": seed, "precision": precision,
                              "nnp": out}), flush=True)
        jax.config.update("jax_default_matmul_precision", None)
    return 0


if __name__ == "__main__":
    sys.exit(main())
