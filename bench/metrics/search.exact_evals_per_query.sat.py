"""Exact Hausdorff evaluations per ExactHaus query answered in the window
(``EngineStats.per_op['topk_hausdorff']``): what the bound phases left."""


def read(run):
    q = sum(s.exact_queries for s in run.spans)
    return sum(s.exact_evals for s in run.spans) / q if q else None
