"""Mean dispatch groups the planner made per drain
(``EngineStats.plan_groups`` over each of the window's ``engine.search``
calls)."""


def read(run):
    if not run.spans:
        return None
    return sum(s.groups for s in run.spans) / len(run.spans)
