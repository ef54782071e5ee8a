"""Mean duration (ms, host clock) of one ``engine.search`` call of the
window: planning, dispatch and reading every group's answers back."""


def read(run):
    d = [s.end - s.start for s in run.spans]
    return 1e3 * sum(d) / len(d) if d else None
