"""The program's spans as the benchmark reads them: self time, the
window's drains and the seven readers, on hand-made spans."""
import dataclasses

import numpy as np
import pytest

from bench import phases
from bench.metrics import reader
from bench.run import Run
from repro.engine.spans import Span

READERS = {
    "server.deliver_ms.sat": "server.deliver",
    "engine.plan_ms.sat": "engine.plan",
    "engine.marshal_ms.sat": "engine.marshal",
    "engine.launch_ms.sat": "engine.launch",
    "engine.readback_ms.sat": "engine.readback",
    "engine.results_ms.sat": "engine.results",
}


def drain(d: int, t: int) -> list:
    """One drain's spans from ``t`` ns: forming the batch [t, t+10], the
    engine call [t+10, t+90] with one group, delivery [t+90, t+100]."""
    i = 100 * d
    return [
        Span("server.drain", t, t + 10, i + 1, None, d,
             {"requests": 4, "wait_sum_ms": 2.0 * d,
              "wait_max_ms": 1.0}),
        Span("engine.execute", t + 10, t + 90, i + 2, None, d,
             {"requests": 4, "groups": 1}),
        Span("engine.plan", t + 11, t + 13, i + 3, i + 2, d),
        Span("engine.group", t + 14, t + 88, i + 4, i + 2, d,
             {"op": "topk_ia", "stage": 1, "rows": 4}),
        Span("engine.marshal", t + 15, t + 25, i + 5, i + 4, d),
        Span("engine.launch", t + 26, t + 40, i + 6, i + 4, d),
        Span("jax.compile", t + 30, t + 38, i + 7, i + 6, d,
             {"seconds": 8e-9}),
        Span("engine.readback", t + 41, t + 71, i + 8, i + 4, d),
        Span("engine.results", t + 72, t + 86, i + 9, i + 4, d),
        Span("server.deliver", t + 90, t + 100, i + 10, None, d),
    ]


def spans_run(spans, window=(0.0, 200e-9)) -> Run:
    run = Run([], np.array([]), [], window, 0.0)
    run.phases = spans
    return run


def test_self_time_of_nested_spans():
    own = phases.self_ns(drain(1, 0))
    assert own[102] == 80 - 2 - 74        # execute less plan and group
    assert own[104] == 74 - 10 - 14 - 30 - 14
    assert own[106] == 14 - 8             # launch less its compile
    assert own[107] == 8 and own[108] == 30


def test_window_takes_drains_by_engine_call_start():
    spans = drain(1, 0) + drain(2, 100) + drain(3, 200)
    # drain 3's call starts at 210 ns, past the window's end
    assert sorted(phases.window_drains(spans, (0.0, 200e-9))) == [1, 2]
    assert sorted(phases.window_drains(spans, (15e-9, 300e-9))) == [2, 3]


def test_readers_on_hand_made_spans():
    # drains 1 and 2 are inside; drain 3 starts its call past the window
    run = spans_run(drain(1, 0) + drain(2, 100) + drain(3, 200))
    got = {name: reader(name)(run) for name in READERS}
    assert got == pytest.approx({"server.deliver_ms.sat": 10e-6,
                                 "engine.plan_ms.sat": 2e-6,
                                 "engine.marshal_ms.sat": 10e-6,
                                 "engine.launch_ms.sat": 6e-6,
                                 "engine.readback_ms.sat": 30e-6,
                                 "engine.results_ms.sat": 14e-6})
    # (2.0 + 4.0) ms of waits over 8 requests
    assert reader("server.queue_wait_ms.sat")(run) == pytest.approx(0.75)


@pytest.mark.parametrize("name", [*READERS, "server.queue_wait_ms.sat",
                                  "engine.results_cache_ms.sat",
                                  "engine.results_handoff_ms.sat"])
def test_readers_without_spans_read_none(name):
    assert reader(name)(spans_run([])) is None
    # spans, but no drain inside the window
    assert reader(name)(spans_run(drain(3, 200))) is None


def test_engine_phases_sum_to_the_engine_call():
    # the self times of engine.execute and its descendants make up the
    # call, as the harness's engine.search span measures it from outside
    run = spans_run(drain(1, 0))
    own = phases.self_ns(run.phases)
    inside = [s for s in run.phases
              if s.name.startswith(("engine.", "jax."))]
    assert sum(own[s.id] for s in inside) == 80


def test_program_spans_are_taken_once_and_kept(monkeypatch):
    """Without ``run.phases`` the first reader takes the program's spans
    from its recorder and keeps them on the run for the others."""
    from repro.engine import spans as recorder
    taken = []

    def take():
        taken.append(1)
        return drain(1, 0), 0

    monkeypatch.setattr(recorder, "take", take)
    run = Run([], np.array([]), [], (0.0, 200e-9), 0.0)
    assert reader("engine.marshal_ms.sat")(run) == pytest.approx(10e-6)
    assert reader("engine.readback_ms.sat")(run) == pytest.approx(30e-6)
    assert len(taken) == 1 and len(run.phases) == len(drain(1, 0))


def test_results_readers_by_part():
    """The result-cache insertion and the handoff, read apart from the
    rest of ``engine.results`` by the spans' ``part``."""
    spans = []
    for d, t in ((1, 0), (2, 100)):
        ss = drain(d, t)
        assert ss[8].name == "engine.results"      # [t+72, t+86]
        ss[8] = dataclasses.replace(ss[8], attrs={"part": "cache",
                                                  "rows": 4})
        ss.append(Span("engine.results", t + 88, t + 89, 100 * d + 11,
                       100 * d + 2, d, {"part": "handoff", "rows": 1}))
        spans += ss
    run = spans_run(spans)
    assert reader("engine.results_cache_ms.sat")(run) == pytest.approx(14e-6)
    assert reader("engine.results_handoff_ms.sat")(run) == pytest.approx(
        1e-6)
    assert reader("engine.results_ms.sat")(run) == pytest.approx(15e-6)
