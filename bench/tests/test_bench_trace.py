"""The trace reduction on a small synthetic trace."""
import pytest

from bench import trace
from bench.drive import Record, Span
from bench.metrics import reader
from bench.run import Run

import numpy as np


def synthetic():
    # one device; window [0, 100] ns; ops busy [10, 20] and [30, 40]
    ops = [[("fusion.1", 10, 20), ("fusion.2", 30, 40)]]
    modules = [("jit_topk_ia(3)", 10, 20), ("jit_topk_gbo(4)", 30, 40)]
    searching = [(20, 25)]                 # host inside engine.search
    no_request = [(0, 5), (40, 100)]       # client had nothing outstanding
    return trace.reduce(ops, modules, (0, 100), searching, no_request)


def test_busy_and_idle_share():
    r = synthetic()
    assert r.busy_s == pytest.approx(20e-9)
    assert r.window_s == pytest.approx(100e-9)
    run = Run([], np.array([50e-9, 60e-9]), [], (0.0, 100e-9), 0.0, r)
    assert reader("device.idle_share.sat")(run) == pytest.approx(80.0)
    # 20 ns busy over the 2 answers inside the window
    assert reader("device.busy_ms_per_request.sat")(run) == pytest.approx(1e-5)


def test_gap_labels():
    r = synthetic()
    labels = dict(r.idle_gaps)
    assert labels["client.no_request"] == pytest.approx(65e-9)
    assert labels["server.queue"] == pytest.approx(10e-9)
    assert labels["engine.search"] == pytest.approx(5e-9)
    assert r.idle_gaps[0][0] == "client.no_request"
    assert dict(r.device_ops) == {"jit_topk_ia": pytest.approx(10e-9),
                                  "jit_topk_gbo": pytest.approx(10e-9)}


def test_busy_averages_devices_and_gaps_need_all_idle():
    ops = [[("a", 0, 50)], [("b", 50, 100)]]
    r = trace.reduce(ops, [], (0, 100), [], [])
    assert r.busy_s == pytest.approx(50e-9)
    assert r.idle_gaps == []


def test_align_maps_host_clock_to_trace_clock():
    spans = [(1.0, 1.5), (2.0, 2.1)]
    marks = [(1e9 + 7_000, 1.5e9 + 7_000), (2e9 + 7_000, 2.1e9 + 7_000)]
    assert trace.align(marks, spans) == pytest.approx(7_000)
    assert trace.align([], spans) is None


def test_span_and_queue_readers():
    # the server's queue is read as the requests each drain takes from it
    recs = [Record("k", {}, due=0.0, start=0.002, done=0.010),
            Record("k", {}, due=0.001, start=0.004, done=0.012)]
    spans = [Span(0.002, 0.006, 3, groups=2, exact_evals=10,
                  exact_queries=2),
             Span(0.007, 0.009, 1, groups=1)]
    run = Run(recs, np.array([0.010, 0.012]), spans, (0.0, 0.02), 1.5)
    assert reader("server.requests_per_drain.sat")(run) == pytest.approx(2.0)
    assert reader("engine.groups_per_drain.sat")(run) == pytest.approx(1.5)
    assert reader("engine.search_ms.sat")(run) == pytest.approx(3.0)
    assert reader("search.exact_evals_per_query.sat")(run) == pytest.approx(
        5.0)
    assert reader("qps")(run) == pytest.approx(100.0)
    assert reader("setup_s")(run) == 1.5
    assert reader("device.idle_share.sat")(run) is None
