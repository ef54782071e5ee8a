"""The closed loop: whole drains, and a window of whole drains."""
import time

import numpy as np

from bench import drive, traffic


class FakeEngine:
    """Answers every request of a drain after a fixed service time."""

    def __init__(self, service_s: float):
        from repro.engine.engine import EngineStats
        self.stats = EngineStats()
        self.leaf_capacity = 16
        self.service_s = service_s

    def search(self, queries):
        from repro.engine import SearchResult
        time.sleep(self.service_s)
        return [SearchResult(op="range_search", mask=np.zeros(1, bool))
                for _ in queries]


def test_closed_loop_drains_whole_and_measures_whole_drains():
    from repro.launch.serve_search import SearchServer
    engine = FakeEngine(0.02)
    spans = drive.SearchSpans(engine)
    server = SearchServer(engine, max_batch=4)
    from repro.engine import Query
    box = np.zeros(2, np.float32)
    reqs = [traffic.Request("k", {}, query=Query(op="range_search", r_lo=box,
                                                  r_hi=box + 1))
            for _ in range(4000)]
    loop = drive.ClosedLoop(server, spans, reqs, clients=16)
    try:
        loop.start()
        t0, t1 = loop.run(0.3)
        loop.wait(t1 + 5)
    finally:
        server.stop()
    # every drain takes the 16 clients' requests whole (16 > max_batch
    # lets a drain grow to OVERFILL x max_batch)
    assert {s.n for s in spans.spans} == {16}
    # the window is whole drains: it starts and ends at drain starts
    assert t0 in spans.starts and t1 in spans.starts
    done = np.asarray([r.done for r in loop.records])
    n = int(((done >= t0) & (done <= t1)).sum())
    drains = sum(1 for s in spans.spans if t0 <= s.start < t1)
    assert n == 16 * drains
