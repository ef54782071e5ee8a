"""The load driver: a closed loop from the client's side of
``SearchServer``, and the harness's own span around each
``engine.search`` call.

Every request carries its own record: when it was due, when the search
call that served it started, and when its answer reached the client.
Times are ``time.perf_counter()`` seconds.  Completions are taken in the
future's done-callback, which the server runs on its dispatcher thread
the moment it sets the answer, so a closed loop's next request is queued
before the dispatcher drains again, with no client threads.
"""
from __future__ import annotations

import bisect
import threading
import time
from dataclasses import dataclass


@dataclass
class Record:
    kind: str
    spec: dict                      # the request's parameters
    due: float = 0.0
    start: float = float("nan")     # the serving engine.search call began
    done: float = float("nan")      # the answer reached the client
    answer: object = None
    error: BaseException | None = None


@dataclass
class Span:
    start: float
    end: float
    n: int                 # requests in the call (one server drain)
    groups: int = 0        # dispatch groups the planner made
    exact_evals: int = 0   # ExactHaus exact evaluations
    exact_queries: int = 0 # ExactHaus queries answered


class SearchSpans:
    """Wraps ``engine.search``: one span per call, and each request's
    record gets the call's start time.  With ``annotate`` the call is
    also marked in the profiler's trace (``engine.search``)."""

    def __init__(self, engine):
        self.engine = engine
        self.spans: list[Span] = []
        self.by_query: dict = {}       # id(query) -> Record
        self.starts: list[float] = []  # call start times, as they begin
        self.annotate = False
        self._search = engine.search
        engine.search = self._call

    def _call(self, queries):
        from jax.profiler import TraceAnnotation
        t0 = time.perf_counter()
        self.starts.append(t0)
        for q in queries:
            rec = self.by_query.get(id(q))
            if rec is not None and rec.start != rec.start:
                rec.start = t0
        before = self._counters()
        if self.annotate:
            with TraceAnnotation("engine.search"):
                out = self._search(queries)
        else:
            out = self._search(queries)
        after = self._counters()
        self.spans.append(Span(t0, time.perf_counter(), len(queries),
                               *(b - a for a, b in zip(before, after))))
        return out

    def _counters(self):
        st = self.engine.stats
        exact = st.per_op.get("topk_hausdorff", {})
        return (st.plan_groups, exact.get("exact_evaluations", 0),
                exact.get("queries", 0))

    def restore(self) -> None:
        self.engine.search = self._search


class Loop:
    """Submits requests to a server and keeps one :class:`Record` each."""

    def __init__(self, server, spans: SearchSpans, requests):
        self.server = server
        self.spans = spans
        self.requests = requests
        self.records: list[Record] = []
        self.next = 0
        self.lock = threading.Lock()
        self.outstanding = 0
        self.idle_since = None         # no request outstanding since
        self.idle: list = []           # (start, end) with none outstanding

    def _submit(self, due: float) -> Record:
        req = self.requests[self.next % len(self.requests)]
        self.next += 1
        rec = Record(req.kind, req.spec, due=due)
        self.records.append(rec)
        self.spans.by_query[id(req.query)] = rec
        with self.lock:
            if self.outstanding == 0 and self.idle_since is not None:
                self.idle.append((self.idle_since, time.perf_counter()))
                self.idle_since = None
            self.outstanding += 1
        fut = self.server.submit_query(req.query)
        fut.add_done_callback(lambda f, rec=rec: self._done(f, rec))
        return rec

    def _done(self, fut, rec: Record) -> None:
        rec.done = time.perf_counter()
        exc = fut.exception()
        if exc is None:
            rec.answer = fut.result()
        else:
            rec.error = exc
        with self.lock:
            self.outstanding -= 1
            if self.outstanding == 0:
                self.idle_since = rec.done
        self.on_done(rec)

    def on_done(self, rec: Record) -> None:
        pass

    def wait(self, deadline: float) -> None:
        """Block until every submitted request has answered or the
        deadline (perf_counter seconds) passes."""
        while time.perf_counter() < deadline:
            with self.lock:
                if self.outstanding == 0:
                    return
            time.sleep(0.01)


class ClosedLoop(Loop):
    """``clients`` clients with no think time: each sends its next
    request the moment its answer arrives.

    :meth:`start` enqueues every client's first request before the
    server's dispatcher starts (as ``serve_search.serve_prefilled``
    does), so the first drain is already a full one and every later drain
    takes the answers' resubmissions whole.  Warm-up runs on the same
    loop; :meth:`run` then measures whole drains of the steady state."""

    def __init__(self, server, spans, requests, clients: int):
        super().__init__(server, spans, requests)
        self.clients = clients
        self.end = float("inf")

    def start(self) -> None:
        from repro.launch.serve_search import Request
        for _ in range(self.clients):
            req = self.requests[self.next % len(self.requests)]
            self.next += 1
            now = time.perf_counter()
            rec = Record(req.kind, req.spec, due=now)
            self.records.append(rec)
            self.spans.by_query[id(req.query)] = rec
            self.outstanding += 1
            item = Request(req.kind, req.query, t_submit=now)
            item.future.add_done_callback(
                lambda f, rec=rec: self._done(f, rec))
            self.server._queue.put(item)
        self.server.start()

    def _next_drain(self, after: float, wait_s: float = 120.0) -> float:
        """Start time of the first drain that starts at or after
        ``after``."""
        give_up = time.perf_counter() + wait_s
        while time.perf_counter() < give_up:
            starts = self.spans.starts
            i = bisect.bisect_left(starts, after)
            if i < len(starts):
                return starts[i]
            time.sleep(0.001)
        raise RuntimeError(f"no drain started within {wait_s} s")

    def run(self, seconds: float) -> tuple:
        """The window runs from the start of a drain to the start of the
        first drain ``seconds`` later: whole drains, so the requests it
        completes over its length is the served rate, with no drain cut
        by its edges."""
        t0 = self._next_drain(time.perf_counter())
        time.sleep(max(0.0, t0 + seconds - time.perf_counter()))
        t1 = self._next_drain(t0 + seconds)
        self.end = t1
        return t0, t1

    def on_done(self, rec: Record) -> None:
        if rec.done < self.end:
            self._submit(rec.done)
