"""One run of one benchmark cell on the chip.

    python3 -m bench.run --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Builds the cell's lake from the seed, indexes it with the program's
``build_repository``, serves it through ``SearchServer`` over a
``QueryEngine`` (the normal path: ``submit_query`` -> ``engine.search``
on the local dispatcher), warms up every shape the cell's traffic uses,
then measures a window of ``--seconds``.  After the window every answer
is awaited, the program is freed, and a seeded sample of the window's
answers is compared with the plain reference (``bench/reference.py``).

Standard error carries the set-up split, the window's compile count and,
as its last lines, each compared number beside its limit.  The last line
of standard output is one JSON object: ``correct``, ``attempted`` and
``failed`` (the requests the run sent, warm-up included, and those of
them that raised or never answered), ``metrics`` (the cell's end-to-end
metrics, or with ``--trace 1`` its per-layer metrics), ``device``,
``breakdown`` (traced runs) and ``checks``.  Exits 1 with no result where JAX finds no
accelerator or fewer chips than the cell asks for.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import gc  # noqa: E402
import json  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
from dataclasses import dataclass  # noqa: E402

import numpy as np  # noqa: E402

from bench import data, drive, reference, spec, traffic  # noqa: E402
from bench import trace as trace_lib  # noqa: E402
from bench.metrics import reader  # noqa: E402

sys.path.insert(0, str(spec.ROOT / "src"))

#: how long past the window's close an answer is awaited
ANSWER_WAIT_S = 60.0


def log(msg: str) -> None:
    print(f"[bench] {msg}", file=sys.stderr, flush=True)


class CompileLog:
    """Backend-compile seconds and persistent-cache hits and misses, from
    JAX's monitoring events."""

    def __init__(self):
        import jax
        from jax._src import dispatch
        self.seconds, self.hits, self.misses = 0.0, 0, 0
        self._event = dispatch.BACKEND_COMPILE_EVENT
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._count)

    def _duration(self, event, seconds, **_):
        if event == self._event:
            self.seconds += seconds

    def _count(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.hits += 1
        elif event == "/jax/compilation_cache/cache_misses":
            self.misses += 1

    def snapshot(self):
        return self.seconds, self.hits, self.misses


def enable_compile_cache() -> str:
    """The program's persistent cache, with every program written to it:
    the op-by-op build compiles hundreds of small programs, each under
    JAX's default one-second threshold."""
    import jax
    from repro import compile_cache
    where = compile_cache.enable()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return where


@dataclass
class Run:
    """What the metric readers read."""
    records: list          # requests due in the window
    done: np.ndarray       # when each answer of the run reached the client
    spans: list            # engine.search calls started in the window
    window: tuple          # (start, end), perf_counter seconds
    setup_s: float
    trace: object = None   # trace_lib.Reduced of a traced run


def run(cell: spec.Cell, seed: int, seconds: float, traced: bool) -> dict:
    import jax
    from repro.core.build import build_repository
    from repro.engine import QueryEngine
    from repro.launch.serve_search import SearchServer

    cfg, mix = cell.config, cell.mix
    cache_dir = enable_compile_cache()
    compiles = CompileLog()
    if mix["loop"] != "closed":
        raise ValueError(f"unknown loop {mix['loop']!r}: the driver runs "
                         f"closed loops")

    # -- set-up: data, requests, build, warm-up ---------------------------
    t = time.perf_counter()
    lake = data.trajectories(cfg, seed)
    n_window = int(mix["max_rate"] * seconds) + mix["clients"]
    n_warm = mix["clients"] * (int(mix["warm_drains"]) + 1)
    stream = traffic.requests(mix, cfg, lake, seed, n_warm + n_window)
    for r in stream:
        r.query = traffic.to_query(r.spec)
    warm, window = stream[:n_warm], stream[n_warm:]
    t_data = time.perf_counter() - t

    c0 = compiles.snapshot()
    t = time.perf_counter()
    repo, info = build_repository(lake.datasets(),
                                  leaf_capacity=int(cfg["leaf_capacity"]),
                                  theta=int(cfg["theta"]))
    jax.block_until_ready(repo)
    engine = QueryEngine(repo, leaf_capacity=int(cfg["leaf_capacity"]))
    t_build = time.perf_counter() - t
    c1 = compiles.snapshot()

    t = time.perf_counter()
    spans = drive.SearchSpans(engine)
    server = SearchServer(engine, max_batch=int(mix.get("max_batch", 64)))
    loop = drive.ClosedLoop(server, spans, warm + window, int(mix["clients"]))
    loop.start()
    while len(spans.spans) < int(mix["warm_drains"]):
        time.sleep(0.005)
    t_warm = time.perf_counter() - t
    c2 = compiles.snapshot()
    # what set-up left on the heap (the lake, the request pool, the
    # program's build) is kept out of the collector's passes in the window
    gc.collect()
    gc.freeze()
    setup_s = time.perf_counter() - T_START
    log(f"setup: {setup_s:.3f} s = data {t_data:.3f} s + build "
        f"{t_build:.3f} s + warm-up {t_warm:.3f} s + imports and the rest; "
        f"{info['n_datasets']} datasets in {info['n_slots']} slots; "
        f"outlier threshold {float(info['outlier_threshold'])!r}; "
        f"compile cache {cache_dir}: build {c1[1] - c0[1]} hits / "
        f"{c1[2] - c0[2]} misses ({c1[0] - c0[0]:.3f} s backend compile), "
        f"warm-up {c2[1] - c1[1]} hits / {c2[2] - c1[2]} misses "
        f"({c2[0] - c1[0]:.3f} s), whole set-up {c2[1]} hits / {c2[2]} misses")

    # -- the measured window ----------------------------------------------
    log_dir = tempfile.mkdtemp(prefix="bench-trace-") if traced else None
    if traced:
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 1
        jax.profiler.start_trace(log_dir, profiler_options=opts)
        spans.annotate = True
    n_spans0 = len(spans.spans)
    n_rec0 = len(loop.records)
    t0, t1 = loop.run(seconds)
    if traced:
        spans.annotate = False
        jax.profiler.stop_trace()
    c3 = compiles.snapshot()
    gc.unfreeze()
    loop.wait(t1 + ANSWER_WAIT_S)
    server.stop()
    spans.restore()
    if loop.next > len(loop.requests):
        log(f"the request pool ran out: {loop.next} requests sent from "
            f"{len(loop.requests)}; raise the mix's max_rate")
    records = [r for r in loop.records[n_rec0:] if t0 <= r.due <= t1]
    in_window = [s for s in spans.spans[n_spans0:] if t0 <= s.start <= t1]
    log(f"window: {len(records)} requests due, {len(in_window)} drains; "
        f"compiles inside it: {c3[2] - c2[2]} cache misses, "
        f"{c3[1] - c2[1]} cache hits, {c3[0] - c2[0]:.3f} s backend compile")
    reduced = None
    if traced:
        tr = trace_lib.read_xplane(log_dir)
        shutil.rmtree(log_dir, ignore_errors=True)
        offset = trace_lib.align(
            tr.search_ns, [(s.start, s.end) for s in in_window])
        if offset is None:
            raise RuntimeError("the trace holds no engine.search annotation")
        ns = lambda ivs: [(a * 1e9 + offset, b * 1e9 + offset)
                          for a, b in ivs]
        idle = list(loop.idle)
        if loop.outstanding == 0 and loop.idle_since is not None:
            idle.append((loop.idle_since, t1))
        reduced = trace_lib.reduce(
            tr.ops, tr.modules, (t0 * 1e9 + offset, t1 * 1e9 + offset),
            ns([(s.start, s.end) for s in in_window]), ns(idle))
        log(f"trace: busy {reduced.busy_s:.6f} s of {reduced.window_s:.6f} s"
            f" on {len(tr.ops)} device(s); planes {tr.planes}; "
            f"{len(tr.search_ns)} search annotations")

    done = np.asarray([r.done for r in loop.records if r.error is None])
    run_data = Run(records, done, in_window, (t0, t1), setup_s, reduced)
    wanted = cell.per_layer if traced else cell.end_to_end
    metrics = {}
    for m in wanted:
        value = reader(m["name"])(run_data)
        if value is not None:
            metrics[m["name"]] = {"value": float(value), "unit": m["unit"]}

    devices = jax.devices()
    peak = max((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
               for d in devices)
    device = {"platform": devices[0].platform, "kind": devices[0].device_kind,
              "count": len(devices), "memory_peak_bytes": int(peak)}
    if reduced is not None:
        device["busy_s"] = reduced.busy_s
        device["window_s"] = reduced.window_s

    # -- correctness, once the program is freed -----------------------------
    # every request the run sent, warm-up included, has to answer: a lost
    # one also takes its client out of the closed loop
    sent = loop.records
    n_sent = len(sent)
    failed = sum(1 for r in sent if r.error is not None or r.done != r.done)
    for r in sent:
        if r.error is not None:
            log(f"request {r.kind} failed: {r.error!r}")
    del engine, server, repo, spans, loop, sent
    gc.collect()
    t = time.perf_counter()
    checks = check(cfg, mix, lake, records, seed, failed)
    log(f"reference: {time.perf_counter() - t:.3f} s")
    correct = all(c["value"] <= c["limit"] for c in checks.values())

    result = {"correct": bool(correct and records), "attempted": n_sent,
              "failed": failed, "metrics": metrics, "device": device}
    if reduced is not None:
        result["breakdown"] = {"device_ops": reduced.device_ops,
                               "idle_gaps": reduced.idle_gaps}
    result["checks"] = checks
    for name, c in checks.items():
        log(f"check {name}: {c['value']!r} limit {c['limit']!r}")
    return result


def check(cfg, mix, lake, records, seed, failed) -> dict:
    """The compared numbers over a seeded sample of the window's answers
    (``check_per_kind`` of each kind), each with its limit.  The answers
    are judged against every lake an admissible outlier threshold leaves
    (``reference.Judge``), on the one that fits them best."""
    rng = np.random.default_rng([seed, 11])
    by_kind = {}
    for r in records:
        if r.error is None and r.done == r.done:
            by_kind.setdefault(r.kind, []).append(r)
    ref = reference.RefLake(lake.points, lake.lengths, cfg)
    judge = reference.Judge(ref)
    per = int(mix["check_per_kind"])
    for kind, recs in sorted(by_kind.items()):
        pick = rng.choice(len(recs), size=min(per, len(recs)), replace=False)
        for i in sorted(pick):
            s = recs[i].spec
            judge.add((kind, i), s, reference.semantic(ref, s,
                                                       recs[i].answer))
    limits = cfg["limits"]
    v, per_answer = judge.verdict(limits)
    gaps = reference.total(per_answer)
    version = judge.versions[v]
    log(f"outlier threshold: the reference's knee {float(ref.knee)!r}, "
        f"{len(judge.versions)} admissible lakes, judged on the one at "
        f"{version.threshold!r}")
    per_kind = {}
    for ((kind, i), s, got), g in zip(judge.seen, per_answer):
        per_kind.setdefault(kind, []).append(g)
        if reference.over_limits(g, limits):
            log(f"over a limit: {kind} #{i}: {g}; spec "
                f"{reference.describe(s)}; got {got!r}; reference "
                f"{reference.answer(version, s)!r}")
    for kind, gs in sorted(per_kind.items()):
        log(f"kind {kind}: {len(gs)} compared, {reference.total(gs)}")
    values = {"value_gap": gaps.value_gap, "count_gap": gaps.count_gap,
              "mask_diff": gaps.mask_diff, "approx_ratio": gaps.approx_ratio,
              "missing": failed}
    return {k: {"value": v, "limit": limits[k]} for k, v in values.items()}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    cell = spec.load_cell(args.workload)

    import jax
    devices = jax.devices()
    if devices[0].platform == "cpu" or len(devices) < cell.chips:
        log(f"needs {cell.chips} accelerator chip(s); JAX found "
            f"{len(devices)} {devices[0].platform} device(s)")
        return 1
    result = run(cell, args.seed, args.seconds, bool(args.trace))
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
