"""The comparison that decides ``correct`` refuses the control and a
broken program, and passes the program as it is.  Tiny sizes, on the CPU,
skipping the harness's look for a chip."""
import dataclasses

import pytest

from bench import control, run, spec


def tiny_cell(config="tdrive", mix="mixed10-closed256"):
    cfg = dict(spec.load_config(config), datasets=64)
    m = dict(spec.load_mix(mix), clients=16, max_batch=8, warm_drains=2,
             max_rate=100, check_per_kind=3)
    e2e = tuple({"name": n, "unit": u} for n, u in
                (("qps", "req/s"), ("setup_s", "s")))
    return spec.Cell("tiny", 1, cfg, m, e2e, ())


def over_limits(readings: dict, limits: dict) -> list:
    return [k for k in ("value_gap", "count_gap", "mask_diff",
                        "approx_ratio") if readings[k] > limits[k]]


def test_the_bfloat16_control_fails():
    cell = tiny_cell()
    r = control.readings(cell, seed=2**31 + 3, per_kind=3)
    assert over_limits(r, cell.config["limits"])


@pytest.fixture
def no_cache(monkeypatch):
    monkeypatch.setattr(run, "enable_compile_cache", lambda: "off")


def test_a_sound_run_is_correct(no_cache):
    res = run.run(tiny_cell(), seed=2**31 + 9, seconds=2.0, traced=False)
    assert res["correct"], res["checks"]
    assert res["attempted"] > 0 and res["failed"] == 0
    assert set(res["metrics"]) == {"qps", "setup_s"}
    assert list(res)[-1] == "checks"


def altered_answer(plan):
    """ExactHaus answers with ranks 0 and 1 swapped where produced."""
    produce = plan._run_group

    def run_group(engine, g):
        rows, ids = produce(engine, g)
        if g.op == "topk_hausdorff":
            rows = [dataclasses.replace(r, ids=r.ids[[1, 0, *range(
                2, len(r.ids))]]) for r in rows]
        return rows, ids

    return "_run_group", run_group, "value_gap"


def half_the_drain_left_out(plan):
    """Every drain answers only its first half."""
    execute = plan.execute

    def half(engine, items):
        return execute(engine, items)[:max(1, len(items) // 2)]

    return "execute", half, "missing"


@pytest.mark.parametrize("fault", [altered_answer, half_the_drain_left_out])
def test_a_broken_program_is_not_correct(no_cache, monkeypatch, fault):
    from repro.engine import plan
    name, broken, number = fault(plan)
    monkeypatch.setattr(plan, name, broken)
    monkeypatch.setattr(run, "ANSWER_WAIT_S", 2.0)
    res = run.run(tiny_cell(), seed=2**31 + 9, seconds=2.0, traced=False)
    assert not res["correct"]
    c = res["checks"][number]
    assert c["value"] > c["limit"]
