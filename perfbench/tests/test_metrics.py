"""The metric arithmetic: the window's rate, the plan's lane fill and
serial steps, and the per-layer readers."""

import numpy as np
import pytest

from perfbench import run
from perfbench.lib import registry
from perfbench.metrics import (arrivals_per_s, device_idle, host_exec_ms,
                               lane_fill, plan_ms, setup_s, step_us)


def window(grids, t_open=10.0):
    rec = run.Record({}, {})
    rec.t_open = t_open
    rec.grids = [dict(g, end=g["end"] + t_open) for g in grids]
    return rec


def test_rate_is_over_the_whole_window():
    # three grids of equal work, one slow: the rate is all arrivals over
    # all the time, not the median of per-grid rates
    grids = [{"arrivals": 100, "end": 1.0, "ok": True},
             {"arrivals": 100, "end": 2.0, "ok": True},
             {"arrivals": 100, "end": 5.0, "ok": True}]
    assert arrivals_per_s.read(window(grids)) == pytest.approx(60.0)
    per_grid = [100 / 1.0, 100 / 1.0, 100 / 3.0]
    assert arrivals_per_s.read(window(grids)) != pytest.approx(
        np.median(per_grid))


def test_failed_grid_counts_time_not_arrivals():
    grids = [{"arrivals": 100, "end": 1.0, "ok": True},
             {"arrivals": 100, "end": 2.0, "ok": False}]
    assert arrivals_per_s.read(window(grids)) == pytest.approx(50.0)
    assert arrivals_per_s.read(window([])) is None


def test_setup_is_read_from_the_record():
    rec = run.Record({}, {})
    rec.setup_s = 36.5
    assert setup_s.read(rec) == 36.5


@pytest.mark.parametrize("workload,traffic", [
    ("t9-grid", {"horizon_s": 60, "subset": {"apps": [0, 1]}}),
])
def test_lane_fill_and_steps_match_a_direct_count(workload, traffic):
    w = registry.workload(registry.benchmark(), workload)
    cfg = registry.config(w["config"])
    tr = dict(registry.traffic(w["traffic"]), **traffic)
    eng = registry.engine(cfg["engine"])
    grid = eng.realize(cfg, tr, eng.base(cfg, tr), 2 ** 40 + 7, 0)
    plan = eng.plan(cfg, grid)
    shapes = run._shapes(plan)
    real = sum(int(np.isfinite(d.arrays["times"][:d.n_real]).sum())
               for d in plan.dispatches)
    slots = sum(d.arrays["times"].size for d in plan.dispatches)
    steps = sum(d.arrays["times"].shape[1] * d.arrays["times"].shape[2]
                for d in plan.dispatches)
    assert real == grid.arrivals
    assert lane_fill.slots(shapes) == slots
    rec = run.Record(cfg, tr)
    rec.grids = [{"arrivals": grid.arrivals, "shapes": shapes,
                  "plan_s": 0.5}]
    assert lane_fill.read(rec) == pytest.approx(100.0 * real / slots)
    rec.trace = {"engine_s": 2.0, "busy_s": 3.0, "window_s": 4.0,
                 "exec_idle_s": 0.25, "exec_spans": 1}
    assert step_us.read(rec) == pytest.approx(2e6 / steps)
    assert device_idle.read(rec) == pytest.approx(25.0)
    assert host_exec_ms.read(rec) == pytest.approx(250.0)
    assert plan_ms.read(rec) == pytest.approx(500.0)


def test_readers_return_nothing_without_a_trace():
    rec = run.Record({}, {})
    rec.grids = [{"arrivals": 10, "shapes": [], "plan_s": None}]
    for m in (lane_fill, plan_ms, step_us, device_idle, host_exec_ms):
        assert m.read(rec) is None
