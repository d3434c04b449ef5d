"""The check that decides ``correct``, driven through a whole run at a
size a test can hold (the chip check skipped): a sound run passes, the
control (the reference in bfloat16 in the program's place) fails, and so
does each fault planted in the timed path."""

import pytest

from perfbench import run
from perfbench.lib import compare, faults, registry

SMALL = {
    "t9-grid": {"horizon_s": 120,
                "subset": {"cases": ["azure-like-medium"], "apps": [0]}},
}
# bfloat16 keeps 8 bits of a time, so the control needs horizons long
# enough for its rounding to reach whole seconds, as at the cell's size
CONTROL = {
    "t9-grid": {"horizon_s": 300, "subset": {"apps": [0]}},
}
SEED = 2 ** 36 + 11


def small_traffic(workload, sizes=SMALL):
    w = registry.workload(registry.benchmark(), workload)
    return dict(registry.traffic(w["traffic"]), **sizes[workload])


def small_run(workload):
    return run.run_cell(workload, SEED, 0.1, False,
                        traffic=small_traffic(workload), require_tpu=False,
                        log=lambda s: None)


@pytest.mark.parametrize("workload", sorted(SMALL))
def test_sound_run_is_correct(workload):
    out = small_run(workload)
    assert out["correct"], out["checks"]
    assert out["compiles_in_window"] == 0
    assert list(out)[-1] == "checks"


@pytest.mark.parametrize("workload", sorted(SMALL))
def test_control_is_not_correct(workload):
    w = registry.workload(registry.benchmark(), workload)
    cfg = registry.config(w["config"])
    traffic = small_traffic(workload, CONTROL)
    eng = registry.engine(cfg["engine"])
    grid = eng.realize(cfg, traffic, eng.base(cfg, traffic), SEED, 0)
    cells = [(grid.inputs[i], grid.horizon_s)
             for _, i in compare.sample(SEED, [grid])]
    values = compare.values(compare.references(cfg, cells, "bfloat16"),
                            compare.references(cfg, cells))
    ok, _ = compare.verdict(values, cfg["correct"]["limits"])
    assert not ok


@pytest.mark.parametrize("fault", faults.FAULTS)
@pytest.mark.parametrize("workload", sorted(SMALL))
def test_fault_is_not_correct(workload, fault, monkeypatch):
    import jax
    try:
        with monkeypatch.context() as m:
            faults.plant(fault, m.setattr)
            out = small_run(workload)
    finally:
        jax.clear_caches()      # no program traced with the fault survives
    assert not out["correct"], out["checks"]


def test_sample_is_one_whole_grid_drawn_from_the_seed():
    class G:
        def __init__(self, n):
            self.cells = [None] * n
    grids = [G(5), G(5), G(5), G(5)]
    draws = {compare.sample(s, grids)[0][0] for s in range(2 ** 40, 2 ** 40 + 40)}
    assert len(draws) > 1
    pairs = compare.sample(2 ** 40 + 3, grids)
    assert [i for _, i in pairs] == list(range(5))
    assert len({k for k, _ in pairs}) == 1
    assert pairs == compare.sample(2 ** 40 + 3, grids)
    assert compare.sample(7, []) == []


def test_grid_numbers_sum_the_cells_first():
    def t(e, c, f, s):
        return {"energy_j": e, "cost_usd": c, "fpga_spinups": f,
                "cpu_spinups": s, "deadline_misses": 0, "requests": 10}
    refs = [t(100.0, 10.0, 4, 6), t(100.0, 10.0, 5, 5)]
    progs = [t(110.0, 9.0, 5, 6), t(90.0, 11.0, 4, 5)]
    v = compare.values(progs, refs)
    assert v["energy_rel"] == pytest.approx(0.1)
    assert v["cost_rel"] == pytest.approx(0.1)
    assert v["spinup_rel"] == pytest.approx(0.1)
    assert v["grid_energy_rel"] == 0.0 and v["grid_cost_rel"] == 0.0
    assert v["grid_spinup_rel"] == 0.0
    v = compare.values([t(110.0, 11.0, 6, 6)], [t(100.0, 10.0, 4, 6)])
    assert v["grid_cost_rel"] == pytest.approx(0.1)
    assert v["grid_spinup_rel"] == pytest.approx(0.2)
