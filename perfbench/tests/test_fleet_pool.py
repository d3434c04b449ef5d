"""The FaaS pool cell (`spork-azfn-pool`, engine `perfbench.engines.fleet`)
at sizes a test can hold, with the chip check skipped.

* The plain reference (`perfbench.lib.reference_fleet`) gives the
  program's serial oracle `repro.fleet.FleetSim` exactly on small cells
  of dyadic times, sizes and bucket knobs, under both admissions (with
  the oracle's predictor in place of its own, as
  `test_reference.py` does for the single-app reference).
* `sweep_fleet` agrees with the reference within the configuration's
  limits on a seeded 64-tenant, 300 s cut; the control (the reference in
  bfloat16) and each fault planted in the fleet path do not.
* Every grid of a run has the same plan shapes; the generator's output
  is pinned by checksums; unknown traffic keys are refused.
"""

import hashlib
import json
import os

import numpy as np
import pytest

from perfbench import run
from perfbench.engines import fleet as fe
from perfbench.lib import compare, faults_fleet, registry

CFG = registry.config("spork-azfn-pool")
TRAFFIC = registry.traffic("pool-grid")
SEED = 2 ** 37 + 23
# 64 apps at 10 invocations a second over 300 s: one population's six
# cells, on the configuration's own worker table and bucket knobs
SMALL_CFG = dict(CFG, tenants=64, invocations_per_s=10.0)
SMALL_TRAFFIC = dict(TRAFFIC, horizon_s=300, subset={"populations": [0]})
CHECKSUMS = os.path.join(registry.BENCH_DIR, "data",
                         "fleet_generator_checksums.json")


def small_run():
    return run.run_cell("azfn-pool-grid", SEED, 0.1, False, config=SMALL_CFG,
                        traffic=SMALL_TRAFFIC, require_tpu=False,
                        log=lambda s: None)


# ---------------------------------------------- reference against oracle
def oracle_predict(fleet):
    """The reference's `Predictor.predict`, answered by the oracle's."""
    from repro.sim.events import EventSim

    def predict(self, n_prev, n_curr):
        p = EventSim(fleet, 0.05, n_max=self.n_max).predictor
        p.H, p.life_sum, p.life_cnt = self.H, self.life_sum, self.life_cnt
        return p.predict(n_prev, n_curr)
    return predict


def dyadic_population(horizon: int = 120):
    """Six apps on the 1/8 s grid, dyadic sizes and weights, each with a
    burst of equal times that empties a bucket."""
    rng = np.random.default_rng(17)
    sizes = np.array([0.0625, 0.125, 0.25, 0.5, 0.125, 0.0625])
    weights = np.array([0.125, 0.25, 0.5, 1.0, 2.0, 0.125])
    streams = []
    for i in range(6):
        t = rng.integers(0, horizon * 8, 40 + 30 * i) / 8.0
        burst = np.full(12, float(rng.integers(1, horizon)))
        streams.append(np.sort(np.concatenate([t, burst])))
    return streams, sizes, weights


@pytest.mark.parametrize("adm", CFG["admissions"], ids=lambda a: a["name"])
@pytest.mark.parametrize("disp", CFG["dispatchers"])
def test_reference_matches_the_fleet_oracle(disp, adm, monkeypatch):
    from repro.fleet import FleetCell, TenantSpec, simulate_fleet
    from perfbench.lib import reference as ref
    streams, sizes, weights = dyadic_population()
    fleet = fe.fleet_params(CFG)
    cell = FleetCell(tenants=tuple(
        TenantSpec(arrival_times=s, request_size_s=float(z), weight=float(w))
        for s, z, w in zip(streams, sizes, weights)),
        dispatcher=disp, admission=fe.admission_policy(adm), fleet=fleet,
        horizon_s=120.0)
    want, _ = simulate_fleet(cell, n_max=CFG["n_max"])
    bounds = np.concatenate([[0], np.cumsum([len(s) for s in streams])])
    inputs = (*fe.merge(np.concatenate(streams), bounds), sizes,
              CFG["deadline_mult"] * sizes, weights, disp, adm)
    monkeypatch.setattr(ref.Predictor, "predict", oracle_predict(fleet))
    got = fe.reference_of(CFG, inputs, 120.0)
    shed = want.breakdown["shed_requests"]
    assert got["requests"] == want.breakdown["offered_requests"]
    assert got["deadline_misses"] == want.deadline_misses + shed
    for k in ("fpga_spinups", "cpu_spinups"):
        assert got[k] == getattr(want, k), k
    for k in ("energy_j", "cost_usd"):
        assert got[k] == pytest.approx(getattr(want, k), rel=1e-12, abs=0)
    assert (shed > 0) == (adm["name"] == "token_bucket")


# ----------------------------------------------------- the check, small
def test_sound_run_is_correct():
    out = small_run()
    assert out["correct"], out["checks"]
    assert out["failed"] == 0 and out["compiles_in_window"] == 0
    assert out["attempted"] == 6


def test_control_is_not_correct():
    grid = fe.realize(SMALL_CFG, SMALL_TRAFFIC,
                      fe.base(SMALL_CFG, SMALL_TRAFFIC), SEED, 0)
    cells = [(grid.inputs[i], grid.horizon_s)
             for _, i in compare.sample(SEED, [grid])]
    values = compare.values(
        compare.references(SMALL_CFG, cells, "bfloat16"),
        compare.references(SMALL_CFG, cells))
    ok, _ = compare.verdict(values, CFG["correct"]["limits"])
    assert not ok


@pytest.mark.parametrize("fault", faults_fleet.FAULTS)
def test_fault_is_not_correct(fault, monkeypatch):
    import jax
    try:
        with monkeypatch.context() as m:
            faults_fleet.plant(fault, m.setattr)
            out = small_run()
    finally:
        jax.clear_caches()      # no program traced with the fault survives
    assert not out["correct"], out["checks"]


# ------------------------------------------------------ grids and streams
def test_grid_shapes_are_the_same_on_every_grid():
    streams = fe.base(SMALL_CFG, SMALL_TRAFFIC)
    shapes, counters = set(), set()
    for k in range(4):
        plan = fe.plan(SMALL_CFG, fe.realize(SMALL_CFG, SMALL_TRAFFIC,
                                             streams, SEED, k))
        shapes.add(tuple((d.chunk, *d.arrays["times"].shape)
                         for d in plan.dispatches))
        counters.add(tuple(v for k_, v in plan.meta.items()
                           if k_ != "plan_id"))
    assert len(shapes) == 1 and len(counters) == 1


def test_grid_inputs_lead_with_the_merged_times():
    grid = fe.realize(SMALL_CFG, SMALL_TRAFFIC,
                      fe.base(SMALL_CFG, SMALL_TRAFFIC), SEED, 1)
    from repro.fleet import resolve_fleet_cell
    for cell, inp in zip(grid.cells, grid.inputs):
        rs = resolve_fleet_cell(cell)
        np.testing.assert_array_equal(inp[0], rs.times)
        np.testing.assert_array_equal(inp[1], rs.tids)
    assert grid.arrivals == sum(len(i[0]) for i in grid.inputs)


@pytest.fixture(scope="module")
def full_base():
    return fe.base(CFG, TRAFFIC)


def digests(pops) -> dict:
    out = {}
    for p, pop in enumerate(pops):
        h = hashlib.sha256()
        for a in (pop.rates, pop.sizes, pop.app, pop.second, pop.count):
            h.update(np.ascontiguousarray(a).tobytes())
        times, _ = fe.realize_population(pop, 30, CFG["horizon_s"],
                                         fe.gen.rng_for(2 ** 35 + 1, 4, p))
        out[f"population{p}"] = {
            "base": h.hexdigest(), "arrivals": int(pop.count.sum()),
            "realization": hashlib.sha256(times.tobytes()).hexdigest()}
    return out


def test_generators_match_checksums(full_base):
    with open(CHECKSUMS) as f:
        want = json.load(f)["spork-azfn-pool"]
    assert digests(full_base) == want


def test_population_keeps_the_trace_skew(full_base):
    """The rate fit's skew (top 18.6% of apps make 99.6% of invocations
    in the source) survives the scaling; sizes keep their bounds."""
    law = CFG["size_law"]
    for pop in full_base:
        r = np.sort(pop.rates)[::-1]
        assert r.sum() == pytest.approx(CFG["invocations_per_s"])
        assert r[:int(0.186 * len(r))].sum() / r.sum() > 0.99
        assert law["min_s"] <= pop.sizes.min() <= pop.sizes.max() \
            <= law["max_s"]
        assert len(pop.rates) == CFG["tenants"]


@pytest.mark.parametrize("key,sub", [("loop", None), ("clients", None),
                                     (None, "tenants"), (None, "cases")])
def test_traffic_key_not_implemented_is_refused(key, sub):
    traffic = dict(TRAFFIC, horizon_s=60)
    if key:
        traffic[key] = 1
    else:
        traffic["subset"] = {sub: [0]}
    with pytest.raises(ValueError, match=key or sub):
        fe.base(CFG, traffic)
