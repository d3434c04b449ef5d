"""The plain reference against the program's own serial oracle
(`repro.sim.events.EventSim`, float64 except its jitted float32
predictor). With the oracle's predictor put in place of its own, the
reference gives the oracle's totals exactly, cell for cell: the copy of
the semantics is faithful, and where the two differ with their own
predictors the cause is the predictor's precision (a near-tie in its
argmin), which is also what separates the float32 engine from the
float64 reference."""

import numpy as np
import pytest

from perfbench.engines import events as ev
from perfbench.lib import reference as ref
from perfbench.lib import registry

CFG = registry.config("spork-table9-des")
HORIZON = 300
SEED = 2 ** 41 + 9


def oracle_predict(fleet):
    """The reference's `Predictor.predict`, answered by the oracle's."""
    from repro.sim.events import EventSim

    def predict(self, n_prev, n_curr):
        p = EventSim(fleet, 0.05, n_max=self.n_max).predictor
        p.H, p.life_sum, p.life_cnt = self.H, self.life_sum, self.life_cnt
        return p.predict(n_prev, n_curr)
    return predict


@pytest.fixture(scope="module")
def grid():
    traffic = dict(registry.traffic("full-grid"), horizon_s=HORIZON,
                   subset={"apps": [0]})
    return ev.realize(CFG, traffic, ev.base(CFG, traffic), SEED, 0)


@pytest.mark.parametrize("i", range(9))
def test_reference_matches_the_serial_oracle(grid, i, monkeypatch):
    from repro.sim.events import simulate_events
    fleet = ev.fleet_params(CFG)
    times, size, dl, disp = grid.inputs[i]
    want = simulate_events(times, size, fleet, dispatcher=disp,
                           horizon_s=float(HORIZON), deadline_s=dl,
                           n_max=CFG["n_max"])
    monkeypatch.setattr(ref.Predictor, "predict", oracle_predict(fleet))
    got = ev.reference_of(CFG, grid.inputs[i], float(HORIZON))
    for k in ("requests", "deadline_misses", "fpga_spinups", "cpu_spinups"):
        assert got[k] == getattr(want, k), (grid.labels[i], k)
    for k in ("energy_j", "cost_usd"):
        assert got[k] == pytest.approx(getattr(want, k), rel=1e-12,
                                       abs=0), (grid.labels[i], k)
    assert want.fpga_spinups > 0 and np.isfinite(got["energy_j"])
