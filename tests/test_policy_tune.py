"""Gradient tuning correctness: `jax.grad` vs finite differences, and
convergence against the §5.1 grid search.

The relaxation (`repro.policies.tune.relaxed_cost`) is dtype-agnostic
so the derivative checks run in float64 (`jax.enable_x64`)
where central differences are trustworthy to ~1e-6: the analytic
gradient through the whole `lax.scan` must match central FD on every
tuned parameter (headroom, forecast gain, utilization target) at
multiple points. The end-to-end tuner must then match or beat
`tune_fpga_dynamic` / `tune_fpga_dynamic_cells` on the true
(real-simulator) objective — by construction, the contract
benchmarks/policy_tuning.py records.
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.core.traces import synthetic_trace
from repro.core.workers import DEFAULT_FLEET
from repro.policies import tune
from repro.sim.ratesim import tune_fpga_dynamic
from repro.sim.sweep import SweepCell, tune_fpga_dynamic_cells


def _trace(seed=3, bias=0.65):
    return synthetic_trace(seed=seed, bias=bias, horizon_s=600,
                           request_size_s=0.05, mean_demand_workers=100.0)


# points spanning the domain: at/near init, mid-descent, near bounds
THETAS = [(0.5, 0.0, 0.9), (2.3, 0.7, 0.85), (7.0, 1.5, 0.65)]


@pytest.mark.parametrize("theta0", THETAS)
def test_grad_matches_central_fd_all_params(theta0):
    """Analytic `jax.grad` vs central finite differences on every tuned
    parameter, in float64 where FD error ~h^2 is far below tolerance."""
    tr = _trace()
    with jax.enable_x64(True):
        spec = tune.make_spec(tr.counts, tr.request_size_s, DEFAULT_FLEET,
                              dtype=jnp.float64)
        theta = jnp.asarray(theta0, jnp.float64)
        g = np.asarray(tune.relaxed_grad(theta, spec))
        assert g.shape == (3,)
        h = 1e-5
        for i in range(3):
            e = np.zeros(3)
            e[i] = h
            fp = float(tune.relaxed_cost(theta + e, spec))
            fm = float(tune.relaxed_cost(theta - e, spec))
            fd = (fp - fm) / (2 * h)
            np.testing.assert_allclose(
                g[i], fd, rtol=5e-4, atol=1e-3,
                err_msg=f"param {i} ({['headroom', 'gain', 'util'][i]}) "
                        f"at theta={theta0}")


def test_grad_is_informative_on_every_param():
    """No dead parameters: each of the three tuned params moves the
    surrogate (the reason the relaxation exists — the integer dynamics
    have zero gradient almost everywhere)."""
    tr = _trace()
    with jax.enable_x64(True):
        spec = tune.make_spec(tr.counts, tr.request_size_s, DEFAULT_FLEET,
                              dtype=jnp.float64)
        g = np.asarray(tune.relaxed_grad(
            jnp.asarray([2.0, 0.5, 0.9], jnp.float64), spec))
    assert np.all(np.abs(g) > 0.0), g


def test_fit_decreases_surrogate_loss():
    tr = _trace()
    spec = tune.make_spec(tr.counts, tr.request_size_s, DEFAULT_FLEET)
    theta, losses = tune.fit(spec, steps=60)
    assert losses[-1] < losses[0]
    assert np.isfinite(losses).all()
    th = np.asarray(theta)
    assert th[0] >= 0.0 and 0.0 <= th[1] <= 4.0 and 0.5 <= th[2] <= 1.0


@pytest.mark.parametrize("policy", ["fpga_dynamic", "predictive"])
def test_tune_gradient_matches_or_beats_grid(policy):
    """Convergence contract: on the true objective (energy +
    lexicographic miss penalty) the gradient tuner never loses to the
    grid search, for the grid's own policy AND the predictive policy
    the grid cannot tune."""
    tr = _trace()
    res = tune.tune_gradient(tr.counts, tr.request_size_s, DEFAULT_FLEET,
                             policy=policy, steps=80)
    assert res.objective <= res.grid_objective
    assert res.totals.deadline_misses == 0
    assert res.source in ("gradient", "grid")
    assert res.n_sim_evals >= 1
    assert len(res.losses) >= 2 and res.losses[-1] < res.losses[0]


def test_tune_gradient_matches_batched_grid_cells():
    """Against the batched grid path (`tune_fpga_dynamic_cells`): the
    per-trace gradient result is never worse than the sweep-engine
    grid optimum on the true objective."""
    tr = _trace(seed=1, bias=0.55)
    cells = [SweepCell("fpga_dynamic", tr.counts, tr.request_size_s,
                       DEFAULT_FLEET)]
    (grid_h, grid_tot), = tune_fpga_dynamic_cells(cells)
    res = tune.tune_gradient(tr.counts, tr.request_size_s, DEFAULT_FLEET,
                             steps=80)
    assert res.objective <= tune.objective_of(grid_tot)
    # both paths answer the same question; the serial and batched grid
    # searches agree with each other (test_sweep), so the gradient
    # result must also never lose to the serial one
    sh, stot = tune_fpga_dynamic(tr.counts, tr.request_size_s,
                                 DEFAULT_FLEET)
    assert res.objective <= tune.objective_of(stot)
    assert grid_h == sh


def test_objective_is_lexicographic_in_misses():
    """One miss must outweigh any energy saving the tuner can find."""
    a = tune.MISS_PENALTY_J
    assert a >= 1e8
    t0 = type("T", (), {"energy_j": 1e7, "deadline_misses": 0})
    t1 = type("T", (), {"energy_j": 0.0, "deadline_misses": 1})
    assert tune.objective_of(t0) < tune.objective_of(t1)
