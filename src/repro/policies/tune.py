"""Gradient-based policy tuning: `jax.grad` through the rate simulator.

The §5.1 grid search (`ratesim.tune_fpga_dynamic`) evaluates every
integer headroom level; it scales linearly in levels and cannot tune
continuous parameters (the predictive policy's forecast gain) at all.
This module tunes `RateParams` by gradient descent instead.

Integer provisioning dynamics are piecewise-constant — their gradients
are zero almost everywhere — so the descent runs on a smooth *fluid
relaxation* of the fpga_dynamic / predictive control loop
(`relaxed_cost`): provisioning becomes a first-order lag whose speed
encodes the spin-up latency, the ceil() in the target a pass-through,
and the deadline-miss indicator a softplus of capacity shortfall.
`jax.grad` flows through the whole `lax.scan` (one interval per step).
The relaxation is dtype-agnostic on purpose: the gradient-correctness
tests re-run it in float64 (``jax.enable_x64``) to compare
against central finite differences at tight tolerance.

The continuous optimum is then *integer-refined*: a handful of nearby
integer headrooms (x candidate gains for the predictive policy) are
evaluated with the REAL simulator, together with the grid-search
optimum itself — so `tune_gradient` matches or beats
`tune_fpga_dynamic` on the true objective BY CONSTRUCTION, while
spending O(refine window) real-simulator evaluations instead of
O(max_k). benchmarks/policy_tuning.py records the comparison in
results/BENCH_sweep.json.
"""

from __future__ import annotations

import time
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.metrics import RunTotals
from repro.core.workers import FleetParams

#: One deadline miss outweighs any plausible energy saving — the grid
#: search's lexicographic (misses, then energy) order, as one scalar.
MISS_PENALTY_J = 1e9


class RelaxSpec(NamedTuple):
    """Static description of one relaxed tuning problem. All leaves are
    plain floats / arrays (no FleetParams object) so `relaxed_cost`
    stays a pure jax function of (theta, spec)."""

    demand: jnp.ndarray     # (K,) work per interval, CPU-seconds
    interval_s: float
    spin_up_s: float
    S: float                # FPGA speedup
    I_f: float              # FPGA idle W
    B_f: float              # FPGA busy W
    miss_weight: float      # J-equivalent per CPU-second of shortfall
    sharp: float            # softness knob: higher == closer to exact


def make_spec(counts, size_s: float, fleet: FleetParams,
              miss_weight: float = 2000.0, sharp: float = 4.0,
              dtype=jnp.float32) -> RelaxSpec:
    """Build a `RelaxSpec` from a per-second trace + fleet parameters."""
    interval_s = max(int(round(fleet.T_s)), 1)
    spin_up_s = max(int(round(fleet.fpga.spin_up_s)), 1)
    counts = np.asarray(counts, np.float64)
    k = len(counts) // interval_s
    demand = counts[:k * interval_s].reshape(k, interval_s).sum(1) * size_s
    return RelaxSpec(
        demand=jnp.asarray(demand, dtype), interval_s=float(interval_s),
        spin_up_s=float(spin_up_s), S=float(fleet.S),
        I_f=float(fleet.fpga.idle_w), B_f=float(fleet.fpga.busy_w),
        miss_weight=float(miss_weight), sharp=float(sharp))


def _softplus(x, sharp):
    """Smooth max(x, 0) with sharpness knob; -> relu as sharp -> inf."""
    return jax.nn.softplus(x * sharp) / sharp


def relaxed_cost(theta, spec: RelaxSpec):
    """Differentiable surrogate of the fpga_dynamic / predictive loop.

    ``theta`` is ``(headroom, gain, util)``: continuous headroom in
    workers, the predictive trend-extrapolation gain, and the
    utilization target the provisioner divides demand by (the real
    policies run at util == 1; the relaxation exposes it as a third
    tunable so the surrogate can trade idle energy against miss risk).

    Per interval: forecast ``lam_hat = lam + gain * (lam - lam_prev)``
    (the predictive policy's `_target`), target
    ``lam_hat / util + headroom``, then the FPGA count relaxes toward
    the target — upward at the spin-up-lagged rate
    ``interval / (interval + spin_up)``, downward immediately (the real
    policies reclaim within one interval). Cost is idle energy +
    spin-up energy + ``miss_weight`` x softplus capacity shortfall.
    Dtype follows ``theta``/``spec`` (float64-safe for FD tests)."""
    headroom, gain, util = theta[0], theta[1], theta[2]
    one = jnp.ones((), theta.dtype)
    interval = spec.interval_s * one
    lam = spec.demand.astype(theta.dtype) / (spec.S * interval)  # FPGA units
    alpha_up = interval / (interval + spec.spin_up_s)

    def step(carry, lam_k):
        n, lam_prev = carry
        lam_hat = lam_k + gain * (lam_k - lam_prev)
        target = lam_hat / util + headroom
        delta = target - n
        w_up = jax.nn.sigmoid(spec.sharp * delta)
        n_new = n + (w_up * alpha_up + (1.0 - w_up)) * delta
        idle_j = spec.I_f * interval * _softplus(n_new - lam_k, spec.sharp)
        spin_j = spec.B_f * spec.spin_up_s * _softplus(delta, spec.sharp)
        short = _softplus(lam_k - n_new, spec.sharp)      # FPGA-units short
        cost = idle_j + spin_j + spec.miss_weight * short * spec.S * interval
        return (n_new, lam_k), cost

    init = (lam[0] + headroom, lam[0])
    _, costs = jax.lax.scan(step, init, lam)
    return jnp.sum(costs)


relaxed_grad = jax.grad(relaxed_cost)


def fit(spec: RelaxSpec, theta0=(0.0, 1.0, 0.9), steps: int = 300,
        lr: float = 0.1):
    """Adam on `relaxed_cost`. Returns (theta, loss_curve). Projection
    after each step keeps theta in the domain the real policies accept
    (headroom >= 0, gain in [0, 4], util in [0.5, 1])."""
    theta = jnp.asarray(theta0, spec.demand.dtype)
    lo = jnp.asarray([0.0, 0.0, 0.5], theta.dtype)
    hi = jnp.asarray([1e6, 4.0, 1.0], theta.dtype)
    b1, b2, eps = 0.9, 0.999, 1e-8

    @jax.jit
    def step(theta, m, v, t):
        g = relaxed_grad(theta, spec)
        m = b1 * m + (1 - b1) * g
        v = b2 * v + (1 - b2) * g * g
        mhat = m / (1 - b1 ** t)
        vhat = v / (1 - b2 ** t)
        theta = theta - lr * mhat / (jnp.sqrt(vhat) + eps)
        return jnp.clip(theta, lo, hi), m, v

    m = jnp.zeros_like(theta)
    v = jnp.zeros_like(theta)
    losses = []
    for t in range(1, steps + 1):
        losses.append(float(relaxed_cost(theta, spec)))
        theta, m, v = step(theta, m, v, t)
    losses.append(float(relaxed_cost(theta, spec)))
    return theta, losses


class TuneResult(NamedTuple):
    """Outcome of `tune_gradient` (all real-simulator numbers)."""

    headroom: int           # selected integer headroom (workers)
    gain: float             # selected forecast gain (1.0 for fpga_dynamic)
    totals: RunTotals       # real-simulator totals at the selection
    objective: float        # energy_j + MISS_PENALTY_J * misses
    theta: tuple            # continuous optimum (headroom, gain, util)
    losses: tuple           # surrogate loss curve (monitoring)
    grid_headroom: int      # §5.1 grid-search optimum, for comparison
    grid_objective: float
    source: str             # "gradient" (refined point won) | "grid"
    n_sim_evals: int        # real-simulator evaluations spent refining


def objective_of(tot: RunTotals) -> float:
    """Scalar true objective: energy with a lexicographic-scale miss
    penalty, so zero-miss always beats any-miss (the grid search's
    selection rule)."""
    return float(tot.energy_j) + MISS_PENALTY_J * float(tot.deadline_misses)


def tune_gradient(counts, size_s: float, fleet: FleetParams,
                  policy: str = "fpga_dynamic", n_max: int = 512,
                  steps: int = 300, lr: float = 0.1,
                  miss_weight: float = 2000.0) -> TuneResult:
    """Gradient-tune a rate policy's `RateParams` on one trace.

    Descends `relaxed_cost`, integer-refines the continuous optimum
    with real-simulator evaluations (a +/-1 window of headrooms, x3
    gains for the predictive policy), and compares against the §5.1
    grid-search optimum — which joins the candidate set, so the result
    matches or beats `tune_fpga_dynamic` on `objective_of` by
    construction."""
    from repro.sim import ratesim

    spec = make_spec(counts, size_s, fleet, miss_weight=miss_weight)
    theta, losses = fit(spec, steps=steps, lr=lr)
    h_star, g_star = float(theta[0]), float(theta[1])

    grid_h, grid_tot = ratesim.tune_fpga_dynamic(counts, size_s, fleet,
                                                 n_max=n_max)
    grid_obj = objective_of(grid_tot)

    # Refine window: around the continuous optimum AND just below the
    # grid optimum — the grid only samples unit-sized multiples, so the
    # true integer optimum often sits between (k-1) and k units; probing
    # it is how the gradient path *beats* (not just matches) the grid.
    heads = sorted({max(h, 0) for h in
                    (int(np.floor(h_star)), int(np.ceil(h_star)),
                     int(np.ceil(h_star)) + 1,
                     int(grid_h) - 2, int(grid_h) - 1)})
    gains = ((1.0,) if policy != "predictive"
             else tuple(sorted({1.0, round(g_star, 3)})))
    best = (grid_obj, int(grid_h), 1.0, grid_tot, "grid")
    n_evals = 0
    for h in heads:
        for g in gains:
            tot = ratesim.simulate(policy, counts, size_s, fleet,
                                   headroom=h, n_max=n_max,
                                   forecast_gain=g)
            n_evals += 1
            obj = objective_of(tot)
            if obj < best[0]:
                best = (obj, h, g, tot, "gradient")

    obj, h, g, tot, source = best
    return TuneResult(
        headroom=h, gain=g, totals=tot, objective=obj,
        theta=tuple(float(x) for x in theta), losses=tuple(losses),
        grid_headroom=int(grid_h), grid_objective=grid_obj,
        source=source, n_sim_evals=n_evals)
