import math

import jax.numpy as jnp
import numpy as np
import pytest
try:
    from hypothesis import given, settings, strategies as st
except ImportError:    # environment without hypothesis: local shim
    from _hypothesis_shim import given, settings, strategies as st

from repro.core.breakeven import ObjectiveCoeffs, energy_coeffs
from repro.core.predictor import (Predictor, amortization_vector,
                                  expected_objective_jnp)
from repro.core.workers import DEFAULT_FLEET


def brute_force_expected(hist, coeffs, amort):
    """Literal Alg. 2 inner loops."""
    n = len(hist)
    total = hist.sum()
    out = np.full(n, np.inf)
    nz = np.nonzero(hist)[0]
    if len(nz) == 0:
        return out
    for cand in range(nz.min(), nz.max() + 1):
        e = amort[cand]
        for b in range(n):
            if hist[b] == 0:
                continue
            p = hist[b] / total
            if cand > b:
                e += p * (coeffs.co_over * (cand - b) + coeffs.co_min * b)
            elif cand < b:
                e += p * (coeffs.co_min * cand + coeffs.co_under * (b - cand))
            else:
                e += p * coeffs.co_min * cand
        out[cand] = e
    return out


@given(data=st.data())
@settings(max_examples=30, deadline=None)
def test_expected_objective_matches_bruteforce(data):
    n = 24
    hist = np.array(data.draw(st.lists(st.integers(0, 5), min_size=n,
                                       max_size=n)), dtype=np.float64)
    coeffs = energy_coeffs(DEFAULT_FLEET)
    amort = np.linspace(0, 100, n)
    got = np.asarray(expected_objective_jnp(jnp.asarray(hist), coeffs,
                                            jnp.asarray(amort)))
    want = brute_force_expected(hist, coeffs, amort)
    if hist.sum() == 0:
        assert np.all(np.isinf(got))
    else:
        mask = np.isfinite(want)
        np.testing.assert_allclose(got[mask], want[mask], rtol=1e-4)
        assert np.all(np.isinf(got[~mask]))


def test_amortization_vector():
    n = 8
    Ts = 10.0
    life_sum = np.array([40.0, 0, 10, 0, 0, 0, 0, 0])
    life_cnt = np.array([2.0, 0, 1, 0, 0, 0, 0, 0])
    amort = np.asarray(amortization_vector(jnp.asarray(life_sum),
                                           jnp.asarray(life_cnt),
                                           jnp.asarray(1), Ts, 500.0))
    # levels: 0 -> life 20 (2 epochs) but below n_curr=1 so not charged;
    # level 1 -> no data -> 1 epoch -> 500; level 2 -> life 10 -> 1 epoch
    assert amort[0] == 0 and amort[1] == 0
    np.testing.assert_allclose(amort[2], 500.0)
    np.testing.assert_allclose(amort[3], 1000.0)
    np.testing.assert_allclose(amort[4], 1500.0)


def test_empty_histogram_falls_back_to_prev():
    p = Predictor(16, energy_coeffs(DEFAULT_FLEET), DEFAULT_FLEET.T_s)
    assert p.predict(n_prev=5, n_curr=3) == 5


def test_peaked_histogram_prediction():
    """With a delta-function history the predictor must allocate exactly
    that count (over- and under-allocation both cost more)."""
    p = Predictor(32, energy_coeffs(DEFAULT_FLEET), DEFAULT_FLEET.T_s)
    for _ in range(20):
        p.observe(4, 7)
    assert p.predict(n_prev=4, n_curr=7) == 7


def test_underallocation_bias_when_spinup_dominates():
    """If expected lifetimes are one interval, spin-up amortization makes
    mid-range allocations cheaper than chasing the peak."""
    fleet = DEFAULT_FLEET
    p = Predictor(64, energy_coeffs(fleet), fleet.T_s)
    for _ in range(5):
        p.observe(2, 2)
        p.observe(2, 40)
    # short lifetimes -> expensive spin-ups
    for lvl in range(64):
        p.record_lifetime(lvl, fleet.T_s)
    pred_short = p.predict(n_prev=2, n_curr=2)
    # long lifetimes -> cheap spin-ups -> can afford more workers
    for lvl in range(64):
        for _ in range(50):
            p.record_lifetime(lvl, 100 * fleet.T_s)
    pred_long = p.predict(n_prev=2, n_curr=2)
    assert pred_long >= pred_short


@pytest.mark.parametrize("n", [128, 200])
def test_prefix_sum_is_independent_of_batch_width(n):
    """A cell's prefix sums must not change with the number of cells
    vmapped beside it: `MeshBackend` runs each device's lane shard of a
    chunk and is held bit-identical to `LocalBackend`."""
    import jax
    from repro.core.predictor import _prefix_sum
    x = np.random.default_rng(n).uniform(0.0, 1.0, (32, n)).astype(np.float32)
    scan = jax.jit(jax.vmap(_prefix_sum))
    full = np.asarray(scan(jnp.asarray(x)))
    for width in (1, 4, 8, 16):
        np.testing.assert_array_equal(np.asarray(scan(jnp.asarray(x[:width]))),
                                      full[:width])
    np.testing.assert_allclose(full, np.cumsum(x.astype(np.float64), axis=1),
                               rtol=1e-5)
