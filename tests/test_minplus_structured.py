"""Structured O(N log N) min-plus transition vs the dense O(N^2) oracle.

The structured path (monotone segment decomposition; derivation in the
repro.core.dp module docstring) must match `minplus_step_jnp` exactly on
non-increasing y_c vectors. Exactness here means bit-identical: the
property tests draw integer-valued inputs whose products and sums stay
below 2**24, where float32 arithmetic is exact in BOTH formulations, so
values, argmins, and first-minimizer tie handling must agree to the bit.
Continuous-input agreement and full solve_dp paths/objectives (N up to
several thousand) are covered by the fixed-seed tests below.
"""

import jax.numpy as jnp
import numpy as np
import pytest
try:
    from hypothesis import given, settings, strategies as st
except ImportError:    # environment without hypothesis: local shim
    from _hypothesis_shim import given, settings, strategies as st

from repro.core.dp import (
    minplus_step_jnp,
    minplus_step_structured,
    solve_dp,
    solve_dp_batch,
)
from repro.core.workers import DEFAULT_FLEET


def _monotone_yc(rng, n, lo=0, hi=50):
    """Random non-increasing integer-valued y_c vector (float32-exact)."""
    return jnp.asarray(np.sort(rng.integers(lo, hi, n))[::-1]
                       .astype(np.float32))


def _exact_instance(seed, n):
    """Instance where every intermediate in both formulations is an
    exactly-representable float32 integer (|values| < 2**24)."""
    rng = np.random.default_rng(seed)
    F = jnp.asarray(rng.integers(-4096, 4096, n).astype(np.float32))
    ycp = _monotone_yc(rng, n)
    ycc = _monotone_yc(rng, n)
    coeffs = tuple(float(x) for x in rng.integers(0, 32, 4))
    return F, ycp, ycc, coeffs


@given(seed=st.integers(0, 100_000), n=st.integers(1, 600))
@settings(max_examples=25, deadline=None)
def test_structured_matches_dense_exactly(seed, n):
    """Values AND argmins bit-identical on random monotone instances."""
    F, ycp, ycc, coeffs = _exact_instance(seed, n)
    want_v, want_a = minplus_step_jnp(F, ycp, ycc, coeffs)
    got_v, got_a = minplus_step_structured(F, ycp, ycc, coeffs)
    np.testing.assert_array_equal(np.asarray(got_v), np.asarray(want_v))
    np.testing.assert_array_equal(np.asarray(got_a), np.asarray(want_a))


@given(seed=st.integers(0, 100_000), n=st.integers(2, 300))
@settings(max_examples=15, deadline=None)
def test_structured_first_minimizer_on_ties(seed, n):
    """Heavy-tie instances (quantized F, flat/duplicated y_c plateaus,
    zero or tiny coefficients) must reproduce the dense oracle's
    first-minimizer rule, not merely an equally-minimal index."""
    rng = np.random.default_rng(seed)
    F = jnp.asarray(rng.integers(0, 3, n).astype(np.float32))
    ycp = _monotone_yc(rng, n, 0, 3)
    ycc = _monotone_yc(rng, n, 0, 3)
    coeffs = tuple(float(x) for x in rng.integers(0, 2, 4))
    want_v, want_a = minplus_step_jnp(F, ycp, ycc, coeffs)
    got_v, got_a = minplus_step_structured(F, ycp, ycc, coeffs)
    np.testing.assert_array_equal(np.asarray(got_v), np.asarray(want_v))
    np.testing.assert_array_equal(np.asarray(got_a), np.asarray(want_a))


def test_structured_all_zero_coeffs_ties():
    """trans == 0 everywhere: every destination ties across all sources;
    the argmin must be the first global minimizer of F for every j."""
    n = 257
    F = jnp.asarray(np.tile([2.0, 1.0, 1.0, 3.0], 65)[:n]
                    .astype(np.float32))
    z = jnp.zeros((n,), jnp.float32)
    want_v, want_a = minplus_step_jnp(F, z, z, (0.0, 0.0, 0.0, 0.0))
    got_v, got_a = minplus_step_structured(F, z, z, (0.0, 0.0, 0.0, 0.0))
    np.testing.assert_array_equal(np.asarray(got_v), np.asarray(want_v))
    np.testing.assert_array_equal(np.asarray(got_a), np.asarray(want_a))
    assert np.all(np.asarray(got_a) == 1)      # first of the tied minima


@given(seed=st.integers(0, 100_000))
@settings(max_examples=10, deadline=None)
def test_structured_continuous_inputs_close(seed):
    """Continuous (non-integer) inputs: values agree to float tolerance
    and the structured argmin attains the dense minimum."""
    rng = np.random.default_rng(seed)
    n = int(rng.integers(2, 400))
    F = jnp.asarray(rng.normal(0, 100, n), jnp.float32)
    ycp = jnp.asarray(np.sort(rng.uniform(0, 40, n))[::-1], jnp.float32)
    ycc = jnp.asarray(np.sort(rng.uniform(0, 40, n))[::-1], jnp.float32)
    coeffs = tuple(float(x) for x in rng.uniform(0, 10, 4))
    want_v, _ = minplus_step_jnp(F, ycp, ycc, coeffs)
    got_v, got_a = minplus_step_structured(F, ycp, ycc, coeffs)
    np.testing.assert_allclose(np.asarray(got_v), np.asarray(want_v),
                               rtol=1e-5, atol=1e-4)
    # the chosen source must attain the dense minimum for its destination
    af, df, ac, dc = coeffs
    ii = np.asarray(got_a, np.int64)
    jj = np.arange(n)
    tr = (af * np.maximum(jj - ii, 0) + df * np.maximum(ii - jj, 0)
          + ac * np.maximum(np.asarray(ycc) - np.asarray(ycp)[ii], 0)
          + dc * np.maximum(np.asarray(ycp)[ii] - np.asarray(ycc), 0))
    np.testing.assert_allclose(np.asarray(F)[ii] + tr, np.asarray(want_v),
                               rtol=1e-4, atol=1e-3)


@given(seed=st.integers(0, 100_000), n=st.integers(2, 200))
@settings(max_examples=10, deadline=None)
def test_structured_falls_back_on_non_monotone(seed, n):
    """Violating the monotonicity precondition must route to the dense
    transition at runtime (exact equality, any input)."""
    rng = np.random.default_rng(seed)
    F = jnp.asarray(rng.integers(-100, 100, n).astype(np.float32))
    ycp = jnp.asarray(rng.integers(0, 9, n).astype(np.float32))  # shuffled
    ycc = jnp.asarray(rng.integers(0, 9, n).astype(np.float32))
    coeffs = tuple(float(x) for x in rng.integers(0, 10, 4))
    want_v, want_a = minplus_step_jnp(F, ycp, ycc, coeffs)
    got_v, got_a = minplus_step_structured(F, ycp, ycc, coeffs)
    np.testing.assert_array_equal(np.asarray(got_v), np.asarray(want_v))
    np.testing.assert_array_equal(np.asarray(got_a), np.asarray(want_a))


# ------------------------------------------------------- full DP solves
@pytest.mark.parametrize("seed,n_levels,t", [(0, 512, 16), (1, 1024, 10),
                                             (2, 3072, 8)])
def test_solve_dp_structured_matches_dense(seed, n_levels, t):
    """Full forward+backtrack at N up to several thousand: identical
    paths, identical objectives (fixed seeds keep this deterministic)."""
    fleet = DEFAULT_FLEET.replace(max_fpgas=2 * n_levels, max_cpus=10 ** 6)
    rng = np.random.default_rng(seed)
    W = rng.uniform(0, (n_levels - 2) * fleet.S * fleet.T_s, size=t)
    dense = solve_dp(W, fleet, energy_weight=1.0, transition="dense",
                     n_levels=n_levels)
    structured = solve_dp(W, fleet, energy_weight=1.0,
                          transition="structured", n_levels=n_levels)
    np.testing.assert_array_equal(structured.y_fpga, dense.y_fpga)
    np.testing.assert_array_equal(structured.y_cpu, dense.y_cpu)
    assert structured.objective == dense.objective


@pytest.mark.parametrize("n_levels", [64, 200])
def test_solve_dp_batch_transitions_match_dense(n_levels):
    """The batched (vmapped) structured forward must agree with dense per
    row across energy weights, at a lane-aligned and an unaligned level
    count.

    With continuous stage costs an exact tie in the dense formula can be
    a 1-ulp difference in the separable rewrite (and vice versa), so two
    equally-optimal paths may legitimately differ at a tied interval;
    the assertion is therefore optimality-equivalence — identical
    objectives and identical exact evaluations under the row's weights —
    rather than path identity (which the integer-exact property tests
    and the fixed-seed solve_dp tests above do pin down)."""
    from repro.core.dp import _objective_weights
    rng = np.random.default_rng(5)
    Ws = np.stack([rng.uniform(0, 40 * DEFAULT_FLEET.T_s, size=12)
                   for _ in range(4)])
    weights = [1.0, 0.6, 0.3, 0.0]
    dense = solve_dp_batch(Ws, DEFAULT_FLEET, weights, n_levels=n_levels,
                           transition="dense")
    got = solve_dp_batch(Ws, DEFAULT_FLEET, weights, n_levels=n_levels,
                         transition="structured")
    for w, d, g in zip(weights, dense, got):
        np.testing.assert_allclose(g.objective, d.objective, rtol=1e-6)
        we, wc = _objective_weights(w, DEFAULT_FLEET)
        np.testing.assert_allclose(we * g.energy_j + wc * g.cost_usd,
                                   we * d.energy_j + wc * d.cost_usd,
                                   rtol=1e-6)


def test_transition_rejects_unknown_backend():
    W = np.full(8, 10.0)
    with pytest.raises(ValueError, match="unknown transition"):
        solve_dp(W, DEFAULT_FLEET, transition="blocked")
