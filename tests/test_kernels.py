"""Pallas kernels vs pure-jnp oracles (interpret mode on CPU; the TPU
lowering of each kernel is compiled in tests/test_tpu_compile.py).

Each kernel is swept over shapes/dtypes and checked with assert_allclose
against its ref.py oracle, plus hypothesis property tests.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
try:
    from hypothesis import given, settings, strategies as st
except ImportError:    # environment without hypothesis: local shim
    from _hypothesis_shim import given, settings, strategies as st

from repro.core.breakeven import energy_coeffs
from repro.core.predictor import amortization_vector
from repro.core.workers import DEFAULT_FLEET
from repro.kernels.decode_attn.decode_attn import decode_attention_pallas
from repro.kernels.decode_attn.ref import decode_attention_ref
from repro.kernels.minplus.minplus import minplus_pallas
from repro.kernels.minplus.ref import minplus_step_ref
from repro.kernels.spork_predict.ops import expected_objective
from repro.kernels.spork_predict.ref import expected_objective_ref


# ---------------------------------------------------------------- minplus
@pytest.mark.parametrize("n", [8, 100, 128, 257, 1024])
def test_minplus_matches_ref(n):
    rng = np.random.default_rng(n)
    F = jnp.asarray(rng.normal(0, 100, n), jnp.float32)
    ycp = jnp.asarray(rng.integers(0, 50, n), jnp.float32)
    ycc = jnp.asarray(rng.integers(0, 50, n), jnp.float32)
    coeffs = (500.0, 5.0, 0.75, 0.75)
    want, want_arg = minplus_step_ref(F, ycp, ycc, coeffs)
    got, got_arg = minplus_pallas(F, ycp, ycc, jnp.asarray(coeffs),
                                  interpret=True)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=1e-5)
    # argmins must point at equally-minimal values (ties may differ by
    # block order); check by value
    gv = np.asarray(F)[np.asarray(got_arg)]
    wv = np.asarray(F)[np.asarray(want_arg)]
    tr = lambda a: np.asarray(got)  # value already checked; spot check args
    assert np.all(np.asarray(got_arg) >= 0) and np.all(np.asarray(got_arg) < n)


@given(seed=st.integers(0, 10_000), n=st.integers(2, 200))
@settings(max_examples=15, deadline=None)
def test_minplus_property(seed, n):
    rng = np.random.default_rng(seed)
    F = jnp.asarray(rng.normal(0, 10, n), jnp.float32)
    ycp = jnp.asarray(rng.integers(0, 5, n), jnp.float32)
    ycc = jnp.asarray(rng.integers(0, 5, n), jnp.float32)
    coeffs = tuple(float(x) for x in rng.uniform(0, 10, 4))
    want, _ = minplus_step_ref(F, ycp, ycc, coeffs)
    got, arg = minplus_pallas(F, ycp, ycc, jnp.asarray(coeffs), interpret=True)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=1e-4,
                               atol=1e-4)


@pytest.mark.parametrize("n", [130, 257])
def test_minplus_non_multiple_of_block(n):
    """Padded tail (BLOCK=128 tiling) must not leak the +3e38 sentinel
    into values or argmins; compare against the jnp oracle exactly."""
    from repro.core.dp import minplus_step_jnp
    rng = np.random.default_rng(n * 7 + 1)
    F = jnp.asarray(rng.normal(0, 50, n), jnp.float32)
    ycp = jnp.asarray(rng.integers(0, 20, n), jnp.float32)
    ycc = jnp.asarray(rng.integers(0, 20, n), jnp.float32)
    coeffs = (120.0, 2.5, 0.4, 0.6)
    want, want_arg = minplus_step_jnp(F, ycp, ycc, coeffs)
    got, got_arg = minplus_pallas(F, ycp, ycc, jnp.asarray(coeffs),
                                  interpret=True)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-4)
    assert np.all(np.asarray(got_arg) < n)
    np.testing.assert_array_equal(np.asarray(got_arg), np.asarray(want_arg))


@pytest.mark.parametrize("n", [130, 257])
def test_minplus_argmin_tie_breaking(n):
    """Both paths must return the FIRST minimizer: quantized F plus zero
    transition costs produce many exact ties, within and across blocks."""
    from repro.core.dp import minplus_step_jnp
    rng = np.random.default_rng(n)
    F = jnp.asarray(rng.integers(0, 3, n).astype(np.float32))  # heavy ties
    ycp = jnp.zeros((n,), jnp.float32)
    ycc = jnp.zeros((n,), jnp.float32)
    coeffs = (0.0, 0.0, 0.0, 0.0)       # trans == 0: every min is a tie
    want, want_arg = minplus_step_jnp(F, ycp, ycc, coeffs)
    got, got_arg = minplus_pallas(F, ycp, ycc, jnp.asarray(coeffs),
                                  interpret=True)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=1e-6)
    np.testing.assert_array_equal(np.asarray(got_arg), np.asarray(want_arg))


def test_minplus_inside_dp_forward():
    """Chained through a T-step min-plus forward pass (F_t = min-plus
    step of F_{t-1} plus a stage cost, as `core.dp` runs it), the kernel
    must reproduce the jnp transition's values and argmins exactly on
    integer-valued stage costs."""
    from repro.core.dp import minplus_step_jnp
    rng = np.random.default_rng(7)
    n, T = 200, 12
    ycs = np.sort(rng.integers(0, 40, (T, n)), axis=1)[:, ::-1]
    stage = rng.integers(0, 500, (T, n)).astype(np.float32)
    coeffs = (40.0, 3.0, 2.0, 1.0)
    fk = fj = jnp.asarray(stage[0])
    for t in range(1, T):
        ycp = jnp.asarray(ycs[t - 1].astype(np.float32))
        ycc = jnp.asarray(ycs[t].astype(np.float32))
        vk, ak = minplus_pallas(fk, ycp, ycc, jnp.asarray(coeffs),
                                interpret=True)
        vj, aj = minplus_step_jnp(fj, ycp, ycc, coeffs)
        np.testing.assert_array_equal(np.asarray(ak), np.asarray(aj))
        fk = vk + stage[t]
        fj = vj + stage[t]
        np.testing.assert_array_equal(np.asarray(fk), np.asarray(fj))


# ------------------------------------------ minplus (monotone y_c inputs)
@pytest.mark.parametrize("n", [1, 8, 100, 128, 130, 257, 1024])
def test_minplus_kernel_matches_structured_on_monotone(n):
    """On the monotone y_c inputs the DP feeds its transitions, the
    dense kernel must be bit-identical to BOTH the dense jnp oracle and
    the structured O(N log N) path (integer inputs: no rounding),
    including non-multiples of the 128 lane block."""
    from repro.core.dp import minplus_step_jnp, minplus_step_structured
    rng = np.random.default_rng(n * 13 + 5)
    F = jnp.asarray(rng.integers(-1000, 1000, n).astype(np.float32))
    ycp = jnp.asarray(np.sort(rng.integers(0, 50, n))[::-1]
                      .astype(np.float32))
    ycc = jnp.asarray(np.sort(rng.integers(0, 50, n))[::-1]
                      .astype(np.float32))
    coeffs = (500.0, 5.0, 3.0, 2.0)
    want_v, want_a = minplus_step_jnp(F, ycp, ycc, coeffs)
    ref_v, ref_a = minplus_step_structured(F, ycp, ycc, coeffs)
    got_v, got_a = minplus_pallas(F, ycp, ycc, jnp.asarray(coeffs),
                                  interpret=True)
    np.testing.assert_array_equal(np.asarray(got_v), np.asarray(want_v))
    np.testing.assert_array_equal(np.asarray(got_a), np.asarray(want_a))
    np.testing.assert_array_equal(np.asarray(got_v), np.asarray(ref_v))
    np.testing.assert_array_equal(np.asarray(got_a), np.asarray(ref_a))


@given(seed=st.integers(0, 10_000), n=st.integers(1, 300))
@settings(max_examples=15, deadline=None)
def test_minplus_kernel_monotone_property(seed, n):
    from repro.core.dp import minplus_step_jnp
    rng = np.random.default_rng(seed)
    F = jnp.asarray(rng.integers(-500, 500, n).astype(np.float32))
    ycp = jnp.asarray(np.sort(rng.integers(0, 8, n))[::-1]
                      .astype(np.float32))
    ycc = jnp.asarray(np.sort(rng.integers(0, 8, n))[::-1]
                      .astype(np.float32))
    coeffs = tuple(float(x) for x in rng.integers(0, 16, 4))
    want_v, want_a = minplus_step_jnp(F, ycp, ycc, coeffs)
    got_v, got_a = minplus_pallas(F, ycp, ycc, jnp.asarray(coeffs),
                                  interpret=True)
    np.testing.assert_array_equal(np.asarray(got_v), np.asarray(want_v))
    np.testing.assert_array_equal(np.asarray(got_a), np.asarray(want_a))


def test_minplus_ops_wrapper_tie_breaking():
    """First-minimizer rule through the public `minplus_step` wrapper
    (tuple coefficients, platform-decided mode) under heavy ties."""
    from repro.core.dp import minplus_step_jnp
    from repro.kernels.minplus import minplus_step
    n = 130
    rng = np.random.default_rng(n)
    F = jnp.asarray(rng.integers(0, 3, n).astype(np.float32))
    z = jnp.zeros((n,), jnp.float32)
    coeffs = (0.0, 0.0, 0.0, 0.0)
    want_v, want_a = minplus_step_jnp(F, z, z, coeffs)
    got_v, got_a = minplus_step(F, z, z, coeffs)
    np.testing.assert_array_equal(np.asarray(got_v), np.asarray(want_v))
    np.testing.assert_array_equal(np.asarray(got_a), np.asarray(want_a))


# ---------------------------------------------------------- spork_predict
@pytest.mark.parametrize("n", [16, 128, 200, 512])
def test_spork_predict_matches_ref(n):
    rng = np.random.default_rng(n)
    hist = jnp.asarray(rng.integers(0, 6, n), jnp.float32)
    coeffs = energy_coeffs(DEFAULT_FLEET)
    amort = amortization_vector(
        jnp.asarray(rng.uniform(0, 100, n), jnp.float32),
        jnp.asarray(rng.integers(0, 3, n), jnp.float32),
        jnp.asarray(2), DEFAULT_FLEET.T_s, coeffs.amort_unit)
    want = np.asarray(expected_objective_ref(hist, coeffs, amort))
    got = np.asarray(expected_objective(hist, coeffs, amort))
    mask = np.isfinite(want)
    np.testing.assert_allclose(got[mask], want[mask], rtol=2e-5)
    np.testing.assert_array_equal(np.isfinite(got), mask)


@given(seed=st.integers(0, 10_000))
@settings(max_examples=15, deadline=None)
def test_spork_predict_argmin_property(seed):
    """The kernel and oracle must agree on the chosen allocation."""
    rng = np.random.default_rng(seed)
    n = 64
    hist = jnp.asarray(rng.integers(0, 4, n), jnp.float32)
    coeffs = energy_coeffs(DEFAULT_FLEET)
    amort = jnp.asarray(np.cumsum(rng.uniform(0, 50, n)), jnp.float32)
    want = np.asarray(expected_objective_ref(hist, coeffs, amort))
    got = np.asarray(expected_objective(hist, coeffs, amort))
    if np.isfinite(want).any():
        assert int(np.argmin(got)) == int(np.argmin(want))


# ------------------------------------------------------------ decode_attn
SHAPES = [  # (B, Hq, Hkv, D, S)
    (2, 8, 8, 64, 256),      # MHA
    (2, 16, 8, 64, 300),     # GQA 2:1, ragged tail
    (1, 10, 1, 128, 512),    # MQA (recurrentgemma-style)
    (4, 6, 2, 128, 1024),    # GQA 3:1
]


@pytest.mark.parametrize("shape", SHAPES)
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_decode_attn_matches_ref(shape, dtype):
    b, hq, hkv, d, s = shape
    key = jax.random.PRNGKey(hash(shape) % 2**31)
    kq, kk, kv, kl = jax.random.split(key, 4)
    q = jax.random.normal(kq, (b, hq, d), dtype)
    k = jax.random.normal(kk, (b, s, hkv, d), dtype)
    v = jax.random.normal(kv, (b, s, hkv, d), dtype)
    lengths = jax.random.randint(kl, (b,), 1, s + 1)
    want = decode_attention_ref(q, k, v, lengths)
    got = decode_attention_pallas(q, k, v, lengths, block_s=128,
                                  interpret=True)
    tol = 2e-2 if dtype == jnp.bfloat16 else 2e-5
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32),
                               rtol=tol, atol=tol)


def test_decode_attn_zero_length_rows():
    """length=0 batches must produce zeros, not NaNs."""
    b, hq, hkv, d, s = 2, 4, 2, 64, 256
    key = jax.random.PRNGKey(0)
    q = jax.random.normal(key, (b, hq, d), jnp.float32)
    k = jax.random.normal(key, (b, s, hkv, d), jnp.float32)
    v = jax.random.normal(key, (b, s, hkv, d), jnp.float32)
    lengths = jnp.asarray([0, s])
    got = np.asarray(decode_attention_pallas(q, k, v, lengths, interpret=True))
    assert np.all(np.isfinite(got))
    np.testing.assert_allclose(got[0], 0.0, atol=1e-6)


@given(seed=st.integers(0, 10_000), s=st.integers(1, 700))
@settings(max_examples=10, deadline=None)
def test_decode_attn_ragged_property(seed, s):
    b, hq, hkv, d = 2, 4, 2, 64
    key = jax.random.PRNGKey(seed)
    kq, kk, kv, kl = jax.random.split(key, 4)
    q = jax.random.normal(kq, (b, hq, d), jnp.float32)
    k = jax.random.normal(kk, (b, s, hkv, d), jnp.float32)
    v = jax.random.normal(kv, (b, s, hkv, d), jnp.float32)
    lengths = jax.random.randint(kl, (b,), 0, s + 1)
    want = decode_attention_ref(q, k, v, lengths)
    got = decode_attention_pallas(q, k, v, lengths, block_s=128,
                                  interpret=True)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-4, atol=1e-4)
