"""Jit'd wrapper matching core.predictor.expected_objective_jnp."""

from __future__ import annotations

import jax.numpy as jnp

from repro.core.breakeven import ObjectiveCoeffs

from .spork_predict import spork_predict_pallas


def expected_objective(hist: jnp.ndarray, coeffs: ObjectiveCoeffs,
                       amort: jnp.ndarray) -> jnp.ndarray:
    """Same contract as the oracle: +inf outside [min bin, max bin]."""
    n = hist.shape[0]
    idx = jnp.arange(n)
    has = hist > 0
    lo = jnp.min(jnp.where(has, idx, n)).astype(jnp.float32)
    hi = jnp.max(jnp.where(has, idx, -1)).astype(jnp.float32)
    params = jnp.stack([
        jnp.asarray(coeffs.co_min, jnp.float32),
        jnp.asarray(coeffs.co_over, jnp.float32),
        jnp.asarray(coeffs.co_under, jnp.float32),
        jnp.sum(hist).astype(jnp.float32), lo, hi])
    out = spork_predict_pallas(hist, amort, params)
    return jnp.where(out >= 1.0e38, jnp.inf, out)
