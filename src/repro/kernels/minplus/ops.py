"""Jit'd wrapper for the min-plus transition kernel.

`minplus_step` has the exact signature of the jnp oracle
(repro.core.dp.minplus_step_jnp). The kernel compiles on a TPU and runs
in interpret mode elsewhere (`repro.kernels.backend.use_interpret`).
"""

from __future__ import annotations

import jax.numpy as jnp

from .minplus import minplus_pallas


def _pack(coeffs) -> jnp.ndarray:
    af, df, ac, dc = coeffs
    return jnp.stack([jnp.asarray(af, jnp.float32),
                      jnp.asarray(df, jnp.float32),
                      jnp.asarray(ac, jnp.float32),
                      jnp.asarray(dc, jnp.float32)])


def minplus_step(F: jnp.ndarray, yc_prev: jnp.ndarray, yc_cur: jnp.ndarray,
                 coeffs) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Dense O(N^2) transition kernel (the original HBM-light contraction)."""
    return minplus_pallas(F, yc_prev, yc_cur, _pack(coeffs))
