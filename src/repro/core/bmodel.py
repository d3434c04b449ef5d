"""b-model self-similar trace generator (Wang et al., ICDE 2002; paper [87]).

The b-model recursively splits a volume of work over a time range: at each
of ``k`` levels a segment's volume is split (b, 1-b) between its two halves
with the biased side chosen uniformly at random. ``bias=0.5`` yields a
uniform trace; ``bias=0.75`` is highly variable (the paper reports >20x
load differences between consecutive intervals at b=0.75).

The cascade is log-depth and fully vectorized; it is jittable so that trace
generation can run inside sharded parameter sweeps.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.rng import pinned_stream


@pinned_stream
@functools.partial(jax.jit, static_argnames=("levels",))
def bmodel_series(key: jax.Array, bias: jax.Array | float, levels: int,
                  total_volume: jax.Array | float) -> jax.Array:
    """Generate ``2**levels`` per-interval volumes summing to total_volume.

    bias may be a traced scalar so sweeps can vmap over burstiness.
    """
    vols = jnp.asarray([total_volume], dtype=jnp.float32)
    bias = jnp.asarray(bias, dtype=jnp.float32)
    for lvl in range(levels):
        key, sub = jax.random.split(key)
        bits = jax.random.bernoulli(sub, 0.5, (vols.shape[0],))
        left = jnp.where(bits, bias, 1.0 - bias)
        halves = jnp.stack([vols * left, vols * (1.0 - left)], axis=1)
        vols = halves.reshape(-1)
    return vols


def bmodel_rates(key: jax.Array, bias: float, horizon_s: int,
                 mean_rate: float) -> jax.Array:
    """Per-second arrival rates (req/s) over >= horizon_s seconds.

    Uses the smallest power-of-two cascade covering the horizon and
    truncates; total volume is scaled so the *mean* over the horizon equals
    ``mean_rate``.
    """
    levels = max(1, int(np.ceil(np.log2(max(horizon_s, 2)))))
    n = 2 ** levels
    series = bmodel_series(key, bias, levels, mean_rate * n)
    return series[:horizon_s]


def bmodel_rates_np(seed: int, bias: float, horizon_s: int,
                    mean_rate: float) -> np.ndarray:
    """NumPy convenience wrapper (host-side trace prep)."""
    key = jax.random.PRNGKey(seed)
    return np.asarray(bmodel_rates(key, bias, horizon_s, mean_rate))
