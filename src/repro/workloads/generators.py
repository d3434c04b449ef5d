"""JAX-native workload generators: whole seed batches in one dispatch.

Every generator here is a pure function of a PRNG key plus scalar shape
parameters, returning per-second arrival *rates* (req/s) as a float32
vector — jittable and vmappable, so `repro.workloads.scenarios` can
synthesize an entire ``(seeds, horizon)`` grid (rates, Poisson counts
and request sizes) in ONE device dispatch instead of the per-trace
host-side numpy loops `core.traces` started from.

Families:

  * ``bmodel_rates_jnp`` — the paper's §5.1 self-similar b-model at
    per-minute resolution with linear interpolation to seconds (the same
    construction as `repro.core.traces.synthetic_trace`, in-graph).
  * ``mmpp_rates`` — a 2-state Markov-modulated Poisson process via
    `jax.lax.scan`: exponential-ish burst episodes at a multiple of the
    baseline rate, normalized so the stationary mean equals the target.
  * ``diurnal_rates`` — two-harmonic daily shape with lognormal
    multiplicative noise; ``flash_crowd_overlay`` multiplies in a
    ramp-then-exponential-decay spike at a random onset.
  * ``pareto_sizes`` / ``lognormal_sizes`` — heavy-tail request-size
    samplers (per-seed scalar sizes for `SweepCell.size_s`).
  * ``poisson_counts`` — `jax.random.poisson` arrival-count sampling,
    the on-device replacement for `Trace.sample_counts`.

The generators are building blocks; named, validated combinations live
in `repro.workloads.registry` (see docs/EXPERIMENTS.md §Scenario
validators for how each stand-in is quantitatively flagged).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.bmodel import bmodel_series
from repro.core.rng import pinned_stream


def interp_minutes(per_min: jnp.ndarray, horizon_s: int) -> jnp.ndarray:
    """Linear per-minute -> per-second interpolation (paper §5.1 rates
    "change linearly within each minute"). ``per_min`` has ``minutes + 1``
    entries so the last minute interpolates toward a real endpoint."""
    minutes = per_min.shape[0] - 1
    t = jnp.arange(horizon_s, dtype=jnp.float32)
    idx = jnp.minimum((t // 60).astype(jnp.int32), minutes - 1)
    frac = (t % 60) / 60.0
    return per_min[idx] * (1 - frac) + per_min[jnp.minimum(idx + 1, minutes)] * frac


def bmodel_rates_jnp(key: jax.Array, bias, horizon_s: int,
                     mean_rate) -> jnp.ndarray:
    """Per-second rates from a per-minute b-model cascade + interpolation.

    In-graph twin of `core.traces.synthetic_trace`'s rate construction:
    the smallest power-of-two cascade covering ``minutes + 1`` per-minute
    volumes, truncated, then interpolated to seconds. ``bias`` and
    ``mean_rate`` may be traced scalars (vmappable over burstiness)."""
    minutes = int(np.ceil(horizon_s / 60.0))
    levels = max(1, int(np.ceil(np.log2(max(minutes + 1, 2)))))
    n = 2 ** levels
    per_min = bmodel_series(key, bias, levels,
                            jnp.float32(mean_rate) * n)[:minutes + 1]
    return interp_minutes(per_min, horizon_s)


@pinned_stream
def mmpp_rates(key: jax.Array, horizon_s: int, mean_rate,
               burst_ratio=8.0, p_enter=0.02, p_exit=0.2) -> jnp.ndarray:
    """2-state MMPP rates via `lax.scan` over seconds.

    State 0 emits a baseline rate, state 1 emits ``burst_ratio`` x the
    baseline; per-second transition probabilities ``p_enter``/``p_exit``
    give geometric episode lengths (mean burst ``1/p_exit`` s). The
    baseline is scaled so the *stationary* mean rate equals
    ``mean_rate`` (stationary burst occupancy ``p_enter / (p_enter +
    p_exit)``)."""
    burst_ratio = jnp.float32(burst_ratio)
    p_enter = jnp.float32(p_enter)
    p_exit = jnp.float32(p_exit)
    pi_burst = p_enter / (p_enter + p_exit)
    base = jnp.float32(mean_rate) / (1.0 + (burst_ratio - 1.0) * pi_burst)

    def step(state, k):
        u = jax.random.uniform(k)
        p_burst_next = jnp.where(state == 1, 1.0 - p_exit, p_enter)
        nxt = (u < p_burst_next).astype(jnp.int32)
        rate = base * jnp.where(nxt == 1, burst_ratio, 1.0)
        return nxt, rate

    keys = jax.random.split(key, horizon_s)
    _, rates = jax.lax.scan(step, jnp.int32(0), keys)
    return rates


@pinned_stream
def diurnal_rates(key: jax.Array, horizon_s: int, mean_rate,
                  period_s=86400.0, amp1=0.6, amp2=0.25, phase=0.0,
                  noise=0.08) -> jnp.ndarray:
    """Two-harmonic diurnal shape with lognormal multiplicative noise,
    renormalized so the realized mean equals ``mean_rate`` exactly."""
    t = jnp.arange(horizon_s, dtype=jnp.float32)
    w = 2.0 * jnp.pi * t / jnp.float32(period_s)
    shape = (1.0 + jnp.float32(amp1) * jnp.sin(w + phase)
             + jnp.float32(amp2) * jnp.sin(2.0 * w + 0.7 + phase))
    shape = jnp.maximum(shape, 0.0)
    noise = jnp.float32(noise)
    mult = jnp.exp(noise * jax.random.normal(key, (horizon_s,))
                   - 0.5 * noise * noise)
    rates = shape * mult
    return jnp.float32(mean_rate) * rates / jnp.maximum(jnp.mean(rates), 1e-9)


@pinned_stream
def flash_crowd_overlay(key: jax.Array, horizon_s: int, amp=8.0,
                        ramp_s=30.0, decay_s=300.0,
                        window=(0.2, 0.7)) -> jnp.ndarray:
    """Multiplicative flash-crowd spike: 1 everywhere except a linear
    ramp to ``amp`` over ``ramp_s`` starting at a random onset (uniform
    in ``window`` as a fraction of the horizon), then exponential decay
    with time constant ``decay_s``. Multiply into any base rate."""
    t = jnp.arange(horizon_s, dtype=jnp.float32)
    lo, hi = window
    t0 = (lo + (hi - lo) * jax.random.uniform(key)) * horizon_s
    dt = t - t0
    ramp = jnp.clip(dt / jnp.float32(ramp_s), 0.0, 1.0)
    decay = jnp.exp(-jnp.maximum(dt - jnp.float32(ramp_s), 0.0)
                    / jnp.float32(decay_s))
    return 1.0 + (jnp.float32(amp) - 1.0) * ramp * decay


@pinned_stream
def pareto_sizes(key: jax.Array, n: int, alpha=1.6, x_min_s=0.020,
                 cap_s=10.0) -> jnp.ndarray:
    """Pareto(alpha) request sizes with scale ``x_min_s``, capped at
    ``cap_s`` (the paper's longest bucket bound)."""
    u = jax.random.uniform(key, (n,), minval=1e-6, maxval=1.0)
    return jnp.minimum(jnp.float32(x_min_s) * u ** (-1.0 / jnp.float32(alpha)),
                       jnp.float32(cap_s))


@pinned_stream
def lognormal_sizes(key: jax.Array, n: int, median_s=0.1, sigma=0.8,
                    lo_s=0.010, hi_s=10.0) -> jnp.ndarray:
    """Lognormal request sizes clipped to ``[lo_s, hi_s]`` (the demand
    skew used by the production stand-ins)."""
    z = jax.random.normal(key, (n,))
    return jnp.clip(jnp.exp(jnp.log(jnp.float32(median_s))
                            + jnp.float32(sigma) * z),
                    jnp.float32(lo_s), jnp.float32(hi_s))


@pinned_stream
def poisson_counts(key: jax.Array, rates: jnp.ndarray) -> jnp.ndarray:
    """Poisson arrival counts for a rate grid — on-device twin of
    `Trace.sample_counts` (different RNG stream, same distribution)."""
    return jax.random.poisson(key, jnp.maximum(rates, 0.0)).astype(jnp.int32)
