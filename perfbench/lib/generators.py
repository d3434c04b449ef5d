"""The benchmark's own stream generators (NumPy, seeded).

A copy of the algorithm behind the program's stand-in app traces
(`repro.core.bmodel`, `repro.workloads.scenarios.synthetic_trace`),
rewritten on NumPy's PCG64 so the streams do not move with JAX's PRNG
implementation. `perfbench/data/generator_checksums.json` pins their
output; `perfbench/tests/test_generators.py` checks it.

Two stages:

* **Base demand** (`base_counts`): per-second Poisson arrival counts of
  one stream, a pure function of the configuration's stream parameters
  and its fixed stream seed. This is the deployment's demand shape.
* **Realization** (`realize_times`): the stream's arrival times for one
  grid of one run. The base counts are rotated by a whole number of
  scheduling intervals drawn from the run's seed, and every arrival gets
  a fresh position inside its second. Each realization is a new input
  array with the same multiset of per-interval counts, so every seed
  drives the same amount of work through the same compiled shapes.
"""

from __future__ import annotations

import numpy as np


def rng_for(*key: int) -> np.random.Generator:
    """PCG64 stream keyed by a tuple of integers of any size or sign."""
    return np.random.default_rng([int(k) % (1 << 64) for k in key])


def bmodel_series(rng: np.random.Generator, bias: float, levels: int,
                  total: float) -> np.ndarray:
    """b-model cascade (Wang et al., ICDE 2002): ``2**levels`` volumes
    summing to ``total``; at each level every segment splits (b, 1-b)
    between its halves with the biased side drawn uniformly."""
    vols = np.array([total], np.float64)
    for _ in range(levels):
        left = np.where(rng.random(vols.shape[0]) < 0.5, bias, 1.0 - bias)
        vols = np.stack([vols * left, vols * (1.0 - left)], axis=1).reshape(-1)
    return vols


def interp_minutes(per_min: np.ndarray, horizon_s: int) -> np.ndarray:
    """Per-minute volumes to per-second rates, changing linearly within
    each minute (paper §5.1); ``per_min`` has ``minutes + 1`` entries."""
    minutes = per_min.shape[0] - 1
    t = np.arange(horizon_s, dtype=np.float64)
    idx = np.minimum((t // 60).astype(np.int64), minutes - 1)
    frac = (t % 60) / 60.0
    return per_min[idx] * (1 - frac) + per_min[np.minimum(idx + 1, minutes)] * frac


def bmodel_rates(rng: np.random.Generator, bias: float, horizon_s: int,
                 mean_rate: float) -> np.ndarray:
    """Per-second rates: the smallest power-of-two per-minute cascade
    covering ``minutes + 1`` volumes, truncated, then interpolated."""
    minutes = int(np.ceil(horizon_s / 60.0))
    levels = max(1, int(np.ceil(np.log2(max(minutes + 1, 2)))))
    per_min = bmodel_series(rng, bias, levels, mean_rate * 2 ** levels)
    return interp_minutes(per_min[:minutes + 1], horizon_s)


def stream_rates(kind: str, params: dict, seed: int, horizon_s: int,
                 size_s: float, demand_workers: float) -> np.ndarray:
    """Per-second expected arrival rates of one stream."""
    rng = rng_for(seed, 0)
    mean_rate = demand_workers / size_s
    if kind == "bmodel":
        return bmodel_rates(rng, params["bias"], horizon_s, mean_rate)
    raise ValueError(f"unknown stream kind {kind!r}")


def base_counts(kind: str, params: dict, seed: int, horizon_s: int,
                size_s: float, demand_workers: float) -> np.ndarray:
    """Per-second Poisson arrival counts of one stream (int64)."""
    rates = stream_rates(kind, params, seed, horizon_s, size_s,
                         demand_workers)
    return rng_for(seed, 1).poisson(np.maximum(rates, 0.0)).astype(np.int64)


def realize_times(counts: np.ndarray, shift_s: int,
                  rng: np.random.Generator) -> np.ndarray:
    """Arrival times of one realization: counts rotated by ``shift_s``
    seconds, each arrival uniform in (s, s + 1] of its second, sorted.
    With ``shift_s`` a multiple of the scheduling interval every arrival
    stays in an interval whose count multiset equals the base's."""
    c = np.roll(counts, shift_s)
    sec = np.repeat(np.arange(len(c), dtype=np.float64), c)
    return np.sort(sec + (1.0 - rng.random(len(sec))))

