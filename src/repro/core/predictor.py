"""Spork's lightweight predictor (paper Alg. 2) — conditional-histogram
expected-objective minimization, plus the lifetime map for amortizing
spin-up overheads.

Two interchangeable implementations:
  * `expected_objective_jnp` / `predict_jnp`: pure-jnp, vectorized over all
    candidate allocations x histogram bins; jittable inside the rate
    simulator's scan. Doubles as the oracle for the `spork_predict` Pallas
    kernel (see repro/kernels/spork_predict/ref.py which re-exports it).
  * `Predictor`: a plain-Python/NumPy stateful version used by the exact
    discrete-event simulator.

The expected objective of allocating n_hat given the conditional histogram
p(n) is (see core.breakeven for the coefficient mapping):

    J(n_hat) = amort(n_hat)
             + sum_n p(n) [ co_min*min(n_hat,n) + co_over*(n_hat-n)+
                            + co_under*(n-n_hat)+ ]

    amort(n_hat) = sum_{lvl=n_curr}^{n_hat-1} amort_unit / ceil(life(lvl)/T_s)

Candidates outside [min bin, max bin] of the observed distribution are
dominated (strictly more idle above, strictly more CPU spill below) and are
masked out, matching Alg. 2's candidate set.
"""

from __future__ import annotations

import numpy as np

import jax
import jax.numpy as jnp

from .breakeven import ObjectiveCoeffs


def _prefix_sum(x: jnp.ndarray) -> jnp.ndarray:
    """Inclusive prefix sum along the last axis, batch-friendly.

    A log-step (Hillis-Steele) scan: ceil(log2 n) shifted elementwise
    adds, so every element is summed in an order fixed by n alone. Its
    result therefore does not depend on how many cells share a vmapped
    program -- a `MeshBackend` device's lane shard gives bit for bit what
    the whole chunk gives on `LocalBackend`. (A triangular matmul is
    not: XLA:CPU's accumulation order follows the batch size, and a TPU
    takes one bf16 pass for an f32 matmul by default.)"""
    n = x.shape[-1]
    pad = [(0, 0)] * (x.ndim - 1)
    s = 1
    while s < n:
        x = x + jnp.pad(x[..., :n - s], pad + [(s, 0)])
        s *= 2
    return x


def amortization_vector(life_sum: jnp.ndarray, life_cnt: jnp.ndarray,
                        n_curr: jnp.ndarray, interval_s: float,
                        amort_unit: float) -> jnp.ndarray:
    """amort(n_hat) for every candidate n_hat in [0, N).

    life_sum/life_cnt: per-level lifetime statistics (L). Levels with no
    data default to one interval (full spin-up charged, conservative).
    """
    n = life_sum.shape[0]
    avg_life = jnp.where(life_cnt > 0, life_sum / jnp.maximum(life_cnt, 1), interval_s)
    epochs = jnp.maximum(jnp.ceil(avg_life / interval_s), 1.0)
    per_level = amort_unit / epochs                       # cost of a spin-up at level
    lvl = jnp.arange(n)
    gated = jnp.where(lvl >= n_curr, per_level, 0.0)      # only new workers
    csum = _prefix_sum(gated)
    # amort(n_hat) = sum over levels < n_hat
    return jnp.concatenate([jnp.zeros((1,)), csum])[:n]


def expected_objective_jnp(hist: jnp.ndarray, coeffs: ObjectiveCoeffs,
                           amort: jnp.ndarray) -> jnp.ndarray:
    """J(n_hat) for all n_hat; hist is the unnormalized count histogram.

    O(N) via prefix sums (the naive candidate x bin form is O(N^2) —
    dominant in the rate simulator's per-interval tick, see the `minplus`
    and `spork_predict` kernels for the materialization-free TPU paths):

      E[min(c, n)]  = M(c-1) + c * (P_tot - P(c-1))
      E[(c - n)+]   = c * P(c-1) - M(c-1)
      E[(n - c)+]   = (M_tot - M(c-1)) - c * (P_tot - P(c-1))

    with P/M the cumulative probability / first-moment sums over bins.
    """
    n = hist.shape[0]
    total = jnp.sum(hist)
    p = hist / jnp.maximum(total, 1.0)
    bins = jnp.arange(n, dtype=jnp.float32)
    P = _prefix_sum(p)
    M = _prefix_sum(p * bins)
    zero = jnp.zeros((1,), P.dtype)
    Pm1 = jnp.concatenate([zero, P[:-1]])                 # P(c-1)
    Mm1 = jnp.concatenate([zero, M[:-1]])                 # M(c-1)
    tail_p = P[-1] - Pm1                                  # P(n >= c)
    e_min = Mm1 + bins * tail_p
    e_over = bins * Pm1 - Mm1
    e_under = (M[-1] - Mm1) - bins * tail_p
    j = (coeffs.co_min * e_min + coeffs.co_over * e_over
         + coeffs.co_under * e_under + amort)
    # Candidate range: [min observed bin, max observed bin] (Alg. 2).
    has = hist > 0
    idx = jnp.arange(n)
    lo = jnp.min(jnp.where(has, idx, n))
    hi = jnp.max(jnp.where(has, idx, -1))
    mask = (idx >= lo) & (idx <= hi)
    return jnp.where(mask, j, jnp.inf)


def predict_jnp(H: jnp.ndarray, life_sum: jnp.ndarray, life_cnt: jnp.ndarray,
                n_prev: jnp.ndarray, n_curr: jnp.ndarray,
                coeffs: ObjectiveCoeffs, interval_s: float) -> jnp.ndarray:
    """Alg. 2: n_{t+1} from the histogram conditioned on n_{t-1}.

    Falls back to n_prev when the conditional histogram is empty.
    """
    hist = H[n_prev]
    amort = amortization_vector(life_sum, life_cnt, n_curr, interval_s,
                                coeffs.amort_unit)
    j = expected_objective_jnp(hist, coeffs, amort)
    best = jnp.argmin(j).astype(jnp.int32)
    empty = jnp.sum(hist) <= 0
    return jnp.where(empty, n_prev.astype(jnp.int32), best)


def allocator_tick_jnp(H: jnp.ndarray, life_sum: jnp.ndarray,
                       life_cnt: jnp.ndarray, n_lag: jnp.ndarray,
                       lam: jnp.ndarray, n_curr: jnp.ndarray,
                       coeffs: ObjectiveCoeffs, interval_s, tb,
                       gate=True) -> tuple[jnp.ndarray, jnp.ndarray,
                                           jnp.ndarray]:
    """One complete Alg. 1+2 allocator tick, in-graph.

    Folds NeededFPGAs (floor + breakeven rounding on the observed interval
    load ``lam``, in FPGA-seconds), the histogram observation
    ``H[n_lag2, n_needed] += 1``, the lag shift, and `predict_jnp` into a
    single jittable step. This is the batched tick entry point used by the
    vectorized event-driven engine (`repro.sim.events_batched`): vmapping
    it over a leading cell axis runs every simulation's allocator decision
    for the interval in one dispatch. Semantics match the stateful
    `Predictor` + the EventSim tick loop exactly (same clamps, same
    empty-histogram fallback).

    Returns ``(H, n_lag, target)`` — the updated histogram/lag state and
    the allocation target n_{t+1}. ``gate`` (traced bool) makes the whole
    tick a no-op on the H/n_lag state while still computing a (discarded)
    target — the batched engine runs one gated tick per stream entry, and
    gating the scatter-add value (instead of `where`-selecting between
    two H buffers) keeps the histogram update in place.
    """
    n_max = H.shape[0]
    n = jnp.floor(lam / interval_s)
    frac = lam - n * interval_s
    n_needed = jnp.minimum((n + (frac > tb)).astype(jnp.int32), n_max - 1)
    H = H.at[jnp.minimum(n_lag[1], n_max - 1), n_needed].add(
        jnp.where(gate, 1.0, 0.0))
    n_lag = jnp.where(gate, jnp.stack([n_needed, n_lag[0]]), n_lag)
    target = predict_jnp(H, life_sum, life_cnt, n_needed, n_curr, coeffs,
                         interval_s)
    return H, n_lag, target


def lifetime_update_from_rings(alloc_time: jnp.ndarray,
                               life_sum: jnp.ndarray, life_cnt: jnp.ndarray,
                               young_ring: jnp.ndarray,
                               dealloc_ring: jnp.ndarray, up_end: jnp.ndarray,
                               t_end: jnp.ndarray
                               ) -> tuple[jnp.ndarray, jnp.ndarray,
                                          jnp.ndarray]:
    """Replay one interval's worth of per-second pool changes into the
    per-level lifetime statistics, in one vectorized pass.

    The rate simulator allocates FPGA slots as a stack: completions push
    levels ``[u, u+c)`` at the top, idle reclaim pops ``[u-d, u)``. The
    per-second scan therefore only needs to record the push/pop COUNTS
    (``young_ring``/``dealloc_ring``, one int per second) — this replay,
    run once per allocation tick, reconstructs which levels were pushed
    and popped each second and applies the exact same updates the old
    per-second code made:

        alloc_time[i] = last second that pushed level i
        life_sum[i]  += (pop second) - (matching push second)  per pop
        life_cnt[i]  += 1                                      per pop

    All quantities are small integers in float32, so the replay is
    bit-identical to the retired per-second updates. ``t_end`` is the
    tick time (seconds); ring slot s corresponds to absolute second
    ``t_end - S + s`` because ticks land on interval boundaries.
    """
    S = young_ring.shape[0]
    n = alloc_time.shape[0]
    c = young_ring.astype(jnp.int32)
    d = dealloc_ring.astype(jnp.int32)
    delta = c - d
    pre = jnp.cumsum(delta)
    u_after = up_end - (pre[-1] - pre)              # up after second s
    u_before = u_after - delta                      # up entering second s
    top = u_before + c                              # up after completions
    lvl = jnp.arange(n)
    pushed = (lvl[None, :] >= u_before[:, None]) & (lvl[None, :] < top[:, None])
    popped = (lvl[None, :] >= u_after[:, None]) & (lvl[None, :] < top[:, None])
    t_s = (t_end - S + jnp.arange(S)).astype(jnp.float32)
    push_t = jnp.where(pushed, t_s[:, None], -jnp.inf)
    # alloc time in effect at second s = last push <= s, else the carried
    # alloc_time (push times are monotone, so a running max is exact)
    eff = jnp.maximum(jax.lax.cummax(push_t, axis=0), alloc_time[None, :])
    life_sum = life_sum + jnp.sum(
        jnp.where(popped, t_s[:, None] - eff, 0.0), axis=0)
    life_cnt = life_cnt + jnp.sum(popped, axis=0).astype(jnp.float32)
    return eff[-1], life_sum, life_cnt


_predict_jit = jax.jit(predict_jnp)


class Predictor:
    """Stateful NumPy twin for the event-driven simulator."""

    def __init__(self, n_max: int, coeffs: ObjectiveCoeffs, interval_s: float):
        self.n_max = n_max
        self.coeffs = coeffs
        self.interval_s = interval_s
        self.H = np.zeros((n_max, n_max), dtype=np.float64)
        self.life_sum = np.zeros(n_max)
        self.life_cnt = np.zeros(n_max)

    def observe(self, n_lag2: int, n_needed: int) -> None:
        self.H[min(n_lag2, self.n_max - 1), min(n_needed, self.n_max - 1)] += 1

    def record_lifetime(self, level: int, lifetime_s: float) -> None:
        level = min(level, self.n_max - 1)
        self.life_sum[level] += lifetime_s
        self.life_cnt[level] += 1

    def predict(self, n_prev: int, n_curr: int) -> int:
        # jitted (one compile per n_max): the per-tick predict is half the
        # serial DES wall time when dispatched eagerly op-by-op
        n_prev = min(n_prev, self.n_max - 1)
        out = _predict_jit(jnp.asarray(self.H), jnp.asarray(self.life_sum),
                           jnp.asarray(self.life_cnt), jnp.asarray(n_prev),
                           jnp.asarray(n_curr), self.coeffs, self.interval_s)
        return int(out)
