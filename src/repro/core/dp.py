"""Min-plus dynamic-programming equivalent of the Table 3 MILP, in JAX.

Structure (derivation in DESIGN.md): given the FPGA allocation path, the
optimal CPU allocation and the optimal FPGA/CPU work split have closed
forms under the paper's parameter ranges, so the MILP collapses to a
shortest path over FPGA levels j in [0, N] with per-interval stage costs
and inter-interval churn costs:

    F_t(j) = min_i [ F_{t-1}(i) + trans_t(i, j) ] + stage_t(j)

Transition backends (``transition=`` on the solvers):

  dense       O(N^2) per interval; the transition matrix is generated on
              the fly from index arithmetic, never materialized in HBM.
              Retained as the oracle for the structured paths.
  structured  exact O(N log N) per interval via the monotone segment
              decomposition below (default).

Structured decomposition (the fig2 compute-wall fix): the churn cost

    T(i, j) = af*(j-i)+ + df*(i-j)+ + ac*(v(j)-u(i))+ + dc*(u(i)-v(j))+

with u = y_c_prev, v = y_c_cur depends on i only through the pair
(i, u(i)). Both relu pairs flip sign once along the i axis: the FPGA pair
at i = j, and the CPU pair at the crossing k(j) = first i with
u(i) <= v(j) — a single well-defined index because u is non-increasing
in the FPGA level by construction (more FPGAs => less CPU overflow;
`_stage_tables` guarantees this). With m1 = min(j, k(j)) and
m2 = max(j, k(j)) the source axis splits into <= 3 contiguous segments
on which T is separable, T(i, j) = g(i) + h(j):

    [0,  m1)  g1(i) = F(i) - af*i + dc*u(i)   h1(j) =  af*j - dc*v(j)
    [m1, m2)  k<=j: g2 = F - af*i - ac*u(i)   h2    =  af*j + ac*v(j)
              k> j: g3 = F + df*i + dc*u(i)   h3    = -df*j - dc*v(j)
    [m2, N)   g4(i) = F(i) + df*i - ac*u(i)   h4    = -df*j + ac*v(j)

so each destination's min over i collapses to three range-min queries:
the prefix and suffix segments read one entry of an (exclusive) running
min of g1 / g4 (native cummin scans, O(N)), and the middle segment reads
a doubling (sparse) range-min table of g2 / g3 built from log N strided
min-scans — O(N log N) total per interval instead of O(N^2).

Argmin semantics: the public step (`minplus_step_structured`) carries
(value, index) pairs with value-then-index tie-breaking through every
scan and combines segments in source-index order, reproducing the dense
oracle's first-minimizer rule exactly. The DP forward pass instead runs
the value-only transition (`_structured_apply_values` — argmin-pair
bookkeeping roughly doubles the wall time) plus all y_c-only index
machinery hoisted out of the scan, then recovers each backtracked argmin
by evaluating one dense transition row per interval from the stored F
history — O(N) per interval, and first-minimizer by construction since
it IS the dense formula's argmin over the chosen destination's row.

If either y_c input is not non-increasing, `minplus_step_structured`
falls back to the dense transition at runtime (lax.cond), keeping
results correct for arbitrary inputs.

Validity guards (asserted): serving marginal work on an allocated FPGA is
never worse than on a CPU, and holding a CPU idle across an interval is
never cheaper than re-allocating it. Both hold for every configuration in
the paper's Table 6; `solve_dp` refuses configurations where they fail
(those require the exact MILP).

Exactness: equals the MILP optimum when the min-allocation-duration window
is a single interval (T_s = A_f, the paper's operating point, where the
Table 3 window constraint is implied by Y >= U). For finer intervals use
`repro.core.milp`. Verified in tests/test_milp.py.
"""

from __future__ import annotations

import functools
from dataclasses import dataclass

import jax
import jax.numpy as jnp
import numpy as np

from .metrics import RunTotals
from .workers import FleetParams


@dataclass(frozen=True)
class DpSolution:
    y_fpga: np.ndarray           # (T,) optimal FPGA allocation path
    y_cpu: np.ndarray            # (T,) implied CPU allocations
    objective: float
    energy_j: float
    cost_usd: float
    totals: RunTotals


def _check_structure(fleet: FleetParams) -> None:
    cpu, fpga, S, Ts = fleet.cpu, fleet.fpga, fleet.S, fleet.T_s
    if (fpga.busy_w - fpga.idle_w) / S > (cpu.busy_w - cpu.idle_w):
        raise ValueError(
            "FPGA-first serving is not optimal for this config; use core.milp")
    churn = cpu.spin_up_energy_j + cpu.spin_down_energy_j
    if churn > cpu.idle_w * Ts or cpu.spin_up_s > 0.1 * Ts:
        raise ValueError(
            "holding idle CPUs may beat re-allocation for this config; use core.milp")


def _stage_tables(W: jnp.ndarray, fleet: FleetParams, n_levels: int,
                  allow_cpu: bool):
    """Per-(interval, level) stage energy/cost and implied CPU counts."""
    Ts, S = fleet.T_s, fleet.S
    cpu, fpga = fleet.cpu, fleet.fpga
    j = jnp.arange(n_levels, dtype=jnp.float32)[None, :]        # (1, N)
    Wt = W[:, None].astype(jnp.float32)                          # (T, 1)
    cap = j * S * Ts
    served_f = jnp.minimum(Wt, cap)
    overflow = Wt - served_f
    b_f = served_f / (S * Ts)
    b_c = overflow / Ts
    y_c = jnp.ceil(b_c - 1e-9)
    feasible = (overflow <= 1e-9) | allow_cpu
    big = jnp.float32(1e30)
    stage_e = (fpga.idle_w * Ts * j + (fpga.busy_w - fpga.idle_w) * Ts * b_f
               + cpu.idle_w * Ts * y_c + (cpu.busy_w - cpu.idle_w) * Ts * b_c)
    stage_c = fpga.cost_per_s * Ts * j + cpu.cost_per_s * Ts * y_c
    stage_e = jnp.where(feasible, stage_e, big)
    stage_c = jnp.where(feasible, stage_c, big)
    return stage_e, stage_c, y_c, served_f, overflow


def minplus_step_jnp(F: jnp.ndarray, yc_prev: jnp.ndarray, yc_cur: jnp.ndarray,
                     coeffs: tuple[float, float, float, float]):
    """One min-plus transition: returns (new_F, argmin_i) for each j.

    coeffs = (alloc_f, dealloc_f, alloc_c, dealloc_c) in objective units.
    Oracle implementation; the Pallas `minplus` kernel computes the same
    contraction without materializing the (N, N) matrix.
    """
    af, df, ac, dc = coeffs
    n = F.shape[0]
    i = jnp.arange(n, dtype=jnp.float32)[:, None]
    jj = jnp.arange(n, dtype=jnp.float32)[None, :]
    trans = (af * jnp.maximum(jj - i, 0.0) + df * jnp.maximum(i - jj, 0.0)
             + ac * jnp.maximum(yc_cur[None, :] - yc_prev[:, None], 0.0)
             + dc * jnp.maximum(yc_prev[:, None] - yc_cur[None, :], 0.0))
    m = F[:, None] + trans
    return jnp.min(m, axis=0), jnp.argmin(m, axis=0).astype(jnp.int32)


# --------------------------------------------------------------------------
# Structured (monotone-decomposition) transition — see module docstring.
# --------------------------------------------------------------------------

_INF = jnp.float32(jnp.inf)


def _first_min_pair(v1, i1, v2, i2):
    """Elementwise (min value, first index) combine: smaller value wins,
    ties go to the smaller index. Commutative and associative, so it is
    safe in forward/backward associative scans and doubling tables."""
    take1 = (v1 < v2) | ((v1 == v2) & (i1 <= i2))
    return jnp.where(take1, v1, v2), jnp.where(take1, i1, i2)


def _prefix_min_pair(g: jnp.ndarray):
    """Inclusive running (min, first-argmin) of ``g``, left to right.

    Uses the native cummin primitive and recovers the argmin in O(1)
    extra ops: the running min pv is non-increasing, so the first source
    attaining pv[i] is the first index where pv equals pv[i] — i.e. a
    searchsorted of pv against itself. Far cheaper to trace/compile than
    an associative scan over (value, index) pairs."""
    pv = jax.lax.cummin(g)
    pa = jnp.searchsorted(-pv, -pv, side="left").astype(jnp.int32)
    return pv, pa


def _suffix_min_pair(g: jnp.ndarray):
    """Inclusive running (min, first-argmin) of ``g``, right to left.

    sv[m] = min g[m:]; the first minimizer of g[m:] is the first "suffix
    record" j >= m (a j with g[j] == sv[j]): no index in [m, j) attains
    sv[m] (it would itself be a record), so a reverse cummin over record
    indices recovers the exact first-minimizer in two primitives."""
    n = g.shape[0]
    sv = jax.lax.cummin(g, reverse=True)
    idx = jnp.arange(n, dtype=jnp.int32)
    rec = jnp.where(g == sv, idx, jnp.int32(n))
    sa = jax.lax.cummin(rec, reverse=True)
    return sv, sa


def _range_min_table(g: jnp.ndarray):
    """Doubling (sparse) range-min table over the LAST axis: level s entry
    [..., i] holds the (min, first-argmin) of g[..., i : i + 2**s]. Built
    from log N strided min-scans; a query for [lo, hi) combines the two
    overlapping power-of-two blocks at lo and hi - 2**s, preferring the
    left block on ties (any tying index in the right block is >= the left
    block's first minimizer, so first-minimizer semantics survive)."""
    n = g.shape[-1]
    pad_shape = g.shape[:-1]
    v = g
    a = jnp.broadcast_to(jnp.arange(n, dtype=jnp.int32), g.shape)
    levels_v, levels_a = [v], [a]
    for s in range(1, max(1, n.bit_length())):
        h = 1 << (s - 1)
        sv = jnp.concatenate(
            [v[..., h:], jnp.full(pad_shape + (h,), _INF, v.dtype)], axis=-1)
        sa = jnp.concatenate(
            [a[..., h:], jnp.full(pad_shape + (h,), n, jnp.int32)], axis=-1)
        v, a = _first_min_pair(v, a, sv, sa)
        levels_v.append(v)
        levels_a.append(a)
    return jnp.stack(levels_v), jnp.stack(levels_a)


def _structured_sides(yc_prev: jnp.ndarray, yc_cur: jnp.ndarray, coeffs,
                      n_table_levels: int):
    """Everything in the structured transition that does NOT depend on F:
    g-vector offsets, per-destination h terms, segment boundaries, and
    range-query indices. Shapes broadcast over leading axes, so the DP
    forward pass evaluates this ONCE for all T intervals outside its
    scan — the scan body is left with a handful of F-dependent ops."""
    af, df, ac, dc = coeffs
    n = yc_prev.shape[-1]
    i = jnp.arange(n, dtype=jnp.float32)
    j = jnp.arange(n, dtype=jnp.int32)

    # Crossing of the CPU relu pair: first i with yc_prev[i] <= yc_cur[j].
    search = lambda a, q: jnp.searchsorted(a, q, side="left")
    for _ in range(yc_prev.ndim - 1):
        search = jax.vmap(search)
    k = search(-yc_prev, -yc_cur).astype(jnp.int32)
    m1 = jnp.minimum(j, k)
    m2 = jnp.maximum(j, k)
    length = m2 - m1
    s = jnp.floor(jnp.log2(jnp.maximum(length, 1).astype(jnp.float32)))
    s = jnp.clip(s.astype(jnp.int32), 0, n_table_levels - 1)
    r2 = jnp.maximum(m2 - jnp.left_shift(1, s), 0)
    use_g2 = k <= j

    base = jnp.stack([-af * i + dc * yc_prev,       # g1 = F + base[0]
                      -af * i - ac * yc_prev,       # g2
                      df * i + dc * yc_prev,        # g3
                      df * i - ac * yc_prev],       # g4
                     axis=-2)
    h1 = af * i - dc * yc_cur
    h4 = -df * i + ac * yc_cur
    h_mid = jnp.where(use_g2, af * i + ac * yc_cur, -df * i - dc * yc_cur)
    # table row: 0 -> g2 (k <= j: alloc FPGAs + CPUs), 1 -> g3
    w_mid = jnp.where(use_g2, 0, 1).astype(jnp.int32)
    return base, (h1, h_mid, h4), (m1, m2, s, r2, w_mid)


def _structured_apply(F: jnp.ndarray, base: jnp.ndarray, hs, qs):
    """F-dependent half of the structured transition (the scan-body part):
    three range-min queries per destination over the g vectors."""
    h1, h_mid, h4 = hs
    m1, m2, s, r2, w_mid = qs
    n = F.shape[0]
    g = F + base                                    # (4, N)
    inf1 = jnp.full((1,), _INF)
    zero1 = jnp.zeros((1,), jnp.int32)

    # Prefix segment [0, m1): exclusive running min of g1.
    pv, pa = _prefix_min_pair(g[0])
    pv = jnp.concatenate([inf1, pv])[m1] + h1
    pa = jnp.concatenate([zero1, pa])[m1]

    # Suffix segment [m2, N): exclusive-from-the-right running min of g4.
    sv, sa = _suffix_min_pair(g[3])
    sv = jnp.concatenate([sv, inf1])[m2] + h4
    sa = jnp.concatenate([sa, zero1])[m2]

    # Middle segment [m1, m2): one stacked doubling table answers both the
    # g2 (k <= j) and g3 (k > j) cases; w_mid picks the row per query.
    tv, ta = _range_min_table(g[1:3])               # (L, 2, N) each
    mv1, ma1 = tv[s, w_mid, m1], ta[s, w_mid, m1]
    mv2, ma2 = tv[s, w_mid, r2], ta[s, w_mid, r2]
    mv, ma = _first_min_pair(mv1, ma1, mv2, ma2)
    empty = m2 <= m1
    mv = jnp.where(empty, _INF, mv) + h_mid
    ma = jnp.where(empty, 0, ma)

    # Combine in source-index order (prefix < middle < suffix); strict <
    # keeps the earliest segment on ties => global first minimizer.
    best_v, best_a = pv, pa
    take = mv < best_v
    best_v, best_a = jnp.where(take, mv, best_v), jnp.where(take, ma, best_a)
    take = sv < best_v
    best_v, best_a = jnp.where(take, sv, best_v), jnp.where(take, sa, best_a)
    return best_v, best_a.astype(jnp.int32)


def _structured_apply_values(F: jnp.ndarray, base: jnp.ndarray, hs, qs):
    """Value-only `_structured_apply`: no argmin tracking anywhere, so
    every scan/table/query is a bare `minimum`. This is what the DP
    forward pass runs — tracking (value, index) pairs through the scans
    roughly doubled the transition's wall time, and the backtrack can
    recover exact argmins later from the stored F history at O(N) per
    interval (`_dp_forward_core`)."""
    h1, h_mid, h4 = hs
    m1, m2, s, r2, w_mid = qs
    n = F.shape[0]
    g = F + base
    inf1 = jnp.full((1,), _INF)

    pv = jax.lax.cummin(g[0])
    pv = jnp.concatenate([inf1, pv])[m1] + h1
    sv = jax.lax.cummin(g[3], reverse=True)
    sv = jnp.concatenate([sv, inf1])[m2] + h4

    v = g[1:3]
    levels = [v]
    for s_ in range(1, max(1, n.bit_length())):
        h = 1 << (s_ - 1)
        v = jnp.minimum(v, jnp.concatenate(
            [v[..., h:], jnp.full(v.shape[:-1] + (h,), _INF)], axis=-1))
        levels.append(v)
    tv = jnp.stack(levels)
    mv = jnp.minimum(tv[s, w_mid, m1], tv[s, w_mid, r2])
    mv = jnp.where(m2 <= m1, _INF, mv) + h_mid
    return jnp.minimum(jnp.minimum(pv, mv), sv)


def _structured_transition(F: jnp.ndarray, yc_prev: jnp.ndarray,
                           yc_cur: jnp.ndarray, coeffs):
    """Exact structured min-plus transition; requires yc_prev and yc_cur
    non-increasing (see module docstring for the segment derivation)."""
    L = max(1, F.shape[0].bit_length())
    base, hs, qs = _structured_sides(yc_prev, yc_cur, coeffs, L)
    return _structured_apply(F, base, hs, qs)


def minplus_step_structured(F: jnp.ndarray, yc_prev: jnp.ndarray,
                            yc_cur: jnp.ndarray,
                            coeffs: tuple[float, float, float, float],
                            check: bool = True):
    """Drop-in replacement for `minplus_step_jnp` in O(N log N).

    Exact — values, argmins, and first-minimizer tie handling match the
    dense oracle — whenever both y_c vectors are non-increasing, which
    `_stage_tables` guarantees by construction. With ``check=True`` the
    monotonicity precondition is verified at runtime and the dense
    transition is used as a fallback if it is violated; the DP forward
    pass uses ``check=False`` because its inputs are monotone by
    construction (and lax.cond would evaluate both branches under vmap,
    reinstating the O(N^2) cost it exists to remove)."""
    if not check:
        return _structured_transition(F, yc_prev, yc_cur, coeffs)
    mono = (jnp.all(yc_prev[1:] <= yc_prev[:-1])
            & jnp.all(yc_cur[1:] <= yc_cur[:-1]))
    return jax.lax.cond(
        mono,
        lambda: _structured_transition(F, yc_prev, yc_cur, coeffs),
        lambda: minplus_step_jnp(F, yc_prev, yc_cur, coeffs))


TRANSITIONS = ("dense", "structured")


def _transition_step(transition: str):
    """Resolve a transition backend name to a step function (see module
    docstring). `_stage_tables` y_c is non-increasing by construction, so
    the structured paths skip the runtime monotonicity check here."""
    if transition == "dense":
        return minplus_step_jnp
    if transition == "structured":
        return functools.partial(minplus_step_structured, check=False)
    raise ValueError(f"unknown transition {transition!r}; "
                     f"expected one of {TRANSITIONS}")


def _dp_forward_core(stage_obj: jnp.ndarray, y_c: jnp.ndarray,
                     coeffs: jnp.ndarray, n_levels: int, allow_cpu: bool,
                     transition: str = "structured"):
    """Forward min-plus pass + backtrack for one (stage_obj, y_c, coeffs)
    problem. Unjitted: wrapped by `_dp_forward` (single) and vmapped by
    `_solve_batch` (all energy weights / traces in one dispatch)."""
    af, df, ac, dc = coeffs

    j = jnp.arange(n_levels, dtype=jnp.float32)
    # boundary 0: from empty fleet
    F0 = af * j + ac * y_c[0] + stage_obj[0]

    if transition == "structured":
        # Two structural optimizations over the naive step-per-interval
        # form (both matter on CPU, where op dispatch and argmin-pair
        # bookkeeping dominate):
        #   1. the y_c-only half of the transition (g offsets, h terms,
        #      segment boundaries, range-query indices) is hoisted out of
        #      the scan and computed for ALL intervals at once;
        #   2. the forward pass is value-only (`_structured_apply_values`
        #      — bare `minimum` scans, no (value, index) pairs); the scan
        #      emits each interval's incoming F row, and the backtrack
        #      recovers each argmin by evaluating ONE dense transition
        #      row per interval (O(N), first-minimizer semantics of the
        #      dense oracle by construction).
        L = max(1, int(n_levels).bit_length())
        base, hs, qs = _structured_sides(y_c[:-1], y_c[1:],
                                         (af, df, ac, dc), L)

        def body(F, xs):
            stage, base_t, h_t, q_t = xs
            newF = _structured_apply_values(F, base_t, h_t, q_t)
            return newF + stage, F          # emit the incoming F row

        F_last, F_hist = jax.lax.scan(
            body, F0, (stage_obj[1:], base, hs, qs))
        # closing boundary: dealloc everything
        end = F_last + df * j + dc * y_c[-1]
        j_last = jnp.argmin(end).astype(jnp.int32)
        i = jnp.arange(n_levels, dtype=jnp.float32)

        def back(carry, xs):
            F_prev, yc_prev, yc_cur = xs
            jf = carry.astype(jnp.float32)
            row = (F_prev + af * jnp.maximum(jf - i, 0.0)
                   + df * jnp.maximum(i - jf, 0.0)
                   + ac * jnp.maximum(yc_cur[carry] - yc_prev, 0.0)
                   + dc * jnp.maximum(yc_prev - yc_cur[carry], 0.0))
            prev = jnp.argmin(row).astype(jnp.int32)
            return prev, prev

        _, path_rev = jax.lax.scan(back, j_last,
                                   (F_hist, y_c[:-1], y_c[1:]),
                                   reverse=True)
        path = jnp.concatenate([path_rev, j_last[None]])
        return path, jnp.min(end)

    step = _transition_step(transition)

    def body(F, xs):
        stage, yc_prev, yc_cur = xs
        newF, arg = step(F, yc_prev, yc_cur, (af, df, ac, dc))
        return newF + stage, arg

    xs = (stage_obj[1:], y_c[:-1], y_c[1:])
    F_last, args = jax.lax.scan(body, F0, xs)
    # closing boundary: dealloc everything
    end = F_last + df * j + dc * y_c[-1]
    j_last = jnp.argmin(end)

    def back(carry, arg_row):
        prev = arg_row[carry]
        return prev, prev

    _, path_rev = jax.lax.scan(back, j_last.astype(jnp.int32), args, reverse=True)
    path = jnp.concatenate([path_rev, j_last[None].astype(jnp.int32)])
    return path, jnp.min(end)


@functools.partial(jax.jit,
                   static_argnames=("n_levels", "allow_cpu", "transition"))
def _dp_forward(W: jnp.ndarray, stage_obj: jnp.ndarray, y_c: jnp.ndarray,
                coeffs: jnp.ndarray, n_levels: int, allow_cpu: bool,
                transition: str = "structured"):
    del W  # shape information only; the stage tables already encode it
    return _dp_forward_core(stage_obj, y_c, coeffs, n_levels, allow_cpu,
                            transition)


def _objective_weights(energy_weight: float, fleet: FleetParams):
    """(we, wc) mixing weights in normalized objective units."""
    e_unit = fleet.fpga.busy_w * fleet.T_s
    c_unit = fleet.fpga.cost_per_s * fleet.T_s
    we = energy_weight / e_unit if energy_weight > 0 else 0.0
    wc = (1 - energy_weight) / c_unit if energy_weight < 1 else 0.0
    if energy_weight >= 1.0:
        we, wc = 1.0, 0.0
    if energy_weight <= 0.0:
        we, wc = 0.0, 1.0
    return we, wc


def _churn_coeffs(we, wc, fleet: FleetParams):
    return [
        we * fleet.fpga.spin_up_energy_j
        + wc * fleet.fpga.cost_per_s * fleet.fpga.spin_up_s,
        we * fleet.fpga.spin_down_energy_j,
        we * fleet.cpu.spin_up_energy_j
        + wc * fleet.cpu.cost_per_s * fleet.cpu.spin_up_s,
        we * fleet.cpu.spin_down_energy_j,
    ]


@functools.partial(jax.jit,
                   static_argnames=("fleet", "n_levels", "allow_cpu",
                                    "transition"))
def _solve_batch(W_b: jnp.ndarray, we_b: jnp.ndarray, wc_b: jnp.ndarray,
                 coeffs_b: jnp.ndarray, fleet: FleetParams, n_levels: int,
                 allow_cpu: bool, transition: str = "structured"):
    """Stage tables + min-plus forward for a whole batch in one dispatch.

    W_b: (B, T) per-interval work; we_b/wc_b: (B,) objective weights;
    coeffs_b: (B, 4) churn coefficients. Returns (paths (B, T), obj (B,)).
    """
    stage_e, stage_c, y_c, _, _ = jax.vmap(
        lambda w: _stage_tables(w, fleet, n_levels, allow_cpu))(W_b)
    stage_obj = (we_b[:, None, None] * stage_e
                 + wc_b[:, None, None] * stage_c)
    return jax.vmap(
        lambda s, y, c: _dp_forward_core(s, y, c, n_levels, allow_cpu,
                                         transition))(stage_obj, y_c,
                                                      coeffs_b)


def _check_transition(transition: str) -> None:
    if transition not in TRANSITIONS:
        raise ValueError(f"unknown transition {transition!r}; "
                         f"expected one of {TRANSITIONS}")


def solve_dp_batch(work_batch: np.ndarray, fleet: FleetParams,
                   energy_weights, allow_cpu: bool = True,
                   allow_fpga: bool = True, n_levels: int | None = None,
                   transition: str = "structured") -> list[DpSolution]:
    """Batched `solve_dp`: row i of ``work_batch`` is solved with
    ``energy_weights[i]`` in a handful of vmapped dispatches. Build the
    (trace x weight) cross product in the caller; per-row results equal
    `solve_dp` at the same ``n_levels``.

    The DP optimum is invariant to extra levels (stage costs grow
    strictly above the peak need), so the level count per row is a pure
    shape/perf choice. For the dense transition rows are bucketed by
    their own peak-demand level count (rounded up to a multiple of 128)
    and each bucket dispatches once — O(n_levels^2) per interval means
    solving a calm trace at a bursty trace's level count wastes orders
    of magnitude of work. The structured transition is
    ~linear in the level count, where the dominant cost is instead the
    per-program overhead (trace + lower + compile-cache round trip) of
    every distinct bucket shape, so all rows share one bucket sized to
    the batch peak: one program per call. Pass an explicit ``n_levels``
    to override either policy."""
    _check_transition(transition)
    _check_structure(fleet)
    W_np = np.asarray(work_batch, dtype=np.float64)
    if W_np.ndim != 2:
        raise ValueError(f"work_batch must be (B, T), got {W_np.shape}")
    B = W_np.shape[0]
    weights = np.asarray(energy_weights, dtype=np.float64)
    if weights.shape != (B,):
        raise ValueError("energy_weights must align with work_batch rows")

    if not allow_fpga:
        buckets = np.ones((B,), dtype=np.int64)
    elif n_levels is not None:
        buckets = np.full((B,), n_levels, dtype=np.int64)
    else:
        per_row = np.ceil(W_np.max(axis=1) / (fleet.S * fleet.T_s)) + 2
        buckets = (128 * np.ceil(per_row / 128)).astype(np.int64)
        if transition != "dense":
            buckets = np.full((B,), buckets.max(), dtype=np.int64)

    wewc = np.array([_objective_weights(float(w), fleet) for w in weights],
                    np.float32)
    coeffs_b = np.array([_churn_coeffs(we, wc, fleet) for we, wc in wewc],
                        np.float32)

    out: list[DpSolution | None] = [None] * B
    for nl in np.unique(buckets):
        rows = np.nonzero(buckets == nl)[0]
        paths, objs = _solve_batch(jnp.asarray(W_np[rows], dtype=jnp.float32),
                                   jnp.asarray(wewc[rows, 0]),
                                   jnp.asarray(wewc[rows, 1]),
                                   jnp.asarray(coeffs_b[rows]), fleet,
                                   int(nl), allow_cpu, transition)
        paths, objs = np.asarray(paths), np.asarray(objs)
        for k, b in enumerate(rows):
            out[b] = evaluate_path(W_np[b], paths[k], fleet,
                                   objective=float(objs[k]))
    return out


def solve_dp(work_cpu_s: np.ndarray, fleet: FleetParams,
             energy_weight: float = 1.0, allow_cpu: bool = True,
             allow_fpga: bool = True, n_levels: int | None = None,
             transition: str = "structured") -> DpSolution:
    """Solve the idealized scheduler by min-plus DP and evaluate the path."""
    _check_transition(transition)
    _check_structure(fleet)
    W = jnp.asarray(work_cpu_s, dtype=jnp.float32)
    Ts, S = fleet.T_s, fleet.S
    if n_levels is None:
        n_levels = int(np.ceil(float(np.max(work_cpu_s)) / (S * Ts))) + 2
    if not allow_fpga:
        n_levels = 1

    stage_e, stage_c, y_c, _, _ = _stage_tables(W, fleet, n_levels, allow_cpu)
    we, wc = _objective_weights(energy_weight, fleet)
    stage_obj = we * stage_e + wc * stage_c
    coeffs = jnp.asarray(_churn_coeffs(we, wc, fleet), dtype=jnp.float32)

    path, obj = _dp_forward(W, stage_obj, y_c, coeffs, n_levels, allow_cpu,
                            transition)
    path = np.asarray(path)
    return evaluate_path(np.asarray(work_cpu_s), path, fleet,
                         objective=float(obj))


def evaluate_path(W: np.ndarray, y_fpga: np.ndarray, fleet: FleetParams,
                  objective: float = float("nan")) -> DpSolution:
    """Exact energy/cost accounting for a given FPGA allocation path
    (FPGA-first serving, implied CPU allocations). NumPy; used both to
    evaluate DP output and as the rate-level 'oracle platform' evaluator."""
    Ts, S = fleet.T_s, fleet.S
    cpu, fpga = fleet.cpu, fleet.fpga
    y = np.asarray(y_fpga, dtype=np.float64)
    W = np.asarray(W, dtype=np.float64)
    cap = y * S * Ts
    served_f = np.minimum(W, cap)
    overflow = W - served_f
    if np.any(overflow > 1e-6) and fleet.max_cpus == 0:
        raise ValueError("infeasible path: overflow with no CPUs allowed")
    b_f = served_f / (S * Ts)
    b_c = overflow / Ts
    y_cpu = np.ceil(b_c - 1e-9)

    dy_f = np.diff(np.concatenate([[0.0], y, [0.0]]))
    dy_c = np.diff(np.concatenate([[0.0], y_cpu, [0.0]]))
    alloc_f, dealloc_f = np.sum(np.maximum(dy_f, 0)), np.sum(np.maximum(-dy_f, 0))
    alloc_c, dealloc_c = np.sum(np.maximum(dy_c, 0)), np.sum(np.maximum(-dy_c, 0))

    fpga_busy_j = float(np.sum(b_f) * fpga.busy_w * Ts)
    fpga_idle_j = float(np.sum(y - b_f) * fpga.idle_w * Ts)
    cpu_busy_j = float(np.sum(b_c) * cpu.busy_w * Ts)
    cpu_idle_j = float(np.sum(y_cpu - b_c) * cpu.idle_w * Ts)
    spin_j = float(alloc_f * fpga.spin_up_energy_j + dealloc_f * fpga.spin_down_energy_j
                   + alloc_c * cpu.spin_up_energy_j + dealloc_c * cpu.spin_down_energy_j)
    energy = fpga_busy_j + fpga_idle_j + cpu_busy_j + cpu_idle_j + spin_j
    cost = float(np.sum(y) * fpga.cost_per_s * Ts + np.sum(y_cpu) * cpu.cost_per_s * Ts
                 + alloc_f * fpga.cost_per_s * fpga.spin_up_s
                 + alloc_c * cpu.cost_per_s * cpu.spin_up_s)

    totals = RunTotals(
        energy_j=energy, cost_usd=cost, work_cpu_s=float(np.sum(W)),
        work_on_fpga_cpu_s=float(np.sum(served_f)),
        work_on_cpu_cpu_s=float(np.sum(overflow)),
        fpga_spinups=int(alloc_f), cpu_spinups=int(alloc_c),
        fpga_idle_j=fpga_idle_j, fpga_busy_j=fpga_busy_j, cpu_busy_j=cpu_busy_j,
        spinup_j=spin_j,
    )
    return DpSolution(y_fpga=y.astype(int), y_cpu=y_cpu.astype(int),
                      objective=objective, energy_j=energy, cost_usd=cost,
                      totals=totals)


PARETO_WEIGHTS = np.concatenate([[0.0], np.geomspace(0.02, 1.0, 9)])


def pareto_front(work_cpu_s: np.ndarray, fleet: FleetParams,
                 weights: np.ndarray | None = None, **kw) -> list[DpSolution]:
    """Sweep the energy/cost weighting (paper Fig. 3 pareto curves).

    All weights are solved in ONE `_solve_batch` dispatch: the min-plus
    forward pass vmaps over the weight axis instead of re-running the DP
    per weight."""
    if weights is None:
        weights = PARETO_WEIGHTS
    weights = np.asarray(weights, dtype=np.float64)
    W = np.asarray(work_cpu_s, dtype=np.float64)
    W_b = np.broadcast_to(W, (len(weights), len(W)))
    return solve_dp_batch(W_b, fleet, weights, **kw)
