"""Batched event-driven simulator: per-request dispatch as one `lax.scan`.

The exact Python DES (`repro.sim.events.EventSim`) is the semantic oracle
for the paper's Table 9 (dispatch-policy ablation): efficient-first
('spork'), AutoScale-style index packing, and MArk-style round robin only
differ at per-request granularity, so the rate simulator cannot separate
them. But the oracle is a serial heap/bisect loop — the last serial cost
in the benchmark suite. This module re-expresses the same semantics as a
fixed-shape JAX program so the whole Table 9 grid (policy x app x trace)
runs in a handful of dispatches:

  * A fixed-size **worker state table** replaces the heap: FPGA slots in
    ``[0, w_fpga)``, CPU slots in ``[w_fpga, w_fpga + w_cpu)`` (the kind
    is the slot position — no kind column), per slot wid / alive /
    alloc_t / ready_at / available_at / busy_s / allocation level. Slots
    are reused after deallocation; the monotone ``wid`` preserves the
    oracle's tie-breaking and round-robin-ring order.
  * **Lazy lifecycle events**: a worker's ready / idle-timeout times are
    pure functions of its row (dealloc at ``max(ready_at, available_at)
    + idle_timeout`` unless new work arrives first), so there is no event
    heap: every arrival masks timed-out workers out of the candidate sets
    (``live``) and reads readiness as ``ready_at < t``; the dealloc
    *settlement* (energy, cost, the predictor's lifetime stats, slot
    reclamation) runs lazily at interval ticks and the final drain. This
    reproduces the oracle's event order, including arrivals-before-events
    and ticks-before-ready at equal timestamps.
  * **Branch-free dispatch** (paper Alg. 3) tuned for XLA:CPU scans,
    where per-step cost is reduction- and op-count-bound, not flop-bound:
    each arrival does exactly THREE reductions — the wid-comparison
    matrix for round-robin ring ranks (FPGA region only), one stacked max
    over the four (kind x ready/pending) feasible-candidate groups plus
    the ring size, and one stacked max resolving wid tie-breaks, the
    cyclic ring priority and the first free CPU slot. Everything else —
    winner one-hots, assignment writes, miss/work/interval-load
    accounting — is elementwise, accumulated per-slot and only summed at
    ticks (interval load) or at the end of the run (totals). The
    dispatcher is a *traced* integer: all three policies share one
    compiled program.
  * **Flat entry stream**: the scan runs over fixed-width arrival blocks
    interleaved with explicit tick entries (per-cell flags/times), built
    host-side so every Spork tick (Algs. 1-2, via
    `core.predictor.allocator_tick_jnp` — the same `predict_jnp` kernel
    the oracle's `Predictor` calls) lands between the right two
    arrivals. Padding is ~the block width per interval instead of the
    worst-case interval's arrival count.
  * `simulate_events_batch` vmaps the whole thing over a cell axis
    (dispatcher x app x seed x objective): one compiled program per
    (padded entry count, cell count, n_max, table shape).

Equivalence contract (tests/test_events_batched.py): on integer-quantized
instances (arrival times, sizes, spin-ups and timeouts on a coarse dyadic
grid, magnitudes < 2^24 so float32 arithmetic is exact) the engine
matches `EventSim` **exactly** on requests, deadline misses, spin-up
counts and work split, and to ~1e-5 relative on energy/cost (the oracle
accumulates in float64). On continuous traces the trajectories can
diverge at float32 near-ties; totals agree to a few percent (documented
in docs/architecture.md). ``RunTotals.breakdown['slot_overflow']`` counts
dispatch/allocation events dropped because a table region was full —
always 0 for large enough ``w_fpga``/``w_cpu``, and asserted 0 in tests.
"""

from __future__ import annotations

import functools
import math
from dataclasses import dataclass
from typing import Any, Iterable, NamedTuple

import numpy as np

import jax
import jax.numpy as jnp

from repro.core.breakeven import objective_setup
from repro.core.metrics import RunTotals
from repro.core.predictor import ObjectiveCoeffs, allocator_tick_jnp
from repro.core.workers import DEFAULT_FLEET, FleetParams
from repro.ft.failures import (DRAW_CRASH, DRAW_EVAC, DRAW_SPINUP,
                               DRAW_STRAGGLE, FSTAT_OFF, FailStatic,
                               FailureSpec, failure_u01)
from repro.policies import Candidates, dispatch_policies, dispatch_select
from repro.sim.events import DISPATCHERS
from repro.sim.ratesim import Accum

#: name -> traced policy code (from the registry, so plugin dispatch
#: policies join the shared compiled program automatically)
DISPATCH_CODES = {p.name: p.code for p in dispatch_policies()}

_NEG = -jnp.inf

# Arrival-block width of the entry stream. Small enough that per-interval
# padding (~B/2 per interval) is negligible, large enough that the
# per-entry tick body amortizes.
BLOCK = 128

# Upper bound on cells per compiled program; the cell axis is padded to
# the next power of two up to this cap (padding repeats cell 0; padded
# results are discarded), larger grids run in chunks of the cap.
EV_CHUNK_MAX = 32


class EventScalars(NamedTuple):
    """Traced per-cell parameters (every leaf carries the cell axis in
    the batched entry point)."""

    size: jnp.ndarray        # request service time on a CPU worker (s)
    deadline: jnp.ndarray    # completion deadline (s)
    S: jnp.ndarray           # FPGA speedup over CPU
    T_s: jnp.ndarray         # scheduling interval
    tb: jnp.ndarray          # breakeven threshold (objective-dependent)
    co_min: jnp.ndarray      # Alg. 2 objective coefficients
    co_over: jnp.ndarray
    co_under: jnp.ndarray
    amort_unit: jnp.ndarray
    A_f_s: jnp.ndarray       # FPGA spin-up seconds
    A_c_s: jnp.ndarray       # CPU spin-up seconds
    to_f: jnp.ndarray        # FPGA idle timeout (= T_s)
    to_c: jnp.ndarray        # CPU idle timeout
    B_f: jnp.ndarray         # busy / idle watts
    I_f: jnp.ndarray
    B_c: jnp.ndarray
    I_c: jnp.ndarray
    C_f: jnp.ndarray         # $/s
    C_c: jnp.ndarray
    spin_e_f: jnp.ndarray    # spin-up + spin-down energy per worker (J)
    spin_e_c: jnp.ndarray
    d_f_s: jnp.ndarray       # spin-down seconds
    d_c_s: jnp.ndarray
    # failure axis (repro.ft.failures.FailureSpec.floats() order); traced,
    # so cells with different rates share one compiled program — the
    # *static* part (enabled + retry/failover bounds) is `FailStatic`
    f_spin_p: jnp.ndarray    # per-attempt spin-up failure probability
    f_backoff: jnp.ndarray   # seconds between spin-up attempts
    f_crash_p: jnp.ndarray   # per-assignment mid-service crash probability
    f_sfrac: jnp.ndarray     # straggler fraction / slowdown factor
    f_sfactor: jnp.ndarray
    f_evac0: jnp.ndarray     # evacuation window [start, end)
    f_evac1: jnp.ndarray
    f_efrac: jnp.ndarray     # evacuated fraction
    f_seed: jnp.ndarray      # uint32 hash seed
    max_fpgas: jnp.ndarray   # int32 N_f cap
    allocate: jnp.ndarray    # bool: run the Spork allocator at ticks

    @property
    def coeffs(self) -> ObjectiveCoeffs:
        return ObjectiveCoeffs(self.co_min, self.co_over, self.co_under,
                               self.amort_unit)


class WorkerTable(NamedTuple):
    """Fixed-size per-worker state (the heap + bisect lists of the
    oracle). FPGA slots first, CPU slots after; ``wid`` is the monotone
    allocation id that defines every ordering the oracle derives from
    list positions."""

    wid: jnp.ndarray         # (W,) int32, 0 = never used
    alive: jnp.ndarray       # (W,) bool
    alloc_t: jnp.ndarray     # (W,) f32
    ready_at: jnp.ndarray    # (W,) f32 spin-up completion
    avail: jnp.ndarray       # (W,) f32 queue-drain time
    busy: jnp.ndarray        # (W,) f32 accumulated service seconds
    level: jnp.ndarray       # (W,) int32 allocation level at spin-up
    # failure-axis columns (constant when the axis is compiled off)
    n_assign: jnp.ndarray    # (W,) i32 per-worker assignment counter
                             #       (crash-draw hash counter)
    crash_t: jnp.ndarray     # (W,) f32 crash time, +inf = not crashed
    slow: jnp.ndarray        # (W,) f32 straggler multiplier (1.0 normal)
    nfail: jnp.ndarray       # (W,) i32 failed spin-up attempts before ready


class FailAcc(NamedTuple):
    """Resilience counters (RunTotals extension); all-zero when the
    failure axis is off."""

    retries: jnp.ndarray           # i32 failed-then-retried spin-up attempts
    failed_spins: jnp.ndarray      # i32 failed attempts incl. stillborn
    crashes: jnp.ndarray           # i32 workers lost mid-service
    recovered: jnp.ndarray         # i32 crashed requests served by failover
    fail_misses: jnp.ndarray       # i32 misses attributable to failures
    dropped: jnp.ndarray           # i32 requests dropped (failover exhausted)
    cpu_spins: jnp.ndarray         # i32 CPU spin-ups (incl. stillborn;
                                   #     replaces the next_wid derivation)
    wasted_j: jnp.ndarray          # f32 energy of failed spin-up attempts
    extra_cost: jnp.ndarray        # f32 cost of failed spin-up attempts
    work_f: jnp.ndarray            # f32 cpu-seconds served on FPGAs
    work_c: jnp.ndarray            # f32 cpu-seconds served on CPUs
                                   #     (serv_slot can't split work under
                                   #      stragglers/crashes, so the
                                   #      enabled path counts explicitly)


def _fail_zero() -> FailAcc:
    zi, zfs = jnp.int32(0), jnp.float32(0)
    return FailAcc(zi, zi, zi, zi, zi, zi, zi, zfs, zfs, zfs, zfs)


class EvCarry(NamedTuple):
    """Arrival-level carry: the worker table plus per-slot accumulators
    (summed only at ticks / at the end, so arrivals never reduce them)."""

    ws: WorkerTable
    serv_slot: jnp.ndarray   # (W,) f32 service-seconds ever dispatched;
                             # CPU service == request size, so the CPU
                             # half doubles as the cpu-work accumulator
    miss_slot: jnp.ndarray   # (W,) f32 deadline misses
    next_wid: jnp.ndarray    # i32 monotone wid counter
    rr_pos: jnp.ndarray      # i32 raw round-robin cursor (oracle semantics)
    overflow: jnp.ndarray    # i32 events dropped for lack of a free slot
    fail: FailAcc


class TickState(NamedTuple):
    """Interval-level state, untouched by arrival steps."""

    H: jnp.ndarray           # (n_max, n_max) conditional histograms
    n_lag: jnp.ndarray       # (2,) i32
    life_sum: jnp.ndarray    # (n_max,) f32 per-level lifetime stats
    life_cnt: jnp.ndarray    # (n_max,) f32
    F_prev: jnp.ndarray      # f32 F_slot total at the last tick
    C_prev: jnp.ndarray      # f32 C_slot total at the last tick
    spins: jnp.ndarray       # f32 FPGA spin-up count
    energy: jnp.ndarray      # (6,) f32: fpga_busy/fpga_idle/cpu_busy/
                             #           cpu_idle/spin_j/cost settlements


def _settle(es: EventScalars, is_f, c: EvCarry, ts: TickState, t, gate):
    """Dealloc settlement: retire every worker whose idle timeout expired
    strictly before t. The oracle's idle_check fires at max(ready_at,
    available_at) + timeout unless a new assignment intervenes; arrivals
    only *mask* timed-out workers, so applying the accounting lazily here
    (ticks + final drain) is exact — each row is frozen from its timeout
    on. Matches EventSim._dealloc + _finalize per worker."""
    ws = c.ws
    idle_d = (jnp.maximum(ws.ready_at, ws.avail)
              + jnp.where(is_f, es.to_f, es.to_c))
    # crashed rows settle at their (future-dated) crash time, like the
    # oracle's dealloc_t = t_crash; crash_t == +inf (no crash, or the
    # failure axis compiled off) leaves the idle-timeout time — and the
    # strict < reproduces the oracle's tick-before-crash_settle order at
    # equal timestamps. nfail == 0 / crash_t == inf make this identical,
    # bit for bit, to the pre-failure-model settlement.
    dtime = jnp.where(ws.crash_t < jnp.inf, ws.crash_t, idle_d)
    m = ws.alive & (dtime < t) & gate
    mf = m.astype(jnp.float32)
    life = dtime - ws.alloc_t
    spin_s = (jnp.where(is_f, es.A_f_s, es.A_c_s)
              * (1.0 + ws.nfail.astype(jnp.float32)))  # backoff gaps idle
    idle = jnp.maximum(life - ws.busy - spin_s, 0.0)
    busy_j = ws.busy * jnp.where(is_f, es.B_f, es.B_c)
    idle_j = idle * jnp.where(is_f, es.I_f, es.I_c)
    cost = ((life + jnp.where(is_f, es.d_f_s, es.d_c_s))
            * jnp.where(is_f, es.C_f, es.C_c))
    isf = is_f.astype(jnp.float32)
    energy = ts.energy + jnp.stack([
        jnp.sum(mf * isf * busy_j), jnp.sum(mf * isf * idle_j),
        jnp.sum(mf * (1 - isf) * busy_j), jnp.sum(mf * (1 - isf) * idle_j),
        jnp.sum(mf * jnp.where(is_f, es.spin_e_f, es.spin_e_c)),
        jnp.sum(mf * cost)])
    n_max = ts.life_sum.shape[0]
    lvl = jnp.minimum(ws.level, n_max - 1)
    rec = m & is_f
    ts = ts._replace(
        energy=energy,
        life_sum=ts.life_sum.at[lvl].add(jnp.where(rec, life, 0.0)),
        life_cnt=ts.life_cnt.at[lvl].add(rec.astype(jnp.float32)))
    return c._replace(ws=ws._replace(alive=ws.alive & ~m)), ts

def _evac_ok(es: EventScalars, t, wid):
    """Feasibility mask for the evacuation window (EventSim._evac_now):
    False while a worker's hash-drawn evacuation membership is inside an
    active window. Recomputed from ``wid`` (the draw is deterministic)
    rather than stored, so it needs no table column."""
    member = (failure_u01(es.f_seed, wid, 0, DRAW_EVAC, xp=jnp)
              < es.f_efrac)
    return ~(member & (es.f_evac0 <= t) & (t < es.f_evac1))


def _spin_fails(es: EventScalars, wid, R: int):
    """Leading-failure count of the spin-up attempt draws for ``wid``
    (counter = attempt index), capped at R + 1 == stillborn. Mirrors the
    oracle's while loop in EventSim._spin_up attempt by attempt."""
    nf = jnp.zeros(jnp.shape(wid), jnp.int32)
    run = jnp.ones(jnp.shape(wid), bool)
    for k in range(R + 1):
        run = run & (failure_u01(es.f_seed, wid, k, DRAW_SPINUP, xp=jnp)
                     < es.f_spin_p)
        nf = nf + run.astype(jnp.int32)
    return nf


def _slow_draw(es: EventScalars, wid):
    """Straggler multiplier drawn once per worker at spin-up."""
    return jnp.where(
        failure_u01(es.f_seed, wid, 0, DRAW_STRAGGLE, xp=jnp) < es.f_sfrac,
        es.f_sfactor, jnp.float32(1.0))


def _find_candidates(es: EventScalars, code, w_f: int, is_f, idxW,
                     ws: WorkerTable, rr_pos, t, svc_w, live, ok):
    """Alg. 3 candidate search shared by the pristine and failure-aware
    arrival paths (see `_arrival_step` for the reduction layout and
    `EventSim._try_type` / `_try_type_f` for the rules). ``svc_w`` is the
    per-slot service time (straggler-scaled when the failure axis is on),
    ``ok`` the evacuation feasibility mask — evacuated workers keep their
    ring *positions* but are skipped as infeasible, like the oracle.

    Returns (found, oh_cand, rr_found, n_ring, rank_win, any_free,
    slot_idx)."""
    ready = live & (ws.ready_at < t)
    pend = live & ~ready
    widf = ws.wid.astype(jnp.float32)

    # ring ranks: wid-comparison matrix over the FPGA region only
    ringf = ready[:w_f]
    wf = ws.wid[:w_f]
    less = ringf[None, :] & ringf[:, None] & (wf[None, :] < wf[:, None])
    rank = jnp.sum(less.astype(jnp.int32), axis=1)           # (w_f,)
    feas_rr = (ringf & ok[:w_f]
               & (jnp.maximum(ws.avail[:w_f], t)
                  <= t + es.deadline - svc_w[:w_f]))

    # reduction 1: candidate availabilities (4 groups) + ring size
    dl = t + es.deadline
    g_fr = ready & is_f & ok & (ws.avail <= dl - svc_w)
    g_cr = ready & ~is_f & ok & (ws.avail <= dl - svc_w)
    g_fp = pend & is_f & ok & (ws.avail + svc_w <= dl)
    g_cp = pend & ~is_f & ok & (ws.avail + svc_w <= dl)
    nring_v = jnp.pad(jnp.where(ringf, (rank + 1).astype(jnp.float32), _NEG),
                      (0, idxW.shape[0] - w_f), constant_values=_NEG)
    r1 = jnp.max(jnp.stack([
        jnp.where(g_fr, ws.avail, _NEG), jnp.where(g_cr, ws.avail, _NEG),
        jnp.where(g_fp, ws.avail, _NEG), jnp.where(g_cp, ws.avail, _NEG),
        nring_v]), axis=-1)
    am_fr, am_cr, am_fp, am_cp, nring_f = r1[0], r1[1], r1[2], r1[3], r1[4]
    any_fr, any_cr = am_fr > _NEG, am_cr > _NEG
    n_ring = jnp.maximum(nring_f, 1.0).astype(jnp.int32)

    # reduction 2: wid tie-breaks, cyclic ring priority, first free slot
    s = rr_pos % n_ring
    key = jnp.where(rank < s, rank + w_f, rank)
    keyv = jnp.pad(jnp.where(feas_rr, -key.astype(jnp.float32), _NEG),
                   (0, idxW.shape[0] - w_f), constant_values=_NEG)
    free_c = ~ws.alive & ~is_f
    r2 = jnp.max(jnp.stack([
        jnp.where(g_fr & (ws.avail == am_fr), widf, _NEG),
        jnp.where(g_cr & (ws.avail == am_cr), widf, _NEG),
        jnp.where(g_fp & (ws.avail == am_fp), -widf, _NEG),
        jnp.where(g_cp & (ws.avail == am_cp), -widf, _NEG),
        keyv, jnp.where(free_c, -idxW, _NEG)]), axis=-1)
    kmin = -r2[4]
    rr_found = r2[4] > _NEG
    slot_idx = -r2[5]
    any_free = r2[5] > _NEG
    rank_win = kmin.astype(jnp.int32) % w_f

    # winner one-hots (elementwise; tie values from reduction 2)
    oh_f = jnp.where(any_fr, g_fr & (ws.avail == am_fr) & (widf == r2[0]),
                     g_fp & (ws.avail == am_fp) & (widf == -r2[2]))
    oh_c = jnp.where(any_cr, g_cr & (ws.avail == am_cr) & (widf == r2[1]),
                     g_cp & (ws.avail == am_cp) & (widf == -r2[3]))
    oh_rr = jnp.pad(feas_rr & (key.astype(jnp.float32) == kmin),
                    (0, idxW.shape[0] - w_f))

    # policy select: fold every registered dispatch policy's `combine`
    # rule under the traced code, so one compiled program serves them all
    # (spork efficient-first; index_packing busiest-first across types,
    # FPGA wins exact ties; round_robin ring then CPUs; plugins join via
    # repro.policies.register_dispatch).
    f_found = any_fr | (am_fp > _NEG)
    c_found = any_cr | (am_cp > _NEG)
    av_f = jnp.where(any_fr, am_fr, am_fp)
    av_c = jnp.where(any_cr, am_cr, am_cp)
    cand = Candidates(f_found=f_found, c_found=c_found, av_f=av_f,
                      av_c=av_c, oh_f=oh_f, oh_c=oh_c,
                      rr_found=rr_found, oh_rr=oh_rr)
    found, oh_cand = dispatch_select(code, cand)
    return found, oh_cand, rr_found, n_ring, rank_win, any_free, slot_idx


def _arrival_step(es: EventScalars, code, w_f: int, is_f, idxW,
                  c: EvCarry, t) -> EvCarry:
    """One request arrival: Alg. 3 dispatch under the traced policy code,
    CPU spin-up fallback, assignment + per-slot accounting.

    Candidate rules (EventSim._try_type): ready workers (ready_at < t —
    the oracle processes arrivals before same-time ready events) busiest
    feasible first with max-wid tie-break; pending workers most queued
    load first with min-wid tie-break. The round-robin ring is the
    wid-ascending list of ready FPGAs with a raw positional cursor that
    is *not* adjusted when removals shrink the ring, like the oracle's;
    the cyclic scan from cursor position s resolves without a mod by
    minimizing the key (rank < s)*w_f + rank, whose minimizer k also
    yields the new cursor (k % w_f + 1) % n_ring.

    This is the *pristine* path, compiled when the failure axis is off;
    the failure-aware twin is `_arrival_fail`."""
    ws = c.ws
    real = jnp.isfinite(t)
    svc_w = jnp.where(is_f, es.size / es.S, es.size)         # (W,)
    dtime = (jnp.maximum(ws.ready_at, ws.avail)
             + jnp.where(is_f, es.to_f, es.to_c))
    live = ws.alive & (dtime >= t)
    ok = jnp.ones(idxW.shape[0], bool)
    found, oh_cand, rr_found, n_ring, rank_win, any_free, slot_idx = \
        _find_candidates(es, code, w_f, is_f, idxW, ws, c.rr_pos, t,
                         svc_w, live, ok)
    rr_pos = jnp.where(real & (code == 2) & rr_found,
                       (rank_win + 1) % n_ring, c.rr_pos)

    # no feasible worker: spin up a CPU in the first free CPU slot
    spin = real & ~found & any_free
    over = (real & ~found & ~any_free).astype(jnp.int32)
    oh_spin = (idxW == slot_idx) & spin
    do = real & (found | spin)
    oh_do = jnp.where(found, oh_cand, oh_spin) & do

    # assignment (EventSim._assign), all elementwise
    dl = t + es.deadline
    avail_base = jnp.where(oh_spin, t + es.A_c_s, ws.avail)
    new_av = jnp.maximum(avail_base, t) + svc_w
    missed = oh_do & (new_av > dl + 1e-9)
    ws = ws._replace(
        wid=jnp.where(oh_spin, c.next_wid + 1, ws.wid),
        alive=ws.alive | oh_spin,
        alloc_t=jnp.where(oh_spin, t, ws.alloc_t),
        ready_at=jnp.where(oh_spin, t + es.A_c_s, ws.ready_at),
        avail=jnp.where(oh_do, new_av, ws.avail),
        busy=jnp.where(oh_do, jnp.where(oh_spin, 0.0, ws.busy) + svc_w,
                       ws.busy))
    return c._replace(
        ws=ws,
        serv_slot=c.serv_slot + oh_do.astype(jnp.float32) * svc_w,
        miss_slot=c.miss_slot + missed.astype(jnp.float32),
        next_wid=c.next_wid + spin.astype(jnp.int32), rr_pos=rr_pos,
        overflow=c.overflow + over)


def _arrival_fail(es: EventScalars, fstat: FailStatic, code, w_f: int,
                  is_f, idxW, c: EvCarry, t) -> EvCarry:
    """Failure-aware arrival: EventSim._on_arrival's deadline-aware
    failover loop, unrolled (``max_failover`` is static). Each round
    runs the full candidate search; a round is consumed by a stillborn
    burst spin-up or a mid-service crash (the request re-enters dispatch
    at the same timestamp with its *original* deadline); a surviving
    assignment ends the loop; exhaustion drops the request (counted as a
    deadline miss attributable to failures)."""
    real = jnp.isfinite(t)
    dl = t + es.deadline
    R = fstat.max_retries
    act = real
    crashed_any = jnp.zeros((), bool)
    for r in range(1 + fstat.max_failover):
        ws, fl = c.ws, c.fail
        svc_w = jnp.where(is_f, es.size / es.S, es.size) * ws.slow
        idle_d = (jnp.maximum(ws.ready_at, ws.avail)
                  + jnp.where(is_f, es.to_f, es.to_c))
        # crashed workers leave dispatch the instant the crash is drawn
        # (their settlement is future-dated; see _settle)
        live = ws.alive & (idle_d >= t) & (ws.crash_t == jnp.inf)
        ok = _evac_ok(es, t, ws.wid)
        found, oh_cand, rr_found, n_ring, rank_win, any_free, slot_idx = \
            _find_candidates(es, code, w_f, is_f, idxW, ws, c.rr_pos, t,
                             svc_w, live, ok)
        rr_pos = jnp.where(act & (code == 2) & rr_found,
                           (rank_win + 1) % n_ring, c.rr_pos)

        # burst CPU spin-up with bounded retries; stillborn allocations
        # consume the wid + the failover round but never join the table
        spin = act & ~found & any_free
        over = (act & ~found & ~any_free).astype(jnp.int32)
        oh_spin = (idxW == slot_idx) & spin
        new_wid = c.next_wid + 1
        nf_new = _spin_fails(es, new_wid, R)
        still = nf_new > R
        spin_ok = spin & ~still
        spin_still = spin & still
        oh_occ = oh_spin & spin_ok
        nf_f = nf_new.astype(jnp.float32)
        a_c_eff = es.A_c_s * (1.0 + nf_f) + es.f_backoff * nf_f
        slow_new = _slow_draw(es, new_wid)
        spin_i = spin.astype(jnp.int32)
        fl = fl._replace(
            failed_spins=fl.failed_spins + spin_i * nf_new,
            retries=fl.retries + spin_i * jnp.minimum(nf_new, R),
            wasted_j=fl.wasted_j
            + jnp.where(spin, nf_f * (es.A_c_s * es.B_c), 0.0),
            extra_cost=fl.extra_cost + jnp.where(
                spin_still,
                ((R + 1) * es.A_c_s + R * es.f_backoff) * es.C_c, 0.0),
            cpu_spins=fl.cpu_spins + spin_ok.astype(jnp.int32))

        # crash draw per assignment, keyed (wid, n_assigned); the worker
        # dies half a service in, burning half the service as busy time
        # and interval load (EventSim._crash)
        do = act & (found | spin_ok)
        oh_do = jnp.where(found, oh_cand, oh_spin) & do
        wid_eff = jnp.where(oh_spin, new_wid, ws.wid)
        nass_eff = jnp.where(oh_spin, 0, ws.n_assign)
        crash_u = failure_u01(es.f_seed, wid_eff, nass_eff, DRAW_CRASH,
                              xp=jnp)
        crashed = oh_do & (crash_u < es.f_crash_p)
        svc_used = jnp.where(oh_spin, es.size * slow_new, svc_w)
        start = jnp.maximum(jnp.where(oh_spin, t + a_c_eff, ws.avail), t)
        new_av = start + svc_used
        t_crash = start + svc_used * 0.5
        served = oh_do & ~crashed
        missed = served & (new_av > dl + 1e-9)
        ws = ws._replace(
            wid=jnp.where(oh_occ, new_wid, ws.wid),
            alive=ws.alive | oh_occ,
            alloc_t=jnp.where(oh_occ, t, ws.alloc_t),
            ready_at=jnp.where(oh_occ, t + a_c_eff, ws.ready_at),
            avail=jnp.where(served, new_av,
                            jnp.where(oh_occ, t + a_c_eff, ws.avail)),
            busy=jnp.where(oh_do,
                           jnp.where(oh_occ, 0.0, ws.busy)
                           + jnp.where(crashed, svc_used * 0.5, svc_used),
                           ws.busy),
            n_assign=jnp.where(oh_do,
                               jnp.where(oh_occ, 0, ws.n_assign) + 1,
                               ws.n_assign),
            crash_t=jnp.where(crashed, t_crash,
                              jnp.where(oh_occ, jnp.inf, ws.crash_t)),
            slow=jnp.where(oh_occ, slow_new, ws.slow),
            nfail=jnp.where(oh_occ, nf_new, ws.nfail))

        served_s = jnp.any(served)
        crash_s = jnp.any(crashed)
        win_f = jnp.any(served & is_f)
        fl = fl._replace(
            crashes=fl.crashes + crash_s.astype(jnp.int32),
            recovered=fl.recovered
            + (served_s & crashed_any).astype(jnp.int32),
            work_f=fl.work_f + jnp.where(win_f, es.size, 0.0),
            work_c=fl.work_c + jnp.where(served_s & ~win_f, es.size, 0.0))
        if r > 0:
            fl = fl._replace(fail_misses=fl.fail_misses
                             + jnp.any(missed).astype(jnp.int32))
        c = c._replace(
            ws=ws,
            serv_slot=c.serv_slot + jnp.where(
                oh_do, jnp.where(crashed, svc_used * 0.5, svc_used), 0.0),
            miss_slot=c.miss_slot + missed.astype(jnp.float32),
            next_wid=c.next_wid + spin_i, rr_pos=rr_pos,
            overflow=c.overflow + over, fail=fl)
        crashed_any = crashed_any | crash_s
        act = act & (spin_still | crash_s)

    dropped = act.astype(jnp.int32)      # failover rounds exhausted
    fl = c.fail
    return c._replace(fail=fl._replace(
        dropped=fl.dropped + dropped,
        fail_misses=fl.fail_misses + dropped))


def _tick_step(es: EventScalars, fstat: FailStatic, w_f: int, is_f,
               c: EvCarry, ts: TickState, t, active):
    """Per-interval Spork allocator (Algs. 1-2, EventSim._on_tick):
    settle deallocs preceding the tick, observe + predict through the
    shared `allocator_tick_jnp`, then spin up the shortfall into free
    FPGA slots (monotone wids, allocation levels counted like the
    oracle). Runs gated after every entry of the flat stream; inactive
    entries leave all state bit-unchanged.

    With the failure axis on, the allocator sees the *shrunken* live
    fleet — crashed and evacuated FPGAs are excluded from ``n_curr``
    (EventSim._live_fpgas / ft.elastic.surviving) — and each of the m
    provisioning attempts can fail: a stillborn attempt consumes its wid
    and allocation level but leaves the slot free."""
    c, ts = _settle(es, is_f, c, ts, t, active)
    ws = c.ws
    vis = ws.alive & is_f
    if fstat.enabled:
        vis = vis & (ws.crash_t == jnp.inf) & _evac_ok(es, t, ws.wid)
    n_curr = jnp.sum(vis.astype(jnp.int32))
    F_tot = jnp.sum(c.serv_slot[:w_f])
    C_tot = jnp.sum(c.serv_slot[w_f:])
    lam = (F_tot - ts.F_prev) + (C_tot - ts.C_prev) / es.S
    do_alloc = active & es.allocate
    H, n_lag, target = allocator_tick_jnp(
        ts.H, ts.life_sum, ts.life_cnt, ts.n_lag, lam, n_curr, es.coeffs,
        es.T_s, es.tb, gate=do_alloc)
    m = jnp.where(do_alloc,
                  jnp.clip(target - n_curr, 0,
                           jnp.maximum(es.max_fpgas - n_curr, 0)), 0)
    free_f = ~ws.alive[:w_f]
    fr = jnp.cumsum(free_f.astype(jnp.int32)) - 1
    take = jnp.pad(free_f & (fr < m), (0, is_f.shape[0] - w_f))
    frW = jnp.pad(fr, (0, is_f.shape[0] - w_f))
    n_take = jnp.sum(take.astype(jnp.int32))
    if not fstat.enabled:
        ws = ws._replace(
            wid=jnp.where(take, c.next_wid + 1 + frW, ws.wid),
            alive=ws.alive | take,
            alloc_t=jnp.where(take, t, ws.alloc_t),
            ready_at=jnp.where(take, t + es.A_f_s, ws.ready_at),
            avail=jnp.where(take, t + es.A_f_s, ws.avail),
            busy=jnp.where(take, 0.0, ws.busy),
            level=jnp.where(take, n_curr + frW, ws.level))
        n_spun = n_take
    else:
        R = fstat.max_retries
        new_wids = c.next_wid + 1 + frW
        nf = _spin_fails(es, new_wids, R)
        still = nf > R
        succeed = take & ~still
        nf_f = nf.astype(jnp.float32)
        delay = es.A_f_s * (1.0 + nf_f) + es.f_backoff * nf_f
        takef = take.astype(jnp.float32)
        takei = take.astype(jnp.int32)
        fl = c.fail
        c = c._replace(fail=fl._replace(
            failed_spins=fl.failed_spins + jnp.sum(takei * nf),
            retries=fl.retries + jnp.sum(takei * jnp.minimum(nf, R)),
            wasted_j=fl.wasted_j
            + jnp.sum(takef * nf_f) * (es.A_f_s * es.B_f),
            extra_cost=fl.extra_cost
            + jnp.sum((take & still).astype(jnp.float32))
            * (((R + 1) * es.A_f_s + R * es.f_backoff) * es.C_f)))
        ws = ws._replace(
            wid=jnp.where(take, new_wids, ws.wid),
            alive=ws.alive | succeed,
            alloc_t=jnp.where(succeed, t, ws.alloc_t),
            ready_at=jnp.where(succeed, t + delay, ws.ready_at),
            avail=jnp.where(succeed, t + delay, ws.avail),
            busy=jnp.where(succeed, 0.0, ws.busy),
            level=jnp.where(take, n_curr + frW, ws.level),
            n_assign=jnp.where(succeed, 0, ws.n_assign),
            crash_t=jnp.where(succeed, jnp.inf, ws.crash_t),
            slow=jnp.where(succeed, _slow_draw(es, new_wids), ws.slow),
            nfail=jnp.where(succeed, nf, ws.nfail))
        n_spun = jnp.sum(succeed.astype(jnp.int32))
    c = c._replace(ws=ws, next_wid=c.next_wid + n_take,
                   overflow=c.overflow + jnp.where(do_alloc, m - n_take, 0))
    ts = ts._replace(
        H=H, n_lag=n_lag,
        F_prev=jnp.where(active, F_tot, ts.F_prev),
        C_prev=jnp.where(active, C_tot, ts.C_prev),
        spins=ts.spins + n_spun.astype(jnp.float32))
    return c, ts

def _simulate_cell(n_max: int, w_f: int, w_c: int, fstat: FailStatic,
                   es: EventScalars, arrival, extra, tick_reset, rows,
                   tick_t, is_tick) -> tuple:
    """One DES cell program over the flat entry stream: each entry runs
    one (padded) arrival block through the inner scan, then one gated
    tick; the final drain settles every worker and the totals become an
    `Accum`. ``arrival(is_f, idxW, (c, extra), xs)`` steps one arrival,
    ``xs`` holding one slot of each array of ``rows`` (``E x BLOCK``);
    ``extra`` is state carried beside the `EvCarry` (the fleet layer's
    tenant axis), passed through ``tick_reset(extra, tick)`` after every
    entry's tick. Returns ``(Accum, FailAcc, overflow, extra)``.
    ``fstat`` selects the compiled program: disabled cells run the
    pristine pre-failure path (bit-identical to the engine without the
    axis)."""
    W = w_f + w_c
    is_f = jnp.arange(W) < w_f
    idxW = jnp.arange(W, dtype=jnp.float32)

    def zf(*s):
        return jnp.zeros(s, jnp.float32)

    ws = WorkerTable(wid=jnp.zeros((W,), jnp.int32),
                     alive=jnp.zeros((W,), bool), alloc_t=zf(W),
                     ready_at=zf(W), avail=zf(W), busy=zf(W),
                     level=jnp.zeros((W,), jnp.int32),
                     n_assign=jnp.zeros((W,), jnp.int32),
                     crash_t=jnp.full((W,), jnp.inf, jnp.float32),
                     slow=jnp.ones((W,), jnp.float32),
                     nfail=jnp.zeros((W,), jnp.int32))
    c0 = EvCarry(ws, zf(W), zf(W), jnp.int32(0), jnp.int32(0), jnp.int32(0),
                 _fail_zero())
    ts0 = TickState(H=zf(n_max, n_max), n_lag=jnp.zeros((2,), jnp.int32),
                    life_sum=zf(n_max), life_cnt=zf(n_max), F_prev=zf(),
                    C_prev=zf(), spins=zf(), energy=zf(6))

    def inner(carry, xs):
        return arrival(is_f, idxW, carry, xs), None

    def entry(state, xs):
        c, ts, ex = state
        row, tt, tk = xs
        (c, ex), _ = jax.lax.scan(inner, (c, ex), row)
        c, ts = _tick_step(es, fstat, w_f, is_f, c, ts, tt, tk)
        return (c, ts, tick_reset(ex, tk)), None

    (c, ts, ex), _ = jax.lax.scan(entry, (c0, ts0, extra),
                                  (rows, tick_t, is_tick))
    # final drain: every remaining worker idles out at its own timeout
    c, ts = _settle(es, is_f, c, ts, jnp.inf, True)
    fl = c.fail
    if fstat.enabled:
        # stragglers / half-served crashes break the serv_slot -> work
        # and next_wid -> cpu_spinups derivations; the failure path
        # counts both explicitly
        work_f, work_c = fl.work_f, fl.work_c
        missed = jnp.sum(c.miss_slot) + fl.dropped.astype(jnp.float32)
        cpu_spins = fl.cpu_spins.astype(jnp.float32)
    else:
        work_f = jnp.sum(c.serv_slot[:w_f]) * es.S
        work_c = jnp.sum(c.serv_slot[w_f:])
        missed = jnp.sum(c.miss_slot)
        cpu_spins = c.next_wid.astype(jnp.float32) - ts.spins
    acc = Accum(
        fpga_busy_j=ts.energy[0], fpga_idle_j=ts.energy[1],
        cpu_busy_j=ts.energy[2], cpu_idle_j=ts.energy[3],
        spin_j=ts.energy[4], cost=ts.energy[5],
        work_f=work_f, work_c=work_c,
        missed_requests=missed, fpga_spinups=ts.spins,
        cpu_spinups=cpu_spins)
    return acc, fl, c.overflow, ex


def _simulate_one(n_max: int, w_f: int, w_c: int, fstat: FailStatic,
                  es: EventScalars, code, times, tick_t, is_tick) -> tuple:
    """One single-tenant cell: `_simulate_cell` stepping each arrival
    with `_arrival_step` (or `_arrival_fail`), no extra state."""
    def arrival(is_f, idxW, carry, t):
        c, ex = carry
        if fstat.enabled:
            return _arrival_fail(es, fstat, code, w_f, is_f, idxW, c, t), ex
        return _arrival_step(es, code, w_f, is_f, idxW, c, t), ex

    acc, fl, over, _ = _simulate_cell(n_max, w_f, w_c, fstat, es, arrival,
                                      (), lambda ex, tk: ex, times, tick_t,
                                      is_tick)
    return acc, fl, over


def _simulate_cells_core(n_max: int, w_fpga: int, w_cpu: int,
                         fstat: FailStatic, es: EventScalars, codes,
                         times, tick_t, is_tick) -> tuple:
    """Unjitted cell-batched core (vmap over the cell axis). Exposed so
    `repro.sim.exec.MeshBackend` can `shard_map` it over a device mesh;
    `_simulate_cells` is its jitted single-device twin."""
    return jax.vmap(functools.partial(
        _simulate_one, n_max, w_fpga, w_cpu, fstat))(
        es, codes, times, tick_t, is_tick)


_simulate_cells = functools.partial(
    jax.jit, static_argnames=("n_max", "w_fpga", "w_cpu", "fstat"))(
    _simulate_cells_core)


def _scalars(fleet: FleetParams, energy_weight: float, size_s: float,
             deadline_s: float | None, failures: FailureSpec | None) -> tuple:
    """A cell's float `EventScalars` fields, in order, from the DES axes
    they read (``deadline_s`` None: 10 x the request size)."""
    tb, coeffs = objective_setup(fleet, energy_weight)
    deadline = 10.0 * size_s if deadline_s is None else deadline_s
    f = failures.normalized() if failures is not None else None
    ff = f.floats() if f is not None else (0.0,) * 8
    return (size_s, deadline, fleet.S, fleet.T_s, tb, coeffs.co_min,
            coeffs.co_over, coeffs.co_under, coeffs.amort_unit,
            fleet.fpga.spin_up_s, fleet.cpu.spin_up_s,
            fleet.fpga_idle_timeout_s, fleet.cpu_idle_timeout_s,
            fleet.fpga.busy_w, fleet.fpga.idle_w, fleet.cpu.busy_w,
            fleet.cpu.idle_w, fleet.fpga.cost_per_s, fleet.cpu.cost_per_s,
            fleet.fpga.spin_up_energy_j + fleet.fpga.spin_down_energy_j,
            fleet.cpu.spin_up_energy_j + fleet.cpu.spin_down_energy_j,
            fleet.fpga.spin_down_s, fleet.cpu.spin_down_s, *ff)


@dataclass(frozen=True)
class EventCell:
    """One DES grid cell: one app trace under one dispatch policy.

    Like `repro.sim.sweep.SweepCell`, demand is either explicit
    (``arrival_times`` + ``size_s``) or named: ``scenario=spec, seed=k``
    with ``arrival_times=None`` — `sweep.sweep_events` synthesizes the
    arrival stream from the `repro.workloads` scenario library before
    dispatch."""

    dispatcher: str
    arrival_times: np.ndarray | None = None
    size_s: float | None = None
    fleet: FleetParams = DEFAULT_FLEET
    energy_weight: float = 1.0
    horizon_s: float | None = None
    deadline_s: float | None = None
    allocate_fpgas: bool = True
    tag: Any = None
    scenario: Any = None          # repro.workloads.ScenarioSpec | None
    seed: int = 0                 # scenario realization seed
    failures: FailureSpec | None = None   # fault model (static sweep axis)

    def __post_init__(self):
        """Fail-fast construction-time validation: malformed cells raise
        a clear ValueError here instead of an opaque XLA shape error deep
        inside `repro.sim.plan.plan_events`."""
        if self.arrival_times is not None:
            a = np.asarray(self.arrival_times, np.float64)
            if a.ndim != 1:
                raise ValueError(
                    f"EventCell.arrival_times must be a 1-D time stream, "
                    f"got shape {a.shape}")
            if a.size and (not np.all(np.isfinite(a)) or np.any(a < 0)):
                raise ValueError(
                    "EventCell.arrival_times must be non-negative finite "
                    "timestamps")
            if a.size > 1 and np.any(np.diff(a) < 0):
                raise ValueError(
                    "EventCell.arrival_times must be sorted ascending "
                    "(the DES consumes a time-ordered stream)")
        if self.size_s is not None and not (
                np.isfinite(self.size_s) and self.size_s > 0):
            raise ValueError(
                f"EventCell.size_s must be a positive finite service "
                f"time, got {self.size_s!r}")
        if self.deadline_s is not None and not (
                np.isfinite(self.deadline_s) and self.deadline_s > 0):
            raise ValueError(
                f"EventCell.deadline_s must be > 0, got {self.deadline_s!r}")
        if self.horizon_s is not None and not (
                np.isfinite(self.horizon_s) and self.horizon_s > 0):
            raise ValueError(
                f"EventCell.horizon_s must be > 0, got {self.horizon_s!r}")
        if not np.isfinite(self.energy_weight):
            raise ValueError(
                f"EventCell.energy_weight must be finite, got "
                f"{self.energy_weight!r}")
        if np.ndim(self.seed) != 0:
            raise ValueError(
                f"EventCell.seed must be a scalar (one seed per cell — "
                f"expand seed batches into cells), got shape "
                f"{np.shape(self.seed)}")


# Cost: O(arrivals + intervals) per cell: one bucket index per arrival,
# one `np.searchsorted` for the K + 2 bucket bounds, then slices of the
# original array. The stream must be sorted ascending (`EventCell`
# refuses an unsorted one, `resolve_fleet_cell` stable-sorts its merged
# stream), so each bucket is one contiguous run. `plan_events` runs it
# once per cell; its share of planning is the ``repro.plan.entries`` span.
def _entries(arr: np.ndarray, interval_s: float, horizon: float,
             payload: np.ndarray | None = None) -> list[tuple]:
    """Flat entry stream for one cell: fixed-width arrival blocks with
    tick markers riding on the last block of each interval. Bucket k
    holds arrivals in ((k-1)*T_s, k*T_s] so every arrival precedes its
    tick (the oracle pops arrivals before same-time events), and the
    final bucket holds the post-last-tick tail.

    With ``payload`` (a per-arrival array aligned with ``arr``, e.g. the
    fleet layer's tenant indices) entries are ``(row, pay_row, tick)``
    3-tuples, the payload sliced identically to the times; otherwise the
    original ``(row, tick)`` 2-tuples. Raises ValueError if ``arr`` is
    not sorted ascending."""
    arr = np.asarray(arr)
    K = int(np.ceil(horizon / interval_s))
    idx = np.minimum(np.ceil(np.asarray(arr, np.float64) / interval_s)
                     .astype(np.int64), K)
    idx = np.maximum(idx, 0)
    if np.any(idx[1:] < idx[:-1]):
        raise ValueError("_entries needs an arrival stream sorted ascending")
    bounds = np.searchsorted(idx, np.arange(K + 2)).tolist()
    pay = None if payload is None else np.asarray(payload)
    out: list[tuple] = []
    for k in range(K + 1):
        lo, hi = bounds[k], bounds[k + 1]
        tick = k * interval_s if k < K else None
        starts = range(lo, hi, BLOCK) or range(lo, lo + 1)
        last = starts[-1]
        for j in starts:
            t = tick if j == last else None
            e = min(j + BLOCK, hi)
            out.append((arr[j:e], t) if pay is None
                       else (arr[j:e], pay[j:e], t))
    return out


def _pad_pow2(n: int, lo: int = 4, hi: int | None = None) -> int:
    p = max(lo, 1 << int(math.ceil(math.log2(max(n, 1)))))
    return min(p, hi) if hi else p


def simulate_events_batch(cells: Iterable[EventCell], n_max: int = 512,
                          w_fpga: int = 32, w_cpu: int = 64,
                          backend=None) -> list[RunTotals]:
    """Run every DES cell, one dispatch per plan chunk (cells packed by
    entry-stream length); cell order is preserved. Totals carry
    ``breakdown['slot_overflow']`` (0 unless a table region or
    ``max_fpgas`` was too small for the trace).

    A thin plan+execute wrapper: the group/pad/scatter machinery lives
    in `repro.sim.plan.plan_events` and execution in `repro.sim.exec`
    (``backend=`` selects it; None reads ``BENCH_SWEEP_BACKEND``).
    Cells must carry explicit demand — scenario-bearing cells go
    through `repro.sim.sweep.sweep_events`, which resolves them first.
    Returns a bare ``list[RunTotals]``; use `sweep_events` for the
    metadata-carrying `repro.sim.plan.EventSweepResult`."""
    from repro.sim.exec import execute
    from repro.sim.plan import plan_events
    plan = plan_events(cells, n_max=n_max, w_fpga=w_fpga, w_cpu=w_cpu,
                       resolve=False)
    return execute(plan, backend).totals()


def simulate_events_batched(arrival_times: np.ndarray, size_s: float,
                            fleet: FleetParams, dispatcher: str = "spork",
                            energy_weight: float = 1.0,
                            horizon_s: float | None = None,
                            deadline_s: float | None = None,
                            allocate_fpgas: bool = True, n_max: int = 512,
                            w_fpga: int = 32, w_cpu: int = 64,
                            failures: FailureSpec | None = None) -> RunTotals:
    """Drop-in twin of `events.simulate_events` on the batched engine."""
    cell = EventCell(dispatcher, np.asarray(arrival_times), size_s, fleet,
                     energy_weight=energy_weight, horizon_s=horizon_s,
                     deadline_s=deadline_s, allocate_fpgas=allocate_fpgas,
                     failures=failures)
    return simulate_events_batch([cell], n_max=n_max, w_fpga=w_fpga,
                                 w_cpu=w_cpu)[0]
