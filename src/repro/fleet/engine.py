"""Batched multi-tenant fleet engine: tenant axis in the DES scan state.

The batched twin of `repro.fleet.oracle.FleetSim`, built ON TOP of the
single-tenant batched DES (`repro.sim.events_batched`) rather than
beside it: each arrival entry carries a tenant index, and the inner scan

  1. gathers the tenant's admission state, runs the shared float32
     `repro.policies.admission.admission_decide` kernel under the traced
     admission code (all admission policies share one compiled program,
     exactly like dispatch codes), and scatters the state back;
  2. calls the UNCHANGED `_arrival_step` / `_arrival_fail` with the
     tenant's size and SLO deadline swapped into the traced
     `EventScalars` (``es._replace`` — `EventScalars` is a pytree of
     traced scalars, so this is free and touches no engine code), with
     shed/padded arrivals neutralized to ``t = +inf`` (an exact no-op in
     both arrival kernels);
  3. tallies per-tenant counters (`FleetTenantAcc`) from the deltas the
     arrival kernel applied to the shared accumulators — the same
     delta-observation trick the serial oracle uses, so the two engines
     cannot disagree on attribution rules.

Interval ticks run the unchanged `_tick_step` on *aggregate* interval
load (the allocator never reads size/deadline) and reset the
`interval_quota` admission counters. The cell axis is vmapped exactly
like the single-tenant engine; `repro.sim.plan.plan_fleet` builds the
dispatches and both `repro.sim.exec` backends run them (`MeshBackend`
shard_maps `_simulate_fleet_cells_core` over the cell mesh).

Equivalence contract: on dyadic-quantized tenant streams the engine
matches `FleetSim` EXACTLY on offered/admitted/shed/missed counters and
~1e-5 on energies/work (tests/test_fleet.py), extending the
single-tenant contract in docs/architecture.md.
"""

from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp

from repro.policies import admission_decide
from repro.sim.events_batched import (EvCarry, EventScalars, TickState,
                                      WorkerTable, _arrival_fail,
                                      _arrival_step, _fail_zero, _settle,
                                      _tick_step)
from repro.sim.ratesim import Accum


class FleetTenantAcc(NamedTuple):
    """Per-tenant accumulators ((N,) leaves, vmapped over cells)."""

    offered: jnp.ndarray     # i32 arrivals seen by the router
    admitted: jnp.ndarray    # i32 admitted into dispatch
    shed: jnp.ndarray        # i32 rejected by admission
    missed: jnp.ndarray      # i32 SLO deadline misses (incl. drops)
    work_f: jnp.ndarray      # f32 cpu-seconds served on FPGAs
    work_c: jnp.ndarray      # f32 cpu-seconds served on CPUs


def _fleet_arrival(es: EventScalars, fstat, code, acode, w_f: int, is_f,
                   idxW, ta_size, ta_dl, adm_rate, adm_burst, adm_quota,
                   carry, xs):
    """One tenant-tagged arrival: admission -> (gated) dispatch -> tally."""
    c, tok, last, cnt, fa = carry
    t, tid = xs
    real = jnp.isfinite(t)
    # padded entries (t = +inf) must not poison the float32 admission
    # kernel (inf * 0 = NaN); their state writes are discarded below
    t_k = jnp.where(real, t, jnp.float32(0.0))
    admit, tok_n, last_n, cnt_n = admission_decide(
        acode, t_k, tok[tid], last[tid], cnt[tid], adm_rate[tid],
        adm_burst[tid], adm_quota[tid], xp=jnp)
    admit = admit & real
    tok = tok.at[tid].set(jnp.where(real, tok_n, tok[tid]))
    last = last.at[tid].set(jnp.where(real, last_n, last[tid]))
    cnt = cnt.at[tid].set(jnp.where(real, cnt_n, cnt[tid]))
    fa = fa._replace(
        offered=fa.offered.at[tid].add(real.astype(jnp.int32)),
        admitted=fa.admitted.at[tid].add(admit.astype(jnp.int32)),
        shed=fa.shed.at[tid].add((real & ~admit).astype(jnp.int32)))

    # the tenant's size/SLO ride in via the traced scalars; shed and
    # padded arrivals become t = +inf — an exact no-op in both kernels
    es_a = es._replace(size=ta_size[tid], deadline=ta_dl[tid])
    t_eff = jnp.where(admit, t, jnp.inf)
    if fstat.enabled:
        c2 = _arrival_fail(es_a, fstat, code, w_f, is_f, idxW, c, t_eff)
        served_f = c2.fail.work_f > c.fail.work_f
        served_c = c2.fail.work_c > c.fail.work_c
        missed = (jnp.any(c2.miss_slot != c.miss_slot)
                  | (c2.fail.dropped > c.fail.dropped))
    else:
        c2 = _arrival_step(es_a, code, w_f, is_f, idxW, c, t_eff)
        served_f = jnp.any(c2.serv_slot[:w_f] != c.serv_slot[:w_f])
        served_c = jnp.any(c2.serv_slot[w_f:] != c.serv_slot[w_f:])
        missed = jnp.any(c2.miss_slot != c.miss_slot)
    fa = fa._replace(
        missed=fa.missed.at[tid].add(missed.astype(jnp.int32)),
        work_f=fa.work_f.at[tid].add(
            jnp.where(served_f, ta_size[tid], 0.0)),
        work_c=fa.work_c.at[tid].add(
            jnp.where(served_c, ta_size[tid], 0.0)))
    return (c2, tok, last, cnt, fa), None


def _simulate_fleet_one(n_max: int, w_f: int, w_c: int, fstat, es, code,
                        acode, times, tids, tick_t, is_tick, ta_size,
                        ta_dl, adm_rate, adm_burst, adm_quota) -> tuple:
    """One fleet cell over the flat tenant-tagged entry stream. Mirrors
    `repro.sim.events_batched._simulate_one` (same worker-table init,
    same entry scan, same final drain + `Accum` derivation) with the
    admission state + `FleetTenantAcc` threaded alongside; interval
    quota counters reset on tick entries."""
    W = w_f + w_c
    is_f = jnp.arange(W) < w_f
    idxW = jnp.arange(W, dtype=jnp.float32)
    n_ten = ta_size.shape[0]

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
    zi = jnp.zeros((n_ten,), jnp.int32)
    fa0 = FleetTenantAcc(zi, zi, zi, zi, zf(n_ten), zf(n_ten))
    tok0, last0, cnt0 = adm_burst, zf(n_ten), zi

    step = functools.partial(_fleet_arrival, es, fstat, code, acode, w_f,
                             is_f, idxW, ta_size, ta_dl, adm_rate,
                             adm_burst, adm_quota)

    def entry(state, xs):
        c, ts, tok, last, cnt, fa = state
        row_t, row_id, tt, tk = xs
        (c, tok, last, cnt, fa), _ = jax.lax.scan(
            step, (c, tok, last, cnt, fa), (row_t, row_id))
        c, ts = _tick_step(es, fstat, w_f, is_f, c, ts, tt, tk)
        cnt = jnp.where(tk, jnp.zeros_like(cnt), cnt)
        return (c, ts, tok, last, cnt, fa), None

    (c, ts, _, _, _, fa), _ = jax.lax.scan(
        entry, (c0, ts0, tok0, last0, cnt0, fa0),
        (times, tids, tick_t, is_tick))
    c, ts = _settle(es, is_f, c, ts, jnp.inf, True)
    fl = c.fail
    if fstat.enabled:
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
    return acc, fl, c.overflow, fa


def _simulate_fleet_cells_core(n_max: int, w_fpga: int, w_cpu: int,
                               fstat, es, codes, acodes, times, tids,
                               tick_t, is_tick, ta_size, ta_dl, adm_rate,
                               adm_burst, adm_quota) -> tuple:
    """Unjitted cell-batched core (vmap over the cell axis), exposed so
    `repro.sim.exec.MeshBackend` can `shard_map` it over a device mesh;
    `_simulate_fleet_cells` is its jitted single-device twin."""
    return jax.vmap(functools.partial(
        _simulate_fleet_one, n_max, w_fpga, w_cpu, fstat))(
        es, codes, acodes, times, tids, tick_t, is_tick, ta_size, ta_dl,
        adm_rate, adm_burst, adm_quota)


_simulate_fleet_cells = functools.partial(
    jax.jit, static_argnames=("n_max", "w_fpga", "w_cpu", "fstat"))(
    _simulate_fleet_cells_core)
