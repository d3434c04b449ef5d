"""Sweep planning: cell lists -> explicit, testable dispatch plans.

The paper's headline results are parameter-space sweeps (spin-up x
burstiness x policy x seed x fleet; Figs. 5-7, Tables 8-9), and every
sweep entry point used to hand-roll the same machinery: resolve named
scenarios into demand, group cells by their static compile axes, pad
each group chunk to a fixed shape vocabulary, dispatch, and scatter the
results back into cell order. This module makes that machinery ONE
explicit data structure:

  * `plan_sweep(cells)` / `plan_events(cells)` turn any cell list
    (`SweepCell` or `EventCell`) into a `SweepPlan`: scenario
    resolution, group keys, chunk shapes, padding and result scatter
    indices, all computed host-side with NO device work.
  * A `SweepPlan` is a list of `ChunkDispatch`es. Each names the static
    arguments of one compiled program plus the padded host arrays and
    the cell indices its rows scatter back to. Plans are inspectable
    and property-tested (tests/test_plan.py): scatter indices are a
    permutation covering every cell, pads only repeat row 0, and chunk
    shapes come from the fixed vocabulary ({CHUNK, CHUNK_BIG} for rate
    plans, powers of two up to `EV_CHUNK_MAX` for event plans).
  * Execution is a separate, pluggable layer: `repro.sim.exec` runs a
    plan on the current single-device vmapped path (`LocalBackend`,
    bit-identical default) or sharded over a device mesh
    (`MeshBackend`). `sweep`, `sweep_events` and
    `tune_fpga_dynamic_cells` are thin plan+execute wrappers.

Invariants (enforced by tests/test_plan.py):

  * every plan's `cell_idx` lists concatenate to a permutation of
    ``range(len(cells))`` — each cell is dispatched exactly once;
  * padding repeats row 0 of each chunk (padded rows are discarded by
    the scatter, so their values only need to be *valid*, and row 0 is
    always a real cell);
  * rate chunks are exactly CHUNK or CHUNK_BIG; event chunks are powers
    of two in [4, EV_CHUNK_MAX]. Fixed shapes mean each group key
    compiles at most two XLA programs, reused across suites and (via
    the persistent compilation cache) across runs.
"""

from __future__ import annotations

import functools
import itertools
import math
from dataclasses import dataclass, field, replace
from typing import Any, Iterable, Sequence

import numpy as np
from jax.profiler import TraceAnnotation

from repro.core.metrics import Report, RunTotals, report
from repro.core.workers import FleetParams
from repro.ft.failures import fail_static
from repro.policies import get_dispatch_policy, get_rate_policy
from repro.sim.events_batched import (BLOCK, EV_CHUNK_MAX, _entries,
                                      _pad_pow2, _scalars)
from repro.sim.ratesim import (Accum, FleetScalars, accum_to_totals,
                               static_level_for)

# Cells per dispatch (rate plans). Every chunk is padded to one of
# exactly two shapes (small grids -> CHUNK, expanded grids like headroom
# tuning -> rounds of CHUNK_BIG) because each distinct compiled shape
# costs ~0.1-0.3s of compile/loading even when the persistent
# compilation cache (benchmarks/common.py) hits — shape reuse across
# suites is worth far more than tight padding: a padded-out simulator
# cell costs microseconds.
CHUNK = 32
CHUNK_BIG = 256

_N_MAX_CAP = 512

# Policies whose *dynamics* are independent of the scheduling interval
# and FPGA spin-up latency declare `latency_free = True` on their class
# (cpu_dynamic never allocates FPGAs; fpga_static provisions once,
# before the trace starts, and charges spin-up through the traced
# `FleetScalars.A_f_s`). Their cells are regrouped under one canonical
# static key so every spin-up value shares a compiled program.
_CANON_INTERVAL = 10

# Per-process plan number, kept in `SweepPlan.meta["plan_id"]` and on the
# `repro.plan` / `repro.exec` spans, so a trace ties a grid's planning to
# its execution even when the caller plans and executes separately.
_PLAN_IDS = itertools.count(1)


@functools.lru_cache(maxsize=256)
def _fleet_scalars_np(fleet: FleetParams) -> FleetScalars:
    """FleetScalars leaf values as plain floats. Derived from
    `FleetScalars.from_fleet` so the fleet-to-scalars mapping has a single
    source of truth; cached per fleet (hashable frozen dataclass) so
    sweeps don't pay device round-trips per cell."""
    return FleetScalars(*(float(leaf)
                          for leaf in FleetScalars.from_fleet(fleet)))


def resolve_scenarios(cells: Sequence) -> list:
    """Materialize demand for scenario-bearing cells (SweepCell or
    EventCell): cells whose ``counts`` / ``arrival_times`` is None get it
    synthesized from their ``scenario`` spec — ONE batched device
    dispatch per distinct spec (`repro.workloads.scenarios.realize`,
    shared across seeds and cached). Event arrival streams additionally
    hit the module-level per-(spec, seed) cache
    (`repro.workloads.scenarios.scenario_arrivals`), so repeated
    resolutions of the same cells across planner calls never recompute
    them. Cells with explicit demand pass through untouched; cell order
    is preserved."""
    out = list(cells)
    is_event = [hasattr(c, "arrival_times") for c in out]
    pending: dict[Any, list[int]] = {}
    for i, c in enumerate(out):
        demand = c.arrival_times if is_event[i] else c.counts
        if demand is not None:
            continue
        if c.scenario is None:
            raise ValueError(
                f"{type(c).__name__} needs explicit demand or a scenario")
        pending.setdefault(c.scenario, []).append(i)
    if not pending:
        return out
    from repro.workloads.scenarios import scenario_arrivals, scenario_traces
    for spec, idxs in pending.items():
        seeds = sorted({out[i].seed for i in idxs})
        by_seed = dict(zip(seeds, scenario_traces(spec, seeds)))
        for i in idxs:
            c, tr = out[i], by_seed[out[i].seed]
            size = tr.request_size_s if c.size_s is None else c.size_s
            # chaos scenarios carry a fault model; cells inherit it
            # unless they pin their own
            fail = (c.failures if c.failures is not None
                    else getattr(spec, "failures", None))
            if is_event[i]:
                out[i] = replace(c,
                                 arrival_times=scenario_arrivals(
                                     spec, c.seed, _trace=tr),
                                 size_s=size,
                                 horizon_s=(float(spec.horizon_s)
                                            if c.horizon_s is None
                                            else c.horizon_s),
                                 failures=fail)
            else:
                out[i] = replace(c, counts=tr.counts, size_s=size,
                                 failures=fail)
    return out


def _pad(arr: np.ndarray, n: int) -> np.ndarray:
    """Pad the leading axis to n by repeating row 0 (results discarded)."""
    if arr.shape[0] == n:
        return arr
    reps = np.repeat(arr[:1], n - arr.shape[0], axis=0)
    return np.concatenate([arr, reps], axis=0)


@dataclass(frozen=True)
class ChunkDispatch:
    """One device dispatch of a plan: the static arguments of one
    compiled program, the padded host arrays it consumes (every array
    carries the ``chunk``-long cell axis first), and the scatter map
    from its real rows back to plan cell indices."""

    kind: str                       # "rate" | "event"
    static: tuple                   # static args of the jitted core
    arrays: dict[str, np.ndarray]   # padded inputs, leading axis == chunk
    cell_idx: tuple[int, ...]       # row r (< n_real) -> cells[cell_idx[r]]
    chunk: int                      # padded leading-axis length

    @property
    def n_real(self) -> int:
        return len(self.cell_idx)


@dataclass
class SweepPlan:
    """An explicit sweep execution plan: resolved cells (in caller
    order) plus the dispatch list any `repro.sim.exec` backend can run.
    ``work``/``requests`` are per-cell totals precomputed during
    planning (rate plans only; event totals derive from the cells).

    ``meta`` holds the plan's ``plan_id`` and its size counters, sums
    over dispatches or cells that `repro.sim.exec.execute` copies into
    the result's ``meta``: ``cells`` (real rows), ``rows`` (chunk),
    ``h2d_bytes`` (the dispatch arrays), for event and fleet plans
    ``row_entries`` (real rows x E), ``entries_scanned`` (chunk x E),
    ``entries`` (real entries) and ``arrivals`` (real arrivals), and for
    fleet plans ``tenants`` (real tenants), ``tenant_slots`` (padded
    tenant tables, N_pad) and ``populations`` (distinct tenant
    populations, each merged once)."""

    kind: str                       # "rate" | "event"
    cells: list
    dispatches: list[ChunkDispatch]
    n_max: int
    work: np.ndarray | None = None          # (n_cells,) f64, rate only
    requests: np.ndarray | None = None      # (n_cells,) i64, rate only
    meta: dict = field(default_factory=dict)

    @property
    def n_dispatches(self) -> int:
        return len(self.dispatches)


def _counters(dispatches: list[ChunkDispatch], entries: dict | None = None,
              arrivals: int = 0) -> dict:
    """The plan's size counters (`SweepPlan.meta`). With ``entries``
    (each cell's entry stream: event and fleet plans), the share of
    arrival slots the dispatches fill splits into three factors:
    ``row_entries / entries_scanned`` (chunk rounding), ``entries /
    row_entries`` (E rounding), ``arrivals / (BLOCK * entries)``
    (part-full blocks)."""
    out = {"cells": sum(d.n_real for d in dispatches),
           "rows": sum(d.chunk for d in dispatches),
           "h2d_bytes": sum(a.nbytes for d in dispatches
                            for a in d.arrays.values())}
    if entries is not None:
        widths = [(d, d.arrays["times"].shape[1]) for d in dispatches]
        out.update(row_entries=sum(d.n_real * E for d, E in widths),
                   entries_scanned=sum(d.chunk * E for d, E in widths),
                   entries=sum(len(e) for e in entries.values()),
                   arrivals=arrivals)
    return out


def _traced_plan(plan_fn):
    """Run a planner inside the ``repro.plan`` span and number its plan
    (``meta["plan_id"]``)."""
    @functools.wraps(plan_fn)
    def planner(cells: Iterable, *args, **kwargs) -> SweepPlan:
        cells = list(cells)
        plan_id = next(_PLAN_IDS)
        with TraceAnnotation("repro.plan", plan_id=plan_id,
                             cells=len(cells)):
            plan = plan_fn(cells, *args, **kwargs)
        plan.meta = {"plan_id": plan_id, **plan.meta}
        return plan
    return planner


@_traced_plan
def plan_sweep(cells: Iterable, n_max: int | None = None) -> SweepPlan:
    """Plan a rate-simulator sweep: one `ChunkDispatch` per (policy,
    interval, spin-up, horizon) group chunk, arrays laid out exactly as
    `ratesim._simulate_cells` consumes them. Scenario-bearing cells are
    resolved first (one synthesis dispatch per distinct spec).

    The rate simulator has no per-worker identity, so failure-bearing
    cells are *fluidized* here: `FailureSpec.degrade_fleet` folds the
    expected failure overheads into the fleet parameters and the cell's
    ``failures`` is cleared (the plan's cells record what was actually
    simulated; re-planning them will not degrade twice). The DES engines
    are the exact path — docs/architecture.md §Failure model."""
    with TraceAnnotation("repro.plan.resolve"):
        cells = resolve_scenarios(cells)
    cells = [
        c if getattr(c, "failures", None) is None
        or c.failures.normalized() is None
        else replace(c, fleet=c.failures.degrade_fleet(c.fleet),
                     failures=None)
        for c in cells]
    with TraceAnnotation("repro.plan.pack"):
        groups: dict[tuple, list[int]] = {}
        for i, c in enumerate(cells):
            # the policy OBJECT (frozen dataclass: hashable, stable repr) is
            # the group key and rides through `ChunkDispatch.static` — its
            # static structure picks the compiled program, its traced
            # parameters (headroom/level/gain) travel in the arrays
            pol = get_rate_policy(c.policy)
            interval_s = max(int(round(c.fleet.T_s)), 1)
            spin_up_s = max(int(round(c.fleet.fpga.spin_up_s)), 1)
            horizon = (len(c.counts) // interval_s) * interval_s
            if pol.latency_free and horizon % _CANON_INTERVAL == 0:
                interval_s = spin_up_s = _CANON_INTERVAL
            groups.setdefault((pol, interval_s, spin_up_s, horizon,
                               n_max or _N_MAX_CAP), []).append(i)

        n = len(cells)
        work = np.zeros((n,), np.float64)
        requests = np.zeros((n,), np.int64)
        dispatches: list[ChunkDispatch] = []

        for (pol, interval_s, spin_up_s, horizon, nm), idxs in groups.items():
            group = [cells[i] for i in idxs]
            counts = np.stack([np.asarray(c.counts[:horizon], np.int32)
                               for c in group])
            sizes = np.array([c.size_s for c in group], np.float32)
            ew = np.array([c.energy_weight for c in group], np.float32)
            hr = np.array([c.headroom for c in group], np.int32)
            gain = np.array([getattr(c, "forecast_gain", 1.0) for c in group],
                            np.float32)
            scal = np.array([_fleet_scalars_np(c.fleet) for c in group],
                            np.float32)     # (C, len(FleetScalars._fields))
            if pol.name == "fpga_static":
                levels = np.array(
                    [static_level_for(c.counts[:horizon], c.size_s,
                                      c.fleet, nm)
                     for c in group], np.int32)
            else:
                levels = np.zeros((len(group),), np.int32)

            work[idxs] = counts.sum(1, dtype=np.float64) * sizes
            requests[idxs] = counts.sum(1, dtype=np.int64)

            start = 0
            while start < len(group):
                left = len(group) - start
                # Predictor policies carry O(n_max^2) histogram state per
                # cell, so they always use the small shape; cheap policies
                # jump to the big shape for expanded grids (headroom tuning).
                if pol.uses_predictor or left <= CHUNK:
                    chunk = CHUNK
                else:
                    chunk = CHUNK_BIG
                sl = slice(start, min(start + chunk, len(group)))
                start += chunk
                arrays = {
                    "counts": _pad(counts[sl], chunk),
                    "sizes": _pad(sizes[sl], chunk),
                    "scalars": _pad(scal[sl], chunk),
                    "energy_weight": _pad(ew[sl], chunk),
                    "headroom": _pad(hr[sl], chunk),
                    "levels": _pad(levels[sl], chunk),
                    "gain": _pad(gain[sl], chunk),
                }
                dispatches.append(ChunkDispatch(
                    kind="rate",
                    static=(pol, interval_s, spin_up_s, nm, horizon),
                    arrays=arrays, cell_idx=tuple(idxs[sl.start:sl.stop]),
                    chunk=chunk))

    return SweepPlan("rate", cells, dispatches, n_max or _N_MAX_CAP,
                     work=work, requests=requests,
                     meta=_counters(dispatches))


@_traced_plan
def plan_events(cells: Iterable, n_max: int = 512, w_fpga: int = 32,
                w_cpu: int = 64, resolve: bool = True) -> SweepPlan:
    """Plan a DES sweep: cells grouped by padded entry-stream length,
    one `ChunkDispatch` per group chunk, arrays laid out exactly as
    `events_batched._simulate_cells` consumes them. ``resolve=False``
    requires every cell to carry explicit demand already (the engine's
    fail-fast contract: scenario-bearing cells go through
    `repro.sim.sweep.sweep_events`).

    Plans are explicit data: every chunk's padded entry-stream arrays
    (``chunk x E x BLOCK`` float32) are materialized up front, so host
    memory is proportional to the whole sweep rather than one chunk.
    At benchmark scale that is megabytes; callers planning very long
    streams x many chunks should slab their cell lists into multiple
    plans."""
    if resolve:
        with TraceAnnotation("repro.plan.resolve"):
            cells = resolve_scenarios(cells)
    with TraceAnnotation("repro.plan.entries"):
        codes = {}
        for i, cl in enumerate(cells):
            codes[i] = get_dispatch_policy(cl.dispatcher).code
            if cl.arrival_times is None or cl.size_s is None:
                raise ValueError(
                    "EventCell without explicit demand (arrival_times + "
                    "size_s); scenario-bearing cells must go through "
                    "repro.sim.sweep.sweep_events, which resolves them")
        entries: dict[int, list] = {}
        groups: dict[tuple, list[int]] = {}
        arrivals = 0
        for i, cl in enumerate(cells):
            arr = np.asarray(cl.arrival_times, np.float64)
            arrivals += len(arr)
            horizon = float(cl.horizon_s if cl.horizon_s is not None
                            else (arr[-1] + 1.0 if len(arr) else 1.0))
            entries[i] = _entries(arr, cl.fleet.T_s, horizon)
            n_e = len(entries[i])
            # pow2 up to 256 entries, then multiples of 256: every padded
            # entry costs a full BLOCK of inert arrival slots, so tight
            # padding beats shape reuse once streams are long.
            E = (_pad_pow2(n_e, lo=4) if n_e <= 256
                 else 256 * int(math.ceil(n_e / 256)))
            # the failure axis's static part joins the group key: disabled
            # cells compile (and stay on) the pristine pre-failure program
            groups.setdefault((E, fail_static(cl.failures)), []).append(i)

    with TraceAnnotation("repro.plan.pack"):
        dispatches: list[ChunkDispatch] = []
        for (E, fstat), idxs in groups.items():
            chunk = _pad_pow2(len(idxs), lo=4, hi=EV_CHUNK_MAX)
            start = 0
            while start < len(idxs):
                sl = idxs[start:start + chunk]
                start += chunk
                pad = sl + [sl[0]] * (chunk - len(sl))
                times = np.full((len(pad), E, BLOCK), np.inf, np.float32)
                tick_t = np.zeros((len(pad), E), np.float32)
                is_tick = np.zeros((len(pad), E), bool)
                for r, i in enumerate(pad):
                    for e, (row, tick) in enumerate(entries[i]):
                        times[r, e, :len(row)] = row
                        if tick is not None:
                            tick_t[r, e] = tick
                            is_tick[r, e] = True
                arrays = {
                    "scalars": np.array([_scalars(cells[i])[:-2] for i in pad],
                                        np.float32),
                    "fail_seed": np.array(
                        [(cells[i].failures.seed
                          if cells[i].failures is not None else 0)
                         for i in pad], np.uint32),
                    "max_fpgas": np.array([cells[i].fleet.max_fpgas
                                           for i in pad], np.int32),
                    "allocate": np.array([cells[i].allocate_fpgas
                                          for i in pad], bool),
                    "codes": np.array([codes[i] for i in pad], np.int32),
                    "times": times, "tick_t": tick_t, "is_tick": is_tick,
                }
                dispatches.append(ChunkDispatch(
                    kind="event", static=(n_max, w_fpga, w_cpu, fstat),
                    arrays=arrays, cell_idx=tuple(sl), chunk=chunk))

    return SweepPlan("event", cells, dispatches, n_max,
                     meta=_counters(dispatches, entries, arrivals))


@_traced_plan
def plan_fleet(cells: Iterable, n_max: int = 512, w_fpga: int = 32,
               w_cpu: int = 64) -> SweepPlan:
    """Plan a multi-tenant fleet sweep (`repro.fleet.FleetCell` cells):
    the DES plan machinery of `plan_events` with a tenant axis — each
    cell's merged tenant-tagged stream (`repro.fleet.resolve_fleet_cell`)
    becomes ``times`` + ``tids`` entry blocks, and per-tenant
    size/deadline/admission tables ride along padded to a power-of-two
    tenant count. Groups key on (padded entry count, padded tenant
    count, failure static), so a 1024-tenant policy x seed grid whose
    cells share stream/tenant shape is a handful of dispatches
    (benchmarks/fleet_suite.py asserts the budget). Cells that share a
    tenant population share its merged stream
    (`repro.fleet.specs.merge_population`) and its entry stream.

    Execution: `repro.sim.exec` routes ``kind="fleet"`` dispatches to
    `repro.fleet.engine` on either backend; `repro.sim.sweep.sweep_fleet`
    is the plan+execute wrapper returning a `FleetSweepResult`."""
    from repro.fleet.specs import FleetCell, resolve_fleet_cell
    from repro.sim.events_batched import EventCell

    from repro.policies import get_admission_policy
    resolved: dict[int, Any] = {}
    with TraceAnnotation("repro.plan.resolve"):
        for i, cl in enumerate(cells):
            if not isinstance(cl, FleetCell):
                raise TypeError(
                    f"plan_fleet needs repro.fleet.FleetCell cells, got "
                    f"{type(cl).__name__}")
            resolved[i] = resolve_fleet_cell(cl)
    with TraceAnnotation("repro.plan.entries"):
        entries: dict[int, list] = {}
        by_stream: dict[tuple, list] = {}    # cells of one population
        groups: dict[tuple, list[int]] = {}
        codes, acodes = {}, {}
        arrivals = tenant_slots = 0
        for i, cl in enumerate(cells):
            rs = resolved[i]
            arrivals += len(rs.times)
            codes[i] = get_dispatch_policy(cl.dispatcher).code
            acodes[i] = get_admission_policy(cl.admission).code
            key = (cl.population, cl.fleet.T_s, rs.horizon_s)
            if key not in by_stream:
                by_stream[key] = _entries(rs.times, cl.fleet.T_s,
                                          rs.horizon_s, payload=rs.tids)
            entries[i] = by_stream[key]
            n_e = len(entries[i])
            E = (_pad_pow2(n_e, lo=4) if n_e <= 256
                 else 256 * int(math.ceil(n_e / 256)))
            N_pad = _pad_pow2(rs.n_tenants, lo=4)
            tenant_slots += N_pad
            groups.setdefault((E, N_pad, fail_static(rs.failures)),
                              []).append(i)

    def _proxy(i: int) -> EventCell:
        # an EventCell twin carrying the cell's fleet/objective axes so
        # `_scalars` stays the single source of truth; size/deadline are
        # tenant 0's (overridden per arrival by the tenant tables)
        cl, rs = cells[i], resolved[i]
        return EventCell(dispatcher=cl.dispatcher,
                         size_s=float(rs.sizes[0]), fleet=cl.fleet,
                         energy_weight=cl.energy_weight,
                         deadline_s=float(rs.deadlines[0]),
                         allocate_fpgas=cl.allocate_fpgas,
                         failures=rs.failures)

    def _tenant_table(i: int, n_pad: int) -> np.ndarray:
        # (5, N_pad) f32 rows: size, deadline, adm_rate/burst/quota.
        # Padded tenant slots are never referenced by any tid; 1.0
        # size/deadline keeps them valid EventScalars values.
        rs = resolved[i]
        tbl = np.zeros((5, n_pad), np.float32)
        tbl[0, :] = tbl[1, :] = 1.0
        n = rs.n_tenants
        tbl[0, :n] = rs.sizes
        tbl[1, :n] = rs.deadlines
        tbl[2, :n] = rs.adm_rate
        tbl[3, :n] = rs.adm_burst
        tbl[4, :n] = rs.adm_quota
        return tbl

    with TraceAnnotation("repro.plan.pack"):
        dispatches: list[ChunkDispatch] = []
        for (E, N_pad, fstat), idxs in groups.items():
            chunk = _pad_pow2(len(idxs), lo=4, hi=EV_CHUNK_MAX)
            start = 0
            while start < len(idxs):
                sl = idxs[start:start + chunk]
                start += chunk
                pad = sl + [sl[0]] * (chunk - len(sl))
                times = np.full((len(pad), E, BLOCK), np.inf, np.float32)
                tids = np.zeros((len(pad), E, BLOCK), np.int32)
                tick_t = np.zeros((len(pad), E), np.float32)
                is_tick = np.zeros((len(pad), E), bool)
                for r, i in enumerate(pad):
                    for e, (row, prow, tick) in enumerate(entries[i]):
                        times[r, e, :len(row)] = row
                        tids[r, e, :len(prow)] = prow
                        if tick is not None:
                            tick_t[r, e] = tick
                            is_tick[r, e] = True
                tables = np.stack([_tenant_table(i, N_pad) for i in pad])
                arrays = {
                    "scalars": np.array(
                        [_scalars(_proxy(i))[:-2] for i in pad], np.float32),
                    "fail_seed": np.array(
                        [(resolved[i].failures.seed
                          if resolved[i].failures is not None else 0)
                         for i in pad], np.uint32),
                    "max_fpgas": np.array([cells[i].fleet.max_fpgas
                                           for i in pad], np.int32),
                    "allocate": np.array([cells[i].allocate_fpgas
                                          for i in pad], bool),
                    "codes": np.array([codes[i] for i in pad], np.int32),
                    "acodes": np.array([acodes[i] for i in pad], np.int32),
                    "times": times, "tids": tids,
                    "tick_t": tick_t, "is_tick": is_tick,
                    "ta_size": tables[:, 0], "ta_deadline": tables[:, 1],
                    "adm_rate": tables[:, 2], "adm_burst": tables[:, 3],
                    "adm_quota": tables[:, 4],
                }
                dispatches.append(ChunkDispatch(
                    kind="fleet", static=(n_max, w_fpga, w_cpu, fstat),
                    arrays=arrays, cell_idx=tuple(sl), chunk=chunk))

    meta = _counters(dispatches, entries, arrivals)
    meta.update(tenants=sum(rs.n_tenants for rs in resolved.values()),
                tenant_slots=tenant_slots,
                populations=len({cl.population for cl in cells}))
    return SweepPlan("fleet", cells, dispatches, n_max, meta=meta)


class SweepResult:
    """Stacked per-cell `Accum` + conversion to paper-style totals/reports.

    ``n_dispatches`` counts the device dispatches the sweep cost (one
    per plan chunk) — the batching contract benchmarks and tests assert
    on. ``backend``/``n_devices``/``dispatch_devices`` record which
    `repro.sim.exec` backend ran the plan and how many mesh devices
    each dispatch was sharded over (all 1s on `LocalBackend`).
    ``meta`` carries the `repro.sim.harness.ResilientRunner` record:
    executed/restored chunk counters, retried dispatches and
    ``degraded_chunks`` (chunk indices that fell back to the local
    backend)."""

    def __init__(self, cells: Sequence, accum: Accum,
                 total_work: np.ndarray, total_requests: np.ndarray,
                 n_dispatches: int = 0, backend: str = "local",
                 n_devices: int = 1,
                 dispatch_devices: Sequence[int] | None = None,
                 meta: dict | None = None):
        self.cells = list(cells)
        self.accum = accum                      # leaves: (n_cells,) np arrays
        self._work = total_work
        self._requests = total_requests
        self.n_dispatches = n_dispatches
        self.backend = backend
        self.n_devices = n_devices
        self.dispatch_devices = list(dispatch_devices or [])
        self.meta = dict(meta or {})

    def __len__(self) -> int:
        return len(self.cells)

    @property
    def deadline_misses(self) -> np.ndarray:
        return np.asarray(self.accum.missed_requests)

    def totals(self, i: int) -> RunTotals:
        one = Accum(*[leaf[i] for leaf in self.accum])
        return accum_to_totals(one, float(self._work[i]),
                               int(self._requests[i]))

    def report(self, i: int,
               reference_fleet: FleetParams | None = None) -> Report:
        return report(self.totals(i), self.cells[i].fleet,
                      reference_fleet=reference_fleet)

    def reports(self, reference_fleet: FleetParams | None = None) -> list[Report]:
        return [self.report(i, reference_fleet) for i in range(len(self))]


class EventSweepResult:
    """DES counterpart of `SweepResult`: per-cell `RunTotals` in cell
    order plus the same batching-contract metadata (``n_dispatches``,
    ``backend``, ``n_devices``, ``dispatch_devices``).

    Sequence-compatible with the bare ``list[RunTotals]`` it replaced:
    iteration, ``len`` and indexing all see the totals, and
    ``totals()`` / ``totals(i)`` mirror `SweepResult.totals`. ``meta``
    carries the `repro.sim.harness.ResilientRunner` record (see
    `SweepResult`)."""

    def __init__(self, cells: Sequence, totals: Sequence[RunTotals],
                 n_dispatches: int = 0, backend: str = "local",
                 n_devices: int = 1,
                 dispatch_devices: Sequence[int] | None = None,
                 meta: dict | None = None):
        self.cells = list(cells)
        self._totals = list(totals)
        self.n_dispatches = n_dispatches
        self.backend = backend
        self.n_devices = n_devices
        self.dispatch_devices = list(dispatch_devices or [])
        self.meta = dict(meta or {})

    def __len__(self) -> int:
        return len(self._totals)

    def __iter__(self):
        return iter(self._totals)

    def __getitem__(self, i):
        return self._totals[i]

    def totals(self, i: int | None = None):
        """All totals (cell order) or one cell's totals."""
        return list(self._totals) if i is None else self._totals[i]

    def report(self, i: int,
               reference_fleet: FleetParams | None = None) -> Report:
        return report(self._totals[i], self.cells[i].fleet,
                      reference_fleet=reference_fleet)


class FleetSweepResult(EventSweepResult):
    """Multi-tenant counterpart of `EventSweepResult`: per-cell fleet
    `RunTotals` (cell order, with ``breakdown['offered_requests']`` /
    ``['shed_requests']``) plus per-cell, per-tenant
    `repro.core.metrics.TenantTotals` rows. The tenant rows conserve
    against the fleet totals — `repro.sim.harness.check_fleet_result`
    verifies it on every execution (default-on invariant guard)."""

    def __init__(self, cells: Sequence, totals: Sequence[RunTotals],
                 tenants: Sequence[list], n_dispatches: int = 0,
                 backend: str = "local", n_devices: int = 1,
                 dispatch_devices: Sequence[int] | None = None,
                 meta: dict | None = None):
        super().__init__(cells, totals, n_dispatches=n_dispatches,
                         backend=backend, n_devices=n_devices,
                         dispatch_devices=dispatch_devices, meta=meta)
        self._tenants = list(tenants)

    def tenants(self, i: int | None = None):
        """Per-tenant `TenantTotals` rows for every cell (cell order) or
        for one cell."""
        return list(self._tenants) if i is None else self._tenants[i]
