"""Sweep execution backends: run a `SweepPlan` locally or over a mesh.

The planning layer (`repro.sim.plan`) reduces every sweep entry point to
the same question: given a list of `ChunkDispatch`es — static program
arguments plus padded host arrays with a leading cell axis — run each
one and scatter the rows back into cell order. This module owns that
question, behind a two-backend interface:

  * `LocalBackend` (default): the single-device vmapped path — each
    dispatch calls the same jitted programs
    (`ratesim._simulate_cells`, `events_batched._simulate_cells`) the
    pre-plan/execute code called, with identically laid-out arguments,
    so results are bit-identical to the historical path and the
    existing golden tests pin it.
  * `MeshBackend`: the same programs `shard_map`-ped over the cell axis
    of a 1-D device mesh (`repro.launch.mesh.make_cell_mesh`). Every
    vmap lane is independent, so sharding lanes across devices changes
    *where* each cell runs, not *what* it computes — `MeshBackend`
    results are tested bit-identical to `LocalBackend` on a forced
    multi-device CPU host (tests/test_plan.py; CI runs the sweep/DES
    equivalence suites under ``XLA_FLAGS=
    --xla_force_host_platform_device_count=2`` with
    ``BENCH_SWEEP_BACKEND=mesh``). Chunk shapes come from the planner's
    fixed power-of-two-friendly vocabulary, so each dispatch uses the
    largest power-of-two device count that divides its chunk.

`get_backend` resolves the ``backend=`` kwarg threaded through `sweep` /
`sweep_events` / `tune_fpga_dynamic_cells` and the benchmarks: a
`Backend` instance passes through, a name maps to a cached singleton,
and None falls back to the ``BENCH_SWEEP_BACKEND`` env var (default
``local``). Sharding-scheme rationale: docs/DESIGN.md §5; the
plan -> backend flow: docs/architecture.md "Execution backends".

`execute` routes every dispatch through a
`repro.sim.harness.ResilientRunner`: per-chunk checkpoint/resume
(``checkpoint_dir=``), bounded retry with backoff + wall timeout, and
mesh->local degradation on backend failure; invariant guards validate
every result by default (opt-out ``REPRO_SKIP_INVARIANTS``). See
docs/architecture.md "Execution hardening".
"""

from __future__ import annotations

import functools
import os
from typing import Sequence

import numpy as np

import jax
import jax.numpy as jnp

from repro.policies import RateParams
from repro.sim import events_batched, ratesim
from repro.sim.plan import (Accum, ChunkDispatch, EventSweepResult,
                            FleetSweepResult, SweepPlan, SweepResult,
                            accum_to_totals)

ENV_VAR = "BENCH_SWEEP_BACKEND"


def _rate_args(d: ChunkDispatch) -> tuple:
    """Traced arguments for `ratesim._simulate_cells`, in order, laid
    out exactly as the pre-plan/execute sweep loop built them. The
    per-cell policy parameters (headroom, static level, forecast gain)
    ride as one `RateParams` pytree — the policy object itself is
    static, in ``d.static``."""
    a = d.arrays
    fs = ratesim.FleetScalars(*(jnp.asarray(a["scalars"][:, j])
                                for j in range(a["scalars"].shape[1])))
    params = RateParams(jnp.asarray(a["headroom"]),
                        jnp.asarray(a["levels"]),
                        jnp.asarray(a["gain"]))
    return (jnp.asarray(a["counts"]), jnp.asarray(a["sizes"]), fs,
            jnp.asarray(a["energy_weight"]), params)


def _event_args(d: ChunkDispatch) -> tuple:
    """Traced arguments for `events_batched._simulate_cells`, in order.
    The ``scalars`` matrix holds every float field of `EventScalars`
    (incl. the 8 traced failure knobs); the uint32 hash seed and the
    int/bool fields ride as separate arrays."""
    a = d.arrays
    es = events_batched.EventScalars(
        *(jnp.asarray(a["scalars"][:, j])
          for j in range(a["scalars"].shape[1])),
        f_seed=jnp.asarray(a["fail_seed"]),
        max_fpgas=jnp.asarray(a["max_fpgas"]),
        allocate=jnp.asarray(a["allocate"]))
    return (es, jnp.asarray(a["codes"]), jnp.asarray(a["times"]),
            jnp.asarray(a["tick_t"]), jnp.asarray(a["is_tick"]))


def _fleet_args(d: ChunkDispatch) -> tuple:
    """Traced arguments for `repro.fleet.engine._simulate_fleet_cells`:
    the event layout (`_event_args`) plus the tenant axis — per-arrival
    tenant indices and the padded per-tenant size/deadline/admission
    tables."""
    a = d.arrays
    es = events_batched.EventScalars(
        *(jnp.asarray(a["scalars"][:, j])
          for j in range(a["scalars"].shape[1])),
        f_seed=jnp.asarray(a["fail_seed"]),
        max_fpgas=jnp.asarray(a["max_fpgas"]),
        allocate=jnp.asarray(a["allocate"]))
    return (es, jnp.asarray(a["codes"]), jnp.asarray(a["acodes"]),
            jnp.asarray(a["times"]), jnp.asarray(a["tids"]),
            jnp.asarray(a["tick_t"]), jnp.asarray(a["is_tick"]),
            jnp.asarray(a["ta_size"]), jnp.asarray(a["ta_deadline"]),
            jnp.asarray(a["adm_rate"]), jnp.asarray(a["adm_burst"]),
            jnp.asarray(a["adm_quota"]))


class Backend:
    """One way of running a plan's dispatches. Subclasses implement
    `run(dispatch)` (returning the core's output pytree) and
    `devices_for(dispatch)` (how many devices that dispatch spans)."""

    name = "abstract"

    @property
    def n_devices(self) -> int:
        return 1

    def devices_for(self, d: ChunkDispatch) -> int:
        return 1

    def run(self, d: ChunkDispatch):
        raise NotImplementedError


class LocalBackend(Backend):
    """Single-device vmapped execution — the bit-identical default.

    Calls the exact jitted programs the pre-refactor sweep loops called
    (`ratesim._simulate_cells` / `events_batched._simulate_cells`), so
    compiled-program reuse (and the persistent compilation cache)
    behaves as before."""

    name = "local"

    def run(self, d: ChunkDispatch):
        if d.kind == "rate":
            return ratesim._simulate_cells(*d.static, *_rate_args(d))
        if d.kind == "fleet":
            from repro.fleet import engine as fleet_engine
            return fleet_engine._simulate_fleet_cells(*d.static,
                                                      *_fleet_args(d))
        return events_batched._simulate_cells(*d.static, *_event_args(d))


class MeshBackend(Backend):
    """Sharded execution: `shard_map` over the chunk/cell axis.

    The planner's chunk axis is split over a 1-D ``('cells',)`` device
    mesh; each device runs the same vmapped simulator core on its lane
    shard. Lanes are independent, so per-cell results are bit-identical
    to `LocalBackend` (tested on a forced 2-device CPU host). Use
    ``XLA_FLAGS=--xla_force_host_platform_device_count=N`` (newer JAX:
    the ``jax_num_cpu_devices`` config) to fabricate CPU devices, or
    run on a real multi-device backend."""

    name = "mesh"

    def __init__(self, devices: Sequence | None = None):
        self.devices = list(devices) if devices is not None \
            else list(jax.devices())
        self._fns: dict = {}

    @property
    def n_devices(self) -> int:
        return len(self.devices)

    def devices_for(self, d: ChunkDispatch) -> int:
        """Largest power-of-two device count that divides the chunk (the
        plan vocabulary is power-of-two-friendly, so this is normally
        min(pow2(n_devices), chunk))."""
        n = 1
        while n * 2 <= len(self.devices) and d.chunk % (n * 2) == 0:
            n *= 2
        return n

    def _fn(self, kind: str, static: tuple, n_dev: int):
        key = (kind, static, n_dev)
        fn = self._fns.get(key)
        if fn is None:
            from jax.sharding import PartitionSpec as P

            from repro.launch.mesh import make_cell_mesh
            mesh = make_cell_mesh(self.devices[:n_dev])
            if kind == "rate":
                core = ratesim._simulate_cells_core
            elif kind == "fleet":
                from repro.fleet import engine as fleet_engine
                core = fleet_engine._simulate_fleet_cells_core
            else:
                core = events_batched._simulate_cells_core
            sharded = jax.shard_map(functools.partial(core, *static),
                                    mesh=mesh, in_specs=P("cells"),
                                    out_specs=P("cells"), check_vma=False)
            fn = self._fns[key] = jax.jit(sharded)
        return fn

    def run(self, d: ChunkDispatch):
        fn = self._fn(d.kind, d.static, self.devices_for(d))
        args = {"rate": _rate_args,
                "fleet": _fleet_args}.get(d.kind, _event_args)(d)
        return fn(*args)


_BACKENDS = {"local": LocalBackend, "mesh": MeshBackend}
_instances: dict[str, Backend] = {}


def get_backend(backend: str | Backend | None = None) -> Backend:
    """Resolve a backend: an instance passes through, a name maps to a
    cached singleton (so jit caches persist across sweeps), None reads
    ``BENCH_SWEEP_BACKEND`` (default ``local``)."""
    if isinstance(backend, Backend):
        return backend
    name = backend or os.environ.get(ENV_VAR, "local")
    if name not in _BACKENDS:
        raise ValueError(f"unknown sweep backend {name!r} "
                         f"(expected one of {sorted(_BACKENDS)})")
    if name not in _instances:
        _instances[name] = _BACKENDS[name]()
    return _instances[name]


def execute(plan: SweepPlan, backend: str | Backend | None = None, *,
            checkpoint_dir=None, retry=None, validate: bool | None = None):
    """Run every dispatch of a plan on a backend and scatter the rows
    back into cell order. Returns `SweepResult` for rate plans,
    `EventSweepResult` for event plans and `FleetSweepResult` for
    multi-tenant fleet plans; all carry ``n_dispatches``, the
    backend's ``n_devices`` / per-dispatch device counts, and the
    resilience ``meta`` record.

    Execution is hardened by `repro.sim.harness` (docs/architecture.md
    "Execution hardening"): ``checkpoint_dir`` persists each completed
    chunk (content-addressed — a killed run restarted with the same
    directory re-executes only unfinished chunks, bit-identically);
    ``retry`` is a `repro.sim.harness.RetryPolicy` (bounded retry +
    backoff, per-chunk wall timeout, mesh->local degradation); and the
    invariant guards validate every result by default (``validate=None``
    reads the ``REPRO_SKIP_INVARIANTS`` opt-out, True/False force)."""
    from repro.sim.harness import (ResilientRunner, check_sweep_result,
                                   invariants_enabled)
    backend = get_backend(backend)
    runner = ResilientRunner(backend, checkpoint_dir=checkpoint_dir,
                             retry=retry)
    if plan.kind == "rate":
        res = _execute_rate(plan, backend, runner)
    elif plan.kind == "fleet":
        res = _execute_fleet(plan, backend, runner)
    else:
        res = _execute_event(plan, backend, runner)
    res.meta.update(runner.meta())
    if invariants_enabled() if validate is None else validate:
        check_sweep_result(res)
    return res


def _execute_rate(plan: SweepPlan, backend: Backend, runner) -> SweepResult:
    n = len(plan.cells)
    leaves = [np.zeros((n,), np.float64) for _ in Accum._fields]
    devs = []
    for d in plan.dispatches:
        acc = runner.run(d)
        devs.append(backend.devices_for(d))
        dest = list(d.cell_idx)
        for leaf, out in zip(acc, leaves):
            out[dest] = np.asarray(leaf)[:d.n_real]
    return SweepResult(plan.cells, Accum(*leaves), plan.work, plan.requests,
                       n_dispatches=plan.n_dispatches, backend=backend.name,
                       n_devices=backend.n_devices, dispatch_devices=devs)


def _execute_event(plan: SweepPlan, backend: Backend,
                   runner) -> EventSweepResult:
    out = [None] * len(plan.cells)
    devs = []
    for d in plan.dispatches:
        acc, fail, over = runner.run(d)
        devs.append(backend.devices_for(d))
        acc_np = [np.asarray(leaf) for leaf in acc]
        fail_np = [np.asarray(leaf) for leaf in fail]
        over_np = np.asarray(over)
        for r, i in enumerate(d.cell_idx):
            cell = plan.cells[i]
            n_req = len(cell.arrival_times)
            tot = accum_to_totals(Accum(*[leaf[r] for leaf in acc_np]),
                                  n_req * cell.size_s, n_req)
            fl = events_batched.FailAcc(*[leaf[r] for leaf in fail_np])
            # resilience counters + the oracle's finalize composition:
            # wasted spin-up energy joins energy_j, stillborn occupancy
            # joins cost_usd (all exactly zero when the axis is off)
            tot.retries = int(fl.retries)
            tot.failed_spinups = int(fl.failed_spins)
            tot.crashes = int(fl.crashes)
            tot.recovered_requests = int(fl.recovered)
            tot.failure_misses = int(fl.fail_misses)
            tot.wasted_spinup_j = float(fl.wasted_j)
            tot.energy_j += float(fl.wasted_j)
            tot.cost_usd += float(fl.extra_cost)
            tot.breakdown["slot_overflow"] = int(over_np[r])
            out[i] = tot
    return EventSweepResult(plan.cells, out, n_dispatches=plan.n_dispatches,
                            backend=backend.name,
                            n_devices=backend.n_devices,
                            dispatch_devices=devs)


def _execute_fleet(plan: SweepPlan, backend: Backend,
                   runner) -> FleetSweepResult:
    """Scatter fleet-dispatch outputs into per-cell fleet `RunTotals` +
    per-tenant `TenantTotals` rows. Conservation is BY CONSTRUCTION:
    the fleet-level requests / work / misses / work-split are computed
    from the per-tenant accumulators themselves (then energy/cost are
    attributed back out of the fleet totals), so the tenant rows always
    reconcile — `repro.sim.harness.check_fleet_result` enforces it."""
    from repro.core.metrics import attribute_tenants
    from repro.fleet.specs import resolve_fleet_cell

    out = [None] * len(plan.cells)
    tenants = [None] * len(plan.cells)
    devs = []
    for d in plan.dispatches:
        acc, fail, over, fa = runner.run(d)
        devs.append(backend.devices_for(d))
        acc_np = [np.asarray(leaf) for leaf in acc]
        fail_np = [np.asarray(leaf) for leaf in fail]
        over_np = np.asarray(over)
        fa_np = [np.asarray(leaf) for leaf in fa]
        for r, i in enumerate(d.cell_idx):
            cell = plan.cells[i]
            rs = resolve_fleet_cell(cell)       # lru-cached
            n = rs.n_tenants
            offered, admitted, shed, missed, work_f, work_c = (
                leaf[r, :n] for leaf in fa_np)
            n_adm = int(admitted.sum())
            work = float((admitted.astype(np.float64) * rs.sizes).sum())
            tot = accum_to_totals(Accum(*[leaf[r] for leaf in acc_np]),
                                  work, n_adm)
            fl = events_batched.FailAcc(*[leaf[r] for leaf in fail_np])
            tot.retries = int(fl.retries)
            tot.failed_spinups = int(fl.failed_spins)
            tot.crashes = int(fl.crashes)
            tot.recovered_requests = int(fl.recovered)
            tot.failure_misses = int(fl.fail_misses)
            tot.wasted_spinup_j = float(fl.wasted_j)
            tot.energy_j += float(fl.wasted_j)
            tot.cost_usd += float(fl.extra_cost)
            # per-tenant sums ARE the fleet-level numbers (each arrival
            # increments exactly one tenant's counter and the matching
            # shared counter, so these agree with the Accum up to f32)
            tot.deadline_misses = int(missed.sum())
            tot.work_on_fpga_cpu_s = float(
                work_f.astype(np.float64).sum())
            tot.work_on_cpu_cpu_s = float(
                work_c.astype(np.float64).sum())
            tot.breakdown["slot_overflow"] = int(over_np[r])
            tot.breakdown["offered_requests"] = int(offered.sum())
            tot.breakdown["shed_requests"] = int(shed.sum())
            out[i] = tot
            tenants[i] = attribute_tenants(
                tot, rs.weights, rs.sizes, offered, admitted, shed,
                missed, work_f.astype(np.float64),
                work_c.astype(np.float64))
    return FleetSweepResult(plan.cells, out, tenants,
                            n_dispatches=plan.n_dispatches,
                            backend=backend.name,
                            n_devices=backend.n_devices,
                            dispatch_devices=devs)
