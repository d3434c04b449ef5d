"""Multi-tenant fleet cells: tenant specs, SLO classes, stream merging.

`repro.fleet` closes the gap between the paper's one-app-at-a-time
evaluation and its datacenter pitch (PAPER.md §2, §7): N latency-
sensitive tenants sharing ONE FPGA+CPU fleet. This module is the
host-side spec layer — frozen, hashable cells the planner can group and
fingerprint:

  * `TenantSpec` — one tenant: demand (a `repro.workloads` scenario or
    an explicit arrival stream), an SLO class (`SLO_CLASSES` deadline
    multipliers), a fairness weight consumed by the admission policy,
    and an optional per-tenant `FailureSpec`.
  * `FleetCell` — one grid cell: a tenant population + ONE shared fleet
    + one dispatch policy + one admission policy. The cell is what
    `repro.sim.plan.plan_fleet` plans and both engines simulate.
  * `resolve_fleet_cell` — materialize the cell: synthesize every
    tenant's arrivals, merge them into one time-ordered tenant-tagged
    stream (stable sort: equal-time arrivals keep tenant-index order, so
    both engines consume the identical stream), and precompute the
    per-tenant size/deadline/weight and admission-knob tables. The merge
    belongs to the tenant population, not the cell: cells that share a
    population (and differ in dispatcher, admission or fleet) share one
    merged stream.

Explicit streams are held as read-only float64 arrays; each spec digests
its stream once and each population (a cell's tenants and seed) hashes
once, at construction: hashing or comparing a cell of thousands of
tenants never walks their arrivals again.

Trust order matches the single-tenant engines (docs/architecture.md
"Fleet layer"): `repro.fleet.oracle.FleetSim` is the exact serial
oracle, `repro.fleet.engine` the batched twin.
"""

from __future__ import annotations

import functools
import hashlib
from dataclasses import dataclass
from typing import Any, NamedTuple

import numpy as np

from jax.profiler import TraceAnnotation

from repro.core.workers import DEFAULT_FLEET, FleetParams
from repro.ft.failures import FailureSpec
from repro.policies import get_admission_policy, get_dispatch_policy

#: SLO class -> deadline multiplier: deadline = multiplier x request
#: size (the paper's single class is 10x size, §5.1; tight/relaxed
#: bracket it for per-tenant SLO differentiation).
SLO_CLASSES = {"tight": 5.0, "standard": 10.0, "relaxed": 20.0}


def _immutable(a: np.ndarray) -> bool:
    """Whether nothing can write to ``a``'s memory: it and every array
    it views are read-only, down to an owner or an immutable buffer."""
    while isinstance(a, np.ndarray):
        if a.flags.writeable:
            return False
        if a.base is None:
            return True
        a = a.base
    return isinstance(a, bytes)


def _stream(times) -> np.ndarray:
    """An explicit arrival stream as a read-only float64 array: a
    contiguous float64 array that nothing can write to passes through,
    anything else is copied."""
    if (isinstance(times, np.ndarray) and times.dtype == np.float64
            and times.ndim == 1 and times.flags.c_contiguous
            and _immutable(times)):
        return times
    a = np.array(times, np.float64).reshape(-1)
    a.setflags(write=False)
    return a


@dataclass(frozen=True, eq=False)
class TenantSpec:
    """One tenant of a shared fleet (frozen + hashable: plan group key,
    checkpoint fingerprint input).

    Demand is exactly one of: a named workload ``scenario``
    (`repro.workloads.scenarios.ScenarioSpec`, realized at ``seed``) or
    an explicit ``arrival_times`` stream (+ ``request_size_s``), given as
    any sequence of seconds and kept as a read-only float64 array.
    ``slo`` names a `SLO_CLASSES` deadline multiplier; ``weight`` is the
    fairness share the admission policy consumes
    (`repro.policies.admission.AdmissionPolicy.tenant_params`).

    Identity is fixed at construction: the stream's bytes are digested
    once, so hashing and comparing specs costs the same whatever their
    length (two specs are equal when every field and the stream's bytes
    are)."""

    scenario: Any = None                   # ScenarioSpec | None
    arrival_times: np.ndarray | None = None   # explicit stream (seconds)
    request_size_s: float | None = None    # None -> scenario's size
    slo: str = "standard"
    weight: float = 1.0
    seed: int = 0
    failures: FailureSpec | None = None

    def __post_init__(self):
        if (self.scenario is None) == (self.arrival_times is None):
            raise ValueError(
                "TenantSpec needs exactly one of scenario= or "
                "arrival_times=")
        digest = None
        if self.arrival_times is not None:
            a = _stream(self.arrival_times)
            object.__setattr__(self, "arrival_times", a)
            digest = hashlib.blake2b(a.data, digest_size=16).digest()
            if a.size and (not np.all(np.isfinite(a)) or np.any(a < 0)
                           or np.any(np.diff(a) < 0)):
                raise ValueError(
                    "TenantSpec.arrival_times must be sorted non-negative "
                    "finite timestamps")
            if self.request_size_s is None:
                raise ValueError(
                    "TenantSpec with explicit arrival_times needs "
                    "request_size_s")
        if self.request_size_s is not None and not (
                np.isfinite(self.request_size_s)
                and self.request_size_s > 0):
            raise ValueError(
                f"TenantSpec.request_size_s must be > 0, got "
                f"{self.request_size_s!r}")
        if self.slo not in SLO_CLASSES:
            raise ValueError(
                f"TenantSpec.slo must be one of {sorted(SLO_CLASSES)}, "
                f"got {self.slo!r}")
        if not (np.isfinite(self.weight) and self.weight > 0):
            raise ValueError(
                f"TenantSpec.weight must be > 0, got {self.weight!r}")
        key = (self.scenario, digest, self.request_size_s, self.slo,
               self.weight, self.seed, self.failures)
        object.__setattr__(self, "_key", key)

    def __eq__(self, other) -> bool:
        return self is other or (type(other) is TenantSpec
                                 and self._key == other._key)

    def __hash__(self) -> int:
        return hash(self._key)

    @property
    def deadline_mult(self) -> float:
        return SLO_CLASSES[self.slo]


class Population:
    """A tenant population at one seed offset: what a merged stream
    depends on. Hashes once; cells built from one ``tenants`` tuple
    compare equal by identity, without walking it."""

    __slots__ = ("tenants", "seed", "_hash")

    def __init__(self, tenants: tuple, seed: int):
        self.tenants, self.seed = tenants, seed
        self._hash = hash((tenants, seed))

    def __eq__(self, other) -> bool:
        return self is other or (
            type(other) is Population and self._hash == other._hash
            and self.seed == other.seed and self.tenants == other.tenants)

    def __hash__(self) -> int:
        return self._hash

    def __reduce__(self):
        # rebuilt through __init__: string hashes differ between
        # processes, so the hash is computed where it is loaded
        return Population, (self.tenants, self.seed)


@dataclass(frozen=True)
class FleetCell:
    """One multi-tenant grid cell: N tenants x ONE shared fleet x one
    dispatch policy x one admission policy.

    ``failures`` (cell-level) overrides any per-tenant `FailureSpec`;
    with no cell-level spec, at most one *distinct* tenant-level spec may
    be present (one shared fleet has one fault model — conflicting
    per-tenant specs are a construction error, surfaced by
    `resolve_fleet_cell`). ``seed`` offsets every tenant's scenario
    realization seed, so seed sweeps re-draw all tenant demand."""

    tenants: tuple = ()
    dispatcher: str = "spork"
    admission: Any = "admit_all"     # name | AdmissionPolicy instance
    fleet: FleetParams = DEFAULT_FLEET
    energy_weight: float = 1.0
    horizon_s: float | None = None
    seed: int = 0
    allocate_fpgas: bool = True
    failures: FailureSpec | None = None
    tag: Any = None

    def __post_init__(self):
        if not isinstance(self.tenants, tuple):
            object.__setattr__(self, "tenants", tuple(self.tenants))
        if not self.tenants:
            raise ValueError("FleetCell needs at least one tenant")
        for t in self.tenants:
            if not isinstance(t, TenantSpec):
                raise TypeError(
                    f"FleetCell.tenants must be TenantSpec, got {t!r}")
        get_dispatch_policy(self.dispatcher)       # fail fast on typos
        get_admission_policy(self.admission)
        if self.horizon_s is not None and not (
                np.isfinite(self.horizon_s) and self.horizon_s > 0):
            raise ValueError(
                f"FleetCell.horizon_s must be > 0, got {self.horizon_s!r}")
        if not np.isfinite(self.energy_weight):
            raise ValueError(
                f"FleetCell.energy_weight must be finite, got "
                f"{self.energy_weight!r}")
        object.__setattr__(self, "population",
                           Population(self.tenants, self.seed))

    def __hash__(self) -> int:
        return hash((self.population, self.dispatcher, self.admission,
                     self.fleet, self.energy_weight, self.horizon_s,
                     self.allocate_fpgas, self.failures, self.tag))

    @property
    def n_tenants(self) -> int:
        return len(self.tenants)


class ResolvedFleet(NamedTuple):
    """Materialized `FleetCell`: the merged tenant-tagged stream plus the
    per-tenant tables both engines consume verbatim."""

    times: np.ndarray        # (n,) f64 merged arrival times, sorted
    tids: np.ndarray         # (n,) i32 tenant index per arrival
    sizes: np.ndarray        # (N,) f64 request service time per tenant
    deadlines: np.ndarray    # (N,) f64 SLO deadline per tenant
    weights: np.ndarray      # (N,) f64 fairness weights
    adm_rate: np.ndarray     # (N,) f32 admission knobs (policy-computed)
    adm_burst: np.ndarray    # (N,) f32
    adm_quota: np.ndarray    # (N,) f32
    horizon_s: float
    failures: FailureSpec | None

    @property
    def n_tenants(self) -> int:
        return len(self.sizes)


class _Merged(NamedTuple):
    """A population's merged stream and per-tenant tables (read-only)."""

    times: np.ndarray
    tids: np.ndarray
    sizes: np.ndarray
    deadlines: np.ndarray
    weights: np.ndarray
    horizon_s: float | None          # longest scenario horizon, if any
    failures: frozenset              # distinct tenant-level FailureSpecs


def _frozen(a: np.ndarray) -> np.ndarray:
    a.setflags(write=False)
    return a


@functools.lru_cache(maxsize=16)
def merge_population(pop: Population) -> _Merged:
    """Merge one population's tenant streams (cached per population:
    every cell that shares it, in a plan or across plans, reuses one
    merge). Runs inside the ``repro.fleet.resolve`` span.

    Scenario-bearing tenants are synthesized in ONE batched dispatch per
    distinct `ScenarioSpec` (`repro.workloads.scenarios.scenario_traces`
    over the tenant seed set — the same one-synthesis-per-spec contract
    as `repro.sim.plan.resolve_scenarios`, which is what keeps resolving
    a 1024-tenant population cheap).

    The merged stream is built by concatenating per-tenant streams in
    tenant order and stable-sorting by time, so equal-time arrivals keep
    tenant-index order — the documented cross-engine tie rule (both
    engines consume these exact arrays)."""
    tenants, seed = pop.tenants, pop.seed
    n = len(tenants)
    with TraceAnnotation("repro.fleet.resolve", tenants=n):
        streams: list = [None] * n
        sizes: list = [None] * n
        pending: dict = {}
        for i, spec in enumerate(tenants):
            if spec.arrival_times is not None:
                streams[i] = spec.arrival_times
                sizes[i] = float(spec.request_size_s)
            else:
                pending.setdefault(spec.scenario, []).append(i)
        if pending:
            from repro.workloads.scenarios import (scenario_arrivals,
                                                   scenario_traces)
            for sc, idxs in pending.items():
                seeds = sorted({seed + tenants[i].seed for i in idxs})
                by_seed = dict(zip(seeds, scenario_traces(sc, seeds)))
                for i in idxs:
                    spec = tenants[i]
                    s = seed + spec.seed
                    streams[i] = np.asarray(
                        scenario_arrivals(sc, s, _trace=by_seed[s]),
                        np.float64)
                    sizes[i] = float(spec.request_size_s
                                     if spec.request_size_s is not None
                                     else by_seed[s].request_size_s)
        n_per = [len(a) for a in streams]
        times = (np.concatenate(streams) if streams
                 else np.zeros(0, np.float64))
        tids = np.repeat(np.arange(len(streams), dtype=np.int32), n_per)
        order = np.argsort(times, kind="stable")
        times, tids = times[order], tids[order]

        sizes = np.asarray(sizes, np.float64)
        deadlines = sizes * np.array([t.deadline_mult for t in tenants],
                                     np.float64)
        weights = np.array([t.weight for t in tenants], np.float64)
        sc_h = [float(t.scenario.horizon_s) for t in tenants
                if t.scenario is not None]
        return _Merged(times=_frozen(times), tids=_frozen(tids),
                       sizes=_frozen(sizes), deadlines=_frozen(deadlines),
                       weights=_frozen(weights),
                       horizon_s=max(sc_h) if sc_h else None,
                       failures=frozenset(t.failures for t in tenants
                                          if t.failures is not None))


@functools.lru_cache(maxsize=64)
def resolve_fleet_cell(cell: FleetCell) -> ResolvedFleet:
    """Materialize one `FleetCell` (cached — cells are frozen/hashable,
    and the planner, the execution scatter and the oracle all re-resolve
    the same cells): the population's merged stream and tables
    (`merge_population`, shared by every cell of that population) plus
    the cell's own admission knobs, horizon and fault model."""
    m = merge_population(cell.population)
    rate, burst, quota = get_admission_policy(
        cell.admission).tenant_params(m.weights)

    if cell.horizon_s is not None:
        horizon = float(cell.horizon_s)
    elif m.horizon_s is not None:
        horizon = m.horizon_s
    else:
        horizon = float(m.times[-1] + 1.0) if len(m.times) else 1.0

    failures = cell.failures
    if failures is None:
        if len(m.failures) > 1:
            raise ValueError(
                "conflicting per-tenant FailureSpecs on one shared fleet "
                "(set FleetCell.failures to pick one)")
        failures = next(iter(m.failures)) if m.failures else None

    return ResolvedFleet(times=m.times, tids=m.tids, sizes=m.sizes,
                         deadlines=m.deadlines, weights=m.weights,
                         adm_rate=np.asarray(rate, np.float32),
                         adm_burst=np.asarray(burst, np.float32),
                         adm_quota=np.asarray(quota, np.float32),
                         horizon_s=horizon, failures=failures)
