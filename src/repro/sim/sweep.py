"""One-dispatch parameter sweeps: thin wrappers over plan + execute.

The paper's headline results (Figs. 5-7, Tables 8-9) are parameter-space
sweeps: spin-up latency x burstiness x policy x trace seed x worker
parameters. Running each grid cell as its own `ratesim.simulate` (or
`events.simulate_events`) call pays a full JAX dispatch — and a re-jit
per new static shape — per cell. This module batches the grid instead,
as a **plan/execute** pipeline:

  * A `SweepCell` names one rate-simulator grid cell; an
    `repro.sim.events_batched.EventCell` names one DES cell. Cells may
    name their demand instead of carrying it (``scenario=spec,
    seed=k`` against the `repro.workloads` library).
  * `repro.sim.plan` turns any cell list into an explicit `SweepPlan`:
    scenario resolution, static-axis group keys, fixed-vocabulary chunk
    shapes, row-0 padding and result scatter indices — all host-side,
    all property-tested (tests/test_plan.py).
  * `repro.sim.exec` runs the plan on a pluggable backend:
    `LocalBackend` (single-device vmapped dispatches, bit-identical
    default) or `MeshBackend` (`shard_map` over the cell axis of a
    device mesh). ``backend=`` threads through every entry point here;
    None reads the ``BENCH_SWEEP_BACKEND`` env var.
  * `sweep` / `sweep_events` / `tune_fpga_dynamic_cells` below are the
    public entry points: plan, execute, and (for tuning) select — no
    private grouping/padding/dispatch loops of their own.

Equivalence: per-cell totals match per-call `ratesim.simulate` at the
same `n_max` to float32 tolerance (tests/test_sweep.py), and the DES
path matches the `events.EventSim` oracle per the contract in
docs/architecture.md.
"""

from __future__ import annotations

from dataclasses import dataclass, replace
from typing import Any, Iterable

import numpy as np

from repro.core.metrics import RunTotals
from repro.core.workers import DEFAULT_FLEET, FleetParams
from repro.sim.events_batched import EventCell
from repro.sim.exec import Backend, execute
from repro.sim.plan import (CHUNK, CHUNK_BIG, _N_MAX_CAP, EventSweepResult,
                            FleetSweepResult, SweepPlan, SweepResult,
                            plan_events, plan_fleet, plan_sweep,
                            resolve_scenarios)
from repro.sim.ratesim import headroom_unit

__all__ = [
    "SweepCell", "EventCell", "SweepResult", "EventSweepResult",
    "FleetSweepResult", "SweepPlan", "sweep", "sweep_events", "sweep_fleet",
    "tune_fpga_dynamic_cells", "resolve_scenarios", "CHUNK", "CHUNK_BIG",
]


@dataclass(frozen=True)
class SweepCell:
    """One grid cell of a parameter sweep.

    Demand comes either from explicit per-second ``counts`` (+ a scalar
    ``size_s``) or from a named workload scenario: pass
    ``scenario=ScenarioSpec(...), seed=k`` (`repro.workloads`) and leave
    ``counts`` as None — the planner synthesizes every scenario-bearing
    cell's counts (and, if ``size_s`` is None, its request size) in one
    batched device dispatch per spec before grouping, so scenario x
    policy x seed grids are first-class sweep axes."""

    policy: str
    counts: np.ndarray | None = None   # (T,) per-second arrival counts
    size_s: float | None = None        # request service time on a CPU worker
    fleet: FleetParams = DEFAULT_FLEET
    energy_weight: float = 1.0
    headroom: int = 0             # fpga_dynamic family only
    forecast_gain: float = 1.0    # predictive only: trend-extrapolation
                                  # gain (RateParams.gain)
    tag: Any = None               # caller's join key; carried through
    scenario: Any = None          # repro.workloads.ScenarioSpec | None
    seed: int = 0                 # scenario realization seed
    failures: Any = None          # repro.ft.failures.FailureSpec | None;
                                  # fluidized by plan_sweep (degrade_fleet)

    def __post_init__(self):
        """Fail-fast construction-time validation: malformed cells raise
        a clear ValueError here instead of an opaque XLA shape error deep
        inside `repro.sim.plan.plan_sweep`."""
        if self.counts is not None:
            c = np.asarray(self.counts)
            if c.ndim != 1:
                raise ValueError(
                    f"SweepCell.counts must be 1-D per-second counts, got "
                    f"shape {c.shape}")
            if c.size and (np.any(c < 0) or not np.all(np.isfinite(
                    c.astype(np.float64)))):
                raise ValueError(
                    "SweepCell.counts must be non-negative finite arrival "
                    "counts (negative rate injected?)")
        if self.size_s is not None and not (
                np.isfinite(self.size_s) and self.size_s > 0):
            raise ValueError(
                f"SweepCell.size_s must be a positive finite service "
                f"time, got {self.size_s!r}")
        if not np.isfinite(self.energy_weight):
            raise ValueError(
                f"SweepCell.energy_weight must be finite, got "
                f"{self.energy_weight!r}")
        if self.headroom < 0:
            raise ValueError(
                f"SweepCell.headroom must be >= 0, got {self.headroom!r}")
        if not np.isfinite(self.forecast_gain):
            raise ValueError(
                f"SweepCell.forecast_gain must be finite, got "
                f"{self.forecast_gain!r}")
        if np.ndim(self.seed) != 0:
            raise ValueError(
                f"SweepCell.seed must be a scalar (one seed per cell — "
                f"expand seed batches into cells), got shape "
                f"{np.shape(self.seed)}")


def sweep(cells: Iterable[SweepCell], n_max: int | None = None,
          backend: str | Backend | None = None,
          checkpoint_dir=None, retry=None) -> SweepResult:
    """Simulate every cell, one dispatch per (policy, interval, spin-up,
    horizon) group chunk. Cell order is preserved in the result.
    Scenario-bearing cells (``counts=None, scenario=spec``) are
    synthesized first, one batched dispatch per distinct spec.
    ``backend`` selects the `repro.sim.exec` execution backend
    (None -> ``BENCH_SWEEP_BACKEND`` env var -> local).

    ``checkpoint_dir`` makes the sweep resumable (each completed chunk
    is persisted; a killed run restarted with the same directory
    re-executes only unfinished chunks) and ``retry`` is a
    `repro.sim.harness.RetryPolicy` — see docs/architecture.md
    "Execution hardening"."""
    return execute(plan_sweep(cells, n_max=n_max), backend,
                   checkpoint_dir=checkpoint_dir, retry=retry)


def sweep_events(cells: Iterable[EventCell], n_max: int = 512,
                 w_fpga: int = 32, w_cpu: int = 64,
                 backend: str | Backend | None = None,
                 checkpoint_dir=None, retry=None) -> EventSweepResult:
    """Event-level (DES) cells in sweep grids.

    The exact discrete-event counterpart of `sweep`: every `EventCell`
    (dispatcher x arrival trace x fleet x objective) runs on the batched
    `repro.sim.events_batched` engine, packed by entry-stream length and
    vmapped, so a whole Table-9-style grid costs a handful of dispatches
    instead of one serial `events.simulate_events` loop per cell.

    Returns an `EventSweepResult`: cell-ordered totals (iterable /
    indexable like the bare list it replaced, or via ``.totals()``)
    plus the batching-contract metadata — ``n_dispatches``,
    ``backend``, ``n_devices``. Totals carry
    ``breakdown['slot_overflow']`` (always 0 when the worker-table
    regions are large enough — see the engine's equivalence contract in
    docs/architecture.md). Scenario-bearing cells
    (``arrival_times=None, scenario=spec``) get their arrival streams
    synthesized first, like `sweep`. ``checkpoint_dir`` / ``retry``
    harden execution exactly as in `sweep` (docs/architecture.md
    "Execution hardening").
    """
    plan = plan_events(cells, n_max=n_max, w_fpga=w_fpga, w_cpu=w_cpu)
    return execute(plan, backend, checkpoint_dir=checkpoint_dir, retry=retry)


def sweep_fleet(cells, n_max: int = 512, w_fpga: int = 32, w_cpu: int = 64,
                backend: str | Backend | None = None,
                checkpoint_dir=None, retry=None) -> FleetSweepResult:
    """Multi-tenant fleet cells (`repro.fleet.FleetCell`) in sweep grids.

    Each cell is N tenants sharing ONE fleet under one dispatch policy
    and one admission policy; the batched engine (`repro.fleet.engine`)
    carries the tenant axis inside the scan state, so a 1024-tenant x
    policy x seed grid is a handful of dispatches on either backend
    (benchmarks/fleet_suite.py asserts the budget). Returns a
    `FleetSweepResult`: cell-ordered fleet `RunTotals` (with
    ``breakdown['offered_requests']`` / ``['shed_requests']``) plus
    per-tenant `repro.core.metrics.TenantTotals` rows via
    ``.tenants(i)`` — conservation-checked against the fleet totals by
    the default-on invariant guards
    (`repro.sim.harness.check_fleet_result`). ``checkpoint_dir`` /
    ``retry`` harden execution exactly as in `sweep`."""
    plan = plan_fleet(cells, n_max=n_max, w_fpga=w_fpga, w_cpu=w_cpu)
    return execute(plan, backend, checkpoint_dir=checkpoint_dir, retry=retry)


def tune_fpga_dynamic_cells(cells: Iterable[SweepCell], max_k: int = 16,
                            n_max: int | None = None,
                            backend: str | Backend | None = None,
                            checkpoint_dir=None, retry=None,
                            ) -> list[tuple[int, RunTotals]]:
    """Batched §5.1 headroom tuning: expand every cell into all
    ``max_k + 1`` headroom levels, simulate them in one sweep, and pick
    the least level with zero deadline misses.

    The headroom unit is sized to the max consecutive-interval demand
    delta, so real traces tune at k <= ~2; the batch searches k <= max_k
    and falls back to the full serial-equivalent search
    (`ratesim.tune_fpga_dynamic`, k <= 32) for the rare cell still
    missing deadlines at max_k, matching the original loop's semantics
    without paying for 33 levels per cell up front."""
    from repro.sim.ratesim import tune_fpga_dynamic
    cells = resolve_scenarios(cells)
    K = max_k + 1
    units, expanded = [], []
    for c in cells:
        unit = headroom_unit(c.counts, c.size_s, c.fleet)
        units.append(unit)
        expanded.extend(replace(c, policy="fpga_dynamic", headroom=k * unit)
                        for k in range(K))
    res = sweep(expanded, n_max=n_max, backend=backend,
                checkpoint_dir=checkpoint_dir, retry=retry)
    misses = res.deadline_misses.reshape(len(cells), K)
    out = []
    for ci, c in enumerate(cells):
        zero = np.nonzero(misses[ci] == 0)[0]
        if len(zero):
            k = int(zero[0])
            out.append((k * units[ci], res.totals(ci * K + k)))
        else:
            out.append(tune_fpga_dynamic(c.counts, c.size_s, c.fleet,
                                         n_max=n_max or _N_MAX_CAP))
    return out
