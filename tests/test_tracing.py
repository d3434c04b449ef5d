"""The sweep path's spans and counters.

A small `sweep_events` grid runs under `jax.profiler.start_trace`; its
``repro.*`` spans must all appear, nest as the planner and executor call
each other, and carry one ``plan_id`` from planning to execution. The
plan's counters (`SweepPlan.meta`, copied into the result's ``meta``)
must equal direct counts from the dispatch arrays, and the three fill
factors must multiply to the share of arrival slots the dispatches fill.
"""

import glob
from collections import namedtuple

import jax
import numpy as np
import pytest

from repro.core.traces import synthetic_trace
from repro.core.workers import DEFAULT_FLEET
from repro.fleet import FleetCell, TenantSpec
from repro.sim.plan import plan_events, plan_fleet, plan_sweep
from repro.sim.sweep import EventCell, SweepCell, sweep_events

Span = namedtuple("Span", "name start end stats line")

PER_CALL = ("repro.plan", "repro.plan.resolve", "repro.plan.entries",
            "repro.plan.pack", "repro.exec", "repro.harness.guards")
PER_DISPATCH = ("repro.exec.dispatch", "repro.exec.run",
                "repro.exec.transfer", "repro.exec.fetch",
                "repro.exec.scatter")
SMALL = dict(n_max=64, w_fpga=16, w_cpu=32)


def event_cells(horizon=60.0):
    # two stream lengths, so two entry widths E and two dispatches
    rng = np.random.default_rng(5)
    streams = [np.sort(rng.uniform(0.0, horizon, n)) for n in (40, 1500)]
    return [EventCell(d, t, 0.05, DEFAULT_FLEET, horizon_s=horizon)
            for t in streams
            for d in ("spork", "round_robin", "index_packing")]


def fleet_cells():
    rng = np.random.default_rng(6)
    tenants = tuple(
        TenantSpec(arrival_times=tuple(np.sort(rng.integers(0, 480, n)) / 8.0),
                   request_size_s=0.125, seed=i)
        for i, n in enumerate((30, 300)))
    return [FleetCell(tenants=tenants[:k], admission=a, horizon_s=60.0)
            for k in (1, 2)
            for a in ("admit_all", "token_bucket", "interval_quota")]


def rate_cells():
    tr = synthetic_trace(seed=0, horizon_s=400, request_size_s=0.05,
                         mean_demand_workers=20.0)
    return [SweepCell(p, tr.counts, 0.05, DEFAULT_FLEET)
            for p in ("spork", "cpu_dynamic", "fpga_static")]


def read_spans(trace_dir) -> list[Span]:
    (path,) = glob.glob(f"{trace_dir}/**/*.xplane.pb", recursive=True)
    pd = jax.profiler.ProfileData.from_file(path)
    return [Span(e.name, e.start_ns, e.start_ns + e.duration_ns,
                 dict(e.stats), (plane.name, li))
            for plane in pd.planes for li, line in enumerate(plane.lines)
            for e in line.events if e.name.startswith("repro.")]


def traced(trace_dir, fn):
    jax.profiler.start_trace(str(trace_dir))
    try:
        out = fn()
    finally:
        jax.profiler.stop_trace()
    return out, read_spans(trace_dir)


@pytest.fixture(scope="module")
def grid(tmp_path_factory):
    cells = event_cells()
    sweep_events(cells, **SMALL)       # compile outside the trace
    res, spans = traced(tmp_path_factory.mktemp("trace"),
                        lambda: sweep_events(cells, **SMALL))
    return cells, res, spans


def named(spans, name):
    return [s for s in spans if s.name == name]


def inside(child: Span, parent: Span) -> bool:
    return (child.line == parent.line and parent.start <= child.start
            and child.end <= parent.end)


def test_every_span_appears_once_per_phase_or_dispatch(grid):
    cells, res, spans = grid
    assert res.n_dispatches == 2
    for name in PER_CALL:
        assert len(named(spans, name)) == 1, name
    for name in PER_DISPATCH:
        assert len(named(spans, name)) == res.n_dispatches, name
    # no checkpoint directory, no checkpoint span
    assert not named(spans, "repro.harness.checkpoint")
    dispatch = named(spans, "repro.exec.dispatch")
    planned = plan_events(cells, **SMALL).dispatches
    assert [s.stats["chunk"] for s in dispatch] == [0, 1]
    assert [(s.stats["rows"], s.stats["E"]) for s in dispatch] == [
        (d.chunk, d.arrays["times"].shape[1]) for d in planned]


def test_spans_nest(grid):
    _, _, spans = grid
    (plan,) = named(spans, "repro.plan")
    (exe,) = named(spans, "repro.exec")
    for name in ("repro.plan.resolve", "repro.plan.entries",
                 "repro.plan.pack"):
        assert inside(named(spans, name)[0], plan), name
    assert plan.end <= exe.start
    dispatches = named(spans, "repro.exec.dispatch")
    for d in dispatches:
        assert inside(d, exe)
        (run,) = [s for s in named(spans, "repro.exec.run") if inside(s, d)]
        (fetch,) = [s for s in named(spans, "repro.exec.fetch")
                    if inside(s, d)]
        assert run.end <= fetch.start
        assert [s for s in named(spans, "repro.exec.transfer")
                if inside(s, run)]
    for s in named(spans, "repro.exec.scatter") + named(
            spans, "repro.harness.guards"):
        assert inside(s, exe)
        assert not any(inside(s, d) for d in dispatches)


PHASES = {"event": ("resolve", "entries", "pack"),
          "fleet": ("resolve", "entries", "pack"),
          "rate": ("resolve", "pack")}


@pytest.mark.parametrize("kind", list(PHASES))
def test_plan_phases_nest_in_order(tmp_path, kind):
    plan, spans = traced(tmp_path, PLANS[kind])
    (top,) = named(spans, "repro.plan")
    assert top.stats == {"plan_id": plan.meta["plan_id"],
                         "cells": len(plan.cells)}
    phases = sorted((s for s in spans if s.name.startswith("repro.plan.")),
                    key=lambda s: s.start)
    assert [s.name for s in phases] == [f"repro.plan.{p}"
                                        for p in PHASES[kind]]
    assert all(inside(s, top) for s in phases)
    assert all(a.end <= b.start for a, b in zip(phases, phases[1:]))


def test_plan_id_ties_plan_and_exec_spans(grid):
    cells, res, spans = grid
    (plan,) = named(spans, "repro.plan")
    (exe,) = named(spans, "repro.exec")
    pid = plan.stats["plan_id"]
    assert pid == res.meta["plan_id"] > 0
    assert plan.stats["cells"] == len(cells)
    assert exe.stats == {"plan_id": pid, "dispatches": res.n_dispatches}
    for name in ("repro.exec.dispatch", "repro.exec.run"):
        assert {s.stats["plan_id"] for s in named(spans, name)} == {pid}


def test_result_meta_carries_the_plan_counters(grid):
    cells, res, _ = grid
    plan = plan_events(cells, **SMALL)
    want = {k: v for k, v in plan.meta.items() if k != "plan_id"}
    assert set(want) == {"cells", "rows", "h2d_bytes", "row_entries",
                         "entries_scanned", "entries", "arrivals"}
    assert {k: res.meta[k] for k in want} == want
    assert res.meta["executed_chunks"] == res.n_dispatches


def test_checkpointed_sweep_traces_its_store(tmp_path):
    cells = event_cells()[:3]
    for run in ("first", "again"):
        res, spans = traced(tmp_path / f"trace-{run}",
                            lambda: sweep_events(cells, checkpoint_dir=(
                                tmp_path / "ckpt"), **SMALL))
        ckpt = named(spans, "repro.harness.checkpoint")
        dispatches = named(spans, "repro.exec.dispatch")
        # a lookup per dispatch, and a save per dispatch that ran
        per = 2 if run == "first" else 1
        assert len(ckpt) == per * res.n_dispatches
        assert all(any(inside(c, d) for d in dispatches) for c in ckpt)
        if run == "again":
            assert res.meta["restored_chunks"] == res.n_dispatches
            assert not named(spans, "repro.exec.run")


# ----------------------------------------------------------- counters
def _entries_of_row(times_row, is_tick_row) -> int:
    """Entries of one real row, counted from its arrays: every entry up
    to the last interval tick, then the tail's blocks (at least one)."""
    last_tick = int(np.nonzero(is_tick_row)[0].max())
    tail = int(np.isfinite(times_row[last_tick + 1:]).any(axis=1).sum())
    return last_tick + 1 + max(tail, 1)


def direct(plan) -> dict:
    """Every counter, counted from the dispatch arrays alone."""
    ds = plan.dispatches
    out = {"cells": sum(len(d.cell_idx) for d in ds),
           "rows": sum(d.chunk for d in ds),
           "h2d_bytes": sum(a.nbytes for d in ds for a in d.arrays.values())}
    if plan.kind == "rate":
        return out
    if plan.kind == "fleet":
        out.update(
            tenants=sum(len(c.tenants) for c in plan.cells),
            tenant_slots=sum(d.n_real * d.arrays["ta_size"].shape[1]
                             for d in ds),
            populations=len({(c.tenants, c.seed) for c in plan.cells}))
    real = [(d.arrays["times"][:d.n_real], d.arrays["is_tick"][:d.n_real])
            for d in ds]
    out.update(
        row_entries=sum(t.shape[0] * t.shape[1] for t, _ in real),
        entries_scanned=sum(d.arrays["times"].shape[0]
                            * d.arrays["times"].shape[1] for d in ds),
        entries=sum(_entries_of_row(t[r], k[r])
                    for t, k in real for r in range(len(t))),
        arrivals=sum(int(np.isfinite(t).sum()) for t, _ in real))
    return out


PLANS = {"event": lambda: plan_events(event_cells(), **SMALL),
         "fleet": lambda: plan_fleet(fleet_cells(), **SMALL),
         "rate": lambda: plan_sweep(rate_cells())}
ENTRY_COUNTERS = ("cells", "rows", "row_entries", "entries_scanned",
                  "entries", "arrivals", "h2d_bytes")
TENANT_COUNTERS = ("tenants", "tenant_slots", "populations")


@pytest.fixture(scope="module")
def plans():
    return {k: f() for k, f in PLANS.items()}


@pytest.mark.parametrize("kind,counter", [
    *(("event", c) for c in ENTRY_COUNTERS),
    *(("fleet", c) for c in ENTRY_COUNTERS),
    *(("fleet", c) for c in TENANT_COUNTERS),
    *(("rate", c) for c in ("cells", "rows", "h2d_bytes"))])
def test_counter_equals_a_direct_count(plans, kind, counter):
    plan = plans[kind]
    assert plan.meta[counter] == direct(plan)[counter]
    assert type(plan.meta[counter]) is int


def test_event_plans_count_no_tenants(plans):
    assert not set(TENANT_COUNTERS) & set(plans["event"].meta)


def test_population_merge_nests_in_plan_resolve(tmp_path):
    """Each distinct population is merged once, inside the planner's
    resolve phase; the shed counter is the cells' shed requests."""
    from dataclasses import replace

    from repro.fleet.specs import merge_population, resolve_fleet_cell
    from repro.policies.admission import TokenBucket
    from repro.sim.sweep import sweep_fleet
    starved = TokenBucket(rate=0.5, burst=2.0)
    cells = [replace(c, admission=starved)
             if c.admission == "token_bucket" else c for c in fleet_cells()]
    resolve_fleet_cell.cache_clear()
    merge_population.cache_clear()
    res, spans = traced(tmp_path, lambda: sweep_fleet(cells, **SMALL))
    (resolve,) = named(spans, "repro.plan.resolve")
    merges = named(spans, "repro.fleet.resolve")
    assert len(merges) == res.meta["populations"] == 2
    assert all(inside(m, resolve) for m in merges)
    assert sorted(m.stats["tenants"] for m in merges) == [1, 2]
    shed = [res.totals(i).breakdown["shed_requests"]
            for i in range(len(cells))]
    assert res.meta["shed_requests"] == sum(shed) > 0
    assert sum(shed) == sum(r.shed for i in range(len(cells))
                            for r in res.tenants(i))
    assert res.meta["tenants"] == sum(len(c.tenants) for c in cells)


def test_rate_plans_count_no_entries(plans):
    assert set(plans["rate"].meta) == {"plan_id", "cells", "rows",
                                       "h2d_bytes"}


@pytest.mark.parametrize("kind", ["event", "fleet"])
def test_fills_multiply_to_lane_fill(plans, kind):
    m = plans[kind].meta
    row = m["row_entries"] / m["entries_scanned"]
    entry = m["entries"] / m["row_entries"]
    block = m["arrivals"] / (128 * m["entries"])
    ds = plans[kind].dispatches
    lane = (sum(int(np.isfinite(d.arrays["times"][:d.n_real]).sum())
                for d in ds)
            / sum(d.arrays["times"].size for d in ds))
    assert 0 < row <= 1 and 0 < entry <= 1 and 0 < block <= 1
    assert row < 1 and block < 1        # the grid pads rows and blocks
    assert row * entry * block == pytest.approx(lane, rel=1e-12)


def test_plan_ids_count_up():
    a, b = (plan_events(event_cells()[:1], **SMALL) for _ in range(2))
    assert b.meta["plan_id"] == a.meta["plan_id"] + 1
