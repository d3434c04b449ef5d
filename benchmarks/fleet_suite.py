"""Fleet suite: multi-tenant scaling x admission-policy degradation.

The closed-loop benchmark for the fleet layer (`repro.fleet`, the §2/§7
datacenter claims): Zipf-weighted tenant populations
(`repro.workloads.tenant_population`) of 16 -> 1024 tenants share ONE
FPGA+CPU fleet under each registered admission policy
(`repro.policies.admission`), entirely through the batched engine
(`repro.sim.sweep.sweep_fleet`).

Two built-in guards (asserted, not just recorded):

  * **dispatch budget** — the whole grid (4 population sizes x 3
    admission policies) must fit in ``MAX_SWEEP_DISPATCHES``: the
    admission policy is a *traced* code + per-tenant knob tables, so
    extra policies may not add compiled programs — only the padded
    (stream length, tenant count) shape pair does.
  * **tenant conservation** — `repro.sim.harness.check_fleet_result` on
    the full result: per-tenant `TenantTotals` rows must reconcile with
    each cell's fleet `RunTotals` (counters exactly, attribution to
    float rounding).

Rows record per-(n_tenants, admission) degradation: shed rate, deadline
miss rate, the worst per-tenant miss rate, the light-tenant (bottom
quartile by weight) shed rate vs the heavy-tenant one, and energy per
unit of served work — the fairness/SLO curves `results/BENCH_sweep.json`
tracks across PRs. Suite meta records the host CPU count: fleet scans
scale with cores, so wall times are only comparable at equal
``host_cpu_count``.

Fast mode: 60 s tenant horizons; full: 180 s at doubled per-tenant
demand on a worker table widened to 64 FPGA + 128 CPU slots (the
default 32 + 64 drops dispatches at 1024 tenants). Every cell must
report ``slot_overflow == 0``.
"""

from __future__ import annotations

import os
import sys
from typing import NamedTuple

# allow `python benchmarks/fleet_suite.py` from anywhere
_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
for _p in (_ROOT, os.path.join(_ROOT, "src")):
    if _p not in sys.path:
        sys.path.insert(0, _p)

import numpy as np

from repro.fleet import FleetCell
from repro.policies import admission_policy_names
from repro.sim.harness import check_fleet_result
from repro.sim.sweep import sweep_fleet
from repro.workloads import tenant_population

from benchmarks.common import FAST, record_kv

SCALES = (16, 64, 256, 1024)

# One compiled program per (padded stream length, padded tenant count,
# FailStatic) group: each population size contributes one shape pair and
# every admission policy rides the traced code axis, so 4 scales x 3
# policies plans into <= 4 groups. 8 is the acceptance ceiling.
MAX_SWEEP_DISPATCHES = 8


class FleetGrid(NamedTuple):
    cells: list
    horizon_s: float
    demand: float            # mean workers of demand per tenant
    w_fpga: int              # worker-table slots the grid needs
    w_cpu: int


def grid(fast: bool = FAST) -> FleetGrid:
    """Every population size under every admission policy. The worker
    table is sized to the grid's demand: full mode offers ~102 workers
    at 1024 tenants, which overflows the default 32 + 64 slots (dropped
    dispatches, ``slot_overflow > 0``), so it gets 64 + 128."""
    horizon_s = 60.0 if fast else 180.0
    demand = 0.05 if fast else 0.1
    pops = {n: tenant_population(n, horizon_s=horizon_s,
                                 mean_demand_workers=demand, seed=1)
            for n in SCALES}
    cells = [FleetCell(tenants=pops[n], admission=adm, tag=(n, adm))
             for n in SCALES for adm in admission_policy_names()]
    w_fpga, w_cpu = (32, 64) if fast else (64, 128)
    return FleetGrid(cells, horizon_s, demand, w_fpga, w_cpu)


def run() -> list[dict]:
    g = grid()
    cells, horizon_s, demand = g.cells, g.horizon_s, g.demand
    res = sweep_fleet(cells, w_fpga=g.w_fpga, w_cpu=g.w_cpu)
    assert res.n_dispatches <= MAX_SWEEP_DISPATCHES, (
        f"fleet grid took {res.n_dispatches} sweep dispatches "
        f"(> {MAX_SWEEP_DISPATCHES}) — did the admission policy leak "
        f"into a static group key?")
    check_fleet_result(res, where="fleet_suite")
    for i in range(len(res)):
        over = res.totals(i).breakdown["slot_overflow"]
        assert over == 0, f"fleet cell {res.cells[i].tag}: {over} " \
                          f"dispatches dropped (slot_overflow)"

    rows = []
    for i, cell in enumerate(res.cells):
        n, adm = cell.tag
        t = res.totals(i)
        tr = res.tenants(i)
        offered = t.breakdown["offered_requests"]
        shed = t.breakdown["shed_requests"]
        miss_rates = np.array([r.deadline_misses / max(r.admitted, 1)
                               for r in tr])
        weights = np.array([r.weight for r in tr])
        light = weights <= np.quantile(weights, 0.25)
        shed_rate = lambda m: (sum(r.shed for r, k in zip(tr, m) if k)
                               / max(sum(r.requests
                                         for r, k in zip(tr, m) if k), 1))
        served = t.work_on_fpga_cpu_s + t.work_on_cpu_cpu_s
        rows.append({
            "n_tenants": n, "admission": adm,
            "offered": offered, "shed": shed,
            "shed_rate": round(shed / max(offered, 1), 6),
            "miss_rate": round(t.deadline_misses / max(t.requests, 1), 6),
            "worst_tenant_miss_rate": round(float(miss_rates.max()), 6),
            "light_shed_rate": round(shed_rate(light), 6),
            "heavy_shed_rate": round(shed_rate(~light), 6),
            "j_per_served_s": round(t.energy_j / max(served, 1e-9), 3)})

    record_kv("fleet_suite_meta",
              scales=list(SCALES), admission=list(admission_policy_names()),
              horizon_s=horizon_s, mean_demand_workers=demand,
              worker_table=[g.w_fpga, g.w_cpu],
              sweep_dispatches=res.n_dispatches, sweep_cells=len(res),
              conservation_checked=True, fast=FAST,
              host_cpu_count=os.cpu_count(),
              backend=res.backend, n_devices=res.n_devices,
              dispatch_devices=res.dispatch_devices)
    return rows


if __name__ == "__main__":
    from benchmarks.common import emit, timed
    rows, t0 = timed(run)
    emit("fleet_suite", rows, t0)
