"""Table 8: production-workload stand-ins — all schedulers, both sources.

Azure-like and Alibaba-like app sets (core.traces; the real datasets are
not redistributable offline — see DESIGN.md §9), short and medium request
buckets, energy/cost/miss metrics aggregated across apps and normalized
per §5.1. Spork variants: E (energy), C (cost), B (balanced), + ideal.

All (source, bucket, scheduler, app) cells run through the batched sweep
engine — the Spork E/C/B variants differ only in the traced energy
weight, so they share one compiled program and dispatch together.
"""

from __future__ import annotations

from repro.core.metrics import RunTotals, report
from repro.core.traces import production_like_apps
from repro.core.workers import DEFAULT_FLEET
from repro.sim.sweep import SweepCell, sweep, tune_fpga_dynamic_cells

from benchmarks.common import FAST, fast_params

SCHEDULERS = [
    ("CPU-dynamic", "cpu_dynamic", {}),
    ("FPGA-static", "fpga_static", {}),
    ("FPGA-dynamic", "fpga_dynamic", {"tuned": True}),
    ("MArk-ideal", "mark_ideal", {}),
    ("SporkC", "spork", {"energy_weight": 0.0}),
    ("SporkB", "spork", {"energy_weight": 0.5}),
    ("SporkE", "spork", {"energy_weight": 1.0}),
    ("SporkE-ideal", "spork_ideal", {"energy_weight": 1.0}),
]


def grid(fast: bool = FAST, buckets=("short", "medium"),
         sources=("azure", "alibaba")):
    """(plain cells, tuned FPGA-dynamic cells, row order) of the Table-8
    grid: every (source, bucket, scheduler, app) cell. Full mode
    (``fast=False``) runs every Table-7 app over a 7200 s horizon."""
    _, horizon, n_apps = fast_params(fast)
    fleet = DEFAULT_FLEET

    # App trace batches up front, one set per (source, bucket).
    app_sets = {}
    for source in sources:
        for bucket in buckets:
            try:
                app_sets[(source, bucket)] = production_like_apps(
                    source, bucket, seed=1, horizon_s=horizon, n_apps=n_apps)
            except ValueError:
                continue

    plain, tuned, order = [], [], []
    for (source, bucket), apps in app_sets.items():
        for label, policy, kw in SCHEDULERS:
            order.append((source, bucket, label))
            for tr in apps:
                cell = SweepCell(policy, tr.counts, tr.request_size_s, fleet,
                                 energy_weight=kw.get("energy_weight", 1.0),
                                 tag=(source, bucket, label))
                (tuned if kw.get("tuned") else plain).append(cell)
    return plain, tuned, order


def run(buckets=("short", "medium"), sources=("azure", "alibaba")) -> list[dict]:
    fleet = DEFAULT_FLEET
    plain, tuned, order = grid(FAST, buckets, sources)

    merged: dict[tuple, RunTotals] = {}

    def add(tag, tot):
        merged[tag] = merged.setdefault(tag, RunTotals()).merge(tot)

    res = sweep(plain)
    for i, cell in enumerate(res.cells):
        add(cell.tag, res.totals(i))
    for (_, tot), cell in zip(tune_fpga_dynamic_cells(tuned), tuned):
        add(cell.tag, tot)

    rows = []
    for source, bucket, label in order:
        r = report(merged[(source, bucket, label)], fleet)
        rows.append({
            "source": source, "bucket": bucket, "scheduler": label,
            "energy_eff": round(r.energy_efficiency, 4),
            "rel_cost": round(r.relative_cost, 4),
            "miss_rate": round(r.deadline_miss_rate, 6),
            "cpu_frac": round(r.cpu_request_fraction, 4)})
    return rows


if __name__ == "__main__":
    for row in run():
        print(row)
