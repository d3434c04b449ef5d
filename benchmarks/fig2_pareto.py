"""Fig. 2 + Fig. 3: idealized (perfect-information) scheduling study.

Energy-/cost-optimal allocations for CPU-only, FPGA-only, and hybrid
platforms across workload burstiness, via the min-plus DP (exact MILP
equivalent at T_s = A_f; tests/test_milp.py), normalized to the idealized
FPGA-only platform. --pareto adds the Fig. 3 weighted-objective front.

The (bias, seed, platform, objective) grid is solved with
`core.dp.solve_dp_batch`: work traces are generated up front and each
platform group (static `allow_cpu`/`allow_fpga` axes) runs every
(trace, weight) cell in one vmapped min-plus dispatch — including the
ten pareto weights — instead of one `solve_dp` call per cell.

The DP runs on the structured O(N log N) min-plus transition (the
`transition="structured"` backend; monotone segment decomposition, see
core.dp), which removed this suite's O(N^2)-per-interval compute wall:
~56s -> well under the 30s CI ceiling in fast mode. Set
BENCH_TRANSITION=dense to benchmark the dense O(N^2) oracle transition.
"""

from __future__ import annotations

import os

import numpy as np

from repro.core.bmodel import bmodel_rates_np
from repro.core.dp import PARETO_WEIGHTS, solve_dp_batch
from repro.core.metrics import report
from repro.core.workers import DEFAULT_FLEET

from benchmarks.common import fast_params

PLATFORMS = (("hybrid", dict()),
             ("cpu_only", dict(allow_fpga=False)),
             ("fpga_only", dict(allow_cpu=False)))

TRANSITION = os.environ.get("BENCH_TRANSITION", "structured")


def interval_work(seed: int, bias: float, horizon_s: int,
                  size_s: float = 0.01, mean_rate: float = 10_000.0,
                  interval_s: float = 10.0) -> np.ndarray:
    """Per-interval CPU-seconds of demand (paper §3: 10ms requests at
    10k req/s mean)."""
    rates = bmodel_rates_np(seed, bias, horizon_s, mean_rate)
    k = int(len(rates) // interval_s)
    per_s = np.random.default_rng(seed).poisson(np.maximum(rates, 0))
    return (per_s[:int(k * interval_s)].reshape(k, int(interval_s)).sum(1)
            * size_s)


def run(pareto: bool = False) -> list[dict]:
    n_traces, horizon, _ = fast_params()
    fleet = DEFAULT_FLEET.replace(max_fpgas=2048, max_cpus=10 ** 6)
    biases = (0.5, 0.55, 0.6, 0.65, 0.7, 0.75)

    # Work-trace batch up front; one array per (bias, seed).
    work = {(bias, seed): interval_work(seed, bias, horizon)
            for bias in biases for seed in range(n_traces)}

    # Assemble every DP cell, grouped by the static platform axes.
    cells: dict[str, list] = {name: [] for name, _ in PLATFORMS}
    for bias in biases:
        for seed in range(n_traces):
            for platform, _ in PLATFORMS:
                for oname, ew in (("energy", 1.0), ("cost", 0.0)):
                    cells[platform].append(
                        ((bias, platform, oname), work[(bias, seed)], ew))
        if pareto:
            for w in PARETO_WEIGHTS:
                cells["hybrid"].append(
                    ((bias, "hybrid-pareto", f"w={w:.3f}"),
                     work[(bias, 0)], float(w)))

    # One batched dispatch per platform group.
    results: dict[tuple, list] = {}
    for platform, kw in PLATFORMS:
        group = cells[platform]
        sols = solve_dp_batch(np.stack([w for _, w, _ in group]), fleet,
                              [ew for _, _, ew in group],
                              transition=TRANSITION, **kw)
        for (tag, _, _), sol in zip(group, sols):
            r = report(sol.totals, fleet)
            results.setdefault(tag, []).append(
                (r.energy_efficiency, r.relative_cost))

    rows = []
    for bias in biases:
        for platform, _ in PLATFORMS:
            for oname in ("energy", "cost"):
                vals = results[(bias, platform, oname)]
                rows.append({"bias": bias, "platform": platform,
                             "objective": oname,
                             "energy_eff": round(float(np.mean([v[0] for v in vals])), 4),
                             "rel_cost": round(float(np.mean([v[1] for v in vals])), 4)})
        if pareto:
            for w in PARETO_WEIGHTS:
                (e, c), = results[(bias, "hybrid-pareto", f"w={w:.3f}")]
                rows.append({"bias": bias, "platform": "hybrid-pareto",
                             "objective": f"w={w:.3f}",
                             "energy_eff": round(e, 4),
                             "rel_cost": round(c, 4)})
    return rows


if __name__ == "__main__":
    for row in run(pareto=True):
        print(row)
