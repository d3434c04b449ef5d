"""A FaaS partition's shared pool through `repro.sim.sweep.sweep_fleet`.

The configuration describes populations of apps (tenants) with the
Azure Functions 2019 trace's skew: per-app rates from a log-normal fit,
scaled together to the partition's invocations per second; per-app
request sizes from the trace's log-normal of execution times, resampled
into bounds; one b-model stream per app at its mean rate. A grid is
populations x dispatchers x admissions cells, each a `FleetCell` whose
tenants share one fleet; cells of one population share its tenants.

A traffic file may set ``subset`` (lists of ``populations`` indices,
``dispatchers``, ``admissions`` names: the grid keeps only those) and
``horizon_s`` (replaces the configuration's), besides its ``why``. Any
other key is refused, so a knob this module does not implement cannot
pass unseen.

A shed request is one the user saw fail: `answer` and `reference_of`
count it as a missed deadline, and every offered request as a request.
"""

from __future__ import annotations

from dataclasses import dataclass

import numpy as np

from perfbench.engines.events import fleet_params, interval_s
from perfbench.lib import generators as gen
from perfbench.lib import reference_fleet as ref


@dataclass
class Grid:
    """One realization of the grid: the program's cells and, per cell,
    what the reference needs."""

    cells: list
    inputs: list          # per cell: merged (times, tids), then per app
    #                       (sizes, deadlines, weights), dispatcher and
    #                       admission entry
    labels: list          # per cell: a short name
    arrivals: int         # simulated arrivals the grid submits
    horizon_s: float


@dataclass
class Population:
    """One population's base demand: per-app mean rates (1/s) and sizes
    (s), and its per-second counts as (app, second, count) triples of
    the nonzero seconds, apps in order."""

    rates: np.ndarray
    sizes: np.ndarray
    app: np.ndarray
    second: np.ndarray
    count: np.ndarray


TRAFFIC_KEYS = {"why", "subset", "horizon_s"}
SUBSET_KEYS = {"populations", "dispatchers", "admissions"}


def _axes(config: dict, traffic: dict):
    sub = traffic.get("subset") or {}
    unknown = (set(traffic) - TRAFFIC_KEYS) | (set(sub) - SUBSET_KEYS)
    if unknown:
        raise ValueError(f"traffic keys not implemented: {sorted(unknown)}")
    pops = [p for p in range(config["populations"])
            if "populations" not in sub or p in sub["populations"]]
    disps = [d for d in config["dispatchers"]
             if "dispatchers" not in sub or d in sub["dispatchers"]]
    adms = [a for a in config["admissions"]
            if "admissions" not in sub or a["name"] in sub["admissions"]]
    return pops, disps, adms


def horizon(config: dict, traffic: dict) -> int:
    return int(traffic.get("horizon_s") or config["horizon_s"])


def app_rates(config: dict, rng: np.random.Generator) -> np.ndarray:
    """Per-app mean rates (1/s): log10(invocations per minute) from the
    configuration's normal fit, scaled together to its total."""
    fit = config["rate_fit"]
    per_min = 10.0 ** rng.normal(fit["log10_per_min_mu"],
                                 fit["log10_per_min_sigma"],
                                 config["tenants"])
    return per_min * (config["invocations_per_s"] / per_min.sum())


def app_sizes(config: dict, rng: np.random.Generator) -> np.ndarray:
    """Per-app request sizes (s): the log-normal law, each draw outside
    [min_s, max_s] drawn again."""
    law = config["size_law"]
    out = np.empty(config["tenants"])
    todo = np.arange(len(out))
    while len(todo):
        s = np.exp(rng.normal(law["ln_s_mu"], law["ln_s_sigma"], len(todo)))
        ok = (s >= law["min_s"]) & (s <= law["max_s"])
        out[todo[ok]] = s[ok]
        todo = todo[~ok]
    return out


def population(config: dict, p: int, horizon_s: int) -> Population:
    """Population ``p``'s apps and base counts, from its seed alone."""
    seed = config["population_seed0"] + p
    rates = app_rates(config, gen.rng_for(seed, 0))
    sizes = app_sizes(config, gen.rng_for(seed, 1))
    st = config["stream"]
    app, second, count = [], [], []
    for i, r in enumerate(rates):
        c = gen.base_counts(st["kind"], st, (seed << 20) + i, horizon_s,
                            1.0, float(r))
        nz = np.flatnonzero(c)
        app.append(np.full(len(nz), i, np.int32))
        second.append(nz)
        count.append(c[nz])
    return Population(rates, sizes, np.concatenate(app),
                      np.concatenate(second), np.concatenate(count))


def base(config: dict, traffic: dict) -> list:
    """Every population of the grid, in grid order."""
    pops, _, _ = _axes(config, traffic)
    H = horizon(config, traffic)
    return [population(config, p, H) for p in pops]


def realize_population(pop: Population, shift_s: int, horizon_s: int,
                       rng: np.random.Generator):
    """Arrival times of one realization, app by app: every app's counts
    rotated by the same ``shift_s`` seconds, each arrival uniform in
    (s, s + 1] of its second. Returns (times, bounds): app i's sorted
    stream is ``times[bounds[i]:bounds[i + 1]]``; ``times`` is
    read-only."""
    app = np.repeat(pop.app, pop.count)
    sec = np.repeat((pop.second + shift_s) % horizon_s, pop.count)
    t = sec + (1.0 - rng.random(len(sec)))
    times = t[np.lexsort((t, app))]
    times.setflags(write=False)
    bounds = np.searchsorted(app, np.arange(len(pop.rates) + 1))
    return times, bounds


def merge(times: np.ndarray, bounds: np.ndarray):
    """The tenant-tagged stream: app streams concatenated in app order,
    stably sorted by time (equal times keep app order)."""
    tids = np.repeat(np.arange(len(bounds) - 1, dtype=np.int32),
                     np.diff(bounds))
    order = np.argsort(times, kind="stable")
    return times[order], tids[order]


def admission_policy(a: dict):
    from repro.policies.admission import TokenBucket
    if a["name"] == "admit_all":
        return "admit_all"
    if a["name"] == "token_bucket":
        return TokenBucket(rate=a["rate"], burst=a["burst"])
    raise ValueError(f"admission {a['name']!r} not implemented")


def realize(config: dict, traffic: dict, streams: list, seed: int,
            k: int) -> Grid:
    """Grid ``k`` of run ``seed``: every population rotated as a whole by
    a whole number of intervals and re-placed within its seconds, so the
    merged per-interval counts, and the plan's shapes, are the same on
    every grid."""
    from repro.fleet import SLO_CLASSES, FleetCell, TenantSpec
    pops, disps, adms = _axes(config, traffic)
    H = horizon(config, traffic)
    T = interval_s(config)
    fleet = fleet_params(config)
    dl_mult = config["deadline_mult"]
    (slo,) = [name for name, m in SLO_CLASSES.items() if m == dl_mult]
    cells, inputs, labels = [], [], []
    for p, pop in zip(pops, streams):
        rng = gen.rng_for(seed, k, p)
        times, bounds = realize_population(
            pop, T * int(rng.integers(H // T)), H, rng)
        tenants = tuple(
            TenantSpec(arrival_times=times[bounds[i]:bounds[i + 1]],
                       request_size_s=float(pop.sizes[i]), slo=slo,
                       weight=float(pop.rates[i]))
            for i in range(len(pop.rates)))
        merged = merge(times, bounds)
        for d in disps:
            for a in adms:
                cells.append(FleetCell(tenants=tenants, dispatcher=d,
                                       admission=admission_policy(a),
                                       fleet=fleet,
                                       energy_weight=config["energy_weight"],
                                       horizon_s=float(H)))
                inputs.append((*merged, pop.sizes, dl_mult * pop.sizes,
                               pop.rates, d, a))
                labels.append(f"pop{p}/{d}/{a['name']}")
    return Grid(cells, inputs, labels,
                int(sum(len(i[0]) for i in inputs)), float(H))


def plan(config: dict, grid: Grid):
    from repro.sim.plan import plan_fleet
    return plan_fleet(grid.cells, n_max=config["n_max"],
                      w_fpga=config["w_fpga"], w_cpu=config["w_cpu"])


def submit(config: dict, grid: Grid):
    """The user's call: one grid through `sweep_fleet`, totals out."""
    from repro.sim.sweep import sweep_fleet
    return sweep_fleet(grid.cells, n_max=config["n_max"],
                       w_fpga=config["w_fpga"], w_cpu=config["w_cpu"])


def answer(result, i: int) -> dict:
    t = result.totals(i)
    shed = t.breakdown["shed_requests"]
    return {"requests": t.breakdown["offered_requests"],
            "deadline_misses": t.deadline_misses + shed,
            "fpga_spinups": t.fpga_spinups, "cpu_spinups": t.cpu_spinups,
            "energy_j": t.energy_j, "cost_usd": t.cost_usd,
            "slot_overflow": t.breakdown.get("slot_overflow", 0)}


def reference_of(config: dict, inputs: tuple, horizon_s: float,
                 precision: str = "float64") -> dict:
    """The reference's totals for one cell's inputs (`Grid.inputs`); run
    by `perfbench.lib.compare.references` in worker processes."""
    times, tids, sizes, deadlines, weights, disp, adm = inputs
    fl = ref.Fleet.from_config(config["fleet"])
    t = ref.FleetDes(fl, sizes, deadlines, weights, adm, disp,
                     config["n_max"], precision).run_tagged(
        times, tids, horizon_s)
    return {"requests": t.requests, "deadline_misses": t.deadline_misses,
            "fpga_spinups": t.fpga_spinups, "cpu_spinups": t.cpu_spinups,
            "energy_j": t.energy_j, "cost_usd": t.cost_usd}
