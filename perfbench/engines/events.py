"""DES grids (a Table-9-style evaluation) through `repro.sim.sweep.sweep_events`.

Every stream of the configuration is one (case, app) trace, shared by
all dispatchers as in the paper's ablation: a grid is cases x apps x
dispatchers cells, each an `EventCell` with explicit arrival times.

A traffic file may set ``subset`` (lists of ``cases`` labels, ``apps``
indices, ``dispatchers``: the grid keeps only those) and ``horizon_s``
(replaces the configuration's), besides its ``why``. Any other key is
refused, so a knob this module does not implement cannot pass unseen.
"""

from __future__ import annotations

from dataclasses import dataclass

from perfbench.lib import generators as gen
from perfbench.lib import reference as ref


@dataclass
class Grid:
    """One realization of the grid: the program's cells and, per cell,
    what the reference needs."""

    cells: list
    inputs: list          # per cell: (times, size_s, deadline_s, dispatcher)
    labels: list          # per cell: a short name
    arrivals: int         # simulated arrivals the grid submits
    horizon_s: float


TRAFFIC_KEYS = {"why", "subset", "horizon_s"}
SUBSET_KEYS = {"cases", "apps", "dispatchers"}


def _axes(config: dict, traffic: dict):
    sub = traffic.get("subset") or {}
    unknown = (set(traffic) - TRAFFIC_KEYS) | (set(sub) - SUBSET_KEYS)
    if unknown:
        raise ValueError(f"traffic keys not implemented: {sorted(unknown)}")
    cases = [c for c in config["cases"]
             if "cases" not in sub or c["label"] in sub["cases"]]
    apps = [a for a in range(config["apps_per_case"])
            if "apps" not in sub or a in sub["apps"]]
    disps = [d for d in config["dispatchers"]
             if "dispatchers" not in sub or d in sub["dispatchers"]]
    return cases, apps, disps


def horizon(config: dict, traffic: dict) -> int:
    return int(traffic.get("horizon_s") or config["horizon_s"])


def fleet_params(config: dict):
    from repro.core.workers import FleetParams, WorkerSpec
    f = config["fleet"]
    return FleetParams(cpu=WorkerSpec(name="cpu", **f["cpu"]),
                       fpga=WorkerSpec(name="fpga", **f["fpga"]),
                       interval_s=f["interval_s"],
                       cpu_idle_timeout_s=f["cpu_idle_timeout_s"],
                       max_fpgas=f["max_fpgas"])


def interval_s(config: dict) -> int:
    return int(ref.Fleet.from_config(config["fleet"]).T_s)


def base(config: dict, traffic: dict) -> list:
    """Per-second base counts of every stream, in grid order."""
    cases, apps, _ = _axes(config, traffic)
    H = horizon(config, traffic)
    return [gen.base_counts(c["kind"], c, config["app_seed0"] + a, H,
                            c["size_s"], config["mean_demand_workers"])
            for c in cases for a in apps]


def realize(config: dict, traffic: dict, streams: list, seed: int,
            k: int) -> Grid:
    """Grid ``k`` of run ``seed``: every stream rotated by a whole number
    of intervals and re-placed within its seconds."""
    from repro.sim.sweep import EventCell
    cases, apps, disps = _axes(config, traffic)
    H = horizon(config, traffic)
    T = interval_s(config)
    fleet = fleet_params(config)
    cells, inputs, labels = [], [], []
    s = 0
    for c in cases:
        for a in apps:
            rng = gen.rng_for(seed, k, s)
            times = gen.realize_times(streams[s], T * int(rng.integers(H // T)),
                                      rng)
            s += 1
            dl = config["deadline_mult"] * c["size_s"]
            for d in disps:
                cells.append(EventCell(d, times, c["size_s"], fleet,
                                       energy_weight=config["energy_weight"],
                                       horizon_s=float(H)))
                inputs.append((times, c["size_s"], dl, d))
                labels.append(f"{c['label']}/app{a}/{d}")
    return Grid(cells, inputs, labels,
                int(sum(len(i[0]) for i in inputs)), float(H))


def plan(config: dict, grid: Grid):
    from repro.sim.plan import plan_events
    return plan_events(grid.cells, n_max=config["n_max"],
                       w_fpga=config["w_fpga"], w_cpu=config["w_cpu"])


def submit(config: dict, grid: Grid):
    """The user's call: one grid through `sweep_events`, totals out."""
    from repro.sim.sweep import sweep_events
    return sweep_events(grid.cells, n_max=config["n_max"],
                        w_fpga=config["w_fpga"], w_cpu=config["w_cpu"])


def answer(result, i: int) -> dict:
    t = result.totals(i)
    return {"requests": t.requests, "deadline_misses": t.deadline_misses,
            "fpga_spinups": t.fpga_spinups, "cpu_spinups": t.cpu_spinups,
            "energy_j": t.energy_j, "cost_usd": t.cost_usd,
            "slot_overflow": t.breakdown.get("slot_overflow", 0)}


def reference_of(config: dict, inputs: tuple, horizon_s: float,
                 precision: str = "float64") -> dict:
    """The reference's totals for one cell's inputs (`Grid.inputs`); run
    by `perfbench.lib.compare.references` in worker processes."""
    times, size, dl, disp = inputs
    fl = ref.Fleet.from_config(config["fleet"])
    t = ref.Des(fl, size, dl, disp, config["n_max"], precision).run(
        times, horizon_s)
    return {"requests": t.requests, "deadline_misses": t.deadline_misses,
            "fpga_spinups": t.fpga_spinups, "cpu_spinups": t.cpu_spinups,
            "energy_j": t.energy_j, "cost_usd": t.cost_usd}

