"""The comparison that decides ``correct``.

After the window, every cell of one grid that the window completed,
drawn from the seed, is run again on the plain reference
(`perfbench.lib.reference`), in worker processes that never touch the
chip. A whole grid covers every dispatch and every lane of the vmapped
chunks the planner builds. Each number has its own limit in the
configuration file (``correct.limits``); a configuration holds only the
numbers that separate its sound runs from its control.

Per cell, the worst over the grid's cells:

* ``energy_rel``, ``cost_rel``: |program - reference| / reference.
* ``spinup_rel``: |FPGA spin-ups gap| + |CPU spin-ups gap| over the
  reference's spin-ups.
* ``miss_share``: |deadline-miss gap| over the reference's requests.

Over the grid, each total summed over its cells first:
``grid_energy_rel``, ``grid_cost_rel``, ``grid_spinup_rel``. One cell's
totals can swing by several percent when float32 and float64 rounding
send it down different paths (a worker that idles out on one side and
not the other); the sums over a grid move far less, so they hold an
error every cell shares to a tighter limit.
"""

from __future__ import annotations

import multiprocessing
import os

import numpy as np

from perfbench.lib import registry
from perfbench.lib.generators import rng_for


def sample(seed: int, grids: list) -> list:
    """(grid index, cell index) pairs to check: every cell of one grid
    drawn from the seed."""
    if not grids:
        return []
    k = int(rng_for(seed, 0xC0FFEE).integers(len(grids)))
    return [(k, i) for i in range(len(grids[k].cells))]


def _reference(task):
    engine_name, config, inputs, horizon_s, precision = task
    return registry.engine(engine_name).reference_of(config, inputs,
                                                     horizon_s, precision)


def references(config: dict, cells: list, precision: str = "float64") -> list:
    """The reference's totals for each (inputs, horizon) of ``cells``, in
    order, computed in worker processes (spawned, so none holds JAX's
    chip), the heaviest first."""
    tasks = [(config["engine"], config, inputs, horizon, precision)
             for inputs, horizon in cells]
    order = sorted(range(len(tasks)), key=lambda j: -len(tasks[j][2][0]))
    n = max(1, min(len(tasks), (os.cpu_count() or 2) - 1))
    with multiprocessing.get_context("spawn").Pool(n) as pool:
        got = pool.map(_reference, [tasks[j] for j in order], chunksize=1)
        pool.close()
        pool.join()
    out = [None] * len(tasks)
    for j, r in zip(order, got):
        out[j] = r
    return out


def _rel(a: float, b: float) -> float:
    return abs(a - b) / max(abs(b), 1e-12)


def numbers(prog: dict, ref: dict) -> dict:
    """The compared numbers of one cell."""
    return {
        "energy_rel": _rel(prog["energy_j"], ref["energy_j"]),
        "cost_rel": _rel(prog["cost_usd"], ref["cost_usd"]),
        "spinup_rel": ((abs(prog["fpga_spinups"] - ref["fpga_spinups"])
                        + abs(prog["cpu_spinups"] - ref["cpu_spinups"]))
                       / max(ref["fpga_spinups"] + ref["cpu_spinups"], 1)),
        "miss_share": (abs(prog["deadline_misses"] - ref["deadline_misses"])
                       / max(ref["requests"], 1)),
    }


def _spins(t: dict) -> int:
    return t["fpga_spinups"] + t["cpu_spinups"]


def values(progs: list, refs: list) -> dict:
    """Every compared number of one grid's cells: the worst per-cell
    number, and the gaps of the grid's summed totals."""
    per_cell = [numbers(a, b) for a, b in zip(progs, refs)]
    out = {k: float(np.max([d[k] for d in per_cell])) for k in per_cell[0]}
    for name, key in (("grid_energy_rel", "energy_j"),
                      ("grid_cost_rel", "cost_usd")):
        out[name] = _rel(sum(a[key] for a in progs),
                         sum(b[key] for b in refs))
    out["grid_spinup_rel"] = _rel(sum(_spins(a) for a in progs),
                                  sum(_spins(b) for b in refs))
    return out


def verdict(values: dict, limits: dict) -> tuple[bool, dict]:
    """(every limited number within its limit, {name: (value, limit)}).
    The configuration's limits name the numbers it holds; one that the
    comparison did not produce fails."""
    rows = {k: (values.get(k), lim) for k, lim in limits.items()}
    ok = bool(rows) and all(v is not None and v <= lim
                            for v, lim in rows.values())
    return ok, rows
