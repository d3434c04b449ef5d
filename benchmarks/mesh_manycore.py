"""Mesh backend on a fabricated many-core host: the >2-device record.

The `repro.sim.exec.MeshBackend` shard_maps each sweep dispatch's cell
axis over a 1-D device mesh. Until this suite, it had only ever been
*measured* at <= 2 fabricated devices (the CI bit-identity job); this
closes the ROADMAP carried-context item by timing real event/fleet
sweep grids at ``--xla_force_host_platform_device_count=8``:

  * a ``JAX_PLATFORMS=cpu`` subprocess fabricates 8 host CpuDevices
    (XLA splits the host CPU; the devices time-share the physical
    cores, so on a small container these rows measure sharding
    *overhead*, not parallel speedup — the per-row ``host_cpu_count`` is
    what makes the numbers interpretable). It never touches an
    accelerator the parent holds, and a child that fails fails the
    suite;
  * the parent process times the identical grids on the 1-device local
    backend for the baseline rows, on whatever platform it runs.

Every row records ``{suite, backend, platform, n_devices, wall_s}``;
the merged record lands in results/BENCH_sweep.json under
``mesh_manycore``.
"""

from __future__ import annotations

import json
import os
import subprocess
import sys
import time

# allow `python benchmarks/mesh_manycore.py` from anywhere
_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
for _p in (_ROOT, os.path.join(_ROOT, "src")):
    if _p not in sys.path:
        sys.path.insert(0, _p)

from benchmarks.common import FAST, record_kv

FABRICATED_DEVICES = 8
_PROBE_MARK = "MANYCORE_PROBE_JSON:"

#: (scale, n apps/tenants) kept small: the point is backend/device
#: attribution, not workload realism — table9_dispatch/fleet_suite own
#: the realistic grids.
EVENT_SEEDS = (0, 1, 2, 3)
FLEET_SCALES = (16, 64)


def _event_cells():
    import numpy as np

    from repro.core.workers import DEFAULT_FLEET
    from repro.sim.sweep import EventCell

    horizon = 600.0
    cells = []
    for disp in ("spork", "index_packing", "round_robin"):
        for seed in EVENT_SEEDS:
            rng = np.random.default_rng(seed)
            arr = np.sort(rng.uniform(0.0, horizon, 400))
            cells.append(EventCell(disp, arr, 0.25, DEFAULT_FLEET,
                                   horizon_s=horizon))
    return cells


def _fleet_cells():
    from repro.fleet import FleetCell
    from repro.policies import admission_policy_names
    from repro.workloads import tenant_population

    return [FleetCell(tenants=tenant_population(
                          n, horizon_s=60.0, mean_demand_workers=0.05,
                          seed=1),
                      admission=adm)
            for n in FLEET_SCALES for adm in admission_policy_names()]


def _timeit(fn) -> float:
    fn()                                 # compile/warm
    t0 = time.time()
    fn()
    return time.time() - t0


def _measure(backend: str | None, n_devices: int) -> list[dict]:
    import jax

    from repro.sim.sweep import sweep_events, sweep_fleet

    ev, fl = _event_cells(), _fleet_cells()
    platform = jax.devices()[0].platform
    w_ev = _timeit(lambda: sweep_events(ev, n_max=128, backend=backend))
    w_fl = _timeit(lambda: sweep_fleet(fl, backend=backend))
    return [{"suite": suite, "backend": backend or "local",
             "platform": platform, "n_devices": n_devices,
             "cells": n, "wall_s": round(w, 3)}
            for suite, n, w in (("events", len(ev), w_ev),
                                ("fleet", len(fl), w_fl))]


def _probe() -> None:
    """Subprocess entry: run under ``JAX_PLATFORMS=cpu`` and the
    fabricated-device XLA flag."""
    import jax
    rows = _measure("mesh", jax.device_count())
    print(_PROBE_MARK + json.dumps(rows), flush=True)


def run() -> list[dict]:
    rows = _measure(None, 1)
    env = {**os.environ,
           "JAX_PLATFORMS": "cpu",
           "XLA_FLAGS": (os.environ.get("XLA_FLAGS", "") +
                         f" --xla_force_host_platform_device_count="
                         f"{FABRICATED_DEVICES}").strip(),
           "PYTHONPATH": os.pathsep.join([_ROOT,
                                          os.path.join(_ROOT, "src")])}
    proc = subprocess.run(
        [sys.executable, os.path.abspath(__file__), "--probe"],
        env=env, capture_output=True, text=True)
    for line in proc.stdout.splitlines():
        if line.startswith(_PROBE_MARK):
            rows += json.loads(line[len(_PROBE_MARK):])
            break
    else:
        raise RuntimeError(f"many-core probe failed (rc={proc.returncode}):"
                           f"\n{proc.stderr[-2000:]}")
    record_kv("mesh_manycore", rows=rows, fast=FAST,
              host_cpu_count=os.cpu_count(),
              fabricated_devices=FABRICATED_DEVICES)
    for r in rows:
        print(f"{r['suite']:7s} backend={r['backend']:6s} "
              f"{r['platform']} dev={r['n_devices']} "
              f"cells={r['cells']:3d} wall={r['wall_s']:.1f}s")
    return rows


if __name__ == "__main__":
    if "--probe" in sys.argv:
        _probe()
    else:
        run()
