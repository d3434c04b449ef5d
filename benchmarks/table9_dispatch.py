"""Table 9: dispatch-policy ablation under SporkE's allocation logic.

Exact event-driven simulation (per-request semantics are what separate
the policies); production stand-ins at reduced demand so the DES stays
tractable (utilization-preserving; documented in DESIGN.md §9).

Two interchangeable engines (``engine=`` / ``BENCH_TABLE9_ENGINE``):

  * ``python``  — the serial `repro.sim.events.EventSim` oracle, one run
                  per (case, app, policy) cell. The tested ground truth.
  * ``batched`` — `repro.sim.sweep.sweep_events` over the vectorized
                  `repro.sim.events_batched` engine: the whole grid in a
                  handful of vmapped `lax.scan` dispatches. Matches the
                  oracle exactly on integer-quantized traces and to ~1%
                  on these continuous ones (docs/architecture.md).

``batched`` is the fast-mode default. It rests on CPU rows only
(results/BENCH_sweep.json ``table9_engine_compare``: 1.1-1.7x vs the
serial oracle on a 1-core CPU host); no chip measurement has decided it
yet. Run ``python benchmarks/table9_dispatch.py --compare`` to
re-measure both engines in this process and on a fabricated 8-device
CPU mesh (``--xla_force_host_platform_device_count=8`` with
``JAX_PLATFORMS=cpu``, in a subprocess) and refresh the record: per-row
``{engine, backend, platform, n_devices, wall_s, speedup_vs_python}``
plus an ``analysis`` field.
"""

from __future__ import annotations

import os
import sys

# allow `python benchmarks/table9_dispatch.py --compare` from anywhere
_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
for _p in (_ROOT, os.path.join(_ROOT, "src")):
    if _p not in sys.path:
        sys.path.insert(0, _p)

from repro.core.metrics import RunTotals, report
from repro.core.traces import synthetic_trace
from repro.core.workers import DEFAULT_FLEET
from repro.sim.events import simulate_events
from repro.sim.sweep import EventCell, sweep_events

from benchmarks.common import FAST

# Demand in these grids peaks well below 128 FPGA-equivalents, so both
# engines agree with the n_max=512 default bit-for-bit while the batched
# engine's histogram state stays small.
N_MAX = 128

CASES = [("azure-like(short)", 0.68, 0.05),
         ("azure-like(medium)", 0.68, 0.3),
         ("alibaba-like(short)", 0.58, 0.05)]

DISPATCHERS = ("round_robin", "index_packing", "spork")

#: Fast-mode engine default, set from the CPU rows in
#: results/BENCH_sweep.json ``table9_engine_compare`` (1.09x/1.41x/1.72x
#: vs serial across three 1-core CPU runs, local backend).
DEFAULT_ENGINE = "batched"


def _grid(fast: bool = FAST):
    """(label, [(arrival_times, size_s), ...]) per case; traces are
    dispatch-policy-independent so they are generated once per (case,
    app) and shared across all three policies and both engines."""
    horizon = 900 if fast else 3600
    n_apps = 2 if fast else 5
    grid = []
    for label, bias, size in CASES:
        apps = []
        for app in range(n_apps):
            tr = synthetic_trace(seed=100 + app, bias=bias,
                                 horizon_s=horizon, request_size_s=size,
                                 mean_demand_workers=8.0)
            apps.append((tr.arrival_times(seed=7 + app), tr.request_size_s))
        grid.append((label, apps))
    return grid, horizon


def run(engine: str | None = None,
        backend: str | None = None) -> list[dict]:
    engine = engine or os.environ.get("BENCH_TABLE9_ENGINE", DEFAULT_ENGINE)
    assert engine in ("python", "batched"), engine
    fleet = DEFAULT_FLEET
    grid, horizon = _grid()

    merged: dict[tuple, RunTotals] = {}
    if engine == "batched":
        cells = [EventCell(disp, arr, size_s, fleet, horizon_s=horizon,
                           tag=(label, disp))
                 for label, apps in grid
                 for disp in DISPATCHERS
                 for arr, size_s in apps]
        totals = sweep_events(cells, n_max=N_MAX, backend=backend).totals()
        for cell, tot in zip(cells, totals):
            assert tot.breakdown.get("slot_overflow", 0) == 0
            prev = merged.get(cell.tag)
            merged[cell.tag] = tot if prev is None else prev.merge(tot)
    else:
        for label, apps in grid:
            for disp in DISPATCHERS:
                total = RunTotals()
                for arr, size_s in apps:
                    tot = simulate_events(arr, size_s, fleet,
                                          dispatcher=disp,
                                          horizon_s=horizon, n_max=N_MAX)
                    total = total.merge(tot)
                merged[(label, disp)] = total

    rows = []
    for label, _ in grid:
        for disp in DISPATCHERS:
            r = report(merged[(label, disp)], fleet)
            rows.append({"trace": label, "dispatch": disp,
                         "engine": engine,
                         "energy_eff": round(r.energy_efficiency, 4),
                         "rel_cost": round(r.relative_cost, 4),
                         "miss_rate": round(r.deadline_miss_rate, 6)})
    return rows


#: Fabricated many-core host config for the mesh-probe subprocess: XLA
#: splits the host CPU into this many CpuDevices (no extra silicon — on
#: an n-core container the devices time-share n cores, which is exactly
#: what the recorded analysis must call out).
FABRICATED_DEVICES = 8

_PROBE_MARK = "MESH_PROBE_JSON:"


def _timeit(fn) -> float:
    """Post-compile wall: one warm call, then one timed call."""
    import time
    fn()
    t0 = time.time()
    fn()
    return time.time() - t0


def _measure_rows(host_config: str, backend: str | None) -> list[dict]:
    """Serial + batched walls on the current process's exec backend, as
    ``table9_engine_compare`` measurement rows naming the platform."""
    import jax
    platform = jax.devices()[0].platform
    n_devices = jax.device_count() if backend == "mesh" else 1
    wall_p = _timeit(lambda: run("python"))
    w = _timeit(lambda: run("batched", backend=backend))
    return [{"host_config": host_config, "engine": "python",
             "backend": "serial", "platform": "cpu", "n_devices": 1,
             "wall_s": round(wall_p, 3), "speedup_vs_python": 1.0},
            {"host_config": host_config, "engine": "batched",
             "backend": backend or "local", "platform": platform,
             "n_devices": n_devices, "wall_s": round(w, 3),
             "speedup_vs_python": round(wall_p / w, 3)}]


def _mesh_probe() -> None:
    """Subprocess entry (``--mesh-probe``): must run under
    ``JAX_PLATFORMS=cpu`` and
    ``XLA_FLAGS=--xla_force_host_platform_device_count=N``. Emits the
    fabricated-host measurement rows as one marked JSON line."""
    import json

    import jax
    rows = _measure_rows(f"fabricated-{jax.device_count()}dev-mesh",
                         backend="mesh")
    print(_PROBE_MARK + json.dumps(rows), flush=True)


def _probe_manycore_rows() -> list[dict]:
    """Run the fabricated many-core probe in a CPU-only child (so it
    never contends for an accelerator this process holds); a child that
    fails fails the comparison."""
    import json
    import subprocess

    env = {**os.environ,
           "JAX_PLATFORMS": "cpu",
           "XLA_FLAGS": (os.environ.get("XLA_FLAGS", "") +
                         f" --xla_force_host_platform_device_count="
                         f"{FABRICATED_DEVICES}").strip(),
           "PYTHONPATH": os.pathsep.join([_ROOT,
                                          os.path.join(_ROOT, "src")]),
           "BENCH_SWEEP_BACKEND": "mesh"}
    proc = subprocess.run(
        [sys.executable, os.path.abspath(__file__), "--mesh-probe"],
        env=env, capture_output=True, text=True)
    for line in proc.stdout.splitlines():
        if line.startswith(_PROBE_MARK):
            return json.loads(line[len(_PROBE_MARK):])
    raise RuntimeError(f"mesh probe failed (rc={proc.returncode}):\n"
                       f"{proc.stderr[-2000:]}")


def _analysis(rows: list[dict]) -> str:
    """One-paragraph reading of the rows: which batched configuration
    was fastest, on which platform, and what fabricated devices mean."""
    best = max((r for r in rows if r["engine"] == "batched"),
               key=lambda r: r["speedup_vs_python"], default=None)
    if best is None:
        return "no batched rows measured"
    verdict = ("batched engine faster than serial"
               if best["speedup_vs_python"] > 1.0
               else "serial oracle faster — set BENCH_TABLE9_ENGINE=python "
                    "on this host")
    return (f"best batched config: {best['speedup_vs_python']}x vs serial "
            f"({best['backend']} backend, {best['n_devices']} "
            f"{best['platform']} devices; {verdict}). Fabricated CPU "
            f"devices time-share the host's {os.cpu_count() or 1} cores, "
            f"so mesh rows measure sharding overhead, not parallel "
            f"speedup.")


def compare() -> list[dict]:
    """Measure both engines on this host and on a fabricated many-core
    CPU mesh; record the rows (+ analysis) in results/BENCH_sweep.json
    ``table9_engine_compare``."""
    import jax

    from benchmarks.common import record_kv

    rows = _measure_rows("local", backend=None)
    rows += _probe_manycore_rows()

    # numeric drift check rides along: batched vs serial rows
    rows_p = run("python")
    rows_b = run("batched")
    for a, b in zip(rows_p, rows_b):
        drift = abs(a["energy_eff"] - b["energy_eff"])
        print(f"{a['trace']:22s} {a['dispatch']:14s} "
              f"eff {a['energy_eff']:.4f}/{b['energy_eff']:.4f} "
              f"(drift {drift:.4f})")

    grid, _ = _grid()
    wall_p = next(r["wall_s"] for r in rows if r["engine"] == "python")
    local_b = next(r for r in rows
                   if r["engine"] == "batched" and r["backend"] == "local")
    dev = jax.devices()[0]
    record_kv("table9_engine_compare",
              # summary keys (this process's platform, local backend)
              python_wall_s=wall_p,
              batched_wall_s=local_b["wall_s"],
              batched_speedup=local_b["speedup_vs_python"],
              cells=len(DISPATCHERS) * sum(len(apps) for _, apps in grid),
              fast=FAST,
              host_cpu_count=os.cpu_count(),
              platform=dev.platform, device_kind=dev.device_kind,
              default_engine=os.environ.get("BENCH_TABLE9_ENGINE",
                                            DEFAULT_ENGINE),
              rows=rows,
              analysis=_analysis(rows))
    for r in rows:
        print(f"{r['host_config']:22s} {r['engine']:8s} "
              f"backend={r['backend']:7s} {r['platform']} "
              f"dev={r['n_devices']} wall={r['wall_s']:.1f}s "
              f"speedup={r['speedup_vs_python']:.2f}x")
    print(_analysis(rows))
    return rows_p


if __name__ == "__main__":
    if "--mesh-probe" in sys.argv:
        _mesh_probe()
    elif "--compare" in sys.argv:
        compare()
    else:
        for row in run():
            print(row)
