#!/usr/bin/env python3
"""Smoke run of the simulator's main path on a TPU.

    python chip_smoke.py              # one chip: des, fleet, rate, kernels
    python chip_smoke.py --chips 4    # four chips: the mesh phase only

Everything runs in this one process, through the entry points a user
calls (`repro.sim.sweep.sweep_events` / `sweep_fleet` / `sweep`), at the
full-mode (``BENCH_FAST=0``) sizes of the benchmark grids. Every trace
is generated from a seed; nothing is read from ``results/`` and no
checkpoint directory is used, so every cell is computed in this run.

Phases (one chip):

  des      Table-9 grid (3 cases x 5 apps x 3 dispatchers, 3600 s
           horizons) on `LocalBackend`; ``slot_overflow == 0``; one held-out
           ``azure-like(medium)`` cell per dispatcher against the serial
           `simulate_events` oracle (requests exact, energy efficiency
           within 1% — the continuous-trace contract of
           docs/architecture.md).
  fleet    fleet-suite grid (16..1024 tenants x 3 admission policies,
           180 s, on the 64 + 128-slot table it declares);
           ``slot_overflow == 0``; per-tenant conservation against the
           fleet totals; one dyadic cell against the `FleetSim` oracle
           (counters exact).
  rate     Table-8 grid (every Table-7 app, 7200 s) through `sweep`; the
           first cell of each rate policy against per-call
           `ratesim.simulate` at 2e-4 relative.
  kernels  every Pallas kernel compiled (``interpret=False``) against its
           ``ref.py`` on a small seeded input.

Phase ``mesh`` (``--chips 4``): the Table-9 and fleet grids (full-mode
sizes, as above) on a 4-device `MeshBackend` and on `LocalBackend`;
totals and tenant rows must be bit-identical and the mesh outputs must
sit on 4 distinct devices.

Every sweep must report no degraded chunk and no retried dispatch. Each
phase prints one progress line (cells, simulated arrivals, compile and
run seconds, device); the last line of standard output is the JSON
``{"ok": true, "device": {...}}``. Without a TPU the script exits
non-zero before any phase and prints no result.
"""

from __future__ import annotations

import argparse
import dataclasses
import json
import os
import sys
import time

_ROOT = os.path.dirname(os.path.abspath(__file__))
for _p in (_ROOT, os.path.join(_ROOT, "src")):
    if _p not in sys.path:
        sys.path.insert(0, _p)


class SmokeFailure(AssertionError):
    """A smoke check failed."""


def check(cond: bool, msg: str) -> None:
    if not cond:
        raise SmokeFailure(msg)


class CompileClock:
    """Seconds JAX spent tracing, lowering and compiling (its
    ``/jax/core/compile/*`` duration events) since `reset`."""

    def __init__(self):
        import jax
        self.total = 0.0
        jax.monitoring.register_event_duration_secs_listener(self._on_event)

    def _on_event(self, event: str, duration: float, **_) -> None:
        if event.startswith("/jax/core/compile/"):
            self.total += duration

    def reset(self) -> None:
        self.total = 0.0


def device_info() -> dict:
    import jax
    devs = jax.devices()
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}


def progress(phase: str, cells: int, arrivals: int, clock: CompileClock,
             wall: float) -> None:
    d = device_info()
    print(f"phase={phase} cells={cells} arrivals={arrivals} "
          f"compile_s={clock.total:.3f} "
          f"run_s={max(wall - clock.total, 0.0):.3f} "
          f"platform={d['platform']} device_kind={d['kind']!r} "
          f"devices={d['count']}", flush=True)


def check_meta(res, where: str) -> None:
    """No degraded chunk, no retry, nothing restored from a checkpoint."""
    m = res.meta
    check(m["degraded_chunks"] == [],
          f"{where}: degraded chunks {m['degraded_chunks']}")
    check(m["retried_dispatches"] == 0,
          f"{where}: {m['retried_dispatches']} retried dispatches")
    check(m["restored_chunks"] == 0 and not m["checkpointed"],
          f"{where}: results restored from a checkpoint")


# ------------------------------------------------------------------ des
# the Table-9 case whose cells are checked against the serial oracle
# (its shortest traces, so the oracle stays quick)
ORACLE_CASE = "azure-like(medium)"


def table9_cells(fast: bool):
    from benchmarks.table9_dispatch import DISPATCHERS, _grid
    from repro.core.workers import DEFAULT_FLEET
    from repro.sim.sweep import EventCell

    grid, horizon = _grid(fast=fast)
    return [EventCell(disp, arr, size_s, DEFAULT_FLEET, horizon_s=horizon,
                      tag=(label, disp, app))
            for label, apps in grid
            for disp in DISPATCHERS
            for app, (arr, size_s) in enumerate(apps)]


def phase_des(clock: CompileClock, cells=None) -> None:
    from benchmarks.table9_dispatch import N_MAX
    from repro.core.metrics import report
    from repro.sim.events import simulate_events
    from repro.sim.exec import LocalBackend
    from repro.sim.harness import check_sweep_result
    from repro.sim.sweep import sweep_events

    cells = table9_cells(fast=False) if cells is None else cells
    clock.reset()
    t0 = time.perf_counter()
    res = sweep_events(cells, n_max=N_MAX, backend=LocalBackend())
    wall = time.perf_counter() - t0
    check_sweep_result(res, where="des ")
    check_meta(res, "des")
    for i, cell in enumerate(cells):
        over = res.totals(i).breakdown["slot_overflow"]
        check(over == 0, f"des: cell {cell.tag} slot_overflow={over}")
    progress("des", len(cells), sum(len(c.arrival_times) for c in cells),
             clock, wall)

    held_out = {}
    for i, cell in enumerate(cells):
        label, disp, _ = cell.tag
        if label == ORACLE_CASE:
            held_out.setdefault(disp, i)
    check(len(held_out) == len({c.tag[1] for c in cells}),
          f"des: no {ORACLE_CASE} cell for some dispatcher")
    for disp, i in sorted(held_out.items()):
        cell = cells[i]
        want = simulate_events(cell.arrival_times, cell.size_s, cell.fleet,
                               dispatcher=disp, horizon_s=cell.horizon_s,
                               n_max=N_MAX)
        got = res.totals(i)
        check(got.requests == want.requests,
              f"des oracle {cell.tag}: requests {got.requests} "
              f"!= {want.requests}")
        e_got = report(got, cell.fleet).energy_efficiency
        e_want = report(want, cell.fleet).energy_efficiency
        rel = abs(e_got - e_want) / e_want
        check(rel <= 0.01, f"des oracle {cell.tag}: energy efficiency "
                           f"{e_got} vs {e_want} (rel {rel})")
        print(f"des_oracle cell={cell.tag} requests={got.requests} "
              f"energy_eff={e_got} oracle_energy_eff={e_want} rel={rel}",
              flush=True)


# ---------------------------------------------------------------- fleet
def _dyadic_fleet_cell():
    """Explicit-stream tenants on the 1/8 s grid with dyadic sizes: the
    fleet engine's exactness contract (docs/architecture.md)."""
    import numpy as np

    from repro.core.workers import DEFAULT_FLEET
    from repro.fleet import FleetCell, TenantSpec

    rng = np.random.default_rng(3)
    sizes, slos = (0.125, 0.25, 0.0625), ("standard", "tight", "relaxed")
    weights = (1.0, 0.5, 2.0)
    tenants = tuple(
        TenantSpec(arrival_times=tuple(np.sort(rng.integers(0, 480, 120))
                                       / 8.0),
                   request_size_s=sizes[i], slo=slos[i], weight=weights[i],
                   seed=3 + i)
        for i in range(3))
    fleet = DEFAULT_FLEET.replace(cpu=DEFAULT_FLEET.cpu.replace(
        spin_up_s=1.0))
    return FleetCell(tenants=tenants, admission="token_bucket",
                     fleet=fleet, horizon_s=60.0)


def phase_fleet(clock: CompileClock, cells=None, w_fpga: int = 32,
                w_cpu: int = 64) -> None:
    """Fleet grid (default: the full-mode fleet-suite grid on the worker
    table that grid declares)."""
    from benchmarks.fleet_suite import MAX_SWEEP_DISPATCHES, grid
    from repro.fleet import simulate_fleet
    from repro.sim.exec import LocalBackend
    from repro.sim.harness import check_fleet_result
    from repro.sim.sweep import sweep_fleet

    if cells is None:
        g = grid(fast=False)
        cells, w_fpga, w_cpu = g.cells, g.w_fpga, g.w_cpu
    clock.reset()
    t0 = time.perf_counter()
    res = sweep_fleet(cells, w_fpga=w_fpga, w_cpu=w_cpu,
                      backend=LocalBackend())
    wall = time.perf_counter() - t0
    check(res.n_dispatches <= MAX_SWEEP_DISPATCHES,
          f"fleet: {res.n_dispatches} dispatches")
    check_fleet_result(res, where="fleet ")
    check_meta(res, "fleet")
    offered = 0
    for i in range(len(res)):
        t = res.totals(i)
        check(t.breakdown["slot_overflow"] == 0,
              f"fleet: cell {i} slot_overflow")
        offered += t.breakdown["offered_requests"]
    progress("fleet", len(res), offered, clock, wall)

    cell = _dyadic_fleet_cell()
    want, want_rows = simulate_fleet(cell, n_max=64)
    small = sweep_fleet([cell], n_max=64, w_fpga=16, w_cpu=32,
                        backend=LocalBackend())
    check_fleet_result(small, where="fleet oracle ")
    check_meta(small, "fleet oracle")
    got, got_rows = small.totals(0), small.tenants(0)
    for f in ("requests", "deadline_misses", "fpga_spinups", "cpu_spinups",
              "work_on_fpga_cpu_s", "work_on_cpu_cpu_s"):
        check(getattr(got, f) == getattr(want, f),
              f"fleet oracle: {f} {getattr(got, f)} != {getattr(want, f)}")
    for k in ("offered_requests", "shed_requests"):
        check(got.breakdown[k] == want.breakdown[k], f"fleet oracle: {k}")
    for f in ("energy_j", "cost_usd"):
        a, b = getattr(got, f), getattr(want, f)
        check(abs(a - b) <= 1e-4 * abs(b) + 1e-3,
              f"fleet oracle: {f} {a} vs {b}")
    for ra, rb in zip(got_rows, want_rows):
        for f in ("requests", "admitted", "shed", "deadline_misses"):
            check(getattr(ra, f) == getattr(rb, f),
                  f"fleet oracle: tenant {ra.tenant} {f}")
    print(f"fleet_oracle requests={got.requests} "
          f"misses={got.deadline_misses} energy_j={got.energy_j} "
          f"oracle_energy_j={want.energy_j} tenants={len(got_rows)}",
          flush=True)


# ----------------------------------------------------------------- rate
def phase_rate(clock: CompileClock, cells=None) -> None:
    import numpy as np

    from benchmarks.table8_production import grid
    from repro.sim import ratesim
    from repro.sim.exec import LocalBackend
    from repro.sim.harness import check_sweep_result
    from repro.sim.sweep import sweep

    cells = grid(fast=False)[0] if cells is None else cells
    clock.reset()
    t0 = time.perf_counter()
    res = sweep(cells, backend=LocalBackend())
    wall = time.perf_counter() - t0
    check_sweep_result(res, where="rate ")
    check_meta(res, "rate")
    progress("rate", len(cells), int(sum(int(np.sum(c.counts))
                                         for c in cells)), clock, wall)

    # one oracle cell per distinct rate policy (the first in grid order)
    picked = {}
    for i, c in enumerate(cells):
        picked.setdefault(c.policy, i)
    check(len(picked) >= min(3, len(cells)), "rate: too few oracle cells")
    for i in picked.values():
        c = cells[i]
        want = ratesim.simulate(c.policy, c.counts, c.size_s, c.fleet,
                                energy_weight=c.energy_weight)
        got = res.totals(i)
        for f in ("energy_j", "cost_usd", "work_cpu_s",
                  "work_on_fpga_cpu_s", "work_on_cpu_cpu_s", "fpga_idle_j",
                  "fpga_busy_j", "cpu_busy_j", "spinup_j"):
            a, b = getattr(want, f), getattr(got, f)
            check(abs(a - b) <= 2e-4 * max(abs(a), 1.0),
                  f"rate oracle {c.tag} {c.policy}: {f} {b} vs {a}")
        for f in ("requests", "deadline_misses", "fpga_spinups",
                  "cpu_spinups"):
            check(getattr(want, f) == getattr(got, f),
                  f"rate oracle {c.tag} {c.policy}: {f}")
        print(f"rate_oracle cell={c.tag} policy={c.policy} "
              f"energy_j={got.energy_j} oracle_energy_j={want.energy_j}",
              flush=True)


# -------------------------------------------------------------- kernels
def phase_kernels(clock: CompileClock) -> None:
    """Each Pallas kernel in `repro.kernels`, compiled, against its
    ref.py on a small seeded input (tolerances of tests/test_kernels.py).
    The jnp references run with f32 matmuls at full precision (the TPU
    default takes one bf16 pass), as the kernels' own contractions do."""
    import jax

    with jax.default_matmul_precision("highest"):
        _check_kernels(clock)


def _check_kernels(clock: CompileClock) -> None:
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.core.breakeven import energy_coeffs
    from repro.core.predictor import amortization_vector
    from repro.core.workers import DEFAULT_FLEET
    from repro.kernels.decode_attn.decode_attn import decode_attention_pallas
    from repro.kernels.decode_attn.ref import decode_attention_ref
    from repro.kernels.minplus.minplus import minplus_pallas
    from repro.kernels.minplus.ref import minplus_step_ref
    from repro.kernels.spork_predict.ref import expected_objective_ref
    from repro.kernels.spork_predict.spork_predict import spork_predict_pallas

    clock.reset()
    t0 = time.perf_counter()
    n_checked = 0

    # min-plus transition: integer inputs, so values and first-minimizer
    # argmins are exact
    for n in (130, 257):
        rng = np.random.default_rng(n)
        F = jnp.asarray(rng.integers(-1000, 1000, n).astype(np.float32))
        ycp = jnp.asarray(rng.integers(0, 50, n).astype(np.float32))
        ycc = jnp.asarray(rng.integers(0, 50, n).astype(np.float32))
        coeffs = (500.0, 5.0, 3.0, 2.0)
        want, want_arg = minplus_step_ref(F, ycp, ycc, coeffs)
        got, got_arg = minplus_pallas(F, ycp, ycc, jnp.asarray(coeffs),
                                      interpret=False)
        check(np.array_equal(np.asarray(got), np.asarray(want)),
              f"minplus n={n}: values differ")
        check(np.array_equal(np.asarray(got_arg), np.asarray(want_arg)),
              f"minplus n={n}: argmins differ")
        n_checked += 1

    # Alg.-2 expected objective
    n = 200
    rng = np.random.default_rng(n)
    hist = jnp.asarray(rng.integers(0, 6, n), jnp.float32)
    coeffs = energy_coeffs(DEFAULT_FLEET)
    amort = amortization_vector(
        jnp.asarray(rng.uniform(0, 100, n), jnp.float32),
        jnp.asarray(rng.integers(0, 3, n), jnp.float32),
        jnp.asarray(2), DEFAULT_FLEET.T_s, coeffs.amort_unit)
    want = np.asarray(expected_objective_ref(hist, coeffs, amort))
    idx = jnp.arange(n)
    has = hist > 0
    params = jnp.stack([
        jnp.asarray(coeffs.co_min, jnp.float32),
        jnp.asarray(coeffs.co_over, jnp.float32),
        jnp.asarray(coeffs.co_under, jnp.float32),
        jnp.sum(hist),
        jnp.min(jnp.where(has, idx, n)).astype(jnp.float32),
        jnp.max(jnp.where(has, idx, -1)).astype(jnp.float32)])
    got = np.asarray(spork_predict_pallas(hist, amort, params,
                                          interpret=False))
    got = np.where(got >= 1.0e38, np.inf, got)
    mask = np.isfinite(want)
    check(np.array_equal(np.isfinite(got), mask), "spork_predict: mask")
    np.testing.assert_allclose(got[mask], want[mask], rtol=2e-5)
    n_checked += 1

    # GQA flash-decode attention
    for (b, hq, hkv, d, s), dtype, tol in (
            ((2, 16, 8, 64, 300), jnp.float32, 2e-5),
            ((4, 6, 2, 128, 1024), jnp.bfloat16, 2e-2)):
        kq, kk, kv, kl = jax.random.split(jax.random.PRNGKey(s), 4)
        q = jax.random.normal(kq, (b, hq, d), dtype)
        k = jax.random.normal(kk, (b, s, hkv, d), dtype)
        v = jax.random.normal(kv, (b, s, hkv, d), dtype)
        lengths = jax.random.randint(kl, (b,), 1, s + 1)
        want = decode_attention_ref(q, k, v, lengths)
        got = decode_attention_pallas(q, k, v, lengths, block_s=128,
                                      interpret=False)
        np.testing.assert_allclose(np.asarray(got, np.float32),
                                   np.asarray(want, np.float32),
                                   rtol=tol, atol=tol)
        n_checked += 1
    wall = time.perf_counter() - t0
    progress("kernels", n_checked, 0, clock, wall)


# ----------------------------------------------------------------- mesh
def phase_mesh(clock: CompileClock, n_devices: int = 4, ev_cells=None,
               fl_cells=None, w_fpga: int = 32, w_cpu: int = 64) -> None:
    """Table-9 + fleet grids (default: both at full-mode size) on an
    ``n_devices`` `MeshBackend` vs `LocalBackend` on one device:
    bit-identical totals, no degraded chunk, mesh outputs on
    ``n_devices`` distinct devices."""
    import jax

    from benchmarks.fleet_suite import grid as fleet_grid
    from benchmarks.table9_dispatch import N_MAX
    from repro.sim.exec import LocalBackend, MeshBackend
    from repro.sim.plan import plan_events
    from repro.sim.sweep import sweep_events, sweep_fleet

    devs = jax.devices()
    check(len(devs) >= n_devices,
          f"mesh: {len(devs)} devices, need {n_devices}")
    mesh = MeshBackend(devs[:n_devices])
    local = LocalBackend()
    ev_cells = table9_cells(fast=False) if ev_cells is None else ev_cells
    if fl_cells is None:
        g = fleet_grid(fast=False)
        fl_cells, w_fpga, w_cpu = g.cells, g.w_fpga, g.w_cpu

    clock.reset()
    t0 = time.perf_counter()
    results = {}
    for name, run in (
            ("events", lambda b: sweep_events(ev_cells, n_max=N_MAX,
                                              backend=b)),
            ("fleet", lambda b: sweep_fleet(fl_cells, w_fpga=w_fpga,
                                            w_cpu=w_cpu, backend=b))):
        r_mesh, r_local = run(mesh), run(local)
        check_meta(r_mesh, f"mesh {name}")
        check_meta(r_local, f"local {name}")
        check(max(r_mesh.dispatch_devices) == n_devices,
              f"mesh {name}: dispatch devices {r_mesh.dispatch_devices}")
        for i in range(len(r_mesh)):
            a = dataclasses.asdict(r_mesh.totals(i))
            b = dataclasses.asdict(r_local.totals(i))
            check(a == b, f"mesh {name}: cell {i} mesh {a} != local {b}")
        if name == "fleet":
            for i in range(len(r_mesh)):
                check([dataclasses.asdict(r) for r in r_mesh.tenants(i)]
                      == [dataclasses.asdict(r) for r in r_local.tenants(i)],
                      f"mesh fleet: cell {i} tenant rows differ")
        results[name] = r_mesh
    wall = time.perf_counter() - t0

    # where the mesh program puts its output: one shard per device
    d = plan_events(ev_cells, n_max=N_MAX).dispatches[0]
    out = jax.block_until_ready(mesh.run(d))
    shard_devs = {s.device.id for leaf in jax.tree.leaves(out)
                  for s in leaf.addressable_shards}
    check(len(shard_devs) == n_devices,
          f"mesh: output shards on devices {sorted(shard_devs)}")
    progress("mesh", len(ev_cells) + len(fl_cells),
             sum(len(c.arrival_times) for c in ev_cells)
             + sum(results["fleet"].totals(i).breakdown["offered_requests"]
                   for i in range(len(fl_cells))), clock, wall)
    print(f"mesh_check devices={n_devices} bit_identical=True "
          f"degraded_chunks=[] shard_devices={sorted(shard_devs)} "
          f"dispatch_devices={results['events'].dispatch_devices}",
          flush=True)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="1: des, fleet, rate and kernels phases; "
                         "4: the mesh phase only")
    args = ap.parse_args(argv)

    info = device_info()
    if info["platform"] != "tpu":
        print(f"chip_smoke: no TPU (JAX platform {info['platform']!r})",
              file=sys.stderr)
        return 2
    if info["count"] < args.chips:
        print(f"chip_smoke: --chips {args.chips} but JAX sees "
              f"{info['count']} device(s)", file=sys.stderr)
        return 2

    from repro.compile_cache import enable_compilation_cache
    print(f"compile_cache={enable_compilation_cache()}", flush=True)
    clock = CompileClock()
    if args.chips == 4:
        phase_mesh(clock, n_devices=4)
    else:
        phase_des(clock)
        phase_fleet(clock)
        phase_rate(clock)
        phase_kernels(clock)
    print(json.dumps({"ok": True, "device": info}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
