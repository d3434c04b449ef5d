"""Spork-scheduled heterogeneous serving: the paper's scheduler sizing a
fleet that serves the assigned model architectures.

The mapping (DESIGN.md §2, hardware-adaptation notes): the paper's "FPGA"
is the reserved accelerator pool (slow to provision, energy-efficient at
steady load); the "CPU" is the elastic host pool (fast cold-start, cheap
at low load, ~S x slower per request). `fleet_for_arch` derives the
request service time and the accelerator speedup from the architecture's
roofline numbers — decode is bandwidth-bound, so the per-token floor is
active_bytes / HBM_bw on the accelerator; when a dry-run record exists the
measured roofline terms override the analytic estimate. The router itself
is the paper's machinery (Algs. 1-3 via sim.events.EventSim) driven
online, including straggler hedging: a worker whose completion estimate
slips past a request's deadline never receives it (CanMeetDeadline), so
slow workers shed load to freshly spun CPU workers automatically.

`TenantRouter` is the multi-tenant face of the same machinery: the
fleet layer (`repro.fleet`) absorbed this module's role as the
router-level admission layer — one shared fleet, N tenants, per-arrival
admit/shed decisions from `repro.policies.admission` before dispatch.
"""

from __future__ import annotations

import json
from dataclasses import dataclass
from pathlib import Path

import numpy as np

from repro.configs.registry import get_config
from repro.core.metrics import Report, RunTotals, report
from repro.core.workers import DEFAULT_FLEET, FleetParams
from repro.launch.mesh import TARGET_DEVICE_KIND, chip_peaks
from repro.sim.events import EventSim


@dataclass(frozen=True)
class ArchServiceModel:
    arch: str
    token_s_accel: float       # seconds per generated token, accelerator
    speedup: float             # accelerator over elastic-CPU worker


def analytic_token_latency(arch: str) -> float:
    """Bandwidth-bound decode floor on the target chip: active params
    (bf16) / HBM bandwidth."""
    cfg = get_config(arch, "full")
    active = cfg.param_count(active_only=True)
    return active * 2.0 / chip_peaks(TARGET_DEVICE_KIND).hbm_bw


def roofline_token_latency(arch: str,
                           dryrun_dir: str | Path = "results/dryrun",
                           ) -> float | None:
    """Dominant roofline term per decode step from a dry-run record."""
    p = Path(dryrun_dir) / f"{arch}__decode_32k__single.json"
    if not p.exists():
        return None
    rec = json.loads(p.read_text())
    if not rec.get("ok"):
        return None
    flops = rec.get("hlo_flops", 0.0)
    byts = rec.get("hlo_bytes", 0.0)
    if flops <= 0 or byts <= 0:
        return None
    # per-device terms on the target chip; batch shares the step
    batch = 128
    peaks = chip_peaks(TARGET_DEVICE_KIND)
    t = max(flops / peaks.flops_bf16, byts / peaks.hbm_bw) / batch
    return float(t)


def service_model(arch: str, speedup: float = 2.0,
                  dryrun_dir: str | Path = "results/dryrun",
                  ) -> ArchServiceModel:
    t = roofline_token_latency(arch, dryrun_dir) or analytic_token_latency(arch)
    return ArchServiceModel(arch=arch, token_s_accel=t, speedup=speedup)


def fleet_for_arch(arch: str, avg_new_tokens: int = 64,
                   base: FleetParams = DEFAULT_FLEET,
                   dryrun_dir: str | Path = "results/dryrun",
                   ) -> tuple[FleetParams, float]:
    """(FleetParams, request_size_s_on_cpu) for serving `arch`.

    Power/cost/spin-up keep the paper's defaults (they parameterize the
    platform, not the model); the request size comes from the arch's
    decode latency x tokens per request."""
    sm = service_model(arch, dryrun_dir=dryrun_dir)
    size_cpu_s = sm.token_s_accel * sm.speedup * avg_new_tokens
    fleet = base.replace(
        fpga=base.fpga.replace(speedup=sm.speedup),
        cpu=base.cpu.replace(speedup=1.0))
    return fleet, size_cpu_s


class TenantRouter:
    """Online multi-tenant router: the fleet layer's admission + dispatch
    driven request-by-request over ONE shared fleet.

    Wraps `repro.fleet.FleetSim` the way `SporkRouter` wraps `EventSim`:
    `submit(t, tenant)` runs the cell's router-level admission policy
    (`repro.policies.admission`, float32 — decisions bit-identical to
    both batch engines) and, if admitted, dispatches with the tenant's
    own size and SLO deadline; `finish` returns the fleet `Report` plus
    the per-tenant `repro.core.metrics.TenantTotals` rows. Batch-path
    equivalence (online submit == `repro.fleet.simulate_fleet` on the
    same stream) is pinned by tests/test_serve.py."""

    def __init__(self, cell, n_max: int = 512):
        from repro.fleet import FleetSim, resolve_fleet_cell
        self.cell = cell
        self.sim = FleetSim(cell, n_max=n_max)
        self.horizon = resolve_fleet_cell(cell).horizon_s
        self.sim.schedule_ticks(self.horizon)

    def submit(self, t: float, tenant: int) -> bool:
        """One tenant request at time t; returns admitted (False = shed)."""
        return self.sim.submit_tagged(t, tenant)

    def advance(self, t: float) -> None:
        self.sim.drain_until(t, self.horizon)

    def finish(self) -> tuple[Report, list]:
        # drain the WHOLE event heap (spin-ups/reclaims can land past
        # the horizon) — `FleetSim.run_tagged` does the same, and the
        # online==batch equivalence is exact only if both settle alike
        self.sim.drain_until(float("inf"), self.horizon)
        totals, rows = self.sim.finalize_fleet(self.horizon)
        return report(totals, self.cell.fleet), rows


class SporkRouter:
    """Online request router: Spork allocation + efficient-first dispatch
    over a heterogeneous fleet serving one architecture."""

    def __init__(self, arch: str, energy_weight: float = 1.0,
                 dispatcher: str = "spork", avg_new_tokens: int = 64,
                 horizon_s: float = 3600.0,
                 dryrun_dir: str | Path = "results/dryrun"):
        self.fleet, self.size_s = fleet_for_arch(
            arch, avg_new_tokens, dryrun_dir=dryrun_dir)
        self.sim = EventSim(self.fleet, self.size_s, dispatcher=dispatcher,
                            energy_weight=energy_weight)
        self.horizon = horizon_s
        self.sim.schedule_ticks(horizon_s)

    def submit(self, t: float) -> None:
        self.sim.submit(t)

    def advance(self, t: float) -> None:
        self.sim.drain_until(t, self.horizon)

    def finish(self) -> Report:
        self.sim.drain_until(self.horizon, self.horizon)
        totals = self.sim._finalize(self.horizon)
        return report(totals, self.fleet)
