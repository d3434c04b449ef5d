"""Fault-injection equivalence: the serial oracle vs the batched engine.

The failure axis (`repro.ft.failures.FailureSpec`) extends the engines'
equivalence contract: both consume the same counter-based randomness
(`failure_u01`, keyed per (cell, worker, attempt)), so on quantized
instances every resilience counter — retries, failed spin-ups, crashes,
recovered requests, failure-attributed misses — must match EXACTLY,
energies to ~1e-5, across failure modes x dispatchers x backends. An
all-zero spec must be indistinguishable from ``failures=None``
(bit-identical totals, same compiled program group).
"""

import os
import subprocess
import sys
import textwrap

import numpy as np
import pytest

import jax.numpy as jnp

try:
    from hypothesis import given, settings, strategies as st
except ImportError:
    from _hypothesis_shim import given, settings, strategies as st

import strategies as shared
from repro.core.workers import DEFAULT_FLEET
from repro.ft.failures import (DRAW_CRASH, DRAW_SPINUP, FSTAT_OFF,
                               FailureSpec, failure_u01)
from repro.sim.events import DISPATCHERS, simulate_events
from repro.sim.events_batched import simulate_events_batched
from repro.sim.plan import plan_events, resolve_scenarios
from repro.sim.sweep import EventCell, SweepCell, sweep, sweep_events

# Quantized fleet (CPU spin-up 1 s); arrivals are integer-quantized and
# every FailureSpec shape knob below is dyadic (backoff 2.0, factor 4.0),
# so float32 event arithmetic is exact and counters must match exactly.
QFLEET = DEFAULT_FLEET.replace(cpu=DEFAULT_FLEET.cpu.replace(spin_up_s=1.0))

HORIZON = 180

EXACT_FIELDS = ("requests", "deadline_misses", "fpga_spinups",
                "cpu_spinups", "work_on_fpga_cpu_s", "work_on_cpu_cpu_s",
                "retries", "failed_spinups", "crashes",
                "recovered_requests", "failure_misses")
CLOSE_FIELDS = ("energy_j", "cost_usd", "fpga_busy_j", "fpga_idle_j",
                "cpu_busy_j", "spinup_j", "wasted_spinup_j")

FSPECS = {
    "flaky": FailureSpec(spinup_fail_p=0.25, max_retries=2,
                         retry_backoff_s=2.0, seed=3),
    "crashy": FailureSpec(crash_p=0.03, max_failover=2, seed=5),
    "stragglers": FailureSpec(straggler_frac=0.25, straggler_factor=4.0,
                              seed=7),
    "evac": FailureSpec(evac_frac=0.5, evac_start_s=60.0, evac_end_s=120.0,
                        seed=9),
    "combined": FailureSpec(spinup_fail_p=0.125, max_retries=1,
                            retry_backoff_s=2.0, crash_p=0.0625,
                            max_failover=2, straggler_frac=0.125,
                            straggler_factor=2.0, evac_frac=0.25,
                            evac_start_s=80.0, evac_end_s=140.0, seed=11),
}


def bursty_trace(seed: int, hi: float = 8.0) -> np.ndarray:
    """Integer arrival times, alternating high/low rate blocks (the
    engines' exactness contract quantizes arrivals; failure timing knobs
    — backoff 2.0, factor 4.0 — stay dyadic on top of it)."""
    rng = np.random.default_rng(seed)
    rates = np.where((np.arange(HORIZON) // 20) % 2 == 0, hi, 0.5)
    counts = rng.poisson(rates)
    return np.repeat(np.arange(HORIZON, dtype=np.float64), counts)


def assert_totals_match(a, b, tag=""):
    for f in EXACT_FIELDS:
        assert getattr(a, f) == getattr(b, f), \
            f"{tag} {f}: oracle={getattr(a, f)} batched={getattr(b, f)}"
    for f in CLOSE_FIELDS:
        np.testing.assert_allclose(getattr(b, f), getattr(a, f),
                                   rtol=1e-4, atol=1e-3,
                                   err_msg=f"{tag} {f}")


# ------------------------------------------------------ randomness stream

def test_failure_u01_bit_equal_across_backends():
    """The contract that makes cross-engine exactness possible: the
    numpy and jax draws are the same uint32 hash, bit for bit."""
    wids = np.arange(0, 300, dtype=np.uint32)
    for seed in (0, 11, 0xDEADBEEF):
        seed = np.uint32(seed)       # top-bit seeds overflow a traced int
        for purpose in (DRAW_SPINUP, DRAW_CRASH):
            for ctr in (0, 1, 7):
                a = failure_u01(seed, wids, ctr, purpose, xp=np)
                b = np.asarray(failure_u01(seed, jnp.asarray(wids), ctr,
                                           purpose, xp=jnp))
                assert a.dtype == np.float32 == b.dtype
                assert np.array_equal(a, b)
    u = failure_u01(1, np.arange(10_000, dtype=np.uint32), 0, DRAW_CRASH,
                    xp=np)
    assert 0.0 <= u.min() and u.max() < 1.0
    assert abs(float(u.mean()) - 0.5) < 0.02     # roughly uniform


# ------------------------------------------------- zero-failure identity

@pytest.mark.parametrize("disp", DISPATCHERS)
def test_all_zero_spec_bit_identical_to_none(disp):
    arr = bursty_trace(0)
    off = FailureSpec()              # every rate zero -> normalizes away
    for sim in (simulate_events, simulate_events_batched):
        a = sim(arr, 1.0, QFLEET, dispatcher=disp, horizon_s=HORIZON,
                n_max=64, failures=None)
        b = sim(arr, 1.0, QFLEET, dispatcher=disp, horizon_s=HORIZON,
                n_max=64, failures=off)
        for f in EXACT_FIELDS + CLOSE_FIELDS:
            assert getattr(a, f) == getattr(b, f), (sim.__name__, f)
        assert b.retries == b.crashes == b.failed_spinups == 0
        assert b.wasted_spinup_j == 0.0


# ------------------------------------------------- oracle vs batched

@pytest.mark.parametrize("name", sorted(FSPECS))
def test_oracle_equivalence_under_failures(name):
    """Every failure mode x every dispatcher, one batched sweep against
    the per-cell serial oracle. Counters exact, energies close, and the
    injected mode must actually fire (non-trivial counters)."""
    fs = FSPECS[name]
    arr = bursty_trace(1)
    cells = [EventCell(d, arr, 1.0, QFLEET, horizon_s=HORIZON, failures=fs,
                       tag=d) for d in DISPATCHERS]
    got = sweep_events(cells, n_max=64, w_fpga=16, w_cpu=32)
    fired = 0
    for cell, b in zip(cells, got):
        assert b.breakdown["slot_overflow"] == 0
        a = simulate_events(arr, 1.0, QFLEET, dispatcher=cell.dispatcher,
                            horizon_s=HORIZON, n_max=64, failures=fs)
        assert_totals_match(a, b, tag=(name, cell.dispatcher))
        fired += a.retries + a.failed_spinups + a.crashes \
            + (a.wasted_spinup_j > 0) + (a.work_on_cpu_cpu_s > 0)
    assert fired > 0, f"{name} never fired — spec too weak to test anything"


@pytest.mark.parametrize("disp", DISPATCHERS)
def test_failover_exhaustion_under_tight_deadline(disp):
    """Heavy crashes + a tight deadline force failover exhaustion: the
    failure-attributed miss counter must be nonzero and exact."""
    fs = FailureSpec(spinup_fail_p=0.25, max_retries=1, retry_backoff_s=2.0,
                     crash_p=0.125, max_failover=1, seed=13)
    arr = bursty_trace(2, hi=12.0)
    a = simulate_events(arr, 1.0, QFLEET, dispatcher=disp,
                        horizon_s=HORIZON, deadline_s=2.0, n_max=64,
                        failures=fs)
    # failover churn spins many short-lived CPU workers: size the CPU
    # table region up so slot_overflow stays 0 (the exactness gate)
    b = simulate_events_batched(arr, 1.0, QFLEET, dispatcher=disp,
                                horizon_s=HORIZON, deadline_s=2.0, n_max=64,
                                w_fpga=16, w_cpu=128, failures=fs)
    assert b.breakdown["slot_overflow"] == 0
    assert_totals_match(a, b, tag=("tight", disp))
    assert a.failure_misses > 0 and a.crashes > 0
    assert a.failure_misses <= a.deadline_misses
    assert a.recovered_requests + a.failure_misses > 0


# ----------------------------------------------------- planning contracts

@settings(max_examples=8, deadline=None)
@given(disabled=shared.disabled_failure_specs(),
       disp=shared.dispatcher_names)
def test_plan_groups_disabled_specs_with_none(disabled, disp):
    """failures=None and ANY disabled spec (all-zero FailureSpec, any
    enabled spec scaled to 0.0 — drawn from tests/strategies.py) must
    share one FSTAT_OFF program group — no recompile for a disabled
    axis."""
    arr = bursty_trace(3)
    base = [EventCell(disp, arr, 1.0, QFLEET, horizon_s=HORIZON)]
    mixed = base + [
        EventCell(disp, arr, 1.0, QFLEET, horizon_s=HORIZON,
                  failures=disabled)]
    p0 = plan_events(base, n_max=64, w_fpga=16, w_cpu=32)
    p1 = plan_events(mixed, n_max=64, w_fpga=16, w_cpu=32)
    assert p1.n_dispatches == p0.n_dispatches == 1
    assert all(d.static[-1] == FSTAT_OFF for d in p1.dispatches)
    p2 = plan_events(mixed + [EventCell(
        disp, arr, 1.0, QFLEET, horizon_s=HORIZON,
        failures=FSPECS["crashy"])], n_max=64, w_fpga=16, w_cpu=32)
    assert p2.n_dispatches == 2      # the enabled cell gets its own group


@settings(max_examples=8, deadline=None)
@given(fs=shared.failure_specs())
def test_drawn_spec_normalization_consistent(fs):
    """`normalized()` is the single switch: a spec normalizing to None
    must plan into the FSTAT_OFF group; one that survives must not."""
    arr = bursty_trace(4)
    cell = EventCell("spork", arr, 1.0, QFLEET, horizon_s=HORIZON,
                     failures=fs)
    plan = plan_events([cell], n_max=64, w_fpga=16, w_cpu=32)
    is_off = plan.dispatches[0].static[-1] == FSTAT_OFF
    assert is_off == (fs.normalized() is None)


def test_scenario_failure_inheritance():
    """Cells inherit the scenario's fault profile unless they pin their
    own (the chaos_suite baseline contract)."""
    from repro.workloads import registry
    spec = registry.get_chaos("crash_storm")
    inherit, pinned, stripped = resolve_scenarios([
        EventCell("spork", fleet=QFLEET, scenario=spec, seed=0),
        EventCell("spork", fleet=QFLEET, scenario=spec, seed=0,
                  failures=FSPECS["flaky"]),
        EventCell("spork", fleet=QFLEET, scenario=spec.with_(failures=None),
                  seed=0)])
    assert inherit.failures == spec.failures
    assert pinned.failures == FSPECS["flaky"]
    assert stripped.failures is None


def test_rate_sweep_fluidizes_failures():
    """The rate simulator has no worker identity: a failure-bearing
    SweepCell must run as its degraded-fleet equivalent, exactly."""
    from repro.core.traces import synthetic_trace
    tr = synthetic_trace(seed=0, horizon_s=300, request_size_s=0.05,
                         mean_demand_workers=20.0)
    fs = FSPECS["combined"]
    a = sweep([SweepCell("spork", tr.counts, 0.05, DEFAULT_FLEET,
                         failures=fs)])
    b = sweep([SweepCell("spork", tr.counts, 0.05,
                         fs.degrade_fleet(DEFAULT_FLEET))])
    for f, x, y in zip(a.accum._fields, a.accum, b.accum):
        assert np.array_equal(np.asarray(x), np.asarray(y)), f


# ------------------------------------------------------- mesh backend

_TWO_DEV = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=2"
    os.environ["JAX_PLATFORMS"] = "cpu"
    os.environ.pop("BENCH_SWEEP_BACKEND", None)
    import sys
    sys.path.insert(0, "src")
    import jax
    assert jax.device_count() == 2, jax.devices()
    import numpy as np
    from repro.core.workers import DEFAULT_FLEET
    from repro.ft.failures import FailureSpec
    from repro.sim.exec import LocalBackend, MeshBackend
    from repro.sim.sweep import EventCell, sweep_events
    %s
""")


def test_mesh_backend_bit_identical_with_failures():
    """The failure axis must shard like every other axis: a forced
    2-device mesh matches the local path bit for bit, counters included."""
    body = textwrap.dedent("""
    QFLEET = DEFAULT_FLEET.replace(cpu=DEFAULT_FLEET.cpu.replace(
        spin_up_s=1.0))
    fs = FailureSpec(spinup_fail_p=0.25, max_retries=1, crash_p=0.0625,
                     max_failover=2, retry_backoff_s=2.0, seed=11)
    rng = np.random.default_rng(0)
    arr = np.sort(rng.integers(0, 60 * 8, 400)) / 8.0
    cells = [EventCell(d, arr, 1.0, QFLEET, horizon_s=60.0, failures=f)
             for d in ("spork", "index_packing", "round_robin")
             for f in (fs, None)]
    el = sweep_events(cells, n_max=64, w_fpga=16, w_cpu=32,
                      backend=LocalBackend())
    em = sweep_events(cells, n_max=64, w_fpga=16, w_cpu=32,
                      backend=MeshBackend())
    assert set(em.dispatch_devices) == {2}, em.dispatch_devices
    n_crash = 0
    for ta, tb in zip(el, em):
        for f in ("energy_j", "cost_usd", "wasted_spinup_j", "requests",
                  "deadline_misses", "fpga_spinups", "cpu_spinups",
                  "retries", "failed_spinups", "crashes",
                  "recovered_requests", "failure_misses"):
            assert getattr(ta, f) == getattr(tb, f), f
        n_crash += ta.crashes
    assert n_crash > 0
    print("MESH_FAIL_BITWISE_OK")
    """)
    script = _TWO_DEV % body
    out = subprocess.run([sys.executable, "-c", script],
                         capture_output=True, text=True,
                         cwd=os.path.dirname(os.path.dirname(__file__)))
    assert out.returncode == 0, out.stderr[-3000:]
    assert "MESH_FAIL_BITWISE_OK" in out.stdout
