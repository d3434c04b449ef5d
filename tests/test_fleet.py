"""Multi-tenant fleet layer: oracle equivalence + conservation.

`repro.fleet.oracle.FleetSim` (tenant-tagged serial DES) is ground
truth; the batched engine (`repro.fleet.engine`, via `plan_fleet` +
either backend) must match it EXACTLY on every integer counter —
per-tenant offered/admitted/shed/missed and the fleet totals — and to
~1e-5 relative on energy/cost, on dyadic-grid instances. Summed
`repro.core.metrics.TenantTotals` rows must reconcile with the fleet
`RunTotals` (`repro.sim.harness.check_fleet_result`, default-on).
"""

import os
import subprocess
import sys
import textwrap

import numpy as np
import pytest

try:
    from hypothesis import given, settings
except ImportError:                                  # pragma: no cover
    from _hypothesis_shim import given, settings

import strategies as shared

from repro.core.workers import DEFAULT_FLEET
from repro.fleet import (FleetCell, TenantSpec, resolve_fleet_cell,
                         simulate_fleet)
from repro.ft.failures import FailureSpec
from repro.policies import admission_policy_names, get_admission_policy
from repro.policies.admission import IntervalQuota, TokenBucket
from repro.sim.harness import check_fleet_result
from repro.sim.plan import plan_fleet
from repro.sim.sweep import sweep_fleet
from repro.workloads import tenant_population

QFLEET = DEFAULT_FLEET.replace(cpu=DEFAULT_FLEET.cpu.replace(spin_up_s=1.0))

EXACT_FIELDS = ("requests", "deadline_misses", "fpga_spinups",
                "cpu_spinups", "work_on_fpga_cpu_s", "work_on_cpu_cpu_s")
CLOSE_FIELDS = ("energy_j", "cost_usd")
ROW_EXACT = ("requests", "admitted", "shed", "deadline_misses")
ROW_CLOSE = ("work_on_fpga_cpu_s", "work_on_cpu_cpu_s", "energy_j",
             "cost_usd")


def dyadic_tenants(seed: int = 0, n: int = 3, n_arr: int = 120,
                   horizon: float = 60.0) -> tuple:
    """Explicit-stream tenants on the integer/8 grid with dyadic sizes
    — the engines' exactness contract."""
    rng = np.random.default_rng(seed)
    sizes = (0.125, 0.25, 0.0625)
    slos = ("standard", "tight", "relaxed")
    weights = (1.0, 0.5, 2.0)
    return tuple(
        TenantSpec(arrival_times=tuple(
                       np.sort(rng.integers(0, int(horizon) * 8,
                                            n_arr)) / 8.0),
                   request_size_s=sizes[i % 3], slo=slos[i % 3],
                   weight=weights[i % 3], seed=seed + i)
        for i in range(n))


def assert_fleet_match(cell: FleetCell, n_max: int = 64,
                       exact_work: bool = True):
    """Oracle vs batched on one cell; returns (totals, rows) pairs.

    ``exact_work=False`` for scenario-realized sizes (not on the dyadic
    grid, so the f32 work accumulators only match to rounding; every
    integer counter still matches exactly)."""
    at, ar = simulate_fleet(cell, n_max=n_max)
    res = sweep_fleet([cell], n_max=n_max, w_fpga=16, w_cpu=32)
    check_fleet_result(res)
    bt, br = res.totals(0), res.tenants(0)
    assert bt.breakdown["slot_overflow"] == 0
    exact = EXACT_FIELDS if exact_work else tuple(
        f for f in EXACT_FIELDS if not f.startswith("work_"))
    close = CLOSE_FIELDS if exact_work else CLOSE_FIELDS + tuple(
        f for f in EXACT_FIELDS if f.startswith("work_"))
    for f in exact:
        assert getattr(at, f) == getattr(bt, f), \
            f"{f}: oracle={getattr(at, f)} batched={getattr(bt, f)}"
    for k in ("offered_requests", "shed_requests"):
        assert at.breakdown[k] == bt.breakdown[k], k
    for f in close:
        np.testing.assert_allclose(getattr(bt, f), getattr(at, f),
                                   rtol=1e-4, atol=1e-3, err_msg=f)
    assert len(ar) == len(br) == cell.n_tenants
    for i, (ra, rb) in enumerate(zip(ar, br)):
        for f in ROW_EXACT:
            assert getattr(ra, f) == getattr(rb, f), \
                f"tenant {i} {f}: oracle={getattr(ra, f)} " \
                f"batched={getattr(rb, f)}"
        for f in ROW_CLOSE:
            np.testing.assert_allclose(
                getattr(rb, f), getattr(ra, f), rtol=1e-4, atol=1e-3,
                err_msg=f"tenant {i} {f}")
    return (at, ar), (bt, br)


# ------------------------------------------------------ oracle equivalence

@pytest.mark.parametrize("admission", admission_policy_names())
def test_equivalence_explicit_streams(admission):
    for disp in ("spork", "round_robin"):
        cell = FleetCell(tenants=dyadic_tenants(seed=3), admission=admission,
                         dispatcher=disp, fleet=QFLEET, horizon_s=60.0)
        assert_fleet_match(cell)


@pytest.mark.parametrize("admission", admission_policy_names())
def test_equivalence_scenario_population(admission):
    cell = FleetCell(tenants=tenant_population(8, mean_demand_workers=0.2,
                                               horizon_s=60.0),
                     admission=admission, fleet=QFLEET)
    (at, _), _ = assert_fleet_match(cell, exact_work=False)
    assert at.requests > 0


def test_equivalence_with_failures():
    fs = FailureSpec(spinup_fail_p=0.25, max_retries=1, crash_p=0.0625,
                     max_failover=2, retry_backoff_s=2.0, seed=11)
    cell = FleetCell(tenants=dyadic_tenants(seed=5, n_arr=200),
                     admission="token_bucket", fleet=QFLEET,
                     horizon_s=60.0, failures=fs)
    (at, _), (bt, _) = assert_fleet_match(cell)
    for f in ("retries", "failed_spinups", "crashes",
              "recovered_requests", "failure_misses"):
        assert getattr(at, f) == getattr(bt, f), f
    assert at.crashes + at.failed_spinups > 0


@settings(max_examples=6, deadline=None)
@given(cell=shared.fleet_cells())
def test_equivalence_property(cell):
    assert_fleet_match(cell)


SEAM_ARRAYS = ("times", "tick_t", "is_tick", "scalars", "fail_seed",
               "max_fpgas", "allocate", "codes")


@pytest.mark.parametrize("dispatcher", ["round_robin", "index_packing",
                                        "spork"])
def test_one_tenant_fleet_cell_is_its_event_cell(dispatcher):
    """A one-tenant `admit_all` fleet cell and the `EventCell` with its
    stream, size, deadline, fleet and dispatcher share the DES pipeline:
    their plans carry equal DES arrays and their dispatches give equal
    `Accum`, `FailAcc` and overflow outputs, bit for bit."""
    from repro.sim.exec import LocalBackend
    from repro.sim.plan import plan_events
    from repro.sim.sweep import EventCell
    (tenant,) = dyadic_tenants(seed=9, n=1, n_arr=300)
    fcell = FleetCell(tenants=(tenant,), dispatcher=dispatcher,
                      fleet=QFLEET, horizon_s=60.0)
    ecell = EventCell(dispatcher, np.asarray(tenant.arrival_times),
                      tenant.request_size_s, QFLEET, horizon_s=60.0,
                      deadline_s=10.0 * tenant.request_size_s)
    kw = dict(n_max=64, w_fpga=16, w_cpu=32)
    (fd,) = plan_fleet([fcell], **kw).dispatches
    (ed,) = plan_events([ecell], **kw).dispatches
    for k in SEAM_ARRAYS:
        assert fd.arrays[k].dtype == ed.arrays[k].dtype, k
        np.testing.assert_array_equal(fd.arrays[k], ed.arrays[k], err_msg=k)
    acc_f, fail_f, over_f, _ = LocalBackend().run(fd)
    acc_e, fail_e, over_e = LocalBackend().run(ed)
    for name, a, b in ((("acc", acc_f, acc_e), ("fail", fail_f, fail_e))):
        for f, x, y in zip(a._fields, a, b):
            np.testing.assert_array_equal(x, y, err_msg=f"{name}.{f}")
    np.testing.assert_array_equal(over_f, over_e)
    assert float(acc_e.missed_requests[0]) >= 0 and float(acc_e.cost[0]) > 0


# ---------------------------------------------------- admission + fairness

def test_admission_sheds_and_conserves():
    """A starved token bucket sheds; offered = admitted + shed per
    tenant; heavier-weight tenants get proportionally more budget."""
    cell = FleetCell(tenants=dyadic_tenants(seed=7, n_arr=240),
                     admission=TokenBucket(rate=0.5, burst=2.0),
                     fleet=QFLEET, horizon_s=60.0)
    totals, rows = simulate_fleet(cell, n_max=64)
    assert totals.breakdown["shed_requests"] > 0
    for r in rows:
        assert r.requests == r.admitted + r.shed
        assert r.deadline_misses <= r.admitted
    # weight 2.0 tenant admits at >= the rate of the weight 0.5 tenant
    frac = [r.admitted / r.requests for r in rows]
    assert frac[2] >= frac[1]


def test_interval_quota_resets_each_tick():
    """quota=2 per allocator interval: admitted counts track the number
    of intervals, not the offered load."""
    arr = tuple(np.arange(400) * 0.125)   # 50 s of 8 req/s
    cell = FleetCell(
        tenants=(TenantSpec(arrival_times=arr, request_size_s=0.125),),
        admission=IntervalQuota(quota=2.0), fleet=QFLEET, horizon_s=60.0)
    (at, ar), _ = assert_fleet_match(cell)
    n_intervals = int(np.ceil(60.0 / cell.fleet.T_s))
    assert 0 < ar[0].admitted <= 2 * n_intervals
    assert ar[0].shed == 400 - ar[0].admitted


def test_cross_tenant_interference():
    """A bursty co-tenant on the SAME fleet degrades a steady tenant's
    SLO attainment vs running alone — the effect the admission layer
    exists to bound."""
    steady = TenantSpec(arrival_times=tuple(np.arange(480) / 8.0),
                        request_size_s=0.125, slo="tight")
    burst_t = np.sort(np.concatenate(
        [np.full(64, 20.0), np.full(64, 30.0), np.full(64, 40.0)]))
    bursty = TenantSpec(arrival_times=tuple(burst_t), request_size_s=0.5,
                        slo="relaxed")
    alone = simulate_fleet(FleetCell(tenants=(steady,), fleet=QFLEET,
                                     horizon_s=60.0), n_max=64)[1]
    shared_rows = simulate_fleet(FleetCell(tenants=(steady, bursty),
                                           fleet=QFLEET, horizon_s=60.0),
                                 n_max=64)[1]
    assert shared_rows[0].admitted == alone[0].admitted   # admit_all
    assert shared_rows[0].deadline_misses > alone[0].deadline_misses


def test_admission_instance_vs_name():
    """Default-parameter instances and registry names resolve to the
    same decisions (cells hash either way)."""
    t = dyadic_tenants(seed=9)
    a = simulate_fleet(FleetCell(tenants=t, admission="token_bucket",
                                 fleet=QFLEET, horizon_s=60.0))[0]
    b = simulate_fleet(FleetCell(tenants=t, admission=TokenBucket(),
                                 fleet=QFLEET, horizon_s=60.0))[0]
    assert a.requests == b.requests
    assert a.breakdown["shed_requests"] == b.breakdown["shed_requests"]


# --------------------------------------------------- scale + dispatch budget

def test_1024_tenant_grid_dispatch_budget():
    """The acceptance bar: a 1024-tenant population x 3 admission
    policies plans into <= 8 dispatches and executes end-to-end on the
    local backend with the conservation guards on."""
    tenants = tenant_population(1024)
    cells = [FleetCell(tenants=tenants, admission=a)
             for a in admission_policy_names()]
    plan = plan_fleet(cells)
    assert plan.n_dispatches <= 8, plan.n_dispatches
    res = sweep_fleet(cells)
    check_fleet_result(res)
    for i in range(len(cells)):
        t = res.totals(i)
        assert t.breakdown["offered_requests"] > 0
        assert len(res.tenants(i)) == 1024
    # the restrictive policies actually shed at this density
    assert res.totals(1).breakdown["shed_requests"] > 0
    assert res.totals(2).breakdown["shed_requests"] > 0


def test_1024_tenant_mesh_matches_local():
    """Forced-2-device mesh: same grid, bit-identical counters."""
    body = textwrap.dedent("""
    from repro.fleet import FleetCell
    from repro.policies import admission_policy_names
    from repro.sim.exec import LocalBackend, MeshBackend
    from repro.sim.sweep import sweep_fleet
    from repro.workloads import tenant_population
    tenants = tenant_population(256)
    cells = [FleetCell(tenants=tenants, admission=a)
             for a in admission_policy_names()]
    rl = sweep_fleet(cells, backend=LocalBackend())
    rm = sweep_fleet(cells, backend=MeshBackend())
    assert rm.n_dispatches <= 8, rm.n_dispatches
    assert set(rm.dispatch_devices) == {2}, rm.dispatch_devices
    for i in range(len(cells)):
        ta, tb = rl.totals(i), rm.totals(i)
        assert ta.requests == tb.requests
        assert ta.deadline_misses == tb.deadline_misses
        assert ta.breakdown["shed_requests"] == \\
            tb.breakdown["shed_requests"]
        assert ta.energy_j == tb.energy_j
        for ra, rb in zip(rl.tenants(i), rm.tenants(i)):
            assert ra.admitted == rb.admitted and ra.shed == rb.shed
    print("FLEET_MESH_BITWISE_OK")
    """)
    script = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=2"
    os.environ["JAX_PLATFORMS"] = "cpu"
    os.environ.pop("BENCH_SWEEP_BACKEND", None)
    import sys
    sys.path.insert(0, "src")
    import jax
    assert jax.device_count() == 2, jax.devices()
    """) + body
    out = subprocess.run([sys.executable, "-c", script],
                         capture_output=True, text=True,
                         cwd=os.path.dirname(os.path.dirname(__file__)))
    assert out.returncode == 0, out.stderr[-3000:]
    assert "FLEET_MESH_BITWISE_OK" in out.stdout


# ------------------------------------------------------- checkpoint/resume

def test_fleet_checkpoint_resume_bit_identical(tmp_path):
    cells = [FleetCell(tenants=dyadic_tenants(seed=s), admission=a,
                       fleet=QFLEET, horizon_s=60.0)
             for s in (0, 1) for a in ("admit_all", "token_bucket")]
    r1 = sweep_fleet(cells, n_max=64, w_fpga=16, w_cpu=32,
                     checkpoint_dir=tmp_path)
    r2 = sweep_fleet(cells, n_max=64, w_fpga=16, w_cpu=32,
                     checkpoint_dir=tmp_path)
    assert r1.meta["executed_chunks"] == r1.n_dispatches > 0
    assert r2.meta["executed_chunks"] == 0
    assert r2.meta["restored_chunks"] == r1.n_dispatches
    for i in range(len(cells)):
        ta, tb = r1.totals(i), r2.totals(i)
        assert ta.requests == tb.requests
        assert ta.energy_j == tb.energy_j
        for ra, rb in zip(r1.tenants(i), r2.tenants(i)):
            assert ra.row() == rb.row()


# ------------------------------------------------------------ spec hygiene

def test_spec_validation():
    with pytest.raises(ValueError):
        TenantSpec()                                     # no demand source
    with pytest.raises(ValueError):
        TenantSpec(arrival_times=(1.0, 2.0))             # no size
    with pytest.raises(ValueError):
        TenantSpec(arrival_times=(2.0, 1.0), request_size_s=0.1)  # unsorted
    with pytest.raises(ValueError):
        TenantSpec(arrival_times=(1.0,), request_size_s=0.1, slo="gold")
    with pytest.raises(ValueError):
        TenantSpec(arrival_times=(1.0,), request_size_s=0.1, weight=0.0)
    with pytest.raises(ValueError):
        FleetCell(tenants=())
    with pytest.raises(ValueError):
        FleetCell(tenants=dyadic_tenants(), admission="nope")
    # conflicting per-tenant fault models on one shared fleet
    t = dyadic_tenants(n=2)
    bad = (TenantSpec(arrival_times=t[0].arrival_times, request_size_s=0.125,
                      failures=FailureSpec(crash_p=0.0625, seed=1)),
           TenantSpec(arrival_times=t[1].arrival_times, request_size_s=0.125,
                      failures=FailureSpec(crash_p=0.125, seed=2)))
    with pytest.raises(ValueError):
        resolve_fleet_cell(FleetCell(tenants=bad, horizon_s=60.0))


def test_resolved_stream_is_stable_merge():
    """Equal-time arrivals keep tenant-index order (the documented
    cross-engine tie rule)."""
    t0 = TenantSpec(arrival_times=(1.0, 2.0, 2.0), request_size_s=0.125)
    t1 = TenantSpec(arrival_times=(2.0, 3.0), request_size_s=0.125)
    rs = resolve_fleet_cell(FleetCell(tenants=(t0, t1), horizon_s=10.0))
    np.testing.assert_array_equal(rs.times, [1.0, 2.0, 2.0, 2.0, 3.0])
    np.testing.assert_array_equal(rs.tids, [0, 0, 0, 1, 1])


@pytest.mark.parametrize("admission", ["admit_all", "token_bucket"])
def test_array_and_tuple_streams_give_identical_results(admission):
    """A stream given as a float64 array and as a tuple of floats builds
    equal specs, and cells built either way give bit-identical totals
    and tenant rows, each resolved from scratch."""
    from repro.fleet.specs import merge_population
    rng = np.random.default_rng(13)
    raw = [np.sort(rng.integers(0, 480, n)) / 8.0 for n in (50, 90, 140)]

    def cell(streams):
        return FleetCell(tenants=tuple(
            TenantSpec(arrival_times=a, request_size_s=0.125,
                       weight=(0.5, 1.0, 2.0)[i])
            for i, a in enumerate(streams)),
            admission=admission, fleet=QFLEET, horizon_s=60.0)

    by_array = cell([a.copy() for a in raw])
    by_tuple = cell([tuple(float(t) for t in a) for a in raw])
    assert by_array == by_tuple and hash(by_array) == hash(by_tuple)
    out = []
    for c in (by_array, by_tuple):
        resolve_fleet_cell.cache_clear()
        merge_population.cache_clear()
        res = sweep_fleet([c], n_max=64, w_fpga=16, w_cpu=32)
        out.append((res.totals(0), [r.row() for r in res.tenants(0)]))
    (ta, rows_a), (tb, rows_b) = out
    for f in EXACT_FIELDS + CLOSE_FIELDS:
        assert getattr(ta, f) == getattr(tb, f), f
    assert ta.breakdown == tb.breakdown
    assert rows_a == rows_b


def test_population_shared_by_cells_merges_once():
    """Cells that share a tenant population (differing in dispatcher and
    admission) share one merged stream, merged once per plan; a spec's
    stream is a read-only array and a cell hashes without walking it."""
    from repro.fleet.specs import merge_population
    tenants = dyadic_tenants(seed=21, n=3)
    cells = [FleetCell(tenants=tenants, dispatcher=d, admission=a,
                       fleet=QFLEET, horizon_s=60.0)
             for d in ("spork", "round_robin")
             for a in ("admit_all", "token_bucket")]
    before = merge_population.cache_info().misses
    plan = plan_fleet(cells, n_max=64, w_fpga=16, w_cpu=32)
    assert merge_population.cache_info().misses == before + 1
    assert plan.meta["populations"] == 1
    rs = [resolve_fleet_cell(c) for c in cells]
    assert all(r.times is rs[0].times and r.tids is rs[0].tids for r in rs)
    assert rs[0].adm_burst is not rs[1].adm_burst    # per-cell knobs
    with pytest.raises(ValueError):
        tenants[0].arrival_times[0] = 1.0
    with pytest.raises(ValueError):
        rs[0].times[0] = 1.0
    assert cells[0].population == cells[1].population
    assert hash(cells[0].population) == cells[0].population._hash


def test_stream_is_copied_unless_nothing_can_write_to_it():
    """A read-only view of a writable buffer is copied, so writing to the
    buffer later moves neither the spec's stream nor its identity; an
    array that nothing can write to is kept as it is."""
    buf = np.array([0.5, 1.0, 2.0, 4.0])
    view = buf[:3]
    view.setflags(write=False)
    spec = TenantSpec(arrival_times=view, request_size_s=0.1)
    twin = TenantSpec(arrival_times=(0.5, 1.0, 2.0), request_size_s=0.1)
    assert spec.arrival_times is not view and spec == twin
    buf[0] = 3.0
    np.testing.assert_array_equal(spec.arrival_times, [0.5, 1.0, 2.0])
    assert spec == twin and hash(spec) == hash(twin)
    owned = np.array([0.5, 1.0, 2.0])
    owned.setflags(write=False)
    part = owned[1:]
    assert TenantSpec(arrival_times=owned,
                      request_size_s=0.1).arrival_times is owned
    assert TenantSpec(arrival_times=part,
                      request_size_s=0.1).arrival_times is part


def test_cells_fingerprint_again_where_they_are_loaded():
    """A pickled cell is rebuilt through its constructor, so its cached
    hash is the loading process's own: another process (string hashes
    seeded differently) finds it equal to, and in a dict with, a cell it
    built itself."""
    import pickle
    tenants = dyadic_tenants(seed=2, n=2)
    cell = FleetCell(tenants=tenants, admission=TokenBucket(rate=0.5),
                     fleet=QFLEET, horizon_s=60.0)
    assert pickle.loads(pickle.dumps(cell)) == cell
    script = textwrap.dedent("""
    import pickle, sys
    sys.path.insert(0, "src")
    import test_fleet as t
    from repro.fleet import FleetCell
    from repro.policies.admission import TokenBucket
    got = pickle.loads(sys.stdin.buffer.read())
    mine = FleetCell(tenants=t.dyadic_tenants(seed=2, n=2),
                     admission=TokenBucket(rate=0.5), fleet=t.QFLEET,
                     horizon_s=60.0)
    assert got == mine and {mine: 1}[got] == 1
    assert {t_: 1 for t_ in mine.tenants}[got.tenants[1]] == 1
    print("REBUILT_OK")
    """)
    root = os.path.dirname(os.path.dirname(__file__))
    env = dict(os.environ, PYTHONHASHSEED="12345", JAX_PLATFORMS="cpu",
               PYTHONPATH=os.pathsep.join([os.path.join(root, "tests"),
                                           os.path.join(root, "src")]))
    out = subprocess.run([sys.executable, "-c", script],
                         input=pickle.dumps(cell), capture_output=True,
                         cwd=root, env=env)
    assert out.returncode == 0, out.stderr[-3000:]
    assert b"REBUILT_OK" in out.stdout


def test_tenant_population_shape():
    pop = tenant_population(16, zipf_a=1.0, seed=3)
    assert len(pop) == 16
    w = np.array([t.weight for t in pop])
    np.testing.assert_allclose(w.mean(), 1.0, rtol=1e-12)
    assert w[0] == w.max()
    # quantized demand -> few distinct scenario variants
    assert len({t.scenario for t in pop}) <= 6
    slos = {t.slo for t in pop}
    assert slos == {"tight", "standard", "relaxed"}
    # population must resolve + admit params for every registered policy
    for a in admission_policy_names():
        rate, burst, quota = get_admission_policy(a).tenant_params(w)
        assert len(rate) == len(burst) == len(quota) == 16
