"""Ahead-of-time compiles for a described TPU v5e: no chip needed.

The TPU compiler is installed with JAX and compiles for a chip that is
described, not attached (`jax.experimental.topologies`). These tests
compile, for one v5e chip of a described ``v5e:2x2`` host:

  * every Pallas kernel in `repro.kernels` with ``interpret=False`` at
    the widths the simulator grids use (a kernel Mosaic cannot lower
    fails here, not on the chip);
  * one 32-cell chunk of the DES engine (W = 32 + 64 worker slots,
    ``n_max=128``), one fleet-engine chunk, the benchmark's FaaS pool
    fleet chunk (W = 192 + 320, ``n_max=256``, 8,192 tenants, 8 lanes
    at E 5,120) and one rate-engine chunk — the programs `repro.sim.exec.LocalBackend`
    dispatches;
  * the `MeshBackend` program of a DES chunk over all four chips.

The topology is described inside a module-scoped fixture (never at
import, so test collection never loads the TPU library), and the
persistent compilation cache is off around these compiles: a program
compiled for a described chip cannot be read back without one.
"""

import os

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P
from jax.sharding import SingleDeviceSharding


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — any failure means "cannot"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module", autouse=True)
def no_persistent_cache():
    from jax.experimental.compilation_cache import compilation_cache as cc
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", prev)
    cc.reset_cache()


def _spec(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _specs_like(tree, sharding):
    return jax.tree.map(lambda a: _spec(np.shape(a), a.dtype, sharding), tree)


def _compile(fn, *args):
    compiled = jax.jit(fn).lower(*args).compile()
    return compiled.as_text()


# --------------------------------------------------------------- kernels
@pytest.mark.parametrize("n", [128, 384, 1024])
def test_minplus_kernel_compiles_for_v5e(one_chip, n):
    from repro.kernels.minplus.minplus import minplus_pallas
    v = _spec((n,), jnp.float32, one_chip)
    hlo = _compile(lambda F, a, b, p: minplus_pallas(F, a, b, p,
                                                     interpret=False),
                   v, v, v, _spec((4,), jnp.float32, one_chip))
    assert "tpu_custom_call" in hlo


@pytest.mark.parametrize("n", [128, 512])
def test_spork_predict_kernel_compiles_for_v5e(one_chip, n):
    from repro.kernels.spork_predict.spork_predict import spork_predict_pallas
    v = _spec((n,), jnp.float32, one_chip)
    hlo = _compile(lambda h, a, p: spork_predict_pallas(h, a, p,
                                                        interpret=False),
                   v, v, _spec((6,), jnp.float32, one_chip))
    assert "tpu_custom_call" in hlo


@pytest.mark.parametrize("shape,dtype", [
    ((8, 16, 8, 128, 4096), jnp.bfloat16),    # GQA 2:1, long cache
    ((4, 6, 2, 128, 1024), jnp.float32),      # GQA 3:1
    ((2, 10, 1, 128, 300), jnp.bfloat16),     # MQA, ragged tail
    ((2, 16, 8, 64, 300), jnp.float32),       # D < 128: head-major copy
])
def test_decode_attn_kernel_compiles_for_v5e(one_chip, shape, dtype):
    from repro.kernels.decode_attn.decode_attn import decode_attention_pallas
    b, hq, hkv, d, s = shape
    hlo = _compile(
        lambda q, k, v, ln: decode_attention_pallas(q, k, v, ln,
                                                    interpret=False),
        _spec((b, hq, d), dtype, one_chip),
        _spec((b, s, hkv, d), dtype, one_chip),
        _spec((b, s, hkv, d), dtype, one_chip),
        _spec((b,), jnp.int32, one_chip))
    assert "tpu_custom_call" in hlo


# ------------------------------------------------------- engine chunks
def _event_plan(n_cells=32):
    from repro.core.workers import DEFAULT_FLEET
    from repro.sim.events import DISPATCHERS
    from repro.sim.plan import plan_events
    from repro.sim.sweep import EventCell
    rng = np.random.default_rng(0)
    cells = [EventCell(DISPATCHERS[i % 3],
                       np.sort(rng.uniform(0.0, 300.0, 600)), 0.25,
                       DEFAULT_FLEET, horizon_s=300.0)
             for i in range(n_cells)]
    plan = plan_events(cells, n_max=128)
    assert len(plan.dispatches) == 1
    return plan.dispatches[0]


def test_events_chunk_compiles_for_v5e(one_chip):
    from repro.sim import events_batched
    from repro.sim.exec import _event_args
    d = _event_plan()
    assert d.chunk == 32 and d.static[:3] == (128, 32, 64)
    args = _specs_like(_event_args(d), one_chip)
    compiled = events_batched._simulate_cells.lower(*d.static, *args) \
        .compile()
    assert compiled.memory_analysis() is not None


def test_fleet_chunk_compiles_for_v5e(one_chip):
    from repro.fleet import engine as fleet_engine
    from repro.policies import admission_policy_names
    from repro.sim.exec import _fleet_args
    from repro.sim.plan import plan_fleet
    from repro.fleet import FleetCell
    from repro.workloads import tenant_population
    pop = tenant_population(64, horizon_s=60.0, mean_demand_workers=0.05,
                            seed=1)
    plan = plan_fleet([FleetCell(tenants=pop, admission=a)
                       for a in admission_policy_names()])
    d = plan.dispatches[0]
    args = _specs_like(_fleet_args(d), one_chip)
    compiled = fleet_engine._simulate_fleet_cells.lower(*d.static, *args) \
        .compile()
    assert compiled.memory_analysis() is not None


def _pool_population(blocks: int, n_tenants: int = 8192,
                     intervals: int = 120, T_s: float = 10.0) -> tuple:
    """A tenant population whose merged stream fills ``blocks`` arrival
    blocks of 128 in every interval: evenly spaced arrivals, dealt to
    the tenants in turn."""
    from repro.fleet import TenantSpec
    per = 128 * blocks
    k = np.repeat(np.arange(intervals), per)
    j = np.tile(np.arange(per), intervals)
    times = T_s * (k + (j + 1) / per)
    return tuple(TenantSpec(arrival_times=times[i::n_tenants],
                            request_size_s=0.05, weight=1.0 + i % 7)
                 for i in range(n_tenants))


def test_fleet_pool_chunk_compiles_for_v5e(one_chip):
    """The benchmark's FaaS pool cell as it is planned: two populations
    of 8,192 tenants over 1200 s, each population's six cells (three
    dispatchers x two admissions) one 8-lane dispatch, at the cell's
    entry counts (E 4,864 and 5,120), on a W = 192 + 320 worker table
    with ``n_max=256``; the longer dispatch is compiled."""
    from repro.core.workers import DEFAULT_FLEET
    from repro.fleet import FleetCell
    from repro.fleet import engine as fleet_engine
    from repro.policies.admission import TokenBucket
    from repro.sim.events import DISPATCHERS
    from repro.sim.exec import _fleet_args
    from repro.sim.plan import plan_fleet
    assert DEFAULT_FLEET.T_s == 10.0
    cells = [FleetCell(tenants=_pool_population(blocks), dispatcher=d,
                       admission=a, horizon_s=1200.0)
             for blocks in (39, 41) for d in DISPATCHERS
             for a in ("admit_all", TokenBucket(rate=4.0, burst=30.0))]
    plan = plan_fleet(cells, n_max=256, w_fpga=192, w_cpu=320)
    shapes = sorted((d.chunk, d.n_real, *d.arrays["times"].shape[1:],
                     d.arrays["ta_size"].shape[1]) for d in plan.dispatches)
    assert shapes == [(8, 6, 4864, 128, 8192), (8, 6, 5120, 128, 8192)]
    d = max(plan.dispatches, key=lambda d: d.arrays["times"].shape[1])
    assert d.static[:3] == (256, 192, 320)
    args = _specs_like(_fleet_args(d), one_chip)
    compiled = fleet_engine._simulate_fleet_cells.lower(*d.static, *args) \
        .compile()
    assert compiled.memory_analysis() is not None


def test_rate_chunk_compiles_for_v5e(one_chip):
    from repro.core.traces import synthetic_trace
    from repro.core.workers import DEFAULT_FLEET
    from repro.sim import ratesim
    from repro.sim.exec import _rate_args
    from repro.sim.plan import plan_sweep
    from repro.sim.sweep import SweepCell
    cells = [SweepCell("spork", synthetic_trace(
                 seed=s, bias=0.6, horizon_s=1800, request_size_s=0.05,
                 mean_demand_workers=20.0).counts, 0.05, DEFAULT_FLEET)
             for s in range(8)]
    d = plan_sweep(cells).dispatches[0]
    args = _specs_like(_rate_args(d), one_chip)
    compiled = ratesim._simulate_cells.lower(*d.static, *args).compile()
    assert compiled.memory_analysis() is not None


def test_mesh_backend_events_chunk_compiles_for_four_v5e_chips(topo):
    """The `MeshBackend` program: one DES chunk shard_map-ped over the
    cell axis of all four chips."""
    from repro.sim.exec import MeshBackend, _event_args
    d = _event_plan()
    backend = MeshBackend(topo.devices)
    assert backend.devices_for(d) == 4
    fn = backend._fn(d.kind, d.static, 4)
    cells = NamedSharding(jax.sharding.Mesh(np.array(topo.devices),
                                            ("cells",)), P("cells"))
    args = _specs_like(_event_args(d), cells)
    compiled = fn.lower(*args).compile()
    per_device = compiled.memory_analysis()
    assert per_device is not None
