"""How the DES and fleet planners pack cells into dispatches.

`repro.sim.plan._pack_by_length` sorts each static key's cells by padded
entry count, longest first, and cuts them into runs of `EV_CHUNK_MAX`:
a dispatch scans its longest cell's padded length, and shorter cells
ride along on inert entries. `_group_exact` below is the earlier rule,
kept verbatim as the oracle: one group per (padded entry count, static
key), each cut into chunks of its own power-of-two width. The new rule
may never scan more serial entries than the oracle, and every cell's
totals must stay bit-identical to the cell swept alone.
"""

import json
import math
import os

import numpy as np
import pytest

from perfbench.engines import events
from repro.core.workers import DEFAULT_FLEET
from repro.fleet import FleetCell, TenantSpec
from repro.ft.failures import FailureSpec
from repro.sim.events_batched import EV_CHUNK_MAX, _entries, _pad_pow2
from repro.sim.plan import _pack_by_length, _round_entries
from repro.sim.sweep import EventCell, sweep_events, sweep_fleet

CONFIG = os.path.join(os.path.dirname(__file__), "..", "perfbench",
                      "configs", "spork-table9-des.json")
SMALL = dict(n_max=64, w_fpga=16, w_cpu=32)


def _group_exact(groups: dict, entries: dict) -> list[tuple]:
    """The earlier packing, as ``(key, E, chunk, cells)`` per dispatch:
    cells grouped by (E, static key), each group cut into chunks of
    ``_pad_pow2(len(group), lo=4, hi=EV_CHUNK_MAX)``."""
    exact: dict[tuple, list[int]] = {}
    for key, idxs in groups.items():
        for i in idxs:
            n_e = len(entries[i])
            E = (_pad_pow2(n_e, lo=4) if n_e <= 256
                 else 256 * int(math.ceil(n_e / 256)))
            exact.setdefault((E, key), []).append(i)
    out = []
    for (E, key), idxs in exact.items():
        chunk = _pad_pow2(len(idxs), lo=4, hi=EV_CHUNK_MAX)
        start = 0
        while start < len(idxs):
            sl = idxs[start:start + chunk]
            start += chunk
            out.append((key, E, chunk, sl))
    return out


def _random_mix(seed: int):
    """1 to 80 cells of 1 to 6,000 entries under up to three static
    keys; entry streams stand in as ranges (only their length counts)."""
    rng = np.random.default_rng(seed)
    n = int(rng.integers(1, 81))
    keys = [("off",), ("crashy",), (512, "off")][:int(rng.integers(1, 4))]
    lengths = rng.integers(1, 6001, n)
    if seed % 3 == 0:        # few distinct lengths, as a policy grid has
        lengths = rng.choice(lengths[:3], n)
    entries = {i: range(int(m)) for i, m in enumerate(lengths)}
    groups: dict[tuple, list[int]] = {}
    for i in range(n):
        groups.setdefault(keys[int(rng.integers(len(keys)))], []).append(i)
    return groups, entries


@pytest.mark.parametrize("seed", range(40))
def test_packing_covers_each_cell_once_and_never_adds_serial_entries(seed):
    groups, entries = _random_mix(seed)
    packed = _pack_by_length(groups, entries)
    key_of = {i: k for k, idxs in groups.items() for i in idxs}
    seen = [i for _, _, sl, _ in packed for i in sl]
    assert sorted(seen) == sorted(entries)
    for key, E, sl, rows in packed:
        assert 1 <= len(sl) <= EV_CHUNK_MAX
        chunk = len(rows)
        assert 4 <= chunk <= EV_CHUNK_MAX and chunk & (chunk - 1) == 0
        assert chunk == _pad_pow2(len(sl), lo=4, hi=EV_CHUNK_MAX)
        assert rows == sl + [sl[0]] * (chunk - len(sl))
        assert all(key_of[i] == key for i in sl)
        assert all(_round_entries(len(entries[i])) <= E for i in sl)
        assert E == max(_round_entries(len(entries[i])) for i in sl)
    # only a static key's last dispatch may be partial
    for key in groups:
        sizes = [len(sl) for k, _, sl, _ in packed if k == key]
        assert all(s == EV_CHUNK_MAX for s in sizes[:-1])
    oracle = _group_exact(groups, entries)
    assert sum(E for _, E, _, _ in packed) \
        <= sum(E for _, E, _, _ in oracle)
    assert len(packed) <= len(oracle)


@pytest.mark.parametrize("n", [1, 5, 32, 45])
def test_cells_of_one_length_keep_the_exact_grouping(n):
    """A grid whose cells all pad to one length is planned as before:
    the same cells in the same order, at the same E; a partial last
    chunk is padded to its own width, never wider than before."""
    entries = {i: range(300 + i % 7) for i in range(n)}
    groups = {("off",): list(range(n))}
    packed = _pack_by_length(groups, entries)
    oracle = _group_exact(groups, entries)
    assert [(k, E, sl) for k, E, sl, _ in packed] \
        == [(k, E, sl) for k, E, _, sl in oracle]
    assert all(len(rows) <= chunk
               for (*_, rows), (_, _, chunk, _) in zip(packed, oracle))


def _table9_grid(horizon_s: int):
    with open(CONFIG) as f:
        config = json.load(f)
    traffic = {"horizon_s": horizon_s}
    grid = events.realize(config, traffic, events.base(config, traffic),
                          4300000001, 0)
    assert len(grid.cells) == 45
    return config, grid


# horizon -> the plan's (chunk, E) list, serial entries, cells widened
# and the earlier rule's serial entries
TABLE9 = {300: ([(32, 512), (16, 128)], 640, 5, 704),
          3600: ([(32, 5120), (16, 1024)], 6144, 29, 19968)}


@pytest.mark.parametrize("horizon_s", list(TABLE9))
def test_the_benchmark_grid_packs_into_two_dispatches(horizon_s):
    shapes, serial, widened, exact_serial = TABLE9[horizon_s]
    config, grid = _table9_grid(horizon_s)
    plan = events.plan(config, grid)
    assert [(d.chunk, d.arrays["times"].shape[1])
            for d in plan.dispatches] == shapes
    assert plan.meta["serial_entries"] == serial
    assert plan.meta["cells_widened"] == widened
    assert plan.meta["cells"] == 45
    entries = {i: _entries(np.asarray(c.arrival_times), c.fleet.T_s,
                           c.horizon_s) for i, c in enumerate(grid.cells)}
    oracle = _group_exact({("off",): list(entries)}, entries)
    assert sum(E for _, E, _, _ in oracle) == exact_serial


# ------------------------------------------------------ bit identity
HORIZON = 60.0
FAILURES = FailureSpec(spinup_fail_p=0.125, max_retries=1, crash_p=0.0625,
                       straggler_frac=0.125, seed=11)


def _event_grid() -> list:
    """Three stream lengths (E 8, 16 and 32 entries) x three
    dispatchers, and two failure-enabled cells of different lengths
    that share one dispatch."""
    rng = np.random.default_rng(11)
    streams = [np.sort(rng.uniform(0.0, HORIZON, n))
               for n in (40, 1500, 3000)]
    cells = [EventCell(d, t, 0.05, DEFAULT_FLEET, horizon_s=HORIZON)
             for t in streams
             for d in ("spork", "round_robin", "index_packing")]
    cells += [EventCell("spork", t, 0.05, DEFAULT_FLEET, horizon_s=HORIZON,
                        failures=FAILURES) for t in streams[::2]]
    return cells


def test_packed_event_grid_is_bit_identical_to_each_cell_alone():
    cells = _event_grid()
    grid = sweep_events(cells, **SMALL)
    assert grid.meta["cells_widened"] > 0
    assert {len(c.arrival_times) for c in cells} == {40, 1500, 3000}
    for i, c in enumerate(cells):
        alone = sweep_events([c], **SMALL)
        assert alone.meta["cells_widened"] == 0
        assert grid[i] == alone[0]


def _fleet_grid() -> list:
    rng = np.random.default_rng(12)
    tenants = tuple(
        TenantSpec(arrival_times=tuple(np.sort(rng.integers(0, 480, n)) / 8),
                   request_size_s=0.125, seed=i)
        for i, n in enumerate((60, 1500)))
    return [FleetCell(tenants=tenants[:k], admission=a, horizon_s=HORIZON)
            for k in (1, 2) for a in ("admit_all", "token_bucket")]


def test_packed_fleet_grid_is_bit_identical_to_each_cell_alone():
    cells = _fleet_grid()
    grid = sweep_fleet(cells, **SMALL)
    assert grid.n_dispatches == 1 and grid.meta["cells_widened"] == 2
    for i, c in enumerate(cells):
        alone = sweep_fleet([c], **SMALL)
        assert grid[i] == alone[0]
        assert grid.tenants(i) == alone.tenants(0)
