"""The planner's interval bucketing (`events_batched._entries`).

`_entries` cuts one time-sorted arrival stream into its interval buckets
with one `np.searchsorted`. `_entries_masked` below is the earlier
algorithm, kept verbatim as the oracle: a mask ``idx == k`` over the
whole stream for each bucket. The two must give the same entries,
element for element, and plans built on either must be bit-identical.
"""

import json
import os

import numpy as np
import pytest

import repro.sim.plan as plan_mod
from perfbench.engines import events
from perfbench.lib import generators as gen
from repro.fleet import FleetCell, TenantSpec
from repro.sim.events_batched import BLOCK, _entries
from repro.sim.plan import plan_fleet

T_S = 10.0
CONFIG = os.path.join(os.path.dirname(__file__), "..", "perfbench",
                      "configs", "spork-table9-des.json")


def _entries_masked(arr: np.ndarray, interval_s: float, horizon: float,
                    payload: np.ndarray | None = None) -> list[tuple]:
    """Flat entry stream for one cell: fixed-width arrival blocks with
    tick markers riding on the last block of each interval. Bucket k
    holds arrivals in ((k-1)*T_s, k*T_s] so every arrival precedes its
    tick (the oracle pops arrivals before same-time events), and the
    final bucket holds the post-last-tick tail.

    With ``payload`` (a per-arrival array aligned with ``arr``, e.g. the
    fleet layer's tenant indices) entries are ``(row, pay_row, tick)``
    3-tuples, the payload sliced identically to the times; otherwise the
    original ``(row, tick)`` 2-tuples."""
    K = int(np.ceil(horizon / interval_s))
    idx = np.minimum(np.ceil(np.asarray(arr, np.float64) / interval_s)
                     .astype(np.int64), K)
    idx = np.maximum(idx, 0)
    out: list[tuple] = []
    for k in range(K + 1):
        sel = idx == k
        b = np.asarray(arr)[sel]
        blocks = ([b[j:j + BLOCK] for j in range(0, len(b), BLOCK)]
                  or [b[:0]])
        if payload is not None:
            p = np.asarray(payload)[sel]
            pblocks = ([p[j:j + BLOCK] for j in range(0, len(p), BLOCK)]
                       or [p[:0]])
        tick = k * interval_s if k < K else None
        if payload is None:
            out.extend((r, None) for r in blocks[:-1])
            out.append((blocks[-1], tick))
        else:
            out.extend((r, pr, None)
                       for r, pr in zip(blocks[:-1], pblocks[:-1]))
            out.append((blocks[-1], pblocks[-1], tick))
    return out


def _bmodel_stream():
    counts = gen.base_counts("bmodel", {"bias": 0.68}, 100, 300, 0.05, 8.0)
    return gen.realize_times(counts, 30, gen.rng_for(4300000001, 0, 0))


# name -> (arrival times, horizon_s)
STREAMS = {
    "empty": (np.zeros(0), 60.0),
    "single": (np.array([12.5]), 60.0),
    "exact_multiples": (np.array([10.0, 10.0, 20.0, 30.0, 30.0, 60.0]), 60.0),
    "at_zero": (np.array([0.0, 0.0, 0.0, 0.5, 10.0]), 60.0),
    "past_horizon": (np.array([5.0, 55.0, 60.0, 61.0, 75.5, 200.0]), 60.0),
    "one_full_bucket": (np.sort(np.random.default_rng(1).uniform(
        20.0, 30.0, 3 * BLOCK + 17)), 60.0),
    "empty_runs": (np.array([3.0, 4.0, 41.0, 95.0, 96.0, 119.0]), 120.0),
    "bmodel_300s": (_bmodel_stream(), 300.0),
}


def _assert_same(got: list, want: list):
    assert len(got) == len(want)
    for g, w in zip(got, want):
        assert len(g) == len(w)
        assert g[-1] == w[-1]
        for a, b in zip(g[:-1], w[:-1]):
            assert a.dtype == b.dtype
            assert np.array_equal(a, b)


@pytest.mark.parametrize("with_payload", [False, True],
                         ids=["times", "payload"])
@pytest.mark.parametrize("name", list(STREAMS))
def test_entries_match_the_masked_oracle(name, with_payload):
    arr, horizon = STREAMS[name]
    payload = (np.arange(len(arr), dtype=np.int32) % 7 if with_payload
               else None)
    want = _entries_masked(arr, T_S, horizon, payload=payload)
    got = _entries(arr, T_S, horizon, payload=payload)
    _assert_same(got, want)


def test_entries_refuse_an_unsorted_stream():
    with pytest.raises(ValueError, match="sorted ascending"):
        _entries(np.array([5.0, 25.0, 12.0]), T_S, 60.0)


def _table9_plan():
    # the benchmark's own grid cut to 3 cases x 1 app x 3 dispatchers, 300 s
    with open(CONFIG) as f:
        config = json.load(f)
    traffic = {"subset": {"apps": [0]}, "horizon_s": 300}
    grid = events.realize(config, traffic, events.base(config, traffic),
                          4300000001, 0)
    assert len(grid.cells) == 9
    return events.plan(config, grid)


def _fleet_cells():
    rng = np.random.default_rng(6)
    tenants = tuple(
        TenantSpec(arrival_times=tuple(np.sort(rng.integers(0, 2400, n)) / 8),
                   request_size_s=0.125, seed=i)
        for i, n in enumerate((300, 3000)))
    return [FleetCell(tenants=tenants[:k], admission=a, horizon_s=300.0)
            for k in (1, 2) for a in ("admit_all", "token_bucket")]


PLANNERS = {
    "events": _table9_plan,
    "fleet": lambda: plan_fleet(_fleet_cells(), n_max=64, w_fpga=16,
                                w_cpu=32),
}


@pytest.mark.parametrize("kind", list(PLANNERS))
def test_plans_are_bit_identical_to_the_masked_oracle(kind, monkeypatch):
    new = PLANNERS[kind]()
    monkeypatch.setattr(plan_mod, "_entries", _entries_masked)
    old = PLANNERS[kind]()
    assert len(new.dispatches) == len(old.dispatches)
    for a, b in zip(new.dispatches, old.dispatches):
        assert a.cell_idx == b.cell_idx
        assert a.static == b.static
        assert a.arrays.keys() == b.arrays.keys()
        for key in a.arrays:
            x, y = np.asarray(a.arrays[key]), np.asarray(b.arrays[key])
            assert x.dtype == y.dtype, key
            assert np.array_equal(x, y), key
    strip = lambda m: {k: v for k, v in m.items() if k != "plan_id"}
    assert strip(new.meta) == strip(old.meta)
