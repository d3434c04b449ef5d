"""The trace reduction against a small trace recorded on one TPU v5e
(`perfbench/tests/record_trace.py`): it gives back the numbers that run
reported, the program runs it reads from the host plane match the
device's own module events, and the breakdown is bounded and named."""

import glob
import json
import os

import pytest

from perfbench import run
from perfbench.lib import registry, trace

DATA = os.path.join(registry.BENCH_DIR, "data", "trace_small")
WINDOW = sorted(glob.glob(os.path.join(DATA, "trace*.xplane.pb.gz")))
OPS = sorted(glob.glob(os.path.join(DATA, "ops*.xplane.pb.gz")))


@pytest.fixture(scope="module")
def reported():
    with open(os.path.join(DATA, "reported.json")) as f:
        return json.load(f)


@pytest.fixture(scope="module")
def reduced():
    return trace.reduce(WINDOW, OPS)


def test_recorded_on_a_tpu(reported):
    assert reported["device"]["platform"] == "tpu"
    assert WINDOW and OPS


def test_reduction_gives_back_the_reported_numbers(reported, reduced):
    assert reduced["busy_s"] == reported["device"]["busy_s"]
    assert reduced["window_s"] == reported["device"]["window_s"]
    assert reduced["breakdown"] == reported["breakdown"]
    rec = run.Record({}, {})
    rec.grids = reported["record"]["grids"]
    rec.trace = reduced
    for name, m in reported["metrics"].items():
        assert registry.metric(name).read(rec) == pytest.approx(m["value"])
    assert set(reported["metrics"]) == {
        m["name"] for m in registry.benchmark()["per_layer"]}


def test_program_runs_match_device_modules():
    # the op slice holds both the host's view of each program run and
    # the device's own module events, on one clock
    spans, runs, _ = trace.read_events(OPS[0])
    modules = [(e.start_ns, e.start_ns + e.duration_ns)
               for plane in trace.load(OPS[0]).planes
               if plane.name.startswith(trace.DEVICE_PREFIX)
               for line in plane.lines if line.name == "XLA Modules"
               for e in line.events]
    assert runs and len(runs) == len(modules)
    for (hs, he), (ds, de) in zip(runs, sorted(modules)):
        assert 0 <= (he - hs) - (de - ds) <= 1_000_000     # within 1 ms
        assert abs(hs - ds) <= 3_000_000


def test_breakdown_is_bounded_and_named(reduced):
    ops = reduced["breakdown"]["device_ops"]
    gaps = reduced["breakdown"]["idle_gaps"]
    assert 0 < len(ops) <= 10 and 0 < len(gaps) <= 10
    for lst in (ops, gaps):
        secs = [v for _, v in lst]
        assert all(v > 0 for v in secs) and secs == sorted(secs, reverse=True)
    assert all(name.startswith("%") for name, _ in ops)
    assert all(name.startswith("perfbench.") for name, _ in gaps)
    idle = reduced["window_s"] - reduced["busy_s"]
    assert sum(v for _, v in gaps) <= idle + 1e-9
    assert 0 < reduced["busy_s"] <= reduced["engine_s"] + 1e-9


def test_union_cover_and_pairing():
    merged = trace.union([(5, 7), (0, 2), (1, 3), (7, 8)])
    assert merged == [(0, 3), (5, 8)]
    assert trace.covered(merged, 2, 6) == 2
    # a finish with no enqueue before it (the trace began mid-run) is
    # dropped; the rest pair in order
    assert trace.runs([10, 30], [5, 20, 40]) == [(10, 20), (30, 40)]
