"""The split by the program's own spans (`perfbench.lib.spans`,
`perfbench/split.py`) against a small trace recorded on one TPU v5e
(`perfbench/tests/record_spans.py`): it gives back the numbers the run
reported, the program's spans sit on the device trace's clock (every
program run inside a ``repro.exec.run`` span, the planner and executor
inside the benchmark's spans around them), and the idle time is named
after what the program was doing."""

import glob
import json
import os

import pytest

from perfbench import split
from perfbench.lib import registry, spans, trace

DATA = os.path.join(registry.BENCH_DIR, "data")
WINDOW = sorted(glob.glob(os.path.join(DATA, "trace_spans",
                                       "trace*.xplane.pb.gz")))
SMALL = sorted(glob.glob(os.path.join(DATA, "trace_small",
                                      "trace*.xplane.pb.gz")))


@pytest.fixture(scope="module")
def reported():
    with open(os.path.join(DATA, "trace_spans", "reported.json")) as f:
        return json.load(f)


@pytest.fixture(scope="module")
def reduced():
    return spans.reduce(WINDOW)


@pytest.fixture(scope="module")
def events():
    sp = [s for path in WINDOW for s in spans.read_spans(path)]
    runs = [r for path in WINDOW for r in trace.read_events(path)[1]]
    (win,) = [s for s in sp if s[0] == trace.WINDOW]
    return sp, [r for r in runs if win[1] < r[1] and r[0] < win[2]]


def test_recorded_on_a_tpu(reported):
    assert reported["result"]["device"]["platform"] == "tpu"
    assert len(WINDOW) == 1
    assert reported["result"]["correct"]


def test_split_gives_back_the_reported_numbers(reported, reduced):
    result = {k: v for k, v in reported["result"].items()}
    got = split.summarize(result, reported["grids"], reduced,
                          reported["counters"])
    for key in (*split.PHASES, "plan_ms", "plan_covered",
                "idle_in_program", "row_fill", "entry_fill", "block_fill",
                "h2d_mb", "traced_arrivals_per_s"):
        assert got[key] == pytest.approx(reported[key]), key
    assert got["idle_gaps"] == reported["idle_gaps"]
    assert got["span_counts"] == reported["span_counts"]


PER_DISPATCH = {"repro.exec.dispatch", "repro.exec.run",
                "repro.exec.transfer", "repro.exec.fetch",
                "repro.exec.scatter"}


@pytest.mark.parametrize("name", sorted(split.PHASES))
def test_every_phase_has_its_spans_once_a_grid_or_dispatch(reported, name):
    grids = reported["grids"]
    for span in split.PHASES[name]:
        want = (sum(len(g["shapes"]) for g in grids)
                if span in PER_DISPATCH else len(grids))
        assert reported["span_counts"][span] == want, span
    assert reported[name] > 0


def test_fills_multiply_to_lane_fill(reported):
    lane = reported["result"]["metrics"]["lane_fill"]["value"]
    prod = (reported["row_fill"] * reported["entry_fill"]
            * reported["block_fill"] / 1e4)
    assert prod == pytest.approx(lane, rel=1e-12)
    counts = [{k: v for k, v in c.items() if k != "plan_id"}
              for c in reported["counters"]]
    assert all(c == counts[0] for c in counts)


def test_program_runs_fall_inside_exec_run_spans(events):
    sp, runs = events
    runs_spans = [s for s in sp if s[0] == "repro.exec.run"]
    assert runs and len(runs) == len(runs_spans)
    for s, e in runs:
        assert any(r[1] <= s and e <= r[2] for r in runs_spans)


def test_program_spans_nest_in_the_benchmark_spans(events):
    sp, _ = events
    for outer, inner in (("perfbench.plan", "repro.plan"),
                         ("perfbench.execute", "repro.exec")):
        outers = [s for s in sp if s[0] == outer]
        inners = [s for s in sp if s[0] == inner]
        assert len(inners) == len(outers) > 0
        for o, i in zip(sorted(outers, key=lambda s: s[1]),
                        sorted(inners, key=lambda s: s[1])):
            assert o[1] <= i[1] and i[2] <= o[2]
    plans = sorted((s for s in sp if s[0] == "repro.plan"),
                   key=lambda s: s[1])
    execs = sorted((s for s in sp if s[0] == "repro.exec"),
                   key=lambda s: s[1])
    assert [p[4]["plan_id"] for p in plans] == [
        e[4]["plan_id"] for e in execs]


def test_idle_time_is_named_by_the_program(reduced):
    assert reduced["idle_in_program_s"] >= 0.95 * reduced["idle_s"] > 0
    gaps = reduced["idle_gaps"]
    assert gaps and all(name.startswith("repro.") for name, _ in gaps[:4])
    assert sum(v for _, v in gaps) <= reduced["idle_s"] + 1e-9


def test_a_trace_without_program_spans_names_gaps_as_before():
    # trace_small predates the program's spans: the split finds none,
    # and names the gaps as the benchmark's own reduction does
    with open(os.path.join(DATA, "trace_small", "reported.json")) as f:
        before = json.load(f)["breakdown"]["idle_gaps"]
    red = spans.reduce(SMALL)
    assert red["spans"] == {} and red["idle_in_program_s"] == 0.0
    assert red["idle_gaps"] == before
