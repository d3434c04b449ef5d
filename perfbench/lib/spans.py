"""The program's own spans (``repro.*``) in a traced window.

`repro.sim.plan`, `repro.sim.exec` and `repro.sim.harness` open a
`jax.profiler.TraceAnnotation` at each phase and each dispatch, so a
traced window holds them on the host plane beside the benchmark's
``perfbench.*`` spans and the TPU runtime's program runs, on one clock
(`perfbench.lib.trace`). This module splits that window by them:

* ``spans``: for each ``repro.*`` name, its ``count`` in the window and
  its ``self_s``: the window seconds its spans cover less the union of
  the ``repro.*`` spans nested in them on their thread (a
  ``repro.exec.run`` under a `RetryPolicy` timeout runs on a thread of
  its own, so it is nobody's child there);
* ``idle_s``: window seconds in which no program ran, and
  ``idle_in_program_s``: the part of it inside some ``repro.*`` span;
* ``idle_gaps``: the 10 longest gaps with no program running, each
  named by the innermost span, the benchmark's or the program's, open
  at its middle.

The benchmark's traced run does not call it: it reduces the trace files
a run keeps (`perfbench.run.run_cell` with ``keep_trace``) and the
recorded chip trace in ``perfbench/data/trace_spans``.
"""

from __future__ import annotations

from perfbench.lib import trace

PROGRAM = "repro."


def read_spans(path: str) -> list[tuple]:
    """The benchmark's and the program's spans of one trace file, as
    (name, start_ns, end_ns, thread, stats): ``thread`` is (plane name,
    line index), ``stats`` the span's metadata (``plan_id``, ...)."""
    out = []
    for plane in trace.load(path).planes:
        if plane.name.startswith(trace.DEVICE_PREFIX):
            continue
        for li, line in enumerate(plane.lines):
            out.extend((e.name, e.start_ns, e.start_ns + e.duration_ns,
                        (plane.name, li), dict(e.stats))
                       for e in line.events
                       if e.name.startswith(("perfbench.", PROGRAM)))
    return out


def self_times(spans, w0: int, w1: int) -> dict:
    """``count`` and ``self_s`` of each ``repro.*`` span name over the
    window [w0, w1)."""
    prog = [s for s in spans if s[0].startswith(PROGRAM)
            and s[2] > w0 and s[1] < w1]
    out: dict[str, dict] = {}
    for name, s, e, thread, _ in prog:
        lo, hi = max(s, w0), min(e, w1)
        kids = trace.union((cs, ce) for _, cs, ce, t, _ in prog
                           if t == thread and s <= cs and ce <= e
                           and (cs, ce) != (s, e))
        row = out.setdefault(name, {"count": 0, "self_s": 0.0})
        row["count"] += 1
        row["self_s"] += ((hi - lo) - trace.covered(kids, lo, hi)) / 1e9
    return out


def reduce(window_files: list[str]) -> dict:
    """The split of one traced window (see the module docstring)."""
    spans, program_runs = [], []
    for path in window_files:
        spans += read_spans(path)
        program_runs += trace.read_events(path)[1]
    win = [s for s in spans if s[0] == trace.WINDOW]
    if not win:
        return {"spans": {}, "idle_s": 0.0, "idle_in_program_s": 0.0,
                "idle_gaps": []}
    w0, w1 = win[0][1:3]
    busy = trace.union((s, e) for s, e in program_runs if e > w0 and s < w1)
    gaps, prev = [], w0
    for s, e in busy + [(w1, w1)]:
        if s > prev:
            gaps.append((prev, min(s, w1)))
        prev = max(prev, e)
    prog = trace.union((s, e) for n, s, e, _, _ in spans
                       if n.startswith(PROGRAM))
    named = []
    for g0, g1 in gaps:
        mid = (g0 + g1) // 2
        open_ = [sp for sp in spans if sp[1] <= mid < sp[2]]
        name = min(open_, key=lambda sp: sp[2] - sp[1])[0] if open_ \
            else "none"
        named.append([name, (g1 - g0) / 1e9])
    named.sort(key=lambda x: -x[1])
    return {"spans": self_times(spans, w0, w1),
            "idle_s": sum(g1 - g0 for g0, g1 in gaps) / 1e9,
            "idle_in_program_s": sum(trace.covered(prog, g0, g1)
                                     for g0, g1 in gaps) / 1e9,
            "idle_gaps": named[:10]}
