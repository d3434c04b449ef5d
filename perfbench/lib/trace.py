"""Reduction of profiler traces to device busy time and its breakdown.

Reads the ``.xplane.pb`` files that `jax.profiler` writes, with
`jax.profiler.ProfileData` alone. Two traces per traced run:

* **The window**, traced with ``tpu_trace_mode=TRACE_ONLY_HOST``. The
  TPU profiler has no mode that records program runs without every
  operation, and an op-level trace of a whole window overflows its
  buffers (at about 4 M op events per second of the DES scan). The host
  plane still holds, for every program run, the TPU runtime's
  ``DoEnqueueProgram`` (the program is on the device's queue) and
  ``tpu::System::Execute=>Done`` (it finished), and the benchmark's own
  ``perfbench.*`` annotations, all on one clock. A program's run is the
  interval from the end of the first to the start of the second; on the
  recorded chip trace in ``perfbench/data/trace_small`` these intervals
  exceed the device's own ``XLA Modules`` events by 0.38-0.56 ms a run.
* **The op slice**: the first 0.2 s of one engine dispatch, traced at op
  level (``XLA Ops`` on the ``/device:TPU:<n>`` plane).

Numbers, over the ``perfbench.window`` span:

* ``busy_s``: the union of program runs; ``window_s``: the span's length.
* ``engine_s``: the summed length of the program runs. Inside the
  window only the engine's jitted programs run (inputs reach the device
  as transfers, not programs).
* ``exec_idle_s``, ``exec_spans``: host time inside ``perfbench.execute``
  spans with no program running, and the number of those spans.
* ``breakdown``: the 10 operations with most device time in the op
  slice, and the 10 longest idle gaps of the window, each named by the
  innermost benchmark span open at its middle.
"""

from __future__ import annotations

import glob
import gzip
import os

OP_LINE = "XLA Ops"
DEVICE_PREFIX = "/device:TPU:"
ENQUEUE = "DoEnqueueProgram"
DONE = "tpu::System::Execute=>Done"
WINDOW = "perfbench.window"
EXECUTE = "perfbench.execute"


def files(trace_dir: str) -> list[str]:
    return sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb*"),
                            recursive=True))


def load(path: str):
    """`jax.profiler.ProfileData` of one ``.xplane.pb`` (or ``.pb.gz``)."""
    import jax
    opener = gzip.open if path.endswith(".gz") else open
    with opener(path, "rb") as f:
        return jax.profiler.ProfileData.from_serialized_xspace(f.read())


def union(intervals) -> list[tuple[int, int]]:
    """Merge (start, end) intervals into disjoint sorted ones."""
    out: list[list[int]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def covered(merged, lo: int, hi: int) -> int:
    """Length of [lo, hi) covered by disjoint sorted intervals."""
    return sum(max(0, min(e, hi) - max(s, lo)) for s, e in merged)


def runs(enqueued, finished) -> list[tuple[int, int]]:
    """Pair each finish with the earliest unpaired enqueue before it
    (programs on one device run in the order they were enqueued)."""
    enqueued, out, i = sorted(enqueued), [], 0
    for f in sorted(finished):
        if i < len(enqueued) and enqueued[i] <= f:
            out.append((enqueued[i], f))
            i += 1
    return out


def read_events(path: str):
    """(benchmark spans, program runs, op events) of one trace file:
    spans and ops as (name, start_ns, end_ns), runs as (start, end)."""
    pd = load(path)
    spans, ops, enq, done = [], [], [], []
    for plane in pd.planes:
        for line in plane.lines:
            if plane.name.startswith(DEVICE_PREFIX):
                if line.name == OP_LINE:       # "%fusion.7 = f32[...] ..."
                    ops.extend((e.name.split(" = ", 1)[0], e.start_ns,
                                e.start_ns + e.duration_ns)
                               for e in line.events)
                continue
            for e in line.events:
                if e.name.startswith("perfbench."):
                    spans.append((e.name, e.start_ns,
                                  e.start_ns + e.duration_ns))
                elif e.name == ENQUEUE:
                    enq.append(e.start_ns + e.duration_ns)
                elif e.name == DONE:
                    done.append(e.start_ns)
    return spans, runs(enq, done), ops


def top(named: dict, n: int = 10) -> list[list]:
    return [[k, v] for k, v in sorted(named.items(), key=lambda x: -x[1])[:n]]


def reduce_window(spans, program_runs) -> dict:
    """Busy, engine and idle numbers of the ``perfbench.window`` span."""
    win = [s for s in spans if s[0] == WINDOW]
    inside = [(s, e) for s, e in program_runs
              if win and e > win[0][1] and s < win[0][2]]
    if not inside:
        return {"busy_s": 0.0, "window_s": 0.0, "engine_s": 0.0,
                "exec_idle_s": 0.0, "exec_spans": 0, "gaps": []}
    _, w0, w1 = win[0]
    merged = union(inside)
    execs = [s for s in spans if s[0] == EXECUTE and s[2] > w0 and s[1] < w1]
    gaps, prev = [], w0
    for s, e in merged + [(w1, w1)]:
        if s > prev:
            gaps.append((prev, min(s, w1)))
        prev = max(prev, e)
    named = []
    for g0, g1 in gaps:
        mid = (g0 + g1) // 2
        open_ = [sp for sp in spans if sp[1] <= mid < sp[2]]
        name = min(open_, key=lambda sp: sp[2] - sp[1])[0] if open_ else "none"
        named.append([name, (g1 - g0) / 1e9])
    named.sort(key=lambda x: -x[1])
    return {"busy_s": covered(merged, w0, w1) / 1e9,
            "window_s": (w1 - w0) / 1e9,
            "engine_s": sum(min(e, w1) - max(s, w0) for s, e in inside) / 1e9,
            "exec_idle_s": sum((e - s) - covered(merged, s, e)
                               for _, s, e in execs) / 1e9,
            "exec_spans": len(execs), "gaps": named[:10]}


def reduce(window_files: list[str], op_files: list[str] = ()) -> dict:
    """The numbers the per-layer metrics read, from the window's trace and
    the op slice."""
    spans, program_runs = [], []
    for path in window_files:
        sp, r, _ = read_events(path)
        spans += sp
        program_runs += r
    out = reduce_window(spans, program_runs)
    ops: dict[str, float] = {}
    for path in op_files:
        for name, s, e in read_events(path)[2]:
            ops[name] = ops.get(name, 0.0) + (e - s) / 1e9
    out["breakdown"] = {"device_ops": top(ops),
                        "idle_gaps": out.pop("gaps")}
    return out
