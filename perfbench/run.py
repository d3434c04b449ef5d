#!/usr/bin/env python3
"""Runs one benchmark cell once, on the chips of this machine.

    python3 perfbench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

The cell (``BENCHMARK.json`` ``workloads``) names a configuration and a
traffic mix; `perfbench.lib.registry` finds their files. One client
submits whole grids in a closed loop: grid k is a fresh realization of
the traffic from (seed, k), sent when grid k-1 has returned its totals.

* Set-up (``setup_s``): process start to window open. JAX start, the
  compile cache, the configuration's base streams, one warm-up grid from
  a realization the window never submits, and the window's grids,
  realized but not planned. The warm-up runs in full: it is what puts
  every program the window runs into JAX's dispatch cache (compiled or
  loaded from the persistent cache); a program compiled ahead of time
  is traced again at its first call, inside the window. Its time, less
  compilation, also sizes the window: about ``seconds`` over it grids
  are realized, and one more.
* Window: ``--seconds`` of closed-loop grids through the program's
  entry point. With ``--trace 1`` each grid calls the planner and the
  executor that the entry point calls, each inside a profiler
  annotation, and the window is traced.
* Metrics: each is read by its own module, `perfbench/metrics/<name>.py`,
  from the run's `Record`: the cell's end-to-end metrics with
  ``--trace 0``, its per-layer metrics with ``--trace 1``.
* Check: every cell of one completed grid, drawn from the seed, against
  the plain reference (`perfbench.lib.compare`). Each compared number is
  printed beside its limit, on standard error and under ``checks`` in
  the result line.

The last line of standard output is the result JSON. Without a TPU, or
with fewer chips than the cell asks for, it exits 2 and prints none.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import gc  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
for _p in (ROOT, os.path.join(ROOT, "src")):
    if _p not in sys.path:
        sys.path.insert(0, _p)

from perfbench.lib import compare, registry  # noqa: E402
from perfbench.lib.clock import CompileCounter, Spans  # noqa: E402

TRACE_DIR = os.path.join(ROOT, "perfbench", ".cache", "trace")
OPS_DIR = os.path.join(ROOT, "perfbench", ".cache", "ops")
MAX_GRIDS = 64
OP_SLICE_S = 0.2    # length of the op-level trace slice


class NoChip(RuntimeError):
    """JAX found no accelerator, or fewer chips than the cell asks for."""


class Record:
    """What the metric readers (`perfbench/metrics/*.py`) read: the set-up
    time, the window's open on the host clock, the window's grids with
    their host-clock spans and plan shapes, and the reduction of the
    profiler trace."""

    def __init__(self, config: dict, traffic: dict):
        self.config, self.traffic = config, traffic
        self.setup_s = 0.0
        self.t_open = 0.0
        self.grids: list[dict] = []
        self.trace: dict | None = None


def device_info(chips: int, require_tpu: bool) -> dict:
    import jax
    devs = jax.devices()
    if require_tpu and devs[0].platform != "tpu":
        raise NoChip(f"JAX platform is {devs[0].platform!r}, not tpu")
    if len(devs) < chips:
        raise NoChip(f"the cell needs {chips} chip(s), JAX sees {len(devs)}")
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": chips}


def _shapes(plan) -> list[tuple[int, int, int]]:
    """(chunk, entries E, block width) of every dispatch."""
    return [(d.chunk, d.arrays["times"].shape[1], d.arrays["times"].shape[2])
            for d in plan.dispatches]


def _profile_options(host_only: bool):
    """Host events only for the whole window (program runs as the TPU
    runtime reports them, see `perfbench.lib.trace`); device op events
    only for the short slice."""
    import jax
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 1      # the benchmark's own annotations
    if host_only:
        opts.advanced_configuration = {"tpu_trace_mode": "TRACE_ONLY_HOST"}
    return opts


def _op_slice(config: dict, engine, grid) -> None:
    """Trace the first OP_SLICE_S seconds of the grid's largest dispatch
    at op level, then let it finish. Runs after the window."""
    import jax

    from repro.sim.exec import LocalBackend
    plan = engine.plan(config, grid)
    d = max(plan.dispatches, key=lambda d: d.arrays["times"].size)
    shutil.rmtree(OPS_DIR, ignore_errors=True)
    jax.profiler.start_trace(OPS_DIR, profiler_options=_profile_options(False))
    out = LocalBackend().run(d)
    time.sleep(OP_SLICE_S)
    jax.profiler.stop_trace()
    jax.block_until_ready(out)


def _failed_cells(result, n_cells: int) -> int:
    """Cells whose totals cannot stand: dropped dispatches, or a chunk
    that was retried or fell back to another backend."""
    meta = result.meta
    if meta.get("degraded_chunks") or meta.get("retried_dispatches"):
        return n_cells
    return sum(1 for i in range(n_cells)
               if result.totals(i).breakdown.get("slot_overflow", 0) > 0)


def run_cell(name: str, seed: int, seconds: float, trace: bool, *,
             config: dict | None = None, traffic: dict | None = None,
             require_tpu: bool = True, log=print,
             keep_trace: str | None = None) -> dict:
    """One run of one cell; returns the result object (the JSON line).
    ``keep_trace`` copies the trace files there before they are deleted
    (used to record the reduction's test trace)."""
    bench = registry.benchmark()
    cell = registry.workload(bench, name)
    config = config or registry.config(cell["config"])
    traffic = traffic or registry.traffic(cell["traffic"])
    device = device_info(cell["chips"], require_tpu)

    import jax

    from repro.compile_cache import enable_compilation_cache
    from repro.sim.exec import execute
    log(f"compile_cache={enable_compilation_cache()}")
    compiles = CompileCounter()
    engine = registry.engine(config["engine"])

    # ---------------------------------------------------------- set-up
    streams = engine.base(config, traffic)
    warm = engine.realize(config, traffic, streams, seed, -1)
    t0 = time.perf_counter()
    c0 = compiles.seconds
    engine.submit(config, warm)
    est = max(time.perf_counter() - t0 - (compiles.seconds - c0), 1e-3)
    del warm
    n_grids = min(MAX_GRIDS, round(seconds / est) + 1)
    grids = [engine.realize(config, traffic, streams, seed, k)
             for k in range(n_grids)]
    gc.collect()
    gc.freeze()     # the held grids are the benchmark's, not the user's

    rec = Record(config, traffic)
    spans = Spans(annotate=trace)
    if trace:
        shutil.rmtree(TRACE_DIR, ignore_errors=True)
        jax.profiler.start_trace(TRACE_DIR,
                                 profiler_options=_profile_options(True))

    # ---------------------------------------------------------- window
    from repro.sim.harness import InvariantViolation
    kept: list = []          # (grid, result or None) per grid
    raised: list[str] = []
    events0, setup_compile_s = compiles.events, compiles.seconds
    t_open = rec.t_open = time.perf_counter()
    setup_s = rec.setup_s = t_open - T_START
    t_last = t_open
    with spans.span("perfbench.window"):
        for k, g in enumerate(grids):
            # send the next grid only if it ends nearer the window's
            # length than stopping now does
            ts = time.perf_counter()
            if k and ts - t_open + (ts - t_open) / (2 * k) > seconds:
                break
            try:
                plan_s, shapes = None, []
                if trace:
                    with spans.span("perfbench.plan"):
                        plan = engine.plan(config, g)
                    plan_s = time.perf_counter() - ts
                    with spans.span("perfbench.execute"):
                        res = execute(plan)
                    shapes = _shapes(plan)
                    del plan
                else:
                    res = engine.submit(config, g)
            except InvariantViolation as e:
                res = None
                raised.append(f"grid {k}: {e}")
            t_last = time.perf_counter()
            rec.grids.append({"k": k, "start": ts, "end": t_last,
                              "ok": res is not None, "plan_s": plan_s,
                              "arrivals": g.arrivals, "shapes": shapes})
            kept.append((g, res))
    window_s = t_last - t_open
    in_window = compiles.events - events0
    if trace:
        jax.profiler.stop_trace()
        ok_grids = [g for g, r in kept if r is not None]
        if ok_grids:
            _op_slice(config, engine, ok_grids[-1])

    done = [(g, r) for g, r in kept if r is not None]
    attempted = sum(len(g.cells) for g, _ in kept)
    failed = sum(len(g.cells) if r is None else _failed_cells(r, len(g.cells))
                 for g, r in kept)
    stats = jax.devices()[0].memory_stats() or {}
    device["memory_peak_bytes"] = int(stats.get("peak_bytes_in_use", 0))
    log(f"compiles_in_window={in_window} grids={len(kept)} "
        f"window_s={window_s} setup_s={setup_s} est_grid_s={est} "
        f"setup_compile_s={setup_compile_s} "
        f"grid_s={[round(g['end'] - g['start'], 3) for g in rec.grids]}")

    # ---------------------------------------------------------- check
    picks = compare.sample(seed, [g for g, _ in done])
    answers = [engine.answer(done[k][1], i) for k, i in picks]
    cells = [(done[k][0].inputs[i], done[k][0].horizon_s) for k, i in picks]
    kept = done = None
    gc.collect()
    t = time.perf_counter()
    want = compare.references(config, cells) if cells else []
    if picks:
        log(f"reference grid={picks[0][0]} cells={len(picks)} "
            f"seconds={time.perf_counter() - t:.3f}")
    values = compare.values(answers, want) if picks else {}
    ok, rows = compare.verdict(values, config["correct"]["limits"])
    correct = bool(ok and picks and failed == 0 and not raised)

    out = {"correct": correct, "attempted": attempted, "failed": failed,
           "device": device}
    kind = "end_to_end"
    if trace:
        from perfbench.lib import trace as tr
        rec.trace = tr.reduce(tr.files(TRACE_DIR), tr.files(OPS_DIR))
        if keep_trace:
            for d in (TRACE_DIR, OPS_DIR):
                shutil.copytree(d, os.path.join(keep_trace,
                                                os.path.basename(d)))
            out["record"] = {"grids": rec.grids}
        shutil.rmtree(TRACE_DIR, ignore_errors=True)
        shutil.rmtree(OPS_DIR, ignore_errors=True)
        device["busy_s"] = rec.trace["busy_s"]
        device["window_s"] = rec.trace["window_s"]
        out["breakdown"] = rec.trace["breakdown"]
        kind = "per_layer"
    out["metrics"] = {}
    for m in registry.metrics_for(bench, kind, name):
        v = registry.metric(m["name"]).read(rec)
        if v is not None:
            out["metrics"][m["name"]] = {"value": v, "unit": m["unit"]}
    for msg in raised:
        log(f"raised: {msg}")
    out["compiles_in_window"] = in_window
    out["checks"] = {k: {"value": v, "limit": lim}
                     for k, (v, lim) in rows.items()}
    out["checks"]["failed_cells"] = {"value": failed, "limit": 0}
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    try:
        out = run_cell(args.workload, args.seed, args.seconds,
                       bool(args.trace),
                       log=lambda s: print(s, file=sys.stderr, flush=True))
    except NoChip as e:
        print(f"perfbench: {e}", file=sys.stderr)
        return 2
    print(f"compiles_in_window={out['compiles_in_window']}", flush=True)
    for k, c in out["checks"].items():
        print(f"check {k} value={c['value']} limit={c['limit']}",
              file=sys.stderr, flush=True)
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
