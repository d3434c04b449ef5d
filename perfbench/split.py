#!/usr/bin/env python3
"""Splits a traced run of one cell by the program's own spans and counters.

    python3 perfbench/split.py --workload <name> --seed <n> --seconds <s> \\
        [--keep <dir>]

Runs the cell once with ``--trace 1`` (`perfbench.run.run_cell`), keeps
the window's trace files and reduces them with `perfbench.lib.spans`,
then plans every grid of the window again, from the same seed, to read
the plan's counters (`SweepPlan.meta`). Prints one JSON line: the run's
own result (``result``), the host time of each ``repro.*`` phase in
milliseconds per grid, the lane fill split into its three factors, the
bytes sent to the device, how much of the planner call and of the
device's idle time the program's spans cover, and the traced window's
rate. ``--keep`` copies the kept trace files there. Needs the chip, like
`perfbench/run.py`.
"""

from __future__ import annotations

import argparse
import json
import os
import shutil
import sys
import tempfile

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
for _p in (ROOT, os.path.join(ROOT, "src")):
    if _p not in sys.path:
        sys.path.insert(0, _p)

from perfbench import run  # noqa: E402
from perfbench.lib import registry, spans, trace  # noqa: E402

PHASES = {   # per-grid number: the repro.* spans whose self times it sums
    "plan_resolve_ms": ("repro.plan.resolve",),
    "plan_entries_ms": ("repro.plan.entries",),
    "plan_pack_ms": ("repro.plan.pack",),
    "plan_self_ms": ("repro.plan",),
    "exec_transfer_ms": ("repro.exec.transfer",),
    "exec_run_ms": ("repro.exec.run",),
    "exec_fetch_ms": ("repro.exec.fetch", "repro.exec.scatter"),
    "guard_ms": ("repro.harness.guards",),
    "exec_self_ms": ("repro.exec", "repro.exec.dispatch"),
}


def counters(config: dict, traffic: dict, seed: int, ks) -> list[dict]:
    """The plan's counters of each grid ``k`` of run ``seed``."""
    engine = registry.engine(config["engine"])
    streams = engine.base(config, traffic)
    return [engine.plan(config, engine.realize(config, traffic, streams,
                                               seed, k)).meta for k in ks]


def summarize(result: dict, grids: list, split: dict,
              counts: list[dict]) -> dict:
    """The JSON line (see the module docstring) from the run's result,
    its window's grids, the spans' split and the grids' counters."""
    n = sum(1 for g in grids if g.get("plan_s") is not None)
    table = split["spans"]
    out = {k: 1e3 * sum(table.get(s, {}).get("self_s", 0.0)
                        for s in names) / n
           for k, names in PHASES.items()}
    plan_ms = 1e3 * sum(g["plan_s"] for g in grids) / n
    out["plan_ms"] = plan_ms
    out["plan_covered"] = sum(out[k] for k in (
        "plan_resolve_ms", "plan_entries_ms", "plan_pack_ms")) / plan_ms
    out["idle_in_program"] = (split["idle_in_program_s"] / split["idle_s"]
                              if split["idle_s"] else None)
    out["idle_gaps"] = split["idle_gaps"]
    out["span_counts"] = {k: v["count"] for k, v in table.items()}
    if counts and "entries" in counts[0]:
        c = {k: sum(x[k] for x in counts) for k in counts[0]
             if k != "plan_id"}
        out["row_fill"] = 100.0 * c["row_entries"] / c["entries_scanned"]
        out["entry_fill"] = 100.0 * c["entries"] / c["row_entries"]
        out["block_fill"] = 100.0 * c["arrivals"] / (128 * c["entries"])
        out["h2d_mb"] = c["h2d_bytes"] / len(counts) / 1e6
        out["counters"] = counts
    ok = [g for g in grids if g["ok"]]
    out["traced_arrivals_per_s"] = (sum(g["arrivals"] for g in ok)
                                    / (grids[-1]["end"] - grids[0]["start"]))
    out["result"] = result
    return out


def split_cell(name: str, seed: int, seconds: float,
               traffic: dict | None = None, keep: str | None = None,
               log=print) -> dict:
    bench = registry.benchmark()
    cell = registry.workload(bench, name)
    config = registry.config(cell["config"])
    traffic = dict(registry.traffic(cell["traffic"]), **(traffic or {}))
    cache = os.path.join(ROOT, "perfbench", ".cache")
    os.makedirs(cache, exist_ok=True)
    tmp = tempfile.mkdtemp(dir=cache)
    try:
        result = run.run_cell(name, seed, seconds, True, config=config,
                              traffic=traffic, keep_trace=tmp, log=log)
        grids = result.pop("record")["grids"]
        split = spans.reduce(trace.files(os.path.join(tmp, "trace")))
        if keep:
            shutil.rmtree(keep, ignore_errors=True)
            shutil.copytree(tmp, keep)
    finally:
        shutil.rmtree(tmp, ignore_errors=True)
    counts = counters(config, traffic, seed, [g["k"] for g in grids])
    out = summarize(result, grids, split, counts)
    out["grids"] = grids
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--keep")
    args = ap.parse_args(argv)
    try:
        out = split_cell(args.workload, args.seed, args.seconds,
                         keep=args.keep,
                         log=lambda s: print(s, file=sys.stderr, flush=True))
    except run.NoChip as e:
        print(f"perfbench: {e}", file=sys.stderr)
        return 2
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
