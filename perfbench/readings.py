#!/usr/bin/env python3
"""Readings that the correctness limits are set from, on the chip.

    python3 perfbench/readings.py --workload <name> --grids <K> \\
        [--seeds <n> ...] [--control-seeds <n> ...] [--fault <name>]

For each seed it realizes the K grids a window of that seed would hold,
draws the same grid as `perfbench/run.py` checks, runs it through the
program's entry point and prints one JSON line of compared numbers
(program against reference) with ``sound``: no cell dropped a dispatch
(``slot_overflow``), no chunk was retried or degraded, and the
program's invariant guards raised nothing. Only sound lines set a lower
reading. With ``--fault`` the fault of that name
(`perfbench.lib.faults`) is planted in the program first; where the
guards raise, the line says so and its numbers come from the same plan
executed without them. For each
control seed it prints the same numbers for the control: the reference
computed in bfloat16 in the program's place. One process holds the chip
throughout; the references run in worker processes.
"""

from __future__ import annotations

import argparse
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
for _p in (ROOT, os.path.join(ROOT, "src")):
    if _p not in sys.path:
        sys.path.insert(0, _p)

from perfbench.lib import compare, faults, registry  # noqa: E402


def line(kind: str, seed: int, labels: list, got: list, want: list,
         **extra) -> str:
    return json.dumps({"kind": kind, "seed": seed, **extra,
                       "values": compare.values(got, want),
                       "per_cell": [dict(compare.numbers(a, b), cell=c)
                                    for c, a, b in zip(labels, got, want)]})


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--grids", type=int, required=True)
    ap.add_argument("--seeds", type=int, nargs="*", default=[])
    ap.add_argument("--control-seeds", type=int, nargs="*", default=[])
    ap.add_argument("--fault", choices=faults.FAULTS)
    args = ap.parse_args(argv)

    bench = registry.benchmark()
    cell = registry.workload(bench, args.workload)
    config = registry.config(cell["config"])
    traffic = registry.traffic(cell["traffic"])
    engine = registry.engine(config["engine"])
    from repro.compile_cache import enable_compilation_cache
    from repro.sim.exec import execute
    from repro.sim.harness import InvariantViolation
    enable_compilation_cache()
    if args.fault:
        faults.plant(args.fault)
    streams = engine.base(config, traffic)
    kind = f"fault:{args.fault}" if args.fault else "program"
    for seed in args.seeds:
        grids = [engine.realize(config, traffic, streams, seed, k)
                 for k in range(args.grids)]
        picks = compare.sample(seed, grids)
        g = grids[picks[0][0]]
        raised = None
        try:
            res = engine.submit(config, g)
        except InvariantViolation as e:
            raised = str(e)[:300]
            res = execute(engine.plan(config, g), validate=False)
        got = [engine.answer(res, i) for _, i in picks]
        meta = res.meta
        sound = not (args.fault or raised or meta.get("degraded_chunks")
                     or meta.get("retried_dispatches")
                     or any(a["slot_overflow"] for a in got))
        want = compare.references(config, [(g.inputs[i], g.horizon_s)
                                           for _, i in picks])
        print(line(kind, seed, [g.labels[i] for _, i in picks], got, want,
                   sound=sound, raised=raised,
                   slot_overflow=sum(a["slot_overflow"] for a in got)),
              flush=True)
    for seed in args.control_seeds:
        grids = [engine.realize(config, traffic, streams, seed, k)
                 for k in range(args.grids)]
        picks = compare.sample(seed, grids)
        g = grids[picks[0][0]]
        cells = [(g.inputs[i], g.horizon_s) for _, i in picks]
        low = compare.references(config, cells, "bfloat16")
        want = compare.references(config, cells)
        print(line("control", seed, [g.labels[i] for _, i in picks], low,
                   want), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
