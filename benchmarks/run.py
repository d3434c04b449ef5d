"""Benchmark harness: one function per paper table/figure + the roofline
summary. Prints ``name,us_per_call,derived`` CSV lines and records
per-suite wall time in results/BENCH_sweep.json.

BENCH_FAST=0 for full-size runs (10 traces, 2h horizons, all apps).
A suite that raises is reported as ``name,0,error=...`` and the
remaining suites still run; the exit code is then 1.
"""

from __future__ import annotations

import os
import sys
import traceback

# `python benchmarks/run.py` puts benchmarks/ (not the repo root) on
# sys.path; make the repo root and src/ importable regardless of cwd.
_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
for p in (_ROOT, os.path.join(_ROOT, "src")):
    if p not in sys.path:
        sys.path.insert(0, p)


def main() -> int:
    from benchmarks import (chaos_suite, fig2_pareto, fig4_spork_vs_mark,
                            fig5_sensitivity, fig6_worker_efficiency,
                            fig7_request_sizes, fleet_suite, policy_tuning,
                            roofline, scenario_suite, table8_production,
                            table9_dispatch, warmup)
    from benchmarks.common import emit, timed
    from repro.sim.harness import invariants_enabled

    # every sweep below runs through repro.sim.exec.execute, whose
    # invariant guards (conservation laws, NaN/Inf sentinels) are on by
    # default; say so up front so a REPRO_SKIP_INVARIANTS run is visible
    # in the log next to its numbers.
    print(f"invariant_guards,"
          f"{'on' if invariants_enabled() else 'OFF (REPRO_SKIP_INVARIANTS)'}")

    suites = [
        ("sweep_warmup", warmup.run),
        ("fig2_pareto", lambda: fig2_pareto.run(pareto=True)),
        ("table8_production", table8_production.run),
        ("table9_dispatch", table9_dispatch.run),
        ("scenario_suite", scenario_suite.run),
        ("chaos_suite", chaos_suite.run),
        ("fleet_suite", fleet_suite.run),
        ("fig4_spork_vs_mark", fig4_spork_vs_mark.run),
        ("fig5_sensitivity", fig5_sensitivity.run),
        ("fig6_worker_efficiency", fig6_worker_efficiency.run),
        ("fig7_request_sizes", fig7_request_sizes.run),
        ("policy_tuning", policy_tuning.run),
        ("roofline", roofline.run),
    ]
    failed = []
    for name, fn in suites:
        try:
            rows, t0 = timed(fn)
        except Exception as e:  # noqa: BLE001 — report, run the rest
            traceback.print_exc()
            print(f"{name},0,error={type(e).__name__}:{e}")
            failed.append(name)
            continue
        emit(name, rows, t0)
    return 1 if failed else 0


if __name__ == "__main__":
    sys.exit(main())
