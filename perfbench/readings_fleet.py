#!/usr/bin/env python3
"""Readings for a fleet cell: `perfbench/readings.py` with the faults of
`perfbench.lib.faults_fleet` in place of `perfbench.lib.faults`.

    python3 perfbench/readings_fleet.py --workload <name> --grids <K> \\
        [--seeds <n> ...] [--control-seeds <n> ...] [--fault <name>]
"""

from __future__ import annotations

import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
for _p in (ROOT, os.path.join(ROOT, "src")):
    if _p not in sys.path:
        sys.path.insert(0, _p)

from perfbench import readings  # noqa: E402
from perfbench.lib import faults_fleet  # noqa: E402

if __name__ == "__main__":
    readings.faults = faults_fleet
    sys.exit(readings.main())
