#!/usr/bin/env python3
"""Records the small chip trace that test_trace.py reduces.

    python3 perfbench/tests/record_trace.py      # on one TPU chip

Runs a small traced run of ``t9-grid`` (one app of one case, 120 s) and
writes its gzipped trace files and its reported numbers to
``perfbench/data/trace_small/``.
"""

import glob
import gzip
import json
import os
import shutil
import sys
import tempfile

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]

from perfbench import run  # noqa: E402
from perfbench.lib import registry  # noqa: E402

OUT = os.path.join(ROOT, "perfbench", "data", "trace_small")
TRAFFIC = {"horizon_s": 120,
           "subset": {"cases": ["azure-like-medium"], "apps": [0]}}


def main() -> int:
    traffic = dict(registry.traffic("full-grid"), **TRAFFIC)
    cache = os.path.join(ROOT, "perfbench", ".cache")
    os.makedirs(cache, exist_ok=True)
    tmp = tempfile.mkdtemp(dir=cache)
    out = run.run_cell("t9-grid", 2 ** 33 + 17, 1.0, True, traffic=traffic,
                       keep_trace=tmp, log=print)
    shutil.rmtree(OUT, ignore_errors=True)
    os.makedirs(OUT)
    for kind in ("trace", "ops"):
        for i, f in enumerate(glob.glob(os.path.join(tmp, kind, "**",
                                                     "*.xplane.pb"),
                                        recursive=True)):
            with open(f, "rb") as src, gzip.open(
                    os.path.join(OUT, f"{kind}{i}.xplane.pb.gz"), "wb") as dst:
                shutil.copyfileobj(src, dst)
    shutil.rmtree(tmp)
    with open(os.path.join(OUT, "reported.json"), "w") as f:
        json.dump(out, f, indent=1)
    print(json.dumps(out)[:2000])
    return 0


if __name__ == "__main__":
    sys.exit(main())
