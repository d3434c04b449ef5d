#!/usr/bin/env python3
"""Records the small chip trace that test_spans.py reduces.

    python3 perfbench/tests/record_spans.py      # on one TPU chip

Runs `perfbench/split.py`'s traced run of ``t9-grid`` at the size of
``trace_small`` (one app of one case, 120 s) and writes the window's
gzipped trace file, with no op slice, and the split it reported to
``perfbench/data/trace_spans/``.
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

from perfbench import split  # noqa: E402

OUT = os.path.join(ROOT, "perfbench", "data", "trace_spans")
TRAFFIC = {"horizon_s": 120,
           "subset": {"cases": ["azure-like-medium"], "apps": [0]}}


def main() -> int:
    cache = os.path.join(ROOT, "perfbench", ".cache")
    os.makedirs(cache, exist_ok=True)
    tmp = tempfile.mkdtemp(dir=cache)
    kept = os.path.join(tmp, "kept")
    out = split.split_cell("t9-grid", 2 ** 33 + 29, 1.0, TRAFFIC, keep=kept)
    shutil.rmtree(OUT, ignore_errors=True)
    os.makedirs(OUT)
    for i, f in enumerate(glob.glob(os.path.join(kept, "trace", "**",
                                                 "*.xplane.pb"),
                                    recursive=True)):
        with open(f, "rb") as src, gzip.open(
                os.path.join(OUT, f"trace{i}.xplane.pb.gz"), "wb") as dst:
            shutil.copyfileobj(src, dst)
    shutil.rmtree(tmp)
    with open(os.path.join(OUT, "reported.json"), "w") as f:
        json.dump(out, f, indent=1)
    print(json.dumps(out)[:2000])
    return 0


if __name__ == "__main__":
    sys.exit(main())
