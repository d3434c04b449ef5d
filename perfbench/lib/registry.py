"""Finds a cell's files by the names in `BENCHMARK.json`.

* configuration ``<name>``: ``perfbench/configs/<name>.json``; its
  ``engine`` key names ``perfbench/engines/<engine>.py``;
* traffic ``<name>``: ``perfbench/traffic/<name>.json``;
* metric ``<name>``, end-to-end or per-layer:
  ``perfbench/metrics/<name>.py``, whose ``read(record)`` returns the
  number or None (`perfbench.run.Record` is what it reads).

A cell or a metric is added by adding files and an entry; no existing
file changes.
"""

from __future__ import annotations

import importlib
import json
import os

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
BENCH_DIR = os.path.join(ROOT, "perfbench")


def benchmark() -> dict:
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


def workload(bench: dict, name: str) -> dict:
    for w in bench["workloads"]:
        if w["name"] == name:
            return w
    raise KeyError(f"no workload {name!r} in BENCHMARK.json")


def _json(kind: str, name: str) -> dict:
    with open(os.path.join(BENCH_DIR, kind, f"{name}.json")) as f:
        return json.load(f)


def config(name: str) -> dict:
    return _json("configs", name)


def traffic(name: str) -> dict:
    return _json("traffic", name)


def engine(name: str):
    return importlib.import_module(f"perfbench.engines.{name}")


def metric(name: str):
    return importlib.import_module(f"perfbench.metrics.{name}")


def metrics_for(bench: dict, kind: str, cell: str) -> list:
    """The ``end_to_end`` or ``per_layer`` entries this cell reports."""
    return [m for m in bench[kind]
            if "workloads" not in m or cell in m["workloads"]]
