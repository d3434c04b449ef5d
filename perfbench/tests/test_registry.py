"""BENCHMARK.json and the files it names: every configuration, traffic
mix, engine and per-layer metric loads by name, and every name and unit
keeps to the allowed characters."""

import json
import os
import re

import pytest

from perfbench.lib import registry

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
BENCH = registry.benchmark()


def test_top_level_keys():
    assert set(BENCH) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert BENCH["paths"] == ["perfbench"]
    assert BENCH["command"] == ["python3", "perfbench/run.py"]
    assert 1 <= BENCH["run_seconds"] <= 51
    assert len(json.dumps(BENCH)) < 64 * 1024


@pytest.mark.parametrize("c", BENCH["configs"], ids=lambda c: c["name"])
def test_config_loads_by_name(c):
    assert set(c) == {"name", "source", "file", "reduced", "why"}
    assert NAME.match(c["name"]) and all(NAME.match(k) for k in c["reduced"])
    assert c["file"] == f"perfbench/configs/{c['name']}.json"
    cfg = registry.config(c["name"])
    assert cfg["name"] == c["name"]
    assert cfg["reduced"] == c["reduced"]
    assert all(k in cfg for k in cfg["reduced"])
    eng = registry.engine(cfg["engine"])
    for fn in ("base", "realize", "plan", "submit", "answer",
               "reference_of"):
        assert callable(getattr(eng, fn))
    assert any(w["config"] == c["name"] for w in BENCH["workloads"])


@pytest.mark.parametrize("w", BENCH["workloads"], ids=lambda w: w["name"])
def test_workload_loads_by_name(w):
    assert set(w) == {"name", "config", "traffic", "chips", "why"}
    assert NAME.match(w["name"]) and NAME.match(w["traffic"])
    assert w["chips"] in (1, 4) and 1 <= len(w["why"]) <= 200
    registry.config(w["config"])
    cfg = registry.config(w["config"])
    tr = registry.traffic(w["traffic"])
    eng = registry.engine(cfg["engine"])
    assert set(tr) <= eng.TRAFFIC_KEYS
    assert set(tr.get("subset") or {}) <= eng.SUBSET_KEYS
    e2e = registry.metrics_for(BENCH, "end_to_end", w["name"])
    assert "setup_s" in {m["name"] for m in e2e} and len(e2e) >= 2
    assert registry.metrics_for(BENCH, "per_layer", w["name"])


@pytest.mark.parametrize("m", BENCH["end_to_end"] + BENCH["per_layer"],
                         ids=lambda m: m["name"])
def test_metric_names_units(m):
    assert NAME.match(m["name"]) and UNIT.match(m["unit"])
    assert m["better"] in ("lower", "higher")
    assert callable(registry.metric(m["name"]).read)
    names = {w["name"] for w in BENCH["workloads"]}
    assert set(m.get("workloads", names)) <= names
    if "bound" in m:
        assert 0.01 <= m["bound"] <= 0.25
        assert m["source"] in ("host_clock", "device_trace")
    else:
        assert m["moves"] in {e["name"] for e in BENCH["end_to_end"]}


def test_names_are_unique():
    for key in ("configs", "workloads"):
        names = [x["name"] for x in BENCH[key]]
        assert len(names) == len(set(names))
    names = [m["name"] for m in BENCH["end_to_end"] + BENCH["per_layer"]]
    assert len(names) == len(set(names))


def test_layers_named_in_perf_md():
    perf = open(os.path.join(registry.ROOT, "PERF.md")).read()
    for m in BENCH["per_layer"]:
        assert m["layer"] in perf


@pytest.mark.parametrize("key", ["loop", "clients", "reorder"])
def test_traffic_key_not_implemented_is_refused(key):
    cfg = registry.config("spork-table9-des")
    eng = registry.engine(cfg["engine"])
    traffic = dict(registry.traffic("full-grid"), horizon_s=60)
    traffic[key] = 1
    with pytest.raises(ValueError, match=key):
        eng.base(cfg, traffic)
    with pytest.raises(ValueError, match="tenants"):
        eng.base(cfg, dict(registry.traffic("full-grid"), horizon_s=60,
                           subset={"tenants": [1]}))
