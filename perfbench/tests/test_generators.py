"""The benchmark's stream generators reproduce the checksums stored
beside them, so the yardstick cannot drift."""

import hashlib
import json
import os

import numpy as np
import pytest

from perfbench.lib import generators as gen
from perfbench.lib import registry

CHECKSUMS = os.path.join(registry.BENCH_DIR, "data",
                         "generator_checksums.json")


def digests(config_name: str) -> dict:
    cfg = registry.config(config_name)
    eng = registry.engine(cfg["engine"])
    traffic = registry.traffic("full-grid")
    streams = eng.base(cfg, traffic)
    h = hashlib.sha256()
    for c in streams:
        h.update(np.ascontiguousarray(c, np.int64).tobytes())
    t = gen.realize_times(streams[0], 30, gen.rng_for(2 ** 35 + 1, 4, 0))
    return {"base": h.hexdigest(),
            "arrivals": int(sum(int(c.sum()) for c in streams)),
            "realization": hashlib.sha256(t.tobytes()).hexdigest()}


@pytest.mark.parametrize("config_name", ["spork-table9-des"])
def test_generators_match_checksums(config_name):
    with open(CHECKSUMS) as f:
        want = json.load(f)[config_name]
    assert digests(config_name) == want


def test_realization_keeps_interval_counts():
    counts = gen.base_counts("bmodel", {"bias": 0.68}, 100, 600, 0.05, 8.0)
    t = gen.realize_times(counts, 40, gen.rng_for(-3, 2 ** 40))
    per = np.bincount(np.ceil(t / 10).astype(int) - 1, minlength=60)
    base = counts.reshape(60, 10).sum(1)
    assert sorted(per) == sorted(base) and len(t) == counts.sum()
    assert np.all(np.diff(t) >= 0)
