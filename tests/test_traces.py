"""Direct unit tests for the production stand-ins (`repro.core.traces`,
now a façade over `repro.workloads.scenarios`).

Covers the stand-in contract documented in docs/EXPERIMENTS.md
§Production stand-ins — Table 7 app counts, bucket-bounded request
sizes, the 10x-size default deadline, seed determinism — plus golden
values captured from the pre-refactor `core.traces` implementation, so
the workloads-layer refactor (and any future one) stays bit-identical
under fixed seeds.
"""

import jax
import numpy as np
import pytest

from repro.core.traces import (BUCKETS_S, TABLE7, Trace, alibaba_like_apps,
                               azure_like_apps, production_like_apps,
                               synthetic_trace)


def test_table7_app_counts():
    for source, buckets in TABLE7.items():
        for bucket, expected in buckets.items():
            apps = production_like_apps(source, bucket, seed=0, horizon_s=120)
            assert len(apps) == expected, (source, bucket)


def test_missing_bucket_raises():
    with pytest.raises(ValueError, match="no long bucket"):
        alibaba_like_apps("long", horizon_s=120)


def test_request_sizes_within_bucket_bounds():
    for source, buckets in TABLE7.items():
        for bucket in buckets:
            lo, hi = BUCKETS_S[bucket]
            apps = production_like_apps(source, bucket, seed=3,
                                        horizon_s=120, n_apps=8)
            for tr in apps:
                assert lo <= tr.request_size_s <= hi, (source, bucket, tr.name)
                assert tr.meta["source"] == source
                assert tr.meta["bucket"] == bucket


def test_default_deadline_is_10x_request_size():
    tr = synthetic_trace(seed=0, horizon_s=120, request_size_s=0.08)
    assert tr.deadline == pytest.approx(0.8)
    explicit = Trace("x", 0.08, np.ones(10), deadline_s=2.5)
    assert explicit.deadline == 2.5


def test_sample_counts_deterministic_and_poisson_scaled():
    tr = synthetic_trace(seed=5, horizon_s=600)
    a = tr.sample_counts(11).copy()
    b = tr.sample_counts(11).copy()
    np.testing.assert_array_equal(a, b)
    c = tr.sample_counts(12).copy()
    assert not np.array_equal(a, c)
    # Poisson(mean rates): totals match expected volume within a few sigma
    expected = tr.rates_per_s.sum()
    assert abs(a.sum() - expected) < 6 * np.sqrt(expected)


def test_arrival_times_deterministic_sorted_and_counted():
    tr = synthetic_trace(seed=7, horizon_s=300)
    tr.sample_counts(7)
    a = tr.arrival_times(21)
    b = tr.arrival_times(21)
    np.testing.assert_array_equal(a, b)
    assert len(a) == int(tr.counts.sum())
    # arrivals land inside their second, in order within each second
    sec = np.floor(a).astype(int)
    np.testing.assert_array_equal(np.repeat(np.arange(300), tr.counts), sec)


# ----------------------------------------------------------------- goldens
# Captured from the pre-refactor `core.traces` implementation (PR 3 tree)
# at fixed seeds; the workloads-layer delegation must reproduce them
# bit-identically (docs/EXPERIMENTS.md §Production stand-ins).

def test_golden_azure_like():
    az = azure_like_apps("short", seed=1, horizon_s=600, n_apps=2)
    assert [t.name for t in az] == ["azure-short-0", "azure-short-1"]
    assert repr(az[0].request_size_s) == "0.03249538035472372"
    assert repr(float(az[0].rates_per_s.sum())) == "578560.6386108398"
    assert int(az[0].counts.sum()) == 579336
    assert [int(x) for x in az[0].counts[:5]] == [3196, 3153, 3163, 3139, 3163]
    assert repr(az[1].request_size_s) == "0.08922030351678924"
    assert int(az[1].counts.sum()) == 54686


def test_golden_azure_like_under_partitionable_threefry():
    """The stand-in streams keep their bits whatever the caller's
    ``jax_threefry_partitionable`` (`repro.core.rng.pinned_stream`)."""
    with jax.threefry_partitionable(True):
        az = azure_like_apps("short", seed=1, horizon_s=600, n_apps=2)
    assert repr(float(az[0].rates_per_s.sum())) == "578560.6386108398"
    assert int(az[0].counts.sum()) == 579336
    assert int(az[1].counts.sum()) == 54686


def test_golden_alibaba_like():
    al = alibaba_like_apps("medium", seed=2, horizon_s=600, n_apps=2)
    assert repr(al[0].request_size_s) == "0.18264682798437928"
    assert repr(float(al[0].rates_per_s[0])) == "67.79136657714844"
    assert int(al[0].counts.sum()) == 40122
    assert [int(x) for x in al[1].counts[:5]] == [8, 6, 10, 9, 6]


def test_golden_synthetic_trace():
    tr = synthetic_trace(seed=3, bias=0.7, horizon_s=600, request_size_s=0.05)
    assert repr(float(tr.rates_per_s.sum())) == "1417427.2576904297"
    assert int(tr.counts.sum()) == 1417571
    assert [int(x) for x in tr.counts[:5]] == [7626, 7492, 7514, 7332, 7440]
    at = tr.arrival_times(5)
    assert len(at) == 1417571
    assert repr(float(at[:10].sum())) == "0.009589436830737541"
