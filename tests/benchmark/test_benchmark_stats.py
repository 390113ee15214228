"""Percentiles, the ten-samples-beyond rule and the contract's spread."""

import statistics

import numpy as np
import pytest

from benchmark.readers.common import reduce_samples
from benchmark.reduce import stats


@pytest.mark.parametrize("q", [0, 50, 90, 95, 99, 100])
def test_percentile_is_numpys_linear_one(q):
    rng = np.random.RandomState(q)
    values = list(rng.lognormal(size=257))
    assert stats.percentile(values, q) == pytest.approx(
        float(np.percentile(values, q)), rel=1e-12)


def test_percentile_of_nothing_is_an_error():
    with pytest.raises(ValueError):
        stats.percentile([], 50)


@pytest.mark.parametrize("n,q,ok", [
    (199, 95, False), (200, 95, True), (99, 90, False), (100, 90, True),
    (1000, 99, True), (999, 99, False), (20, 50, True), (19, 50, False),
])
def test_ten_samples_beyond_rule(n, q, ok):
    assert stats.supported(n, q) is ok


def test_spread_is_the_contracts_quartile_distance():
    values = [10.0, 10.2, 9.9, 10.1, 10.4, 9.8]
    q1, _, q3 = statistics.quantiles(values, n=4)
    assert stats.spread(values) == pytest.approx(
        (q3 - q1) / statistics.median(values))


@pytest.mark.parametrize("how,want", [
    ("p50", 3.0), ("median", 3.0), ("mean", 3.0), ("sum", 15.0),
    ("p100", 5.0), ("share_over_3.5", 40.0), ("share_over_5", 0.0),
])
def test_reduce_samples(how, want):
    assert reduce_samples([1.0, 2.0, 3.0, 4.0, 5.0], how) == want


def test_reduce_samples_of_nothing_is_nothing():
    assert reduce_samples([], "p95") is None
    with pytest.raises(ValueError):
        reduce_samples([1.0], "mode")
