"""The benchmark's own arithmetic: arrivals, percentiles and spreads,
the trace reduction's interval sums, the seed's weights and labels."""

import numpy as np
import pytest

from bench_rehearsal_util import ROOT  # noqa: F401 -- puts the repo on the path

from benchmark.lib import arrivals, labels, stats, trace_reduce, weights


@pytest.mark.parametrize("process", ["exponential", "uniform", "onoff"])
def test_every_seed_offers_the_same_gaps_in_another_order(process):
    traffic = {"rate_rps": 20.0, "process": process, "on_share": 0.25,
               "burst_requests": 8}
    a = arrivals.schedule(traffic, 10, 3)
    b = arrivals.schedule(traffic, 10, 2 ** 31 + 9)
    assert len(a) == len(b) == 200
    assert a[0] == 0.0 and a[-1] < 10.0 and np.all(np.diff(a) >= 0)
    gaps = lambda x: np.sort(np.diff(np.append(x, 10.0)))  # noqa: E731
    np.testing.assert_allclose(gaps(a), gaps(b), atol=1e-9)
    if process == "exponential":
        assert not np.allclose(a, b)
        mean_gap = np.diff(np.append(a, 10.0)).mean()
        assert mean_gap == pytest.approx(1 / 20.0, rel=1e-9)


def test_onoff_keeps_the_mean_rate_and_bursts():
    traffic = {"rate_rps": 20.0, "process": "onoff", "on_share": 0.25,
               "burst_requests": 8}
    gaps = np.diff(np.append(arrivals.schedule(traffic, 10, 1), 10.0))
    assert gaps.sum() == pytest.approx(10.0)
    assert np.sort(gaps)[-20:].mean() > 5 * np.median(gaps)


def test_unknown_process_and_empty_window_are_refused():
    with pytest.raises(ValueError):
        arrivals.schedule({"rate_rps": 5, "process": "fractal"}, 10, 0)
    with pytest.raises(ValueError):
        arrivals.schedule({"rate_rps": 0.01}, 10, 0)


@pytest.mark.parametrize("samples,q,want", [
    ([], 0.5, None), ([7.0], 0.95, 7.0), ([10, 20], 0.5, 15.0),
    (list(range(101)), 0.95, 95.0), ([3, 1, 2], 0.5, 2.0)])
def test_percentile(samples, q, want):
    assert stats.percentile(samples, q) == want


def test_quartile_spread_is_statistics_quantiles():
    values = [100, 101, 102, 103, 104, 110]
    import statistics

    q1, _, q3 = statistics.quantiles(values, n=4)
    assert stats.quartile_spread(values) == pytest.approx(
        (q3 - q1) / statistics.median(values))


def test_union_and_gaps_of_intervals():
    spans = [(0, 10), (5, 20), (30, 40), (32, 35)]
    assert trace_reduce.union_seconds(spans) == pytest.approx(30e-9)
    assert trace_reduce.gaps(spans, 0, 50) == [(20, 30), (40, 50)]
    assert trace_reduce._covering([(18, 31, "pump"), (0, 5, "wait")],
                                  20, 30) == "pump"
    assert trace_reduce._covering([], 20, 30) == "unattributed"


def test_short_op_name():
    long = ("%fusion.35 = f32[256,8,33,3]{1,3,2,0:T(4,128)S(1)} "
            "fusion(bf16[260,1,7,4,256]{4,2,3,0,1} %slice.275)")
    assert trace_reduce.short_op_name(long) == "fusion.35 f32[256,8,33,3]"
    assert trace_reduce.short_op_name("plain") == "plain"


def test_weights_are_a_function_of_the_seed_alone():
    spec = {"a/kernel": ((3, 3, 4, 8), "kernel"), "a/bias": ((8,), "bias"),
            "a/u": ((8,), "u"), "n/mean": ((8,), "bn_mean"),
            "n/var": ((8,), "bn_var")}
    big = 2 ** 31 + 12345
    one, two = weights.make(spec, big), weights.make(spec, big)
    other = weights.make(spec, big + 1)
    for name in spec:
        np.testing.assert_array_equal(one[name], two[name])
        assert not np.array_equal(one[name], other[name])
    assert float(np.linalg.norm(one["a/u"])) == pytest.approx(1.0, rel=1e-5)
    assert float(np.min(one["n/var"])) > 0.2
    with pytest.raises(ValueError):
        weights.make({"x": ((2,), "mystery")}, 1)


def test_label_stacks_are_one_hot_with_an_edge_channel():
    pool = labels.label_pool(2 ** 31 + 5, 3, 64, 12)
    assert len(pool) == 3 and pool[0].shape == (1, 64, 64, 12)
    classes = pool[0][..., :-1]
    np.testing.assert_array_equal(classes.sum(-1), 1.0)
    assert 0 < pool[0][..., -1].mean() < 0.5
    assert not np.array_equal(pool[0], pool[1])
    again = labels.label_pool(2 ** 31 + 5, 3, 64, 12)
    np.testing.assert_array_equal(pool[2], again[2])
