"""The benchmark's histogram reference (`benchmark/references/histogram.py`,
the one copy, loaded by path) on histograms worked by hand."""
import numpy as np
import pytest

import histrig

ref = histrig.bench_module("references", "histogram")
LES = np.array([1.0, 2.0, 4.0, 8.0])
INF = np.array([1.0, 2.0, 4.0, np.inf])


@pytest.mark.parametrize("q,cum,les,want", [
    # rank 0.5 * 10 = 5 falls in (2, 4], which holds counts 3..7: 2 of its 4
    (0.5, [1, 3, 7, 10], LES, 2.0 + 2.0 * (5 - 3) / 4),
    # rank 0.05 * 10 = 0.5 in the first bucket: from 0, half of its 1
    (0.05, [1, 3, 7, 10], LES, 0.5),
    # rank 9 of 10 in a +Inf top bucket: the highest finite le
    (0.9, [1, 3, 7, 10], INF, 4.0),
    # rank exactly on a bucket's count: that bucket's le
    (0.7, [1, 3, 7, 10], LES, 4.0),
    # counts that dip (float jitter) are raised to the running maximum
    (0.5, [1, 3, 2.999, 10], LES, 4.0 + 4.0 * (5 - 3) / 7),
    # a first bucket whose le is not positive answers its le
    (0.1, [5, 6, 7, 10], np.array([-1.0, 2.0, 4.0, 8.0]), -1.0),
    # an empty window
    (0.9, [0, 0, 0, 0], LES, np.nan),
    (0.9, [np.nan] * 4, LES, np.nan),
    # q outside [0, 1]
    (-0.1, [1, 3, 7, 10], LES, -np.inf),
    (1.5, [1, 3, 7, 10], LES, np.inf),
    (1.0, [1, 3, 7, 10], LES, 8.0),
], ids=["inside", "first-bucket", "inf-top", "on-edge", "monotone",
        "negative-le", "empty", "nan", "q<0", "q>1", "q=1"])
def test_quantile_by_hand(q, cum, les, want):
    got = ref.histogram_quantile(q, np.array([cum], float), les)
    assert got.shape == (1,)
    np.testing.assert_allclose(got[0], want, rtol=1e-15, equal_nan=True)


def test_reference_table_by_hand():
    """Two series, two buckets, six scrapes 10 s apart; one window
    (20 s, 50 s]; the second series restarts at 40 s, both buckets."""
    ts = np.arange(6, dtype=np.int64) * 10_000
    a = np.array([[0, 0], [1, 2], [2, 4], [3, 6], [4, 8], [5, 10]], float)
    b = np.array([[0, 0], [2, 4], [4, 8], [6, 12], [1, 2], [3, 6]], float)
    panel = {"fn": "rate", "agg": "sum", "by": [], "q": 0.5}
    r = ref.Reference(ts, np.array([50_000]), 30_000, [panel], 1,
                      np.array([1.0, 2.0]))
    r.add(np.stack([a, b]), np.array([0, 0]))
    # a: samples at 30, 40, 50 s: bucket 0 rises 3 -> 5, bucket 1 6 -> 10;
    # b corrected: bucket 0 6, 7, 9 and bucket 1 12, 14, 18.  Sampled 20 s
    # of the 30 s window, the 10 s before the first sample extrapolated in
    # full (under 1.1 x the 10 s spacing): increase = delta * 30 / 20;
    # rate = increase / 30 s = delta / 20
    rates = r.bucket_rates(np.array([0]))
    np.testing.assert_allclose(rates[0, 0], [(2 + 3) / 20, (4 + 6) / 20],
                               rtol=1e-15)
    # rank 0.5 * 0.5 = 0.25 is bucket 0's whole count: its le
    np.testing.assert_allclose(r.table(panel, np.array([0])), [[1.0]])
    # a base group the fold leaves out is not in the table
    assert r.table(panel, np.array([-1])).shape == (0, 1)
    with pytest.raises(ValueError):
        ref.Reference(ts, np.array([50_000]), 30_000,
                      [{"fn": "increase", "agg": "sum", "by": [], "q": 0.5}],
                      1, LES)


def test_generator_keeps_its_promises():
    gen = histrig.bench_module("generators", "latency_hist")
    ns = np.arange(40) % 10
    h = gen.chunk(np.random.default_rng([7, 0]), np.empty((40, 240, 64)), ns)
    again = gen.chunk(np.random.default_rng([7, 0]), np.empty((40, 240, 64)),
                      ns)
    assert (h == again).all()
    assert (np.diff(h, axis=2) >= 0).all(), "cumulative over buckets"
    drops = (np.diff(h[:, :, -1], axis=1) < 0)
    assert (drops.sum(axis=1) == 1).all(), "one restart a series"
    assert (np.argmax(drops, axis=1) + 1 >= 120).all(), "in the second half"
    # at the restart every bucket that held anything falls
    s, t = np.nonzero(drops)
    assert ((h[s, t + 1] < h[s, t]) | (h[s, t] == 0)).all()
    # a window of any group: medians a bucket or more apart, eight or more
    # buckets hold observations
    first_half = h[:, 100] - h[:, 70]
    medians = []
    for k in range(10):
        per_bucket = np.diff(first_half[ns == k].sum(axis=0), prepend=0.0)
        assert (per_bucket > 0).sum() >= 8
        medians.append(int(np.searchsorted(
            np.cumsum(per_bucket), per_bucket.sum() / 2)))
    assert (np.diff(medians) >= 1).all(), medians
    les = 2.0 * 2.0 ** np.arange(64)
    total, count = gen.sum_and_count(h, les)
    assert (count == h[:, :, -1]).all() and total.shape == count.shape
