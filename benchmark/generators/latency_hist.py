"""Native request-latency histograms, as an instrumented service exports
them: every scrape a series has seen Poisson(rate) more observations (rate 20
to 80 a scrape, drawn per series), each a log-normal latency whose median
depends on the series' `_ns_` (2^(8 + 1.5 k) for App-k: at least one bucket
apart) and whose sigma is 1.5 octaves, so a window of any group spreads over
eight or more of the 64 geometric buckets (le = 2 * 2^b).  A Poisson stream
split by bucket is independent Poisson streams, so each bucket's increments
are drawn directly.  Buckets are cumulative over `le` and over time; every
series restarts once in the second half of its samples, all buckets together.
"""
import math

import numpy as np

MEDIAN_LOG2, MEDIAN_STEP, SIGMA_LOG2 = 8.0, 1.5, 1.5
RATE_LO, RATE_HI = 20.0, 80.0
BAND_SIGMAS = 6.5           # beyond it a bucket's mass is under 1e-10


def bucket_mass(k, buckets):
    """P(latency in bucket b) for `_ns_` number k: bucket b holds
    (2^b, 2^(b+1)], bucket 0 everything up to 2, the last the rest."""
    edges = np.arange(1, buckets, dtype=np.float64)          # log2 of les
    z = (edges - (MEDIAN_LOG2 + MEDIAN_STEP * k)) / SIGMA_LOG2
    cdf = np.array([0.5 * (1.0 + math.erf(x / math.sqrt(2.0))) for x in z])
    return np.diff(np.concatenate([[0.0], cdf, [1.0]]))


def chunk(rng, out, ns=None):
    """Fill `out` [n, T, B] f64 with n histogram series from `rng`; `ns[j]`
    is series j's `_ns_` number (j mod 10 where none is given)."""
    n, T, B = out.shape
    ns = np.arange(n) % 10 if ns is None else np.asarray(ns)
    out[:] = 0.0
    rate = rng.uniform(RATE_LO, RATE_HI, size=n)
    for k in np.unique(ns):
        rows = np.flatnonzero(ns == k)
        mid = MEDIAN_LOG2 + MEDIAN_STEP * k
        lo = max(int(mid - BAND_SIGMAS * SIGMA_LOG2), 0)
        hi = min(int(mid + BAND_SIGMAS * SIGMA_LOG2) + 1, B)
        lam = rate[rows, None, None] * bucket_mass(k, B)[None, None, lo:hi]
        out[rows, :, lo:hi] = rng.poisson(
            np.broadcast_to(lam, (rows.size, T, hi - lo)))
    np.cumsum(out, axis=2, out=out)
    np.cumsum(out, axis=1, out=out)
    if T > 10:
        r = rng.integers(T // 2, T, size=n)
        before = out[np.arange(n), r - 1]                    # [n, B]
        out -= (np.arange(T)[None, :, None] >= r[:, None, None]) \
            * before[:, None, :]
    return out


def sum_and_count(h, les):
    """The schema's other two columns from the buckets: `count` is the top
    bucket, `sum` every observation at its bucket's geometric midpoint."""
    mids = les / math.sqrt(2.0)
    per_bucket = np.diff(h, axis=2, prepend=0.0)
    return per_bucket @ mids, h[:, :, -1].copy()
