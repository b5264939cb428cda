"""`ingest/generator.counter_batch` semantics (upstream's
TestTimeseriesProducer counters): exponential increments of mean 10, each
series resetting to ~0 once in the second half of its samples."""
import numpy as np


def chunk(rng, out):
    """Fill `out` [n, T] f64 with n counter series from `rng`."""
    n, T = out.shape
    rng.standard_exponential(out=out)
    out *= 10.0
    np.cumsum(out, axis=1, out=out)
    if T > 10:
        r = rng.integers(T // 2, T, size=n)
        before = out[np.arange(n), r - 1]
        out -= (np.arange(T)[None, :] >= r[:, None]) * before[:, None]
    return out
