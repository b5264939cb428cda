"""TSBS devops `cpu` fields (cmd/tsbs_generate_data, the `cpu` measurement;
from memory): every field a random walk clamped to [0, 100], a normal step
of unit deviation every interval, its start drawn uniformly from the range.
One call fills one field (one metric) of every host."""
import numpy as np

LOW, HIGH = 0.0, 100.0


def chunk(rng, out):
    """Fill `out` [n, T] f64 with n clamped walks from `rng`."""
    n, T = out.shape
    # walked down the time axis a whole interval at a time: [T, n], each
    # interval's n values beside each other, turned into `out` at the end
    walk = rng.standard_normal((T, n))
    walk[0] = rng.uniform(LOW, HIGH, size=n)
    for t in range(1, T):
        row = walk[t]
        row += walk[t - 1]
        np.clip(row, LOW, HIGH, out=row)
    out[:] = walk.T
    return out
