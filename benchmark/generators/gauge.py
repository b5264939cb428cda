"""`ingest/generator.gauge_batch` semantics (upstream's
TestTimeseriesProducer gauges): a sine of period 40 pi samples around 100,
amplitude 50, a random phase per series, plus N(0, 2) noise."""
import numpy as np


def chunk(rng, out):
    """Fill `out` [n, T] f64 with n gauge series from `rng`."""
    n, T = out.shape
    phase = rng.uniform(0, 2 * np.pi, size=n)
    rng.standard_normal(out=out)
    out *= 2.0
    out += 100.0
    # 50 sin(t / 20 + phase) by the angle sum: n + T sines instead of n * T
    # (a third of the cell's data generation at 262,144 x 720)
    t = np.arange(T) / 20.0
    out += np.outer(50.0 * np.cos(phase), np.sin(t))
    out += np.outer(50.0 * np.sin(phase), np.cos(t))
    return out
