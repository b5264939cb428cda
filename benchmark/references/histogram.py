"""The plain f64 reference for native-histogram configurations:
`histogram_quantile(q, sum by (..)(rate(metric[range])))` over `[n, T, B]`
cumulative bucket columns on one shared timestamp row.

NumPy only; imports nothing of the program and takes nothing the program has
made.  The per-bucket counter arithmetic (window indices, reset correction,
Prometheus' extrapolatedRate over (wend - range, wend]) is `../reference.py`'s,
the benchmark's own and proven against the chip since PR 24: a bucket is a
counter.  The quantile is written here from the published description of
`histogram_quantile`:

    rank = q x the top bucket's count; take the first bucket whose
    cumulative count is at or over the rank; interpolate linearly between
    that bucket's lower bound (the `le` of the bucket below; 0 for the first
    bucket when its `le` is positive) and its `le`, by the share of the
    bucket's own count that the rank reaches into.  q < 0 -> -Inf, q > 1 ->
    +Inf; a rank in a `+Inf` top bucket -> the highest finite `le`; a rank in
    a first bucket whose `le` is not positive -> that `le`; no observations
    (or a NaN anywhere) -> NaN; counts that fall from one bucket to the next
    (float jitter after rate and sum) are first raised to the running
    maximum.

Departures, each on purpose:
- Prometheus returns NaN for a classic histogram whose top bucket is not
  `+Inf`; upstream's `Histogram.quantile` (native histograms) does not ask for
  one, and neither does this: the configuration's 64 buckets are all finite.
- Resets are found per bucket (a bucket's own count fell), as Prometheus does
  for `_bucket` series; upstream's and Prometheus' native histograms reset a
  whole histogram when any bucket falls.  The two differ only where a bucket's
  first count after a restart is at or over its last count before it.
- A group's window is present when the shared timestamp row has two samples
  in it (all series share the row and have no holes).
"""
import importlib.util
import os

import numpy as np


def _counter_math():
    path = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                        os.pardir, "reference.py")
    spec = importlib.util.spec_from_file_location("benchmark_reference", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


counters = _counter_math()


def histogram_quantile(q, cum, les):
    """cum [..., B] cumulative bucket counts, les [B] ascending -> [...]."""
    cum = np.maximum.accumulate(np.asarray(cum, np.float64), axis=-1)
    shape = cum.shape[:-1]
    out = np.full(shape, np.nan)
    if q != q:
        return out
    if q < 0 or q > 1:
        return np.full(shape, -np.inf if q < 0 else np.inf)
    flat, res = cum.reshape(-1, cum.shape[-1]), out.reshape(-1)
    finite = les[np.isfinite(les)]
    for i, row in enumerate(flat):
        total = row[-1]
        if not total > 0:           # empty, or not a number
            continue
        rank = q * total
        b = int(np.argmax(row >= rank))
        if np.isinf(les[b]):
            res[i] = finite[-1]
        elif b == 0 and les[0] <= 0:
            res[i] = les[0]
        else:
            lower = les[b - 1] if b else 0.0
            below = row[b - 1] if b else 0.0
            inside = row[b] - below
            res[i] = lower + (les[b] - lower) * (
                (rank - below) / inside if inside > 0 else 0.0)
    return out


class Reference:
    """Accumulates, block of series by block, the per-bucket increase of
    every base group over every window end the traffic can ask for;
    `table()` folds base groups into a panel's `by` labels and takes the
    panel's quantile `q`."""

    def __init__(self, ts_row, wends, range_ms, panels, num_base, les):
        for p in panels:
            if p["fn"] != "rate" or p["agg"] != "sum" or "q" not in p:
                raise ValueError(f"no reference for {p}")
        self.ts_row, self.wends, self.range_ms = ts_row, wends, range_ms
        self.les = np.asarray(les, np.float64)
        self.num_base = num_base
        self.n_w = counters.ref_windows(ts_row, wends, range_ms)[2]
        self.sums = np.zeros((num_base, len(self.les), len(wends)))

    def increase_by_base(self, vals, base_ids):
        """[num_base, B, W]: what a block of series adds to the sums.  vals
        [n, T, B] f64 raw cumulative buckets; base_ids [n].  Touches
        nothing of the instance, so blocks may be worked side by side."""
        n, T, B = vals.shape
        rows = np.ascontiguousarray(np.moveaxis(vals, 2, 1)).reshape(n * B, T)
        corr = counters.correct_counters(rows, np.empty_like(rows))
        inc = counters.ref_increase(self.ts_row, corr, self.wends,
                                    self.range_ms)
        onehot = (np.arange(self.num_base)[:, None]
                  == base_ids[None, :]).astype(np.float64)
        return (onehot @ inc.reshape(n, -1)).reshape(self.num_base, B, -1)

    def accumulate(self, part):
        self.sums += part

    def add(self, vals, base_ids):
        """vals [n, T, B] f64 raw cumulative buckets; base_ids [n]."""
        self.accumulate(self.increase_by_base(vals, base_ids))

    def bucket_rates(self, fold):
        """[G, W, B]: sum by the panel's groups of rate per bucket."""
        G = int(fold.max()) + 1
        out = np.zeros((G,) + self.sums.shape[1:])
        for b in np.flatnonzero(fold >= 0):
            out[fold[b]] += self.sums[b]
        out = np.moveaxis(out, 1, 2) / (self.range_ms / 1000.0)
        out[:, self.n_w < 2] = np.nan
        return out

    def table(self, panel, fold):
        """[G, W] f64 answers of one panel; NaN where a window is absent."""
        return histogram_quantile(panel["q"], self.bucket_rates(fold),
                                  self.les)
