"""The plain f64 reference of a configuration whose series are scraped as
Prometheus scrapes them: every series has its own timestamps, the shared
scrape grid plus its target's offset inside the scrape interval.

NumPy only: it imports nothing of the program and takes nothing the program
has made.  Semantics are `reference.py`'s (PromQL range functions over
(wend - range, wend], Prometheus' extrapolatedRate on reset-corrected counter
values, then `agg by (labels)` over the series) with `ts_row + phase[s]` in
place of the one timestamp row: the first and last sample of a window are
found for every (series, window) by a binary search on that series' own
timestamps, so a window may hold another count of samples for one series
than for its neighbour, and a series with fewer than two samples in a window
is absent from it (its group's sum adds the others; a group none of whose
series is present is NaN).  The arithmetic is `reference.py`'s, operation for
operation, so with every phase 0 the tables are its tables bit for bit.

Covers `sum`/`avg by` over `rate` and `increase`; another pairing is refused
with an error, not approximated.

    Reference(ts_row, wends_ms, range_ms, panels, num_base)
    add(vals [n, T] f64, base_ids [n], phase [n] whole ms)
    table(panel, fold) -> [G, W] f64
"""
import numpy as np


def series_windows(ts_row, phase, wends, range_ms):
    """First / last sample index and count of each window (wend - range,
    wend] of each series: [n, W] each.  A search for `t` in `ts_row +
    phase[s]` is a search for `t - phase[s]` in `ts_row` (whole ms)."""
    lo = np.searchsorted(ts_row, (wends - range_ms + 1)[None, :]
                         - phase[:, None], side="left")
    hi = np.searchsorted(ts_row, wends[None, :] - phase[:, None],
                         side="right") - 1
    return lo, hi, hi - lo + 1


def correct_counters(vals, out):
    """out <- vals with counter resets corrected by walking each row: a drop
    adds the full previous value to everything after it."""
    np.subtract(vals[:, 1:], vals[:, :-1], out=out[:, 1:])
    out[:, 0] = 0.0
    np.multiply(out[:, 1:] < 0, vals[:, :-1], out=out[:, 1:])
    np.cumsum(out, axis=1, out=out)
    out += vals
    return out


def series_increase(ts_row, phase, corr, wends, range_ms):
    """increase(v[range]) per series and window from reset-corrected values:
    Prometheus' extrapolatedRate on each series' own first and last sample.
    NaN where the series holds fewer than two samples in the window."""
    lo, hi, n = series_windows(ts_row, phase, wends, range_ms)
    ok = n >= 2
    lo, hi, n = np.where(ok, lo, 0), np.where(ok, hi, 1), np.where(ok, n, 2)
    we = np.broadcast_to(wends.astype(np.float64), lo.shape)
    v1 = np.take_along_axis(corr, lo, axis=1)
    v2 = np.take_along_axis(corr, hi, axis=1)
    t1 = (ts_row[lo] + phase[:, None]).astype(np.float64)
    t2 = (ts_row[hi] + phase[:, None]).astype(np.float64)
    dur_start = (t1 - (we - range_ms)) / 1000.0
    dur_end = (we - t2) / 1000.0
    sampled = (t2 - t1) / 1000.0
    avg = sampled / (n - 1)
    delta = v2 - v1
    with np.errstate(divide="ignore", invalid="ignore"):
        dur_zero = sampled * (v1 / delta)
    take = (delta > 0) & (v1 >= 0) & (dur_zero < dur_start)
    dur_start = np.where(take, dur_zero, dur_start)
    thr = avg * 1.1
    extrap = sampled + np.where(dur_start < thr, dur_start, avg / 2) \
        + np.where(dur_end < thr, dur_end, avg / 2)
    return np.where(ok, delta * (extrap / sampled), np.nan)


class Reference:
    """Accumulates, block of series by block, the per-series increases summed
    per base group (a one-hot matmul in f64) beside the count of series
    present, over every window end the traffic can ask for; `table()` folds
    the base groups into one panel's `by` labels."""

    def __init__(self, ts_row, wends, range_ms, panels, num_base):
        self.ts_row, self.wends, self.range_ms = ts_row, wends, range_ms
        self.num_base = num_base
        for p in panels:
            if p["fn"] not in ("rate", "increase") \
                    or p["agg"] not in ("sum", "avg"):
                raise ValueError(f"no reference for {p['agg']} over "
                                 f"{p['fn']}")
        W = len(wends)
        self.sums = np.zeros((num_base, W))
        self.present = np.zeros((num_base, W))

    def add(self, vals, base_ids, phase):
        """vals [n, T] f64 raw samples; base_ids [n] in 0..num_base-1;
        phase [n] whole ms: series i's samples lie at ts_row + phase[i]."""
        onehot = (np.arange(self.num_base)[:, None]
                  == base_ids[None, :]).astype(np.float64)
        corr = correct_counters(vals, np.empty_like(vals))
        inc = series_increase(self.ts_row, np.asarray(phase, np.int64), corr,
                              self.wends, self.range_ms)
        here = ~np.isnan(inc)
        self.sums += onehot @ np.where(here, inc, 0.0)
        self.present += onehot @ here.astype(np.float64)

    def table(self, panel, fold):
        """[G, W] f64 answers of one panel; `fold` [B] maps each base group
        to the panel's group (0..G-1), or to -1 where a selector leaves the
        base group out.  Absent windows are NaN."""
        G = int(fold.max()) + 1
        scale = np.ones(len(self.wends))
        if panel["fn"] == "rate":
            scale = scale / (self.range_ms / 1000.0)
        out = np.zeros((G, len(self.wends)))
        cnt = np.zeros((G, len(self.wends)))
        for b in np.flatnonzero(fold >= 0):
            out[fold[b]] += self.sums[b]
            cnt[fold[b]] += self.present[b]
        out = out * scale
        if panel["agg"] == "avg":
            with np.errstate(divide="ignore", invalid="ignore"):
                out = out / cnt
        out[cnt == 0] = np.nan
        return out
