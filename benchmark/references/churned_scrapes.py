"""The plain f64 reference of a configuration whose series come and go:
every series lies on the shared scrape grid plus its target's offset, as
`scrape_offsets.py`'s do, and holds only SOME of the grid's samples: it may
start late (a target that replaced another), end early (a target that was
replaced) and miss scrapes in between (a target that restarted).

NumPy only: it imports nothing of the program and takes nothing the program
has made.  A sample that does not exist is not a sample: the reset correction
walks the samples that exist, in their order (a drop across a hole is a
reset), a window (wend - range, wend] holds the existing samples whose own
timestamps fall in it, its first and last sample are the first and last that
exist, and a series with fewer than two existing samples in a window is
absent from it, as in Prometheus (its group's sum adds the others; a group
none of whose series is present is NaN).  The arithmetic on those boundaries
is `scrape_offsets.py`'s (and so `reference.py`'s), operation for operation:
with every sample present the tables are its tables bit for bit.

Covers `sum`/`avg by` over `rate` and `increase`; another pairing is refused
with an error, not approximated.

    Reference(ts_row, wends_ms, range_ms, panels, num_base)
    add(vals [n, T] f64, base_ids [n], phase [n] whole ms, exists [n, T] bool)
    table(panel, fold) -> [G, W] f64
"""
import numpy as np


def existing_neighbours(exists):
    """(at_or_after, at_or_before, held): for every slot of every series the
    index of the first existing sample at or after it (T where none), of the
    last at or before it (-1 where none), and the count of existing samples
    up to and including it.  [n, T] each."""
    n, T = exists.shape
    idx = np.arange(T)[None, :]
    at_or_before = np.maximum.accumulate(np.where(exists, idx, -1), axis=1)
    at_or_after = np.minimum.accumulate(
        np.where(exists, idx, T)[:, ::-1], axis=1)[:, ::-1]
    return at_or_after, at_or_before, np.cumsum(exists, axis=1)


def correct_counters(vals, exists, at_or_before):
    """vals with counter resets corrected over the samples that exist: a
    sample below the existing sample before it adds that sample's full value
    to itself and to everything after it.  Slots without a sample hold
    nothing that is read."""
    n, T = vals.shape
    prev = np.concatenate([np.full((n, 1), -1), at_or_before[:, :-1]], axis=1)
    before = np.take_along_axis(vals, np.maximum(prev, 0), axis=1)
    drop = exists & (prev >= 0) & (before > vals)
    return vals + np.cumsum(np.where(drop, before, 0.0), axis=1)


def series_windows(ts_row, phase, exists, neighbours, wends, range_ms):
    """First / last EXISTING sample index and count of existing samples of
    each window (wend - range, wend] of each series: [n, W] each.  The
    window's slots are found as `scrape_offsets.py` finds them (a search for
    `t` in `ts_row + phase[s]` is one for `t - phase[s]` in `ts_row`), the
    samples inside them by the neighbour tables (`existing_neighbours`)."""
    T = len(ts_row)
    after, before, held = neighbours
    lo = np.searchsorted(ts_row, (wends - range_ms + 1)[None, :]
                         - phase[:, None], side="left")
    hi = np.searchsorted(ts_row, wends[None, :] - phase[:, None],
                         side="right") - 1
    some = (lo <= hi) & (lo < T) & (hi >= 0)
    lo_c, hi_c = np.clip(lo, 0, T - 1), np.clip(hi, 0, T - 1)
    first = np.take_along_axis(after, lo_c, axis=1)
    last = np.take_along_axis(before, hi_c, axis=1)
    n = np.take_along_axis(held, hi_c, axis=1) \
        - np.take_along_axis(held, lo_c, axis=1) \
        + np.take_along_axis(exists, lo_c, axis=1)
    return first, last, np.where(some, n, 0)


def series_increase(ts_row, phase, vals, exists, wends, range_ms):
    """increase(v[range]) per series and window: Prometheus' extrapolatedRate
    on each series' own first and last existing sample of the window, values
    reset-corrected over the samples that exist.  NaN where the series holds
    fewer than two samples in the window."""
    neighbours = existing_neighbours(exists)
    lo, hi, n = series_windows(ts_row, phase, exists, neighbours, wends,
                               range_ms)
    corr = correct_counters(vals, exists, neighbours[1])
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
    with np.errstate(divide="ignore", invalid="ignore"):
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

    def add(self, vals, base_ids, phase, exists):
        """vals [n, T] f64 raw samples (what a slot without a sample holds
        is not read); base_ids [n] in 0..num_base-1; phase [n] whole ms:
        series i's samples lie at ts_row + phase[i]; exists [n, T] bool:
        which of them exist."""
        onehot = (np.arange(self.num_base)[:, None]
                  == base_ids[None, :]).astype(np.float64)
        inc = series_increase(self.ts_row, np.asarray(phase, np.int64), vals,
                              np.asarray(exists, bool), self.wends,
                              self.range_ms)
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
