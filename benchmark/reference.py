"""The plain f64 reference the benchmark holds the served answers to.

NumPy only: it imports nothing of the program and takes nothing the program
has made.  The window arithmetic is `chip_smoke.py`'s (proven against the
chip in PR 24), copied here so that later PRs may change the program and the
smoke, not the yardstick.  Semantics: PromQL range functions over (wend -
range, wend] on one shared timestamp row, Prometheus' extrapolatedRate on
reset-corrected counter values, then `agg by (labels)` over the series.

All series of a configuration share one timestamp row and have no holes, so
a window is present for every series or for none.
"""
import numpy as np

PER_SERIES_SUM = {"increase", "rate", "sum_over_time", "avg_over_time"}
EXTREMA = {"max_over_time": "max", "min_over_time": "min"}


def ref_windows(ts_row, wends, range_ms):
    """First/last sample index and count of each window (wend-range, wend]."""
    lo = np.searchsorted(ts_row, wends - range_ms + 1, side="left")
    hi = np.searchsorted(ts_row, wends, side="right") - 1
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


def ref_increase(ts_row, corr, wends, range_ms):
    """increase(v[range]) per series and window from reset-corrected values:
    Prometheus' extrapolatedRate.  rate = increase / range seconds."""
    lo, hi, n = ref_windows(ts_row, wends, range_ms)
    out = np.full((corr.shape[0], len(wends)), np.nan)
    ok = n >= 2
    lo, hi, n, we = lo[ok], hi[ok], n[ok], wends[ok].astype(np.float64)
    v1, v2 = corr[:, lo], corr[:, hi]
    t1, t2 = ts_row[lo].astype(np.float64), ts_row[hi].astype(np.float64)
    dur_start = np.broadcast_to((t1 - (we - range_ms)) / 1000.0, v1.shape)
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
    out[:, ok] = delta * (extrap / sampled)
    return out


def ref_sum_over_time(ts_row, csum, wends, range_ms):
    """sum_over_time(v[range]) from per-row running sums `csum`."""
    lo, hi, n = ref_windows(ts_row, wends, range_ms)
    out = np.full((csum.shape[0], len(wends)), np.nan)
    ok = n >= 1
    lo, hi = lo[ok], hi[ok]
    head = np.where(lo > 0, csum[:, np.maximum(lo - 1, 0)], 0.0)
    out[:, ok] = csum[:, hi] - head
    return out


def ref_window_extreme(ts_row, rows, wends, range_ms, op):
    """max/min over each window's samples of every row of `rows` [B, T]."""
    lo, hi, n = ref_windows(ts_row, wends, range_ms)
    red = np.max if op == "max" else np.min
    out = np.full((rows.shape[0], len(wends)), np.nan)
    for w in np.flatnonzero(n >= 1):
        out[:, w] = red(rows[:, lo[w]:hi[w] + 1], axis=1)
    return out


class Reference:
    """Accumulates, chunk of series by chunk, what the panels of one cell
    need over every window end the traffic can ask for, grouped by the
    finest grouping any panel uses (`base` ids); `table()` then folds the
    base groups into one panel's `by` labels.

    `sum`/`avg` panels over increase, rate, sum_over_time, avg_over_time:
    per-series values, summed per base group (a one-hot matmul in f64).
    `max by (max_over_time)` and `min by (min_over_time)`: the extreme over
    a group's series and a window's samples in either order is the same
    number exactly, so the series are folded first ([B, T]) and the windows
    after.  Any other pairing is refused, not approximated."""

    def __init__(self, ts_row, wends, range_ms, panels, num_base):
        self.ts_row, self.wends, self.range_ms = ts_row, wends, range_ms
        self.num_base = num_base
        self.n_w = ref_windows(ts_row, wends, range_ms)[2]
        need = set()
        for p in panels:
            if p["fn"] in PER_SERIES_SUM and p["agg"] in ("sum", "avg"):
                need.add("increase" if p["fn"] in ("increase", "rate")
                         else "sum_over_time")
            elif EXTREMA.get(p["fn"]) == p["agg"]:
                need.add(p["agg"])
            else:
                raise ValueError(f"no reference for {p['agg']} over "
                                 f"{p['fn']}")
        self.need = need
        W, T = len(wends), len(ts_row)
        self.sums = {k: np.zeros((num_base, W)) for k in need
                     if k in ("increase", "sum_over_time")}
        self.rows = {"max": np.full((num_base, T), -np.inf),
                     "min": np.full((num_base, T), np.inf)}
        self.count = np.zeros(num_base)

    def add(self, vals, base_ids):
        """vals [n, T] f64 raw samples; base_ids [n] in 0..num_base-1."""
        onehot = (np.arange(self.num_base)[:, None]
                  == base_ids[None, :]).astype(np.float64)
        self.count += onehot.sum(axis=1)
        if "increase" in self.need:
            corr = correct_counters(vals, np.empty_like(vals))
            self.sums["increase"] += onehot @ ref_increase(
                self.ts_row, corr, self.wends, self.range_ms)
        if "sum_over_time" in self.need:
            csum = np.cumsum(vals, axis=1)
            self.sums["sum_over_time"] += onehot @ ref_sum_over_time(
                self.ts_row, csum, self.wends, self.range_ms)
        for op in ("max", "min"):
            if op in self.need:
                red = np.maximum if op == "max" else np.minimum
                for b in np.unique(base_ids):
                    m = vals[base_ids == b]
                    self.rows[op][b] = red(self.rows[op][b], (
                        m.max(axis=0) if op == "max" else m.min(axis=0)))

    def table(self, panel, fold):
        """[G, W] f64 answers of one panel; `fold` [B] maps each base group
        to the panel's group (0..G-1).  Absent windows are NaN."""
        G = int(fold.max()) + 1
        fn, agg = panel["fn"], panel["agg"]
        if agg in ("max", "min"):
            per_base = ref_window_extreme(self.ts_row, self.rows[agg],
                                          self.wends, self.range_ms, agg)
            red = np.fmax if agg == "max" else np.fmin
            out = np.full((G, len(self.wends)), np.nan)
            for b in range(self.num_base):
                out[fold[b]] = red(out[fold[b]], per_base[b])
            return out
        src = self.sums["increase" if fn in ("increase", "rate")
                        else "sum_over_time"]
        scale = np.ones(len(self.wends))
        if fn == "rate":
            scale = scale / (self.range_ms / 1000.0)
        elif fn == "avg_over_time":
            with np.errstate(divide="ignore"):
                scale = scale / self.n_w
        min_n = 2 if fn in ("increase", "rate") else 1
        out = np.zeros((G, len(self.wends)))
        cnt = np.zeros(G)
        for b in range(self.num_base):
            out[fold[b]] += src[b]
            cnt[fold[b]] += self.count[b]
        out = out * scale
        if agg == "avg":
            out = out / cnt[:, None]
        out[:, self.n_w < min_n] = np.nan
        return out
