"""The plain f64 reference of `tsbscpu-gauges-40k`: TSBS devops
`double-groupby` as PromQL, `avg by (hostname)(avg_over_time(metric[1h]))`.

NumPy only: it imports nothing of the program and takes nothing the program
has made.  Every series lies on one shared timestamp row without holes.  For
each series, `avg_over_time` over a window (wend - range, wend] is the sum
of its samples there (a difference of the row's f64 running sum) over their
count; `avg by (hostname)` is the mean of those over the series of a host
and metric that hold the window (one series here, and written for any).  It
keeps one [hosts, W] table a metric that some panel asks, over every window
end the traffic can ask: what the traffic asks, not the ten metrics.
"""
import numpy as np

FNS = {"avg_over_time": "avg", "sum_over_time": "sum"}


def windows(ts_row, wends, range_ms):
    """First/last sample index and count of each window (wend-range, wend]."""
    lo = np.searchsorted(ts_row, wends - range_ms + 1, side="left")
    hi = np.searchsorted(ts_row, wends, side="right") - 1
    return lo, hi, hi - lo + 1


class Reference:
    def __init__(self, ts_row, wends_ms, range_ms, panels, hosts):
        for p in panels:
            if FNS.get(p["fn"]) != p["agg"] or p["by"] != ["hostname"]:
                raise ValueError(f"no reference for {p}")
        self.lo, self.hi, self.n = windows(ts_row, wends_ms, range_ms)
        self.hosts = hosts
        self.sums = {p["metric"]: np.zeros((hosts, len(wends_ms)))
                     for p in panels}
        self.count = {m: np.zeros(hosts) for m in self.sums}

    def asks(self, metric):
        return metric in self.sums

    def add(self, metric, vals, host_ids):
        """vals [n, T] f64 raw samples of `metric`; host_ids [n]."""
        csum = np.cumsum(vals, axis=1)
        ok = self.n >= 1
        lo, hi = self.lo[ok], self.hi[ok]
        head = np.where(lo > 0, csum[:, np.maximum(lo - 1, 0)], 0.0)
        per = np.zeros((len(vals), len(self.n)))
        per[:, ok] = csum[:, hi] - head
        np.add.at(self.sums[metric], host_ids, per)
        np.add.at(self.count[metric], host_ids, 1.0)

    def table(self, panel, fold):
        """[G, W] f64 answers of one panel; `fold` [hosts] maps a host to the
        panel's group, or to -1 where it is left out.  Absent windows are
        NaN.  One group a host: the fold is a selection, never a sum."""
        kept = np.flatnonzero(fold >= 0)
        if len(np.unique(fold[kept])) != len(kept):
            raise ValueError("a group of more than one host")
        out = np.full((len(kept), len(self.n)), np.nan)
        sums, cnt = self.sums[panel["metric"]], self.count[panel["metric"]]
        with np.errstate(divide="ignore", invalid="ignore"):
            # sum_over_time: summed over a host's series; avg_over_time: a
            # series' mean over its samples, then the mean over the series
            per = sums[kept] if panel["fn"] == "sum_over_time" \
                else sums[kept] / self.n / cnt[kept, None]
        out[fold[kept]] = per
        out[:, self.n < 1] = np.nan
        return out
