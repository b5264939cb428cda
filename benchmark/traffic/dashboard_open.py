"""Traffic kind `dashboard_open`: one viewer keeps opening dashboards that
nothing has cached.

An open is the dashboard's panels with one shared (start, end, step).  The
result cache keys on (promql, step, start mod step) and treats a request that
reaches back before its entry's start as a plain miss
(`query/resultcache.py`), so: open n takes phase `order[n mod P]` (a whole
number of seconds off the newest sample, the order drawn from the seed) and,
within a phase, an `end` one step earlier than that phase's previous open,
starting at the newest sample.  Every seed sends the same set of requests in
another order.  The warm-up uses a phase of its own, which the window never
asks for, so it leaves nothing the window could hit.

Parameters (the `traffic` object of a workload file): `panels` (fn, agg,
by), `range_s`, `span_s`, `step_s`, `phases`, `phase_stride_s`,
`warmup_phase_s`, `warmup_opens`, `in_flight`, `scan_limit`.
"""
import math

import numpy as np


def promql(panel, metric, range_s):
    rng = f"{range_s // 60}m" if range_s % 60 == 0 else f"{range_s}s"
    sel = f"{panel['fn']}({metric}[{rng}])"
    if panel["by"]:
        return f"{panel['agg']} by ({', '.join(panel['by'])})({sel})"
    return f"{panel['agg']}({sel})"


class Plan:
    def __init__(self, cfg, tp, seed):
        self.cfg, self.tp = cfg, tp
        self.panels = tp["panels"]
        self.step_s, self.span_s = tp["step_s"], tp["span_s"]
        self.range_s = tp["range_s"]
        self.newest_s = (cfg["start_ms"] + (cfg["samples"] - 1)
                         * cfg["scrape_ms"]) // 1000
        self.phases = [i * tp["phase_stride_s"] for i in range(tp["phases"])]
        held_s = (cfg["samples"] - 1) * cfg["scrape_ms"] // 1000
        # every window of every open holds its full count of samples
        self.opens_per_phase = (held_s - self.span_s - self.range_s
                                - max(self.phases + [tp["warmup_phase_s"]])
                                ) // self.step_s + 1
        if self.opens_per_phase < 1 or tp["warmup_phase_s"] in self.phases:
            raise ValueError("traffic does not fit the configuration")
        self.order = np.random.default_rng([seed, 77]).permutation(
            len(self.phases)).tolist()
        self.queries = [promql(p, cfg["metric"], self.range_s)
                        for p in self.panels]
        self.n_windows = self.span_s // self.step_s + 1

    @property
    def capacity(self):
        """Requests the window can send before an open would repeat."""
        return len(self.phases) * self.opens_per_phase * len(self.panels)

    def window_ends_s(self):
        """Every window end (unix seconds, ascending) any request can ask."""
        ends = set()
        for phase, opens in [(p, self.opens_per_phase) for p in self.phases] \
                + [(self.tp["warmup_phase_s"], self.tp["warmup_opens"])]:
            newest = self.newest_s - phase
            for m in range(self.n_windows + opens - 1):
                ends.add(newest - m * self.step_s)
        return np.array(sorted(ends), dtype=np.int64)

    def _open(self, ident, phase, k):
        end = self.newest_s - phase - k * self.step_s
        return [{"id": f"{ident}.{j}", "panel": j,
                 "path": "/api/v1/query_range",
                 "params": {"query": q, "start": end - self.span_s,
                            "end": end, "step": self.step_s,
                            "scanLimit": self.tp["scan_limit"],
                            "stats": "true"}}
                for j, q in enumerate(self.queries)]

    def warmup(self):
        return [r for k in range(self.tp["warmup_opens"])
                for r in self._open(f"w{k}", self.tp["warmup_phase_s"], k)]

    def requests(self):
        P = len(self.phases)
        return [r for n in range(P * self.opens_per_phase)
                for r in self._open(f"o{n}", self.phases[self.order[n % P]],
                                    n // P)]

    # ---- grouping: which series fall into which group of which panel

    def _mods(self, labels):
        mods = []
        for lab in labels:
            spec = self.cfg["labels"][lab]
            if not isinstance(spec, dict) or "mod" not in spec:
                raise ValueError(f"cannot group by {lab}: one group a series")
            mods.append(spec["mod"])
        return mods

    def num_base(self):
        """Series i belongs to base group i mod this: the finest grouping
        any panel uses repeats with the least common multiple of its
        labels' periods."""
        union = sorted({lab for p in self.panels for lab in p["by"]})
        return math.lcm(1, *self._mods(union))

    def fold(self, panel):
        """(fold[B] base group -> panel group, label values of each group)."""
        mods = self._mods(panel["by"])
        keys, fold = {}, []
        for b in range(self.num_base()):
            key = tuple(self.cfg["labels"][lab]["prefix"] + str(b % m)
                        for lab, m in zip(panel["by"], mods))
            fold.append(keys.setdefault(key, len(keys)))
        return np.array(fold), [list(k) for k in keys]
