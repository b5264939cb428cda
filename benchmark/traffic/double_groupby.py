"""Traffic kind `double_groupby`: TSBS devops `double-groupby-N` over
`dashboard_open`'s requests.

An open is one TSBS query: the mean of N cpu metrics, an hour at a time, of
every host over the last twelve hours.  TSBS's VictoriaMetrics generator
writes it as one request over `__name__=~`; here it is N requests, one a
metric, `avg by (hostname)(avg_over_time(<metric>[1h]))`, sharing `start`,
`end` and `step` (a panel carries its `metric` beside `fn`, `agg`, `by`).
One group a host: a response has a row for every host.

The store holds one range and nine minutes more, so an open cannot step an
hour back as `dashboard_open`'s do: every open has a phase of its own, a
whole number of seconds off the newest sample, and there is ONE open a
phase.  Phases 0 .. P-1 belong to the window, in an order drawn from the
seed; the warm-up's opens take the phases after them, which the window never
asks.  No two phases are a whole number of steps apart, so by the result
cache's rule (promql, step, start mod step) no request of a run can hit what
another left, and every window of every request holds its full count of
samples.

Parameters (the `traffic` object of a workload file): `panels` (metric, fn,
agg, by), `range_s`, `span_s`, `step_s`, `phases`, `phase_stride_s`,
`warmup_opens`, `in_flight`, `scan_limit`.
"""
import importlib.util
import os

import numpy as np


def _dashboard_open():
    path = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                        "dashboard_open.py")
    spec = importlib.util.spec_from_file_location(
        "benchmark_traffic_dashboard_open", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def promql(panel, range_s):
    rng = f"{range_s // 3600}h" if range_s % 3600 == 0 else f"{range_s}s"
    return (f"{panel['agg']} by ({', '.join(panel['by'])})"
            f"({panel['fn']}({panel['metric']}[{rng}]))")


class Plan(_dashboard_open().Plan):
    def __init__(self, cfg, tp, seed):
        self.cfg, self.tp = cfg, tp
        self.panels = tp["panels"]
        self.step_s, self.span_s = tp["step_s"], tp["span_s"]
        self.range_s = tp["range_s"]
        self.hosts = cfg["series"] // len(cfg["metrics"])
        self.newest_s = (cfg["start_ms"] + (cfg["samples"] - 1)
                         * cfg["scrape_ms"]) // 1000
        stride = tp["phase_stride_s"]
        self.phases = [i * stride for i in range(tp["phases"])]
        self.warm_phases = [(tp["phases"] + i) * stride
                            for i in range(tp["warmup_opens"])]
        self.opens_per_phase = 1
        held_s = (cfg["samples"] - 1) * cfg["scrape_ms"] // 1000
        every = self.phases + self.warm_phases
        if max(every) + self.span_s + self.range_s > held_s \
                or len({p % self.step_s for p in every}) < len(every) \
                or any(p["by"] != ["hostname"] or p["metric"]
                       not in cfg["metrics"] for p in self.panels):
            # a window short of samples, two phases that would share cache
            # entries, or a panel that is no double-groupby of this store
            raise ValueError("traffic does not fit the configuration")
        self.order = np.random.default_rng([seed, 77]).permutation(
            len(self.phases)).tolist()
        self.n_windows = self.span_s // self.step_s + 1
        self.select, self.ks = {}, [None]
        self.queries = {None: [promql(p, self.range_s) for p in self.panels]}

    def window_ends_s(self):
        """Every window end (unix seconds, ascending) any request can ask."""
        return np.array(sorted(
            self.newest_s - phase - m * self.step_s
            for phase in self.phases + self.warm_phases
            for m in range(self.n_windows)), dtype=np.int64)

    def warmup(self):
        return [r for n, phase in enumerate(self.warm_phases)
                for r in self._open(f"w{n}", phase, 0, None)]

    def requests(self):
        return [r for n, i in enumerate(self.order)
                for r in self._open(f"o{n}", self.phases[i], 0, None)]

    # ---- grouping: series i of a metric is host i; one group a host

    def selected_series(self):
        """Series a request selects: one metric's row of every host."""
        return self.hosts

    def num_base(self):
        return self.hosts

    def fold(self, panel, k=None):
        prefix = self.cfg["labels"]["hostname"]["prefix"]
        return np.arange(self.hosts), [[prefix + str(i)]
                                       for i in range(self.hosts)]
