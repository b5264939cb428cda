"""Traffic kind `quantile_open`: `dashboard_open` over a latency dashboard.
Each panel is `histogram_quantile(q, sum [by (..)](rate(metric[range])))`:
the panel carries `q` beside `fn` (rate), `agg` (sum) and `by`.  Grids,
phases, warm-up, tables and grouping are `dashboard_open`'s; only the promql
is wrapped, so the result cache's rule holds as there (the six promqls
differ, and the opens of one phase reach one step further back each).
Panels that differ only in `q` share a leaf's work and differ above it.
"""
import importlib.util
import os


def _dashboard_open():
    path = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                        "dashboard_open.py")
    spec = importlib.util.spec_from_file_location(
        "benchmark_traffic_dashboard_open", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


class Plan(_dashboard_open().Plan):
    def __init__(self, cfg, tp, seed):
        super().__init__(cfg, tp, seed)
        self.queries = {
            k: [f"histogram_quantile({p['q']}, {inner})"
                for p, inner in zip(self.panels, inners)]
            for k, inners in self.queries.items()}
