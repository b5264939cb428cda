"""The fused histogram dispatch's share (%) of its roofline: the least time
the chip could take for what one dispatch needs (`costs_hist.hist_fused_leaf`
over the peaks of `peaks.json`), over the mean device time of the matching
events.  As `roofline.py`, with a series counted as `cfg["buckets"]` kernel
rows: one dispatch works the series a request selects over the histogram
dispatches a request made (`dispatches`), and where those are not a whole
number a request nothing is read.  A panel's groups are spread over the
leaves, each on at least one: a dispatch is counted `groups / leaves` of them
and at least one, never more than it holds.  The log line says which bound,
bytes or operations, holds.  A program without the counter (an older commit)
gives None.
"""
import importlib.util
import os
import sys


def _costs_hist():
    path = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                        os.pardir, "costs_hist.py")
    spec = importlib.util.spec_from_file_location("benchmark_costs_hist",
                                                  path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def needed(cfg, plan, leaves):
    """What one of a request's `leaves` dispatches needs, a panel's mean."""
    tables = plan.tables()
    groups = sum(max(len(g) / leaves, 1.0) for _, _, g in tables) \
        / len(tables)
    return _costs_hist().hist_fused_leaf(
        series=plan.selected_series() / leaves, buckets=cfg["buckets"],
        span_s=plan.span_s, range_s=plan.range_s, step_s=plan.step_s,
        scrape_ms=cfg["scrape_ms"], groups=groups)


def read(ctx, line, pattern, dispatches):
    evs = ctx["tracelib"].matching(ctx["trace"], line, pattern)
    before, after = ctx["counters"]["window"]
    made = sum(after.get(c, 0.0) - before.get(c, 0.0) for c in dispatches)
    if not evs or not made or not ctx["results"] or ctx["peak"] is None:
        return None
    leaves = made / len(ctx["results"])
    if abs(leaves - round(leaves)) > 0.02:
        print(f"roofline of {pattern}: {made:.0f} histogram dispatches for "
              f"{len(ctx['results'])} requests, rows a dispatch unknown",
              file=sys.stderr)
        return None
    need = needed(ctx["cfg"], ctx["plan"], round(leaves))
    least, bound = ctx["costs"].least_seconds(need, ctx["peak"])
    mean = sum(ev[2] for ev in evs) / len(evs) / 1e9
    print(f"roofline of {pattern} (histogram rows): bound by {bound}, needs "
          f"{need}, least {least * 1e3:.4f} ms, measured {mean * 1e3:.4f} ms",
          file=sys.stderr)
    return 100.0 * least / mean
