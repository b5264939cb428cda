"""The tiled-band fused program's share (%) of its roofline, a LAUNCH: the
least time the chip could take for what one launch needs
(`costs_band.band_launch` over the peaks of `peaks.json`), over the mean
device time of the matching events.  As `roofline_ragged.py`: divided by
launches (`launches`: the `/metrics` families that count jit calls of the
program), not by leaves, so it reads no N-th where a request's leaves ride
one launch; an event is all of a request's working sets, its rows every
series the request selects, its groups those of the request's answer.  Where
the launches are not a whole number a request, or no launch of the window
built a tile of band (`tiled`: the families that count those; a program
without the form, an older commit, has none), nothing is read.  The log line
says which bound holds.
"""
import importlib.util
import os
import sys


def _costs_band():
    path = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                        os.pardir, "costs_band.py")
    spec = importlib.util.spec_from_file_location("benchmark_costs_band", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def needed(cfg, plan):
    """What one launch needs: a request's rows, a panel's mean groups."""
    tables = plan.tables()
    groups = sum(len(g) for _, _, g in tables) / len(tables)
    return _costs_band().band_launch(
        series=plan.selected_series(), span_s=plan.span_s,
        range_s=plan.range_s, step_s=plan.step_s,
        scrape_ms=cfg["scrape_ms"], groups=groups)


def read(ctx, line, pattern, launches, tiled):
    evs = ctx["tracelib"].matching(ctx["trace"], line, pattern)
    before, after = ctx["counters"]["window"]
    made = sum(after.get(c, 0.0) - before.get(c, 0.0) for c in launches)
    tiles = sum(after.get(c, 0.0) - before.get(c, 0.0) for c in tiled)
    if not evs or not made or not tiles or not ctx["results"] \
            or ctx["peak"] is None:
        return None
    a_request = made / len(ctx["results"])
    if abs(a_request - round(a_request)) > 0.02:
        print(f"roofline of {pattern} (tiled band): {made:.0f} launches for "
              f"{len(ctx['results'])} requests, rows a launch unknown",
              file=sys.stderr)
        return None
    need = needed(ctx["cfg"], ctx["plan"])
    need = {k: v / round(a_request) for k, v in need.items()}
    least, bound = ctx["costs"].least_seconds(need, ctx["peak"])
    mean = sum(ev[2] for ev in evs) / len(evs) / 1e9
    print(f"roofline of {pattern} (tiled band, a launch): bound by "
          f"{bound}, needs {need}, least {least * 1e3:.4f} ms, measured "
          f"{mean * 1e3:.4f} ms", file=sys.stderr)
    return 100.0 * least / mean
