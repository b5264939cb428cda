"""A kernel's share (%) of its roofline: the least time the chip could take
for what one dispatch needs (`costs.<cost>` from the cell's shapes, over the
peaks of `peaks.json`), over the mean device time of the matching events."""
import sys


def read(ctx, line, pattern, cost):
    evs = ctx["tracelib"].matching(ctx["trace"], line, pattern)
    if not evs or ctx["peak"] is None:
        return None
    cfg, plan = ctx["cfg"], ctx["plan"]
    groups = sum(len(plan.fold(p)[1]) for p in plan.panels) / len(plan.panels)
    need = getattr(ctx["costs"], cost)(
        series=sum(ctx["per_shard"]) / len(ctx["per_shard"]),
        span_s=plan.span_s, range_s=plan.range_s, step_s=plan.step_s,
        scrape_ms=cfg["scrape_ms"], groups=groups)
    least, bound = ctx["costs"].least_seconds(need, ctx["peak"])
    mean = sum(ev[2] for ev in evs) / len(evs) / 1e9
    print(f"roofline of {pattern}: bound by {bound}, needs {need}, least "
          f"{least * 1e3:.4f} ms, measured {mean * 1e3:.4f} ms",
          file=sys.stderr)
    return 100.0 * least / mean
