"""Sum of the deltas of `/metrics` families over a phase of the run
(`setup`: server start to window start; `window`), optionally per request
answered in the window.  A family that was never booked counts 0."""


def read(ctx, counters, phase="window", per=None):
    before, after = ctx["counters"][phase]
    total = sum(after.get(c, 0.0) - before.get(c, 0.0) for c in counters)
    if per == "request":
        if not ctx["results"]:
            return None
        return total / len(ctx["results"])
    return total
