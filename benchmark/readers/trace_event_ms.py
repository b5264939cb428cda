"""Mean device duration (ms) of the trace events on `line` whose name
matches `pattern`: one event per dispatch of that program."""


def read(ctx, line, pattern):
    evs = ctx["tracelib"].matching(ctx["trace"], line, pattern)
    if not evs:
        return None
    return sum(ev[2] for ev in evs) / len(evs) / 1e6
