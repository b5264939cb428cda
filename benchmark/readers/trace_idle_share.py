"""Share (%) of the traced window in which no operation ran on the device:
1 - union of the busy intervals / window, averaged over the chips used."""


def read(ctx):
    if not ctx["tracelib"].device_planes(ctx["trace"]):
        return None
    busy = ctx["tracelib"].busy_seconds(ctx["trace"])
    return 100.0 * (1.0 - busy / ctx["trace_window_s"])
