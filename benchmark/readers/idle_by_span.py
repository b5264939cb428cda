"""Share (%) of the first device's idle time, inside the traced window, during
which some host thread's INNERMOST open span of the program is one of the
listed ones; or, with `none_open`, during which no thread has any span open
(spans in `exclude`, the background's, do not count as open).

The program enters every span as a profiler annotation `filodb:<name>`
(utils/metrics.span), so a trace holds them on its host plane, one line a
thread, on the clock of the device's operations.  Nesting on a line is by
containment.  `spans` and `exclude` list names, an entry ending in `.` being
a prefix.  With several requests in flight the shares of different lists
overlap and do not sum to 100.  A trace without a device plane (a rehearsal)
or without annotations (an older commit) gives None.
"""
LABEL = "filodb:"


def innermost(events):
    """[(start, end, name)]: for each instant at which an event of one
    thread's line is open, the innermost open one."""
    segs, stack, at = [], [], 0

    def upto(t):
        nonlocal at
        if stack and t > at:
            segs.append((at, t, stack[-1][1]))
        at = max(at, t)

    for name, start, dur in sorted(events, key=lambda ev: (ev[1], -ev[2])):
        while stack and stack[-1][0] <= start:
            upto(stack[-1][0])
            stack.pop()
        upto(start)
        stack.append((start + dur, name))
    while stack:
        upto(stack[-1][0])
        stack.pop()
    return segs


def overlap(a, b):
    """Nanoseconds common to two sorted lists of disjoint intervals."""
    i = j = total = 0
    while i < len(a) and j < len(b):
        lo, hi = max(a[i][0], b[j][0]), min(a[i][1], b[j][1])
        if hi > lo:
            total += hi - lo
        if a[i][1] < b[j][1]:
            i += 1
        else:
            j += 1
    return total


def listed(name, entries):
    return any(name.startswith(e) if e.endswith(".") else name == e
               for e in entries)


def host_lines(lib, planes):
    """The program's annotations, one list a thread line that has any."""
    dev = lib.device_planes(planes)
    lines = [[ev for ev in line["events"] if ev[0].startswith(LABEL)]
             for p in planes if p not in dev for line in p["lines"]]
    return [evs for evs in lines if evs]


def idle_intervals(lib, planes):
    """[(start, end)] inside the traced window (first to last device
    operation) in which no operation ran on the first device."""
    dev = lib.device_planes(planes)
    window = lib.span_ns(planes)
    if not dev or window is None:
        return []
    lo, hi = window
    busy = lib.clip(lib.union(lib.line_events(dev[0], lib.OPS_LINE)), lo, hi)
    edges = [lo] + [x for iv in busy for x in iv] + [hi]
    return [(edges[i], edges[i + 1]) for i in range(0, len(edges), 2)
            if edges[i + 1] > edges[i]]


def read(ctx, spans=(), exclude=(), none_open=False):
    lib, planes = ctx["tracelib"], ctx["trace"]
    idle, lines = idle_intervals(lib, planes), host_lines(lib, planes)
    idle_ns = sum(e - s for s, e in idle)
    if not idle_ns or not lines:
        return None
    if none_open:
        held = lib.union([ev for evs in lines for ev in evs
                          if not listed(ev[0][len(LABEL):], exclude)])
        return 100.0 * (1.0 - overlap(idle, held) / idle_ns)
    held = lib.union(
        [[name, s, e - s] for evs in lines for s, e, name in innermost(evs)
         if listed(name[len(LABEL):], spans)
         and not listed(name[len(LABEL):], exclude)])
    return 100.0 * overlap(idle, held) / idle_ns
