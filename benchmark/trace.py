"""From the JAX profiler's xplane file to the numbers the benchmark reports.

A trace is read into plain data: a list of planes, each {"name", "lines":
[{"name", "events": [[name, start_ns, duration_ns], ...]}]}.  Every reduction
below works on that form, so it runs the same on a trace just taken and on
the small recorded one in `testdata/` that the self-check holds it to.

Device planes are the `/device:TPU:<n>` planes.  On them the line "XLA Ops"
has one event per operation that ran on the device (its busy time), and "XLA
Modules" one event per launched program, named `<jit name>(<fingerprint>)`.
"""
import glob
import gzip
import json
import os
import re

DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"


def find_xplane(logdir):
    found = sorted(glob.glob(os.path.join(
        logdir, "plugins", "profile", "*", "*.xplane.pb")))
    return found[-1] if found else None


def load_xplane(path):
    from jax.profiler import ProfileData
    planes = []
    for plane in ProfileData.from_file(path).planes:
        lines = [{"name": line.name,
                  "events": [[ev.name, int(ev.start_ns), int(ev.duration_ns)]
                             for ev in line.events]}
                 for line in plane.lines]
        planes.append({"name": plane.name, "lines": lines})
    return planes


def save(planes, path):
    with gzip.open(path, "wt") as f:
        json.dump(planes, f)


def load(path):
    with gzip.open(path, "rt") as f:
        return json.load(f)


def device_planes(planes):
    return [p for p in planes if DEVICE_PLANE.match(p["name"])]


def line_events(plane, line_name):
    return [ev for line in plane["lines"] if line["name"] == line_name
            for ev in line["events"]]


def union(events):
    """Disjoint busy intervals [(start, end)] covered by `events`, sorted."""
    out = []
    for s, e in sorted((ev[1], ev[1] + ev[2]) for ev in events):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def clip(intervals, lo, hi):
    return [[max(s, lo), min(e, hi)] for s, e in intervals
            if min(e, hi) > max(s, lo)]


def busy_seconds(planes):
    """Seconds in which an operation ran on the device, averaged over the
    device planes."""
    per = [sum(e - s for s, e in union(line_events(plane, OPS_LINE))) / 1e9
           for plane in device_planes(planes)]
    return sum(per) / len(per) if per else 0.0


def span_ns(planes):
    """(first start, last end) of the device operations, or None."""
    evs = [ev for p in device_planes(planes) for ev in line_events(p, OPS_LINE)]
    if not evs:
        return None
    return min(ev[1] for ev in evs), max(ev[1] + ev[2] for ev in evs)


def matching(planes, line_name, pattern):
    """Device events on `line_name` whose name matches `pattern`."""
    rx = re.compile(pattern)
    return [ev for p in device_planes(planes)
            for ev in line_events(p, line_name) if rx.search(ev[0])]


def top_programs(planes, n=10):
    """[[name, seconds]] of the launched programs that took most device
    time, by the trace's name without its fingerprint: the operations inside
    one program are mostly anonymous fusions, the programs are not."""
    tot = {}
    for p in device_planes(planes):
        for name, _, dur in line_events(p, MODULES_LINE):
            name = name.split("(", 1)[0]
            tot[name] = tot.get(name, 0) + dur
    return [[k, v / 1e9] for k, v in
            sorted(tot.items(), key=lambda kv: -kv[1])[:n]]


def idle_gaps(planes, lo_ns, hi_ns, label, n=10):
    """[[label, seconds]] of the longest spans inside [lo, hi] in which no
    operation ran on the first device; `label(start_ns, end_ns)` names what
    the host side was doing."""
    dev = device_planes(planes)
    if not dev:
        return []
    iv = clip(union(line_events(dev[0], OPS_LINE)), lo_ns, hi_ns)
    edges = [lo_ns] + [x for s, e in iv for x in (s, e)] + [hi_ns]
    gaps = [(edges[i], edges[i + 1]) for i in range(0, len(edges), 2)
            if edges[i + 1] > edges[i]]
    gaps.sort(key=lambda g: g[0] - g[1])
    return [[label(s, e), (e - s) / 1e9] for s, e in gaps[:n]]


def summary(planes):
    """What a person looks at first: planes, lines, counts, common names."""
    out = []
    for p in planes:
        for line in p["lines"]:
            names = {}
            for name, _, dur in line["events"]:
                c = names.setdefault(name, [0, 0])
                c[0] += 1
                c[1] += dur
            top = sorted(names.items(), key=lambda kv: -kv[1][1])[:12]
            out.append({"plane": p["name"], "line": line["name"],
                        "events": len(line["events"]),
                        "top": [[k, c, d / 1e9] for k, (c, d) in top]})
    return out


if __name__ == "__main__":
    import sys
    src = sys.argv[1]
    planes = load_xplane(src) if src.endswith(".pb") else load(src)
    json.dump(summary(planes), sys.stdout, indent=1)
    print()
    span = span_ns(planes)
    if span:
        print("device ops span", span, "busy_s", busy_seconds(planes))
