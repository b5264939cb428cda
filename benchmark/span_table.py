#!/usr/bin/env python3
"""The program's span tree, as a traced run's host plane holds it.

    python3 benchmark/span_table.py <trace.json.gz | *.xplane.pb>

(`run.py --trace 1 --keep-trace <path>` saves the plain form.)  Prints one
JSON object: for each `filodb:<name>` annotation its count, mean duration
and mean SELF time (duration less its children's, by containment on its
thread's line) per request, and that self time's share of the mean
`http.request`; which parent spans break the closure rule (mean self time
over 10% of the request); the innermost span open on any thread during
the device's idle time, by share; and a few enqueue -> launch -> fetch
triples, which show that host lines and device lines share one clock.
"""
import importlib.util
import json
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = "filodb:http.request"


def load(kind, name):
    spec = importlib.util.spec_from_file_location(
        "benchmark_" + name, os.path.join(HERE, kind, name + ".py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def table(planes):
    lib, idle_by = load("", "trace"), load("readers", "idle_by_span")
    lines = idle_by.host_lines(lib, planes)
    count, dur, self_ns, parents = {}, {}, {}, set()
    for evs in lines:
        for name, _, d in evs:
            count[name] = count.get(name, 0) + 1
            dur[name] = dur.get(name, 0) + d
        segs = idle_by.innermost(evs)
        for s, e, name in segs:
            self_ns[name] = self_ns.get(name, 0) + e - s
        # a span with anything nested inside it on its line is a parent
        ordered = sorted(evs, key=lambda ev: (ev[1], -ev[2]))
        for a, b in zip(ordered, ordered[1:]):
            if b[1] < a[1] + a[2]:
                parents.add(a[0])
    n = count.get(ROOT, 0)
    if not n:
        return {"requests": 0, "spans": []}
    req_ms = dur[ROOT] / n / 1e6
    rows = [{"span": k[len(idle_by.LABEL):], "calls": count[k],
             "mean_ms": dur[k] / count[k] / 1e6,
             "self_ms_per_request": self_ns.get(k, 0) / n / 1e6,
             "self_share_of_request":
                 100.0 * self_ns.get(k, 0) / n / 1e6 / req_ms,
             "parent": k in parents}
            for k in sorted(count, key=lambda k: -self_ns.get(k, 0))]
    out = {"requests": n, "request_mean_ms": req_ms, "spans": rows,
           "closure_rule_broken_by": [
               r["span"] for r in rows
               if r["parent"] and r["self_share_of_request"] > 10.0]}
    idle = idle_by.idle_intervals(lib, planes)
    if idle:
        idle_ns = sum(e - s for s, e in idle)
        by = {}
        for evs in lines:
            for s, e, name in idle_by.innermost(evs):
                by.setdefault(name, []).append([name, s, e - s])
        out["idle_s"] = idle_ns / 1e9
        out["idle_share_by_innermost_span"] = sorted(
            ([k[len(idle_by.LABEL):],
              100.0 * idle_by.overlap(idle, lib.union(v)) / idle_ns]
             for k, v in by.items()), key=lambda kv: -kv[1])[:12]
        runs = sorted(lib.matching(planes, lib.MODULES_LINE, "^jit__run"),
                      key=lambda ev: ev[1])
        evs = sorted((ev for line in lines for ev in line), key=lambda e: e[1])
        enq = [e for e in evs if e[0] == "filodb:leaf.kernel_enqueue"]
        fet = [e for e in evs if e[0] == "filodb:leaf.result_fetch"]
        triples = []
        for r in runs[:400:80]:
            before = [e for e in enq if e[1] <= r[1]]
            after = [e for e in fet if e[1] + e[2] >= r[1] + r[2]]
            if before and after:
                triples.append({
                    "enqueue_start_to_launch_ms": (r[1] - before[-1][1]) / 1e6,
                    "launch_ms": r[2] / 1e6,
                    "launch_end_to_next_fetch_end_ms": min(
                        e[1] + e[2] - r[1] - r[2] for e in after) / 1e6})
        out["clock_check"] = triples
    return out


if __name__ == "__main__":
    lib = load("", "trace")
    src = sys.argv[1]
    planes = lib.load_xplane(src) if src.endswith(".pb") else lib.load(src)
    json.dump(table(planes), sys.stdout, indent=1)
    print()
