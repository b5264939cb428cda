"""Thread CPU milliseconds a request, over the window, of the listed ROOT
spans: what a request holds of the interpreter lock, beside the wall time
that `span_self_ms` reads (most of which, with six requests in flight, is
waiting for that lock).

The program books the thread CPU time of a thread's OUTERMOST span into
`span_<name>_cpu_seconds_total` (utils/metrics.span; the spans under a root
do not read the clock, so a tree's CPU is its root's, children and all).
`whole_of` lists roots; the answer is per answered request.  `table: true`
also writes to stderr, once, every span's calls, self wall ms and whole wall
ms a request over the window, the roots' CPU ms beside them, and the cores
that all roots together kept busy over the requests' span (1.0 = one
saturated interpreter lock).  A program without the CPU families (an older
commit) gives None.
"""
import importlib.util
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
CPU = "_cpu_seconds_total"


def sibling(name):
    spec = importlib.util.spec_from_file_location(
        "benchmark_readers_" + name, os.path.join(HERE, name + ".py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


# the accepted wall reader: its names for the families
WALL = sibling("span_self_ms")


def write_table(before, after, results, out):
    """Every span of the window, the roots (heaviest CPU) first."""
    n = len(results)

    def ms(fam):
        if fam not in after:
            return None
        return 1000.0 * (after[fam] - before.get(fam, 0.0)) / n

    rows = []
    for fam in after:
        if fam.startswith("span_") and fam.endswith(WALL.CALLS):
            stem = fam[:-len(WALL.CALLS)]
            calls = after[fam] - before.get(fam, 0.0)
            if calls:
                rows.append((stem[len("span_"):], calls / n,
                             ms(stem + WALL.SELF), ms(stem + WALL.DUR),
                             ms(stem + CPU)))
    rows.sort(key=lambda r: (-(r[4] or 0.0), -(r[2] or 0.0)))

    def cell(v):
        return "-" if v is None else f"{v:.4f}"

    print(f"span table: ms a request over the window's {n} requests "
          "(cpu: of a thread's outermost span, its children's with it)",
          file=out)
    print(f"{'span':<40}{'calls/req':>10}{'self_wall_ms':>14}"
          f"{'wall_ms':>12}{'cpu_ms':>12}", file=out)
    for name, calls, self_w, w, c in rows:
        print(f"{name:<40}{calls:>10.3f}{cell(self_w):>14}{cell(w):>12}"
              f"{cell(c):>12}", file=out)
    cpu = sum(r[4] or 0.0 for r in rows)
    secs = max(r["done"] for r in results) - min(r["send"] for r in results)
    print(f"{'sum':<40}{'':>10}{sum(r[2] or 0.0 for r in rows):>14.4f}"
          f"{'':>12}{cpu:>12.4f}", file=out)
    print(f"the roots' cpu: {cpu * n / 1000.0:.3f} s over the requests' "
          f"{secs:.3f} s = {cpu * n / 1000.0 / secs:.3f} cores",
          file=out, flush=True)


def read(ctx, whole_of, table=False):
    before, after = ctx["counters"]["window"]
    results = ctx["results"]
    fams = [WALL.flat(s) + CPU for s in whole_of]
    if not results or any(f not in after for f in fams):
        return None
    if table:
        write_table(before, after, results, sys.stderr)
    return 1000.0 * sum(after[f] - before.get(f, 0.0) for f in fams) \
        / len(results)
