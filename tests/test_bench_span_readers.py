"""The benchmark's two span readers (ISSUE 27), held to cases worked by hand
and to a small recorded trace.  No JAX: the readers see plain planes and
counter snapshots."""
import importlib.util
import json
import os

import pytest

BENCH = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "benchmark")


def load(kind, name):
    spec = importlib.util.spec_from_file_location(
        "bench_" + name, os.path.join(BENCH, kind, name + ".py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


tracelib = load("", "trace")
span_self_ms = load("readers", "span_self_ms")
idle_by_span = load("readers", "idle_by_span")


# ------------------------------------------------------------ span_self_ms

def fam(name, what):
    return "span_" + name.replace(".", "_") + what


def snapshot(rows):
    """{family: value} from {span: (self seconds, calls)}."""
    out = {"leaf_fused_kernel_total": 99.0}
    for name, (secs, calls) in rows.items():
        out[fam(name, "_self_seconds_total")] = secs
        out[fam(name, "_calls_total")] = calls
    return out


def with_duration(snap, name, secs):
    return dict(snap, **{fam(name, "_seconds_total"): secs})


BEFORE = snapshot({"leaf.index_lookup": (1.0, 100), "leaf.present": (0.5, 100),
                   "http.request": (0.2, 25),
                   "exec.ReduceAggregateExec": (0.1, 25)})
AFTER = snapshot({"leaf.index_lookup": (1.4, 140),    # +0.4 s, +40 calls
                  "leaf.present": (0.6, 140),         # +0.1 s
                  "leaf.pad_values": (0.2, 4),        # new in the window
                  "http.request": (0.25, 35),         # +0.05 s
                  "exec.ReduceAggregateExec": (0.13, 35),        # +0.03 s
                  "exec.MultiSchemaPartitionsExec": (0.02, 40),  # new
                  "flush.pass": (7.0, 1)})            # background
# ten requests answered, 100 ms each on the client's clock
RESULTS = [{"send": 10.0 + i, "done": 10.1 + i} for i in range(10)]
CTX = {"counters": {"window": (BEFORE, AFTER)}, "results": RESULTS}


@pytest.mark.parametrize("args, want", [
    # exact names, per request: (0.4 + 0.1) s over 10 requests
    ({"spans": ["leaf.index_lookup", "leaf.present"]}, 50.0),
    # per call: over the +40 calls of the first listed name
    ({"spans": ["leaf.index_lookup", "leaf.present"], "per": "call"}, 12.5),
    # a prefix takes the family that appeared inside the window too, and
    # leaves the background span out: (0.4 + 0.1 + 0.2) s
    ({"spans": ["leaf."]}, 70.0),
    # a prefix first, an exact name second: that one's calls divide
    ({"spans": ["leaf.", "leaf.index_lookup"], "per": "call"}, 17.5),
    # exclude takes the leaf's own exec node back out of `exec.`
    ({"spans": ["exec."], "exclude": ["exec.MultiSchemaPartitionsExec"]},
     3.0),
    ({"spans": ["exec."]}, 5.0),
    # the share of a request no listed span saw: 1 - (5 + 70 + 5) / 100
    ({"spans": ["http.", "leaf.", "exec."],
      "share_of_latency": "outside"}, 20.0),
    # one span's whole duration, as the program books it: +0.9 s over 10
    ({"duration_of": "http.request"}, 90.0),
    # ... and the share of the client's 100 ms that the root never saw
    ({"duration_of": "http.request", "share_of_latency": "outside"}, 10.0),
], ids=["per_request", "per_call", "prefix", "prefix_then_exact", "exclude",
        "prefix_exec", "outside_share", "duration_of",
        "outside_of_the_root"])
def test_span_self_ms_worked_by_hand(args, want):
    ctx = dict(CTX, counters={"window": (
        with_duration(BEFORE, "http.request", 3.0),
        with_duration(AFTER, "http.request", 3.9))})
    assert span_self_ms.read(ctx, **args) == pytest.approx(want, rel=1e-12)


def test_span_self_ms_on_a_program_without_the_spans():
    """The parent commit books no such family: nothing to read, no error."""
    old = {"leaf_fused_kernel_total": 5.0}
    ctx = {"counters": {"window": (old, old)}, "results": RESULTS}
    assert span_self_ms.read(ctx, spans=["leaf."]) is None
    assert span_self_ms.read(ctx, spans=["leaf.index_lookup"],
                             per="call") is None
    assert span_self_ms.read(ctx, duration_of="http.request",
                             share_of_latency="outside") is None
    # spans there, but no request answered / no call made
    ctx = {"counters": {"window": (BEFORE, AFTER)}, "results": []}
    assert span_self_ms.read(ctx, spans=["leaf."]) is None
    ctx = {"counters": {"window": (AFTER, AFTER)}, "results": RESULTS}
    assert span_self_ms.read(ctx, spans=["leaf.index_lookup"],
                             per="call") is None


def test_prefix_does_not_swallow_a_longer_name():
    fams = {fam("execplan", "_self_seconds_total"): 1.0,
            fam("exec.Foo", "_self_seconds_total"): 1.0,
            fam("exec.Foo", "_calls_total"): 1.0}
    assert span_self_ms.chosen(fams, ["exec."]) == \
        [fam("exec.Foo", "_self_seconds_total")]
    assert span_self_ms.chosen(fams, ["execplan"]) == \
        [fam("execplan", "_self_seconds_total")]


# ------------------------------------------------------------ idle_by_span

def F(name, start, dur):
    return ["filodb:" + name, start, dur]


# the device is busy [0,10) [30,40) [90,100): idle [10,30) and [40,90), 70 ns
DEVICE = {"name": "/device:TPU:0", "lines": [
    {"name": "XLA Ops", "events": [["a", 0, 10], ["b", 30, 10],
                                   ["c", 90, 10]]},
    {"name": "XLA Modules", "events": [["jit__run(1)", 0, 10]]}]}
# thread A: one request [5,60) with a nested prepare [12,22) holding a
# lookup [14,18), and a fetch [42,50); thread B: a request [45,85) with a
# fetch [48,60).  An event of the runtime's own lies over everything.
HOST = {"name": "/host:CPU", "lines": [
    {"name": "A", "events": [
        ["PjitFunction(f)", 0, 100], F("http.request", 5, 55),
        F("leaf.prepare", 12, 10), F("leaf.index_lookup", 14, 4),
        F("leaf.result_fetch", 42, 8)]},
    {"name": "B", "events": [
        F("leaf.result_fetch", 48, 12), F("http.request", 45, 40)]}]}
TRACED = {"tracelib": tracelib, "trace": [DEVICE, HOST]}


def test_innermost_on_one_thread_line():
    assert idle_by_span.innermost(HOST["lines"][0]["events"][1:]) == [
        (5, 12, "filodb:http.request"), (12, 14, "filodb:leaf.prepare"),
        (14, 18, "filodb:leaf.index_lookup"), (18, 22, "filodb:leaf.prepare"),
        (22, 42, "filodb:http.request"), (42, 50, "filodb:leaf.result_fetch"),
        (50, 60, "filodb:http.request")]


@pytest.mark.parametrize("args, idle_ns", [
    # two threads' fetches overlap one gap: [42,50) u [48,60) = 18 of [40,90)
    ({"spans": ["leaf.result_fetch"]}, 18),
    # nested: prepare is innermost only around its child, [12,14) + [18,22)
    ({"spans": ["leaf.prepare"]}, 6),
    # the prefix takes parent and child, the exclusion the fetches: [12,22)
    ({"spans": ["leaf."], "exclude": ["leaf.result_fetch"]}, 10),
    # the requests' own time: [10,12) [22,30) [40,42) [45,48) [50,85)
    ({"spans": ["http."]}, 50),
    # a gap with nothing open: [85,90), after the last request closed
    ({"none_open": True}, 5),
    ({"spans": ["flush."]}, 0),
], ids=["two_threads_one_gap", "innermost_only", "prefix_and_exclude",
        "outside_leaves", "nothing_open", "not_in_the_trace"])
def test_idle_by_span_worked_by_hand(args, idle_ns):
    assert idle_by_span.read(TRACED, **args) == \
        pytest.approx(100.0 * idle_ns / 70, rel=1e-12)


@pytest.mark.parametrize("args, idle_ns", [
    # the scheduler's thread holds flush.pass [80,100) over the gap that
    # no request covers: open, unless the background is told not to count
    ({"none_open": True}, 0),
    ({"none_open": True,
      "exclude": ["flush.", "flush", "mirror.", "mirror_bg_rebuild"]}, 5),
    # innermost on that thread while the device idles: flush [82,88)
    ({"spans": ["flush"]}, 6),
    ({"spans": ["flush."]}, 4),
], ids=["background_counts_as_open", "background_excluded",
        "background_innermost", "background_parent"])
def test_idle_by_span_with_a_background_thread(args, idle_ns):
    bg = {"name": "/host:CPU", "lines": HOST["lines"] + [
        {"name": "C", "events": [F("flush.pass", 80, 20),
                                 F("flush", 82, 6)]}]}
    traced = {"tracelib": tracelib, "trace": [DEVICE, bg]}
    assert idle_by_span.read(traced, **args) == \
        pytest.approx(100.0 * idle_ns / 70, rel=1e-12)


def test_idle_by_span_with_nothing_to_read():
    # a rehearsal has no device plane; the parent commit no annotation
    assert idle_by_span.read({"tracelib": tracelib, "trace": [HOST]},
                             spans=["leaf."]) is None
    bare = {"name": "/host:CPU", "lines": [
        {"name": "A", "events": [["PjitFunction(f)", 0, 100]]}]}
    assert idle_by_span.read({"tracelib": tracelib, "trace": [DEVICE, bare]},
                             none_open=True) is None
    assert idle_by_span.read({"tracelib": tracelib, "trace": []},
                             none_open=True) is None


# ------------------------------------------------- the small recorded trace

def brute_idle_ns(planes, take):
    """Idle nanoseconds during which `take(innermost names)` holds, counted
    stretch by stretch between neighbouring event edges: no code shared
    with the reader's sweep."""
    dev = tracelib.device_planes(planes)[0]
    ops = tracelib.line_events(dev, tracelib.OPS_LINE)
    lo, hi = tracelib.span_ns(planes)
    lines = [[ev for ev in line["events"] if ev[0].startswith("filodb:")]
             for p in planes if p is not dev for line in p["lines"]]
    edges = sorted({min(max(x, lo), hi) for evs in lines + [ops]
                    for _, s, d in evs for x in (s, s + d)} | {lo, hi})
    ops = sorted((s, s + d) for _, s, d in ops)
    total = held = k = 0
    for a, b in zip(edges, edges[1:]):
        while k < len(ops) and ops[k][1] <= a:
            k += 1
        if any(s <= a and b <= e for s, e in ops[k:k + 8]):
            continue
        total += b - a
        inner = []
        for evs in lines:
            open_ = [ev for ev in evs if ev[1] <= a and b <= ev[1] + ev[2]]
            if open_:
                inner.append(max(open_, key=lambda ev: (ev[1], -ev[2]))[0])
        if take(inner):
            held += b - a
    return held, total


def test_idle_by_span_on_the_recorded_trace():
    planes = tracelib.load(os.path.join(
        BENCH, "testdata", "trace_hostspans_small.json.gz"))
    with open(os.path.join(BENCH, "testdata",
                           "trace_hostspans_small.expected.json")) as f:
        want = json.load(f)
    ctx = {"tracelib": tracelib, "trace": planes}
    for name, case in want["readings"].items():
        got = idle_by_span.read(ctx, **case["args"])
        assert got == pytest.approx(case["value"], rel=1e-9), name
    fetch, idle = brute_idle_ns(
        planes, lambda inner: "filodb:leaf.result_fetch" in inner)
    assert idle == pytest.approx(want["idle_ns"])
    assert idle_by_span.read(ctx, spans=["leaf.result_fetch"]) == \
        pytest.approx(100.0 * fetch / idle, rel=1e-9)
    nothing, _ = brute_idle_ns(planes, lambda inner: not inner)
    assert idle_by_span.read(ctx, none_open=True) == \
        pytest.approx(100.0 * nothing / idle, rel=1e-9)
    # the spans are on the device's clock: every launch of the fused program
    # in the slice begins inside or after an enqueue and ends before the
    # end of some fetch that was open or still to come
    runs = tracelib.matching(planes, tracelib.MODULES_LINE, "^jit__run")
    host = [ev for p in planes if p["name"].startswith("/host")
            for line in p["lines"] for ev in line["events"]]
    enq = [ev for ev in host if ev[0] == "filodb:leaf.kernel_enqueue"]
    fet = [ev for ev in host if ev[0] == "filodb:leaf.result_fetch"]
    assert runs and enq and fet
    inside = [r for r in runs
              if any(e[1] <= r[1] for e in enq)
              and any(f[1] + f[2] >= r[1] + r[2] for f in fet)]
    assert len(inside) >= want["launches_between_enqueue_and_fetch"]
