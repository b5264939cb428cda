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


# ------------------------------------- the CPU readers and the rest (PR 40)

span_cpu_ms = load("readers", "span_cpu_ms")
span_sum = load("readers", "span_sum")
ROOT = os.path.dirname(BENCH)
with open(os.path.join(ROOT, "BENCHMARK.json")) as _f:
    BENCHMARK = json.load(_f)
FIVE = [w["name"] for w in BENCHMARK["workloads"]]
# metric -> (reader, the cells that list it)
NEW = {
    "request_cpu_ms": ("span_cpu_ms", FIVE),
    "conn_before_door_ms": ("span_sum", FIVE),
    "conn_close_ms": ("span_self_ms", FIVE),
    "door_unseen_share": ("span_sum", FIVE),
    "gc_full_pass_s": ("counter_delta", FIVE),
    "idle_under_collector_share": ("idle_by_span", FIVE),
}
SPAN_KEYS = ("spans", "self_of", "whole_of", "less")


def layer_metric(name):
    with open(os.path.join(BENCH, "layer_metrics", name + ".json")) as f:
        return json.load(f)


def cpu_snapshot(rows):
    """{family: value} from {span: (self wall, wall, cpu or None, calls)}:
    a span under a root has no CPU family."""
    out = {}
    for name, (self_s, dur, cpu, calls) in rows.items():
        out[fam(name, "_self_seconds_total")] = self_s
        out[fam(name, "_seconds_total")] = dur
        out[fam(name, "_calls_total")] = calls
        if cpu is not None:
            out[fam(name, "_cpu_seconds_total")] = cpu
    return out


CPU_BEFORE = cpu_snapshot({
    "conn.serve": (1.0, 9.0, 2.0, 100), "conn.accept": (0.5, 0.5, 0.05, 100),
    "flush.pass": (0.0, 0.0, 0.0, 0),
    "exec.ReduceAggregateExec": (3.0, 4.0, None, 100),
    "leaf.index_lookup": (0.4, 0.4, None, 400)})
CPU_AFTER = cpu_snapshot({
    "conn.serve": (1.3, 11.0, 2.6, 110),            # cpu +0.6 s
    "conn.accept": (0.6, 0.6, 0.06, 110),           # cpu +0.01 s
    "flush.pass": (0.2, 0.7, 0.3, 1),               # cpu +0.3 s
    "exec.ReduceAggregateExec": (3.9, 5.0, None, 110),
    "leaf.index_lookup": (0.8, 0.8, None, 440)})
CPU_CTX = {"counters": {"window": (CPU_BEFORE, CPU_AFTER)},
           "results": RESULTS}


def test_span_cpu_ms_takes_the_whole_cpu_of_the_roots():
    # conn.serve +0.6 s and conn.accept +0.01 s over ten requests
    assert span_cpu_ms.read(CPU_CTX, whole_of=["conn.serve", "conn.accept"]) \
        == pytest.approx(61.0, rel=1e-9)
    assert span_cpu_ms.read(CPU_CTX, whole_of=["conn.serve"]) == \
        pytest.approx(60.0, rel=1e-9)


def test_span_cpu_ms_on_a_program_without_the_cpu_families():
    """The parent commit books the wall families alone; so does this one
    for a span that was never a thread's outermost."""
    ctx = {"counters": {"window": (BEFORE, AFTER)}, "results": RESULTS}
    assert span_cpu_ms.read(ctx, whole_of=["conn.serve", "conn.accept"],
                            table=True) is None
    assert span_cpu_ms.read(CPU_CTX, whole_of=["leaf.index_lookup"]) is None
    assert span_cpu_ms.read(dict(CPU_CTX, results=[]),
                            whole_of=["conn.serve"]) is None


def test_the_span_table_goes_to_stderr_once(capsys):
    got = span_cpu_ms.read(CPU_CTX, whole_of=["conn.serve", "conn.accept"],
                           table=True)
    assert got == pytest.approx(61.0)
    out, err = capsys.readouterr()
    assert out == ""
    lines = err.splitlines()
    assert lines[0].startswith("span table:") and "10 requests" in lines[0]
    rows = {ln.split()[0]: ln.split()[1:] for ln in lines[2:-2]}
    assert len(rows) == 5
    # calls, self wall, whole wall and (a root's) CPU ms a request, the
    # roots first by CPU (names as /metrics flattens them)
    assert [ln.split()[0] for ln in lines[2:5]] == \
        ["conn_serve", "flush_pass", "conn_accept"]
    assert rows["conn_serve"] == ["1.000", "30.0000", "200.0000", "60.0000"]
    assert rows["leaf_index_lookup"] == ["4.000", "40.0000", "40.0000", "-"]
    assert lines[-2].split() == ["sum", "190.0000", "91.0000"]
    # 0.91 s of CPU over the 9.1 s from the first send to the last answer
    sent = max(r["done"] for r in RESULTS) - min(r["send"] for r in RESULTS)
    assert lines[-1].endswith(f"= {0.91 / sent:.3f} cores")


def conn_ctx(handover=True):
    snap = {fam("conn.serve", "_seconds_total"): 5.0,
            fam("conn.serve", "_self_seconds_total"): 1.0,
            fam("conn.read_request", "_self_seconds_total"): 2.0,
            fam("conn.close", "_seconds_total"): 0.5,
            "conn_handover_seconds_total": 1.0}
    later = {fam("conn.serve", "_seconds_total"): 5.8,          # +800 ms
             fam("conn.serve", "_self_seconds_total"): 1.02,    # +20 ms
             fam("conn.read_request", "_self_seconds_total"): 2.03,  # +30
             fam("conn.close", "_seconds_total"): 0.6,          # +100 ms
             "conn_handover_seconds_total": 1.05}               # +50 ms
    if not handover:
        del later["conn_handover_seconds_total"]
    return {"counters": {"window": (snap, later)}, "results": RESULTS}


UNSEEN = dict(whole_of=["conn.serve"], less=["conn.close"],
              counters=["conn_handover_seconds_total"],
              share_of_latency="unseen")
BEFORE_DOOR = dict(self_of=["conn.serve", "conn.read_request"],
                   counters=["conn_handover_seconds_total"])


def test_span_sum_worked_by_hand():
    ctx = conn_ctx()
    # (50 + 800 - 100) ms over 10 requests of 100 ms: 75 seen, 25% unseen
    assert span_sum.read(ctx, **UNSEEN) == pytest.approx(25.0, rel=1e-9)
    assert span_sum.read(ctx, whole_of=["conn.serve"],
                         share_of_latency="unseen") == \
        pytest.approx(20.0, rel=1e-9)
    # (50 + 20 + 30) ms over 10 requests
    assert span_sum.read(ctx, **BEFORE_DOOR) == pytest.approx(10.0, rel=1e-9)


@pytest.mark.parametrize("args", [UNSEEN, BEFORE_DOOR])
def test_span_sum_on_a_program_without_a_family(args):
    """The parent has no such span; a tree without the hand-over's counter
    reads nothing either (the stages would not be one after the other)."""
    old = {"counters": {"window": (BEFORE, AFTER)}, "results": RESULTS}
    assert span_sum.read(old, **args) is None
    assert span_sum.read(conn_ctx(handover=False), **args) is None
    assert span_sum.read(dict(conn_ctx(), results=[]), **args) is None


def test_the_door_metrics_close():
    """door_outside_share = door_unseen_share + conn_before_door_ms's
    share of the latency, by the files' own lists."""
    ctx = conn_ctx()
    snap, later = ctx["counters"]["window"]
    # http.request is what conn.serve holds beside its self time, the
    # request's read and the close
    snap[fam("http.request", "_seconds_total")] = 0.0
    later[fam("http.request", "_seconds_total")] = 0.8 - 0.02 - 0.03 - 0.1
    outside = span_self_ms.read(
        ctx, **layer_metric("door_outside_share")["args"])
    unseen = span_sum.read(ctx, **layer_metric("door_unseen_share")["args"])
    before_ms = span_sum.read(
        ctx, **layer_metric("conn_before_door_ms")["args"])
    mean_ms = 100.0
    assert outside == pytest.approx(unseen + 100.0 * before_ms / mean_ms,
                                    rel=1e-9)


def opened_spans():
    """Every span name the program's code opens: string literals passed
    to `span(` / `span_part(` / `enter_annotation(`, and the
    `exec.<PlanClass>` pattern."""
    import re
    names = set()
    pkg = os.path.join(ROOT, "filodb_tpu")
    for dirpath, _, files in os.walk(pkg):
        for fn in files:
            if fn.endswith(".py"):
                with open(os.path.join(dirpath, fn)) as f:
                    names |= set(re.findall(
                        r'\b(?:span|span_part|enter_annotation)'
                        r'\(\s*f?"([^"{]+)', f.read()))
    return names


def counters_booked():
    """Every counter name the program's code books by a string literal."""
    import re
    names = set()
    for dirpath, _, files in os.walk(os.path.join(ROOT, "filodb_tpu")):
        for fn in files:
            if fn.endswith(".py"):
                with open(os.path.join(dirpath, fn)) as f:
                    names |= set(re.findall(
                        r'registry\.counter\(\s*"([^"]+)"', f.read()))
    return names


@pytest.mark.parametrize("name", sorted(NEW))
def test_a_new_layer_metric_is_listed_and_names_spans_that_exist(name):
    reader, cells = NEW[name]
    spec = layer_metric(name)
    entry = next(m for m in BENCHMARK["per_layer"] if m["name"] == name)
    assert spec["name"] == name and spec["reader"] == reader
    assert os.path.exists(os.path.join(BENCH, "readers", reader + ".py"))
    assert (entry["unit"], entry["layer"], entry["moves"]) == \
        (spec["unit"], spec["layer"], spec["moves"])
    assert entry["workloads"] == cells
    assert entry["layer"] in {m["layer"] for m in BENCHMARK["per_layer"]
                              if m["name"] not in NEW}
    args = spec["args"]
    named = [s for key in SPAN_KEYS for s in args.get(key, ())]
    named += [args["duration_of"]] if "duration_of" in args else []
    plain = [c for c in args.get("counters", ()) if not c.startswith("span_")]
    named += [c[len("span_"):-len("_seconds_total")]
              for c in args.get("counters", ()) if c.startswith("span_")]
    # (a counter's name is the span's with its dots flattened)
    opened = {s.replace(".", "_") for s in opened_spans()}
    for s in named:
        assert s.replace(".", "_") in opened, s
    for c in plain:
        assert c[:-len("_total")] in counters_booked(), c
    assert named, "every new metric reads some span"


def test_the_new_spans_are_read_by_a_metric():
    """Every span this PR opens is named by some new metric's file, and
    each of its counters too."""
    read, counters = set(), set()
    for name in NEW:
        args = layer_metric(name)["args"]
        for key in SPAN_KEYS:
            read |= set(args.get(key, ()))
        read |= {args.get("duration_of")}
        counters |= set(args.get("counters", ()))
    assert {"conn.accept", "conn.serve", "conn.read_request", "conn.close",
            "gc.full_pass"} <= read
    assert {"gc_full_pass_seconds_total", "conn_handover_seconds_total",
            "span_flush_heap_settle_seconds_total"} <= counters


def test_every_metric_of_the_benchmark_has_its_file_and_its_reader():
    listed = {m["name"] for m in BENCHMARK["per_layer"]}
    files = {fn[:-len(".json")]
             for fn in os.listdir(os.path.join(BENCH, "layer_metrics"))}
    assert listed <= files
    for name in files:
        assert os.path.exists(os.path.join(
            BENCH, "readers", layer_metric(name)["reader"] + ".py")), name
