"""A root span's CPU time beside its wall time, the connection's spans
outside `http.request`, and the collector's full passes by name (ISSUE 40)."""
import gc
import threading
import time
import urllib.error
import urllib.request

import pytest

from filodb_tpu.http.routes import PromHttpApi
from filodb_tpu.http.server import FiloHttpServer
from filodb_tpu.utils import heap, metrics
from filodb_tpu.utils.metrics import (current_trace_id, registry, span,
                                      span_part, trace_context)

WALL = ("_self_seconds", "_seconds", "_calls")
CPU = "_cpu_seconds"


def val(name):
    return registry.counter(name).value


def has(name):
    return any(n == name for (n, _tags) in registry._counters)


def flat(name):
    return "span_" + name.replace(".", "_")


def families(name):
    """{family suffix: value} of one span, as the registry holds them
    (the CPU family only where the span has exited as a root)."""
    out = {f: val(flat(name) + f) for f in WALL}
    if has(flat(name) + CPU):
        out[CPU] = val(flat(name) + CPU)
    return out


def moved(name, before):
    return {f: v - before.get(f, 0.0) for f, v in families(name).items()}


def spin(seconds):
    end = time.perf_counter() + seconds
    while time.perf_counter() < end:
        pass


@pytest.fixture
def session(tmp_path):
    """A profiler session on the CPU backend, as the annotation tests take
    one."""
    import jax
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 1
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    try:
        yield
    finally:
        jax.profiler.stop_trace()


# ------------------------------------------------------------ the CPU clock

def test_a_sleeping_span_is_off_the_cpu():
    with span("cpu.sleeper") as sp:
        time.sleep(0.05)
    assert sp.dur_ns >= 50e6 and sp.cpu_ns < 10e6
    assert val("span_cpu_sleeper_seconds") >= 0.05
    assert val("span_cpu_sleeper_cpu_seconds") == \
        pytest.approx(sp.cpu_ns * 1e-9, rel=1e-9)


def test_a_spinning_span_is_on_the_cpu():
    # Held to the thread's own CPU clock, read around the same spin, and
    # not to wall time: beside other test workers the thread loses its
    # core for whole slices, and that clock may tick in 10 ms.  The spin
    # lasts 0.2 s OF THAT CLOCK, however long it takes.
    spun = 200_000_000
    c0 = time.thread_time_ns()
    with span("cpu.spinner") as sp:
        end = time.thread_time_ns() + spun
        while time.thread_time_ns() < end:
            pass
    around = time.thread_time_ns() - c0
    # the span's two readings lie around the spin's and inside the test's
    assert spun <= sp.cpu_ns <= around
    # about its duration: what entering and leaving cost (and a tick) on
    # top, and never on the CPU for longer than it lasted
    assert around <= 1.2 * spun
    assert sp.cpu_ns <= 1.05 * sp.dur_ns
    assert val("span_cpu_spinner_cpu_seconds") >= spun * 1e-9


def a_tree(prefix):
    """root > 2 x mid > 3 x leaf, spinning at every level; the spans."""
    names = [prefix + s for s in ("_root", "_mid", "_leaf")]
    before = {n: families(n) for n in names}
    spans = []
    with span(names[0]) as root:
        spans.append(root)
        spin(0.003)
        for _ in range(2):
            with span(names[1]) as mid:
                spans.append(mid)
                spin(0.002)
                for _ in range(3):
                    with span(names[2]) as leaf, span_part(prefix + "_part"):
                        spans.append(leaf)
                        spin(0.001)
    return names, spans, {n: moved(n, before[n]) for n in names}


def the_root_alone_has_cpu(prefix):
    names, spans, after = a_tree(prefix)
    root = spans[0]
    assert [after[n]["_calls"] for n in names] == [1, 2, 6]
    # the root's CPU holds its children's: 13 ms of spinning under it
    assert root.cpu_ns > 0.4 * 13e6
    assert after[names[0]][CPU] == pytest.approx(root.cpu_ns * 1e-9, rel=1e-9)
    # a span under a root reads no clock and gets no CPU family
    assert all(sp.cpu_ns is None for sp in spans[1:])
    assert not any(has(flat(n) + CPU) for n in names[1:])
    assert not has(flat(prefix + "_part") + CPU)
    assert val(flat(prefix + "_part") + "_calls") == 6
    # the wall clock, as ever: self times sum to the root's duration
    assert sum(after[n]["_self_seconds"] for n in names) == \
        pytest.approx(root.dur_ns * 1e-9, rel=1e-9)
    return spans


def test_a_threads_outermost_span_alone_reads_the_cpu_clock():
    spans = the_root_alone_has_cpu("cpu.lone")
    assert all(sp._ann is None for sp in spans)


def test_inside_a_session_too_the_root_alone_reads_the_cpu_clock(session,
                                                                 monkeypatch):
    seen = []
    real = metrics._find_trace_annotation()

    def noting(label):
        seen.append(label)
        return real(label)

    noting.is_enabled = real.is_enabled
    monkeypatch.setattr(metrics, "_trace_annotation", noting)
    the_root_alone_has_cpu("cpu.seen")
    # every span and part was an annotation all the same
    assert seen.count("filodb:cpu.seen_leaf") == 6
    assert seen.count("filodb-part:cpu.seen_part") == 6


def test_a_span_that_is_root_on_one_thread_and_not_on_another():
    """The family is the name's: it holds the exits as a root alone."""
    with span("cpu.either") as alone:
        spin(0.002)
    with span("cpu.either_over"):
        with span("cpu.either") as under:
            spin(0.002)
    assert under.cpu_ns is None
    assert val("span_cpu_either_calls") == 2
    assert val("span_cpu_either_cpu_seconds") == \
        pytest.approx(alone.cpu_ns * 1e-9, rel=1e-9)


def test_spans_off_reads_no_cpu_clock(monkeypatch):
    reads = []
    real = time.thread_time_ns
    monkeypatch.setattr(metrics.time, "thread_time_ns",
                        lambda: reads.append(1) or real())
    metrics.set_spans_enabled(False)
    try:
        with span("cpu.off") as sp, span_part("cpu.off_part"):
            pass
    finally:
        metrics.set_spans_enabled(True)
    assert not reads and sp.cpu_ns is None and sp.dur_ns > 0
    assert val("span_cpu_off_calls") == 0
    with span("cpu.off"), span("cpu.off_inner"):
        pass
    assert len(reads) == 2 and val("span_cpu_off_calls") == 1


# ------------------------------------------------- the connection's spans

CONN = ("conn.accept", "conn.serve", "conn.read_request", "conn.close")


@pytest.fixture(scope="module")
def door():
    srv = FiloHttpServer(PromHttpApi({}), port=0)
    srv.start()
    yield f"http://127.0.0.1:{srv.port}"
    srv.stop()


def fetch(url):
    try:
        with urllib.request.urlopen(url, timeout=30) as r:
            return r.status, r.read()
    except urllib.error.HTTPError as e:
        return e.code, e.read()


def settled(names, before, n):
    """The spans' movements once every one has `n` more calls: a
    connection's spans are booked after the client has its answer."""
    deadline = time.monotonic() + 10.0
    while True:
        now = {s: moved(s, before[s]) for s in names}
        if all(now[s]["_calls"] >= n for s in names):
            return now
        assert time.monotonic() < deadline, now
        time.sleep(0.005)


def test_a_connections_life_lies_under_spans(door):
    names = CONN + ("http.request", "http.route", "http.encode",
                    "http.write")
    before = {s: families(s) for s in names}
    handed = val("conn_handover_seconds")
    # (the ids the collector knew: it keeps the newest 256, so a position
    # says nothing once a worker's earlier tests have filled it)
    known = set(metrics.collector.trace_ids())
    n = 7
    t0 = time.perf_counter()
    for i in range(n):
        status, body = fetch(f"{door}/api/v1/query?query=up&time={i}")
        assert status == 404 and b"no datasets" in body
    now = settled(names, before, n)
    assert [now[s]["_calls"] for s in names] == [n] * len(names)
    assert now["conn.serve"]["_seconds"] >= now["http.request"]["_seconds"]
    assert now["conn.serve"]["_seconds"] >= \
        now["conn.read_request"]["_seconds"] + \
        now["http.request"]["_seconds"] + now["conn.close"]["_seconds"]
    # what a client waits for between the two threads, booked by the
    # handler thread as its first act, before conn.serve opens: one
    # stage after the other, so together they fit in the time it all took
    waited = val("conn_handover_seconds") - handed
    assert 0 < waited
    assert waited + now["conn.serve"]["_seconds"] < time.perf_counter() - t0
    # closure over the two threads: the self times of everything under
    # the connection's two outermost spans sum to their durations
    assert sum(now[s]["_self_seconds"] for s in names) == pytest.approx(
        now["conn.serve"]["_seconds"] + now["conn.accept"]["_seconds"],
        rel=1e-6)
    # a thread's outermost span has its CPU, the spans under it none
    assert now["conn.serve"][CPU] > 0 and now["conn.accept"][CPU] > 0
    assert not any(CPU in now[s] for s in names[2:])
    # the request's trace is what it was: its root is http.request, with
    # no parent, and the paths start there
    tid = next(t for t in metrics.collector.trace_ids() if t not in known)
    evs = metrics.collector.trace(tid)
    assert [e["span"] for e in evs if e["parent_id"] is None] == \
        ["http.request"]
    assert {e["span"] for e in evs} == {
        "http.request", "http.request.http.route",
        "http.request.http.encode", "http.request.http.write"}
    assert not any(e["name"].startswith("conn.") for e in evs)


def test_the_operators_routes_are_connections_too(door):
    before = {s: families(s) for s in CONN + ("http.request",)}
    status, body = fetch(f"{door}/metrics")
    assert status == 200
    now = settled(CONN, before, 1)
    assert [now[s]["_calls"] for s in CONN] == [1] * 4
    assert moved("http.request", before["http.request"])["_calls"] == 0
    # the standard family, read at scrape
    line = next(ln for ln in body.decode().splitlines()
                if ln.startswith("process_cpu_seconds_total"))
    assert 0 < float(line.split()[1]) <= time.process_time()


def test_a_bad_request_line_still_closes_its_spans(door):
    import socket
    before = {s: families(s) for s in CONN}
    host, port = door[len("http://"):].split(":")
    with socket.create_connection((host, int(port)), timeout=10) as s:
        s.sendall(b"NONSENSE\r\n\r\n")
        assert b"400" in s.recv(4096)
    with socket.create_connection((host, int(port)), timeout=10) as s:
        s.sendall(b"BREW /pot HTTP/1.0\r\n\r\n")
        assert b"501" in s.recv(4096)
    with socket.create_connection((host, int(port)), timeout=10) as s:
        pass                    # a connection that says nothing
    now = settled(CONN, before, 3)
    assert [now[s]["_calls"] for s in CONN] == [3] * 4
    # and the next request reads as ever
    assert fetch(f"{door}/metrics")[0] == 200


# ------------------------------------------------ the collector's passes

def gc_counts():
    return (val("gc_full_passes"), val("gc_full_pass_seconds"))


def test_a_full_pass_is_booked_by_name_and_a_young_one_is_not():
    before = gc_counts()
    gc.collect(0)
    gc.collect(1)
    assert gc_counts() == before
    t0 = time.perf_counter()
    gc.collect(2)
    took = time.perf_counter() - t0
    passes, seconds = (b - a for a, b in zip(before, gc_counts()))
    assert passes == 1 and 0 < seconds <= took
    # the hook opens no span: nothing to re-enter, no family to double
    assert not has("span_gc_full_pass_calls")


def test_a_settle_books_its_pass_once():
    before, settles = gc_counts(), val("heap_settles")
    took = heap.settle_heap(min_interval_s=0.0)
    assert took is not None and val("heap_settles") == settles + 1
    assert gc_counts() == before, "the hook left the settle's pass alone"
    gc.unfreeze()


def test_a_full_pass_in_a_session_is_an_annotation_by_the_spans_name(
        session, monkeypatch):
    seen = []
    real = metrics._find_trace_annotation()

    def noting(label):
        seen.append(label)
        return real(label)

    noting.is_enabled = real.is_enabled
    monkeypatch.setattr(metrics, "_trace_annotation", noting)
    gc.collect()
    assert seen == ["filodb:gc.full_pass"]
    metrics.set_spans_enabled(False)
    try:
        gc.collect()
    finally:
        metrics.set_spans_enabled(True)
    assert seen == ["filodb:gc.full_pass"]


def test_outside_a_session_a_full_pass_enters_no_annotation():
    assert metrics.enter_annotation("cpu.nothing") is None


def test_a_full_pass_inside_a_half_entered_span_harms_nothing(session,
                                                              monkeypatch):
    """The collector starts a pass at any bytecode boundary: here inside
    `span.__enter__`, after the span is on its thread's stack and before
    its fields are all set."""
    real = metrics._find_trace_annotation()

    def colliding(label):
        if label == "filodb:cpu.half":
            gc.collect()
        return real(label)

    colliding.is_enabled = real.is_enabled
    monkeypatch.setattr(metrics, "_trace_annotation", colliding)
    before = gc_counts()
    with trace_context("t-half"), span("cpu.half_outer"):
        with span("cpu.half") as sp:
            assert current_trace_id() == "t-half"
        assert current_trace_id() == "t-half"
    assert gc_counts()[0] == before[0] + 1
    assert sp.dur_ns > 0 and val("span_cpu_half_calls") == 1
    assert sorted(e["name"] for e in metrics.collector.trace("t-half")) == \
        ["cpu.half", "cpu.half_outer"]


def test_a_full_pass_inside_the_booking_loop_does_not_hang(monkeypatch):
    """... or on a thread that is inside `_book` with its lock held."""
    real = metrics._SpanSite.hist

    def hist_after_a_pass(site, tags):
        gc.collect()
        return real(site, tags)

    monkeypatch.setattr(metrics._SpanSite, "hist", hist_after_a_pass)
    before = gc_counts()
    done = []

    def run():
        with span("cpu.booked_outer"):
            with span("cpu_booked_hist", hist=True):
                pass
        done.append(1)

    t = threading.Thread(target=run, daemon=True)
    t.start()
    t.join(timeout=20)
    assert done, "booking deadlocked"
    assert val("span_cpu_booked_outer_calls") == 1
    assert val("span_cpu_booked_hist_calls") == 1
    assert registry.histogram("span_cpu_booked_hist_seconds").count == 1
    assert gc_counts()[0] == before[0] + 1
