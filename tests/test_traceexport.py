"""Zipkin trace-exporter unit coverage (utils/traceexport.py) — the
ISSUE-10 satellite: batch shape, drain-on-flush, and the sink-failure
path never wedging query serving (previously only the happy file/HTTP
paths were exercised, in tests/test_tracing.py)."""
import queue
import threading
import time

import pytest

from filodb_tpu.utils.metrics import (collector, registry, span,
                                      trace_context)
from filodb_tpu.utils.traceexport import TraceExporter, _zipkin_span


def _event(i=0):
    return {"span": f"exec.{i}", "name": f"exec.{i}", "trace_id": "t",
            "span_id": "%016x" % (i + 1),
            "parent_id": "%016x" % i if i else None,
            "start_ns": time.perf_counter_ns(), "dur_ns": 2_000_000,
            "dur_s": 0.002, "node": "n1", "shard": str(i)}


# ---------------------------------------------------------------- batching

def test_flush_ships_in_batch_sized_chunks():
    """One _flush drains the WHOLE queue but ships it in `batch`-sized
    POSTs (Zipkin collectors reject oversized bodies; the batch bound is
    the contract)."""
    shipped = []
    exp = TraceExporter("http://unused.invalid/api/v2/spans", batch=16)
    exp._ship = lambda spans: shipped.append(list(spans))
    for i in range(40):
        exp.sink("a" * 32, _event(i))
    exp._flush()
    assert [len(b) for b in shipped] == [16, 16, 8]
    # every span arrived exactly once, order preserved
    names = [s["name"] for b in shipped for s in b]
    assert names == [f"exec.{i}" for i in range(40)]


def test_zipkin_span_shape():
    """The v2 span dict: 32-hex traceId (uuid dashes stripped; non-uuid
    ids hashed), microsecond duration floored at 1, tags carry the
    event's extra fields but not the structural ones."""
    ev = _event(3)
    sp = _zipkin_span("11111111-2222-3333-4444-555555555555", ev)
    assert sp["traceId"] == "11111111222233334444555555555555"
    assert sp["name"] == "exec.3"
    assert sp["duration"] == 2000
    assert sp["localEndpoint"]["serviceName"] == "n1"
    assert sp["tags"] == {"shard": "3"}
    # the event's own ids, not minted ones; a root has no parentId
    assert sp["id"] == "%016x" % 4 and sp["parentId"] == "%016x" % 3
    assert "parentId" not in _zipkin_span("a" * 32, _event(0))
    # timestamp: the monotonic start through the trace's one anchor
    with trace_context("zipkin-anchor"), span("anchored"):
        pass
    real = collector.trace("zipkin-anchor")[0]
    z = _zipkin_span("zipkin-anchor", real)
    assert z["id"] == real["span_id"]
    assert abs(z["timestamp"] / 1e6 - time.time()) < 5.0
    # a non-hex trace id still produces a valid 32-hex id
    weird = _zipkin_span("not-a-uuid!", _event())
    assert len(weird["traceId"]) == 32
    assert all(c in "0123456789abcdef" for c in weird["traceId"])
    # zero-duration events never emit duration=0 (Zipkin drops them)
    sp0 = _zipkin_span("a" * 32, {"span": "s", "dur_s": 0.0})
    assert sp0["duration"] == 1


# ----------------------------------------------------------- drain on stop

def test_stop_drains_remaining_queue():
    """stop() must ship everything still queued (the final flush) —
    spans recorded just before shutdown are not silently dropped."""
    shipped = []
    # a long flush interval so the background thread never gets there
    # first: the drain must come from stop() itself
    exp = TraceExporter("http://unused.invalid/api/v2/spans",
                        flush_interval_s=60.0, batch=8)
    exp._ship = lambda spans: shipped.append(list(spans))
    exp.start()
    try:
        for i in range(20):
            exp.sink("b" * 32, _event(i))
    finally:
        exp.stop()
    assert sum(len(b) for b in shipped) == 20


# ------------------------------------------------------------ sink failure

def test_sink_failure_never_blocks_recording_path():
    """A dead collector must cost the query path NOTHING: sink() stays
    non-blocking (overflow drops are counted, never waited on), the
    export thread keeps running, and recovery resumes shipping."""
    calls = {"n": 0}
    broken = {"yes": True}

    def flaky_ship(spans):
        calls["n"] += 1
        if broken["yes"]:
            raise ConnectionError("collector down")

    exp = TraceExporter("http://unused.invalid/api/v2/spans",
                        flush_interval_s=0.02, max_queue=32, batch=8)
    exp._ship = flaky_ship
    err0 = registry.counter("trace_export_errors").value
    drop0 = registry.counter("trace_export_dropped").value
    exp.start()
    try:
        # flood well past the queue bound while the sink is failing:
        # every sink() call must return immediately
        t0 = time.perf_counter()
        for i in range(500):
            exp.sink("c" * 32, _event(i))
        assert time.perf_counter() - t0 < 1.0, "sink() blocked"
        deadline = time.time() + 5
        while time.time() < deadline and \
                registry.counter("trace_export_errors").value == err0:
            time.sleep(0.01)
        assert registry.counter("trace_export_errors").value > err0
        assert registry.counter("trace_export_dropped").value > drop0
        # the exporter job surfaced the streak (alertable via selfmon)
        from filodb_tpu.utils.jobs import jobs
        h = jobs.get("trace_export")
        assert h is not None and h.consecutive_errors >= 1
        # recovery: the sink heals, new spans ship again
        broken["yes"] = False
        exp.sink("d" * 32, _event(0))
        pre = calls["n"]
        deadline = time.time() + 5
        while time.time() < deadline and calls["n"] == pre:
            time.sleep(0.01)
        assert calls["n"] > pre
        assert h.consecutive_errors == 0     # note_ok reset the streak
    finally:
        exp.stop()


def test_sink_failure_does_not_wedge_query_serving():
    """End to end through the span pipeline: with the export sink
    attached to the collector and permanently failing, traced spans
    still record and complete at full speed — export is fire-and-forget
    off the serving path."""

    def dead_ship(spans):
        raise ConnectionError("collector down")

    exp = TraceExporter("http://unused.invalid/api/v2/spans",
                        flush_interval_s=0.02, max_queue=8)
    exp._ship = dead_ship
    exp.start()
    try:
        t0 = time.perf_counter()
        for i in range(200):
            with trace_context(f"{i:032x}"):
                with span("serving_probe"):
                    pass
        elapsed = time.perf_counter() - t0
        # 200 traced no-op spans must complete in well under a second
        # even with the exporter's queue full and its sink down
        assert elapsed < 1.0, f"span recording wedged: {elapsed:.3f}s"
        # and the collector still holds the traces (the in-memory store
        # is independent of export health)
        assert collector.trace(f"{199:032x}")
    finally:
        exp.stop()
