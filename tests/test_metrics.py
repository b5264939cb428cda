"""Metrics/tracing tests (models ref: Kamon metric assertions sprinkled in
TimeSeriesShardSpec + KamonLogger reporters)."""
import logging
import threading
import urllib.request

import numpy as np
import pytest

from filodb_tpu.core.memstore import TimeSeriesMemStore
from filodb_tpu.ingest.generator import gauge_batch
from filodb_tpu.utils.metrics import (FiloSchedulers, Histogram, collector,
                                      registry, span, trace_context)

START = 1_600_000_020_000


def test_counter_gauge_histogram_basics():
    c = registry.counter("test_ops", kind="a")
    c.increment()
    c.increment(4)
    assert c.value == 5
    assert registry.counter("test_ops", kind="a") is c
    assert registry.counter("test_ops", kind="b") is not c
    g = registry.gauge("test_depth")
    g.update(42)
    assert g.value == 42
    h = Histogram()
    for v in (0.02, 0.02, 8.0):
        h.record(v)
    # interpolated within the (0.01, 0.05] bucket, not its upper bound
    assert h.count == 3 and 0.01 < h.percentile(0.5) < 0.05


def test_span_records_and_reports():
    """(Was the reporter-hook test; the hook is gone and the collector's
    events are what a reader of spans gets.)"""
    self0 = registry.counter("span_outer_self_seconds").value
    with trace_context("t-span-records"):
        with span("outer", hist=True, q="1"):
            with span("inner"):
                pass
    evs = collector.trace("t-span-records")
    assert [e["span"] for e in evs] == ["outer", "outer.inner"]  # by start
    outer, inner = evs
    assert [e["name"] for e in evs] == ["outer", "inner"]
    assert inner["parent_id"] == outer["span_id"] and \
        outer["parent_id"] is None
    assert outer["q"] == "1" and outer["trace_id"] == "t-span-records"
    assert outer["start_ns"] <= inner["start_ns"] and \
        inner["start_ns"] + inner["dur_ns"] \
        <= outer["start_ns"] + outer["dur_ns"]
    assert registry.histogram("span_outer_seconds", q="1").count >= 1
    # self time = duration less the children's, booked by the program
    booked = registry.counter("span_outer_self_seconds").value - self0
    assert booked == pytest.approx(
        (outer["dur_ns"] - inner["dur_ns"]) * 1e-9, abs=1e-12)
    assert registry.counter("span_outer_calls").value >= 1


def test_ingest_and_query_emit_metrics():
    ms = TimeSeriesMemStore()
    sh = ms.setup("mtest", 0)
    sh.ingest(gauge_batch(5, 50, start_ms=START))
    assert registry.counter("ingested_rows", dataset="mtest",
                            shard="0").value == 250
    sh.flush_all_groups()
    assert registry.histogram("span_flush_seconds", dataset="mtest").count > 0

    from filodb_tpu.parallel.shardmapper import ShardEvent, ShardMapper
    from filodb_tpu.query.engine import QueryEngine
    mapper = ShardMapper(1)
    mapper.update_from_event(ShardEvent("IngestionStarted", "mtest", 0, "x"))
    eng = QueryEngine("mtest", ms, mapper)
    res = eng.query_range("heap_usage", START // 1000, 60, START // 1000 + 300)
    assert res.error is None
    assert registry.histogram("span_execplan_seconds",
                              plan="MultiSchemaPartitionsExec").count > 0


def test_prometheus_exposition_format():
    registry.counter("expo_total_ops", x="1").increment(3)
    registry.gauge("expo_live").update(7)
    registry.histogram("expo_lat").record(0.3)
    text = registry.expose_prometheus()
    assert 'expo_total_ops_total{x="1"} 3' in text
    assert "expo_live 7" in text
    assert 'expo_lat_bucket{le="+Inf"} 1' in text
    assert "expo_lat_count 1" in text


def test_metrics_http_endpoint():
    from filodb_tpu.standalone import DatasetConfig, FiloServer
    srv = FiloServer([DatasetConfig("prometheus", num_shards=1)], http_port=0)
    srv.memstore.get_shard("prometheus", 0).ingest(
        gauge_batch(6, 20, start_ms=START))
    srv.start()
    try:
        with urllib.request.urlopen(
                f"http://127.0.0.1:{srv.http.port}/metrics", timeout=30) as r:
            assert r.status == 200
            assert "text/plain" in r.headers["Content-Type"]
            text = r.read().decode()
        assert 'num_partitions{dataset="prometheus",shard="0"} 6' in text
        assert "ingested_rows_total" in text
    finally:
        srv.shutdown()


def test_traced_part_filters_log(caplog):
    ms = TimeSeriesMemStore()
    sh = ms.setup("ttest", 0)
    sh.traced_part_filters = [("_ns_", "App-1")]
    with caplog.at_level(logging.INFO, logger="filodb.shard"):
        sh.ingest(gauge_batch(10, 5, start_ms=START))
    traced = [r.getMessage() for r in caplog.records
              if "TRACED" in r.message]
    # r4: matched series are followed through creation AND ingest
    assert len([m for m in traced if "created" in m]) == 1
    assert len([m for m in traced if "ingest" in m]) == 1
    assert all("App-1" in m for m in traced)


def test_scheduler_assertions_gated():
    FiloSchedulers.enabled = False
    FiloSchedulers.assert_thread_name("nope")      # no-op when disabled
    FiloSchedulers.enabled = True
    try:
        with pytest.raises(AssertionError):
            FiloSchedulers.assert_thread_name("definitely-not-this-thread")
        t = threading.Thread(
            target=lambda: FiloSchedulers.assert_thread_name("ingest"),
            name="filodb-ingest-0")
        t.start()
        t.join()
    finally:
        FiloSchedulers.enabled = False


# ----------------------------------------------------------- profiler


def test_sampling_profiler_catches_hot_function():
    import threading
    import time
    from filodb_tpu.utils.profiler import SamplingProfiler

    stop = threading.Event()

    def hot_spin():
        x = 0
        while not stop.is_set():
            for i in range(2000):
                x += i * i
        return x

    t = threading.Thread(target=hot_spin, daemon=True)
    t.start()
    p = SamplingProfiler()
    assert p.start(hz=200)
    assert not p.start()            # double-start refused
    time.sleep(0.5)
    assert p.stop()
    stop.set(); t.join(timeout=5)
    assert p.samples > 20
    rep = p.report()
    assert "hot_spin" in rep, rep
    assert "sampling profiler" in rep
    # stopped profiler reports without error and start() resets counters
    assert p.start(hz=50) and p.stop()


def test_profiler_http_routes():
    from filodb_tpu.http.routes import PromHttpApi
    api = PromHttpApi({})
    status, body = api.handle("POST", "/admin/profiler/start", {"hz": "150"})
    assert status == 200 and body["status"] == "started"
    status, _ = api.handle("POST", "/admin/profiler/start", {})
    assert status == 400                      # already running
    status, rep = api.handle("GET", "/admin/profiler/report", {})
    assert status == 200 and "sampling profiler" in rep
    status, body = api.handle("POST", "/admin/profiler/stop", {})
    assert status == 200 and body["status"] == "stopped"
    status, _ = api.handle("POST", "/admin/profiler/stop", {})
    assert status == 400


def test_profiler_input_validation():
    from filodb_tpu.http.routes import PromHttpApi
    from filodb_tpu.utils.profiler import SamplingProfiler
    import pytest as _pytest
    p = SamplingProfiler()
    for bad in (float("inf"), float("nan"), 0.0, -5.0):
        with _pytest.raises(ValueError):
            p.start(bad)
    assert p.start(10_000.0)           # clamped, not rejected
    assert p.hz == p.MAX_HZ
    assert p.stop()
    api = PromHttpApi({})
    status, body = api.handle("POST", "/admin/profiler/start", {"hz": "abc"})
    assert status == 400, body
    status, body = api.handle("POST", "/admin/profiler/start", {"hz": "inf"})
    assert status == 400, body
    status, body = api.handle("GET", "/admin/profiler/start", {})
    assert status == 405, body
    status, body = api.handle("POST", "/admin/profiler/bogus", {})
    assert status == 404
