"""Prometheus remote_write front door: shared prompb codec table,
/api/v1/write conformance (decode → columnar ingest → remote-read/PromQL
round trip), tenant backpressure (429 + Retry-After), WAL-backed acks,
and Influx-door admission parity (doc/http_api.md, doc/ingestion.md)."""
import struct

import numpy as np
import pytest

from filodb_tpu.config import FilodbSettings
from filodb_tpu.http import remotepb
from filodb_tpu.utils import snappy
from filodb_tpu.utils.usage import usage

START = 1_600_000_000_000


@pytest.fixture(autouse=True)
def _fresh_usage():
    usage.clear()
    win = usage.window_s
    yield
    usage.window_s = win
    usage.clear()


def _series(n=8, k=4, ws="demo", ns="app", metric="http_req_total"):
    out = []
    for i in range(n):
        labels = [("__name__", metric), ("_ws_", ws), ("_ns_", ns),
                  ("inst", str(i))]
        samples = [(float(i * 100 + j), START + j * 10_000)
                   for j in range(k)]
        out.append(remotepb.PromTimeSeries(labels, samples))
    return out


def _payload(series):
    return snappy.compress(remotepb.encode_write_request(series))


def _server(tmp_path=None, wal=False, shards=2, config=None):
    from filodb_tpu.standalone import DatasetConfig, FiloServer
    cfg = config or FilodbSettings()
    if wal:
        cfg.wal.enabled = True
        cfg.wal.dir = str(tmp_path / "wal")
    return FiloServer(datasets=[DatasetConfig("prometheus",
                                              num_shards=shards)],
                      config=cfg)


# ------------------------------------------------------- codec table parity

def test_codec_table_is_the_implementation():
    """Remote-read and remote-write must not grow drifting copies: the
    CODECS table entries ARE the module-level encode/decode functions
    both protocols compose."""
    assert remotepb.CODECS["Label"] == (remotepb.encode_label,
                                        remotepb.decode_label)
    assert remotepb.CODECS["Sample"] == (remotepb.encode_sample,
                                         remotepb.decode_sample)
    assert remotepb.CODECS["TimeSeries"] == (remotepb.encode_timeseries,
                                             remotepb.decode_timeseries)


def test_codec_table_parity_hand_built_fixtures():
    """Encode/decode parity against hand-assembled protobuf wire bytes
    (varint keys, length-delimited strings, little-endian doubles) — the
    exact bytes a real prompb writer emits."""
    # Label { name = "job" (field 1), value = "api" (field 2) }
    label_wire = b"\x0a\x03job\x12\x03api"
    assert remotepb.decode_label(label_wire) == ("job", "api")
    assert remotepb.encode_label(("job", "api")) == label_wire
    # Sample { value = 1.5 (field 1, fixed64), timestamp = 1600000000000 }
    sample_wire = b"\x09" + struct.pack("<d", 1.5) \
        + b"\x10" + b"\x80\x80\xba\xbb\xc8\x2e"
    assert remotepb.decode_sample(sample_wire) == (1.5, START)
    assert remotepb.encode_sample((1.5, START)) == sample_wire
    # TimeSeries { labels = [the label], samples = [the sample] }
    ts_wire = (b"\x0a" + bytes([len(label_wire)]) + label_wire
               + b"\x12" + bytes([len(sample_wire)]) + sample_wire)
    ts = remotepb.decode_timeseries(ts_wire)
    assert ts.labels == [("job", "api")]
    assert ts.samples == [(1.5, START)]
    assert remotepb.encode_timeseries(ts) == ts_wire
    # WriteRequest { timeseries = [the series] } and the read-response
    # QueryResult share the SAME series bytes — table parity on the wire
    wr_wire = b"\x0a" + bytes([len(ts_wire)]) + ts_wire
    assert remotepb.encode_write_request([ts]) == wr_wire
    got = remotepb.decode_write_request(wr_wire)
    assert got == [ts]


def test_write_request_roundtrip_and_unknown_fields():
    series = _series(3, 2)
    wire = remotepb.encode_write_request(series)
    assert remotepb.decode_write_request(wire) == series
    # a client sending prompb Metadata (WriteRequest field 3) must not
    # break decode: unknown length-delimited fields skip per proto3
    wire2 = wire + b"\x1a\x04\x08\x01\x12\x00"
    assert remotepb.decode_write_request(wire2) == series
    # negative timestamps survive the two's-complement varint
    s = remotepb.PromTimeSeries([("__name__", "m")], [(-2.5, -1000)])
    assert remotepb.decode_write_request(
        remotepb.encode_write_request([s])) == [s]


# ------------------------------------------------------------- conformance

def test_write_ingest_promql_and_remote_read_roundtrip():
    srv = _server()
    try:
        status, resp = srv.api.handle("POST", "/api/v1/write", {},
                                      _payload(_series()))
        assert status == 204
        # PromQL sees the samples
        status, resp = srv.api.handle(
            "GET", "/api/v1/query_range",
            {"query": "http_req_total",
             "start": str(START // 1000), "end": str(START // 1000 + 30),
             "step": "10"}, b"")
        assert status == 200
        result = resp["data"]["result"]
        assert len(result) == 8
        by_inst = {dict(r["metric"]).get("inst"): r["values"]
                   for r in result}
        assert [float(v) for _, v in by_inst["3"]] == [300.0, 301.0,
                                                       302.0, 303.0]
        # and the remote-read door returns the same series back
        rq = remotepb.encode_read_request([remotepb.PromQuery(
            START, START + 30_000,
            [remotepb.LabelMatcher(remotepb.EQ, "__name__",
                                   "http_req_total")])])
        status, blob = srv.api.handle("POST", "/api/v1/read", {},
                                      snappy.compress(rq))
        assert status == 200
        res = remotepb.decode_read_response(snappy.decompress(blob))
        assert len(res[0]) == 8
        assert sum(len(s.samples) for s in res[0]) == 32
    finally:
        srv.shutdown()


def test_write_to_a_series_held_as_counter_is_read_back(tmp_path):
    """A series first ingested through a typed door (prom-counter) keeps
    its schema when remote_write appends to it: the acknowledged sample
    must be queryable, and survive a WAL restart, not land in a gauge
    store addressed by another store's rows (chip_smoke.py found it)."""
    from filodb_tpu.core.partkey import PartKey
    srv = _server(tmp_path, wal=True)
    series = _series(6, 1)
    keys = [PartKey.make("http_req_total",
                         {k: v for k, v in s.labels if k != "__name__"})
            for s in series]
    mapper, spread = srv.mappers["prometheus"], srv.spreads["prometheus"]
    for i, pk in enumerate(keys):
        sh = srv.memstore.get_shard("prometheus", mapper.ingestion_shard(
            pk.shard_key_hash(), pk.partition_hash(),
            spread.spread_for(pk.shard_key())))
        sh.ingest_columns("prom-counter", [pk],
                          np.array([[START - 10_000]]),
                          {"count": np.array([[float(i)]])})

    def newest(server):
        status, resp = server.api.handle(
            "GET", "/api/v1/query",
            {"query": "http_req_total", "time": str(START // 1000)}, b"")
        assert status == 200
        return sorted(float(r["value"][1]) for r in resp["data"]["result"])
    try:
        status, _ = srv.api.handle("POST", "/api/v1/write", {},
                                   _payload(series))
        assert status == 204
        want = [float(i * 100) for i in range(6)]
        assert newest(srv) == want
        assert all(st.num_series == 0
                   for sh in srv.memstore.shards_for("prometheus")
                   for name, st in sh.stores.items() if name == "gauge")
    finally:
        srv.shutdown()
    srv2 = _server(tmp_path, wal=True)
    try:
        # only the remote-written samples were logged: they replay into
        # the schema they were acknowledged in
        assert newest(srv2) == want
        assert {name for sh in srv2.memstore.shards_for("prometheus")
                for name, st in sh.stores.items()
                if st.num_series} == {"prom-counter"}
    finally:
        srv2.shutdown()


def test_write_ragged_sample_counts_slab_grouping():
    """Series with different sample counts land via separate rectangular
    slabs — same totals, no per-sample path."""
    srv = _server()
    try:
        series = _series(4, 2) + _series(3, 5, metric="other_total")
        status, _ = srv.api.handle("POST", "/api/v1/write", {},
                                   _payload(series))
        assert status == 204
        got = sum(sh.stats.rows_ingested
                  for sh in srv.memstore.shards_for("prometheus"))
        assert got == 4 * 2 + 3 * 5
    finally:
        srv.shutdown()


def test_write_malformed_payloads_400():
    srv = _server(shards=1)
    try:
        # not snappy at all
        status, resp = srv.api.handle("POST", "/api/v1/write", {},
                                      b"\xff\xfe garbage")
        assert status == 400 and resp["status"] == "error"
        # valid snappy of truncated protobuf (length-delimited field
        # promising more bytes than exist)
        status, resp = srv.api.handle("POST", "/api/v1/write", {},
                                      snappy.compress(b"\x0a\xff\x01ab"))
        assert status == 400
        # empty write is a no-op 2xx (Prometheus sends keep-alive shapes)
        status, _ = srv.api.handle("POST", "/api/v1/write", {},
                                   snappy.compress(b""))
        assert status == 204
    finally:
        srv.shutdown()


# ------------------------------------------------------------ backpressure

def test_over_limit_tenant_429_retry_after():
    cfg = FilodbSettings()
    cfg.query.tenant_ingest_samples_limit = 40
    cfg.query.tenant_limit_window_s = 0.3
    srv = _server(config=cfg)
    try:
        usage.window_s = 0.3
        pay = _payload(_series(8, 4))        # 32 samples per request
        st1, _ = srv.api.handle("POST", "/api/v1/write", {}, pay)
        st2, _ = srv.api.handle("POST", "/api/v1/write", {}, pay)
        st3, resp = srv.api.handle("POST", "/api/v1/write", {}, pay)
        assert (st1, st2) == (204, 204)      # the crossing batch lands
        assert st3 == 429
        assert resp["errorType"] == "too_many_requests"
        assert int(resp["_headers"]["Retry-After"]) >= 1
        # ANOTHER tenant is not starved by the abuser
        other = _payload(_series(2, 2, ws="other", ns="ns2",
                                 metric="other_m"))
        st, _ = srv.api.handle("POST", "/api/v1/write", {}, other)
        assert st == 204
        # the window rolls and the tenant is admitted again
        import time
        time.sleep(0.35)
        st, _ = srv.api.handle("POST", "/api/v1/write", {}, pay)
        assert st == 204
    finally:
        srv.shutdown()


def test_mixed_tenant_write_no_bypass():
    """An over-limit tenant must not ride in behind another tenant's
    series: admission is per SERIES tenant, the admitted tenant's
    samples land, and the response is still a 429 so the rejected
    tenant's re-send is never silently dropped."""
    cfg = FilodbSettings()
    cfg.query.tenant_ingest_samples_limit = 10
    srv = _server(config=cfg)
    try:
        abusive = _series(8, 4, ws="abuser")          # 32 samples
        srv.api.handle("POST", "/api/v1/write", {}, _payload(abusive))
        # smuggle attempt: a polite first series, then the abuser again
        polite = _series(2, 2, ws="polite", metric="polite_total")
        st, resp = srv.api.handle("POST", "/api/v1/write", {},
                                  _payload(polite + abusive))
        assert st == 429                     # rejection is LOUD
        assert int(resp["_headers"]["Retry-After"]) >= 1
        rows = {(r["ws"], r["ns"]): r for r in usage.snapshot()}
        # polite's samples landed; the abuser's second batch did not
        assert rows[("polite", "app")]["ingestSamples"] == 4
        assert rows[("abuser", "app")]["ingestSamples"] == 32
        assert rows[("abuser", "app")]["ingestRejected"] >= 1
    finally:
        srv.shutdown()


def test_tenant_from_scope_orgid_header():
    cfg = FilodbSettings()
    cfg.query.tenant_ingest_samples_limit = 10
    srv = _server(config=cfg)
    try:
        pay = _payload(_series(8, 4, ws="", ns=""))   # no tenant labels
        hdr = {"X-Scope-OrgID": "hdrws/hdrns"}
        srv.api.handle("POST", "/api/v1/write", {}, pay, headers=hdr)
        st, _ = srv.api.handle("POST", "/api/v1/write", {}, pay,
                               headers=hdr)
        assert st == 429
        # the rejection was booked under the HEADER tenant
        rows = {(r["ws"], r["ns"]): r for r in usage.snapshot()}
        assert rows[("hdrws", "hdrns")]["ingestRejected"] >= 1
        # a different org id sails through
        st, _ = srv.api.handle("POST", "/api/v1/write", {}, pay,
                               headers={"X-Scope-OrgID": "fresh"})
        assert st == 204
    finally:
        srv.shutdown()


# ------------------------------------------------------------ WAL-backed ack

def test_write_with_wal_survives_restart(tmp_path):
    cfg = FilodbSettings()
    srv = _server(tmp_path, wal=True, config=cfg)
    try:
        st, _ = srv.api.handle("POST", "/api/v1/write", {},
                               _payload(_series()))
        assert st == 204
        wal = srv.wals["prometheus"]
        assert wal.writer.committed_seq >= 0     # acked == group-committed
    finally:
        srv.shutdown()
    # cold restart on the same WAL dir: replay re-drives ingest_columns
    cfg2 = FilodbSettings()
    srv2 = _server(tmp_path, wal=True, config=cfg2)
    try:
        status, resp = srv2.api.handle(
            "GET", "/api/v1/query_range",
            {"query": "http_req_total",
             "start": str(START // 1000), "end": str(START // 1000 + 30),
             "step": "10"}, b"")
        assert status == 200
        assert len(resp["data"]["result"]) == 8
    finally:
        srv2.shutdown()


def test_wal_commit_failure_withholds_ack(tmp_path):
    from filodb_tpu.utils.faults import faults
    srv = _server(tmp_path, wal=True)
    try:
        with faults.plan("wal.fsync", "error", first_k=1):
            st, resp = srv.api.handle("POST", "/api/v1/write", {},
                                      _payload(_series(4, 2)))
        assert st == 503                     # ack withheld, client retries
        assert resp["errorType"] == "unavailable"
        # the retry succeeds and the data is correct (replay dedup would
        # absorb any on-disk duplicate of the failed attempt)
        st, _ = srv.api.handle("POST", "/api/v1/write", {},
                               _payload(_series(4, 2)))
        assert st == 204
    finally:
        srv.shutdown()


# ---------------------------------------------------- Influx-door parity

def test_influx_gateway_admission_parity():
    """The Influx doors enforce the SAME per-tenant ingest admission: no
    door bypasses the limits.  The TCP-path sink drops WITH accounting;
    the HTTP /influx endpoint backpressures with 429 + Retry-After."""
    from filodb_tpu.utils.metrics import registry
    cfg = FilodbSettings()
    cfg.query.tenant_ingest_samples_limit = 10
    srv = _server(config=cfg)
    try:
        usage.window_s = 60.0
        lines = [f"req,_ws_=demo,_ns_=app,inst={i} "
                 f"counter=1 {START * 1_000_000}" for i in range(8)]
        body = "\n".join(lines).encode()
        st1, _ = srv.api.handle("POST", "/influx/write", {}, body)
        st2, _ = srv.api.handle("POST", "/influx/write", {}, body)
        st3, resp = srv.api.handle("POST", "/influx/write", {}, body)
        assert (st1, st2) == (204, 204)
        assert st3 == 429
        assert int(resp["_headers"]["Retry-After"]) >= 1
        gw = srv.gateways["prometheus"]
        assert gw.drops.get("tenant_limit_exceeded", 0) >= 8
        c = registry.counter("tenant_ingest_rejections", ws="demo",
                             ns="app")
        assert c.value >= 1
    finally:
        srv.shutdown()


def test_container_sink_admission_parity():
    """gateway/server.py's Kafka-path sink (the TCP listener's pipeline)
    rejects over-limit tenants before publishing, with drop accounting —
    the no-reply-channel flavor of the same admission."""
    from filodb_tpu.core.schemas import DEFAULT_SCHEMAS
    from filodb_tpu.gateway.server import KafkaContainerSink
    from filodb_tpu.parallel.shardmapper import ShardMapper
    cfg = FilodbSettings()
    cfg.query.tenant_ingest_samples_limit = 10
    frames = []

    def produce(topic, partition, value):
        frames.append((topic, partition, value))
        return len(frames)

    sink = KafkaContainerSink(produce, "ts", ShardMapper(2),
                              schemas=DEFAULT_SCHEMAS, config=cfg)
    lines = [f"req,_ws_=demo,_ns_=app,inst={i} "
             f"counter=1 {START * 1_000_000}" for i in range(8)]
    assert sink.publish_lines(lines) == 8
    assert sink.publish_lines(lines) == 8    # crossing batch lands
    assert sink.publish_lines(lines) == 0    # rejected, not published
    assert sink.drops.get("tenant_limit_exceeded", 0) == 8
    assert len(frames) > 0


def test_mixed_tenant_batch_keeps_admitted_records():
    """One Influx batch carrying an over-limit tenant AND a fresh tenant:
    the fresh tenant's records still land (per-tenant admission, not
    per-batch)."""
    cfg = FilodbSettings()
    cfg.query.tenant_ingest_samples_limit = 4
    srv = _server(config=cfg)
    try:
        abusive = [f"req,_ws_=abuser,_ns_=x,inst={i} "
                   f"counter=1 {START * 1_000_000}" for i in range(6)]
        srv.api.handle("POST", "/influx/write", {},
                       "\n".join(abusive).encode())  # crosses the limit
        mixed = abusive + [
            f"req,_ws_=polite,_ns_=y,inst={i} "
            f"counter=1 {(START + 10_000) * 1_000_000}" for i in range(3)]
        st, _ = srv.api.handle("POST", "/influx/write", {},
                               "\n".join(mixed).encode())
        assert st == 204                      # some records landed
        # polite's records are all in; abuser's second batch was dropped
        rows = {(r["ws"], r["ns"]): r for r in usage.snapshot()}
        assert rows[("polite", "y")]["ingestSamples"] == 3
        assert rows[("abuser", "x")]["ingestSamples"] == 6
    finally:
        srv.shutdown()
