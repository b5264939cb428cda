"""Benchmark suite mirroring the reference's jmh classes.

ref: jmh/src/main/scala/filodb.jmh/ — IngestionBenchmark,
EncodingBenchmark, PartKeyIndexBenchmark, GatewayBenchmark,
QueryInMemoryBenchmark (:31-35,126-133 query set),
QueryHiCardInMemoryBenchmark, HistogramIngestBenchmark,
HistogramQueryBenchmark; runner run_benchmarks.sh.

Each benchmark prints one JSON line {"bench", "metric", "value", "unit"}.
Run all: python -m bench.suite            (add --quick for smoke sizing)
Run one: python -m bench.suite ingestion
The benchmark the driver runs is benchmark/run.py (BENCHMARK.json).
"""
from __future__ import annotations

import argparse
import os
import json
import time
from typing import Callable, Dict, List

import numpy as np

START = 1_600_000_020_000


def _emit(bench: str, metric: str, value: float, unit: str, **extra):
    print(json.dumps({"bench": bench, "metric": metric,
                      "value": round(value, 1), "unit": unit, **extra}))


def _time_it(fn: Callable, iters: int) -> float:
    t0 = time.perf_counter()
    for _ in range(iters):
        fn()
    return (time.perf_counter() - t0) / iters


# ------------------------------------------------------------- ingestion


def bench_ingestion(quick: bool):
    """Samples/sec through the shard ingest path
    (ref: IngestionBenchmark.scala)."""
    from filodb_tpu.core.memstore import TimeSeriesMemStore
    from filodb_tpu.ingest.generator import gauge_batch
    S, T = (500, 200) if quick else (2000, 720)
    batch = gauge_batch(S, T, start_ms=START)
    iters = 3 if quick else 5
    times = []
    for i in range(iters):
        ms = TimeSeriesMemStore()
        sh = ms.setup(f"bench{i}", 0)
        t0 = time.perf_counter()
        sh.ingest(batch)
        times.append(time.perf_counter() - t0)
    best = min(times)
    _emit("ingestion", "samples_per_sec", S * T / best, "samples/s",
          series=S, samples=T)


# -------------------------------------------------------------- encoding


def bench_encoding(quick: bool):
    """Chunk encode/decode throughput (ref: EncodingBenchmark.scala,
    IntSumReadBenchmark)."""
    from filodb_tpu.memory.chunks import decode_chunkset, encode_chunkset
    n = 10_000 if quick else 100_000
    ts = START + np.arange(n, dtype=np.int64) * 10_000
    vals = np.cumsum(np.random.default_rng(0).exponential(10, n))
    col_types = {"value": "double"}
    enc = lambda: encode_chunkset(ts, {"value": vals}, col_types, START)  # noqa: E731
    per = _time_it(enc, 3 if quick else 10)
    _emit("encoding", "encode_samples_per_sec", n / per, "samples/s")
    cs = enc()
    per = _time_it(lambda: decode_chunkset(cs), 3 if quick else 10)
    _emit("encoding", "decode_samples_per_sec", n / per, "samples/s",
          bytes_per_sample=round(cs.nbytes / n, 2))


# ----------------------------------------------------------------- index


def bench_index(quick: bool):
    """Tag-index add + filter lookup ops/sec
    (ref: PartKeyIndexBenchmark.scala)."""
    from filodb_tpu.core.index import Equals, EqualsRegex, PartKeyIndex
    from filodb_tpu.core.partkey import PartKey
    # full mode runs the 1M-doc config from the VERDICT target
    # (index lookup <= ~10ms at 1M series, ref PartKeyIndexBenchmark.scala)
    n = 20_000 if quick else 1_000_000
    keys = [PartKey.make(f"metric_{i % 50}",
                         {"_ws_": "demo", "_ns_": f"App-{i % 100}",
                          "instance": f"i{i}"}) for i in range(n)]
    idx = PartKeyIndex()
    t0 = time.perf_counter()
    for i, pk in enumerate(keys):
        idx.add_partition(i, pk, START)
    add_per_sec = n / (time.perf_counter() - t0)
    _emit("partkey_index", "adds_per_sec", add_per_sec, "ops/s", keys=n)
    filters = [Equals("_metric_", "metric_7"), Equals("_ns_", "App-42")]
    per = _time_it(lambda: idx.part_ids_from_filters(filters, 0, 1 << 62),
                   50 if quick else 200)
    _emit("partkey_index", "equals_lookups_per_sec", 1 / per, "ops/s",
          keys=n, latency_ms=round(per * 1000, 3))
    rx = [EqualsRegex("_ns_", "App-1.*")]
    per = _time_it(lambda: idx.part_ids_from_filters(rx, 0, 1 << 62),
                   20 if quick else 50)
    _emit("partkey_index", "regex_lookups_per_sec", 1 / per, "ops/s",
          keys=n, latency_ms=round(per * 1000, 3))


# --------------------------------------------------------------- gateway


def bench_gateway(quick: bool):
    """Influx line parse -> RecordBatch throughput
    (ref: GatewayBenchmark.scala)."""
    from filodb_tpu.core.schemas import DEFAULT_SCHEMAS
    from filodb_tpu.gateway.influx import influx_lines_to_batches
    n = 5_000 if quick else 20_000
    lines = [f"cpu_usage,_ws_=demo,_ns_=App-{i % 8},host=h{i % 100} "
             f"value={i * 0.5} {(START + i) * 1_000_000}" for i in range(n)]
    per = _time_it(lambda: influx_lines_to_batches(lines, DEFAULT_SCHEMAS),
                   3 if quick else 5)
    _emit("gateway", "influx_lines_per_sec", n / per, "lines/s")


# ------------------------------------------------------------ query set


def _mk_query_engine(S, T, quick):
    from filodb_tpu.core.memstore import TimeSeriesMemStore
    from filodb_tpu.ingest.generator import counter_batch, gauge_batch
    from filodb_tpu.parallel.shardmapper import ShardEvent, ShardMapper
    from filodb_tpu.query.engine import QueryEngine
    ms = TimeSeriesMemStore()
    sh = ms.setup("prometheus", 0)
    sh.ingest(counter_batch(S, T, start_ms=START))
    sh.ingest(gauge_batch(S, T, start_ms=START))
    mapper = ShardMapper(1)
    mapper.update_from_event(
        ShardEvent("IngestionStarted", "prometheus", 0, "b"))
    return QueryEngine("prometheus", ms, mapper)


QUERY_SET = [  # ref: QueryInMemoryBenchmark.scala:126-133
    ("raw_scan", 'request_total{_ws_="demo"}'),
    ("sum_rate", 'sum(rate(request_total[5m]))'),
    ("sum_by_rate", 'sum by (_ns_)(rate(request_total[5m]))'),
    ("quantile", 'quantile(0.75,heap_usage)'),
    ("sum_over_time", 'sum(sum_over_time(heap_usage[5m]))'),
]


def bench_query(quick: bool):
    """PromQL QPS over the in-memory store
    (ref: QueryInMemoryBenchmark.scala:31-35 — 100 series x 720 samples
    per shard; QPS per query shape)."""
    S, T = (100, 200) if quick else (100, 720)
    eng = _mk_query_engine(S, T, quick)
    s = START // 1000
    end = s + T * 10
    for name, q in QUERY_SET:
        run = lambda: eng.query_range(q, s + 600, 60, end)  # noqa: E731
        assert run().error is None, (name, run().error)
        per = _time_it(run, 5 if quick else 20)
        _emit("query_inmemory", f"{name}_qps", 1 / per, "queries/s",
              series=S)


def bench_dashboard_batch(quick: bool):
    """Dashboard panel throughput: P fused panels over one window grid,
    batched into merged kernel dispatches (engine.query_range_batch)
    vs issued one at a time.  The round-4 on-chip finding: a fused leaf
    query is dispatch-bound, so batching is where dashboard latency goes
    (doc/kernels.md; no reference analogue — iterator engines pay
    per-series either way)."""
    import os
    had = os.environ.get("FILODB_TPU_FUSED_INTERPRET")
    os.environ["FILODB_TPU_FUSED_INTERPRET"] = "1"
    S, T = (2_000, 240) if quick else (20_000, 720)
    eng = _mk_query_engine(S, T, quick)
    s = START // 1000
    end = s + T * 10
    panels = ['sum(rate(request_total[5m])) by (_ns_)',
              'avg(rate(request_total[5m])) by (dc)',
              'sum(rate(request_total[5m])) by (_ns_, dc)',
              'count(rate(request_total[5m])) by (dc)',
              'min(rate(request_total[5m])) by (_ns_)',
              'max(rate(request_total[5m])) by (dc)',
              'sum(rate(request_total[5m])) by (dc)',
              'sum(rate(request_total[5m])) by (instance)']
    args = (s + 600, 60, end)

    def seq():
        for q in panels:
            assert eng.query_range(q, *args).error is None

    def batch():
        for r in eng.query_range_batch(panels, *args):
            assert r.error is None

    try:
        seq(); batch()                   # warm mirror + caches
        iters = 3 if quick else 10
        t_seq = _time_it(seq, iters)
        t_batch = _time_it(batch, iters)
    finally:
        # restore: leaking interpret mode would silently reroute every
        # later bench's queries through the interpret fused path
        if had is None:
            os.environ.pop("FILODB_TPU_FUSED_INTERPRET", None)
        else:
            os.environ["FILODB_TPU_FUSED_INTERPRET"] = had
    _emit("dashboard_batch", "sequential_panels_per_s",
          len(panels) / t_seq, "panels/s", series=S)
    _emit("dashboard_batch", "batched_panels_per_s",
          len(panels) / t_batch, "panels/s", series=S,
          speedup=round(t_seq / t_batch, 2))


def bench_query_hicard(quick: bool):
    """Single-shard high-cardinality scan
    (ref: QueryHiCardInMemoryBenchmark.scala)."""
    S, T = (20_000, 40) if quick else (100_000, 60)
    eng = _mk_query_engine(S, T, quick)
    s = START // 1000
    q = 'sum(rate(request_total[5m]))'
    run = lambda: eng.query_range(q, s + 360, 60, s + T * 10)  # noqa: E731
    assert run().error is None
    per = _time_it(run, 2 if quick else 5)
    _emit("query_hicard", "sum_rate_qps", 1 / per, "queries/s", series=S)


def bench_query_odp(quick: bool):
    """Query served by on-demand paging from the persistence tier after the
    dense working set was truncated (ref: QueryOnDemandBenchmark.scala —
    queries against data that must page in from the column store)."""
    import tempfile
    from filodb_tpu.core.memstore import TimeSeriesMemStore
    from filodb_tpu.ingest.generator import counter_batch
    from filodb_tpu.persist.localstore import (LocalDiskColumnStore,
                                               LocalDiskMetaStore)
    from filodb_tpu.query.engine import QueryEngine
    S, T = (500, 240) if quick else (2000, 720)
    tmp = tempfile.mkdtemp(prefix="filodb_odp_bench_")
    cs, meta = LocalDiskColumnStore(tmp), LocalDiskMetaStore(tmp)
    ms = TimeSeriesMemStore(column_store=cs, meta_store=meta)
    sh = ms.setup("prometheus", 0)
    sh.ingest(counter_batch(S, T, start_ms=START), offset=1)
    sh.flush_all_groups()
    # cold store: recovered index, no resident data -> every query pages
    cold = TimeSeriesMemStore(column_store=cs, meta_store=meta)
    sh2 = cold.setup("prometheus", 0)
    sh2.recover_index()
    eng = QueryEngine("prometheus", cold)
    s = START // 1000
    q = 'sum(rate(request_total[5m]))'
    t0 = time.perf_counter()
    res = eng.query_range(q, s + 600, 60, s + T * 10)
    first = time.perf_counter() - t0
    assert res.error is None, res.error
    _emit("query_odp", "first_query_page_in_s", first, "s",
          series=S, samples=S * T,
          samples_paged_per_sec=round(S * T / first, 1))
    # warm: data now resident, same query
    per = _time_it(lambda: eng.query_range(q, s + 600, 60, s + T * 10),
                   3 if quick else 10)
    _emit("query_odp", "warm_qps_after_page_in", 1 / per, "queries/s",
          series=S)


def bench_partition_list(quick: bool):
    """lookup_partitions throughput over a populated shard
    (ref: PartitionListBenchmark.scala)."""
    from filodb_tpu.core.index import Equals
    from filodb_tpu.core.memstore import TimeSeriesMemStore
    from filodb_tpu.ingest.generator import counter_batch
    S = 20_000 if quick else 200_000
    ms = TimeSeriesMemStore()
    sh = ms.setup("prometheus", 0)
    sh.ingest(counter_batch(S, 2, start_ms=START, num_apps=100))
    lo, hi = 0, 1 << 62
    broad = [Equals("_metric_", "request_total")]
    per = _time_it(lambda: sh.lookup_partitions(broad, lo, hi),
                   20 if quick else 50)
    _emit("partition_list", "broad_lookups_per_sec", 1 / per, "ops/s",
          series=S, latency_ms=round(per * 1000, 3))
    narrow = [Equals("_metric_", "request_total"), Equals("_ns_", "App-7")]
    per = _time_it(lambda: sh.lookup_partitions(narrow, lo, hi),
                   50 if quick else 200)
    _emit("partition_list", "narrow_lookups_per_sec", 1 / per, "ops/s",
          series=S, latency_ms=round(per * 1000, 3))


def bench_query_under_ingest(quick: bool):
    """Query QPS while a thread continuously ingests into the same shard
    (ref: QueryAndIngestBenchmark.scala — the reference runs queries during
    its second window of live ingestion).  Reports concurrent QPS and the
    quiesced QPS for the same store so the interference cost is visible."""
    import threading
    from filodb_tpu.core.memstore import TimeSeriesMemStore
    from filodb_tpu.core.records import RecordBatch
    from filodb_tpu.ingest.generator import counter_batch
    from filodb_tpu.parallel.shardmapper import ShardEvent, ShardMapper
    from filodb_tpu.query.engine import QueryEngine
    S, T = (500, 360) if quick else (2000, 720)
    full = counter_batch(S, T, start_ms=START)
    ms = TimeSeriesMemStore()
    sh = ms.setup("prometheus", 0)
    half_ms = START + (T // 2) * 10_000
    keep = full.timestamps < half_ms
    sh.ingest(RecordBatch(full.schema, full.part_keys, full.part_idx[keep],
                          full.timestamps[keep],
                          {k: v[keep] for k, v in full.columns.items()},
                          full.bucket_les))
    mapper = ShardMapper(1)
    mapper.update_from_event(ShardEvent("IngestionStarted", "prometheus", 0, "b"))
    eng = QueryEngine("prometheus", ms, mapper)
    s = START // 1000
    q = 'sum by (_ns_)(rate(request_total[5m]))'
    run = lambda: eng.query_range(q, s + 600, 60, s + T * 10)  # noqa: E731
    assert run().error is None
    stop = threading.Event()

    def ingester():
        # stream the second half in small slices until the bench ends
        idx = T // 2
        while not stop.is_set():
            if idx >= T:
                idx = T // 2  # wrap: re-deliver (dropped as out-of-order)
            lo = START + idx * 10_000
            hi = lo + 20 * 10_000
            k = (full.timestamps >= lo) & (full.timestamps < hi)
            sh.ingest(RecordBatch(full.schema, full.part_keys,
                                  full.part_idx[k], full.timestamps[k],
                                  {kk: v[k] for kk, v in full.columns.items()},
                                  full.bucket_les))
            idx += 20
    t = threading.Thread(target=ingester, daemon=True)
    t.start()
    try:
        per_concurrent = _time_it(run, 5 if quick else 20)
    finally:
        stop.set()
        t.join(timeout=30)
    per_quiesced = _time_it(run, 5 if quick else 20)
    _emit("query_under_ingest", "concurrent_qps", 1 / per_concurrent,
          "queries/s", series=S,
          quiesced_qps=round(1 / per_quiesced, 1))


# -------------------------------------------------------------- histogram


def bench_histogram(quick: bool):
    """Histogram-schema ingest + quantile query
    (ref: HistogramIngestBenchmark.scala:24, HistogramQueryBenchmark)."""
    from filodb_tpu.core.memstore import TimeSeriesMemStore
    from filodb_tpu.ingest.generator import histogram_batch
    from filodb_tpu.parallel.shardmapper import ShardEvent, ShardMapper
    from filodb_tpu.query.engine import QueryEngine
    S, T = (50, 100) if quick else (200, 360)
    batch = histogram_batch(S, T, start_ms=START)
    times = []
    for i in range(3):
        ms = TimeSeriesMemStore()
        sh = ms.setup(f"hb{i}", 0)
        t0 = time.perf_counter()
        sh.ingest(batch)
        times.append(time.perf_counter() - t0)
    _emit("histogram", "ingest_samples_per_sec", S * T / min(times),
          "samples/s", buckets=batch.columns["h"].shape[-1])
    ms = TimeSeriesMemStore()
    sh = ms.setup("prometheus", 0)
    sh.ingest(batch)
    mapper = ShardMapper(1)
    mapper.update_from_event(
        ShardEvent("IngestionStarted", "prometheus", 0, "b"))
    eng = QueryEngine("prometheus", ms, mapper)
    s = START // 1000
    q = 'histogram_quantile(0.9,sum by (le)(rate(http_latency[5m])))'
    run = lambda: eng.query_range(q, s + 600, 60, s + T * 10)  # noqa: E731
    res = run()
    assert res.error is None, res.error
    per = _time_it(run, 2 if quick else 5)
    _emit("histogram", "quantile_qps", 1 / per, "queries/s", series=S)


def bench_histogram_compression(quick: bool):
    """Histogram storage-format efficiency, the HistogramCompressor
    harness analogue (ref: memory/.../HistogramCompressor.scala:1-216;
    doc/compression.md:97 claims ~50x vs the traditional per-bucket
    Prometheus data model at 64 buckets).  Measures bytes/histogram-sample
    for: the per-bucket time-series model, BinaryHistogram ingest blobs,
    the section-based appendable vector, and the sealed 2D-delta matrix
    codec."""
    import numpy as np

    from filodb_tpu.core.partkey import PartKey
    from filodb_tpu.memory.binhist import (AppendableSectHistVector,
                                           encode_blob_column)
    from filodb_tpu.memory.histogram import encode_hist_matrix

    B = 64
    T = 300 if quick else 2_000
    rng = np.random.default_rng(9)
    # busy + quiet mixture like real request-latency histograms
    rate = np.where(rng.random(B) < 0.3, 8.0, 0.2)
    inc = rng.poisson(rate, size=(T, B))
    per_bucket = np.cumsum(inc, axis=0)
    mat = np.cumsum(per_bucket, axis=1).astype(np.float64)
    les = 2.0 * 2.0 ** np.arange(B)

    # traditional prom data model: one series per bucket; each sample is
    # (ts i64 + value f64) plus the bucket series' part key amortized
    labels = {"_ws_": "demo", "_ns_": "App-0", "instance": "host-1",
              "path": "/api/v1/query"}
    pk_bytes = sum(
        len(PartKey.make("http_latency_bucket",
                         dict(labels, le=str(le))).to_bytes())
        for le in les)
    bucket_series_bytes = T * B * 16 + pk_bytes
    per_hist_bucket_series = bucket_series_bytes / T

    blob_bytes = len(encode_blob_column(mat, les))
    vec = AppendableSectHistVector(les)
    for row in mat:
        vec.append(row)
    sealed_bytes = len(encode_hist_matrix(mat))

    per_hist_blob = blob_bytes / T
    _emit("hist_compression", "bucket_series_bytes_per_hist",
          per_hist_bucket_series, "bytes", buckets=B)
    _emit("hist_compression", "binhist_blob_bytes_per_hist", per_hist_blob,
          "bytes", buckets=B,
          vs_bucket_series=round(per_hist_bucket_series / per_hist_blob, 1))
    _emit("hist_compression", "section_vector_bytes_per_hist",
          vec.num_bytes / T, "bytes", buckets=B,
          vs_bucket_series=round(per_hist_bucket_series
                                 / (vec.num_bytes / T), 1))
    _emit("hist_compression", "sealed_2d_delta_bytes_per_hist",
          sealed_bytes / T, "bytes", buckets=B,
          vs_bucket_series=round(per_hist_bucket_series
                                 / (sealed_bytes / T), 1))


def bench_cardinality(quick: bool):
    """Cardinality store at the reference's millions-of-prefixes scale
    (ref: RocksDbCardinalityStore.scala:256): batched write throughput,
    flush cost, and top-k query latency on the durable SQLite store."""
    import tempfile

    from filodb_tpu.core.ratelimit import (CardinalityRecord,
                                           SqliteCardinalityStore)
    n = 50_000 if quick else 1_000_000
    path = tempfile.mktemp(prefix="filodb_card_bench_", suffix=".db")
    store = SqliteCardinalityStore(path, flush_every=4096)
    t0 = time.perf_counter()
    for i in range(n):
        store.write(CardinalityRecord(
            ("demo", f"ns-{i % 1000}", f"app-{i}"), ts_count=i % 97 + 1))
    store.flush()
    wall = time.perf_counter() - t0
    _emit("cardinality", "writes_per_sec", n / wall, "ops/s", prefixes=n)
    t0 = time.perf_counter()
    kids = store.scan_children(("demo", "ns-7"))
    scan_s = time.perf_counter() - t0
    _emit("cardinality", "scan_children_latency_ms", scan_s * 1000, "ms",
          children=len(kids))
    store.close()
    import os as _os
    _os.unlink(path)


def bench_memory(quick: bool):
    """Resident memory per series after sealing history to the compressed
    tier (ref: doc/ingestion.md:110 '1.5 million time series fit within
    1GB heap' — the reference's only quantitative memory claim)."""
    from filodb_tpu.core.memstore import TimeSeriesMemStore
    from filodb_tpu.ingest.generator import counter_batch

    S = 2_000 if quick else 20_000
    T = 360                                   # 1h of 10s samples
    ms = TimeSeriesMemStore()
    shard = ms.setup("prometheus", 0)
    for lo in range(0, S, 2_000):             # batch to bound peak RAM
        n = min(2_000, S - lo)
        b = counter_batch(n, T, start_ms=START,
                          metric=f"m{lo}")
        # real counters are integral — exercises the delta-delta-as-long
        # double encoding (ref: DoubleVector.scala 'when integral')
        b.columns["count"] = np.floor(b.columns["count"])
        shard.ingest(b)
    dense_before = shard.memory_usage()["dense_bytes"]
    shard.enforce_memory(budget_bytes=1, active_tail_rows=32)
    u = shard.memory_usage()
    per_series = u["total_bytes"] / S
    _emit("memory", "bytes_per_series_1h", per_series, "bytes",
          series=S, samples_per_series=T,
          dense_bytes=u["dense_bytes"], resident_bytes=u["resident_bytes"],
          dense_before_bytes=dense_before,
          series_per_gb=round((1 << 30) / per_series),
          compressed_bytes_per_sample=round(
              u["resident_bytes"] / (S * T), 3))


def bench_intsum(quick: bool):
    """Bit-packed int vector decode + scan-sum (ref: IntSumReadBenchmark,
    BasicFiloBenchmark — sum over an encoded int vector)."""
    from filodb_tpu.memory import intvec
    n = 100_000 if quick else 1_000_000
    vals = np.random.default_rng(1).integers(0, 1000, n).astype(np.int64)
    enc = intvec.pack_ints(vals)
    iters = 5 if quick else 20
    per = _time_it(lambda: int(intvec.unpack_ints(enc, n).sum()), iters)
    _emit("intsum", "decode_sum_values_per_sec", n / per, "values/s",
          width_bits=intvec.packed_width_bits(enc),
          bytes_per_value=round(len(enc) / n, 3))
    per = _time_it(lambda: intvec.pack_ints(vals), iters)
    _emit("intsum", "encode_values_per_sec", n / per, "values/s")


def bench_utf8(quick: bool):
    """UTF8 blob + dictionary string vector encode/decode
    (ref: UTF8StringBenchmark, DictStringBenchmark)."""
    from filodb_tpu.memory import utf8vec
    n = 10_000 if quick else 100_000
    vocab = [f"value-{i}".encode() for i in range(64)]
    col = [vocab[i % 64] for i in range(n)]
    iters = 3 if quick else 10
    per = _time_it(lambda: utf8vec.pack_utf8(col), iters)
    _emit("utf8", "blob_encode_strings_per_sec", n / per, "strings/s")
    enc = utf8vec.pack_dict_utf8(col)
    per = _time_it(lambda: utf8vec.pack_dict_utf8(col), iters)
    _emit("utf8", "dict_encode_strings_per_sec", n / per, "strings/s",
          bytes_per_string=round(len(enc) / n, 3),
          plain_bytes_per_string=round(len(utf8vec.pack_utf8(col)) / n, 3))
    per = _time_it(lambda: utf8vec.unpack_dict_utf8(enc), iters)
    _emit("utf8", "dict_decode_strings_per_sec", n / per, "strings/s")


def bench_downsample(quick: bool):
    """Batch downsampler throughput: raw persisted chunks -> 5m rollups
    (ref: spark-jobs/.../DownsamplerMain.scala — the 5th driver-designated
    target config in BASELINE.md)."""
    from filodb_tpu.core.memstore import TimeSeriesMemStore
    from filodb_tpu.core.store import InMemoryColumnStore, InMemoryMetaStore
    from filodb_tpu.downsample.batch_job import DownsamplerJob
    from filodb_tpu.ingest.generator import gauge_batch, counter_batch

    S, T = (200, 360) if quick else (2000, 720)
    raw_cs, raw_meta = InMemoryColumnStore(), InMemoryMetaStore()
    ms = TimeSeriesMemStore(column_store=raw_cs, meta_store=raw_meta)
    shard = ms.setup("prometheus", 0)
    shard.ingest(gauge_batch(S // 2, T, start_ms=START))
    shard.ingest(counter_batch(S // 2, T, start_ms=START))
    shard.flush_all_groups()
    samples = S * T
    iters = 2 if quick else 3
    times = []
    for _ in range(iters):
        job = DownsamplerJob(raw_cs, InMemoryColumnStore(), "prometheus",
                             resolutions=(300_000,))
        t0 = time.perf_counter()
        stats = job.run([0], START, START + T * 10_000)
        times.append(time.perf_counter() - t0)
    best = min(times)
    _emit("downsample", "raw_samples_per_sec", samples / best, "samples/s",
          series=S, parts=stats.parts_scanned,
          records_emitted=stats.records_emitted,
          chunks_written=stats.chunks_written)


def bench_downsample_dist(quick: bool):
    """Distributed downsampler rollup throughput vs worker count: shard
    splits over worker processes on the shared local store (ref:
    DownsamplerMain.scala:64-90 Spark fan-out over scan splits).  Reports
    samples rolled/s for 1 worker and N workers — on a multi-core host the
    scaling approaches N x; this 1-core CI box mostly shows the fan-out
    machinery overhead staying small."""
    import tempfile

    from filodb_tpu.core.memstore import TimeSeriesMemStore
    from filodb_tpu.core.store import InMemoryMetaStore
    from filodb_tpu.downsample.dist_job import DistributedDownsamplerJob
    from filodb_tpu.ingest.generator import counter_batch, gauge_batch
    from filodb_tpu.persist.localstore import LocalDiskColumnStore

    shards, S, T = (2, 100, 240) if quick else (6, 400, 720)
    tmp = tempfile.mkdtemp(prefix="bench_dsdist_")
    raw_root = os.path.join(tmp, "raw")
    cs = LocalDiskColumnStore(raw_root)
    ms = TimeSeriesMemStore(column_store=cs, meta_store=InMemoryMetaStore())
    for sh in range(shards):
        s = ms.setup("prometheus", sh)
        s.ingest(gauge_batch(S // 2, T, start_ms=START, seed=sh))
        s.ingest(counter_batch(S // 2, T, start_ms=START, seed=sh + 100))
        s.flush_all_groups()
    cs.close()
    samples = shards * S * T
    for workers in (1, 2 if quick else 4):
        ds_root = os.path.join(tmp, f"ds_w{workers}")
        job = DistributedDownsamplerJob(raw_root, ds_root, "prometheus",
                                        workers=workers,
                                        resolutions=(300_000,))
        t0 = time.perf_counter()
        stats = job.run(list(range(shards)), START, START + T * 10_000)
        dt = time.perf_counter() - t0
        _emit("downsample_dist", f"rolled_samples_per_sec_w{workers}",
              samples / dt, "samples/s", workers=workers, shards=shards,
              parts=stats.parts_scanned,
              records_emitted=stats.records_emitted)


def bench_dispatch(quick: bool):
    """Cross-node query dispatch QPS over the TCP wire (the Akka-remoting
    analogue; ref: exec/PlanDispatcher.scala:20-57, client/Serializer —
    plan subtree + serialized results over the socket)."""
    from filodb_tpu.ingest.generator import counter_batch
    from filodb_tpu.parallel.testcluster import make_two_node_cluster

    S, T = (100, 240) if quick else (400, 720)
    cluster = make_two_node_cluster([counter_batch(S, T, start_ms=START)])
    try:
        start_s = START // 1000
        q = 'sum by (_ns_)(rate(request_total[5m]))'
        run = lambda: cluster.engine.query_range(  # noqa: E731
            q, start_s + 600, 60, start_s + T * 10)
        assert run().error is None
        n = 20 if quick else 50
        per = _time_it(run, n)
        _emit("dispatch", "cross_node_queries_per_sec", 1.0 / per,
              "queries/s", shards=4, nodes=2, series=S)
    finally:
        cluster.stop()


def bench_persist(quick: bool):
    """Flush-to-disk and read-back throughput through the CRC-framed
    column store (the ChunkSink/RawChunkSource analogue of the reference's
    Cassandra write/read path, ref: CassandraColumnStore.scala:53-80)."""
    import shutil
    import tempfile

    from filodb_tpu.core.memstore import TimeSeriesMemStore
    from filodb_tpu.ingest.generator import counter_batch
    from filodb_tpu.persist.localstore import (LocalDiskColumnStore,
                                               LocalDiskMetaStore)
    S, T = (500, 360) if quick else (2000, 720)
    tmp = tempfile.mkdtemp(prefix="filodb-bench-persist-")
    try:
        cs = LocalDiskColumnStore(tmp)
        ms = TimeSeriesMemStore(column_store=cs,
                                meta_store=LocalDiskMetaStore(tmp))
        sh = ms.setup("prometheus", 0)
        sh.ingest(counter_batch(S, T, start_ms=START))
        t0 = time.perf_counter()
        sh.flush_all_groups()
        fl = time.perf_counter() - t0
        _emit("persist", "flush_samples_per_sec", S * T / fl, "samples/s",
              series=S)
        # COLD store for the read: a fresh instance pays the real
        # recovery frame scan, not the writer's warm in-memory index
        cold = LocalDiskColumnStore(tmp)
        t0 = time.perf_counter()
        n = 0
        for rec in cold.read_part_keys("prometheus", 0):
            for c in cold.read_chunks("prometheus", 0, rec.part_key,
                                      0, 1 << 62):
                n += c.info.num_rows
        rd = time.perf_counter() - t0
        _emit("persist", "read_samples_per_sec", n / rd, "samples/s",
              samples=n)
    finally:
        shutil.rmtree(tmp, ignore_errors=True)


BENCHES: Dict[str, Callable[[bool], None]] = {
    "dispatch": bench_dispatch,
    "persist": bench_persist,
    "downsample": bench_downsample,
    "downsample_dist": bench_downsample_dist,
    "ingestion": bench_ingestion,
    "intsum": bench_intsum,
    "utf8": bench_utf8,
    "memory": bench_memory,
    "encoding": bench_encoding,
    "index": bench_index,
    "gateway": bench_gateway,
    "query": bench_query,
    "query_hicard": bench_query_hicard,
    "dashboard_batch": bench_dashboard_batch,
    "query_odp": bench_query_odp,
    "partition_list": bench_partition_list,
    "query_under_ingest": bench_query_under_ingest,
    "histogram": bench_histogram,
    "hist_compression": bench_histogram_compression,
    "cardinality": bench_cardinality,
}


def main(argv: List[str] = None):
    ap = argparse.ArgumentParser(description="filodb-tpu benchmark suite")
    ap.add_argument("bench", nargs="?", choices=sorted(BENCHES),
                    help="run one benchmark (default: all)")
    ap.add_argument("--quick", action="store_true")
    from bench.platform import add_platform_arg, apply_platform
    add_platform_arg(ap)
    args = ap.parse_args(argv)
    apply_platform(args)
    targets = [args.bench] if args.bench else sorted(BENCHES)
    for name in targets:
        BENCHES[name](args.quick)


if __name__ == "__main__":
    main()
