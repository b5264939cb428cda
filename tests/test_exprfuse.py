"""Whole-expression device compilation (PR 17, query/exprfuse.py).

The compiler fuses plan TREES — binary ops with every match modifier,
nested agg chains, fixed-window subqueries, topk/bottomk/quantile —
into merged batched dispatches, with label matching resolved host-side
once and memoized.  The contract under test: every compiled shape is
BIT-identical to the same queries run one at a time with the compiler
off; unsupported or failing shapes degrade node-by-node (counted, never
an error); a killed query is filtered out BEFORE any fused dispatch;
the batch gather memo shares one scan + correction chain across a
dashboard's panels; cold persisted-tier leaves ride pushed
RemoteAggregateExec groups across the wire with their cold_tier
verdicts merged into the returned stats."""
import numpy as np
import pytest

from filodb_tpu.config import settings
from filodb_tpu.core.records import RecordBatch
from filodb_tpu.ingest.generator import (counter_batch, gauge_batch,
                                         histogram_batch)
from filodb_tpu.promql.parser import (TimeStepParams,
                                      query_range_to_logical_plan)
from filodb_tpu.query import exprfuse
from filodb_tpu.query.activequeries import CancellationToken
from filodb_tpu.query.rangevector import PlannerParams, QueryContext
from filodb_tpu.utils.metrics import registry

from test_query_engine import _mk_engine

START_MS = 1_600_000_000_000
START_S = START_MS // 1000
T = 180
END_S = START_S + T * 10
ARGS = (START_S + 900, 60, END_S)

# the required shapes from the ISSUE-17 battery: binary ops across the
# match modifiers (on/ignoring/group_left/bool/comparison filters), agg
# chains, a fixed-window subquery, the rank/sketch aggregations, plus
# ragged-NaN and histogram working sets
FIXED_PANELS = [
    'sum by (_ns_)(rate(request_total[5m]))',
    'avg by (dc)(rate(request_total[5m]))',
    'max by (_ns_)(max_over_time(heap_usage[5m]))',
    'count by (_ns_)(increase(request_total[10m]))',
    'sum by (_ns_)(rate(request_total[5m]))'
    ' / on (_ns_) count by (_ns_)(rate(request_total[5m]))',
    'sum by (_ns_, dc)(rate(request_total[5m]))'
    ' / on (_ns_) group_left sum by (_ns_)(rate(request_total[5m]))',
    'sum by (_ns_)(rate(request_total[5m]))'
    ' >= bool ignoring (dc) avg by (_ns_)(rate(request_total[5m]))',
    'sum by (_ns_)(max_over_time(heap_usage[5m]))'
    ' - on (_ns_) avg by (_ns_)(avg_over_time(heap_usage[5m]))',
    'sum by (_ns_)(rate(request_total[5m])) > 0.1',
    'max_over_time(sum by (_ns_)(rate(request_total[5m]))[10m:1m])',
    'topk(3, sum by (_ns_)(rate(request_total[5m])))',
    'bottomk(2, sum by (_ns_)(increase(request_total[5m])))',
    'quantile(0.9, rate(request_total[5m]))',
    'count_values("v", sum by (_ns_)(round(rate(request_total[5m]))))',
    'sum by (_ns_)(rate(ragged_total[5m]))',
    'avg by (dc)(last_over_time(ragged_total[5m]))',
    'histogram_quantile(0.9, sum by (_ns_)(rate(http_latency[5m])))',
]

# seeded fuzz: random (agg x fn x grouping x window x working set)
# combos — regenerated identically every run, so a failure names a
# reproducible query string
_AGGS = ["sum", "avg", "min", "max", "count"]
_CTR_FNS = ["rate", "increase"]
_GAUGE_FNS = ["max_over_time", "min_over_time", "avg_over_time",
              "last_over_time", "delta"]
_BYS = ["by (_ns_)", "by (dc)", "by (_ns_, dc)", ""]


def _fuzz_panels(n=12, seed=0x17):
    rng = np.random.default_rng(seed)
    out = []
    for _ in range(n):
        if rng.random() < 0.5:
            metric = "request_total" if rng.random() < 0.7 else "ragged_total"
            fn = str(rng.choice(_CTR_FNS))
        else:
            metric, fn = "heap_usage", str(rng.choice(_GAUGE_FNS))
        agg = str(rng.choice(_AGGS))
        by = str(rng.choice(_BYS))
        win = str(rng.choice(["5m", "10m"]))
        out.append(f'{agg} {by}({fn}({metric}[{win}]))')
    return out


def _batches():
    ctr = counter_batch(24, T, start_ms=START_MS, resets=True)
    ragged = counter_batch(16, T, start_ms=START_MS, metric="ragged_total",
                           seed=3)
    vals = ragged.columns["count"].copy()
    rng = np.random.default_rng(5)
    vals[rng.random(vals.shape) < 0.12] = np.nan       # scrape gaps
    ragged = RecordBatch(ragged.schema, ragged.part_keys, ragged.part_idx,
                         ragged.timestamps, {"count": vals},
                         ragged.bucket_les)
    return [ctr, ragged, gauge_batch(24, T, start_ms=START_MS),
            histogram_batch(12, T, start_ms=START_MS)]


@pytest.fixture(scope="module")
def engine():
    # two shards: every aggregation tree holds >= 2 eligible leaves, so
    # the single-query compiler path (min_leaves=2) engages too
    return _mk_engine(_batches(), num_shards=2)


@pytest.fixture()
def host_routed(monkeypatch):
    """The deterministic-comparison config the bench uses: no device
    mirror, host-routed fused leaves on any backend — the dense working
    sets evaluate through ops/hostleaf in f64 whether or not their
    gathers are memoized, so compiled-vs-off identity is exact."""
    monkeypatch.setattr(settings().query, "host_route_max_samples", 1 << 60)
    monkeypatch.setattr(settings().store, "device_mirror_enabled", False)
    monkeypatch.setenv("FILODB_TPU_FORCE_HOST_ROUTE", "1")


def _exact_map(res):
    """key -> (wends bytes, value bytes): equality means BIT-identical."""
    assert res.error is None, res.error
    out = {}
    for k, wends, v in res.series():
        out[tuple(sorted(k.labels_dict.items()))] = (
            np.asarray(wends).tobytes(), np.asarray(v).tobytes())
    return out


def _off_reference(engine, queries):
    q = settings().query
    prev = q.exprfuse_enabled
    q.exprfuse_enabled = False
    try:
        return [_exact_map(engine.query_range(s, *ARGS)) for s in queries]
    finally:
        q.exprfuse_enabled = prev


def test_battery_bit_identical(engine, host_routed):
    """The full battery — fixed shapes + seeded fuzz — compiled as ONE
    dashboard batch equals the compiler-off sequential run bitwise."""
    queries = FIXED_PANELS + _fuzz_panels()
    want = _off_reference(engine, queries)
    fused0 = registry.counter("query_exprfuse", verdict="fused").value
    got = engine.query_range_batch(queries, *ARGS)
    assert registry.counter("query_exprfuse", verdict="fused").value \
        > fused0, "no leaf compiled — the battery never engaged exprfuse"
    for q, w, g in zip(queries, want, got):
        g = _exact_map(g)
        assert set(g) == set(w), q
        for k in w:
            assert g[k] == w[k], f"not bit-identical: {q} {dict(k)}"


def test_single_query_tree_compiles_bit_identical(engine, host_routed):
    """min_leaves=2: a multi-leaf single query (2 shards, binary join)
    compiles through exec_logical_plan and still equals compiler-off."""
    q = ('sum by (_ns_)(rate(request_total[5m]))'
         ' / on (_ns_) count by (_ns_)(rate(request_total[5m]))')
    want = _off_reference(engine, [q])[0]
    fused0 = registry.counter("query_exprfuse", verdict="fused").value
    got = _exact_map(engine.query_range(q, *ARGS))
    assert registry.counter("query_exprfuse", verdict="fused").value > fused0
    assert got == want


def test_forced_degradation_bit_identical(engine, host_routed, monkeypatch):
    """A preflight that BLOWS UP on every leaf must degrade node-by-node
    — counted verdicts, no error, results still bit-identical."""
    from filodb_tpu.query.leafexec import MultiSchemaPartitionsExec
    queries = FIXED_PANELS[:6]
    want = _off_reference(engine, queries)

    def boom(self, source):
        raise RuntimeError("forced preflight failure")

    monkeypatch.setattr(MultiSchemaPartitionsExec, "prepare_fused", boom)
    deg0 = registry.counter("query_exprfuse", verdict="degraded").value
    got = engine.query_range_batch(queries, *ARGS)
    assert registry.counter("query_exprfuse", verdict="degraded").value \
        > deg0, "forced failures were not counted as degradations"
    for q, w, g in zip(queries, want, got):
        assert _exact_map(g) == w, q


def test_stats_surface_verdicts(engine, host_routed):
    res = engine.query_range_batch([FIXED_PANELS[0], FIXED_PANELS[1]],
                                   *ARGS)
    total = sum(r.stats.exprfuse_fused + r.stats.exprfuse_degraded
                for r in res)
    assert total > 0
    d = res[0].stats.to_dict()
    assert "exprfuse" in d
    assert set(d["exprfuse"]) == {"fused", "degraded"}


def test_disabled_config_never_engages(engine, monkeypatch):
    monkeypatch.setattr(settings().query, "exprfuse_enabled", False)
    f0 = registry.counter("query_exprfuse", verdict="fused").value
    d0 = registry.counter("query_exprfuse", verdict="degraded").value
    res = engine.query_range_batch(FIXED_PANELS[:3], *ARGS)
    assert all(r.error is None for r in res)
    assert registry.counter("query_exprfuse", verdict="fused").value == f0
    assert registry.counter("query_exprfuse", verdict="degraded").value == d0


def test_kill_token_checked_before_fused_dispatch(engine, monkeypatch):
    """PR-13 contract: a query cancelled between prepare and finish is
    filtered out of the merged dispatch — the kernel never runs for it
    and execution surfaces the structured query_canceled error."""
    monkeypatch.setenv("FILODB_TPU_FUSED_INTERPRET", "1")
    plan = query_range_to_logical_plan(
        FIXED_PANELS[0], TimeStepParams(*ARGS))
    ctx = QueryContext(query_id="kill-drill")
    ctx.cancel = CancellationToken()
    ep = engine.planner.materialize(plan, ctx)
    comp = exprfuse.compile_tree(ep, engine.source)
    assert comp is not None and comp.calls, "no fused calls prepared"
    ctx.cancel.cancel("admin", "kill drill")
    d0 = registry.counter("fused_batch_dispatches").value
    exprfuse.finish_prepared(comp.calls)
    assert registry.counter("fused_batch_dispatches").value == d0, \
        "killed query's work reached a fused dispatch"
    res = ep.execute(engine.source)
    assert res.error is not None and res.error.startswith("query_canceled")


def test_batch_gather_memo_shares_scans(engine, host_routed):
    """Panels over one working set scan + counter-correct it ONCE under
    the batch's memo scope; outside a batch the memo is inert."""
    queries = [
        'sum by (_ns_)(rate(request_total[5m]))',
        'avg by (dc)(rate(request_total[5m]))',
        'count by (_ns_)(rate(request_total[5m]))',
        'max by (_ns_)(rate(request_total[5m]))',
    ]
    engine.query_range_batch(queries, *ARGS)        # warm plans/caches
    h0 = registry.counter("leaf_gather_memo_hits").value
    res = engine.query_range_batch(queries, *ARGS)
    assert all(r.error is None for r in res)
    assert registry.counter("leaf_gather_memo_hits").value > h0, \
        "shared working set was re-gathered per panel"
    h1 = registry.counter("leaf_gather_memo_hits").value
    assert engine.query_range(queries[0], *ARGS).error is None
    assert registry.counter("leaf_gather_memo_hits").value == h1, \
        "memo engaged outside a batch scope"


def test_join_index_map_cache_hits(engine, host_routed):
    """The resolved binary-join label match is memoized on the operands'
    working-set identity: a dashboard re-poll of the same join skips the
    per-series dict matching."""
    q = ('max by (_ns_)(rate(request_total[5m]))'
         ' - on (_ns_) min by (_ns_)(rate(request_total[5m]))')
    first = _exact_map(engine.query_range(q, *ARGS))
    h0 = registry.counter("exprfuse_join_cache", verdict="hit").value
    second = _exact_map(engine.query_range(q, *ARGS))
    assert registry.counter("exprfuse_join_cache", verdict="hit").value \
        > h0, "re-polled join did not hit the index-map cache"
    assert second == first


# ------------------------------------------------- cold-leaf pushdown

COLD_DS = "exprfuse-cold"
WINDOW_MS = 3600 * 1000
CT0 = START_MS - (START_MS % WINDOW_MS)
C_INTERVAL = 60_000
C_WINDOWS = 3
C_NS = C_WINDOWS * WINDOW_MS // C_INTERVAL
C_SERIES = 8


@pytest.fixture()
def cold_cluster(tmp_path):
    """One data node serving a persisted-segment tier over TCP, plus a
    coordinator whose planner materializes SelectPersistedSegmentsExec
    leaves with remote dispatchers — the cold-pushdown shape."""
    from filodb_tpu.core.devicecache import ColdSegmentCache
    from filodb_tpu.core.memstore import TimeSeriesMemStore
    from filodb_tpu.core.partkey import PartKey
    from filodb_tpu.parallel.shardmapper import ShardEvent, ShardMapper
    from filodb_tpu.parallel.transport import (NodeQueryServer,
                                               RemoteNodeDispatcher)
    from filodb_tpu.persist.compactor import SegmentCompactor
    from filodb_tpu.persist.localstore import LocalDiskColumnStore
    from filodb_tpu.persist.segments import PersistedTier, SegmentStore
    from filodb_tpu.query.engine import QueryEngine
    from filodb_tpu.query.planners import PersistedClusterPlanner

    grid = CT0 + np.arange(C_NS, dtype=np.int64) * C_INTERVAL
    pks = [PartKey("cold_gauge", (("inst", f"i{i}"), ("_ws_", "w"),
                                  ("_ns_", f"n{i % 2}")))
           for i in range(C_SERIES)]
    # integer-valued samples: partial components are exactly
    # representable, so pushdown on/off must agree bitwise
    vals = (np.arange(C_SERIES)[:, None] * 50.0
            + (np.arange(C_NS) % 11)[None, :])
    cs = LocalDiskColumnStore(str(tmp_path))
    ms_full = TimeSeriesMemStore(column_store=cs)
    sh = ms_full.setup(COLD_DS, 0)
    sh.ingest_columns("gauge", pks,
                      np.broadcast_to(grid, (C_SERIES, C_NS)),
                      {"value": vals})
    sh.flush_all_groups()
    seg_store = SegmentStore(str(tmp_path))
    comp = SegmentCompactor(cs, seg_store, COLD_DS, 1, window_ms=WINDOW_MS,
                            closed_lag_ms=0)
    assert comp.compact_all(now_ms=int(grid[-1]) + 10 * WINDOW_MS) \
        == C_WINDOWS
    tier = PersistedTier(seg_store, COLD_DS, 1,
                         ColdSegmentCache(64 << 20, use_placer=False))
    srv = NodeQueryServer(TimeSeriesMemStore()).start()
    mapper = ShardMapper(1)
    mapper.update_from_event(
        ShardEvent("IngestionStarted", COLD_DS, 0, "remote"))
    planner = PersistedClusterPlanner(
        COLD_DS, mapper, tier,
        dispatcher_factory=lambda s: RemoteNodeDispatcher(*srv.address))
    eng = QueryEngine(COLD_DS, TimeSeriesMemStore(), mapper,
                      planner=planner)
    yield eng
    srv.stop()


def test_cold_leaves_push_with_tier_verdicts(cold_cluster):
    """SelectPersistedSegmentsExec leaves ride a pushed
    RemoteAggregateExec group: only the dataset name crosses the wire
    (the decoder rebinds the node-local tier), the pushed partial comes
    back bit-identical to the per-shard path, and the leaf's cold_tier
    verdict arrives merged into the coordinator's stats."""
    q = 'sum by (_ns_)(max_over_time(cold_gauge[5m]))'
    args = (CT0 // 1000 + 900, 60, (CT0 + C_WINDOWS * WINDOW_MS) // 1000)
    p0 = registry.counter("query_pushdown", verdict="pushed").value
    res = cold_cluster.query_range(q, *args)
    pushed = _exact_map(res)
    assert registry.counter("query_pushdown", verdict="pushed").value > p0
    assert res.stats.pushdown_pushed >= 1
    assert res.stats.cold_tier in ("cold_hit", "cold_paged"), \
        "cold-leaf tier verdict did not ride back with the partial"
    flat = _exact_map(cold_cluster.query_range(
        q, *args, PlannerParams(aggregation_pushdown=False)))
    assert pushed == flat


def test_cold_leaf_serialize_roundtrip(cold_cluster):
    """The wire form of a cold leaf carries only the dataset-name tier
    marker and rebinds to the registered tier on decode."""
    from filodb_tpu.parallel import serialize
    from filodb_tpu.persist.segments import query_tier
    from filodb_tpu.query.exec import SelectPersistedSegmentsExec

    tier = query_tier(COLD_DS)
    assert tier is not None
    leaf = SelectPersistedSegmentsExec(
        QueryContext(query_id="rt"), COLD_DS, 0, [], CT0,
        CT0 + WINDOW_MS, tier)
    blob = serialize.dumps(leaf)
    back = serialize.loads(blob)
    assert isinstance(back, SelectPersistedSegmentsExec)
    assert back.tier is tier
