"""engine.query_range_batch: a dashboard's panels over one window grid
merge compatible fused leaves into single kernel dispatches (multi-hot
epilogue, ops/pallas_fused.fused_leaf_agg_batch) with results identical
to the queries run one at a time.

The reference has no analogue (its iterator engine pays per-series cost
either way); this is the TPU-shaped answer to the round-4 on-chip
finding that fused leaf queries are dispatch-bound (doc/kernels.md)."""
import numpy as np
import pytest

from filodb_tpu.ingest.generator import counter_batch
from filodb_tpu.utils.metrics import registry

from test_query_engine import _mk_engine

START_MS = 1_600_000_000_000
START_S = START_MS // 1000
T = 240
END_S = START_S + T * 10

PANELS = [
    'sum(rate(request_total{_ws_="demo"}[5m])) by (_ns_)',
    'avg(rate(request_total{_ws_="demo"}[5m])) by (dc)',
    'sum(rate(request_total{_ws_="demo"}[5m])) by (_ns_, dc)',
    'count(rate(request_total{_ws_="demo"}[5m])) by (dc)',
    'min(rate(request_total{_ws_="demo"}[5m])) by (_ns_)',
    'max(rate(request_total{_ws_="demo"}[5m])) by (dc)',
]


@pytest.fixture()
def fused_env(monkeypatch):
    monkeypatch.setenv("FILODB_TPU_FUSED_INTERPRET", "1")


def _series_map(res):
    assert res.error is None, res.error
    return {tuple(sorted(k.labels_dict.items())): np.asarray(v)
            for k, _, v in res.series()}


def _mk(batches=None):
    return _mk_engine(batches or [counter_batch(60, T, start_ms=START_MS,
                                                resets=True)])


def test_batch_matches_individual_queries(fused_env):
    engine = _mk()
    args = (START_S + 600, 60, END_S)
    want = [_series_map(engine.query_range(q, *args)) for q in PANELS]
    dispatches0 = registry.counter("fused_batch_dispatches").value
    merged0 = registry.counter("fused_batch_merged_panels").value
    got = engine.query_range_batch(PANELS, *args)
    assert registry.counter("fused_batch_merged_panels").value - merged0 \
        >= 4, "sum/avg/count panels did not merge"
    # 6 panels, at most two dispatches: one group-mode (sum/avg/count and
    # ragged counts merged via disjoint-id multi-hot), one per-series
    # mode shared by min/max
    assert registry.counter("fused_batch_dispatches").value - dispatches0 \
        <= 2
    for q, w, g in zip(PANELS, want, got):
        g = _series_map(g)
        assert set(g) == set(w), q
        for k in w:
            np.testing.assert_allclose(g[k], w[k], rtol=2e-5, atol=1e-4,
                                       equal_nan=True, err_msg=q)


def test_batch_mixed_eligibility(fused_env):
    """Non-fusable and erroring queries ride along untouched."""
    engine = _mk()
    args = (START_S + 600, 60, END_S)
    queries = [PANELS[0],
               'rate(request_total{_ws_="demo"}[5m])',      # no agg: general
               'sum(nosuch_metric[5m])',                    # parse error
               'topk(2, rate(request_total{_ws_="demo"}[5m]))',  # candidate
               PANELS[1]]
    got = engine.query_range_batch(queries, *args)
    assert got[2].error is not None
    for i in (0, 1, 3, 4):
        w = _series_map(engine.query_range(queries[i], *args))
        g = _series_map(got[i])
        assert set(g) == set(w), queries[i]
        for k in w:
            np.testing.assert_allclose(g[k], w[k], rtol=2e-5, atol=1e-4,
                                       equal_nan=True, err_msg=queries[i])


def test_batch_general_path_without_fused(monkeypatch):
    """With the fused kernel unavailable (no TPU, interpret off), the
    batch API still answers every query via the general path."""
    monkeypatch.delenv("FILODB_TPU_FUSED_INTERPRET", raising=False)
    engine = _mk()
    args = (START_S + 600, 60, END_S)
    got = engine.query_range_batch(PANELS[:3], *args)
    for q, g in zip(PANELS[:3], got):
        w = _series_map(engine.query_range(q, *args))
        g = _series_map(g)
        assert set(g) == set(w)
        for k in w:
            np.testing.assert_allclose(g[k], w[k], rtol=1e-6,
                                       equal_nan=True)


def test_batch_ragged_matches_individual_queries(fused_env):
    """NaN scrape gaps (the production-normal shape): the merged ragged
    dispatch — multi-hot presence epilogue + disjoint-offset counts
    slicing in fused_leaf_agg_batch — must match per-query results."""
    from filodb_tpu.core.records import RecordBatch
    batch = counter_batch(48, T, start_ms=START_MS)
    vals = batch.columns["count"].copy()
    rng = np.random.default_rng(11)
    vals[rng.random(vals.shape) < 0.1] = np.nan      # scrape gaps
    batch = RecordBatch(batch.schema, batch.part_keys, batch.part_idx,
                        batch.timestamps, {"count": vals},
                        batch.bucket_les)
    engine = _mk([batch])
    args = (START_S + 600, 60, END_S)
    want = [_series_map(engine.query_range(q, *args)) for q in PANELS]
    merged0 = registry.counter("fused_batch_merged_panels").value
    got = engine.query_range_batch(PANELS, *args)
    assert registry.counter("fused_batch_merged_panels").value - merged0 \
        >= 4, "ragged panels did not merge"
    for q, w, g in zip(PANELS, want, got):
        g = _series_map(g)
        assert set(g) == set(w), q
        for k in w:
            np.testing.assert_allclose(g[k], w[k], rtol=2e-5, atol=1e-4,
                                       equal_nan=True, err_msg=q)


def test_batch_multi_shard(fused_env):
    """Two shards: each shard's leaves merge within their own working
    set (different mirrors -> different compat keys), and the stitched
    results still match individual queries."""
    engine = _mk_engine([counter_batch(60, T, start_ms=START_MS,
                                       resets=True)], num_shards=2)
    args = (START_S + 600, 60, END_S)
    queries = PANELS[:4]
    want = [_series_map(engine.query_range(q, *args)) for q in queries]
    merged0 = registry.counter("fused_batch_merged_panels").value
    got = engine.query_range_batch(queries, *args)
    # 4 panels x 2 shard-leaves each: both shards' sets merge
    assert registry.counter("fused_batch_merged_panels").value - merged0 \
        >= 6, "per-shard leaf sets did not merge"
    for q, w, g in zip(queries, want, got):
        g = _series_map(g)
        assert set(g) == set(w), q
        for k in w:
            np.testing.assert_allclose(g[k], w[k], rtol=2e-5, atol=1e-4,
                                       equal_nan=True, err_msg=q)


def test_coalescer_merges_concurrent_queries(fused_env):
    """Server-side micro-batching: concurrent query_range calls over one
    window grid coalesce into a single engine batch with per-query
    results identical to direct execution."""
    import threading

    from filodb_tpu.query.coalesce import QueryCoalescer
    engine = _mk()
    args = (START_S + 600, 60, END_S)
    for q in PANELS[:4]:
        assert engine.query_range(q, *args).error is None   # warm mirror
    co = QueryCoalescer(engine, window_s=0.25)
    merged0 = registry.counter("fused_batch_merged_panels").value
    results = {}
    errors = []

    def call(q):
        try:
            results[q] = _series_map(co.query_range(q, *args))
        except Exception as e:  # noqa: BLE001
            errors.append(e)

    threads = [threading.Thread(target=call, args=(q,))
               for q in PANELS[:4]]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=60)
    assert not errors, errors
    assert registry.counter("fused_batch_merged_panels").value - merged0 \
        >= 3, "concurrent queries did not coalesce"
    for q in PANELS[:4]:
        want = _series_map(engine.query_range(q, *args))
        assert set(results[q]) == set(want), q
        for k in want:
            np.testing.assert_allclose(results[q][k], want[k], rtol=2e-5,
                                       atol=1e-4, equal_nan=True,
                                       err_msg=q)


def test_coalescer_window_zero_is_passthrough(fused_env):
    from filodb_tpu.query.coalesce import QueryCoalescer
    engine = _mk()
    args = (START_S + 600, 60, END_S)
    co = QueryCoalescer(engine, window_s=0.0)
    got = _series_map(co.query_range(PANELS[0], *args))
    want = _series_map(engine.query_range(PANELS[0], *args))
    assert set(got) == set(want)


def test_coalescer_failed_batch_falls_back(fused_env, monkeypatch):
    """A batch-path failure must not lose queries that succeed alone."""
    from filodb_tpu.query.coalesce import QueryCoalescer
    engine = _mk()
    args = (START_S + 600, 60, END_S)

    def boom(*a, **k):
        raise RuntimeError("batch path down")

    monkeypatch.setattr(engine, "query_range_batch", boom)
    co = QueryCoalescer(engine, window_s=0.05)
    res = co.query_range(PANELS[0], *args)
    assert res.error is None
    assert _series_map(res)


def test_batch_histogram_quantile_dashboard(fused_env):
    """The canonical quantile dashboard: p50/p90/p99 panels over ONE
    bucket metric differ only above the leaf, so their leaf calls dedup
    to a single kernel run; a differently-grouped hist panel merges via
    slot offsets.  All results equal individual queries."""
    from filodb_tpu.ingest.generator import histogram_batch
    engine = _mk_engine([histogram_batch(24, T, start_ms=START_MS)])
    args = (START_S + 600, 60, END_S)
    panels = [
        'histogram_quantile(0.5, sum(rate(http_latency{_ws_="demo"}[5m])))',
        'histogram_quantile(0.9, sum(rate(http_latency{_ws_="demo"}[5m])))',
        'histogram_quantile(0.99, sum(rate(http_latency{_ws_="demo"}[5m])))',
        'histogram_quantile(0.9, '
        'sum(rate(http_latency{_ws_="demo"}[5m])) by (_ns_))',
    ]
    want = [_series_map(engine.query_range(q, *args)) for q in panels]
    dedup0 = registry.counter("fused_batch_deduped").value
    got = engine.query_range_batch(panels, *args)
    assert registry.counter("fused_batch_deduped").value - dedup0 >= 2, \
        "identical quantile-panel leaves did not dedup"
    for q, w, g in zip(panels, want, got):
        g = _series_map(g)
        assert set(g) == set(w), q
        for k in w:
            np.testing.assert_allclose(g[k], w[k], rtol=2e-5, atol=1e-4,
                                       equal_nan=True, err_msg=q)


def test_coalescer_separates_planner_params(fused_env):
    """Requests with different planner params (limits, spread) must land
    in separate coalescing groups — sharing a batch across them would
    apply one request's limits to another's query."""
    import threading

    from filodb_tpu.query.coalesce import QueryCoalescer
    from filodb_tpu.query.rangevector import PlannerParams
    engine = _mk()
    args = (START_S + 600, 60, END_S)
    engine.query_range(PANELS[0], *args)            # warm mirror
    co = QueryCoalescer(engine, window_s=0.2)
    results = {}

    def call(tag, pp):
        results[tag] = co.query_range(PANELS[0], *args, pp)

    tight = PlannerParams(sample_limit=1)           # must error
    loose = PlannerParams()
    ts = [threading.Thread(target=call, args=("tight", tight)),
          threading.Thread(target=call, args=("loose", loose))]
    for t in ts:
        t.start()
    for t in ts:
        t.join(timeout=60)
    assert results["loose"].error is None
    assert results["tight"].error is not None \
        and "limit" in results["tight"].error


# ----------------------------------------------- one device program a request
# (ISSUE 36: the shard leaves of a request share a plan object and a device)

def _fused_counts():
    return {c: registry.counter(c).value for c in (
        "fused_enqueues", "fused_enqueue_sets", "fused_enqueue_uploads",
        "leaf_fused_kernel", "leaf_fused_errors")}


def _delta(before):
    after = _fused_counts()
    return {c: after[c] - before[c] for c in before}


@pytest.mark.parametrize("query,per_series", [
    ('sum(rate(request_total{_ws_="demo"}[5m])) by (_ns_)', 0),
    ('avg(rate(request_total{_ws_="demo"}[5m])) by (dc)', 0),
    ('max(rate(request_total{_ws_="demo"}[5m])) by (dc)', 1),
], ids=["sum", "avg", "max-stays-a-call-a-shard"])
def test_a_four_shard_query_is_one_enqueue_of_four_sets(fused_env, query,
                                                        per_series):
    """Four shards, one request: the four leaves' group-mode runs are ONE
    jit call carrying four working sets; a min/max leaf keeps its own
    per-series run, one a shard.  The answer is the one the leaves give
    when each finishes alone (whole-expression compilation off)."""
    from filodb_tpu.config import settings
    engine = _mk_engine([counter_batch(120, T, start_ms=START_MS,
                                       resets=True)], num_shards=4)
    args = (START_S + 600, 60, END_S)
    engine.query_range(query, *args)            # mirrors, caches, compiles
    before = _fused_counts()
    got = _series_map(engine.query_range(query, *args))
    d = _delta(before)
    assert d["leaf_fused_kernel"] == 4 and d["leaf_fused_errors"] == 0
    if per_series:
        assert (d["fused_enqueues"], d["fused_enqueue_sets"]) == (4, 4)
    else:
        assert (d["fused_enqueues"], d["fused_enqueue_sets"]) == (1, 4)
        # the grid's plan has had its rows on the device since the warm
        # query's call (ISSUE 41)
        assert d["fused_enqueue_uploads"] == 0
    q = settings().query
    old, q.exprfuse_enabled = q.exprfuse_enabled, False
    try:
        before = _fused_counts()
        want = _series_map(engine.query_range(query, *args))
        d = _delta(before)
    finally:
        q.exprfuse_enabled = old
    assert (d["fused_enqueues"], d["fused_enqueue_sets"]) == (4, 4)
    assert set(got) == set(want)
    for k in want:
        assert got[k].tobytes() == want[k].tobytes(), k


def test_a_batch_of_panels_over_two_shards_is_one_call(fused_env):
    """query_range_batch: each shard's panels merge into one working set
    (merge_gid_cols, as before), and the two shards' sets ride ONE call."""
    engine = _mk_engine([counter_batch(60, T, start_ms=START_MS,
                                       resets=True)], num_shards=2)
    args = (START_S + 600, 60, END_S)
    queries = PANELS[:3]                        # sum, avg, sum: group mode
    want = [_series_map(r) for r in engine.query_range_batch(queries, *args)]
    before = _fused_counts()
    got = engine.query_range_batch(queries, *args)
    d = _delta(before)
    assert d["leaf_fused_kernel"] == 6
    assert (d["fused_enqueues"], d["fused_enqueue_sets"]) == (1, 2)
    assert d["fused_enqueue_uploads"] == 1      # the panels' offsets alone
    for w, g in zip(want, got):
        g = _series_map(g)
        assert set(g) == set(w)
        for k in w:
            assert g[k].tobytes() == w[k].tobytes()


def test_a_failed_merged_call_leaves_every_leaf_to_finish_alone(
        fused_env, monkeypatch):
    """A batch-level failure of the one call (here: its enqueue raises
    whenever it carries more than one set) loses nothing: every FusedCall
    stays parked and its leaf finishes standalone, one call a leaf."""
    from filodb_tpu.ops import pallas_fused as pf
    engine = _mk_engine([counter_batch(120, T, start_ms=START_MS,
                                       resets=True)], num_shards=4)
    args = (START_S + 600, 60, END_S)
    query = PANELS[0]
    want = _series_map(engine.query_range(query, *args))
    real = pf.FusedDispatch.enqueue
    seen = []

    def enqueue(self):
        seen.append(len(self))
        if len(self) > 1:
            raise RuntimeError("merged call down")
        return real(self)

    monkeypatch.setattr(pf.FusedDispatch, "enqueue", enqueue)
    before = _fused_counts()
    got = _series_map(engine.query_range(query, *args))
    d = _delta(before)
    assert seen == [4, 1, 1, 1, 1]
    assert (d["fused_enqueues"], d["fused_enqueue_sets"]) == (4, 4)
    assert d["leaf_fused_kernel"] == 4
    assert set(got) == set(want)
    for k in want:
        assert got[k].tobytes() == want[k].tobytes(), k
