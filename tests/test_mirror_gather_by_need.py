"""The mirror's row gather runs when somebody reads it (ISSUE 34).

`DeviceMirror.gather_cached` hands a leaf a `MirrorGather`: shapes from the
snapshot and the row count, no device work.  A `RawBlock` field takes its
rows out of the mirror on its first read.  A fused leaf whose padded values
are cached reads none; on a miss it reads `values` and `vbase`, never
`ts_off`; the general path and `_fused_minmax` read what they always read
and answer bit for bit what an eager gather answers."""
import dataclasses

import jax.numpy as jnp
import numpy as np
import pytest

from filodb_tpu.core.devicecache import (DeferredRows, DeviceMirror,
                                         MirrorGather)
from filodb_tpu.core.memstore import TimeSeriesMemStore
from filodb_tpu.ingest.generator import (counter_batch, gauge_batch,
                                         histogram_batch)
from filodb_tpu.query.execbase import (RawBlock, _FUSED_CACHE_LOCK,
                                       _FUSED_VALS_CACHE)
from filodb_tpu.utils.metrics import registry

from test_query_engine import _mk_engine

START_MS = 1_600_000_000_000
START_S = START_MS // 1000
T = 240
ARGS = (START_S + 600, 60, START_S + T * 10)
ARRAYS = ("ts_off", "values", "vbase", "other")

RATE = 'sum(rate(request_total{_ws_="demo"}[5m])) by (_ns_)'
SUM_OT = 'sum(sum_over_time(heap_usage{_ws_="demo"}[5m])) by (_ns_)'
QUANTILE = ('histogram_quantile(0.9, sum(rate(http_latency{_ws_="demo"}[5m]))'
            ' by (_ns_))')
BATCHES = {
    RATE: lambda: counter_batch(40, T, start_ms=START_MS, resets=True),
    SUM_OT: lambda: gauge_batch(40, T, start_ms=START_MS),
    QUANTILE: lambda: histogram_batch(24, T, start_ms=START_MS),
}


@pytest.fixture()
def fused_env(monkeypatch):
    monkeypatch.setenv("FILODB_TPU_FUSED_INTERPRET", "1")


def takes():
    return {a: registry.counter("mirror_gather_takes", array=a).value
            for a in ARRAYS}


def handles():
    return registry.counter("mirror_gather_deferred").value


def moved(before):
    return {a: n - before[a] for a, n in takes().items() if n != before[a]}


def forget_padded_values():
    with _FUSED_CACHE_LOCK:
        _FUSED_VALS_CACHE.clear()


def answer(res):
    assert res.error is None, res.error
    return {tuple(sorted(k.labels_dict.items())): np.asarray(v)
            for k, _, v in res.series()}


def same(got, want):
    assert set(got) == set(want) and got
    for k in want:
        np.testing.assert_array_equal(got[k], want[k])


def eager(monkeypatch):
    """What the program did before: every array a block carries is taken
    when the block is made."""
    by_need = MirrorGather.deferred

    def taken_now(self, array, col=None):
        lazy = by_need(self, array, col)
        return None if lazy is None else lazy.resolve()

    monkeypatch.setattr(MirrorGather, "deferred", taken_now)


def mirrored_store(kind):
    ms = TimeSeriesMemStore()
    sh = ms.setup("prometheus", 0)
    sh.ingest(histogram_batch(12, 60, start_ms=START_MS) if kind == "hist"
              else counter_batch(16, 60, start_ms=START_MS, resets=True))
    (store,) = sh.stores.values()
    mirror = DeviceMirror()
    with sh._write_locked("test"):
        assert mirror.ensure_fresh(store)
    return sh, store, mirror


def every_array(snap):
    yield "ts_off", None, snap.ts_off
    for name, arr in snap.cols.items():
        yield "values", name, arr
    for name, arr in snap.vbases.items():
        yield "vbase", name, arr


SNAPSHOTS = [(k, r) for k in ("scalar", "hist") for r in ("whole", "subset")]


def rows_of(store, which):
    return (np.arange(store.num_series) if which == "whole"
            else np.array([7, 2, 5], np.int64))


@pytest.mark.parametrize("kind,which", SNAPSHOTS)
def test_handle_knows_every_shape_without_a_take(kind, which):
    _, store, mirror = mirrored_store(kind)
    snap = mirror.snapshot()
    rows = rows_of(store, which)
    t0, h0 = takes(), handles()
    got = mirror.gather_cached(rows, snap)
    assert got.base_ms == snap.base_ms and got.snap is snap
    ndims = set()
    for array, col, src in every_array(snap):
        want = jnp.take(src, jnp.asarray(rows.astype(np.int32)), axis=0)
        assert got.spec(array, col) == (want.shape, want.dtype)
        lazy = got.deferred(array, col)
        assert (lazy.shape, lazy.dtype, lazy.ndim) \
            == (want.shape, want.dtype, want.ndim)
        ndims.add((array, want.ndim))
    assert ("values", 3 if kind == "hist" else 2) in ndims
    assert got.deferred("vbase", "no_such_column") is None
    assert moved(t0) == {} and handles() - h0 == 1


@pytest.mark.parametrize("kind,which", SNAPSHOTS)
def test_each_array_read_is_the_snapshots_take(kind, which):
    _, store, mirror = mirrored_store(kind)
    snap = mirror.snapshot()
    rows = rows_of(store, which)
    idx = jnp.asarray(rows.astype(np.int32))
    got = mirror.gather_cached(rows, snap)
    n = 0
    for array, col, src in every_array(snap):
        t0 = takes()
        mine = got.read(array, col)
        np.testing.assert_array_equal(np.asarray(mine),
                                      np.asarray(jnp.take(src, idx, axis=0)))
        assert mine.dtype == src.dtype
        # that array only, once: a second read is the first one's result
        assert moved(t0) == {"ts_off" if array == "ts_off" else "other": 1}
        assert got.read(array, col) is mine
        assert got.deferred(array, col).resolve() is mine
        n += 1
        assert sum(takes().values()) - sum(t0.values()) == 1
    assert n == 1 + 2 * len(snap.cols)


def test_a_failed_take_is_not_remembered(monkeypatch):
    _, store, mirror = mirrored_store("scalar")
    got = mirror.gather_cached(np.arange(4), mirror.snapshot())
    real, calls = jnp.take, []

    def take_fails_once(*a, **kw):
        calls.append(1)
        if len(calls) == 1:
            raise RuntimeError("device lost")
        return real(*a, **kw)

    monkeypatch.setattr(jnp, "take", take_fails_once)
    t0 = takes()
    with pytest.raises(RuntimeError):
        got.read("ts_off")
    assert moved(t0) == {}
    assert got.read("ts_off").shape == (4, store.time_used)
    assert moved(t0) == {"ts_off": 1} and len(calls) == 2


def test_a_refresh_after_the_handle_does_not_change_what_is_read():
    """The handle reads the snapshot the leaf validated, not the one a
    refresh published before the first read."""
    sh, store, mirror = mirrored_store("scalar")
    snap = mirror.snapshot()
    rows = np.arange(store.num_series)
    got = mirror.gather_cached(rows, snap)
    name = store.schema.value_column
    want = np.asarray(jnp.take(snap.cols[name], jnp.asarray(
        rows.astype(np.int32)), axis=0))
    more = counter_batch(16, 90, start_ms=START_MS, resets=True)
    late = more.timestamps >= START_MS + 60 * 10_000
    sh.ingest(dataclasses.replace(
        more, part_idx=more.part_idx[late], timestamps=more.timestamps[late],
        columns={c: v[late] for c, v in more.columns.items()}))
    with sh._write_locked("test"):
        assert mirror.ensure_fresh(store)
    newer = mirror.snapshot()
    assert newer is not snap and newer.t_used > snap.t_used
    assert got.spec("values", name)[0] == want.shape
    np.testing.assert_array_equal(np.asarray(got.read("values", name)), want)
    assert mirror.gather_cached(rows).spec("values", name)[0] \
        == (len(rows), newer.t_used)


def test_rawblock_reads_what_it_was_given_and_defers_what_it_can():
    _, store, mirror = mirrored_store("hist")
    name = store.schema.value_column
    ts, vals, vb = np.zeros((3, 5), np.int32), np.ones((3, 5)), np.ones(3)
    plain = RawBlock([], ts, vals, 0, vbase=vb)
    assert plain.ts_off is ts and plain.values is vals and plain.vbase is vb
    assert plain.values_shape == (3, 5)
    assert RawBlock([], ts, vals, 0).vbase is None
    assert {"ts_off", "values", "vbase"} \
        <= {f.name for f in dataclasses.fields(RawBlock)}
    got = mirror.gather_cached(np.array([1, 3]), mirror.snapshot())
    t0 = takes()
    blk = RawBlock([], got.deferred("ts_off"), got.deferred("values", name),
                   got.base_ms, vbase=got.deferred("vbase", name))
    assert blk.values_shape == (2, store.time_used, store.num_buckets)
    assert moved(t0) == {}
    v = blk.values
    assert v.shape == blk.values_shape and blk.values is v
    assert moved(t0) == {"values": 1}
    again = dataclasses.replace(blk, bucket_les=np.arange(3.0))
    assert again.values is v and again.vbase.shape == (2, store.num_buckets)
    assert moved(t0) == {"values": 1, "vbase": 1, "ts_off": 1}


@pytest.mark.parametrize("promql", [RATE, SUM_OT, QUANTILE],
                         ids=["rate", "sum_over_time", "histogram_quantile"])
def test_a_fused_hit_launches_no_take(fused_env, promql):
    engine = _mk_engine([BATCHES[promql]()])
    fused = registry.counter("leaf_fused_kernel")
    forget_padded_values()
    t0 = takes()
    first = answer(engine.query_range(promql, *ARGS))
    # the miss reads the value column and its base, and not the offsets
    assert moved(t0) == {"values": 1, "vbase": 1}
    t1, h1, f1 = takes(), handles(), fused.value
    same(answer(engine.query_range(promql, *ARGS)), first)
    assert fused.value - f1 == 1, "the fused leaf did not engage"
    assert moved(t1) == {} and handles() - h1 == 1


@pytest.mark.parametrize("case", ["not_fusable", "grid_not_uniform"])
def test_general_path_takes_what_it_reads_and_answers_as_before(
        fused_env, monkeypatch, case):
    if case == "not_fusable":
        engine = _mk_engine([BATCHES[SUM_OT]()])
        promql = 'sum(stddev_over_time(heap_usage{_ws_="demo"}[5m])) by (dc)'
    else:
        engine = _mk_engine([
            gauge_batch(20, T, start_ms=START_MS),
            # (a second scrape interval: rows that merely start late or
            # hold fewer samples are placed on the grid's slots, ISSUE 42)
            gauge_batch(10, T // 2, start_ms=START_MS + 5_000,
                        step_ms=15_000, metric="other_gauge", seed=5)])
        promql = 'sum(sum_over_time(other_gauge{_ws_="demo"}[5m])) by (dc)'
    general = registry.counter("leaf_general_path")
    engine.query_range(promql, *ARGS)                   # builds the mirror
    t0, h0, g0 = takes(), handles(), general.value
    got = answer(engine.query_range(promql, *ARGS))
    assert general.value - g0 == 1
    assert moved(t0) == {"ts_off": 1, "values": 1, "vbase": 1}
    assert handles() - h0 == 1
    eager(monkeypatch)
    same(got, answer(engine.query_range(promql, *ARGS)))


def test_fused_minmax_takes_values_and_vbase(monkeypatch):
    engine = _mk_engine([BATCHES[SUM_OT]()])
    promql = 'max(max_over_time(heap_usage{_ws_="demo"}[5m])) by (_ns_)'
    minmax = registry.counter("leaf_fused_minmax")
    engine.query_range(promql, *ARGS)
    t0, m0 = takes(), minmax.value
    got = answer(engine.query_range(promql, *ARGS))
    assert minmax.value - m0 == 1
    assert moved(t0) == {"values": 1, "vbase": 1}
    eager(monkeypatch)
    same(got, answer(engine.query_range(promql, *ARGS)))


def test_batch_path_parks_the_handle_until_phase_three(fused_env):
    """`prepare_fused` parks the block, `inject_fused` hands the merged
    kernel's partial in, phase 3 picks both up: three panels over one
    working set take its values once, on the miss, and nothing after."""
    engine = _mk_engine([BATCHES[RATE]()])
    panels = [RATE, 'avg(rate(request_total{_ws_="demo"}[5m])) by (dc)',
              'sum(rate(request_total{_ws_="demo"}[5m])) by (_ns_, dc)']
    want = [answer(engine.query_range(q, *ARGS)) for q in panels]
    forget_padded_values()
    t0, h0 = takes(), handles()
    for got, w in zip(engine.query_range_batch(panels, *ARGS), want):
        same(answer(got), w)
    assert moved(t0) == {"values": 1, "vbase": 1}
    assert handles() - h0 == len(panels)
    t1 = takes()
    for got, w in zip(engine.query_range_batch(panels, *ARGS), want):
        same(answer(got), w)
    assert moved(t1) == {}


def test_a_leaf_keeps_its_handle_no_longer_than_its_execution(fused_env):
    from filodb_tpu.query.leafexec import MultiSchemaPartitionsExec
    engine = _mk_engine([BATCHES[RATE]()])
    seen = []
    real = MultiSchemaPartitionsExec._prepare_fused

    def spy(self, source):
        out = real(self, source)
        seen.append(self)
        return out

    MultiSchemaPartitionsExec._prepare_fused = spy
    try:
        answer(engine.query_range_batch([RATE], *ARGS)[0])
    finally:
        MultiSchemaPartitionsExec._prepare_fused = real
    assert seen and all(leaf._prefused is None for leaf in seen)
    with _FUSED_CACHE_LOCK:
        held = list(_FUSED_VALS_CACHE.values())
    assert held and not any(isinstance(x, (MirrorGather, DeferredRows))
                            for entry in held for x in entry)
