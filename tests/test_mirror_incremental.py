"""Incremental device-mirror updates: append-only ingest must produce a
mirror numerically identical to a from-scratch upload (transfer O(new
samples)); anything that rearranges cells must fall back to a full
refresh (ref: BlockManager working-set semantics; devicecache.py)."""
import numpy as np
import pytest

from filodb_tpu.core.devicecache import DeviceMirror
from filodb_tpu.core.memstore import TimeSeriesMemStore
from filodb_tpu.core.records import RecordBatch
from filodb_tpu.ingest.generator import counter_batch, histogram_batch
from filodb_tpu.utils.metrics import registry

START = 1_600_000_000_000


def _slices(batch, bounds):
    for lo_i, hi_i in bounds:
        lo = START + lo_i * 10_000
        hi = START + hi_i * 10_000
        k = (batch.timestamps >= lo) & (batch.timestamps < hi)
        yield RecordBatch(batch.schema, batch.part_keys, batch.part_idx[k],
                          batch.timestamps[k],
                          {kk: v[k] for kk, v in batch.columns.items()},
                          batch.bucket_les)


def _mirror_state(mirror, store):
    snap = mirror._snap
    out = {"ts": np.asarray(snap.ts_off)}
    packed = None
    if snap.interval:
        # a PLACED snapshot (columns are the scrape grid's slots, NaN where
        # a row holds no sample: ISSUE 42) holds the samples an unplaced one
        # holds: each row's, packed to the front, compare as before
        from filodb_tpu.ops.timewindow import PAD_TS
        held = np.isfinite(np.asarray(next(iter(snap.cols.values()))))
        packed = np.argsort(~held, axis=1, kind="stable")
        width = max(store.time_used, 1)
        out["ts"] = np.take_along_axis(
            np.where(held, out["ts"], PAD_TS), packed, axis=1)[:, :width]
    for n, a in snap.cols.items():
        out[f"col_{n}"] = np.asarray(a)
        if packed is not None:
            out[f"col_{n}"] = np.take_along_axis(
                out[f"col_{n}"], packed, axis=1)[:, :width]
        # reconstruct ABSOLUTE values: rebased + vbase (bases may differ
        # between incremental and full paths for fresh rows; absolutes
        # must not)
        vb = np.asarray(snap.vbases[n])
        out[f"abs_{n}"] = out[f"col_{n}"] + (
            vb[:, None, :] if out[f"col_{n}"].ndim == 3 else vb[:, None])
    return out


def _assert_equivalent(store, mirror):
    """Mirror state after incremental updates == a fresh full upload."""
    fresh = DeviceMirror()
    assert fresh._refresh(store)
    a, b = _mirror_state(mirror, store), _mirror_state(fresh, store)
    np.testing.assert_array_equal(a["ts"], b["ts"])
    for k in b:
        if k.startswith("abs_"):
            np.testing.assert_allclose(a[k], b[k], rtol=1e-6, atol=1e-6,
                                       equal_nan=True)


def _incr_count():
    return registry.counter("device_mirror_incremental").value


def test_append_only_counter_updates_incrementally():
    ms = TimeSeriesMemStore()
    sh = ms.setup("prometheus", 0)
    # resets=True exercises tail reset-correction continuation
    full = counter_batch(30, 200, start_ms=START, resets=True)
    slices = list(_slices(full, [(0, 50), (50, 90), (90, 140), (140, 200)]))
    sh.ingest(slices[0], offset=0)
    store = sh.stores["prom-counter"]
    mirror = DeviceMirror()
    assert mirror.ensure_fresh(store)
    before = _incr_count()
    for i, sl in enumerate(slices[1:], 1):
        sh.ingest(sl, offset=i)
        assert mirror.ensure_fresh(store)
        _assert_equivalent(store, mirror)
    assert _incr_count() - before >= 3, "appends did not take the fast path"


def test_new_series_and_time_growth():
    ms = TimeSeriesMemStore()
    sh = ms.setup("prometheus", 0)
    base = counter_batch(10, 60, start_ms=START)
    sh.ingest(base, offset=0)
    store = sh.stores["prom-counter"]
    mirror = DeviceMirror()
    assert mirror.ensure_fresh(store)
    # NEW series appear later (S growth) while old ones extend (T growth);
    # sized so new cells stay under the incremental threshold
    from filodb_tpu.core.partkey import PartKey
    ext = counter_batch(10, 90, start_ms=START)
    k = ext.timestamps >= START + 60 * 10_000
    sh.ingest(RecordBatch(ext.schema, ext.part_keys, ext.part_idx[k],
                          ext.timestamps[k],
                          {kk: v[k] for kk, v in ext.columns.items()},
                          ext.bucket_les), offset=1)
    more = counter_batch(3, 90, start_ms=START, seed=9)
    keys = [PartKey.make(pk.metric, {**dict(pk.tags), "inst": f"n{i}"})
            for i, pk in enumerate(more.part_keys)]
    more = RecordBatch(more.schema, keys, more.part_idx, more.timestamps,
                       more.columns, more.bucket_les)
    sh.ingest(more, offset=2)
    before = _incr_count()
    assert mirror.ensure_fresh(store)
    assert _incr_count() == before + 1
    _assert_equivalent(store, mirror)

    # a growth burst past the threshold correctly chooses the full upload
    burst = counter_batch(40, 400, start_ms=START, seed=11)
    keys2 = [PartKey.make(pk.metric, {**dict(pk.tags), "inst": f"b{i}"})
             for i, pk in enumerate(burst.part_keys)]
    sh.ingest(RecordBatch(burst.schema, keys2, burst.part_idx,
                          burst.timestamps, burst.columns,
                          burst.bucket_les), offset=3)
    before = _incr_count()
    assert mirror.ensure_fresh(store)
    assert _incr_count() == before, "burst should take the full path"
    _assert_equivalent(store, mirror)


def test_histogram_incremental():
    ms = TimeSeriesMemStore()
    sh = ms.setup("prometheus", 0)
    full = histogram_batch(8, 120, start_ms=START)
    mirror = DeviceMirror()
    before = _incr_count()
    # slice sizes comfortably below the 50% threshold so the [R, L, B]
    # seeded-correction path is guaranteed exercised, not silently skipped
    for i, sl in enumerate(_slices(full, [(0, 60), (60, 90), (90, 120)])):
        sh.ingest(sl, offset=i)
        store = sh.stores["prom-histogram"]
        assert mirror.ensure_fresh(store)
        _assert_equivalent(store, mirror)
    assert _incr_count() - before >= 2, \
        "histogram appends did not take the incremental path"


def test_all_nan_row_gets_real_vbase_on_first_finite_append():
    """A row whose first upload had no finite values (vbase 0) must get a
    REAL base from its first finite append — large counters would
    otherwise land on device un-rebased and lose their f32 deltas."""
    ms = TimeSeriesMemStore()
    sh = ms.setup("prometheus", 0)
    base = counter_batch(4, 40, start_ms=START)
    nan_cols = {k: np.full_like(v, np.nan) for k, v in base.columns.items()}
    sh.ingest(RecordBatch(base.schema, base.part_keys, base.part_idx,
                          base.timestamps, nan_cols, base.bucket_les),
              offset=0)
    store = sh.stores["prom-counter"]
    mirror = DeviceMirror()
    assert mirror.ensure_fresh(store)
    # now append HUGE counter values where f32 absolute storage loses +1s
    big = 2.0 ** 31
    n = 20
    ts = np.tile(START + (40 + np.arange(n, dtype=np.int64)) * 10_000, 4)
    idx = np.repeat(np.arange(4, dtype=np.int32), n)
    vals = big + np.arange(n, dtype=np.float64)[None, :] + \
        np.arange(4)[:, None] * 1000.0
    sh.ingest(RecordBatch(base.schema, base.part_keys, idx, ts,
                          {"count": vals.ravel()}), offset=1)
    before = _incr_count()
    assert mirror.ensure_fresh(store)
    assert _incr_count() == before + 1
    snap = mirror._snap
    rb = np.asarray(snap.cols["count"])
    finite = rb[np.isfinite(rb)]
    # rebased device values must be SMALL (deltas preserved in f32)
    assert np.abs(finite).max() < 1e5, np.abs(finite).max()


def test_rearranging_ops_fall_back_to_full_refresh():
    cs_ms = TimeSeriesMemStore()
    sh = cs_ms.setup("prometheus", 0)
    sh.ingest(counter_batch(10, 120, start_ms=START), offset=0)
    store = sh.stores["prom-counter"]
    mirror = DeviceMirror()
    assert mirror.ensure_fresh(store)
    sv = store.shift_version
    # eviction shifts cells -> shift_version bumps -> incremental refused
    sh.flush_all_groups()
    store.evict_oldest(30)
    assert store.shift_version > sv
    before = _incr_count()
    assert mirror.ensure_fresh(store)
    assert _incr_count() == before, "shifted store must NOT go incremental"
    _assert_equivalent(store, mirror)


def test_incremental_correctness_through_query_path(monkeypatch):
    """End-to-end: rates served from an incrementally-updated mirror match
    a mirror-disabled engine exactly."""
    from filodb_tpu.query.engine import QueryEngine
    ms = TimeSeriesMemStore()
    sh = ms.setup("prometheus", 0)
    full = counter_batch(20, 240, start_ms=START, resets=True)
    eng = QueryEngine("prometheus", ms)
    s = START // 1000

    def q(e):
        r = e.query_range('sum by (_ns_)(rate(request_total[5m]))',
                          s + 600, 60, s + 2390)
        assert r.error is None, r.error
        return {str(k): np.asarray(v) for k, _, v in r.series()}

    for i, sl in enumerate(_slices(full, [(0, 80), (80, 160), (160, 240)])):
        sh.ingest(sl, offset=i)
        got = q(eng)
    # truth: same data, mirror disabled
    ms2 = TimeSeriesMemStore()
    sh2 = ms2.setup("prometheus", 0)
    # config.store is the process-wide settings() singleton: restore the
    # flag after the test or every later store silently loses its mirror
    # (this leak hid the fused path from any test running after this one)
    monkeypatch.setattr(sh2.config.store, "device_mirror_enabled", False)
    sh2.ingest(counter_batch(20, 240, start_ms=START, resets=True), offset=0)
    want = q(QueryEngine("prometheus", ms2))
    assert set(got) == set(want)
    for k in want:
        np.testing.assert_allclose(got[k], want[k], rtol=1e-5,
                                   equal_nan=True)


def test_series_growth_with_zero_new_samples_pads_without_error():
    """A new row registered with no surviving samples (s grows, no new
    cells) must take the cheap pad-only path, not the error fallback."""
    ms = TimeSeriesMemStore()
    sh = ms.setup("prometheus", 0)
    sh.ingest(counter_batch(10, 60, start_ms=START), offset=0)
    store = sh.stores["prom-counter"]
    mirror = DeviceMirror()
    assert mirror.ensure_fresh(store)
    # register a row directly with zero samples (what a fully-dropped
    # out-of-order batch leaves behind), bumping the generation
    with store.mutation():
        store.new_row()
    before_err = registry.counter("device_mirror_incremental_errors").value
    before_incr = _incr_count()
    assert mirror.ensure_fresh(store)
    assert registry.counter("device_mirror_incremental_errors").value \
        == before_err
    assert _incr_count() == before_incr + 1
    _assert_equivalent(store, mirror)
    # and appends after the pad continue incrementally + correctly
    full = counter_batch(10, 90, start_ms=START)
    k = full.timestamps >= START + 60 * 10_000
    sh.ingest(RecordBatch(full.schema, full.part_keys, full.part_idx[k],
                          full.timestamps[k],
                          {kk: v[k] for kk, v in full.columns.items()},
                          full.bucket_les), offset=1)
    assert mirror.ensure_fresh(store)
    _assert_equivalent(store, mirror)
