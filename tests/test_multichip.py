"""Multi-device equivalence suite for the multi-chip fused scan.

Runs on the harness's 8 virtual CPU devices (conftest forces
XLA_FLAGS=--xla_force_host_platform_device_count=8); the `multichip`
marker auto-skips below 2 local devices so tier-1 stays green on
1-device boxes.

What it proves (doc/multichip.md):
  - the full engine with sharded DeviceMirrors + per-device fused
    dispatch returns BIT-IDENTICAL results to the unsharded engine for
    dense, ragged, gauge and histogram shapes, through the fused kernel
    (dense, ragged, `reduce_window`) and the general path;
  - the partial-only collective merge equals the host-side
    ops/agg.reduce_phase merge;
  - a device-pinned DeviceMirror round-trips the shard partition's
    columns bit-exactly from its assigned device.
"""
import numpy as np
import pytest

import jax

from filodb_tpu.core.memstore import TimeSeriesMemStore
from filodb_tpu.core.records import RecordBatch
from filodb_tpu.ingest.generator import (counter_batch, gauge_batch,
                                         histogram_batch)
from filodb_tpu.parallel.mesh import make_mesh, merge_device_partials
from filodb_tpu.utils.metrics import registry

from test_query_engine import _mk_engine, START_MS, START_S, NUM_SAMPLES

pytestmark = pytest.mark.multichip

QEND_S = START_S + 3600
STEP_S = 60


RAGGED_METRIC = "flaky_total"


def _ragged_counter_batch(num_series, num_samples, seed=7):
    """Counters with a tenth of their samples NaN, under a metric of their
    own: under `request_total` the rows would carry the dense batch's keys
    and timestamps, and the shard drops such a batch whole."""
    rng = np.random.default_rng(seed)
    cb = counter_batch(num_series, num_samples, start_ms=START_MS, seed=seed,
                       metric=RAGGED_METRIC)
    v = cb.columns["count"].copy()
    v[rng.random(v.shape) < 0.1] = np.nan
    return RecordBatch(cb.schema, cb.part_keys, cb.part_idx, cb.timestamps,
                       {"count": v}, cb.bucket_les)


def _series_map(res):
    assert res.error is None, res.error
    return {tuple(sorted(k.labels_dict.items())): np.asarray(v)
            for k, _, v in res.series()}


# case -> (query, the leaf counters that must move on the sharded engine
# when the fused kernel serves: the route the case is there for)
QUERIES = {
    "sum-rate": (
        'sum by (_ns_) (rate(request_total{_ws_="demo"}[5m]))',
        ("leaf_fused_kernel",)),
    "avg-rate": (
        'avg by (_ns_) (rate(request_total{_ws_="demo"}[5m]))',
        ("leaf_fused_kernel",)),
    "max-rate": (
        'max by (_ns_) (rate(request_total{_ws_="demo"}[5m]))',
        ("leaf_fused_kernel",)),
    "sum-increase-by-instance": (
        'sum by (instance) (increase('
        'request_total{_ws_="demo",_ns_="App-0"}[10m]))',
        ("leaf_fused_kernel",)),
    "hist-quantile": (
        'histogram_quantile(0.9, sum by (_ns_) ('
        'rate(http_latency{_ws_="demo"}[5m])))',
        ("leaf_hist_fused",)),
    # an *_over_time sum through the fused kernel, over a gauge column
    "gauge-sum-over-time": (
        'sum by (_ns_) (sum_over_time(heap_usage{_ws_="demo"}[5m]))',
        ("leaf_fused_kernel",)),
    # the ragged kernel's presence output; avg dividing by counts
    "ragged-count-rate": (
        'count by (_ns_) (rate(%s{_ws_="demo"}[5m]))' % RAGGED_METRIC,
        ("leaf_fused_kernel", "leaf_ragged_fused")),
    "ragged-avg-rate": (
        'avg by (_ns_) (rate(%s{_ws_="demo"}[5m]))' % RAGGED_METRIC,
        ("leaf_fused_kernel", "leaf_ragged_fused")),
    # the reduce_window route
    "gauge-min-min-over-time": (
        'min by (_ns_) (min_over_time(heap_usage{_ws_="demo"}[5m]))',
        ("leaf_fused_minmax",)),
    # the general path over sharded mirrors
    "stddev-rate": (
        'stddev by (_ns_) (rate(request_total{_ws_="demo"}[5m]))',
        ("leaf_general_path",)),
}


def _mirror_devices(eng):
    devs = set()
    for s in range(4):
        sh = eng.source.get_shard("prometheus", s)
        for store in sh.stores.values():
            m = getattr(store, "device_mirror", None)
            if m is not None and m.device is not None:
                devs.add(m.device)
    return devs


@pytest.fixture(scope="module", params=[False, True],
                ids=["general", "fused-kernel"])
def parity_engines(request):
    """(unsharded engine, sharded engine, fused_kernel): the two engines
    of a mode, built once over the same batches.  A store's mirror is made
    (and placed, or not) at its first query, so every query of the sharded
    engine runs under FILODB_TPU_FORCE_SHARDED_MIRROR and none of the
    other's does (`_ask`)."""
    def batches():
        return [counter_batch(96, NUM_SAMPLES, start_ms=START_MS),
                _ragged_counter_batch(32, NUM_SAMPLES, seed=11),
                gauge_batch(48, NUM_SAMPLES, start_ms=START_MS),
                histogram_batch(24, NUM_SAMPLES, num_buckets=8,
                                start_ms=START_MS)]

    eng_flat = _mk_engine(batches(), num_shards=4, spread=2)
    eng_shard = _mk_engine(batches(), num_shards=4, spread=2)
    with pytest.MonkeyPatch.context() as mp:
        _ask(mp, eng_shard, QUERIES["sum-rate"][0], True, request.param)
    # the mirrors really are partitioned: the shards' stores must sit on
    # more than one device
    devs = _mirror_devices(eng_shard)
    assert len(devs) >= 2, f"mirrors not spread across devices: {devs}"
    return eng_flat, eng_shard, request.param


def _ask(monkeypatch, eng, query, sharded, fused_kernel):
    if fused_kernel:
        monkeypatch.setenv("FILODB_TPU_FUSED_INTERPRET", "1")
    else:
        monkeypatch.delenv("FILODB_TPU_FUSED_INTERPRET", raising=False)
    if sharded:
        monkeypatch.setenv("FILODB_TPU_FORCE_SHARDED_MIRROR", "1")
    else:
        monkeypatch.delenv("FILODB_TPU_FORCE_SHARDED_MIRROR", raising=False)
    return _series_map(eng.query_range(query, START_S + 600, STEP_S, QEND_S))


@pytest.mark.parametrize("case", list(QUERIES))
def test_engine_sharded_mirrors_bit_parity(monkeypatch, parity_engines,
                                           case):
    """The engine with per-shard device-pinned mirrors (the sharded
    DeviceMirror mode feeding the per-device dispatch) must return
    bit-identical results to the unsharded engine — same leaves, same
    partial merges, only the executing device differs."""
    eng_flat, eng_shard, fused_kernel = parity_engines
    query, route = QUERIES[case]
    on_device = registry.counter("hist_device_quantiles").value
    flat = _ask(monkeypatch, eng_flat, query, False, fused_kernel)
    assert not _mirror_devices(eng_flat)
    if registry.counter("hist_device_quantiles").value > on_device:
        # ISSUE 51: on ONE device a histogram quantile's merge and quantile
        # are the epilogue of the leaves' one call, in f32; over sharded
        # mirrors the leaves are a call a device and the fold stays on the
        # host, in f64 (declined `dispatch`).  Bit parity is between the
        # two engines' HOST paths; the epilogue's answer is held to it
        from filodb_tpu.query import exprfuse
        epilogue = flat
        with monkeypatch.context() as m:
            m.setattr(exprfuse, "_hist_quantiles", lambda ep, calls: [])
            flat = _ask(m, eng_flat, query, False, fused_kernel)
        assert flat.keys() == epilogue.keys()
        for k, want in flat.items():
            np.testing.assert_allclose(epilogue[k], want, rtol=2e-5,
                                       err_msg=str(k))
    declined = registry.counter("hist_device_quantile_declined",
                                reason="dispatch")
    on_device, on_host = registry.counter("hist_device_quantiles").value, \
        declined.value
    watched = ("leaf_host_gather", "leaf_fused_errors") + route
    before = {c: registry.counter(c).value for c in watched}
    sharded = _ask(monkeypatch, eng_shard, query, True, fused_kernel)
    moved = {c: registry.counter(c).value - before[c] for c in watched}

    assert flat and flat.keys() == sharded.keys()
    for k, want in flat.items():
        assert np.isfinite(want).any(), k
        np.testing.assert_array_equal(sharded[k], want, err_msg=str(k))
    # every leaf read its shard's mirror
    assert moved["leaf_host_gather"] == 0 and moved["leaf_fused_errors"] == 0
    # and no epilogue ran across devices: a histogram quantile said why
    assert registry.counter("hist_device_quantiles").value == on_device
    if fused_kernel and case == "hist-quantile":
        assert declined.value == on_host + 1
    if fused_kernel:
        assert all(moved[c] > 0 for c in route), moved


def test_mirror_placer_prefers_home_and_respects_hbm_cap():
    from filodb_tpu.core.devicecache import MirrorPlacer
    p = MirrorPlacer()
    devs = jax.local_devices()
    limit = 1000
    d0 = p.assign(0, 600, limit)
    assert d0 == devs[0]
    p.book(d0, 600)
    # shard len(devs) maps home to device 0, which no longer fits ->
    # least-booked device takes it
    d_spill = p.assign(len(devs), 600, limit)
    assert d_spill != d0
    # nothing fits: still places (per-store cap handles degradation)
    for d in devs:
        p.book(d, limit)
    assert p.assign(1, 600, limit) in devs


def test_mirror_shard_partition_roundtrip():
    """A device-pinned mirror must serve back exactly the columns the
    shard partition holds, from its assigned device."""
    from filodb_tpu.core.devicecache import DeviceMirror
    ms = TimeSeriesMemStore()
    ms.setup("prometheus", 0)
    sh = ms.get_shard("prometheus", 0)
    sh.ingest(counter_batch(16, 64, start_ms=START_MS))
    (schema_name, store), = [(k, v) for k, v in sh.stores.items()]
    dev = jax.local_devices()[min(3, jax.local_device_count() - 1)]
    mirror = DeviceMirror(device=dev, shard_num=0)
    with sh._write_locked("test"):
        assert mirror.ensure_fresh(store)
    snap = mirror.snapshot()
    rows = np.arange(store.num_series)
    got = mirror.gather_cached(rows, snap)
    assert got is not None
    ts_off, base = got.read("ts_off"), got.base_ms
    # round-trip: device copy == host truth (offsets + absolute values)
    s, t = store.num_series, store.time_used
    want_ts = store.ts[:s, :t]
    counts = store.counts[:s]
    pos = np.arange(t)[None, :]
    got_ts = np.asarray(ts_off, np.int64)
    valid = pos < counts[:, None]
    np.testing.assert_array_equal(got_ts[valid] + base, want_ts[valid])
    name = store.schema.value_column
    got_vals = np.asarray(got.read("values", name), np.float64) \
        + np.asarray(got.read("vbase", name), np.float64)[:, None]
    # the mirror reset-corrects counter columns in f64 before rebasing,
    # so the host truth is the corrected column
    from filodb_tpu.ops.counter import host_counter_correct
    want_vals = host_counter_correct(store.cols[name][:s, :t])
    np.testing.assert_allclose(got_vals[valid], want_vals[valid],
                               rtol=1e-6)
    # committed to the assigned device
    for arr in (snap.ts_off, *snap.cols.values()):
        assert set(arr.devices()) == {dev}, \
            f"snapshot array on {arr.devices()}, wanted {dev}"
    from filodb_tpu.core.devicecache import placer
    assert placer.booked(dev) >= 0


def test_merge_device_partials_collective_matches_host():
    """The partial-only psum collective and the host-side reduce_phase
    merge are the same reduce — one rides ICI, one rides host memory."""
    mesh = make_mesh(4, 2, devices=jax.devices()[:8])
    rng = np.random.default_rng(0)
    G, Wlp = 16, 128
    parts = {}
    for s in range(4):
        for t in range(2):
            parts[(s, t)] = jax.device_put(
                rng.standard_normal((G, Wlp)).astype(np.float32),
                mesh.devices[s, t])
    via_coll = merge_device_partials(parts, mesh, "sum", collective=True)
    via_host = merge_device_partials(parts, mesh, "sum", collective=False)
    assert via_coll.shape == via_host.shape == (G, 2 * Wlp)
    np.testing.assert_allclose(via_coll, via_host, rtol=1e-6, atol=1e-6)
    for comb, ref in (("min", np.minimum), ("max", np.maximum)):
        got = merge_device_partials(parts, mesh, comb, collective=True)
        want = np.concatenate(
            [ref.reduce([np.asarray(parts[(s, t)], np.float64)
                         for s in range(4)], axis=0) for t in range(2)],
            axis=1)
        np.testing.assert_allclose(got, want, rtol=1e-6)


def test_make_mesh_exposes_shape_and_unused_devices():
    make_mesh(2, 1, devices=jax.devices()[:8])
    assert registry.gauge("mesh_shard_axis").value == 2
    assert registry.gauge("mesh_time_axis").value == 1
    assert registry.gauge("mesh_unused_devices").value == 6
    make_mesh(4, 2, devices=jax.devices()[:8])
    assert registry.gauge("mesh_unused_devices").value == 0
