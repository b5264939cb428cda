"""Multi-device equivalence suite for the multi-chip fused scan.

Runs on the harness's 8 virtual CPU devices (conftest forces
XLA_FLAGS=--xla_force_host_platform_device_count=8); the `multichip`
marker auto-skips below 2 local devices so tier-1 stays green on
1-device boxes.

What it proves (doc/multichip.md):
  - the full engine with sharded DeviceMirrors + per-device fused
    dispatch returns BIT-IDENTICAL results to the unsharded engine for
    dense, ragged and histogram `sum/max/avg by (rate())` shapes;
  - the MeshExecutor per-device dispatch path matches the general mesh
    path and actually fans out one kernel per device;
  - the partial-only collective merge equals the host-side
    ops/agg.reduce_phase merge;
  - a device-pinned DeviceMirror round-trips the shard partition's
    columns bit-exactly from its assigned device;
  - PackedShards packing is memoized per (shard-set, keys-generation):
    a re-poll after value-only ingest hits the layout memo
    (the ISSUE-6 acceptance gate).
"""
import numpy as np
import pytest

import jax

from filodb_tpu.core.index import Equals
from filodb_tpu.core.memstore import TimeSeriesMemStore
from filodb_tpu.core.records import RecordBatch
from filodb_tpu.ingest.generator import (counter_batch, gauge_batch,
                                         histogram_batch)
from filodb_tpu.ops.timewindow import make_window_ends
from filodb_tpu.parallel.mesh import (MeshExecutor, make_mesh,
                                      merge_device_partials)
from filodb_tpu.parallel.shardmapper import ShardEvent, ShardMapper
from filodb_tpu.utils.metrics import registry

from test_query_engine import _mk_engine, START_MS, START_S, NUM_SAMPLES

pytestmark = pytest.mark.multichip

QEND_S = START_S + 3600
STEP_S = 60


def _ragged_counter_batch(num_series, num_samples, seed=7):
    rng = np.random.default_rng(seed)
    cb = counter_batch(num_series, num_samples, start_ms=START_MS, seed=seed)
    v = cb.columns["count"].copy()
    v[rng.random(v.shape) < 0.1] = np.nan
    return RecordBatch(cb.schema, cb.part_keys, cb.part_idx, cb.timestamps,
                       {"count": v}, cb.bucket_les)


def _series_map(res):
    assert res.error is None, res.error
    return {tuple(sorted(k.labels_dict.items())): np.asarray(v)
            for k, _, v in res.series()}


QUERIES = [
    'sum by (_ns_) (rate(request_total{_ws_="demo"}[5m]))',
    'avg by (_ns_) (rate(request_total{_ws_="demo"}[5m]))',
    'max by (_ns_) (rate(request_total{_ws_="demo"}[5m]))',
    'sum by (instance) (increase(request_total{_ws_="demo",_ns_="App-0"}[10m]))',
    'histogram_quantile(0.9, sum by (_ns_) (rate(http_latency{_ws_="demo"}[5m])))',
]


@pytest.mark.parametrize("fused_kernel", [False, True],
                         ids=["general", "fused-kernel"])
def test_engine_sharded_mirrors_bit_parity(monkeypatch, fused_kernel):
    """The engine with per-shard device-pinned mirrors (the sharded
    DeviceMirror mode feeding the per-device dispatch) must return
    bit-identical results to the unsharded engine — same leaves, same
    partial merges, only the executing device differs."""
    def batches():
        return [counter_batch(96, NUM_SAMPLES, start_ms=START_MS),
                _ragged_counter_batch(32, NUM_SAMPLES, seed=11),
                histogram_batch(24, NUM_SAMPLES, num_buckets=8,
                                start_ms=START_MS)]

    if fused_kernel:
        monkeypatch.setenv("FILODB_TPU_FUSED_INTERPRET", "1")
    monkeypatch.delenv("FILODB_TPU_FORCE_SHARDED_MIRROR", raising=False)
    eng_flat = _mk_engine(batches(), num_shards=4, spread=2)
    flat = {q: _series_map(eng_flat.query_range(q, START_S + 600, STEP_S,
                                                QEND_S)) for q in QUERIES}

    monkeypatch.setenv("FILODB_TPU_FORCE_SHARDED_MIRROR", "1")
    eng_shard = _mk_engine(batches(), num_shards=4, spread=2)
    sharded = {q: _series_map(eng_shard.query_range(q, START_S + 600,
                                                    STEP_S, QEND_S))
               for q in QUERIES}

    for q in QUERIES:
        assert flat[q].keys() == sharded[q].keys(), q
        for k, want in flat[q].items():
            np.testing.assert_array_equal(sharded[q][k], want,
                                          err_msg=f"{q} {k}")

    # the mirrors really are partitioned: the shards' stores must sit on
    # more than one device
    devs = set()
    for s in range(4):
        sh = eng_shard.source.get_shard("prometheus", s)
        for store in sh.stores.values():
            m = getattr(store, "device_mirror", None)
            if m is not None and m.device is not None:
                devs.add(m.device)
    assert len(devs) >= 2, f"mirrors not spread across devices: {devs}"


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


def _mk_store4(n_series=64, ragged=False):
    ms = TimeSeriesMemStore()
    mapper = ShardMapper(4)
    for s in range(4):
        ms.setup("prometheus", s)
        mapper.update_from_event(
            ShardEvent("IngestionStarted", "prometheus", s, "local"))
    batch = (_ragged_counter_batch(n_series, NUM_SAMPLES)
             if ragged else counter_batch(n_series, NUM_SAMPLES,
                                          start_ms=START_MS))
    shard_of_key = np.asarray([
        mapper.ingestion_shard(pk.shard_key_hash(), pk.partition_hash(), 2)
        for pk in batch.part_keys])
    for s in range(4):
        keep = shard_of_key[batch.part_idx] == s
        if keep.any():
            sub = RecordBatch(batch.schema, batch.part_keys,
                              batch.part_idx[keep], batch.timestamps[keep],
                              {k: v[keep] for k, v in
                               batch.columns.items()},
                              batch.bucket_les)
            ms.get_shard("prometheus", s).ingest(sub)
    return ms


@pytest.mark.parametrize("ragged", [False, True], ids=["dense", "ragged"])
def test_mesh_perdevice_dispatch_parity_and_fanout(monkeypatch, ragged):
    """run_agg's fused route must dispatch the single-chip kernel once
    per mesh device (never inside shard_map) and match the general mesh
    path."""
    monkeypatch.setenv("FILODB_TPU_FUSED_INTERPRET", "1")
    ms = _mk_store4(ragged=ragged)
    mesh = make_mesh(4, 2, devices=jax.devices()[:8])
    ex = MeshExecutor(ms, "prometheus", mesh)
    filters = [Equals("_metric_", "request_total")]
    packed = ex.lookup_and_pack(filters, START_MS, QEND_S * 1000,
                                by=("_ns_",), fn_name="rate")
    assert packed.shared_ts_row is not None
    assert packed.dense is (not ragged)
    wends = make_window_ends((START_S + 600) * 1000, QEND_S * 1000,
                             STEP_S * 1000)
    k0 = registry.counter("mesh_fused_kernel").value
    d0 = registry.counter("mesh_fused_perdevice_dispatches").value
    fused, labels = ex.run_agg(packed, wends, range_ms=300_000,
                               fn_name="rate", agg_op="sum")
    assert registry.counter("mesh_fused_kernel").value == k0 + 1
    assert registry.counter("mesh_fused_perdevice_dispatches").value \
        == d0 + 8, "per-device dispatch must fan out over all 8 devices"
    # general mesh path over the same pack
    from filodb_tpu.ops import agg as agg_ops
    from filodb_tpu.parallel.mesh import distributed_window_agg
    from jax.sharding import NamedSharding, PartitionSpec as P
    wends_p, W = ex._prep_wends(packed, wends)
    wends_dev = jax.device_put(wends_p, NamedSharding(mesh, P("time")))
    partials = distributed_window_agg(
        mesh, packed.ts_off, packed.values, packed.group_ids, wends_dev,
        range_ms=300_000, fn_name="rate", agg_op="sum",
        num_groups=packed.num_groups, base_ms=packed.base_ms,
        vbase=packed.vbase, precorrected=packed.precorrected,
        dense=packed.dense)
    general = np.asarray(agg_ops.present("sum", partials))[:, :W]
    assert (np.isnan(fused) == np.isnan(general)).all()
    np.testing.assert_allclose(fused, general, rtol=2e-5, atol=1e-4,
                               equal_nan=True)


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


def test_pack_layout_memo_hits_on_repoll():
    """ISSUE-6 acceptance: PackedShards repack is memoized per
    (shard-set, keys-generation) — a re-poll after value-only ingest
    must hit the layout memo (no per-series repack)."""
    ms = _mk_store4()
    mesh = make_mesh(4, 2, devices=jax.devices()[:8])
    ex = MeshExecutor(ms, "prometheus", mesh)
    filters = [Equals("_metric_", "request_total")]
    t0, t1 = START_MS, QEND_S * 1000
    h0 = registry.counter("mesh_pack_memo_hits").value
    ex.lookup_and_pack(filters, t0, t1, by=("_ns_",), fn_name="rate")
    # value-only ingest: same series keys, new samples -> store
    # generations move (pack cache invalidated) but keys stay
    batch = counter_batch(64, 4,
                          start_ms=START_MS + NUM_SAMPLES * 10_000)
    mapper = ShardMapper(4)
    shard_of_key = np.asarray([
        mapper.ingestion_shard(pk.shard_key_hash(), pk.partition_hash(), 2)
        for pk in batch.part_keys])
    for s in range(4):
        keep = shard_of_key[batch.part_idx] == s
        if keep.any():
            sub = RecordBatch(batch.schema, batch.part_keys,
                              batch.part_idx[keep], batch.timestamps[keep],
                              {k: v[keep] for k, v in
                               batch.columns.items()},
                              batch.bucket_les)
            ms.get_shard("prometheus", s).ingest(sub)
    ex.lookup_and_pack(filters, t0, t1 + 40_000, by=("_ns_",),
                       fn_name="rate")
    assert registry.counter("mesh_pack_memo_hits").value > h0, \
        "re-poll after value-only ingest must hit the layout memo"


def test_make_mesh_exposes_shape_and_unused_devices():
    make_mesh(2, 1, devices=jax.devices()[:8])
    assert registry.gauge("mesh_shard_axis").value == 2
    assert registry.gauge("mesh_time_axis").value == 1
    assert registry.gauge("mesh_unused_devices").value == 6
    make_mesh(4, 2, devices=jax.devices()[:8])
    assert registry.gauge("mesh_unused_devices").value == 0
