"""Distributed mesh execution tests on the 8-virtual-device CPU mesh.

The correctness oracle is the single-process engine over the same data —
the analogue of the reference's multi-JVM specs asserting cluster results
match (ref: standalone/src/multi-jvm/.../IngestionAndRecoverySpec.scala).
"""
import numpy as np
import pytest

import jax

from jax.sharding import NamedSharding, PartitionSpec as P

from filodb_tpu.core.memstore import TimeSeriesMemStore
from filodb_tpu.core.records import RecordBatch
from filodb_tpu.core.index import Equals
from filodb_tpu.ingest.generator import counter_batch
from filodb_tpu.ops import agg as agg_ops
from filodb_tpu.ops.counter import rebase_values
from filodb_tpu.ops.timewindow import PAD_TS, make_window_ends, to_offsets
from filodb_tpu.parallel.mesh import (make_mesh, pack_shards,
                                      device_put_packed,
                                      distributed_window_agg,
                                      distributed_window_raw)
from filodb_tpu.parallel.shardmapper import ShardEvent, ShardMapper, SpreadProvider
from filodb_tpu.query.engine import QueryEngine

from test_query_engine import START_MS, START_S, NUM_SAMPLES

QEND_S = START_S + 3600
STEP_S = 60


def _mk_store(num_shards=4, n_series=64):
    ms = TimeSeriesMemStore()
    mapper = ShardMapper(num_shards)
    for s in range(num_shards):
        ms.setup("prometheus", s)
        mapper.update_from_event(
            ShardEvent("IngestionStarted", "prometheus", s, "local"))
    batch = counter_batch(n_series, NUM_SAMPLES, start_ms=START_MS)
    shard_of_key = np.asarray([
        mapper.ingestion_shard(pk.shard_key_hash(), pk.partition_hash(), 2)
        for pk in batch.part_keys])
    for s in range(num_shards):
        keep = shard_of_key[batch.part_idx] == s
        if keep.any():
            sub = RecordBatch(batch.schema, batch.part_keys,
                              batch.part_idx[keep], batch.timestamps[keep],
                              {k: v[keep] for k, v in batch.columns.items()},
                              batch.bucket_les)
            ms.get_shard("prometheus", s).ingest(sub)
    return ms, mapper


@pytest.fixture(scope="module")
def mesh42():
    return make_mesh(4, 2, devices=jax.devices("cpu")[:8])


@pytest.fixture(scope="module")
def store4():
    return _mk_store(num_shards=4)


def _engine_result(ms, mapper, promql):
    eng = QueryEngine("prometheus", ms, mapper, SpreadProvider(default_spread=2))
    res = eng.query_range(promql, START_S + 600, STEP_S, QEND_S)
    assert res.error is None, res.error
    return res


def shard_blocks(ms, filters, start_ms, end_ms, counter=False):
    """A block a shard out of the store, in the form `pack_shards` takes
    and the driver's dry run and tests/mh_worker.py make theirs: (offsets
    from start_ms, rebased f64 values, the rows' labels, value bases); a
    shard that selects nothing gives the one all-pad row without labels.
    `counter`: reset-correct on the host (a pack to be `precorrected`)."""
    blocks = []
    for shard in ms.shards_for("prometheus"):
        lookup = shard.lookup_partitions(filters, start_ms, end_ms)
        schema = lookup.first_schema
        pids = lookup.pids_by_schema.get(schema) if schema else None
        if pids is None or not len(pids):
            blocks.append((np.full((1, 1), PAD_TS, np.int32),
                           np.full((1, 1), np.nan), []))
            continue
        store = shard.stores[schema]
        rows = shard.rows_for(pids)
        ts, cols, counts = shard.snapshot_read(
            store, lambda: store.gather_rows(rows))
        vals, vbase = rebase_values(
            cols[shard.schemas[schema].value_column], counter)
        blocks.append((to_offsets(ts, counts, start_ms), vals,
                       [key.labels for key in shard.keys_for(pids)], vbase))
    return blocks


def mesh_agg(mesh, packed, wends_ms, *, range_ms, fn_name, agg_op):
    """`distributed_window_agg` over a placed pack, presented on the host:
    window ends in absolute ms, moved onto the pack's base and padded to
    a multiple of the time axis with ends before all data (empty windows,
    cut off again).  -> [G, W]."""
    wends = (np.asarray(wends_ms, np.int64) - packed.base_ms).astype(np.int32)
    W = wends.shape[0]
    wends = np.concatenate(
        [wends, np.full(-W % mesh.shape["time"], -PAD_TS, np.int32)])
    partials = distributed_window_agg(
        mesh, packed.ts_off, packed.values, packed.group_ids,
        jax.device_put(wends, NamedSharding(mesh, P("time"))),
        range_ms=range_ms, fn_name=fn_name, agg_op=agg_op,
        num_groups=packed.num_groups, base_ms=packed.base_ms,
        vbase=packed.vbase, precorrected=packed.precorrected,
        dense=packed.dense)
    return np.asarray(agg_ops.present(agg_op, partials))[:, :W]


def _mesh_result(ms, mesh, agg_op, fn_name, by=(), range_ms=300_000):
    start_ms = (START_S + 600) * 1000 - range_ms
    blocks = shard_blocks(
        ms, [Equals("_metric_", "request_total"), Equals("_ws_", "demo"),
             Equals("_ns_", "App-0")], start_ms, QEND_S * 1000)
    packed = device_put_packed(pack_shards(blocks, by=by, base_ms=start_ms),
                               mesh)
    wends = make_window_ends((START_S + 600) * 1000, QEND_S * 1000,
                             STEP_S * 1000)
    out = mesh_agg(mesh, packed, wends, range_ms=range_ms, fn_name=fn_name,
                   agg_op=agg_op)
    return out, packed.group_labels


def test_mesh_sum_rate_matches_engine(store4, mesh42):
    ms, mapper = store4
    res = _engine_result(ms, mapper, 'sum(rate(request_total{_ws_="demo",_ns_="App-0"}[5m]))')
    out, labels = _mesh_result(ms, mesh42, "sum", "rate")
    assert out.shape[0] == 1 and not labels[0]
    got = out[0]
    rows = list(res.series())
    assert len(rows) == 1
    want = np.asarray(rows[0][2])
    valid = ~np.isnan(want)
    np.testing.assert_allclose(got[valid], want[valid], rtol=1e-9)
    assert np.isnan(got[~valid]).all()


@pytest.mark.parametrize("agg_op,fn", [("min", "min_over_time"),
                                       ("max", "max_over_time"),
                                       ("avg", "avg_over_time"),
                                       ("count", "last_over_time"),
                                       ("stddev", "sum_over_time")])
def test_mesh_aggs_match_engine(store4, mesh42, agg_op, fn):
    ms, mapper = store4
    res = _engine_result(
        ms, mapper,
        f'{agg_op}({fn}(request_total{{_ws_="demo",_ns_="App-0"}}[5m]))')
    out, _ = _mesh_result(ms, mesh42, agg_op, fn)
    want = np.asarray(next(res.series())[2])
    valid = ~np.isnan(want)
    np.testing.assert_allclose(out[0][valid], want[valid], rtol=1e-8)


def test_mesh_group_by(store4, mesh42):
    ms, mapper = store4
    res = _engine_result(
        ms, mapper, 'sum by (instance) (rate(request_total{_ws_="demo",_ns_="App-0"}[5m]))')
    out, labels = _mesh_result(ms, mesh42, "sum", "rate", by=("instance",))
    rows = list(res.series())
    assert len(labels) == len(rows)
    by_engine = {k.labels_dict.get("instance"): np.asarray(v)
                 for k, _, v in rows}
    for slot, lab in enumerate(labels):
        want = by_engine[lab["instance"]]
        valid = ~np.isnan(want)
        np.testing.assert_allclose(out[slot][valid], want[valid], rtol=1e-9)


def test_mesh_raw_path_shapes(mesh42):
    # 4 shards, 8 series each, tiny grid; raw result keeps sharded layout
    rng = np.random.default_rng(0)
    blocks = []
    for d in range(4):
        ts = np.cumsum(np.full((8, 100), 10_000, np.int64), axis=1)
        vals = rng.random((8, 100))
        labels = [{"instance": f"i{d}-{i}"} for i in range(8)]
        from filodb_tpu.ops.timewindow import to_offsets
        blocks.append((to_offsets(ts, np.full(8, 100), 0), vals, labels))
    packed = pack_shards(blocks)
    packed = device_put_packed(packed, mesh42)
    wends = np.arange(100_000, 1_000_001, 50_000, dtype=np.int32)
    # pad to multiple of time axis (2)
    if wends.shape[0] % 2:
        wends = np.concatenate([wends, wends[-1:] + 50_000])
    out = distributed_window_raw(mesh42, packed.ts_off, packed.values,
                                 jax.device_put(wends), range_ms=60_000,
                                 fn_name="sum_over_time")
    assert out.shape == (4, 8, wends.shape[0])
    assert np.isfinite(np.asarray(out)).any()


def test_mesh_empty_shard_contributes_nothing(mesh42):
    # shard 3 has no matching series: NaN rows must not poison the psum
    from filodb_tpu.ops.timewindow import to_offsets, PAD_TS
    ts = np.cumsum(np.full((4, 50), 10_000, np.int64), axis=1)
    vals = np.ones((4, 50))
    labels = [{"instance": f"i{i}"} for i in range(4)]
    blocks = [(to_offsets(ts, np.full(4, 50), 0), vals, labels)]
    for _ in range(3):
        blocks.append((np.full((1, 1), PAD_TS, np.int32),
                       np.full((1, 1), np.nan), []))
    packed = device_put_packed(pack_shards(blocks), mesh42)
    wends = np.asarray([200_000, 300_000, 400_000, 500_000], np.int32)
    out = distributed_window_agg(
        mesh42, packed.ts_off, packed.values, packed.group_ids,
        jax.device_put(wends), range_ms=100_000, fn_name="sum_over_time",
        agg_op="sum", num_groups=packed.num_groups)
    from filodb_tpu.ops import agg as agg_ops
    final = np.asarray(agg_ops.present("sum", out))
    # 4 series * 10 samples/window * 1.0 each = 40
    np.testing.assert_allclose(final[0], 40.0)
