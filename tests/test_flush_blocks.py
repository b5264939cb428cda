"""A flush pass encodes a block of equal-length series whole
(`chunks.encode_chunksets`, `nibblepack.pack_rows`): the same chunks, bit
for bit, as one `encode_chunkset` a series."""
import numpy as np
import pytest

import filodb_tpu.core.shard as shardmod
from filodb_tpu.core.memstore import TimeSeriesMemStore
from filodb_tpu.core.partkey import PartKey
from filodb_tpu.memory import chunks as ch
from filodb_tpu.memory import nibblepack as nbp

START_MS = 1_600_000_000_000


def _rows(rng, kind, shape):
    if kind == "zero":
        return np.zeros(shape, np.uint64)
    if kind == "small":
        return rng.integers(0, 20, shape, dtype=np.uint64)
    if kind == "wide":
        return rng.integers(0, 1 << 63, shape, dtype=np.uint64)
    vals = rng.integers(0, 1 << 40, shape, dtype=np.uint64)
    vals[rng.random(shape) < 0.5] = 0
    return vals


@pytest.mark.parametrize("native", [True, False])
@pytest.mark.parametrize("kind", ["zero", "small", "wide", "mixed"])
def test_pack_rows_is_pack_of_each_row(kind, native, monkeypatch):
    if native and nbp._native is None:
        pytest.skip("native library not built")
    if not native:
        monkeypatch.setattr(nbp, "_native", None)
    rng = np.random.default_rng(11)
    for shape in ((0, 5), (1, 1), (3, 7), (5, 8), (4, 33), (9, 720)):
        vals = _rows(rng, kind, shape)
        assert nbp.pack_rows(vals) == [nbp._pack_py(r) for r in vals]


def _same(a, b):
    assert (a.info.num_rows, a.info.start_time_ms, a.info.end_time_ms) == (
        b.info.num_rows, b.info.start_time_ms, b.info.end_time_ms)
    assert a.columns == b.columns and a.bucket_scheme == b.bucket_scheme


@pytest.mark.parametrize("n", [1, 2, 7, 8, 9, 240])
def test_block_encoder_is_the_series_encoder(n):
    """Rows that take each branch: a regular and a jittered timestamp row,
    integral, fractional, NaN-holed and too-large doubles, a value whose
    slope float64 would round, longs over the whole int64 range, a
    histogram."""
    rng = np.random.default_rng(n)
    R = 12
    ts = START_MS + rng.integers(0, 10_000, R)[:, None] \
        + np.arange(n)[None] * 10_000
    ts[3] = np.sort(ts[3] + rng.integers(-3, 4, n))
    v = np.cumsum(rng.standard_exponential((R, n)) * 10, axis=1)
    v[1] = np.floor(v[1])
    v[2, n // 2] = np.nan
    v[4] = 2.0 ** 60
    v[5] = np.floor(v[5]) + 2.0 ** 52
    lg = rng.integers(-2 ** 62, 2 ** 62, (R, n))
    lg[0] = np.arange(n) * 5
    h = np.cumsum(rng.integers(0, 5, (R, n, 4)), axis=2).astype(np.float64)
    cols = {"count": v, "l": lg, "h": h}
    types = {"count": "double", "l": "long", "h": "hist"}
    got = ch.encode_chunksets(ts, cols, types, 123)
    ids = [cs.info.chunk_id for cs in got]
    assert ids == sorted(ids) and len(set(ids)) == R
    for r in range(R):
        _same(got[r], ch.encode_chunkset(
            ts[r], {k: a[r] for k, a in cols.items()}, types, 123))
        assert got[r].info.ingestion_time_ms == 123
        dec = ch.decode_chunkset(got[r])
        np.testing.assert_array_equal(dec["timestamp"], ts[r])
        np.testing.assert_array_equal(dec["count"], v[r])
        np.testing.assert_array_equal(dec["l"], lg[r])


@pytest.mark.parametrize("ragged", [False, True])
def test_flush_writes_the_series_encoder_s_chunks(ragged, monkeypatch):
    """40 counter series through `flush_all_groups` (every fifth one two
    samples short when `ragged`): each resident chunk is `encode_chunkset`
    of that series, and a group's rows of one length went in one call."""
    calls = []
    real = ch.encode_chunksets
    monkeypatch.setattr(shardmod, "encode_chunksets",
                        lambda ts, *a, **k: calls.append(ts.shape) or
                        real(ts, *a, **k))
    shard = TimeSeriesMemStore().setup("prometheus", 0)
    rng = np.random.default_rng(5)
    S, T = 40, 300
    keys = [PartKey.make("request_total", {"_ws_": "demo", "_ns_": "App-0",
                                           "instance": f"I{i}"})
            for i in range(S)]
    ts = START_MS + rng.integers(0, 10_000, S)[:, None] \
        + np.arange(T)[None] * 10_000
    vals = np.cumsum(rng.standard_exponential((S, T)) * 10, axis=1)
    shard.ingest_columns("prom-counter", keys, ts[:, :T - 2],
                         {"count": vals[:, :T - 2]})
    full = np.arange(S) % 5 != 0 if ragged else np.ones(S, bool)
    idx = np.flatnonzero(full)
    shard.ingest_columns("prom-counter", [keys[i] for i in idx],
                         ts[idx, T - 2:], {"count": vals[idx, T - 2:]})
    assert shard.flush_all_groups() == S
    for i, key in enumerate(keys):
        pid = next(p for p, info in enumerate(shard.partitions)
                   if info is not None and info.part_key == key)
        (cs,) = shard.resident.read(pid, 0, 1 << 62)
        n = T if full[i] else T - 2
        _same(cs, ch.encode_chunkset(ts[i, :n], {"count": vals[i, :n]},
                                     {"count": "double"}, 0))
        dec = ch.decode_chunkset(cs)
        np.testing.assert_array_equal(dec["timestamp"], ts[i, :n])
        np.testing.assert_array_equal(dec["count"], vals[i, :n])
    assert sum(rows for rows, _ in calls) == S
    assert {n for _, n in calls} == ({T, T - 2} if ragged else {T})
    groups = len({shard.partitions[p].group for p in range(S)})
    assert len(calls) <= groups * (2 if ragged else 1)
