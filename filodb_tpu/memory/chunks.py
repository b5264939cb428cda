"""Columnar chunk format.

A chunk is one partition's worth of samples between flush boundaries, encoded
per column (ref: core/.../store/ChunkSetInfo.scala:60-70 for the metadata
fields; memory/.../format/BinaryVector.scala for the per-column vector model).

TPU-native departure from the reference: chunks are *wire/storage* artifacts
only.  The query-hot working set is kept decoded as dense [series, time]
arrays (see core/blockstore.py) because TPUs want dense vectorized math, not
branchy bit-unpacking (SURVEY.md section 7 step 1).  Encoding therefore
optimizes for storage/replay, not random access.
"""
from __future__ import annotations

import dataclasses
import itertools
from typing import Dict, List, Optional

import numpy as np

from filodb_tpu.memory import nibblepack
from filodb_tpu.memory.histogram import HistogramBuckets, encode_hist_matrix, decode_hist_matrix


@dataclasses.dataclass(frozen=True)
class ChunkSetInfo:
    """Chunk metadata (ref: store/ChunkSetInfo.scala:60-70: id = timeuuid-like,
    ingestionTime, numRows, startTime, endTime)."""
    chunk_id: int
    ingestion_time_ms: int
    num_rows: int
    start_time_ms: int
    end_time_ms: int


@dataclasses.dataclass
class ColumnChunk:
    """One encoded column of a chunk."""
    kind: str        # 'ts-dd' | 'f64-xor' | 'f64-i64dd' | 'i64-dd' | 'hist-2d'
    payload: bytes
    base: int = 0             # ts-dd/i64-dd: line base
    slope: int = 0            # ts-dd/i64-dd: line slope
    num_buckets: int = 0      # hist-2d

    @property
    def nbytes(self) -> int:
        return len(self.payload)


@dataclasses.dataclass
class ChunkSet:
    info: ChunkSetInfo
    columns: Dict[str, ColumnChunk]
    bucket_scheme: Optional[HistogramBuckets] = None

    @property
    def nbytes(self) -> int:
        return sum(c.nbytes for c in self.columns.values())


def encode_ts_column(ts: np.ndarray) -> ColumnChunk:
    base, slope, payload = nibblepack.pack_timestamps(ts)
    return ColumnChunk("ts-dd", payload, base=base, slope=slope)


def encode_double_column(vals: np.ndarray) -> ColumnChunk:
    """Doubles: delta-delta-as-long when all values are integral (the
    DeltaDeltaVector trick, ref: memory/.../format/vectors/DoubleVector.scala
    delta-delta-as-long 'when integral' — real counters are integers and
    pack to ~1-2 B/sample), XOR-mantissa packing otherwise."""
    v = np.asarray(vals, dtype=np.float64)
    if (len(v) and np.isfinite(v).all() and (v == np.floor(v)).all()
            and (np.abs(v) < 2.0**53).all()):
        base, slope, deltas = nibblepack.delta_delta_encode(
            v.astype(np.int64))
        return ColumnChunk("f64-i64dd", nibblepack.pack_i64(deltas),
                           base=base, slope=slope)
    return ColumnChunk("f64-xor", nibblepack.pack_f64_xor(v))


def encode_long_column(vals: np.ndarray) -> ColumnChunk:
    base, slope, deltas = nibblepack.delta_delta_encode(vals)
    return ColumnChunk("i64-dd", nibblepack.pack_i64(deltas), base=base, slope=slope)


def encode_hist_column(mat: np.ndarray) -> ColumnChunk:
    return ColumnChunk("hist-2d", encode_hist_matrix(mat), num_buckets=mat.shape[1])


def decode_column(col: ColumnChunk, num_rows: int) -> np.ndarray:
    if col.kind == "ts-dd":
        return nibblepack.unpack_timestamps(col.base, col.slope, col.payload, num_rows)
    if col.kind == "f64-xor":
        return nibblepack.unpack_f64_xor(col.payload, num_rows)
    if col.kind == "f64-i64dd":
        return nibblepack.delta_delta_decode(
            col.base, col.slope,
            nibblepack.unpack_i64(col.payload, num_rows)).astype(np.float64)
    if col.kind == "i64-dd":
        return nibblepack.delta_delta_decode(
            col.base, col.slope, nibblepack.unpack_i64(col.payload, num_rows))
    if col.kind == "hist-2d":
        return decode_hist_matrix(col.payload, num_rows, col.num_buckets)
    raise ValueError(f"unknown column chunk kind {col.kind!r}")


# itertools.count.__next__ is atomic under the GIL — flush encoding runs on
# a thread pool, and a `x[0] += 1` load/add/store would race there
_next_chunk_id = itertools.count(1)


def make_chunk_id() -> int:
    """Monotonic chunk id (the reference uses timeuuid ordering,
    ref ChunkSetInfo 'id=timeuuid'); monotonicity is what recovery relies on."""
    return next(_next_chunk_id)


def encode_chunkset(ts: np.ndarray,
                    columns: Dict[str, np.ndarray],
                    col_types: Dict[str, str],
                    ingestion_time_ms: int,
                    bucket_scheme: Optional[HistogramBuckets] = None) -> ChunkSet:
    """Encode one sealed chunk.  `columns` excludes the timestamp column;
    `col_types` maps column name -> 'double' | 'long' | 'hist'."""
    ts = np.asarray(ts, dtype=np.int64)
    n = len(ts)
    info = ChunkSetInfo(make_chunk_id(), ingestion_time_ms, n,
                        int(ts[0]) if n else 0, int(ts[-1]) if n else 0)
    encoded: Dict[str, ColumnChunk] = {"timestamp": encode_ts_column(ts)}
    for name, vals in columns.items():
        t = col_types[name]
        if t == "double":
            encoded[name] = encode_double_column(vals)
        elif t == "long":
            encoded[name] = encode_long_column(vals)
        elif t == "hist":
            encoded[name] = encode_hist_column(vals)
        else:
            raise ValueError(f"unsupported column type {t!r}")
    return ChunkSet(info, encoded, bucket_scheme)


# |first|, |last| under this keep a row's slope exact in float64 division,
# as the one-series encoder's Python integers are
_DD_EXACT = 1 << 52


def _dd_chunks(kind: str, t: np.ndarray) -> List[ColumnChunk]:
    """delta_delta_encode + pack_i64 of every row of `t` [R, n] int64."""
    n = t.shape[1]
    base = t[:, 0]
    if n > 1:
        slope = np.rint((t[:, -1] - base) / (n - 1)).astype(np.int64)
    else:
        slope = np.zeros(len(t), np.int64)
    line = base[:, None] + slope[:, None] * np.arange(n, dtype=np.int64)
    payloads = nibblepack.pack_rows(nibblepack.zigzag_encode(t - line))
    return [ColumnChunk(kind, p, base=b, slope=s)
            for p, b, s in zip(payloads, base.tolist(), slope.tolist())]


def _long_rows(kind: str, t: np.ndarray, one) -> List[ColumnChunk]:
    """`one(row)` of every row of an integer block, the rows whose slope
    float64 holds exactly in whole-block calls."""
    out: List[Optional[ColumnChunk]] = [None] * len(t)
    ends = t[:, [0, -1]]
    exact = ((ends > -_DD_EXACT) & (ends < _DD_EXACT)).all(axis=1)
    idx = np.flatnonzero(exact)
    for i, c in zip(idx.tolist(), _dd_chunks(kind, t[idx])):
        out[i] = c
    for i in np.flatnonzero(~exact).tolist():
        out[i] = one(t[i])
    return out


def _double_rows(vals: np.ndarray) -> List[ColumnChunk]:
    """encode_double_column of every row of `vals` [R, n]."""
    v = np.asarray(vals, dtype=np.float64)
    out: List[Optional[ColumnChunk]] = [None] * len(v)
    integral = (np.isfinite(v) & (v == np.floor(v))
                & (np.abs(v) < 2.0**53)).all(axis=1)
    idx = np.flatnonzero(integral)
    if idx.size:
        for i, c in zip(idx.tolist(), _long_rows(
                "f64-i64dd", v[idx].astype(np.int64),
                encode_double_column)):
            out[i] = c
    idx = np.flatnonzero(~integral)
    if idx.size:
        bits = np.ascontiguousarray(v[idx]).view(np.uint64)
        xored = bits.copy()
        xored[:, 1:] ^= bits[:, :-1]
        for i, p in zip(idx.tolist(), nibblepack.pack_rows(xored)):
            out[i] = ColumnChunk("f64-xor", p)
    return out


def encode_chunksets(ts: np.ndarray, columns: Dict[str, np.ndarray],
                     col_types: Dict[str, str], ingestion_time_ms: int,
                     bucket_scheme: Optional[HistogramBuckets] = None
                     ) -> List[ChunkSet]:
    """encode_chunkset of every row of a block: `ts` [R, n] with n >= 1,
    each column [R, n] (a histogram's [R, n, B]).  The same chunks bit for
    bit (chunk ids in row order), in a few whole-block NumPy calls and one
    codec call a column where encode_chunkset makes some forty a series: a
    flush pass beside six requests in flight costs what it hands the
    interpreter lock over, not what it computes (PERF.md section 6)."""
    ts = np.asarray(ts, dtype=np.int64)
    encoded = {"timestamp": _long_rows("ts-dd", ts, encode_ts_column)}
    for name, vals in columns.items():
        t = col_types[name]
        if t == "double":
            encoded[name] = _double_rows(vals)
        elif t == "long":
            encoded[name] = _long_rows("i64-dd", np.asarray(
                vals, dtype=np.int64), encode_long_column)
        elif t == "hist":
            encoded[name] = [encode_hist_column(m) for m in vals]
        else:
            raise ValueError(f"unsupported column type {t!r}")
    n = ts.shape[1]
    return [ChunkSet(ChunkSetInfo(make_chunk_id(), ingestion_time_ms, n,
                                  first, last),
                     {name: col[r] for name, col in encoded.items()},
                     bucket_scheme)
            for r, (first, last) in enumerate(zip(ts[:, 0].tolist(),
                                                  ts[:, -1].tolist()))]


def decode_chunkset(cs: ChunkSet) -> Dict[str, np.ndarray]:
    return {name: decode_column(col, cs.info.num_rows)
            for name, col in cs.columns.items()}
