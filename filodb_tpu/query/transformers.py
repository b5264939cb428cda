"""RangeVectorTransformers: the per-plan result pipeline
(PeriodicSamplesMapper, aggregation map/present, instant functions,
label/sort/limit/scalar mappers).

Split from query/exec.py (round 4, no behavior change).
ref: query/.../exec/RangeVectorTransformer.scala:36,
AggrOverRangeVectors.scala, PeriodicSamplesMapper.scala.
"""
from __future__ import annotations

import math
import dataclasses
import logging
import os
import threading
from typing import Callable, Dict, List, Optional, Sequence, Tuple, Union

import numpy as np
import jax.numpy as jnp

from filodb_tpu.core.index import ColumnFilter, Equals
from filodb_tpu.ops import agg as agg_ops
from filodb_tpu.ops import hist as hist_ops
from filodb_tpu.ops.instant import (INSTANT_FUNCTIONS, ARITH_OPERATORS,
                                    COMPARISON_OPERATORS, apply_binary_op)
from filodb_tpu.ops import counter as counter_ops
from filodb_tpu.ops.rangefns import RANGE_FUNCTIONS, evaluate_range_function
from filodb_tpu.ops.timewindow import PAD_TS, to_offsets, make_window_ends
from filodb_tpu.query.rangevector import (QueryContext, QueryResult, QueryStats,
                                          RangeVectorKey, ResultBlock,
                                          concat_blocks, remove_nan_series)

from filodb_tpu.query.execbase import (
    AggPartial, Data, GroupCardinalityError, HistQuantileAnswer, RawBlock,
    ScalarResult, _block_empty, _lru_touch, agg_token, present_partial)


# ------------------------------------------------------------- transformers


class RangeVectorTransformer:
    """ref: exec/RangeVectorTransformer.scala:36."""

    def apply(self, data: Data, ctx: QueryContext, stats: QueryStats,
              source=None) -> Data:
        raise NotImplementedError

    def args_str(self) -> str:
        return ""

    def __str__(self):
        return f"{type(self).__name__}({self.args_str()})"


@dataclasses.dataclass
class PeriodicSamplesMapper(RangeVectorTransformer):
    """Raw samples -> regular step grid, optional range function
    (ref: exec/PeriodicSamplesMapper.scala:27)."""
    start_ms: int
    step_ms: int
    end_ms: int
    window_ms: Optional[int] = None     # None => plain lookback sampling
    function: Optional[str] = None
    function_args: Tuple[float, ...] = ()
    offset_ms: int = 0
    lookback_ms: int = 5 * 60 * 1000

    def args_str(self):
        return (f"start={self.start_ms}, step={self.step_ms}, end={self.end_ms}, "
                f"window={self.window_ms}, functionId={self.function}, "
                f"offset={self.offset_ms}")

    def apply(self, data: Data, ctx: QueryContext, stats: QueryStats,
              source=None) -> Data:
        wends = make_window_ends(self.start_ms, self.end_ms, self.step_ms)
        if data is None or (isinstance(data, RawBlock) and not data.keys):
            return _block_empty(wends)
        assert isinstance(data, RawBlock), "PeriodicSamplesMapper needs raw data"
        window = self.window_ms if self.window_ms else self.lookback_ms
        fn = self.function
        base = data.base_ms
        # timestamp(): the kernel computes f32 offset-seconds (exact for
        # query-sized ranges); the epoch base adds back below in f64 — f32
        # cannot hold epoch seconds to sub-minute precision
        kernel_base = 0 if fn == "timestamp" else base
        # offset: shift the window grid back, evaluate, keep original stamps
        eval_wends = wends - self.offset_ms
        wends_off = (eval_wends - base).astype(np.int32)
        vals = data.values
        vb = data.vbase
        # shared scrape grid: ship ONE [1, T] offset row and let it
        # broadcast through the kernel (exact for every range function —
        # window bounds come from row 0 and every gather takes the
        # column fast path).  Halves the general path's HBM timestamp
        # traffic and skips the S-fold ts transfer entirely.
        shared = data.shared_grid      # rows with phases ship their own
        ts_in = data.ts_off[:1] if shared else data.ts_off
        if vals.ndim == 3:
            S, T, B = vals.shape
            flat = np.moveaxis(vals, 2, 1).reshape(S * B, T)
            ts_rep = ts_in if shared else np.repeat(data.ts_off, B, axis=0)
            vb_flat = None if vb is None else jnp.asarray(vb).reshape(S * B)
            out = np.asarray(evaluate_range_function(
                jnp.asarray(ts_rep), jnp.asarray(flat),
                jnp.asarray(wends_off), window, fn,
                tuple(self.function_args), base_ms=kernel_base,
                vbase=vb_flat, precorrected=data.precorrected,
                shared_grid=shared, dense=data.dense))
            out = np.moveaxis(out.reshape(S, B, -1), 1, 2)     # [S, W, B]
        else:
            out = np.asarray(evaluate_range_function(
                jnp.asarray(ts_in), jnp.asarray(vals),
                jnp.asarray(wends_off), window, fn,
                tuple(self.function_args), base_ms=kernel_base,
                vbase=None if vb is None else jnp.asarray(vb),
                precorrected=data.precorrected, shared_grid=shared,
                dense=data.dense))
        if fn == "timestamp":
            out = out.astype(np.float64) + base / 1000.0
        return ResultBlock(data.keys, wends, out, data.bucket_les,
                           cache_token=getattr(data, "cache_token", None))


@dataclasses.dataclass
class RepeatToGridMapper(RangeVectorTransformer):
    """PromQL `@` modifier finisher: the upstream mapper evaluated on a
    single-step grid pinned at the @ timestamp; tile that one column
    across the query's output grid (Prometheus: the pinned value at every
    step)."""
    start_ms: int
    step_ms: int
    end_ms: int

    def args_str(self):
        return (f"start={self.start_ms}, step={self.step_ms}, "
                f"end={self.end_ms}")

    def apply(self, data: Data, ctx: QueryContext, stats: QueryStats,
              source=None) -> Data:
        wends = make_window_ends(self.start_ms, self.end_ms, self.step_ms)
        if data is None:
            return None
        assert isinstance(data, ResultBlock), "@ repeat needs periodic data"
        vals = np.asarray(data.values)
        assert vals.shape[1] == 1, "@ inner grid must be single-step"
        reps = (1, len(wends)) + (1,) * (vals.ndim - 2)
        return ResultBlock(data.keys, wends, np.tile(vals, reps),
                           data.bucket_les,
                           cache_token=data.cache_token)


def _count_hist_quantile(on_device: bool) -> None:
    """A quantile over a native-histogram block, and whether the device
    call's epilogue answered it: hist_device_quantiles_total over
    hist_quantile_requests_total is the share that did."""
    from filodb_tpu.utils.metrics import registry
    registry.counter("hist_quantile_requests").increment()
    registry.counter("hist_device_quantiles").increment(int(on_device))


@dataclasses.dataclass
class InstantVectorFunctionMapper(RangeVectorTransformer):
    """ref: exec/RangeVectorTransformer.scala:61."""
    function: str
    args: Tuple = ()

    def args_str(self):
        return f"function={self.function}"

    def apply(self, data: Data, ctx: QueryContext, stats: QueryStats,
              source=None) -> Data:
        if isinstance(data, HistQuantileAnswer):
            # this mapper's quantile, finished on the device with the
            # merge under it (exprfuse recognised the tree by this mapper)
            assert self.function == "histogram_quantile" \
                and float(self.args[0]) == data.q
            _count_hist_quantile(on_device=True)
            return data.block
        if not isinstance(data, ResultBlock) or data.num_series == 0:
            return data
        vals = data.values
        if self.function in ("histogram_quantile", "histogram_max_quantile"):
            q = float(self._arg_value(self.args[0], source))
            if not data.is_histogram:
                # classic Prometheus histograms: `_bucket` series carrying
                # cumulative counts in `le` labels (upstream
                # promql/quantile.go bucketQuantile; the reference accepts
                # both forms, prometheus/.../PrometheusModel.scala)
                return self._classic_bucket_quantile(q, data)
            _count_hist_quantile(on_device=False)
            # no jnp pre-conversion: host [G, W, B] comps take the
            # numpy twin inside histogram_quantile (a device round trip
            # here cost a ~70 ms dispatch per quantile panel)
            from filodb_tpu.utils.metrics import span
            with span("exec.hist_quantile"):
                out = np.asarray(hist_ops.histogram_quantile(
                    q, vals, np.asarray(data.bucket_les)))
            return ResultBlock(data.keys, data.wends, out,
                               cache_token=data.cache_token)
        if self.function == "histogram_bucket":
            le = float(self._arg_value(self.args[0], source))
            out = np.asarray(hist_ops.histogram_bucket(
                le, jnp.asarray(vals), jnp.asarray(data.bucket_les)))
            return ResultBlock(data.keys, data.wends, out,
                               cache_token=data.cache_token)
        fn = INSTANT_FUNCTIONS[self.function]
        # elementwise functions broadcast per-step scalar args over [S, W]
        extra = [np.asarray(self._arg_value(a, source, per_step=True))
                 for a in self.args]
        out = np.asarray(fn(jnp.asarray(vals),
                            *[jnp.asarray(x) for x in extra]))
        return ResultBlock(data.keys, data.wends, out, data.bucket_les,
                           cache_token=data.cache_token)

    @staticmethod
    def _classic_bucket_quantile(q: float, data: ResultBlock) -> ResultBlock:
        """histogram_quantile over le-labeled `_bucket` series: group by
        the labels minus `le`, assemble each group's cumulative-count
        matrix in ascending le order, and reuse the native quantile
        kernel (it already applies the ensureMonotonic fixup and the
        first/+Inf-bucket edge rules).  Groups without a +Inf bucket are
        dropped, matching upstream.  Groups sharing one le ladder batch
        into a single [G, W, B] kernel call (the repo's batch-dense rule);
        an absent bucket sample (scrape gap / later-born bucket series)
        fills down from the bucket below — it contributes no extra
        observations instead of poisoning the group's quantile to NaN."""
        vals = np.asarray(data.values)
        groups: Dict[tuple, list] = {}
        for i, k in enumerate(data.keys):
            le_txt = k.labels_dict.get("le")
            if le_txt is None:
                continue
            try:
                le = float(le_txt)
            except ValueError:
                continue
            gk = k.without(("le", "_metric_", "__name__")).labels
            groups.setdefault(gk, []).append((le, i))
        by_ladder: Dict[tuple, list] = {}
        for gk, entries in sorted(groups.items()):
            entries.sort(key=lambda e: e[0])
            les = tuple(e[0] for e in entries)
            if len(les) < 2 or not math.isinf(les[-1]):
                continue                  # upstream requires an +Inf bucket
            mat = vals[[e[1] for e in entries]]           # [B, W]
            if np.isnan(mat).any():
                mat = mat.copy()
                mat[0] = np.where(np.isnan(mat[0]), 0.0, mat[0])
                for bi in range(1, mat.shape[0]):
                    mat[bi] = np.where(np.isnan(mat[bi]), mat[bi - 1],
                                       mat[bi])
            by_ladder.setdefault(les, []).append((gk, mat))
        keys, rows = [], []
        for les, members in by_ladder.items():
            stacked = np.stack([m.T for _, m in members])  # [G, W, B]
            out = np.asarray(hist_ops.histogram_quantile(
                q, stacked, np.array(les)))
            for (gk, _), row in zip(members, out):
                keys.append(RangeVectorKey(gk))
                rows.append(row)
        if not keys:
            return ResultBlock([], data.wends,
                               np.zeros((0, len(data.wends))))
        return ResultBlock(keys, data.wends, np.stack(rows))

    @staticmethod
    def _arg_value(a, source, per_step: bool = False):
        """Resolve a (possibly deferred) scalar argument.  per_step returns a
        [W] array for elementwise functions; otherwise a single float — a
        genuinely time-varying scalar is rejected rather than silently
        collapsed to its first step."""
        if hasattr(a, "resolve"):                 # deferred scalar subplan
            a = a.resolve(source)
        if isinstance(a, ScalarResult):
            if len(a.values) == 0:
                return np.nan
            if per_step:
                return a.values
            vals = a.values[~np.isnan(a.values)]
            if len(vals) and not np.all(vals == vals[0]):
                raise ValueError(
                    "time-varying scalar argument not supported for this "
                    "function")
            return a.values[0] if len(vals) == 0 else vals[0]
        return a


@dataclasses.dataclass
class ScalarOperationMapper(RangeVectorTransformer):
    """vector op scalar (ref: RangeVectorTransformer.scala:186)."""
    operator: str
    scalar: Union[float, ScalarResult]
    scalar_is_lhs: bool = False
    bool_modifier: bool = False

    def args_str(self):
        return f"operator={self.operator}, scalarOnLhs={self.scalar_is_lhs}"

    def apply(self, data: Data, ctx: QueryContext, stats: QueryStats,
              source=None) -> Data:
        if not isinstance(data, ResultBlock) or data.num_series == 0:
            return data
        vals = np.asarray(data.values)
        scalar = self.scalar
        if hasattr(scalar, "resolve"):            # deferred scalar subplan
            scalar = scalar.resolve(source)
        if isinstance(scalar, ScalarResult):
            # empty scalar stream (e.g. scalar(absent-selector) across
            # shards) behaves as NaN, same as the 1-shard path
            sv = (scalar.values[None, :] if scalar.values.shape[0]
                  == vals.shape[1] else np.full((1, 1), np.nan))
        else:
            sv = np.full((1, 1), float(scalar))
        sv = np.broadcast_to(sv, vals.shape)
        a, b = (sv, vals) if self.scalar_is_lhs else (vals, sv)
        # comparison filtering keeps the VECTOR side's value
        out = np.asarray(apply_binary_op(
            jnp.asarray(a), jnp.asarray(b), op=self.operator,
            bool_modifier=self.bool_modifier,
            keep_side=("rhs" if self.scalar_is_lhs else "lhs")))
        return ResultBlock(data.keys, data.wends, out, data.bucket_les,
                           cache_token=data.cache_token)


def _group_ids(keys: Sequence[RangeVectorKey], by: Tuple[str, ...],
               without: Tuple[str, ...]) -> Tuple[np.ndarray, List[RangeVectorKey]]:
    """Host-side grouping: series key -> group key (by/without semantics)."""
    gmap: Dict[RangeVectorKey, int] = {}
    gids = np.empty(len(keys), dtype=np.int32)
    gkeys: List[RangeVectorKey] = []
    for i, k in enumerate(keys):
        if by:
            gk = k.only(by)
        elif without:
            gk = k.without(tuple(without) + ("_metric_", "__name__"))
        else:
            gk = RangeVectorKey(())
        gid = gmap.get(gk)
        if gid is None:
            gid = len(gkeys)
            gmap[gk] = gid
            gkeys.append(gk)
        gids[i] = gid
    return gids, gkeys


def _group_ids_of_part(gids: np.ndarray, gkeys: List[RangeVectorKey],
                       member: np.ndarray
                       ) -> Tuple[np.ndarray, List[RangeVectorKey]]:
    """_group_ids' answer for the keys at positions `member` (ascending) of
    a key sequence whose own answer is (gids, gkeys): the groups that still
    hold a series, numbered by first appearance, in array operations in
    place of a Python loop over the keys (0.47 s at 73,000 of them)."""
    g = gids[member]
    first = np.full(len(gkeys), g.size, np.int64)
    first[g[::-1]] = np.arange(g.size - 1, -1, -1)     # the last write wins
    kept = np.flatnonzero(first < g.size)
    kept = kept[np.argsort(first[kept], kind="stable")]
    rank = np.empty(len(gkeys), np.int32)
    rank[kept] = np.arange(kept.size, dtype=np.int32)
    return rank[g], [gkeys[k] for k in kept.tolist()]


_CANDIDATE_OPS = {"topk", "bottomk", "count_values"}

# host group-id cache: (cache_token, by, without) -> (gids, gkeys).
# _group_ids is an O(S) Python loop (key.only() per series) that
# dominated warm general-path queries (~0.3s of 0.4s at 65k series,
# ~5s at 1M); the token (shard keys_serial, keys_epoch, pids bytes)
# identifies the key set exactly, so repeat dashboard queries do a
# dict hit instead.  Entries are treated as immutable.
_HOST_GROUP_CACHE: Dict[tuple, tuple] = {}
_HOST_GROUP_LOCK = threading.Lock()


def _group_ids_cached(token, keys, by, without):
    if token is None:
        return _group_ids(keys, by, without)
    k = (token, tuple(by), tuple(without))
    with _HOST_GROUP_LOCK:
        ent = _lru_touch(_HOST_GROUP_CACHE, k)
    if ent is not None and len(ent[0]) == len(keys):
        return ent
    gids, gkeys = _group_ids(keys, by, without)
    with _HOST_GROUP_LOCK:
        # entries from OLDER epochs of the same shard are dead — a
        # reclaimed pid may have been recycled for a different series.
        # Strictly older only: an in-flight query holding a pre-prune
        # token must not evict valid newer-epoch entries, nor install
        # its own never-hittable stale one.  Only LEAF tokens carry the
        # (serial, epoch:int, ...) shape this compares; derived tokens
        # (execbase.agg_token / _reduced_token) embed the leaf epoch
        # inside themselves — a prune mints a NEW token, and the stale
        # entry ages out through the LRU cap instead.
        if len(token) > 1 and isinstance(token[1], int):
            def _epoch(o):
                t = o[0]
                return (t[1] if t[0] == token[0] and len(t) > 1
                        and isinstance(t[1], int) else None)
            for old in [o for o in _HOST_GROUP_CACHE
                        if _epoch(o) is not None and _epoch(o) < token[1]]:
                del _HOST_GROUP_CACHE[old]
            if any(_epoch(o) is not None and _epoch(o) > token[1]
                   for o in _HOST_GROUP_CACHE):
                return gids, gkeys
        _HOST_GROUP_CACHE[k] = (gids, gkeys)
        # a chip's four shards times the four groupings of a dashboard,
        # twice: the fused leaf takes the ids of a range's part from the
        # whole selection's (leafexec._WholeSet.group_ids), so those stay
        while len(_HOST_GROUP_CACHE) > 32:
            _HOST_GROUP_CACHE.pop(next(iter(_HOST_GROUP_CACHE)))
    return gids, gkeys


@dataclasses.dataclass
class AggregateMapReduce(RangeVectorTransformer):
    """Map phase of 3-phase aggregation (ref: AggrOverRangeVectors.scala:76)."""
    op: str
    params: Tuple = ()
    by: Tuple[str, ...] = ()
    without: Tuple[str, ...] = ()

    def args_str(self):
        return (f"aggrOp={self.op}, aggrParams={list(self.params)}, "
                f"without={list(self.without)}, by={list(self.by)}")

    def apply(self, data: Data, ctx: QueryContext, stats: QueryStats,
              source=None) -> Data:
        assert isinstance(data, (ResultBlock, type(None)))
        if data is None or data.num_series == 0:
            return None
        vals = np.asarray(data.values)
        gids, gkeys = _group_ids_cached(
            getattr(data, "cache_token", None), data.keys, self.by,
            self.without)
        limit = ctx.planner_params.group_by_cardinality_limit
        if limit and len(gkeys) > limit:
            raise GroupCardinalityError(
                f"group-by cardinality limit {limit} exceeded "
                f"({len(gkeys)} groups)")
        if data.is_histogram and self.op == "sum":
            # histogram sum: elementwise over buckets — [G, W, B+1] where the
            # extra slot counts present series (empty-step masking)
            present = ~np.isnan(vals)
            comp = np.where(present, vals, 0.0)
            G = len(gkeys)
            S, W, B = vals.shape
            agg = np.zeros((G, W, B + 1))
            np.add.at(agg[..., :B], gids, comp)     # view write-through
            np.add.at(agg[..., B], gids, present.any(axis=2).astype(float))
            return AggPartial("hist_sum", gkeys, data.wends, comp=agg,
                              params=self.params, bucket_les=data.bucket_les,
                              cache_token=agg_token(
                                  "hist_sum", self.by, self.without,
                                  data.cache_token))
        if self.op == "quantile" and vals.ndim == 2:
            from filodb_tpu.ops import sketch as sketch_ops
            sk = sketch_ops.sketch_from_values(vals, gids, len(gkeys))
            return AggPartial(self.op, gkeys, data.wends, sketch=sk,
                              params=self.params,
                              cache_token=agg_token(
                                  self.op, self.by, self.without,
                                  data.cache_token))
        if self.op in _CANDIDATE_OPS or self.op == "quantile":
            cand_keys, cand_vals, cand_groups = self._candidates(
                data, vals, gids, len(gkeys))
            return AggPartial(self.op, gkeys, data.wends, cand_keys=cand_keys,
                              cand_vals=cand_vals, cand_groups=cand_groups,
                              params=self.params)
        comp = np.asarray(agg_ops.map_phase(
            self.op, jnp.asarray(vals), jnp.asarray(gids), len(gkeys)))
        return AggPartial(self.op, gkeys, data.wends, comp=comp,
                          params=self.params,
                          cache_token=agg_token(self.op, self.by,
                                                self.without,
                                                data.cache_token))

    def _candidates(self, data, vals, gids, num_groups):
        if self.op in ("topk", "bottomk"):
            k = int(self.params[0])
            mask = np.asarray(agg_ops.topk_mask(
                jnp.asarray(vals), jnp.asarray(gids), num_groups, k,
                largest=(self.op == "topk")))
            keep = mask.any(axis=1)
            rows = np.flatnonzero(keep)
        else:
            rows = np.arange(len(data.keys))
        return ([data.keys[int(r)] for r in rows], vals[rows], gids[rows])


class AggregatePresenter(RangeVectorTransformer):
    """Present phase (ref: AggrOverRangeVectors.scala:125)."""

    def __init__(self, op: str, params: Tuple = ()):
        self.op = op
        self.params = params

    def args_str(self):
        return f"aggrOp={self.op}, aggrParams={list(self.params)}"

    def apply(self, data: Data, ctx: QueryContext, stats: QueryStats,
              source=None) -> Data:
        if data is None or isinstance(data, HistQuantileAnswer):
            return data
        assert isinstance(data, AggPartial)
        return present_partial(data)


@dataclasses.dataclass
class AbsentFunctionMapper(RangeVectorTransformer):
    """absent() (ref: RangeVectorTransformer.scala:340)."""
    filters: Tuple[ColumnFilter, ...]
    start_ms: int = 0
    step_ms: int = 0
    end_ms: int = 0

    def args_str(self):
        return "functionId=absent"

    def apply(self, data: Data, ctx: QueryContext, stats: QueryStats,
              source=None) -> Data:
        wends = (data.wends if isinstance(data, ResultBlock)
                 else make_window_ends(self.start_ms, self.end_ms,
                                       max(self.step_ms, 1)))
        if isinstance(data, ResultBlock) and data.num_series:
            present = ~np.isnan(np.asarray(data.values)).all(axis=0)
        else:
            present = np.zeros(len(wends), dtype=bool)
        out = np.where(present, np.nan, 1.0)[None, :]
        labels = {f.column: f.value for f in self.filters
                  if isinstance(f, Equals)
                  and f.column not in ("__name__", "_metric_")}
        return ResultBlock([RangeVectorKey.make(labels)], wends, out)


@dataclasses.dataclass
class SortFunctionMapper(RangeVectorTransformer):
    """sort()/sort_desc() by mean value (ref: RangeVectorTransformer.scala:254)."""
    descending: bool = False

    def args_str(self):
        return f"function={'sort_desc' if self.descending else 'sort'}"

    def apply(self, data: Data, ctx: QueryContext, stats: QueryStats,
              source=None) -> Data:
        if not isinstance(data, ResultBlock) or data.num_series <= 1:
            return data
        with np.errstate(invalid="ignore"):
            means = np.nanmean(np.asarray(data.values), axis=1)
        means = np.where(np.isnan(means), -np.inf if not self.descending else np.inf,
                         means)
        order = np.argsort(-means if self.descending else means, kind="stable")
        return data.select(order)


@dataclasses.dataclass
class MiscellaneousFunctionMapper(RangeVectorTransformer):
    """label_replace / label_join (ref: rangefn/MiscellaneousFunction.scala)."""
    function: str
    string_args: Tuple[str, ...] = ()

    def args_str(self):
        return f"function={self.function}"

    def apply(self, data: Data, ctx: QueryContext, stats: QueryStats,
              source=None) -> Data:
        if not isinstance(data, ResultBlock):
            return data
        import re
        if self.function == "label_replace":
            dst, repl, src, regex = self.string_args
            pat = re.compile("^(?:" + regex + ")$")
            keys = []
            for k in data.keys:
                lbls = k.labels_dict
                m = pat.match(lbls.get(src, ""))
                if m:
                    val = m.expand(_dollar_to_backslash(repl))
                    if val:
                        lbls[dst] = val
                    else:
                        lbls.pop(dst, None)
                keys.append(RangeVectorKey.make(lbls))
            keys, vals = _merge_relabeled(keys, data, "label_replace")
            return ResultBlock(keys, data.wends, vals, data.bucket_les)
        if self.function == "label_join":
            dst, sep, *srcs = self.string_args
            keys = []
            for k in data.keys:
                lbls = k.labels_dict
                val = sep.join(lbls.get(s, "") for s in srcs)
                if val:
                    lbls[dst] = val
                else:
                    lbls.pop(dst, None)
                keys.append(RangeVectorKey.make(lbls))
            keys, vals = _merge_relabeled(keys, data, "label_join")
            return ResultBlock(keys, data.wends, vals, data.bucket_les)
        raise ValueError(f"unknown misc function {self.function}")


def _merge_relabeled(keys, data, fn_name: str):
    """Upstream semantics for relabeling that lands several series on
    one labelset: it is an ERROR only when the duplicates co-occur in
    the same evaluation step ("vector cannot contain metrics with the
    same labelset"); series whose samples never overlap (e.g. the two
    halves of a restart, absent-as-NaN here) MERGE into one series
    (ref: prometheus functions.go label_replace + per-step Series
    dedup).  Returns (keys, values) with disjoint duplicates merged."""
    groups: dict = {}
    for i, k in enumerate(keys):
        groups.setdefault(k.labels, []).append(i)
    if all(len(rows) == 1 for rows in groups.values()):
        return keys, data.values
    vals = np.asarray(data.values)
    out_keys, out_rows = [], []
    for sig, rows in groups.items():
        if len(rows) == 1:
            out_keys.append(keys[rows[0]])
            out_rows.append(vals[rows[0]])
            continue
        sub = vals[rows]                      # [d, W] or [d, W, B]
        # presence is NaN-only (the staleness convention everywhere else:
        # nonleaf dedup, absent()): +/-Inf is a legal sample value (1/0,
        # histogram_quantile overflow) and must collide/merge like any
        # other sample, not vanish (round-5 review)
        present = ~np.isnan(sub)
        if sub.ndim == 3:
            present = present.any(axis=-1)
        if (present.sum(axis=0) > 1).any():
            raise ValueError(
                f"{fn_name}: vector cannot contain metrics with the "
                f"same labelset")
        merged = np.full(sub.shape[1:], np.nan, vals.dtype)
        for d in range(sub.shape[0]):
            m = present[d]
            merged[m] = sub[d][m]
        out_keys.append(keys[rows[0]])
        out_rows.append(merged)
    return out_keys, np.stack(out_rows)


def _dollar_to_backslash(repl: str) -> str:
    """PromQL uses $1; python re.expand uses \\1."""
    import re as _re
    return _re.sub(r"\$(\d+)", r"\\\1", repl)


@dataclasses.dataclass
class LimitFunctionMapper(RangeVectorTransformer):
    limit: int

    def args_str(self):
        return f"limit={self.limit}"

    def apply(self, data: Data, ctx: QueryContext, stats: QueryStats,
              source=None) -> Data:
        if isinstance(data, ResultBlock) and data.num_series > self.limit:
            return data.select(np.arange(self.limit))
        return data


@dataclasses.dataclass
class ScalarFunctionMapper(RangeVectorTransformer):
    """scalar(vector): 1 series -> scalar stream, else NaN (ref:
    RangeVectorTransformer ScalarFunctionMapper)."""
    function: str = "scalar"

    def args_str(self):
        return f"function={self.function}"

    def apply(self, data: Data, ctx: QueryContext, stats: QueryStats,
              source=None) -> Data:
        assert isinstance(data, (ResultBlock, type(None)))
        if data is None or data.num_series != 1:
            wends = data.wends if data is not None else np.zeros(0, np.int64)
            return ScalarResult(wends, np.full(len(wends), np.nan))
        return ScalarResult(data.wends, np.asarray(data.values)[0])


@dataclasses.dataclass
class VectorFunctionMapper(RangeVectorTransformer):
    """vector(scalar) (ref: RangeVectorTransformer VectorFunctionMapper)."""

    def args_str(self):
        return "function=vector"

    def apply(self, data: Data, ctx: QueryContext, stats: QueryStats,
              source=None) -> Data:
        if isinstance(data, ScalarResult):
            return ResultBlock([RangeVectorKey(())], data.wends,
                               data.values[None, :])
        return data

