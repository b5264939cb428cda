"""Non-leaf exec plans: concat/stitch, tree-reduce aggregation, binary
joins and set operators, subqueries.

Split from query/exec.py (round 4, no behavior change).
ref: query/.../exec/DistConcatExec.scala, BinaryJoinExec.scala,
StitchRvsExec.scala, AggrOverRangeVectors.scala:51.
"""
from __future__ import annotations

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
    AggPartial, ExecPlan, NonLeafExecPlan, RawBlock, ScalarResult,
    _block_empty, _union_scheme, reduce_partials)
from filodb_tpu.query.transformers import _group_ids


class DistConcatExec(NonLeafExecPlan):
    """Concatenate child results (ref: exec/DistConcatExec.scala)."""

    # children are same-selector per-shard leaves: a shard listed twice
    # (both owners during a live handoff) must contribute exactly once
    dedup_shard_children = True

    def compose(self, results, stats):
        blocks = [r for r in results if isinstance(r, ResultBlock)]
        raws = [r for r in results if isinstance(r, RawBlock)]
        if raws:
            # raw blocks concat only if same grid/base — planner guarantees.
            # Cross-shard bucket-scheme drift is resolved by rebucketing
            # every block onto the union scheme (HistogramBuckets.scala:340)
            les0 = raws[0].bucket_les
            if any((r.bucket_les is None) != (les0 is None) or (
                    les0 is not None and r.bucket_les is not None
                    and not np.array_equal(les0, r.bucket_les))
                   for r in raws[1:]):
                union = _union_scheme([r.bucket_les for r in raws])
                if union is None:
                    raise ValueError(
                        "cannot concat histogram blocks: some shards carry "
                        "no bucket boundaries")
                # scheme drift across shards is a data-model event worth
                # seeing at /metrics: rebucketing is correct but costs an
                # O(S*T*B) remap per query until retention ages it out
                from filodb_tpu.utils.metrics import registry
                registry.counter("hist_concat_rebuckets").increment()
                from filodb_tpu.memory.histogram import rebucket
                raws = [dataclasses.replace(
                            r,
                            values=rebucket(np.asarray(r.values),
                                            r.bucket_les, union),
                            vbase=(rebucket(np.asarray(r.vbase),
                                            r.bucket_les, union)
                                   if r.vbase is not None
                                   and np.asarray(r.vbase).ndim == 2
                                   else r.vbase),
                            bucket_les=union)
                        if not np.array_equal(r.bucket_les, union) else r
                        for r in raws]
                les0 = union
            keys = []
            for r in raws:
                keys.extend(r.keys)
            T = max(r.ts_off.shape[1] for r in raws)
            def pad(a, fill):
                out = np.full((a.shape[0], T) + a.shape[2:], fill, a.dtype)
                out[:, :a.shape[1]] = a
                return out
            from filodb_tpu.ops.timewindow import PAD_TS
            ts = np.concatenate([pad(r.ts_off, PAD_TS) for r in raws])
            vals = np.concatenate([pad(np.asarray(r.values), np.nan)
                                   for r in raws])
            vbase = None
            if any(r.vbase is not None for r in raws):
                vbase = np.concatenate([
                    np.asarray(r.vbase) if r.vbase is not None
                    else np.zeros(np.asarray(r.values).shape[:1]
                                  + np.asarray(r.values).shape[2:])
                    for r in raws])
            return RawBlock(keys, ts, vals, raws[0].base_ms,
                            raws[0].bucket_les,
                            samples=sum(r.samples for r in raws),
                            vbase=vbase,
                            precorrected=all(r.precorrected for r in raws),
                            # pad NaNs live at PAD_TS slots (excluded via
                            # ts), so raggedness merges as AND over blocks
                            dense=all(r.dense for r in raws))
        return concat_blocks(blocks)


class LocalPartitionDistConcatExec(DistConcatExec):
    """ref: exec/DistConcatExec.scala LocalPartitionDistConcatExec."""


class _AggStreamFold:
    """Incremental fold for a STREAMED ship-everything child: each
    arriving row-slice mini block runs the map phase immediately and
    merges into ONE running AggPartial — the coordinator holds a frame
    plus a [G, W] partial, never the child's full [S, W] block.  The
    candidate ops stay correct piecewise for the same reason they are
    correct per shard: per-piece top-k is a superset of each group's
    true top-k, and the present phase applies the final mask."""

    def __init__(self, op, params, by, without, ctx):
        from filodb_tpu.query.transformers import AggregateMapReduce
        self._mapper = AggregateMapReduce(op, params, by, without)
        self._ctx = ctx
        self._stats = QueryStats()
        self._partial = None

    def add(self, block) -> None:
        p = self._mapper.apply(block, self._ctx, self._stats)
        if p is None:
            return
        self._partial = p if self._partial is None else \
            reduce_partials([self._partial, p])
        # the per-slice map only sees one slice's worth of groups, so
        # the limit must also be enforced on the MERGED partial — the
        # streamed fold raises exactly where non-streamed compose would
        limit = self._ctx.planner_params.group_by_cardinality_limit
        if limit and len(self._partial.group_keys) > limit:
            from filodb_tpu.query.execbase import GroupCardinalityError
            raise GroupCardinalityError(
                f"group-by cardinality limit {limit} exceeded "
                f"({len(self._partial.group_keys)} groups in the "
                f"streamed fold)")

    def result(self):
        return self._partial


# ops whose map phase may run per row slice and reduce across slices
# without changing the presented result (quantile's sketch
# re-compression is merge-tree-dependent — it assembles whole)
_FOLDABLE_OPS = frozenset({"sum", "count", "avg", "min", "max", "stddev",
                           "stdvar", "group", "topk", "bottomk",
                           "count_values"})


class ReduceAggregateExec(NonLeafExecPlan):
    """Reduce phase across shards (ref: AggrOverRangeVectors.scala:51).

    Children normally reply with AggPartial (the map phase rides the
    leaves).  With aggregation pushdown DISABLED (the ship-everything
    A/B baseline, query/pushdown.py), remote children ship their full
    per-series ResultBlocks instead and the map phase runs HERE — by/
    without are carried so the coordinator-side map is possible."""

    # a duplicate shard here would double-count its samples into the
    # aggregate — the dedup contract matters most on this plan
    dedup_shard_children = True

    # node-level reduce (RemoteAggregateExec): the composed partial is an
    # INTERMEDIATE that another reduce will merge — quantile sketches must
    # not re-compress here (reduce_partials compress=False) and candidate
    # partials may prune to the node-local top-k before crossing the wire
    node_level = False

    # the histogram quantile above this reduce, where the children's one
    # device call finished it (an execbase.HistQuantileAnswer, parked by
    # exprfuse.finish_prepared for the one execution that follows)
    hist_answer = None

    def __init__(self, ctx, children, op: str, params: Tuple = (),
                 by: Tuple[str, ...] = (), without: Tuple[str, ...] = ()):
        super().__init__(ctx, children)
        self.op = op
        self.params = params
        self.by = tuple(by)
        self.without = tuple(without)

    def args_str(self):
        return f"aggrOp={self.op}, aggrParams={list(self.params)}"

    def compose(self, results, stats):
        from filodb_tpu.query.transformers import AggregateMapReduce
        mapper = None
        parts = []
        for r in results:
            if isinstance(r, ResultBlock) and r.num_series:
                # ship-everything child (pushdown off): map phase runs
                # coordinator-side over the full shipped series block
                if mapper is None:
                    mapper = AggregateMapReduce(self.op, self.params,
                                                self.by, self.without)
                r = mapper.apply(r, self.ctx, stats)
            if isinstance(r, AggPartial):
                parts.append(r)
        return reduce_partials(parts, compress=not self.node_level)

    def child_stream_fold(self, child):
        if self.op not in _FOLDABLE_OPS:
            return None
        return lambda: _AggStreamFold(self.op, self.params, self.by,
                                      self.without, self.ctx)

    def _do_execute(self, source):
        results, stats = self._gather(source)
        # plan-time pushdown verdict (query/pushdown.py): remote children
        # this aggregation could NOT push surface in ?stats=true /
        # explain analyze / the slowlog next to the pushed counts the
        # dispatchers booked
        npn = getattr(self, "pushdown_not_pushable", 0)
        if npn:
            stats.pushdown_not_pushable += npn
        # the histogram quantile above this reduce came back from the
        # children's device call already (the children's partials hold no
        # sums): nothing to merge
        answer, self.hist_answer = self.hist_answer, None
        if answer is not None:
            return answer, stats
        return self.compose(results, stats), stats


class RemoteAggregateExec(ReduceAggregateExec):
    """Node-level reduce pushdown (query/pushdown.py): children are the
    per-shard map subtrees owned by ONE data node, and the whole plan
    serializes to that node via its PushdownDispatcher — the node runs
    leaf scan + range function + map phase per shard, reduces locally
    (inherited compose = reduce_partials), and replies with a single
    [G, W] AggPartial.  Decoded on the data node the children fall back
    to InProcessPlanDispatcher, so execution there is the ordinary
    scatter-gather one level down (the PR-6 chip-level partial merge,
    promoted to nodes).

    Rank/sketch aggregations push exactly (PR 17): quantile node
    partials concatenate their shards' centroids WITHOUT re-compressing
    (node_level -> reduce_partials compress=False), so the
    coordinator's single merge sees the flat per-shard centroid layout;
    topk/bottomk node partials prune to the per-window node-local
    top-k before replying (ops/select.topk_keep_rows) — rows outside
    every window's local top-k cannot reach any global top-k, the same
    containment the streaming fold relies on."""

    node_level = True

    def compose(self, results, stats):
        part = super().compose(results, stats)
        if part is not None and part.cand_vals is not None \
                and self.op in ("topk", "bottomk") and len(part.cand_vals):
            from filodb_tpu.ops import select as select_ops
            keep = np.asarray(select_ops.topk_keep_rows(
                jnp.asarray(part.cand_vals), jnp.asarray(part.cand_groups),
                len(part.group_keys), int(self.params[0]),
                largest=(self.op == "topk")))
            if not keep.all():
                part = dataclasses.replace(
                    part,
                    cand_keys=[k for k, m in zip(part.cand_keys, keep) if m],
                    cand_vals=part.cand_vals[keep],
                    cand_groups=part.cand_groups[keep])
        return part

    def args_str(self):
        shards = sorted(getattr(c, "shard", -1) for c in self._children)
        return (f"aggrOp={self.op}, aggrParams={list(self.params)}, "
                f"shards={shards}")


class BinaryJoinExec(NonLeafExecPlan):
    """Vector-vector join (ref: exec/BinaryJoinExec.scala:210).

    lhs children come first, then rhs children; the split index separates
    them (mirrors the reference's lhs/rhs Seq[ExecPlan]).
    """

    def __init__(self, ctx, lhs: Sequence[ExecPlan], rhs: Sequence[ExecPlan],
                 operator: str, cardinality: str = "OneToOne",
                 on: Optional[Tuple[str, ...]] = None,
                 ignoring: Tuple[str, ...] = (),
                 include: Tuple[str, ...] = (),
                 bool_modifier: bool = False):
        super().__init__(ctx, list(lhs) + list(rhs))
        self.n_lhs = len(lhs)
        self.operator = operator
        self.cardinality = cardinality
        self.on = tuple(on) if on is not None else None
        self.ignoring = tuple(ignoring)
        self.include = tuple(include)
        self.bool_modifier = bool_modifier

    def args_str(self):
        return (f"binaryOp={self.operator}, on={self.on}, "
                f"ignoring={list(self.ignoring)}")

    def _match_key(self, k: RangeVectorKey) -> RangeVectorKey:
        if self.on is not None:
            return k.only(self.on)
        drop = self.ignoring + ("_metric_", "__name__")
        return k.without(drop)

    def compose(self, results, stats):
        lhs_blocks = [r for r in results[:self.n_lhs] if isinstance(r, ResultBlock)]
        rhs_blocks = [r for r in results[self.n_lhs:] if isinstance(r, ResultBlock)]
        lhs = concat_blocks(lhs_blocks)
        rhs = concat_blocks(rhs_blocks)
        if lhs is None or rhs is None:
            return None
        many_side, one_side = lhs, rhs
        flip = False
        if self.cardinality == "OneToMany":
            many_side, one_side = rhs, lhs
            flip = True
        # label matching resolves host-side ONCE into (mi, oi) index
        # maps, memoized on the operand blocks' cache_token (PR 17 —
        # query/exprfuse.py); the join itself is one jitted
        # gather+binop program over the full value blocks
        from filodb_tpu.query.exprfuse import join_index_maps
        from filodb_tpu.ops.select import gather_binop
        mi, oi, keys = join_index_maps(self, many_side, one_side)
        if not len(mi):
            return None
        mv = jnp.asarray(np.asarray(many_side.values))
        ov = jnp.asarray(np.asarray(one_side.values))
        # a = query LHS values
        a, b, ai, bi = (ov, mv, oi, mi) if flip else (mv, ov, mi, oi)
        out = np.asarray(gather_binop(
            a, b, jnp.asarray(ai), jnp.asarray(bi), op=self.operator,
            bool_modifier=self.bool_modifier, keep_side="lhs"))
        return ResultBlock(keys, many_side.wends, out)

    def _result_labels(self, many_key: RangeVectorKey,
                       one_key: RangeVectorKey) -> RangeVectorKey:
        if self.cardinality in ("ManyToOne", "OneToMany"):
            lbls = many_key.without(("_metric_", "__name__")).labels_dict
            if self.include:
                od = one_key.labels_dict
                for lbl in self.include:
                    if lbl in od:
                        lbls[lbl] = od[lbl]
                    else:
                        lbls.pop(lbl, None)
            return RangeVectorKey.make(lbls)
        if self.on is not None:
            return many_key.only(self.on)
        return many_key.without(self.ignoring + ("_metric_", "__name__"))


class SetOperatorExec(NonLeafExecPlan):
    """and/or/unless (ref: exec/SetOperatorExec.scala)."""

    def __init__(self, ctx, lhs: Sequence[ExecPlan], rhs: Sequence[ExecPlan],
                 operator: str, on: Optional[Tuple[str, ...]] = None,
                 ignoring: Tuple[str, ...] = ()):
        super().__init__(ctx, list(lhs) + list(rhs))
        self.n_lhs = len(lhs)
        self.operator = operator.lower()
        self.on = tuple(on) if on is not None else None
        self.ignoring = tuple(ignoring)

    def args_str(self):
        return f"binaryOp={self.operator}, on={self.on}, ignoring={list(self.ignoring)}"

    def _match_key(self, k: RangeVectorKey) -> RangeVectorKey:
        if self.on is not None:
            return k.only(self.on)
        return k.without(self.ignoring + ("_metric_", "__name__"))

    def _presence_by_key(self, block: ResultBlock) -> Dict[RangeVectorKey, np.ndarray]:
        """match-key -> [W] bool, True where any series with that key has a
        sample at the step.  Vectorized: one `_group_ids` pass maps each
        series to its match-key group, then a single grouped OR
        (`np.logical_or.reduceat` over gid-sorted rows) replaces the old
        per-series Python loop — this sits on every and/or/unless path."""
        vals = np.asarray(block.values)
        if vals.ndim == 3:                       # histogram block
            vals = vals[..., 0]
        S = len(block.keys)
        if S == 0:
            return {}
        if self.on is not None and not self.on:
            # on() with an empty label list: everything shares the empty
            # match key (k.only(()) — _group_ids' falsy-by branch would
            # wrongly take `without` semantics here)
            gids = np.zeros(S, dtype=np.int32)
            gkeys = [RangeVectorKey(())]
        elif self.on is not None:
            gids, gkeys = _group_ids(block.keys, tuple(self.on), ())
        else:
            # ignoring=() must still strip only _metric_/__name__ (the
            # _match_key rule); _group_ids' empty-without branch would
            # collapse everything onto the empty key, so pad with a
            # name no real label can carry
            gids, gkeys = _group_ids(block.keys, (),
                                     tuple(self.ignoring) or ("\x00",))
        present = ~np.isnan(vals)
        order = np.argsort(gids, kind="stable")
        starts = np.searchsorted(gids[order], np.arange(len(gkeys)))
        grouped = np.logical_or.reduceat(present[order], starts, axis=0)
        return {gk: grouped[g] for g, gk in enumerate(gkeys)}

    def compose(self, results, stats):
        lhs = concat_blocks([r for r in results[:self.n_lhs]
                             if isinstance(r, ResultBlock)])
        rhs = concat_blocks([r for r in results[self.n_lhs:]
                             if isinstance(r, ResultBlock)])
        op = self.operator
        if op == "and":
            if lhs is None or rhs is None:
                return None
            rhs_keys = {self._match_key(k) for k in rhs.keys}
            # per-step AND: lhs kept where rhs series present at that step
            rk_rows = self._presence_by_key(rhs)
            rows, outs = [], []
            lvals = np.asarray(lhs.values)
            for i, k in enumerate(lhs.keys):
                mk = self._match_key(k)
                if mk in rhs_keys:
                    rows.append(i)
                    outs.append(np.where(rk_rows[mk], lvals[i], np.nan))
            if not rows:
                return None
            return ResultBlock([lhs.keys[i] for i in rows], lhs.wends,
                               np.stack(outs))
        if op == "or":
            if lhs is None:
                return rhs
            if rhs is None:
                return lhs
            lvals = np.asarray(lhs.values)
            lhs_present = self._presence_by_key(lhs)
            keys = list(lhs.keys)
            vals = [lvals]
            rvals = np.asarray(rhs.values)
            extra_rows, extra_keys = [], []
            for i, k in enumerate(rhs.keys):
                mk = self._match_key(k)
                mask = lhs_present.get(mk)
                row = rvals[i]
                if mask is not None:
                    row = np.where(mask, np.nan, row)
                extra_rows.append(row)
                extra_keys.append(k)
            if extra_rows:
                keys = keys + extra_keys
                vals.append(np.stack(extra_rows))
            return ResultBlock(keys, lhs.wends, np.concatenate(vals))
        if op == "unless":
            if lhs is None:
                return None
            if rhs is None:
                return lhs
            rk_rows = self._presence_by_key(rhs)
            lvals = np.asarray(lhs.values)
            outs = []
            for i, k in enumerate(lhs.keys):
                mk = self._match_key(k)
                mask = rk_rows.get(mk)
                outs.append(np.where(mask, np.nan, lvals[i])
                            if mask is not None else lvals[i])
            return remove_nan_series(
                ResultBlock(list(lhs.keys), lhs.wends, np.stack(outs)))
        raise ValueError(op)


class SubqueryExec(NonLeafExecPlan):
    """Evaluate an outer range function over an inner periodic series
    (foo[5m:1m] with rate/max_over_time/... outside).  The inner child's
    step-grid samples are treated as raw samples for the outer window kernel
    (ref: exec/... subquery handling via PeriodicSamplesMapper on inner)."""

    def __init__(self, ctx, children, start_ms, step_ms, end_ms, function,
                 function_args, subquery_window_ms, subquery_step_ms,
                 offset_ms=0):
        super().__init__(ctx, children)
        self.start_ms, self.step_ms, self.end_ms = start_ms, step_ms, end_ms
        self.function = function
        self.function_args = tuple(function_args)
        self.subquery_window_ms = subquery_window_ms
        self.subquery_step_ms = subquery_step_ms
        self.offset_ms = offset_ms

    def args_str(self):
        return (f"function={self.function}, window={self.subquery_window_ms}, "
                f"step={self.subquery_step_ms}")

    def compose(self, results, stats):
        block = concat_blocks([r for r in results if isinstance(r, ResultBlock)])
        wends = make_window_ends(self.start_ms, self.end_ms, self.step_ms)
        if block is None:
            return _block_empty(wends)
        inner_ts = np.asarray(block.wends)
        base = int(inner_ts[0]) if len(inner_ts) else 0
        vals = np.asarray(block.values)
        S = vals.shape[0]
        ts_off = np.broadcast_to((inner_ts - base).astype(np.int32),
                                 (S, len(inner_ts))).copy()
        # NaN steps are absent samples; offsets stay valid (kernel masks NaN)
        eval_wends = (wends - self.offset_ms - base).astype(np.int32)
        out = np.asarray(evaluate_range_function(
            jnp.asarray(ts_off), jnp.asarray(vals), jnp.asarray(eval_wends),
            self.subquery_window_ms, self.function, self.function_args,
            base_ms=base, dense=not bool(np.isnan(vals).any())))
        return ResultBlock(block.keys, wends, out)


class StitchRvsExec(NonLeafExecPlan):
    """Merge same-key series evaluated over adjacent time ranges
    (ref: exec/StitchRvsExec.scala).

    Vectorized (PR 15): the old per-series dict-of-rows Python loop ran
    once per series per tier on EVERY long-range query's stitch path; it
    is now one searchsorted + one fancy-indexed scatter per block into a
    preallocated [S, W_union] output (histogram [S, W, B] blocks stitch
    bucketwise the same way — the old loop could not)."""

    def compose(self, results, stats):
        blocks = [r for r in results if isinstance(r, ResultBlock)]
        if not blocks:
            return None
        if len(blocks) == 1:
            return blocks[0]
        wends = np.unique(np.concatenate([np.asarray(b.wends)
                                          for b in blocks]))
        row_of: Dict[RangeVectorKey, int] = {}
        keys: List[RangeVectorKey] = []
        for b in blocks:
            for k in b.keys:
                if k not in row_of:
                    row_of[k] = len(keys)
                    keys.append(k)
        # shape + bucket scheme come from the widest block, not
        # blocks[0]: an EMPTY tier (0 series, 2-D values) may arrive
        # first while a later tier carries [S, W, B] histogram data
        ref = max(blocks, key=lambda b: np.asarray(b.values).ndim)
        extra = np.asarray(ref.values).shape[2:]
        out = np.full((len(keys), len(wends)) + extra, np.nan)
        for b in blocks:
            if b.num_series == 0:
                continue
            vals = np.asarray(b.values)
            pos = np.searchsorted(wends, np.asarray(b.wends))
            rows = np.fromiter((row_of[k] for k in b.keys),
                               dtype=np.int64, count=len(b.keys))
            # scatter present samples; absent (NaN) steps keep whatever
            # an earlier tier put there (later blocks win on overlap,
            # exactly the old loop's fill rule)
            idx = np.ix_(rows, pos)
            take = ~np.isnan(vals)
            out[idx] = np.where(take, vals, out[idx])
        les = next((b.bucket_les for b in blocks
                    if b.bucket_les is not None), None)
        return ResultBlock(keys, wends, out, les)

