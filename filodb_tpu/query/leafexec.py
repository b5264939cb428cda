"""Leaf exec plans: the shard-local gather + fused-path leaf and the
scalar generators.

Split from query/exec.py (round 4, no behavior change).
ref: query/.../exec/MultiSchemaPartitionsExec.scala,
TimeScalarGeneratorExec.scala.
"""
from __future__ import annotations

import dataclasses
import logging
import os
import threading
import time as _time
from typing import Callable, Dict, List, Optional, Sequence, Tuple, Union

import numpy as np
import jax.numpy as jnp

from filodb_tpu.core.blockstore import estimate_samples
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
    AggPartial, GroupCardinalityError, LazyKeys, LeafExecPlan,
    QueryError, QueryResultLike, RawBlock, ScalarResult,
    _FUSED_CACHE_LOCK, _FUSED_MINMAX_PAD_CACHE, _FUSED_PLAN_CACHE,
    _FUSED_VALS_CACHE, _block_empty, _group_cache_insert,
    _group_cache_lookup, _lru_touch, _note_mirror_limit, agg_token,
    fused_plan, fused_values)
from filodb_tpu.query.transformers import (
    AggregateMapReduce, PeriodicSamplesMapper, RangeVectorTransformer,
    _group_ids, _group_ids_cached, _group_ids_of_part)
from filodb_tpu.query.fusedbatch import FusedCall, finish_fused_calls
from filodb_tpu.utils.metrics import span


class MultiSchemaPartitionsExec(LeafExecPlan):
    """Leaf: index lookup + dense gather on the owning shard
    (ref: exec/MultiSchemaPartitionsExec.scala:27-60,
    SelectRawPartitionsExec.doExecute:125)."""

    def __init__(self, ctx: QueryContext, dataset: str, shard: int,
                 filters: Sequence[ColumnFilter], chunk_start_ms: int,
                 chunk_end_ms: int, columns: Sequence[str] = (),
                 schema: Optional[str] = None):
        super().__init__(ctx)
        self.dataset = dataset
        self.shard = shard
        self.filters = list(filters)
        self.chunk_start_ms = chunk_start_ms
        self.chunk_end_ms = chunk_end_ms
        self.columns = list(columns)
        self.schema = schema
        self._transformer_overrides: Dict[int, RangeVectorTransformer] = {}
        self._prefused = None

    def _execute_impl(self, source) -> QueryResultLike:
        # (wrapped by ExecPlan.execute_internal's resource tally)
        pre = getattr(self, "_prefused", None)
        if pre is not None:
            # phase-3 of engine.query_range_batch: the gather and fused
            # preflight already ran in prepare_fused (keeping this leaf's
            # _transformer_overrides), and the kernel work was batched
            self._prefused = None
            data, stats, fused = pre
            if isinstance(fused, FusedCall):
                # engine collected the call but never finished it (e.g. a
                # batch peer errored): run it standalone
                fused = self._finish_or_degrade(fused)
        else:
            self._transformer_overrides = {}
            self._fused_cache_key = self._fused_whole = None
            data, stats = self._do_execute(source)
            try:
                fused = self._try_fused(data, stats)
            except (GroupCardinalityError, QueryError):
                # real query errors (cardinality limit, cancellation)
                # must surface, never degrade to the general path
                raise
            except Exception as e:  # noqa: BLE001 — fusion is an optimization
                from filodb_tpu.utils.metrics import (log_fused_degradation,
                                                      registry)
                registry.counter("leaf_fused_errors").increment()
                log_fused_degradation("leaf", e)
                fused = None
        start = 0
        if fused is not None:
            data, start = fused, 2
        elif data is not None:
            from filodb_tpu.utils.metrics import registry
            registry.counter("leaf_general_path").increment()
        for i, t in enumerate(self.transformers[start:], start):
            t = self._transformer_overrides.get(i, t)
            data = t.apply(data, self.ctx, stats, source)
        return data, stats

    def prepare_fused(self, source):
        """Phase-1 of engine.query_range_batch: run the gather and the
        fused preflight, but NOT the kernel.  Returns a FusedCall when
        this leaf's kernel work can be merged with other panels'
        (finish_fused_calls), else None.  Either way the gathered data is
        parked on the leaf so phase-3 execution never re-gathers; the
        engine injects the finished AggPartial via inject_fused."""
        # the leaf's work, hoisted out of the tree by the engine: its own
        # span per leaf, so the tree still shows which shard took what
        with span("leaf.prepare", shard=str(self.shard)):
            return self._prepare_fused(source)

    def _prepare_fused(self, source):
        self._transformer_overrides = {}
        self._fused_cache_key = self._fused_whole = None
        data, stats = self._do_execute(source)
        try:
            pre = self._try_fused(data, stats, defer=True)
        except GroupCardinalityError:
            # real query error — park the gather anyway so phase-3
            # surfaces the SAME error from the general aggregate path
            # (transformers.py group limit) without paying the index
            # lookup + dense gather twice
            self._prefused = (data, stats, None)
            return None
        except QueryError:
            raise                        # cancellation must surface
        except Exception as e:  # noqa: BLE001 — fusion is an optimization
            from filodb_tpu.utils.metrics import (log_fused_degradation,
                                                  registry)
            registry.counter("leaf_fused_errors").increment()
            log_fused_degradation("leaf", e)
            pre = None
        self._prefused = (data, stats, pre)
        return pre if isinstance(pre, FusedCall) else None

    def inject_fused(self, partial) -> None:
        """Phase-2 handoff: replace the parked FusedCall with its batched
        result (an AggPartial)."""
        data, stats, _ = self._prefused
        self._prefused = (data, stats, partial)

    def _finish_or_degrade(self, fc):
        self._check_cancel("fused kernel dispatch")
        try:
            return finish_fused_calls([fc])[0]
        except QueryError:
            raise                        # cancellation must surface
        except Exception as e:  # noqa: BLE001 — fusion is an optimization
            from filodb_tpu.utils.metrics import (log_fused_degradation,
                                                  registry)
            registry.counter("leaf_fused_errors").increment()
            log_fused_degradation("leaf", e)
            return None

    def _try_fused(self, data, stats, defer: bool = False):
        """The fused peephole (_build_fused), then, unless deferred, the
        kernel dispatch of the FusedCall it built."""
        if data is None:
            return None                  # an empty leaf has nothing to fuse
        with span("leaf.fused_prepare"):
            pre = self._build_fused(data, stats)
        if defer or not isinstance(pre, FusedCall):
            return pre
        self._check_cancel("fused kernel dispatch")
        return finish_fused_calls([pre])[0]

    def _build_fused(self, data, stats):
        """Peephole: PeriodicSamplesMapper(rate|increase|delta) followed by
        AggregateMapReduce(sum) over a shared-grid fully-finite working set
        collapses into the single-HBM-pass MXU kernel (ops/pallas_fused.py)
        — the leaf analogue of the reference pushing AggregateMapReduce to
        data nodes (ref: AggrOverRangeVectors.scala:76), fused one level
        further.  Returns None (general path), an AggPartial where no
        merged kernel dispatch is involved (host math, host route,
        min/max), or for the matmul-kernel path a FusedCall with
        everything resolved but the dispatch, so that the engine can merge
        compatible panels into one."""
        if len(self.transformers) < 2 or not isinstance(data, RawBlock) \
                or not data.keys or data.shared_ts_row is None:
            return None
        t0 = self._transformer_overrides.get(0, self.transformers[0])
        t1 = self._transformer_overrides.get(1, self.transformers[1])
        if not isinstance(t0, PeriodicSamplesMapper) \
                or not isinstance(t1, AggregateMapReduce):
            return None
        from filodb_tpu.ops import pallas_fused as pf
        # the shape only: a mirrored block's rows are gathered when its
        # values are read, which a hit on the padded-values cache never does
        shape = data.values_shape
        ndim = len(shape)
        is_hist = ndim == 3
        if ndim not in (2, 3) or t0.function_args or t1.params:
            return None
        if t0.window_ms is None:
            # instant-vector selector (`sum by (x) (metric)`): plain
            # lookback sampling IS last_over_time over the stale-lookback
            # window — the same normalization the general apply() does
            if t0.function is not None:
                return None
            t0 = dataclasses.replace(t0, window_ms=t0.lookback_ms,
                                     function="last_over_time")
        fn = t0.function or ""
        dense = data.dense
        if not pf.can_fuse(fn, t1.op, True, dense):
            return None
        # rows of a phase grid hold their own count of samples a window:
        # what follows from ONE row's counts (the host-only count paths,
        # the host route, reduce_window's window geometry) is not theirs.
        # The matmul kernel's phased variant is, for scalar columns
        phased = data.phased
        if phased and (is_hist or fn in pf.MINMAX_FNS):
            return None
        if is_hist:
            # histogram buckets are counters too: flatten [S, T, B] into
            # S*B kernel rows with per-(group, bucket) slots — the hist
            # analogue (ref: HistogramQueryBenchmark's
            # sum(rate(..._bucket[5m])) + histogram_quantile).  Ragged
            # (NaN-holed) bucket rows ride the kernel's valid-boundary
            # machinery like scalar rows do (round-5 verdict item 5) —
            # each flattened bucket row finds its own boundaries
            if fn not in ("rate", "increase") or t1.op != "sum" \
                    or data.bucket_les is None:
                return None
        # host-only fast paths: under the dense shared grid every series
        # has IDENTICAL per-window sample counts, so count_over_time and
        # the count aggregate are pure host math — no device work at all
        if dense and not is_hist and not phased:
            if fn == "count_over_time":
                return self._fused_count_over_time(data, t0, t1)
            if t1.op == "count":
                return self._fused_count_agg(data, t0, t1)
        wends = make_window_ends(t0.start_ms, t0.end_ms, t0.step_ms)
        eval_wends = wends - t0.offset_ms - data.base_ms
        if eval_wends.size == 0 or abs(eval_wends).max() >= (1 << 30):
            return None
        routed = self._try_host_routed(data, t0, t1, wends, eval_wends,
                                       fn, dense, is_hist)
        if routed is not None:
            return routed
        if fn in pf.MINMAX_FNS:
            # pure-XLA reduce_window path — any backend, no Pallas
            return self._fused_minmax(data, t0, t1, wends, eval_wends)
        interpret = pf.kernel_mode()
        if interpret is None:
            return None                 # kernel is MXU-targeted
        if fn in ("rate", "increase") and not data.precorrected:
            return None
        # VMEM guard, part 1 (group count not yet known — use the minimum):
        # very long ranges with many windows must take the general path,
        # not fail at kernel lowering
        Tp = pf._pad_to(shape[1], pf._LANE)
        Wp = pf._pad_to(eval_wends.size, pf._LANE)
        kind = fn if fn in pf.OVER_TIME_FNS else "rate_family"
        if pf.pick_block(Tp, Wp, 8, kind, not dense, phased=phased) is None:
            return None
        from filodb_tpu.utils.metrics import registry
        # plan + prepared-input caches: a repeat query over an unchanged
        # snapshot (the dashboard-poll pattern) skips the selection-matrix
        # rebuild AND the full padded device copy (PreparedInputs contract)
        key = vkey = self._fused_cache_key
        # a range that leaves some of the selector's series out (their life
        # lies past it: targets that come and go) reads the working set of
        # ALL of them, the one every other range of the shard reads, and
        # drops the rows it leaves out by their group: `whole` is that
        # set (_WholeSet).  The padded values key on the set, the groups on
        # the set and the part.  (min and max ride the per-series run,
        # which takes a set's rows for its series: the part's own set)
        whole, rows = None, shape[0]
        if key is not None and self._fused_whole is not None \
                and not is_hist and t1.op in ("sum", "avg", "count"):
            whole = self._fused_whole
            rows = whole.rows
            vkey = key[:3] + (whole.rows_key,)
            key = vkey + (key[3],)
        # a working set out of a PLACED mirror is stored whole rows first
        # (the rows that fill every slot of the grid, then the rows with a
        # hole: pf.whole_first) and a launch runs the dense body over the
        # first part.  The group-mode run alone takes such a set; min and
        # max ride the per-series run, whose output is a row a series in
        # the set's order: theirs is a set of its own, stored as it was
        src = data if whole is None else whole
        split_ok = not is_hist and t1.op in ("sum", "avg", "count")
        if key is not None and src.placed and not split_ok:
            key = vkey = key + ("rows in order",)
        plan = padded_vals = groups = gkeys = None
        # a plan reads differences of timestamps only: built from the row
        # moved to start at 0 (the window ends with it), two shards whose
        # mirrors have other bases under one absolute row share it
        plan_row = data.shared_ts_row
        plan_shift = int(plan_row[0]) if plan_row.size else 0
        if plan_shift:
            plan_row = plan_row - plan_row.dtype.type(plan_shift)
        if key is not None:
            # a plan is built from the shared timestamp row, the grid, the
            # window and the base, and from nothing of the shard: a
            # request's leaves and an open's panels share one build
            plan_key = ("plan", plan_row.tobytes(), t0.start_ms,
                        t0.step_ms, t0.end_ms, t0.offset_ms, t0.window_ms,
                        data.base_ms + plan_shift)
            with _FUSED_CACHE_LOCK:
                plan = _FUSED_PLAN_CACHE.lookup(plan_key)
                padded_vals = _FUSED_VALS_CACHE.lookup(vkey)
            groups, gkeys = _group_cache_lookup(key, t1.by, t1.without)
            if padded_vals is not None:
                registry.counter("leaf_fused_prep_hits").increment()
        if plan is None:
            def build():
                with span("leaf.build_plan"):
                    return pf.build_plan(plan_row.astype(np.int64),
                                         eval_wends - plan_shift,
                                         t0.window_ms)
            # one builder a grid: the panels of an open miss together, and
            # a request whose leaves held two equal plans would be two
            # device calls
            plan = build() if key is None else fused_plan(plan_key, build)
        if not plan.exact:
            # the kernel's times are f32 milliseconds off the row's first
            # sample: some boundary of this grid is not one (odd
            # milliseconds past 4.66 h).  Declined by name, not answered
            # from times a millisecond off
            registry.counter("leaf_inexact_times").increment()
            return None
        if gkeys is None:
            with span("leaf.group_ids"):
                if whole is None:
                    gids, gkeys = _group_ids_cached(
                        data.cache_token, data.keys, t1.by, t1.without)
                else:
                    gids, gkeys = whole.group_ids(t1.by, t1.without)
        self._check_group_limit(gkeys)
        B = shape[2] if is_hist else 1
        num_slots = len(gkeys) * B      # hist: one kernel group per (g, b)
        # VMEM guard, part 2: full estimate now that group count is known —
        # BEFORE the padded device copy, so diverted queries cost nothing
        # same padded group count _run will use — a gate tested on the
        # unpadded count could accept a shape _run then rejects
        if pf.pick_block(Tp, Wp, pf.pad_group_count(num_slots), kind,
                         not dense, phased=phased) is None:
            return None
        if padded_vals is None:
            def pad():
                if is_hist:
                    vals, vbase = data.values, data.vbase
                    # [S, T, B] -> [S*B, T] rows (bucket-major within a
                    # series, same layout PeriodicSamplesMapper flattens to)
                    with span("leaf.hist_flatten"):
                        flat = jnp.moveaxis(jnp.asarray(vals), 2, 1) \
                            .reshape(shape[0] * B, shape[1])
                        vb_flat = (np.zeros(flat.shape[0], np.float32)
                                   if vbase is None
                                   else jnp.asarray(vbase,
                                                    jnp.float32).reshape(-1))
                    with span("leaf.pad_values"):
                        return pf.pad_values(flat, vb_flat, plan)
                # rows out of the mirror come padded to the ladder's rung
                # already: the take, the pad and the kernel then compile
                # once a rung, not once a row count.  Here, once a working
                # set, its rows are ordered whole rows first where the
                # mirror is placed and the set holds both kinds (each part
                # on a rung of its own); nothing of it runs on a hit
                Sp = pf.pad_series_count(rows)
                vals = src.rows_padded("values", Sp, split_ok)
                vbase = src.rows_padded("vbase", Sp, split_ok)
                if vbase is None:
                    vbase = np.zeros(vals.shape[0], np.float32)
                # the kernel variant follows from the data: the phased one
                # where some row of THIS working set has a phase
                phase = None
                if phased and src.phase.any():
                    phase = src.rows_padded("phase", Sp, split_ok)
                with span("leaf.pad_values"):
                    return pf.pad_values(
                        vals, vbase, plan, phase=phase,
                        split=src.whole_first() if split_ok else None)
            # one builder a working set: the other leaves that missed with
            # this one wait for its padded values and share them
            padded_vals = pad() if vkey is None else fused_values(vkey, pad)
        if groups is None:
            with span("leaf.pad_groups"):
                if is_hist:
                    gids_flat = (np.asarray(gids, np.int64)[:, None] * B
                                 + np.arange(B)[None, :]).reshape(-1)
                    groups = pf.pad_groups(gids_flat, shape[0] * B,
                                           num_slots)
                else:
                    # pad_groups' column with the leaf's rows where they
                    # stand in the set's arrays: a part's rows where they
                    # stand among the set's (the rows this range leaves
                    # out belong to no group, as the rows that pad the set
                    # to its rung do), a set stored whole rows first by
                    # the order it was padded in
                    at = None if whole is None else whole.member
                    if padded_vals.at is not None:
                        at = padded_vals.at if at is None \
                            else padded_vals.at[at]
                    groups = pf.pad_groups(
                        gids, shape[0], len(gkeys), at=at,
                        rows=padded_vals.vals_p.shape[0])
            _group_cache_insert(key, t1.by, t1.without, groups, gkeys)
        registry.counter("leaf_fused_kernel").increment()
        # the groups the leaf's epilogue sums into (a histogram's slots):
        # over the leaves, a guard that a deployment groups as it says
        registry.counter("leaf_fused_groups").increment(num_slots)
        if padded_vals.phase_p is not None:
            registry.counter("leaf_phase_fused").increment()
        if not dense:
            registry.counter("leaf_ragged_fused").increment()
        if not is_hist:
            # broadened matmul path: any fusable (fn, agg) combination,
            # ragged (validity-weighted) when the working set has NaN
            # holes.  Packaged as a FusedCall so engine.query_range_batch
            # can merge compatible panels into one kernel dispatch; the
            # single-query path (_try_fused) finishes it immediately.
            ck = None if key is None else key + (
                t0.start_ms, t0.step_ms, t0.end_ms, t0.offset_ms,
                t0.window_ms, data.base_ms)
            fc = FusedCall(
                plan=plan, values=padded_vals, groups=groups, gkeys=gkeys,
                wends=wends, fn=fn, op=t1.op,
                precorrected=data.precorrected, interpret=interpret,
                ragged=not dense, num_series=rows, cache_key=ck,
                cache_token=agg_token(t1.op, t1.by, t1.without,
                                      data.cache_token))
            return fc
        # histogram leaf (sum(rate(bucket_metric))): (group, bucket)
        # slots ride the same FusedCall machinery so quantile dashboards
        # batch too — identical panels (p50/p90/p99 over one metric)
        # dedup to ONE kernel run (fusedbatch finisher reshapes slots to
        # [G, W, B] and appends the present-series count)
        registry.counter("leaf_hist_fused").increment()
        ck = None if key is None else key + (
            t0.start_ms, t0.step_ms, t0.end_ms, t0.offset_ms,
            t0.window_ms, data.base_ms, "hist", B)
        fc = FusedCall(
            plan=plan, values=padded_vals,
            groups=groups, gkeys=gkeys, wends=wends, fn=fn, op="sum",
            precorrected=data.precorrected, interpret=interpret,
            ragged=not dense, num_series=shape[0] * B, cache_key=ck,
            bucket_les=data.bucket_les, num_buckets=B,
            cache_token=agg_token("hist_sum", t1.by, t1.without,
                                  data.cache_token))
        return fc

    def _try_host_routed(self, data, t0, t1, wends, eval_wends, fn,
                         dense, is_hist):
        """Cost-based host evaluation for small working sets (round-5
        verdict item 6; crossover/threshold: query.host_route_max_samples
        via RawBlock.route_host).  Returns an AggPartial or None to
        continue onto the device paths."""
        if not (data.route_host and dense and not is_hist
                and data.shared_grid
                and t1.op in ("sum", "avg", "count", "min", "max")
                and isinstance(data.values, np.ndarray)):
            return None
        if fn in ("rate", "increase") and not data.precorrected:
            return None
        from filodb_tpu.ops import hostleaf
        from filodb_tpu.ops import pallas_fused as pf
        from filodb_tpu.utils.metrics import registry, span
        # batch-scoped FINISHED-partial memo: a dashboard repeats whole
        # subexpressions (sum by (ns)(rate(m[5m])) rides alone AND as a
        # ratio operand AND under topk), and within one gather-memo
        # scope an identical (working set, fn, op, grouping, grid) key
        # means identical inputs — so the evaluation is shared like the
        # scan.  Inert outside engine.query_range_batch's memo scope.
        mkey = None
        if data.cache_token is not None:
            mkey = ("hpartial", data.cache_token, fn, t1.op,
                    tuple(t1.by), tuple(t1.without), t0.start_ms,
                    t0.step_ms, t0.end_ms, t0.offset_ms, t0.window_ms,
                    data.base_ms)
            hit = hostleaf.memo_get(mkey)
            if hit is not None:
                self._check_group_limit(hit.group_keys)
                registry.counter("leaf_host_routed").increment()
                self.route = "host"
                return dataclasses.replace(hit)
        plan = pf.build_plan(
            np.asarray(data.shared_ts_row, np.int64), eval_wends,
            t0.window_ms)
        # token-keyed group cache: the O(S) key.only() loop dominated
        # repeat host-routed leaves (same working set, new panel)
        gids, gkeys = _group_ids_cached(data.cache_token, data.keys,
                                        t1.by, t1.without)
        self._check_group_limit(gkeys)
        with span("leaf_host_routed", hist=True, fn=fn, op=t1.op):
            comp = hostleaf.host_leaf_agg(
                plan, data.values, data.vbase, np.asarray(gids),
                len(gkeys), fn, t1.op)
        registry.counter("leaf_host_routed").increment()
        self.route = "host"
        p = AggPartial(t1.op, gkeys, wends, comp=comp,
                       cache_token=agg_token(t1.op, t1.by, t1.without,
                                             data.cache_token))
        if mkey is not None:
            hostleaf.memo_put(mkey, p)
        return p

    def args_str(self):
        fs = ",".join(str(f) for f in self.filters)
        route = getattr(self, "route", None)
        return (f"dataset={self.dataset}, shard={self.shard}, "
                f"chunkMethod=TimeRangeChunkScan({self.chunk_start_ms},"
                f"{self.chunk_end_ms}), filters=[{fs}], "
                f"colName={self.columns}"
                + (f", route={route}" if route else ""))

    def _window_counts_groups(self, data, t0, t1):
        """Shared host math for the no-device fast paths: per-window
        sample counts on the dense shared grid + grouping."""
        wends = make_window_ends(t0.start_ms, t0.end_ms, t0.step_ms)
        eval_wends = wends - t0.offset_ms - data.base_ms
        if eval_wends.size == 0 or abs(eval_wends).max() >= (1 << 30):
            return None
        from filodb_tpu.ops import pallas_fused as pf
        gids, gkeys = _group_ids_cached(data.cache_token, data.keys,
                                        t1.by, t1.without)
        self._check_group_limit(gkeys)
        n = pf.window_counts(data.shared_ts_row.astype(np.int64),
                             eval_wends, t0.window_ms).astype(np.float64)
        gsize = np.bincount(np.asarray(gids),
                            minlength=len(gkeys))[:len(gkeys)]
        return wends, gkeys, n, gsize.astype(np.float64)

    def _fused_count_over_time(self, data, t0, t1):
        """agg by (count_over_time(...)): under the shared dense grid every
        series has IDENTICAL per-window sample counts, so the whole result
        is host math over (gsize, n) — no device work at all.  Handles all
        five fusable aggregates: each series' value at window w is n[w]."""
        r = self._window_counts_groups(data, t0, t1)
        if r is None:
            return None
        wends, gkeys, n, gsize = r
        valid = (n >= 1).astype(np.float64)
        op = t1.op
        if op in ("sum", "avg"):
            comp = np.stack([gsize[:, None] * n[None, :] * valid,
                             gsize[:, None] * valid[None, :]], axis=-1)
        elif op == "count":
            comp = (gsize[:, None] * valid[None, :])[..., None]
        else:                            # min/max: every series agrees on n
            absent = np.inf if op == "min" else -np.inf
            per = np.where(valid > 0, n, absent)
            comp = np.stack(
                [np.broadcast_to(per[None, :], (len(gkeys), len(n))),
                 gsize[:, None] * valid[None, :]], axis=-1)
        from filodb_tpu.utils.metrics import registry
        registry.counter("leaf_fused_count_host").increment()
        return AggPartial(op, gkeys, wends, comp=comp,
                          cache_token=agg_token(op, t1.by, t1.without,
                                                data.cache_token))

    def _fused_count_agg(self, data, t0, t1):
        """count by (fn(...)) on a dense shared grid: the count of series
        emitting a value at window w is gsize * 1{n[w] >= min_samples} —
        host math, no device work (the value itself never matters)."""
        r = self._window_counts_groups(data, t0, t1)
        if r is None:
            return None
        wends, gkeys, n, gsize = r
        minsamp = 2 if t0.function in ("rate", "increase", "delta") else 1
        valid = (n >= minsamp).astype(np.float64)
        from filodb_tpu.utils.metrics import registry
        registry.counter("leaf_fused_count_host").increment()
        comp = (gsize[:, None] * valid[None, :])[..., None]
        return AggPartial("count", gkeys, wends, comp=comp,
                          cache_token=agg_token("count", t1.by, t1.without,
                                                data.cache_token))

    def _fused_minmax(self, data, t0, t1, wends, eval_wends):
        """min/max_over_time + any aggregate in one jit via the XLA
        reduce_window path (ops/pallas_fused.fused_minmax_agg) — one HBM
        pass, no host round trip of the [S, T] working set, any backend.
        Requires uniform window geometry; else the general path runs."""
        from filodb_tpu.ops import pallas_fused as pf
        ts_row0 = np.asarray(data.shared_ts_row)
        real = ts_row0[ts_row0 < PAD_TS]
        geom = pf.uniform_window_geometry(real.astype(np.int64),
                                          eval_wends, t0.window_ms)
        if geom is None:
            return None
        f0, stride, width, t_needed = geom
        if t_needed > 2 * real.size:
            # a grid hanging FAR past the data (end=now long after the last
            # scrape) would pad more columns than the data itself — the
            # general path handles that without materializing the padding
            return None
        # grouping: reuse the shared per-working-set group cache (the same
        # per-series label hashing the kernel path caches away)
        key = self._fused_cache_key
        groups_c, gkeys = _group_cache_lookup(key, t1.by, t1.without)
        if gkeys is None:
            gids, gkeys = _group_ids(data.keys, t1.by, t1.without)
            self._check_group_limit(gkeys)      # reject BEFORE caching
            _group_cache_insert(key, t1.by, t1.without,
                                pf.pad_groups(gids, len(data.keys),
                                              len(gkeys)), gkeys)
        else:
            self._check_group_limit(gkeys)
            gids = np.asarray(groups_c.gids_p[:len(data.keys), 0])
        vb = data.vbase
        vals = jnp.asarray(data.values)
        ragged = not data.dense
        if t_needed > real.size:
            # windows hang past the data's right edge (end=now queries):
            # extend with NaN columns so the ragged variant masks them —
            # cached per (working set, t_needed): the dashboard-poll shape
            # would otherwise re-copy the whole set on device every refresh
            pad_key = None if key is None else key + ("minmax_pad",
                                                      t_needed)
            padded = None
            if pad_key is not None:
                with _FUSED_CACHE_LOCK:
                    padded = _lru_touch(_FUSED_MINMAX_PAD_CACHE, pad_key)
            if padded is None:
                padded = jnp.pad(vals[:, :real.size],
                                 ((0, 0), (0, t_needed - real.size)),
                                 constant_values=np.nan)
                if pad_key is not None:
                    with _FUSED_CACHE_LOCK:
                        for k in [k for k in _FUSED_MINMAX_PAD_CACHE
                                  if k[0] == pad_key[0]
                                  and k[1] != pad_key[1]]:
                            del _FUSED_MINMAX_PAD_CACHE[k]
                        _FUSED_MINMAX_PAD_CACHE[pad_key] = padded
                        while len(_FUSED_MINMAX_PAD_CACHE) > 2:
                            _FUSED_MINMAX_PAD_CACHE.pop(
                                next(iter(_FUSED_MINMAX_PAD_CACHE)))
            vals = padded
            ragged = True
        _d0 = _time.perf_counter()
        comp = pf.fused_minmax_agg(
            vals, None if vb is None else jnp.asarray(vb),
            jnp.asarray(gids, jnp.int32), f0, stride, width,
            int(eval_wends.size), t0.function, t1.op, len(gkeys),
            ragged=ragged)
        comp_np = np.asarray(comp, np.float64)   # synchronizing readback
        from filodb_tpu.utils.devicetelem import telem
        telem.record_dispatch(
            "fused_minmax", device=pf._committed_device(vals),
            shape=f"S{vals.shape[0]}xW{int(eval_wends.size)}xG{len(gkeys)}",
            seconds=_time.perf_counter() - _d0,
            bytes_in=int(getattr(vals, "nbytes", 0)),
            bytes_out=comp_np.nbytes)
        from filodb_tpu.utils.metrics import registry
        registry.counter("leaf_fused_minmax").increment()
        return AggPartial(t1.op, gkeys, wends,
                          comp=comp_np,
                          cache_token=agg_token(t1.op, t1.by, t1.without,
                                                data.cache_token))

    def _check_group_limit(self, gkeys) -> None:
        limit = self.ctx.planner_params.group_by_cardinality_limit
        if limit and len(gkeys) > limit:
            raise GroupCardinalityError(
                f"group-by cardinality limit {limit} exceeded "
                f"({len(gkeys)} groups)")

    def _check_cancel(self, where: str) -> None:
        """Cooperative cancellation between the exec-node boundary
        checks: before device dispatches and around the paging loops, so
        a killed cold-tier scan stops mid-leaf instead of finishing a
        result nobody will read."""
        tok = getattr(self.ctx, "cancel", None)
        if tok is not None and tok.cancelled:
            tok.raise_if_cancelled(f"before {where} (shard {self.shard})")

    def _do_execute(self, source) -> QueryResultLike:
        stats = QueryStats(shards_queried=1)
        shard = source.get_shard(self.dataset, self.shard)
        if shard is None:
            return None, stats
        with span("leaf.index_lookup"):
            lookup = shard.lookup_partitions(
                self.filters, self.chunk_start_ms, self.chunk_end_ms)
            schema_name = self.schema or lookup.first_schema
            pids = None if schema_name is None else \
                lookup.pids_by_schema.get(schema_name)
            if pids is None or pids.size == 0:
                # an empty shard's leaf: no series after the index lookup,
                # so no gather, no working set and no dispatch
                from filodb_tpu.utils.metrics import registry
                registry.counter("leaf_empty").increment()
                return None, stats
            store = shard.stores[schema_name]
            # the selection's rows, cache keys and facts (counts,
            # extents, paging verdict): the memo's on a hit
            sel, facts = shard.selection_facts(lookup, schema_name)
            rows = sel.rows

        # Cap data scanned BEFORE materializing (or paging) the [S, T]
        # matrix — a pathological selector must fail fast, not OOM first
        # (ref: OnDemandPagingShard.scala:55 capDataScannedPerShardCheck,
        # ExecPlan.scala:139-180 enforcedLimits).  The estimate clips each
        # series to the query's chunk range assuming uniform spacing (the
        # reference estimates from chunk metadata the same way); checked
        # against the resident data before ODP and again after paging.
        limit = self.ctx.planner_params.scan_limit
        enforced = limit and self.ctx.planner_params.enforced_limits
        estimate = []      # one a leaf, for the scan cap and for leaf_route

        def _scan_estimate() -> int:
            if not estimate:
                with span("leaf.scan_estimate"):
                    estimate.append(facts.estimate(self.chunk_start_ms,
                                                   self.chunk_end_ms))
            return estimate[0]

        def _check_scan_cap(when: str):
            # (a row's estimate is at most its count: rows that hold no
            # more samples than the cap in all cannot pass it, whatever
            # the range, and nothing is estimated)
            if not enforced or facts.samples <= limit:
                return
            to_scan = _scan_estimate()
            if to_scan > limit:
                raise ValueError(
                    f"shard {self.shard}: query would scan ~{to_scan} "
                    f"samples ({when}), over the scan limit {limit} — "
                    f"narrow the filters or time range")

        _check_scan_cap("resident")
        from filodb_tpu.core.shard import PagedLimitExceeded
        try:
            # the cancel callable rides into the per-partition paging
            # loop: a killed query stops paging history mid-scan (the
            # work already paged is kept — valid cache for a retry)
            tok = getattr(self.ctx, "cancel", None)
            with span("leaf.page_check"):
                paged = shard.ensure_paged_pids(
                    schema_name, pids, self.chunk_start_ms,
                    self.chunk_end_ms,
                    max_samples=limit if enforced else None,
                    cancel=(None if tok is None else
                            lambda: self._check_cancel("demand paging")),
                    facts=facts)
        except PagedLimitExceeded as e:
            # structured query error, not a 500: the partial paging work
            # is kept (valid cache for a narrower retry) and the error
            # says how much was paged before the limit hit
            raise QueryError("paged_limit_exceeded", str(e)) from None
        stats.cold_tier = "hot"
        if paged:
            stats.samples_paged += int(paged)
            stats.cold_tier = "cold_paged"
            # ODP grew some series' extents and moved the store's
            # generation: the facts are stale, and so is the estimate
            # made from them
            sel, facts = shard.selection_facts(lookup, schema_name)
            estimate.clear()
            _check_scan_cap("after demand paging")
        schema = shard.schemas[schema_name]
        col_name = (self.columns[0] if self.columns
                    else schema.value_column)
        # schema-specific column + range-function substitution for the
        # downsample gauge schema: min_over_time reads the `min` column,
        # count_over_time becomes sum_over_time over `count`, etc.  Applied
        # as per-execution overrides so the plan stays reusable
        # (ref: MultiSchemaPartitionsExec.finalizePlan schema substitutions;
        # Schemas DS_GAUGE_FN_SUBSTITUTION)
        if schema.name == "ds-gauge" and not self.columns:
            from filodb_tpu.core.schemas import DS_GAUGE_FN_SUBSTITUTION
            for i, t in enumerate(self.transformers):
                if isinstance(t, PeriodicSamplesMapper):
                    sub = DS_GAUGE_FN_SUBSTITUTION.get(t.function)
                    if sub is not None:
                        col_name = sub[0]
                        if sub[1] != t.function:
                            self._transformer_overrides[i] = \
                                dataclasses.replace(t, function=sub[1])
                    break
        # counter semantics: counter-typed columns are reset-corrected in
        # f64 host-side (ops/counter.host_counter_correct) when the range
        # function has counter semantics, so post-rebase f32 deltas are
        # exact even across resets.  Non-counter functions on counter
        # columns (resets/delta/changes) need the RAW values and therefore
        # bypass the (pre-corrected) device mirror.
        col_def = next((c for c in schema.data_columns
                        if c.name == col_name), None)
        counter_col = col_def is not None and (col_def.detect_drops
                                               or col_def.counter)
        fn_is_counter = False
        # whether the leaf's range function counts a NaN as an ABSENT
        # sample wherever it runs, fused or general: what may read a placed
        # mirror snapshot (_PLACED_FNS)
        nan_is_absent = False
        for i, t in enumerate(self.transformers):
            if isinstance(t, PeriodicSamplesMapper):
                spec = RANGE_FUNCTIONS.get(t.function or "")
                fn_is_counter = spec.is_counter if spec else False
                t = self._transformer_overrides.get(i, t)
                nan_is_absent = t.window_ms is not None \
                    and t.function in _PLACED_FNS
                break
        # device-resident fast path: gather rows from the HBM mirror instead
        # of re-shipping the matrix every query (ref: block-memory working
        # set, BlockManager.scala; see core/devicecache.py)
        mirror = None
        # whether this leaf may read the device mirror at all: the mirror
        # holds counter columns corrected, so only counter functions read
        # those from it
        mirrorable = (getattr(shard.config.store, "device_mirror_enabled",
                              True)
                      and (not counter_col or fn_is_counter))
        # cost-based router (round-5 item 6): an estimated working set at
        # or below query.host_route_max_samples skips the device mirror —
        # the host gather is cheap at that size, and _try_fused then
        # evaluates in numpy instead of paying the dispatch floor
        route_host = False
        from filodb_tpu.config import settings as _settings
        _route_cap = _settings().query.host_route_max_samples
        if _route_cap > 0:
            # only where the per-dispatch floor exists: on the CPU
            # backend the "device" path is already host-side, and the
            # interpret-mode tests exercise the kernel deliberately
            forced = bool(os.environ.get("FILODB_TPU_FORCE_HOST_ROUTE"))
            if forced or _attached_chip():
                # a histogram sample is num_buckets values: the cap is
                # compared with what the leaf would gather and correct
                per_sample = (store.num_buckets if col_def is not None
                              and col_def.col_type == "hist" else 1)
                # (a leaf that may read the mirror goes to the device
                # whatever its size: nothing is estimated for it)
                route_host = not (mirrorable and not forced) and leaf_route(
                    _scan_estimate(), per_sample, _route_cap) == "host"
        if not route_host and mirrorable:
            mirror = getattr(store, "device_mirror", None)
            if mirror is None:
                from filodb_tpu.core.devicecache import (
                    DEFAULT_HBM_LIMIT_BYTES, DeviceMirror,
                    mirror_create_lock, placer, sharded_mirrors_enabled,
                    store_nbytes)
                limit = getattr(shard.config.store,
                                "device_mirror_hbm_limit",
                                DEFAULT_HBM_LIMIT_BYTES)
                # sharded mirror mode: pin this shard's mirror to its
                # placed device so the fused kernel dispatches THERE and
                # multi-shard queries fan out across chips (the
                # per-device dispatch contract, doc/multichip.md).
                # Creation is serialized: concurrent first queries each
                # calling placer.assign would double-book the device
                # until GC collects the losing mirror.
                with mirror_create_lock:
                    mirror = getattr(store, "device_mirror", None)
                    if mirror is None:
                        device, est = None, 0
                        if sharded_mirrors_enabled(shard.config.store):
                            est = store_nbytes(store)
                            device = placer.assign(self.shard, est, limit)
                        mirror = store.device_mirror = DeviceMirror(
                            limit, device=device, shard_num=self.shard,
                            reserved_bytes=est)
                        _note_mirror_limit(limit)

        # Mirror refresh (a full host->device upload) runs at most once per
        # query, under the write lock so it can't race a mutation; the
        # subsequent row gather reads only the immutable device copy.  The
        # host fallback copies out under the seqlock so a concurrent
        # ingest/flush can't hand the kernel a torn matrix.
        mirrored = snap = None
        if mirror is not None:
            with span("leaf.mirror_fresh"):
                ok = mirror.is_fresh(store)
                if not ok:
                    bg = getattr(shard.config.store,
                                 "mirror_background_rebuild", True)
                    if mirror.can_update_inline(store) or not bg:
                        with shard._write_locked("mirror_refresh"):
                            # re-check under the lock: an eviction may bump
                            # shift_version between the unlocked check and
                            # lock acquisition, and the full rebuild must
                            # still not run on this query's critical path
                            if not bg or mirror.can_update_inline(store):
                                ok = mirror.ensure_fresh(store)
                    if not ok and bg and not mirror.can_update_inline(store):
                        # eviction rearranged rows (shift_version moved): the
                        # full O(S*T) re-upload must not run on THIS query's
                        # critical path — rebuild in the background and serve
                        # this query via the host windowed gather below
                        # (eviction-proof serving; the round-5 soak's 752 s p99
                        # was one query paying this inline: PERF.md section 7)
                        mirror.request_background_refresh(shard, store)
                        from filodb_tpu.utils.metrics import registry as _reg
                        _reg.counter(
                            "device_mirror_query_fallbacks").increment()
            if ok:
                # one snapshot read serves gather AND fused-eligibility:
                # pairing a newer snapshot's grid with an older one's values
                # would feed the kernel zero-padded phantom columns
                snap = mirror.snapshot()
            if ok and (not snap.interval or nan_is_absent):
                # no device work yet: the rows of an array leave the mirror
                # when the block's field is first read (MirrorGather books
                # the span and the dispatch of each take where it runs)
                mirrored = mirror.gather_cached(rows, snap)
            # (a placed snapshot holds NaN where a row has no sample: a
            # function that takes a NaN for a sample, the staleness rule of
            # last_over_time, count_over_time's slots, raw samples going
            # out, reads the store's own rows below instead)
        # value column selection: histograms gather [S, T, B]
        shared_ts_row = phase = None
        dense = True
        if mirrored is not None:
            base = mirrored.base_ms
            ts_off = mirrored.deferred("ts_off")
            vals = mirrored.deferred("values", col_name)
            vbase = mirrored.deferred("vbase", col_name)
            with span("leaf.counts_copy"):
                if facts.generation != store.generation:
                    sel, facts = shard.selection_facts(lookup, schema_name)
                samples = facts.samples
            precorrected = counter_col   # mirror corrects counter columns
            shared_ts_row = mirror.fused_eligible(col_name, snap,
                                                  allow_ragged=True)
            if shared_ts_row is not None:
                phase = mirrored.deferred("phase")
            elif snap.counts.size:
                # some sample of the store fits no slot of a scrape
                # grid (a scrape later than the tolerance, a second
                # interval): the leaf's transformers run on the general
                # path
                from filodb_tpu.utils.metrics import registry as _reg
                _reg.counter("leaf_offgrid").increment()
            # col_dense is grid-independent (counted cells finite; pads are
            # excluded via PAD_TS), so a non-shared grid with finite values
            # keeps the cheap slot-boundary rate path
            dense = mirror.col_dense(col_name, snap)
            if shared_ts_row is not None:
                # cache identity for the fused path's prepared-input reuse
                # (mirror.serial, not id(): ids are reused after GC; raw
                # rows bytes, not their hash: a collision would silently
                # serve another row-set's values)
                self._fused_cache_key = (mirror.serial, snap.data_gen,
                                         col_name, sel.rows_key)
                among = lookup.among(schema_name)
                if among is not None and among[1].size == rows.size:
                    self._fused_whole = _WholeSet(
                        mirror.gather_cached(among[0].rows, snap),
                        col_name, shard, *among)
        else:
            from filodb_tpu.utils.metrics import registry as _reg
            _reg.counter("leaf_host_gather").increment()
            # windowed gather: copy only the planner's chunk-scan span —
            # a fraction of the store's full time capacity, and far less
            # seqlock-tear exposure under live ingest (the r4 soak's 9x
            # under-ingest degradation was full-row gathers being torn
            # and retried against continuous appends)
            # batch gather memo (PR 17, ops/hostleaf.py): under a
            # query_range_batch prepare scope, N panels over one working
            # set share ONE windowed scan AND its post-processing — the
            # offset grid, the counter-corrected/rebased value matrix,
            # and the density verdict are all pure functions of the key
            # (exact row set, span, column, correction mode, keys
            # epoch), and every downstream consumer treats the arrays
            # as immutable.  Memoizing only the raw gather was measured
            # to leave ~80% of a repeat leaf's cost on the table —
            # host_counter_correct + to_offsets dominate the scan.
            from filodb_tpu.ops import hostleaf as _hostleaf
            precorrected = counter_col and fn_is_counter
            base = self.chunk_start_ms
            _memo_key = (shard.keys_serial, shard.keys_epoch, self.dataset,
                         self.shard, self.chunk_start_ms, self.chunk_end_ms,
                         col_name, precorrected, sel.rows_key)
            _hit = _hostleaf.memo_get(_memo_key)
            if _hit is not None:
                ts_off, vals, vbase, counts, dense = _hit
            else:
                # raw-gather sub-memo: panels that share the span but
                # differ in column/correction mode (e.g. a gauge window
                # next to a counter rate) still share the scan itself
                _raw_key = ("raw",) + _memo_key[:6] + (sel.rows_key,)
                _raw = _hostleaf.memo_get(_raw_key)
                if _raw is not None:
                    ts, cols, counts = _raw
                    ts_off = _hostleaf.memo_get(("off",) + _raw_key[1:])
                else:
                    ts, cols, counts = shard.snapshot_read(
                        store, lambda: store.gather_rows(
                            rows, self.chunk_start_ms, self.chunk_end_ms))
                    _hostleaf.memo_put(_raw_key, (ts, cols, counts))
                    ts_off = None
                if ts_off is None:
                    ts_off = to_offsets(ts, counts, base)
                    _hostleaf.memo_put(("off",) + _raw_key[1:], ts_off)
                # correct (f64) + rebase so counter deltas stay exact on
                # chip
                vals, vbase = counter_ops.rebase_values(cols[col_name],
                                                        precorrected)
                # NaN anywhere (staleness markers or ragged-length
                # padding) routes the rate family onto its
                # valid-boundary variant
                dense = not bool(np.isnan(vals).any())
                _hostleaf.memo_put(_memo_key,
                                   (ts_off, vals, vbase, counts, dense))
            samples = int(counts.sum())
        keys = LazyKeys(shard, pids)
        stats.series_scanned = int(pids.size)
        stats.samples_scanned = samples
        les = store.bucket_les if vals.ndim == 3 else None
        if route_host and shared_ts_row is None and isinstance(
                vals, np.ndarray):
            # the host path computed no shared-grid row; derive it the
            # same way the mirror does so small dense sets stay fusable
            # (identical offset rows across real samples)
            ts_np = np.asarray(ts_off)
            if ts_np.size and counts.size and \
                    (counts == counts[0]).all() and \
                    (ts_np[:, :max(int(counts[0]), 1)]
                     == ts_np[0, :max(int(counts[0]), 1)]).all():
                shared_ts_row = ts_np[0, :int(counts[0])]
        return RawBlock(keys, ts_off, vals, base, les,
                        samples=stats.samples_scanned, vbase=vbase,
                        precorrected=precorrected,
                        shared_ts_row=shared_ts_row, dense=dense,
                        cache_token=(shard.keys_serial, shard.keys_epoch,
                                     sel.pids_key),
                        route_host=route_host, phase=phase), stats


class _WholeSet:
    """The fused working set of ALL the series a selector has on a shard,
    for a leaf whose range leaves some of them out (their life lies past
    it: shard.PartLookupResult.among): its rows still in the mirror, read
    as a block's are (`rows_padded`, `phase`), and `member`, where the
    leaf's own rows stand among them.  Every range reads this one set, so
    a fleet whose targets come and go pads one working set a shard and not
    one for every birth a range's end lies before."""
    __slots__ = ("rows_key", "member", "_held", "_keys", "_token")

    def __init__(self, gather, col_name: str, shard, sel,
                 member: np.ndarray):
        self.rows_key, self.member = sel.rows_key, member
        self._held = {"values": gather.deferred("values", col_name),
                      "vbase": gather.deferred("vbase", col_name),
                      "phase": gather.deferred("phase")}
        # the set's series as a block of them carries them (RawBlock.keys,
        # cache_token): their group ids are the ones its own leaves made
        self._keys = LazyKeys(shard, sel.pids)
        self._token = (shard.keys_serial, shard.keys_epoch, sel.pids_key)

    def group_ids(self, by, without):
        """The leaf's own series' group ids and keys, from the set's: the
        label loop over 73,000 keys (0.47 s) ran when a range that holds
        every life first asked, and a part only renumbers."""
        return _group_ids_of_part(
            *_group_ids_cached(self._token, self._keys, by, without),
            self.member)

    @property
    def rows(self) -> int:
        return self._held["values"].shape[0]

    @property
    def placed(self) -> bool:
        return self._held["values"].placed

    @property
    def phase(self) -> Optional[np.ndarray]:
        held = self._held["phase"]
        return None if held is None else held.host()

    def rows_padded(self, field: str, rows_to: int,
                    whole_first: bool = False):
        held = self._held[field]
        return None if held is None else held.resolve(rows_to, whole_first)

    def whole_first(self):
        return self._held["values"].whole_first()


# Range functions that count a NaN as an absent sample on every path they
# can take: the fused kernel's ragged variants (valid boundaries for the rate
# family, validity-weighted sums, presence by valid count), the reduce_window
# min/max and the general XLA path (ops/rangefns: _valid_bounds,
# _valid_count).  Only these read a PLACED mirror snapshot
# (core/devicecache._MirrorSnapshot.interval), whose empty slots are NaN.
_PLACED_FNS = frozenset(("rate", "increase", "delta", "sum_over_time",
                         "avg_over_time", "min_over_time", "max_over_time"))


class SelectPersistedSegmentsExec(MultiSchemaPartitionsExec):
    """Leaf for the persisted-segment (historical) tier: gathers rows from
    cold-region segment blocks instead of the shard's dense store, then
    runs the SAME transformer / fused pipeline as the hot leaf — cold
    scans take the device path, not `ensure_paged`'s host decode.

    `tier` is a persist.segments.PersistedTier bound at plan time by
    PersistedClusterPlanner (this tier is node-local: segment files +
    the cold DeviceMirror region live on the serving node)."""

    def __init__(self, ctx: QueryContext, dataset: str, shard: int,
                 filters: Sequence[ColumnFilter], chunk_start_ms: int,
                 chunk_end_ms: int, tier, columns: Sequence[str] = (),
                 schema: Optional[str] = None):
        super().__init__(ctx, dataset, shard, filters, chunk_start_ms,
                         chunk_end_ms, columns=columns, schema=schema)
        self.tier = tier

    def args_str(self):
        fs = ",".join(str(f) for f in self.filters)
        return (f"dataset={self.dataset}, shard={self.shard}, tier=cold, "
                f"chunkMethod=TimeRangeChunkScan({self.chunk_start_ms},"
                f"{self.chunk_end_ms}), filters=[{fs}]")

    def _do_execute(self, source) -> QueryResultLike:
        # disaggregated cold tier (persist/objectstore.py): a dead or
        # corrupt object store is a typed shard_unavailable — the leaf's
        # parent drops it under the partial-results gate (flagged
        # partial), exactly like a dead peer, never a hang
        try:
            return self._cold_execute(source)
        except Exception as e:  # noqa: BLE001 — re-raise non-store errors
            from filodb_tpu.persist.objectstore import ObjectStoreError
            if not isinstance(e, ObjectStoreError):
                raise
            raise QueryError(
                "shard_unavailable",
                f"shard {self.shard}: cold tier unavailable ({e})")

    def _cold_execute(self, source) -> QueryResultLike:
        stats = QueryStats(shards_queried=1)
        segs = self.tier.covering(self.shard, self.chunk_start_ms,
                                  self.chunk_end_ms, self.schema)
        if not segs:
            return None, stats
        by_schema: Dict[str, list] = {}
        for m in segs:
            by_schema.setdefault(m.schema_name, []).append(m)
        schema_name = self.schema or next(iter(by_schema))
        metas = sorted(by_schema.get(schema_name, ()),
                       key=lambda m: m.start_ms)
        if not metas:
            return None, stats
        schema = self.tier.schemas[schema_name]
        col_name = (self.columns[0] if self.columns
                    else schema.value_column)
        verdict = "cold_hit"
        picked = []                       # (block, rows)
        self._check_cancel("cold-segment page-in")
        if len(metas) > 1:
            # page the slice's segments in concurrently: decode + upload
            # overlap, so the cold wall is ~one segment, not the sum (the
            # per-column decode inside each is pooled too)
            import concurrent.futures

            def _fetch(m):
                # per-segment cancel check: a killed 30-day scan stops
                # between page-ins instead of decoding the whole slice
                self._check_cancel("cold-segment page-in")
                return self.tier.get_block(m)

            with concurrent.futures.ThreadPoolExecutor(
                    max_workers=min(4, len(metas))) as pool:
                fetched = list(pool.map(_fetch, metas))
        else:
            fetched = [self.tier.get_block(metas[0])]
        self._check_cancel("cold-segment gather")
        for m, (block, v) in zip(metas, fetched):
            rows = block.match_rows(self.filters, self.chunk_start_ms,
                                    self.chunk_end_ms)
            if v == "cold_paged":
                verdict = "cold_paged"
                stats.samples_paged += int(block.counts.sum())
                stats.bytes_paged += int(block.nbytes)
            if rows.size:
                picked.append((block, rows))
        stats.cold_tier = verdict
        if not picked:
            return None, stats
        # scan cap over the FILTER-MATCHED rows (hot-leaf parity: the
        # estimate must reflect what this query scans, not the shard's
        # total segment volume), checked before the gather/merge
        # materializes anything; page-in granularity is the segment and
        # stays bounded by the cold region's byte budget either way
        limit = self.ctx.planner_params.scan_limit
        if limit and self.ctx.planner_params.enforced_limits:
            est = sum(int(b.counts[r].sum()) for b, r in picked)
            if est > limit:
                raise ValueError(
                    f"shard {self.shard}: persisted-tier query would scan "
                    f"~{est} samples, over the scan limit {limit} — "
                    f"narrow the filters or time range")
        base_ms = picked[0][0].meta.start_ms
        span = max(b.meta.end_ms for b, _ in picked) - base_ms
        if span >= (1 << 30):
            raise ValueError(
                "persisted-tier slice spans >2^30 ms — the planner must "
                "split long ranges (PersistedClusterPlanner.plan_split_ms)")
        raw = self._gather_cold(picked, schema, col_name, base_ms, stats)
        return raw, stats

    def _gather_cold(self, picked, schema, col_name: str, base_ms: int,
                     stats: QueryStats):
        from filodb_tpu.query.execbase import RawBlock
        counter_col = col_name in picked[0][0].counter_cols
        fn_is_counter = False
        for t in self.transformers:
            if isinstance(t, PeriodicSamplesMapper):
                spec = RANGE_FUNCTIONS.get(t.function or "")
                fn_is_counter = spec.is_counter if spec else False
                break
        if counter_col and not fn_is_counter:
            # resets/delta/changes need RAW counter values: re-decode the
            # segments host-side (uncached — this is the rare path), like
            # the hot leaf bypassing the pre-corrected mirror
            return self._gather_cold_raw(picked, col_name, base_ms, stats)
        host = any(b.is_host for b, _ in picked)
        seg_inputs = []
        for block, rows in picked:
            ts_off = block.ts_off
            vals = block.cols[col_name]
            if host:
                ts_off = np.asarray(ts_off)
                vals = np.asarray(vals)
            if host or isinstance(vals, np.ndarray):
                ts_g = np.asarray(ts_off)[rows]
                v_g = np.asarray(vals)[rows]
            else:
                idx = jnp.asarray(rows.astype(np.int32))
                ts_g = jnp.take(ts_off, idx, axis=0)
                v_g = jnp.take(vals, idx, axis=0)
            seg_inputs.append({
                "block": block, "rows": rows, "ts_off": ts_g, "vals": v_g,
                "counts": block.counts[rows],
                "t0": block.meta.start_ms,
                "vbase": block.vbase[col_name][rows],
            })
        samples = int(sum(int(si["counts"].sum()) for si in seg_inputs))
        stats.series_scanned = 0
        stats.samples_scanned = samples
        if len(seg_inputs) == 1:
            si = seg_inputs[0]
            block, rows = picked[0]
            keys = block.keys_for(rows)
            stats.series_scanned = int(rows.size)
            dense = block.dense.get(col_name, False)
            shared = block.ts_row0 if block.uniform else None
            self._fused_cache_key = (("cold", block.serial), 0, col_name,
                                     rows.tobytes())
            return RawBlock(keys, si["ts_off"], si["vals"], si["t0"],
                            None, samples=samples, vbase=si["vbase"],
                            precorrected=counter_col,
                            shared_ts_row=shared, dense=dense,
                            cache_token=("cold", block.serial,
                                         rows.tobytes()))
        return self._merge_cold(seg_inputs, picked, col_name, counter_col,
                                base_ms, stats, samples, host)

    def _merge_cold(self, seg_inputs, picked, col_name: str,
                    counter_col: bool, base_ms: int, stats, samples: int,
                    host: bool):
        """Stitch K time-ordered segment gathers into one packed [Su, Tt]
        RawBlock: union the row sets, chain counter corrections across
        segment boundaries, and pack each union row's samples contiguously
        (the general windowing path needs per-row-sorted offsets with pads
        only at the end)."""
        from filodb_tpu.query.execbase import RawBlock
        serials = tuple(b.serial for b, _ in picked)
        rows_token = b"".join(r.tobytes() for _, r in picked)
        mkey = (serials, col_name, rows_token, base_ms)
        cached = self.tier.merged_get(mkey)
        if cached is not None:
            # repeat query over the same cold row set: reuse the packed
            # merge (the cold analogue of the fused prepared-input cache)
            union_keys, ts_out, v_out, out_vbase, shared, dense, Su = cached
            stats.series_scanned = Su
            self._fused_cache_key = (("cold",) + serials, 0, col_name,
                                     rows_token)
            return RawBlock(union_keys, ts_out, v_out, base_ms, None,
                            samples=samples, vbase=out_vbase,
                            precorrected=counter_col, shared_ts_row=shared,
                            dense=dense,
                            cache_token=("cold", serials, rows_token))
        union: Dict[bytes, int] = {}
        union_keys = []
        urows_per = []
        for block, rows in picked:
            pk_bytes = block.identity.pk_bytes
            rl = rows.tolist()
            urows = np.empty(len(rl), dtype=np.int64)
            new_local = []
            for i, r in enumerate(rl):
                u = union.get(pk_bytes[r])
                if u is None:
                    u = union[pk_bytes[r]] = len(union)
                    new_local.append(i)
                urows[i] = u
            if new_local:
                union_keys.extend(
                    block.keys_for(rows[np.asarray(new_local)]))
            urows_per.append(urows)
        Su = len(union)
        stats.series_scanned = Su
        # per-union-row packed layout + cross-segment counter carry
        out_vbase = np.full(Su, np.nan)
        carry = np.zeros(Su)
        prev_last = np.full(Su, np.nan)
        flat_parts_ts, flat_parts_v = [], []
        flat_base = 0
        src_of: list = []                # (flat_base, Tk, urows, counts, adj)
        for si, ur in zip(seg_inputs, urows_per):
            block = si["block"]
            cnt = np.asarray(si["counts"], dtype=np.int64)
            vb = np.asarray(si["vbase"], np.float64)
            first_seen = np.isnan(out_vbase[ur])
            out_vbase[ur] = np.where(first_seen, vb, out_vbase[ur])
            if counter_col:
                fr = block.first_raw[col_name][si["rows"]]
                boundary = (~np.isnan(prev_last[ur])) & \
                    np.less(fr, prev_last[ur],
                            where=~np.isnan(fr) & ~np.isnan(prev_last[ur]),
                            out=np.zeros(len(ur), dtype=bool))
                carry[ur] += np.where(boundary, prev_last[ur], 0.0)
            adj = vb + carry[ur] - out_vbase[ur]          # f64 [Rk]
            if counter_col:
                carry[ur] += block.cum_drop[col_name][si["rows"]]
                lr = block.last_raw[col_name][si["rows"]]
                prev_last[ur] = np.where(np.isnan(lr), prev_last[ur], lr)
            Tk = int(np.asarray(si["ts_off"]).shape[1]) if host else \
                int(si["ts_off"].shape[1])
            delta = int(si["t0"] - base_ms)
            if host:
                ts_adj = np.asarray(si["ts_off"])
                ts_adj = np.where(ts_adj == PAD_TS, PAD_TS,
                                  ts_adj + np.int32(delta))
                src = np.asarray(si["vals"])
                v_adj = (src.astype(np.float64)
                         + adj[:, None]).astype(src.dtype)
            else:
                ts_adj = jnp.where(si["ts_off"] == PAD_TS, PAD_TS,
                                   si["ts_off"] + np.int32(delta))
                v_adj = si["vals"] + jnp.asarray(adj[:, None],
                                                 si["vals"].dtype)
            flat_parts_ts.append(ts_adj.reshape(-1))
            flat_parts_v.append(v_adj.reshape(-1))
            src_of.append((flat_base, Tk, ur, cnt))
            flat_base += len(ur) * Tk
        ct = np.zeros(Su, dtype=np.int64)
        for _, _, ur, cnt in src_of:
            ct[ur] += cnt
        Tmax = int(ct.max()) if Su else 0
        pad_pos = flat_base                    # one sentinel slot appended
        out_idx = np.full((Su, Tmax), pad_pos, dtype=np.int64)
        write_pos = np.zeros(Su, dtype=np.int64)
        for base_k, Tk, ur, cnt in src_of:
            jj = np.arange(Tk)
            valid = jj[None, :] < cnt[:, None]
            src = base_k + np.arange(len(ur))[:, None] * Tk + jj[None, :]
            rows_rep = np.repeat(ur, cnt)
            cols_rep = (write_pos[ur][:, None] + jj[None, :])[valid]
            out_idx[rows_rep, cols_rep] = src[valid]
            write_pos[ur] += cnt
        if host:
            flat_ts = np.concatenate(
                flat_parts_ts + [np.asarray([PAD_TS], np.int32)])
            flat_v = np.concatenate(
                flat_parts_v + [np.asarray([np.nan],
                                           flat_parts_v[0].dtype)])
            ts_out = flat_ts[out_idx]
            v_out = flat_v[out_idx]
        else:
            flat_ts = jnp.concatenate(
                flat_parts_ts + [jnp.asarray([PAD_TS], np.int32)])
            flat_v = jnp.concatenate(
                flat_parts_v
                + [jnp.asarray([np.nan], flat_parts_v[0].dtype)])
            idx_dev = jnp.asarray(out_idx)
            ts_out = jnp.take(flat_ts, idx_dev)
            v_out = jnp.take(flat_v, idx_dev)
        dense = all(b.dense.get(col_name, False) for b, _ in picked)
        # shared grid survives the merge only when every union row took
        # every segment's full uniform grid
        shared = None
        if all(b.uniform for b, _ in picked) \
                and all(len(ur) == Su for _, _, ur, _ in src_of) \
                and Su > 0 and (ct == ct[0]).all():
            parts = []
            for b, _ in picked:
                row0 = b.ts_row0[:int(b.counts[0])].astype(np.int64) \
                    + (b.meta.start_ms - base_ms)
                parts.append(row0.astype(np.int32))
            cat = np.concatenate(parts)
            if cat.size == Tmax:
                shared = cat
        self._fused_cache_key = (("cold",) + serials, 0, col_name,
                                 rows_token)
        self.tier.merged_put(mkey, (union_keys, ts_out, v_out, out_vbase,
                                    shared, dense, Su))
        return RawBlock(union_keys, ts_out, v_out, base_ms, None,
                        samples=samples, vbase=out_vbase,
                        precorrected=counter_col, shared_ts_row=shared,
                        dense=dense,
                        cache_token=("cold", serials, rows_token))

    def _gather_cold_raw(self, picked, col_name: str, base_ms: int,
                         stats):
        """Raw-value host path (non-counter function on a counter column):
        re-decode the segments and merge uncorrected values."""
        from filodb_tpu.query.execbase import RawBlock
        series: Dict[bytes, list] = {}
        keys: Dict[bytes, object] = {}
        for block, rows in picked:
            hdr, ts, cols = self.tier.store.load(block.meta)
            vals = cols.get(col_name)
            if vals is None:
                continue
            for r in rows.tolist():
                kb = block.part_keys[r].to_bytes()
                n = int(hdr["counts"][r])
                series.setdefault(kb, []).append((ts[r, :n], vals[r, :n]))
                keys.setdefault(kb, block.keys_for(np.asarray([r]))[0])
        if not series:
            return None
        Su = len(series)
        merged = []
        for kb, parts in series.items():
            parts.sort(key=lambda p: p[0][0] if len(p[0]) else 0)
            merged.append((np.concatenate([p[0] for p in parts]),
                           np.concatenate([p[1] for p in parts])))
        Tmax = max(len(t) for t, _ in merged)
        counts = np.asarray([len(t) for t, _ in merged], dtype=np.int64)
        ts_grid = np.zeros((Su, Tmax), dtype=np.int64)
        v_grid = np.full((Su, Tmax), np.nan)
        for i, (t, v) in enumerate(merged):
            ts_grid[i, :len(t)] = t
            v_grid[i, :len(v)] = v
        stats.series_scanned = Su
        stats.samples_scanned = int(counts.sum())
        ts_off = to_offsets(ts_grid, counts, base_ms)
        vals, vbase = counter_ops.rebase_values(v_grid, False)
        dense = not bool(np.isnan(
            vals[np.arange(Tmax)[None, :] < counts[:, None]]).any())
        return RawBlock(list(keys.values()), ts_off, vals, base_ms, None,
                        samples=stats.samples_scanned, vbase=vbase,
                        precorrected=False, shared_ts_row=None,
                        dense=dense)


def _estimate_scan(store, rows: np.ndarray, start_ms: int,
                   end_ms: int) -> int:
    """Estimated samples in [start_ms, end_ms] across the given store rows,
    read from the store: what a leaf's SelectionFacts.estimate answers
    from its memoised extents."""
    return estimate_samples(*store.row_extents(rows), start_ms, end_ms)


def _attached_chip() -> bool:
    """Whether this process's leaves dispatch to an attached TPU."""
    import jax
    return jax.default_backend() == "tpu"


def leaf_route(est_samples: int, values_per_sample: int, cap: int,
               mirrored: bool = False) -> str:
    """Where a shard leaf gathers its rows: "host" when its estimated
    working set, in VALUES, is at or under `cap`
    (query.host_route_max_samples; 0 turns the rule off), else "device"
    (the mirror).  A scalar sample is one value, a histogram sample one
    per bucket: a 64-bucket leaf of 400,000 samples is 25.6 M values.

    `mirrored`: the leaf may read the device mirror of an attached chip.
    Such a leaf is never small enough for the host: a fused dispatch over
    the mirror costs the host about 8 ms whatever its rows, and the host
    route reset-corrects whole stored rows, 1.9 s for a 3,000-series shard
    (PERF.md section 6, PR 35).  The cap then routes only leaves that
    cannot read the mirror."""
    if mirrored:
        return "device"
    values = est_samples * max(values_per_sample, 1)
    return "host" if cap > 0 and 0 < values <= cap else "device"


# ------------------------------------------------------------- scalar execs


class TimeScalarGeneratorExec(LeafExecPlan):
    """time(), hour(), ... (ref: exec/TimeScalarGeneratorExec:84)."""

    def __init__(self, ctx, start_ms, step_ms, end_ms, function="time"):
        super().__init__(ctx)
        self.start_ms, self.step_ms, self.end_ms = start_ms, step_ms, end_ms
        self.function = function

    def args_str(self):
        return f"function={self.function}"

    def _do_execute(self, source) -> QueryResultLike:
        wends = make_window_ends(self.start_ms, self.end_ms, self.step_ms)
        secs = wends / 1000.0
        if self.function == "time":
            vals = secs
        else:
            # hour()/minute()/day_of_week()... on step timestamps: the date
            # INSTANT_FUNCTIONS already interpret values as epoch seconds
            vals = np.asarray(INSTANT_FUNCTIONS[self.function](jnp.asarray(secs)))
        return ScalarResult(wends, np.asarray(vals, dtype=float)), QueryStats()


class ScalarFixedDoubleExec(LeafExecPlan):
    """Literal scalar (ref: exec/ScalarFixedDoubleExec:76)."""

    def __init__(self, ctx, start_ms, step_ms, end_ms, value: float):
        super().__init__(ctx)
        self.start_ms, self.step_ms, self.end_ms = start_ms, step_ms, end_ms
        self.value = value

    def args_str(self):
        return f"value={self.value}"

    def _do_execute(self, source) -> QueryResultLike:
        wends = make_window_ends(self.start_ms, self.end_ms, self.step_ms)
        return ScalarResult(wends, np.full(len(wends), self.value)), QueryStats()


class ScalarBinaryOperationExec(LeafExecPlan):
    """scalar op scalar (ref: exec/ScalarBinaryOperationExec:72)."""

    def __init__(self, ctx, start_ms, step_ms, end_ms, operator, lhs, rhs):
        super().__init__(ctx)
        self.start_ms, self.step_ms, self.end_ms = start_ms, step_ms, end_ms
        self.operator = operator
        self.lhs = lhs          # float or ScalarBinaryOperationExec
        self.rhs = rhs

    def args_str(self):
        return f"operator={self.operator}"

    def _eval(self, x, source):
        if isinstance(x, ScalarBinaryOperationExec):
            return x._do_execute(source)[0].values
        return float(x)

    def _do_execute(self, source) -> QueryResultLike:
        wends = make_window_ends(self.start_ms, self.end_ms, self.step_ms)
        a = np.broadcast_to(self._eval(self.lhs, source), wends.shape).astype(float)
        b = np.broadcast_to(self._eval(self.rhs, source), wends.shape).astype(float)
        # scalar-scalar comparisons always behave as `bool` (PromQL requires it)
        out = np.asarray(apply_binary_op(
            jnp.asarray(a), jnp.asarray(b), op=self.operator,
            bool_modifier=True))
        return ScalarResult(wends, out), QueryStats()


