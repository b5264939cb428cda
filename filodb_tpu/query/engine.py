"""QueryEngine — parse -> plan -> execute facade (the QueryActor analogue).

ref: coordinator/.../QueryActor.scala:119-137 (LogicalPlan2Query ->
SingleClusterPlanner.materialize -> ExecPlan.execute) and
prometheus/.../query/PrometheusModel.scala (result JSON conversion).
"""
from __future__ import annotations

import json
import math
import time as _time
import uuid
from dataclasses import dataclass
from typing import Dict, List, Optional

import numpy as np

from filodb_tpu.parallel.shardmapper import ShardMapper, SpreadProvider
from filodb_tpu.promql.parser import (TimeStepParams,
                                      query_range_to_logical_plan)
from filodb_tpu.query import logical as lp
from filodb_tpu.query.planner import SingleClusterPlanner
from filodb_tpu.query.rangevector import (PlannerParams, QueryContext,
                                          QueryResult, QueryStats)


class QueryEngine:

    def __init__(self, dataset: str, source,
                 shard_mapper: Optional[ShardMapper] = None,
                 spread_provider: Optional[SpreadProvider] = None,
                 planner: Optional[SingleClusterPlanner] = None,
                 replan_hook=None, config=None):
        self.dataset = dataset
        self.source = source
        # deployment-injected FilodbSettings (FiloServer passes its own,
        # matching the frontends') — None falls back to the settings()
        # singleton per call so bare constructions track config reloads
        self.config = config
        # embedded-engine deployments (no FiloServer) still get the
        # persistent compile cache; idempotent under the standalone path
        from filodb_tpu.config import apply_jax_runtime, settings
        apply_jax_runtime(settings())
        self.shard_mapper = shard_mapper or _single_shard_mapper()
        self.planner = planner or SingleClusterPlanner(
            dataset, self.shard_mapper, spread_provider)
        # () -> SingleClusterPlanner with a FRESH shard-map snapshot.
        # When a scatter-gather fails shard_unavailable (owner died
        # mid-query), the engine re-plans through this hook up to
        # query.dispatch_retries times — after failover the new plan
        # dispatches to the reassigned owner (ref: the HA planner's
        # route-around-failure stance, HighAvailabilityPlanner.scala:22)
        self.replan_hook = replan_hook

    def _ctx(self, planner_params: Optional[PlannerParams]) -> QueryContext:
        from filodb_tpu.query.activequeries import take_admission
        from filodb_tpu.query.rangevector import compute_deadline
        q = self._qconfig()
        if planner_params is None:
            # bare-engine callers inherit the server's partial-results
            # stance; explicit PlannerParams always win
            planner_params = PlannerParams(
                allow_partial_results=q.allow_partial_results)
        # frontend-admitted queries carry their ActiveQuery entry across
        # the layer gap on a thread-local: the context adopts its id —
        # so the registry key, the trace id, and ctx.query_id are ONE
        # stable identifier — and its CancellationToken
        ent = take_admission()
        qid = ent.query_id if ent is not None else str(uuid.uuid4())
        # end-to-end deadline: the frontend stamps deadline_unix_s at
        # ADMISSION (queue wait counts); otherwise the budget starts now
        ctx = QueryContext(query_id=qid,
                           submit_time_ms=int(_time.time() * 1000),
                           planner_params=planner_params,
                           deadline_unix_s=compute_deadline(
                               planner_params, q.default_timeout_s))
        if ent is not None:
            # plain attributes, NOT dataclass fields: a dispatched
            # subtree serializes without them (remote nodes register
            # their own entry under the same query id)
            ctx.cancel = ent.token
            ctx.active = ent
            # the tenant workspace rides the context so the replica-
            # failover dispatcher can apply the tenant's shuffle-shard
            # node preference (query/qos.py) at dispatch time
            ctx.tenant_ws = ent.tenant_ws
        return ctx

    def _qconfig(self):
        if self.config is not None:
            return self.config.query
        from filodb_tpu.config import settings
        return settings().query

    def query_range(self, promql: str, start_s: int, step_s: int, end_s: int,
                    planner_params: Optional[PlannerParams] = None
                    ) -> QueryResult:
        from filodb_tpu.query.activequeries import peek_admission
        from filodb_tpu.utils.metrics import span
        ent = peek_admission()
        if ent is not None:
            ent.set_phase("parsing")
        # span: the parse share of the fixed per-query floor is
        # attributable in traces (parse itself is AST-memoized —
        # promql.parser.parse_query_cached — so re-polled dashboard
        # strings skip tokenization entirely); stats.parse_s is its clock
        parsing = span("query_parse", hist=True)
        try:
            with parsing:
                plan = query_range_to_logical_plan(
                    promql, TimeStepParams(start_s, step_s, end_s))
        except Exception as e:  # noqa: BLE001 — parse errors surface in result
            return QueryResult([], error=f"parse error: {e}")
        res = self.exec_logical_plan(plan, planner_params)
        res.stats.parse_s += parsing.dur_s
        return res

    def query_instant(self, promql: str, time_s: int,
                      planner_params: Optional[PlannerParams] = None
                      ) -> QueryResult:
        return self.query_range(promql, time_s, 1, time_s, planner_params)

    def query_range_batch(self, promqls: List[str], start_s: int,
                          step_s: int, end_s: int,
                          planner_params: Optional[PlannerParams] = None
                          ) -> List[QueryResult]:
        """Evaluate a dashboard's worth of queries over one time grid,
        merging compatible fused leaves into single kernel dispatches.

        The round-4 on-chip measurements (doc/kernels.md) show a fused
        leaf query is dominated by per-call dispatch latency, not device
        time — so P panels over the same working set and window grid
        should cost ONE dispatch, not P.  Three phases: (1) every
        in-process MultiSchemaPartitionsExec leaf runs its gather + fused
        preflight (prepare_fused), parking the gathered data; (2)
        compatible FusedCalls merge via fusedbatch.finish_fused_calls
        (disjoint-group multi-hot epilogue, at most two dispatches per
        compatible set); (3) each tree executes normally, leaves reusing
        the parked data and injected partials.  Queries that don't fit
        the pattern (parse errors, metadata plans, non-fusable shapes,
        remote-dispatched leaves) take their normal paths unchanged.

        The reference has no analogue — its iterator engine pays per-
        series cost either way; this is a TPU-shaped throughput feature
        (amortizing dispatch the way the MXU amortizes FLOPs).
        """
        from filodb_tpu.ops import hostleaf
        from filodb_tpu.query import exprfuse
        from filodb_tpu.query.fusedbatch import decline_hist_quantile
        from filodb_tpu.query.activequeries import (set_admission,
                                                    take_admission)
        # the coalesce LEADER's admission entry must bind to ITS query,
        # not to whichever batch member happens to mint a context first
        # (a parse failure on the leader's own query would otherwise
        # hand its id/token to another client's query — a kill of the
        # leader's id would then cancel the wrong tenant's work)
        adm = take_admission()
        results: List[Optional[QueryResult]] = [None] * len(promqls)
        entries = []
        for i, q in enumerate(promqls):
            mine = adm is not None and q == adm.promql
            if mine:
                set_admission(adm)
                adm = None
            t0 = _time.perf_counter()
            try:
                plan = query_range_to_logical_plan(
                    q, TimeStepParams(start_s, step_s, end_s))
            except Exception as e:  # noqa: BLE001
                results[i] = QueryResult([], error=f"parse error: {e}")
                if mine:
                    take_admission()     # never leak to the next query
                continue
            parse_t = _time.perf_counter() - t0
            if isinstance(plan, lp.MetadataQueryPlan):
                results[i] = self.exec_logical_plan(plan, planner_params)
                results[i].stats.parse_s += parse_t
                continue
            ctx = self._ctx(planner_params)
            t0 = _time.perf_counter()
            try:
                ep = self.planner.materialize(plan, ctx)
            except Exception as e:  # noqa: BLE001
                results[i] = QueryResult([], error=f"planning error: {e}")
                continue
            entries.append((i, ep, ctx, plan,
                            parse_t, _time.perf_counter() - t0))
        # whole-expression compilation (query/exprfuse.py): EVERY tree's
        # in-process leaves run their fused preflight — under one gather
        # memo scope, so N panels over a shared working set scan it once
        # — then all the prepared kernel work merges into the batched
        # dispatch (killed queries filtered out before the dispatch)
        calls = []
        comps = {}
        if self._qconfig().exprfuse_enabled:
            with hostleaf.batch_gather_memo():
                for i, ep, _, _, _, _ in entries:
                    comp = exprfuse.compile_tree(ep, self.source)
                    if comp is not None:
                        comps[i] = comp
                        calls.extend(comp.calls)
                        # a batch's panels share working sets and calls:
                        # its histogram quantiles keep the host path
                        for _ in comp.quantiles:
                            decline_hist_quantile("batch")
            exprfuse.finish_prepared(calls)
        for i, ep, ctx, plan, parse_t, plan_t in entries:
            res = ep.execute(self.source)
            res.trace_id = ctx.query_id
            if res.error and res.error.startswith("shard_unavailable") \
                    and (self.replan_hook is not None
                         or ctx.planner_params.allow_partial_results):
                # failover retry (and, past the retries, the partial-
                # result degrade) for the dashboard-batch path too: the
                # retried query re-plans through exec_logical_plan (it
                # loses this batch's fusion, which is moot — its shard
                # owner just died)
                res = self.exec_logical_plan(plan, planner_params)
            res.stats.parse_s += parse_t
            res.stats.plan_s += plan_t
            comp = comps.get(i)
            if comp is not None:
                res.stats.exprfuse_fused += comp.fused
                res.stats.exprfuse_degraded += comp.degraded
            results[i] = res
        return results

    def _engage_partial_replan(self, plan: lp.LogicalPlan, ctx):
        """The shard STAYED unavailable after the re-plan retries and
        partials are allowed: degrade instead of fail — engage the
        scatter-gather drop (partial_now) and re-materialize; with the
        peer's breaker now open the next pass fails fast per dropped
        child and the survivors merge into a FLAGGED partial result
        (ref: the Thanos/Cortex partial-response stance).  One home for
        the degrade protocol shared by the metadata and data paths; the
        dataclasses copy keeps the caller's PlannerParams unmutated."""
        import dataclasses as _dc

        from filodb_tpu.utils.metrics import registry
        registry.counter("query_partial_engaged").increment()
        ctx.planner_params = _dc.replace(ctx.planner_params,
                                         partial_now=True)
        return self.planner.materialize(plan, ctx)

    def exec_logical_plan(self, plan: lp.LogicalPlan,
                          planner_params: Optional[PlannerParams] = None
                          ) -> QueryResult:
        from filodb_tpu.utils.metrics import span
        ctx = self._ctx(planner_params)
        ent = getattr(ctx, "active", None)
        if ent is not None:
            ent.set_phase("planning")
        planning = span("query_plan", hist=True)
        try:
            with planning:
                ep = self.planner.materialize(plan, ctx)
        except Exception as e:  # noqa: BLE001
            return QueryResult([], error=f"planning error: {e}")
        plan_t = planning.dur_s         # stats.plan_s is the span's clock
        if ent is not None:
            ent.set_phase("executing")
        if isinstance(plan, lp.MetadataQueryPlan):
            from filodb_tpu.query.execbase import QueryError
            try:
                try:
                    data, stats = ep.execute_internal(self.source)
                except QueryError as e:
                    if e.code != "shard_unavailable" or \
                            not ctx.planner_params.allow_partial_results:
                        raise
                    # metadata scatters degrade like data queries: a
                    # shard that stays down is dropped and the merged
                    # result flagged partial (labels/series from the
                    # survivors beat a hard error on every dashboard's
                    # label dropdown)
                    try:
                        ep = self._engage_partial_replan(plan, ctx)
                    except QueryError:
                        raise
                    except Exception as e2:  # noqa: BLE001
                        return QueryResult([], error=f"replan error: {e2}")
                    data, stats = ep.execute_internal(self.source)
            except QueryError as e:
                # same structured surface as data queries: a dead peer
                # or an expired deadline on a metadata scatter is a
                # typed result error, not a 500
                return QueryResult([], error=str(e))
            stats.plan_s += plan_t
            if isinstance(data, QueryResult):
                if data.partial:
                    # same root-level counter data queries get from
                    # ExecPlan.execute (metadata plans run through
                    # execute_internal, which never increments it)
                    from filodb_tpu.utils.metrics import registry
                    registry.counter("query_partial_results").increment()
                return data
            return QueryResult([], stats)
        # whole-expression compilation (query/exprfuse.py): a multi-leaf
        # tree (joins, multi-shard scatter) batches its leaves' fused
        # preflights into one merged dispatch; single-leaf trees keep
        # the leaf's exact standalone path (min_leaves=2)
        comp = hoisted = None
        if self._qconfig().exprfuse_enabled:
            from filodb_tpu.query import exprfuse
            from filodb_tpu.query.execbase import fold_exec_tally
            from filodb_tpu.utils.metrics import exec_tally
            # the leaves' gather + preflight and their merged dispatch
            # run HERE, before the tree: outside every exec node, so the
            # tree's own tally never sees them.  Tallied like a node, on
            # the two spans' clock, and merged into the result's stats
            # (before this, stats.phases lost this work entirely)
            snap = exec_tally.snapshot()
            with span("engine.prepare_leaves") as preparing:
                comp = exprfuse.compile_tree(ep, self.source, min_leaves=2)
            took = preparing.dur_s
            if comp is not None:
                with span("engine.dispatch_leaves") as dispatching:
                    exprfuse.finish_prepared(comp.calls, comp.quantiles)
                took += dispatching.dur_s
                hoisted = QueryStats()
                fold_exec_tally(hoisted, took)
            exec_tally.restore(snap, took)
        res = ep.execute(self.source)
        if comp is not None:
            res.stats.merge(hoisted)
            res.stats.exprfuse_fused += comp.fused
            res.stats.exprfuse_degraded += comp.degraded
        res.stats.plan_s += plan_t
        res.trace_id = ctx.query_id
        if res.error and res.error.startswith("shard_unavailable") \
                and self.replan_hook is not None:
            from filodb_tpu.utils.metrics import registry
            for _ in range(max(self._qconfig().dispatch_retries, 0)):
                # a shard owner died mid-query: re-plan against a fresh
                # shard-map snapshot and retry on the reassigned owner
                # (only shard_unavailable — dispatch_timeout is never
                # retried, the remote may still be executing)
                registry.counter("query_replan_retries").increment()
                try:
                    self.planner = self.replan_hook()
                    ep = self.planner.materialize(plan, ctx)
                except Exception as e:  # noqa: BLE001
                    return QueryResult([], error=f"replan error: {e}")
                res = ep.execute(self.source)
                res.trace_id = ctx.query_id
                if not (res.error
                        and res.error.startswith("shard_unavailable")):
                    break
        if res.error and res.error.startswith("shard_unavailable") \
                and ctx.planner_params.allow_partial_results:
            try:
                ep = self._engage_partial_replan(plan, ctx)
            except Exception as e:  # noqa: BLE001
                return QueryResult([], error=f"replan error: {e}")
            res = ep.execute(self.source)
            res.trace_id = ctx.query_id
        return res

    # ------------------------------------------------- Prometheus JSON model

    @staticmethod
    def render_prom_matrix(result: QueryResult) -> Dict:
        """ref: PrometheusModel.toPromSuccessResponse (matrix result).  The
        envelope is a small dict WITHOUT `data.result`; the rows ride beside
        it under `_rendered`, already the response's JSON text (a
        `RenderedRows`), for the HTTP server to splice in unwalked."""
        err = _prom_error_payload(result)
        if err is not None:
            return err
        payload = {"status": "success", "data": {"resultType": "matrix"},
                   "_rendered": _render_matrix_rows(result.blocks)}
        return _attach_partial_fields(payload, result.partial,
                                      result.stats.warnings)

    @staticmethod
    def to_prom_matrix(result: QueryResult) -> Dict:
        """`render_prom_matrix` with the rows parsed into `data.result`:
        the same text read again, so there is one set of formatting
        rules."""
        return rows_parsed(QueryEngine.render_prom_matrix(result))

    @staticmethod
    def to_prom_vector(result: QueryResult) -> Dict:
        """Instant-vector response (last step of each series)."""
        err = _prom_error_payload(result)
        if err is not None:
            return err
        out = []
        for key, wends, vals in result.series():
            if vals.ndim == 2 or len(vals) == 0:
                continue
            v = vals[-1]
            if not math.isnan(v):
                out.append({"metric": _prom_labels(key.labels_dict),
                            "value": [int(wends[-1]) / 1000.0, _fmt(v)]})
        payload = {"status": "success",
                   "data": {"resultType": "vector", "result": out}}
        return _attach_partial_fields(payload, result.partial,
                                      result.stats.warnings)


def _walk_plan(ep):
    """Yield every node of an exec tree (pre-order)."""
    yield ep
    for c in ep.children:
        yield from _walk_plan(c)


def _prom_error_payload(result: QueryResult) -> Optional[Dict]:
    """Error half of the Prometheus envelope, or None for success.  One
    home for the errorType taxonomy (deadline expiry maps to "timeout",
    a kill to "canceled", so clients can route on it) shared by the
    matrix and vector serializers."""
    if not result.error:
        return None
    if result.error.startswith("query_timeout"):
        etype = "timeout"
    elif result.error.startswith("query_canceled"):
        etype = "canceled"
    elif result.error.startswith(("tenant_overloaded",
                                  "tenant_limit_exceeded")):
        # read-side throttles share the write side's errorType (the
        # remote_write 429s use it too): clients route on it to back off
        etype = "too_many_requests"
    else:
        etype = "query_error"
    return {"status": "error", "errorType": etype, "error": result.error}


def _attach_partial_fields(payload: Dict, partial: bool,
                           warnings: List[str]) -> Dict:
    """Degradation fields of the envelope — never-silent partials: the
    warnings list plus "partial": true.  Shared by the matrix and vector
    serializers AND the metadata route handlers (labels/series payloads
    flag dropped shards the same way)."""
    if partial or warnings:
        payload["warnings"] = (
            list(warnings)
            or ["partial results: one or more shards were unreachable"])
    if partial:
        payload["partial"] = True
    return payload


def _prom_labels(labels: Dict[str, str]) -> Dict[str, str]:
    out = dict(labels)
    metric = out.pop("_metric_", None)
    if metric:
        out["__name__"] = metric
    return out


@dataclass(frozen=True)
class RenderedRows:
    """The `result` array of a matrix response as its JSON text, the points
    written, and those of them that took the per-point path.  (`json.dumps`
    refuses it: an envelope that still carries one is not a body yet.)"""
    text: str
    points: int
    fallbacks: int


def rows_parsed(payload: Dict) -> Dict:
    """A matrix envelope as Prometheus shapes it: the rows that ride beside
    it as text (`render_prom_matrix`) parsed into `data.result`."""
    rendered = payload.pop("_rendered", None)
    if rendered is not None:
        payload["data"]["result"] = json.loads(rendered.text)
    return payload


_compact_json = json.JSONEncoder(separators=(",", ":")).encode


def _render_matrix_rows(blocks) -> RenderedRows:
    """Every rule of a matrix's rows, once: histogram blocks and rows
    without a point are skipped, `_metric_` leaves as `__name__`, a NaN
    point is left out, a value is its `.17g` text (`+Inf` / `-Inf`), a
    timestamp what `json.dumps` writes for that float."""
    rows: List[str] = []
    points = fallbacks = 0
    for b in blocks:
        vals = np.asarray(b.values)
        if vals.ndim != 2 or not vals.shape[1]:
            continue    # histogram series (no buckets here), or no window
        # one template a block, the JSON text of a point with its timestamp
        # written and its value still to come: a row that is finite
        # throughout is then ONE formatting call, with no list, float or
        # string object a point (20 series x 721 windows a response at a
        # dashboard's own resolution).  Two NumPy calls a block say which
        # rows those are; a call over 500 elements lets the interpreter
        # lock go, and a request waits up to 5 ms to have it back
        stamps = [repr(t / 1000.0)
                  for t in np.asarray(b.wends, np.int64).tolist()]
        pieces = [f'[{t},"%.17g"]' for t in stamps]
        whole = "[" + ",".join(pieces) + "]"
        finite = np.isfinite(vals).all(axis=1).tolist()
        if len(finite) > 1 and all(finite):
            # ... and a block that is finite throughout is one call too: a
            # row a host is 4,000 rows of 13 points a response, and a call a
            # row is then most of the calls (a label's `%` is no directive)
            rows.append(",".join(
                ['{"metric":' + _labels_json(key).replace("%", "%%")
                 + ',"values":' + whole + "}" for key in b.keys])
                % tuple(vals.ravel().tolist()))
            points += vals.size
            continue
        for i, key in enumerate(b.keys):
            if finite[i]:
                text = whole % tuple(vals[i].tolist())
                points += len(stamps)
            else:
                idx = np.flatnonzero(~np.isnan(vals[i])).tolist()
                if not idx:
                    continue
                pts = vals[i][idx].tolist()
                if math.inf in pts or -math.inf in pts:
                    text = "[" + ",".join(
                        [f'[{stamps[j]},"{_fmt(v)}"]'
                         for j, v in zip(idx, pts)]) + "]"
                    fallbacks += len(idx)
                else:
                    text = ("[" + ",".join([pieces[j] for j in idx])
                            + "]") % tuple(pts)
                points += len(idx)
            rows.append(f'{{"metric":{_labels_json(key)},"values":{text}}}')
    return RenderedRows("[" + ",".join(rows) + "]", points, fallbacks)


_LABELS_JSON: Dict[RangeVectorKey, str] = {}
_LABELS_JSON_MAX = 1 << 16


def _labels_json(key: RangeVectorKey) -> str:
    """A row's `metric` object as JSON text, remembered by key: the groups
    of a dashboard or a fleet table are the same keys request after
    request (4,000 hosts a response), and their text a pure function of
    them.  Emptied when full: a bound, not a policy."""
    text = _LABELS_JSON.get(key)
    if text is None:
        if len(_LABELS_JSON) >= _LABELS_JSON_MAX:
            _LABELS_JSON.clear()
        text = _LABELS_JSON[key] = _compact_json(
            _prom_labels(key.labels_dict))
    return text


def _fmt(v: float) -> str:
    if math.isinf(v):
        return "+Inf" if v > 0 else "-Inf"
    return f"{v:.17g}" if v == v else "NaN"


def _single_shard_mapper() -> ShardMapper:
    from filodb_tpu.parallel.shardmapper import ShardEvent
    m = ShardMapper(1)
    m.update_from_event(ShardEvent("IngestionStarted", "", 0, "local"))
    return m
