"""Planner-hierarchy tests (models ref: coordinator/src/test/.../queryplanner/
LongTimeRangePlannerSpec, HighAvailabilityPlannerSpec,
MultiPartitionPlannerSpec, ShardKeyRegexPlannerSpec, LogicalPlanParserSpec)."""
import numpy as np
import pytest

from filodb_tpu.core.index import Equals, EqualsRegex
from filodb_tpu.query import logical as lp
from filodb_tpu.query import planutils as pu
from filodb_tpu.query.exec import ExecPlan, StitchRvsExec
from filodb_tpu.query.planner import QueryPlanner
from filodb_tpu.query.planners import (FailureProvider, FailureTimeRange,
                                       HighAvailabilityPlanner, LocalRoute,
                                       LongTimeRangePlanner,
                                       MultiPartitionPlanner,
                                       MultiPartitionReduceAggregateExec,
                                       PartitionAssignment,
                                       PartitionLocationProvider,
                                       PromQlRemoteExec, RemoteRoute,
                                       ShardKeyRegexPlanner,
                                       SinglePartitionPlanner,
                                       _matrix_json_to_block, plan_routes)
from filodb_tpu.query.planutils import TimeRange
from filodb_tpu.query.rangevector import (QueryContext, QueryStats,
                                          RangeVectorKey, ResultBlock)
from filodb_tpu.promql.parser import (TimeStepParams,
                                      query_range_to_logical_plan)

START_S = 1_600_000_000
T = TimeStepParams(START_S, 60, START_S + 3600)


def _plan(q, params=T):
    return query_range_to_logical_plan(q, params)


class _Dummy(ExecPlan):
    def __init__(self, tag, plan=None):
        super().__init__(QueryContext())
        self.tag = tag
        self.plan = plan

    def _do_execute(self, source):
        return None, QueryStats()


class _RecordingPlanner(QueryPlanner):
    def __init__(self, tag):
        self.tag = tag
        self.materialized = []

    def materialize(self, plan, ctx):
        self.materialized.append(plan)
        return _Dummy(self.tag, plan)


# ------------------------------------------------------------- unparse


@pytest.mark.parametrize("q", [
    'sum(rate(http_requests_total{job="api"}[5m]))',
    'sum by (job,instance)(rate(foo{_ws_="demo",_ns_="app"}[1m]))',
    'histogram_quantile(0.9,sum by (le)(rate(req_bucket{job="a"}[1m])))',
    'foo{job="x"}',
    'foo{job!="x",mode=~"user|sys"}',
    '(foo{a="1"} + bar{b="2"})',
    '(foo{a="1"} * on (host) group_left () bar{b="2"})',
    '(foo{a="1"} > bool 10)',
    'topk(5,foo{job="j"})',
    'quantile(0.5,foo{job="j"})',
    'abs(foo{job="j"})',
    'clamp_max(foo{job="j"},100)',
    'label_replace(foo{job="j"},"dst","$1","src","(.*)")',
    'sort_desc(foo{job="j"})',
    'avg_over_time(foo{job="j"}[10m])',
    'min_over_time((rate(foo{job="j"}[5m]))[30m:1m])',
])
def test_unparse_round_trip(q):
    p1 = _plan(q)
    s = pu.unparse(p1)
    p2 = _plan(s)
    assert p1 == p2, f"{q!r} -> {s!r} did not round-trip"


def test_unparse_offset_and_column():
    p = _plan('rate(foo::count{job="x"}[5m] offset 10m)')
    s = pu.unparse(p)
    assert "offset 10m" in s and "::count" in s
    assert _plan(s) == p


# -------------------------------------------------- time-range utilities


def test_copy_with_time_range_rewrites_selector():
    p = _plan('sum(rate(foo{job="x"}[5m]))')
    tr = TimeRange(START_S * 1000 + 600_000, START_S * 1000 + 1_200_000)
    p2 = pu.copy_with_time_range(p, tr)
    assert p2.start_ms == tr.start_ms and p2.end_ms == tr.end_ms
    inner = p2.vectors.series
    # raw fetch reaches back one window before the new start
    assert inner.range_selector.from_ms == tr.start_ms - 300_000
    assert inner.range_selector.to_ms == tr.end_ms


def test_split_plans_on_grid():
    p = _plan('foo{job="x"}', TimeStepParams(START_S, 60, START_S + 86_400))
    parts = pu.split_plans(p, 6 * 3600 * 1000)
    assert len(parts) == 4
    assert parts[0].start_ms == p.start_ms
    assert parts[-1].end_ms == p.end_ms
    for a, b in zip(parts, parts[1:]):
        assert b.start_ms == a.end_ms + p.step_ms
        assert (a.end_ms - a.start_ms) % p.step_ms == 0


def test_get_lookback_window():
    assert pu.get_lookback_ms(_plan('rate(foo[5m])'), 300_000) == 300_000
    assert pu.get_lookback_ms(_plan('sum(rate(foo[15m]))'), 300_000) == 900_000
    assert pu.get_lookback_ms(_plan('foo'), 300_000) == 300_000


# ------------------------------------------------------ LongTimeRange


def _ltr(earliest_raw_ms, latest_ds_ms):
    raw, ds = _RecordingPlanner("raw"), _RecordingPlanner("downsample")
    return LongTimeRangePlanner(raw, ds, lambda: earliest_raw_ms,
                                lambda: latest_ds_ms), raw, ds


def test_ltr_all_raw():
    start_ms = START_S * 1000
    planner, raw, ds = _ltr(start_ms - 7 * 86_400_000, start_ms - 6 * 3600_000)
    out = planner.materialize(_plan('rate(foo[5m])'), QueryContext())
    assert isinstance(out, _Dummy) and out.tag == "raw"
    assert not ds.materialized


def test_ltr_all_downsample():
    start_ms = START_S * 1000
    planner, raw, ds = _ltr(start_ms + 2 * 3600_000 + 600_000, start_ms + 4e7)
    out = planner.materialize(_plan('rate(foo[5m])'), QueryContext())
    assert isinstance(out, _Dummy) and out.tag == "downsample"
    assert not raw.materialized


def test_ltr_straddle_splits_and_stitches():
    start_ms = START_S * 1000
    earliest_raw = start_ms + 20 * 60_000          # raw starts 20m into query
    planner, raw, ds = _ltr(earliest_raw, start_ms + 86_400_000)
    p = _plan('rate(foo[5m])')
    out = planner.materialize(p, QueryContext())
    assert isinstance(out, StitchRvsExec)
    ds_plan, raw_plan = ds.materialized[0], raw.materialized[0]
    # raw part starts at the first grid instant whose 5m window is in raw
    assert raw_plan.start_ms >= earliest_raw + 300_000
    assert (raw_plan.start_ms - p.start_ms) % p.step_ms == 0
    assert raw_plan.end_ms == p.end_ms
    assert ds_plan.start_ms == p.start_ms
    assert ds_plan.end_ms == raw_plan.start_ms - p.step_ms


# --------------------------------------------------------- HA routing


def test_plan_routes_no_failures():
    assert plan_routes(0, 60, 600, [], 300) == [LocalRoute()]


def test_plan_routes_mid_failure():
    start, step, end = 1_000_000, 60_000, 4_000_000
    fail = TimeRange(2_000_000, 2_100_000)
    routes = plan_routes(start, step, end, [fail], 300_000)
    assert isinstance(routes[0], LocalRoute)
    assert isinstance(routes[1], RemoteRoute)
    assert isinstance(routes[2], LocalRoute)
    # local instants never have a window overlapping the failure
    assert routes[0].time_range.end_ms < fail.start_ms
    assert routes[2].time_range.start_ms - 300_000 >= fail.end_ms
    # grid continuity
    assert routes[1].time_range.start_ms == \
        routes[0].time_range.end_ms + step
    assert routes[2].time_range.start_ms == \
        routes[1].time_range.end_ms + step
    assert routes[2].time_range.end_ms == end


class _FP(FailureProvider):
    def __init__(self, failures):
        self.failures = failures

    def get_failures(self, dataset, tr):
        return [f for f in self.failures
                if f.time_range.end_ms >= tr.start_ms
                and f.time_range.start_ms <= tr.end_ms]


def test_ha_planner_no_failure_goes_local():
    local = _RecordingPlanner("local")
    ha = HighAvailabilityPlanner("ds", local, _FP([]), "http://remote/api")
    out = ha.materialize(_plan('rate(foo[5m])'), QueryContext())
    assert isinstance(out, _Dummy) and out.tag == "local"


def test_ha_planner_failure_routes_remote():
    local = _RecordingPlanner("local")
    start_ms = START_S * 1000
    fail = FailureTimeRange("local", TimeRange(start_ms + 1_200_000,
                                               start_ms + 1_500_000))
    ha = HighAvailabilityPlanner("ds", local, _FP([fail]), "http://remote/api")
    p = _plan('sum(rate(foo{job="x"}[5m]))')
    out = ha.materialize(p, QueryContext())
    assert isinstance(out, StitchRvsExec)
    remotes = [c for c in out.children if isinstance(c, PromQlRemoteExec)]
    assert len(remotes) == 1
    assert remotes[0].endpoint == "http://remote/api"
    # the remote query is the same PromQL re-rendered
    assert "rate" in remotes[0].promql and 'job="x"' in remotes[0].promql
    # remote covers the failure window
    assert remotes[0].start_ms <= fail.time_range.end_ms
    assert remotes[0].end_ms >= fail.time_range.start_ms


def test_remote_failure_is_ignored():
    local = _RecordingPlanner("local")
    start_ms = START_S * 1000
    fail = FailureTimeRange("remote", TimeRange(start_ms, start_ms + 600_000),
                            is_remote=True)
    ha = HighAvailabilityPlanner("ds", local, _FP([fail]), "http://remote/api")
    out = ha.materialize(_plan('rate(foo[5m])'), QueryContext())
    assert isinstance(out, _Dummy) and out.tag == "local"


# ----------------------------------------------------- multi-partition


class _Provider(PartitionLocationProvider):
    def __init__(self, assignments):
        self.assignments = assignments

    def get_partitions(self, filters, tr):
        return self.assignments


def test_multi_partition_all_local():
    local = _RecordingPlanner("local")
    start_ms, end_ms = START_S * 1000, (START_S + 3600) * 1000
    prov = _Provider([PartitionAssignment("local", "",
                                          TimeRange(0, end_ms * 2))])
    mp = MultiPartitionPlanner(prov, "local", local)
    out = mp.materialize(_plan('rate(foo[5m])'), QueryContext())
    assert isinstance(out, _Dummy) and out.tag == "local"


def test_multi_partition_splits_by_time():
    local = _RecordingPlanner("local")
    start_ms = START_S * 1000
    mid = start_ms + 1800_000
    prov = _Provider([
        PartitionAssignment("remote-p", "http://p2/api",
                            TimeRange(0, mid - 1)),
        PartitionAssignment("local", "", TimeRange(mid, start_ms + 10**9)),
    ])
    mp = MultiPartitionPlanner(prov, "local", local)
    p = _plan('rate(foo{job="x"}[5m])')
    out = mp.materialize(p, QueryContext())
    assert isinstance(out, StitchRvsExec)
    remote = [c for c in out.children if isinstance(c, PromQlRemoteExec)][0]
    local_child = [c for c in out.children if isinstance(c, _Dummy)][0]
    assert remote.start_ms == p.start_ms
    assert local_child.plan.end_ms == p.end_ms
    # no overlap, grid-aligned
    assert (local_child.plan.start_ms - p.start_ms) % p.step_ms == 0
    assert local_child.plan.start_ms > remote.end_ms


def test_matrix_json_to_block():
    payload = {"status": "success", "data": {"resultType": "matrix", "result": [
        {"metric": {"job": "x"}, "values": [[START_S, "1.5"],
                                            [START_S + 60, "2.5"]]},
        {"metric": {"job": "y"}, "values": [[START_S + 60, "7"]]},
    ]}}
    b = _matrix_json_to_block(payload)
    assert b.num_series == 2
    assert list(b.wends) == [START_S * 1000, (START_S + 60) * 1000]
    assert b.values[0][0] == 1.5 and b.values[1][1] == 7.0
    assert np.isnan(b.values[1][0])


def test_remote_exec_with_fake_transport():
    calls = []

    def transport(endpoint, params):
        calls.append((endpoint, params))
        return {"data": {"result": [{"metric": {"a": "b"},
                                     "values": [[START_S, "4"]]}]}}

    e = PromQlRemoteExec(QueryContext(), "http://r/api", "up", START_S * 1000,
                         60_000, (START_S + 600) * 1000, transport=transport)
    res = e.execute(None)
    assert res.error is None
    assert res.num_series == 1
    assert calls[0][1]["query"] == "up"
    assert calls[0][1]["step"] == 60


# ---------------------------------------------------- single partition


def test_single_partition_selects_by_metric():
    a, b = _RecordingPlanner("a"), _RecordingPlanner("b")
    sp = SinglePartitionPlanner(
        {"a": a, "b": b},
        planner_selector=lambda m: "b" if m.startswith("agg_") else "a")
    out1 = sp.materialize(_plan('rate(foo{job="x"}[5m])'), QueryContext())
    out2 = sp.materialize(_plan('rate(agg_foo{job="x"}[5m])'), QueryContext())
    assert out1.tag == "a" and out2.tag == "b"


# --------------------------------------------------- shard-key regex


def test_shard_key_regex_fans_out():
    inner = _RecordingPlanner("in")
    matcher = lambda fs: [  # noqa: E731
        (Equals("_ws_", "demo"), Equals("_ns_", "app1")),
        (Equals("_ws_", "demo"), Equals("_ns_", "app2")),
    ]
    skr = ShardKeyRegexPlanner(inner, matcher)
    p = _plan('sum(rate(foo{_ws_="demo",_ns_=~"app.*"}[5m]))')
    out = skr.materialize(p, QueryContext())
    assert isinstance(out, MultiPartitionReduceAggregateExec)
    assert len(inner.materialized) == 2
    for sub, ns in zip(inner.materialized, ("app1", "app2")):
        fs = pu.get_raw_series_filters(sub)[0]
        assert Equals("_ns_", ns) in fs
        assert not any(isinstance(f, EqualsRegex) and f.column == "_ns_"
                       for f in fs)


def test_shard_key_equals_passthrough():
    inner = _RecordingPlanner("in")
    skr = ShardKeyRegexPlanner(inner, lambda fs: [])
    p = _plan('sum(rate(foo{_ws_="demo",_ns_="app1"}[5m]))')
    out = skr.materialize(p, QueryContext())
    assert out.tag == "in"


def test_shard_key_regex_join_sides_fan_out_independently():
    inner = _RecordingPlanner("in")

    def matcher(fs):
        # expand only the regex side's namespaces
        return [(Equals("_ws_", "demo"), Equals("_ns_", "app1")),
                (Equals("_ws_", "demo"), Equals("_ns_", "app2"))]

    skr = ShardKeyRegexPlanner(inner, matcher)
    p = _plan('(sum(rate(foo{_ws_="demo",_ns_=~"app.*"}[5m]))'
              ' + sum(rate(bar{_ws_="demo",_ns_="other"}[5m])))')
    skr.materialize(p, QueryContext())
    # rhs (concrete _ns_="other") must NOT be rewritten with lhs combos
    rhs_plans = [m for m in inner.materialized
                 if any(Equals("_metric_", "bar") in fg or
                        any(getattr(f, "value", None) == "bar" for f in fg)
                        for fg in pu.get_raw_series_filters(m))]
    assert rhs_plans, "rhs side was never materialized"
    for m in rhs_plans:
        for fg in pu.get_raw_series_filters(m):
            assert Equals("_ns_", "other") in fg


def test_multi_partition_same_partition_two_windows():
    local = _RecordingPlanner("local")
    start_ms = START_S * 1000
    prov = _Provider([
        PartitionAssignment("remote-p", "http://p2/api",
                            TimeRange(start_ms, start_ms + 1_200_000)),
        PartitionAssignment("local", "",
                            TimeRange(start_ms + 1_260_000,
                                      start_ms + 2_400_000)),
        PartitionAssignment("remote-p", "http://p2/api",
                            TimeRange(start_ms + 2_460_000,
                                      start_ms + 10**9)),
    ])
    mp = MultiPartitionPlanner(prov, "local", local)
    p = _plan('foo{job="x"}')
    out = mp.materialize(p, QueryContext())
    remotes = [c for c in out.children if isinstance(c, PromQlRemoteExec)]
    assert len(remotes) == 2, "second remote-p window was dropped"
    assert remotes[1].end_ms == p.end_ms


def test_multi_partition_reduce_aggregate_compose():
    k1 = RangeVectorKey.make({"job": "x"})
    k2 = RangeVectorKey.make({"job": "y"})
    wends = np.asarray([1000, 2000], dtype=np.int64)
    b1 = ResultBlock([k1, k2], wends, np.asarray([[1.0, 2.0],
                                                  [np.nan, 5.0]]))
    b2 = ResultBlock([k1], wends, np.asarray([[10.0, np.nan]]))
    ex = MultiPartitionReduceAggregateExec(QueryContext(), [], "sum")
    out = ex.compose([b1, b2], QueryStats())
    vals = {k: v for k, v in zip(out.keys, np.asarray(out.values))}
    assert vals[k1][0] == 11.0 and vals[k1][1] == 2.0
    assert np.isnan(vals[k2][0]) and vals[k2][1] == 5.0


def test_at_modifier_survives_time_range_copy_and_unparse():
    """@ plans: copy_with_time_range must keep the pinned inner grid, and
    unparse must emit valid PromQL for remote routing (HA/multi-partition)."""
    from filodb_tpu.promql.parser import (TimeStepParams,
                                          query_range_to_logical_plan)
    from filodb_tpu.query import planutils as pu
    from filodb_tpu.query import logical as lp

    T = TimeStepParams(1_600_000_600, 60, 1_600_003_600)
    for q in ["foo @ 1600000000",
              "rate(foo[5m] @ 1600000000)",
              "max_over_time(foo[10m:1m] @ 1600000000)",
              "max_over_time(foo[10m:1m] offset 5m @ 1600000000)",
              "rate(foo[5m])[30m:1m] @ 1600000000"]:
        plan = query_range_to_logical_plan(q, T)
        assert isinstance(plan, lp.ApplyAtTimestamp), q
        moved = pu.copy_with_time_range(
            plan, pu.TimeRange(1_600_001_000_000, 1_600_002_000_000))
        assert moved.inner.start_ms == moved.inner.end_ms \
            == 1_600_000_000_000, q
        assert moved.start_ms == 1_600_001_000_000
        # unparse -> reparse round trip preserves the pinned time
        text = pu.unparse(plan)
        again = query_range_to_logical_plan(text, T)
        assert isinstance(again, lp.ApplyAtTimestamp), text
        assert again.inner.start_ms == plan.inner.start_ms, text


def test_at_modifier_long_time_range_routes_by_pinned_time():
    """LongTimeRangePlanner must route @ queries by the PINNED time: an @
    older than raw retention goes to the downsample cluster even when the
    outer grid is recent."""
    from filodb_tpu.promql.parser import (TimeStepParams,
                                          query_range_to_logical_plan)
    from filodb_tpu.query.planners import LongTimeRangePlanner

    calls = []

    class _P:
        def __init__(self, name):
            self.name = name

        def materialize(self, plan, ctx):
            calls.append(self.name)
            return object()

    earliest_raw = 1_600_010_000_000
    planner = LongTimeRangePlanner(
        _P("raw"), _P("ds"), lambda: earliest_raw,
        lambda: earliest_raw + 3_600_000)
    T = TimeStepParams(1_600_020_000, 60, 1_600_023_000)  # recent outer grid
    old = query_range_to_logical_plan("foo @ 1600000000", T)   # pinned OLD
    recent = query_range_to_logical_plan(
        f"foo @ {earliest_raw // 1000 + 600}", T)
    planner.materialize(old, QueryContext())
    planner.materialize(recent, QueryContext())
    assert calls == ["ds", "raw"]


def test_at_sentinels_resolve_to_top_level_bounds():
    """start()/end() inside subqueries resolve to the OUTERMOST query
    bounds (PromQL), not the shifted inner conversion range."""
    from filodb_tpu.promql.parser import (TimeStepParams,
                                          query_range_to_logical_plan)
    from filodb_tpu.query import logical as lp

    T = TimeStepParams(1_600_000_600, 60, 1_600_003_600)
    plan = query_range_to_logical_plan(
        "max_over_time((foo @ start())[30m:1m])", T)
    # find the nested ApplyAtTimestamp and check it pins to query start
    def find(p):
        if isinstance(p, lp.ApplyAtTimestamp):
            return p
        for f in p.__dataclass_fields__:
            v = getattr(p, f)
            if isinstance(v, lp.LogicalPlan):
                r = find(v)
                if r is not None:
                    return r
        return None
    at = find(plan)
    assert at is not None
    assert at.inner.start_ms == 1_600_000_600_000


def test_at_modifier_wrapped_aggregate_routes_by_pinned_time():
    """sum(foo @ t) — pin NOT at the plan root — must still route by the
    pinned data time (the pin detector walks the whole tree)."""
    from filodb_tpu.promql.parser import (TimeStepParams,
                                          query_range_to_logical_plan)
    from filodb_tpu.query.planners import LongTimeRangePlanner

    calls = []

    class _P:
        def __init__(self, name):
            self.name = name

        def materialize(self, plan, ctx):
            calls.append(self.name)
            return object()

    earliest_raw = 1_600_010_000_000
    planner = LongTimeRangePlanner(
        _P("raw"), _P("ds"), lambda: earliest_raw,
        lambda: earliest_raw + 3_600_000)
    T = TimeStepParams(1_600_020_000, 60, 1_600_023_000)
    old = query_range_to_logical_plan("sum(foo @ 1600000000)", T)
    planner.materialize(old, QueryContext())
    assert calls == ["ds"]


def test_at_modifier_pinned_data_range_includes_subquery_window():
    """pinned_data_range must account for a pinned subquery's full
    reach-back (window + lookback), not just the pinned instant."""
    plan = _plan("max_over_time(foo[2h:1m] @ 1600000000)")
    dr = lp.pinned_data_range(plan, 300_000)
    at = 1_600_000_000_000
    assert dr[1] == at
    assert dr[0] == at - 2 * 3600_000 - 300_000


def test_ha_planner_routes_pinned_failures_remote():
    """A local failure window covering the pinned @ time must send the
    whole query to the replica, even when the outer grid is healthy."""
    at_ms = 1_600_000_000_000
    fail = FailureTimeRange("local", TimeRange(at_ms - 600_000,
                                               at_ms + 600_000),
                            is_remote=False)
    local = _RecordingPlanner("local")
    T2 = TimeStepParams(START_S + 7200, 60, START_S + 10800)
    planner = HighAvailabilityPlanner("prometheus", local, _FP([fail]),
                                      "http://replica")
    out = planner.materialize(_plan("foo @ 1600000000", T2), QueryContext())
    assert isinstance(out, PromQlRemoteExec)
    assert not local.materialized
    # healthy pinned time -> local
    out2 = planner.materialize(
        _plan(f"foo @ {at_ms // 1000 + 7200}", T2), QueryContext())
    assert isinstance(out2, _Dummy) and out2.tag == "local"


def test_multi_partition_pinned_spanning_partitions_errors():
    """A pinned (@) read whose data range spans partitions must raise,
    not silently evaluate locally with partial data (round-2 review)."""
    local = _RecordingPlanner("local")
    start_ms = START_S * 1000
    mid = start_ms + 1800_000
    prov = _Provider([
        PartitionAssignment("remote-p", "http://p2/api",
                            TimeRange(0, mid - 1)),
        PartitionAssignment("local", "", TimeRange(mid, start_ms + 10**9)),
    ])
    mp = MultiPartitionPlanner(prov, "local", local)
    p = _plan(f'rate(foo[5m] @ {START_S + 600})')
    with pytest.raises(ValueError, match="pinned"):
        mp.materialize(p, QueryContext())


def test_multi_partition_pinned_single_remote_still_routes():
    """A pinned read wholly inside one remote partition routes there."""
    local = _RecordingPlanner("local")
    start_ms = START_S * 1000
    prov = _Provider([
        PartitionAssignment("remote-p", "http://p2/api",
                            TimeRange(0, start_ms + 10**9)),
    ])
    mp = MultiPartitionPlanner(prov, "local", local)
    p = _plan(f'rate(foo[5m] @ {START_S + 600})')
    out = mp.materialize(p, QueryContext())
    assert isinstance(out, PromQlRemoteExec)
