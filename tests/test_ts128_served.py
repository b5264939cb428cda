"""A chip's 32-shard share of upstream's 128-shard layout through the served
path (ISSUE 35): the six panels of `ts128-counters-262k-32sh.open` over the
HTTP door against the benchmark's plain f64 reference (`benchmark/reference.py`),
on seeded data at 2,048 series x 240 samples over 32 shards routed as the
gateway routes (2 shards stay empty), interpret-mode kernels; which route
each leaf took; and the test that ties the share to the deployment: one
128-shard dataset, its four 32-shard shares reduced apart and then together,
against the 128-shard answer and the f64 oracle.

Tolerance 2e-5 (the cell's limit), relative, on every cell of every response;
readings here 3e-7 to 5e-7."""
import time

import numpy as np
import pytest

import histrig
import ts128rig

TOL = 2e-5
SEEDS = (3500001, 2_147_483_693)
PANELS = range(6)


@pytest.fixture(scope="module", autouse=True)
def interpret_kernels():
    with histrig.environ(FILODB_TPU_FUSED_INTERPRET="1"):
        yield


@pytest.fixture(scope="module", params=SEEDS)
def rig(request):
    r = ts128rig.Ts128Rig(request.param)
    yield r
    r.close()


def test_the_gateways_routing_leaves_two_of_the_32_shards_empty(rig):
    assert len(rig.per_shard) == 32 and sum(rig.per_shard) == ts128rig.SERIES
    assert [s for s, n in enumerate(rig.per_shard) if n == 0] == [26, 27]
    # each of the 40 namespaces on one even/odd pair of shards: the skew
    assert max(rig.per_shard) > 4 * min(n for n in rig.per_shard if n)


@pytest.mark.parametrize("panel", PANELS)
def test_served_panels_match_the_f64_reference(rig, panel):
    req = rig.open(0)[panel]
    (err, why), body = rig.ask(req)
    assert why is None, why
    assert err <= TOL, (req["params"]["query"], err)
    assert len(body["data"]["result"]) == (40, 1, 2, 40, 1, 40)[panel]
    assert body["stats"]["cache"]["result"] == "miss"


def test_every_populated_leaf_is_a_fused_dispatch_and_an_empty_one_is_none(
        rig):
    from filodb_tpu.query.execbase import (_FUSED_CACHE_LOCK,
                                           _FUSED_PLAN_CACHE)
    rig.forget_results()
    for req in rig.open(0):     # every working set and grouping, once
        assert rig.ask(req)[0][1] is None
    with _FUSED_CACHE_LOCK:
        # a plan is no shard's and no server's: the other seed's rig has
        # built this grid's already
        _FUSED_PLAN_CACHE.clear()
    # a request's spans are booked when its handler thread leaves the
    # outermost one, after the client has its body: let the last test's go
    time.sleep(0.3)
    before = rig.samples()
    for req in rig.open(1):
        (err, why), _ = rig.ask(req)
        assert why is None and err <= TOL
    time.sleep(0.3)
    after = rig.samples()

    def delta(name):
        return after.get(name, 0.0) - before.get(name, 0.0)
    assert delta("leaf_fused_kernel_total") == 6 * rig.populated == 180
    assert delta("leaf_empty_total") == 6 * 2
    # a request's 30 working sets ride ONE device program (ISSUE 36)
    assert delta("fused_enqueues_total") == 6
    assert delta("fused_enqueue_sets_total") == 6 * rig.populated
    assert delta("span_leaf_kernel_enqueue_calls_total") == 6
    assert delta("span_leaf_result_fetch_calls_total") == 6
    for fam in ("leaf_host_routed_total", "leaf_host_gather_total",
                "leaf_general_path_total", "leaf_fused_errors_total"):
        assert delta(fam) == 0, fam
    # an open's 180 leaves share ONE plan build; the working sets were
    # padded by open 0 and every grouping is open 0's
    assert delta('fused_cache_lookups_total{cache="plan",result="miss"}') == 1
    assert delta('span_leaf_build_plan_calls_total') == 1
    for cache in ("values", "groups"):
        assert delta('fused_cache_lookups_total{cache="%s",result="miss"}'
                     % cache) == 0, cache
        assert delta('fused_cache_lookups_total{cache="%s",result="hit"}'
                     % cache) == 180
    assert delta("span_leaf_pad_values_calls_total") == 0
    assert delta("span_leaf_group_ids_calls_total") == 0
    assert after['fused_cache_entries{cache="values"}'] >= rig.populated
    assert after['fused_cache_bytes{cache="values"}'] > 0


def test_on_an_attached_chip_no_mirrored_leaf_is_routed_to_the_host(
        rig, monkeypatch):
    """Every leaf of the small rig lies under `host_route_max_samples` (at
    the cell's size four do: 3,249 to 3,305 series x 391 samples): the rule
    keeps a leaf that may read the mirror on the device, and sends the same
    leaf to the host once the mirror is off."""
    from filodb_tpu.config import settings
    from filodb_tpu.query import leafexec
    assert leafexec.leaf_route(1_270_359, 1, 2_000_000) == "host"
    assert leafexec.leaf_route(1_270_359, 1, 2_000_000,
                               mirrored=True) == "device"
    assert settings().query.host_route_max_samples == 2_000_000
    monkeypatch.setattr(leafexec, "_attached_chip", lambda: True)

    def routed(req):
        before = rig.samples()
        (err, why), _ = rig.ask(req)
        assert why is None and err <= TOL
        after = rig.samples()
        return tuple(after.get(f, 0.0) - before.get(f, 0.0) for f in (
            "leaf_fused_kernel_total", "leaf_host_routed_total",
            "leaf_host_gather_total"))
    assert routed(rig.open(2)[0]) == (rig.populated, 0, 0)
    store_cfg = rig.srv.memstore.shards_for(
        rig.cfg["dataset"])[0].config.store
    monkeypatch.setattr(store_cfg, "device_mirror_enabled", False)
    assert routed(rig.open(2)[1]) == (0, rig.populated, rig.populated)


def _size_alone(est_samples, values_per_sample, cap):
    """The router of the program before PR 35: by size, mirrored or not."""
    values = est_samples * max(values_per_sample, 1)
    return "host" if cap > 0 and 0 < values <= cap else "device"


@pytest.mark.parametrize("router, refusal", [
    (None, None), (_size_alone, "says 'host'"),
    (lambda est, per_sample, cap, mirrored=False: "host", "says 'host'")],
    ids=["this-program", "by-size-alone", "always-host"])
def test_the_loader_asks_where_the_smallest_leaf_goes_before_it_loads(
        router, refusal, monkeypatch):
    """`loaders/grid_on_mirror.py`: the configuration's smallest populated
    leaf at its own size (3,249 series x 391 samples, under the cap) must be
    answered from the mirror, or the run ends before anything is made."""
    from filodb_tpu.query import leafexec
    loader = histrig.bench_module("loaders", "grid_on_mirror")
    cfg = histrig.bench_json("configs", ts128rig.CONFIG)
    tp = histrig.bench_json("workloads", ts128rig.CELL)["traffic"]
    plan = histrig.bench_module("traffic", tp["kind"]).Plan(cfg, tp, 7)
    assert cfg["loader"] == "grid_on_mirror"
    assert loader.leaf_samples(cfg, plan) == 3249 * 391 == 1_270_359
    if router is not None:
        monkeypatch.setattr(leafexec, "leaf_route", router)
    if refusal is None:
        loader.require_device_route(cfg, plan)
    else:
        with pytest.raises(RuntimeError, match=refusal):
            loader.load(None, cfg, plan, 7, None, {}, histrig.bench_module)


# ---- the share and the deployment

DEPLOYMENT_SHARDS, CHIPS = 128, 4
QUERIES = ("sum by (_ns_)(rate(request_total[5m]))",
           "sum(increase(request_total[5m]))",
           "sum by (_ns_, dc)(rate(request_total[5m]))")


@pytest.fixture(scope="module")
def deployment():
    """One dataset of 128 shards holding 2,048 counters routed as the
    gateway routes them, the f64 reference over the same samples, and an
    engine over all of it."""
    from filodb_tpu.core.memstore import TimeSeriesMemStore
    from filodb_tpu.core.partkey import PartKey
    from filodb_tpu.parallel.shardmapper import (ShardEvent, ShardMapper,
                                                 SpreadProvider)
    from filodb_tpu.query.engine import QueryEngine
    cfg = ts128rig.small_config(shards=DEPLOYMENT_SHARDS)
    plan = ts128rig.small_plan(cfg, 35)
    S, T = cfg["series"], cfg["samples"]
    ms = TimeSeriesMemStore()
    mapper = ShardMapper(DEPLOYMENT_SHARDS)
    spread = SpreadProvider(default_spread=1)
    shards = []
    for n in range(DEPLOYMENT_SHARDS):
        shards.append(ms.setup(cfg["dataset"], n))
        mapper.update_from_event(
            ShardEvent("IngestionStarted", cfg["dataset"], n, "x"))
    grid = histrig.bench_module("loaders", "grid")
    keys = [PartKey.make(cfg["metric"], {
        lab: grid.label_value(spec, i) for lab, spec in cfg["labels"].items()})
        for i in range(S)]
    shard_of = np.array([mapper.ingestion_shard(
        pk.shard_key_hash(), pk.partition_hash(),
        spread.spread_for(pk.shard_key())) for pk in keys])
    ts_row = cfg["start_ms"] + np.arange(T, dtype=np.int64) * cfg["scrape_ms"]
    vals = histrig.bench_module("generators", cfg["generator"]).chunk(
        np.random.default_rng(35), np.empty((S, T)))
    ref = histrig.bench_module("", "reference").Reference(
        ts_row, plan.window_ends_s() * 1000, plan.range_s * 1000, plan.panels,
        plan.num_base())
    ref.add(vals, np.arange(S) % plan.num_base())
    for sh in shards:
        idx = np.flatnonzero(shard_of == sh.shard_num)
        if idx.size:
            sh.ingest_columns(cfg["schema"], [keys[i] for i in idx],
                              np.broadcast_to(ts_row, (idx.size, T)),
                              {cfg["column"]: vals[idx]}, offset=0)
    engine = QueryEngine(cfg["dataset"], ms, mapper, spread)
    return engine, plan, ref, np.bincount(shard_of,
                                          minlength=DEPLOYMENT_SHARDS)


def _rows(block):
    return {tuple(sorted(k.labels_dict.items())): np.asarray(v, np.float64)
            for k, v in zip(block.keys, np.asarray(block.values))}


@pytest.mark.parametrize("promql", QUERIES)
def test_four_shares_merged_equal_the_deployment_and_the_oracle(
        deployment, promql):
    from filodb_tpu.promql.parser import (TimeStepParams,
                                          query_range_to_logical_plan)
    from filodb_tpu.query.execbase import present_partial, reduce_partials
    from filodb_tpu.query.rangevector import QueryContext
    engine, plan, ref, per_shard = deployment
    assert (per_shard > 0).sum() > 32      # more than one chip's worth
    panel = next(j for j, p in enumerate(plan.panels)
                 if promql == plan.queries[None][j])
    end = plan.newest_s
    args = (end - plan.span_s, plan.step_s, end)
    whole = engine.query_range(promql, *args)
    assert whole.error is None, whole.error
    want = _rows(whole.blocks[0])

    # the same tree, its 128 leaves run apart and reduced share by share
    ep = engine.planner.materialize(
        query_range_to_logical_plan(promql, TimeStepParams(*args)),
        QueryContext())
    leaves = {}

    def walk(node):
        if hasattr(node, "shard") and not getattr(node, "children", None):
            leaves[node.shard] = node
        for c in getattr(node, "children", None) or ():
            walk(c)
    walk(ep)
    assert sorted(leaves) == list(range(DEPLOYMENT_SHARDS))
    per_chip = DEPLOYMENT_SHARDS // CHIPS
    shares = []
    for chip in range(CHIPS):
        parts = [leaves[s].execute_internal(engine.source)[0]
                 for s in range(chip * per_chip, (chip + 1) * per_chip)]
        assert any(p is not None for p in parts)
        shares.append(reduce_partials(parts))
    got = _rows(present_partial(reduce_partials(shares)))
    assert set(got) == set(want)
    for key in want:
        np.testing.assert_allclose(got[key], want[key], rtol=1e-12,
                                   equal_nan=True)
    # and the f64 oracle, by the benchmark's own table
    fold, groups = plan.fold(plan.panels[panel])
    table = ref.table(plan.panels[panel], fold)
    col = {int(t): i for i, t in enumerate(plan.window_ends_s())}
    cols = [col[t] for t in range(args[0], args[2] + 1, args[1])]
    by = plan.panels[panel]["by"]
    assert len(groups) == len(want)
    for g, row in zip(groups, table):
        key = tuple(sorted(zip(by, g)))
        np.testing.assert_allclose(got[key], row[cols], rtol=TOL)
