"""The leaf router counts VALUES (ISSUE 31): `query/leafexec.leaf_route`
decides host or device from (estimated samples, values per sample, the cap);
a scalar leaf is decided bit for bit as the rule before it decided, a
histogram leaf is weighed by its store's bucket count.  Served, with
`FILODB_TPU_FORCE_HOST_ROUTE=1` (the rule runs on a TPU backend only) and
the cap set between a small histogram leaf's samples and its values, the
three counters say which way each leaf went."""
import contextlib
import json

import numpy as np
import pytest

import histrig
from filodb_tpu.query.leafexec import leaf_route

CAP = 2_000_000


@pytest.mark.parametrize("est", [0, 1, 4_480, CAP - 1, CAP, CAP + 1,
                                 25_600_000, 10 ** 12])
@pytest.mark.parametrize("cap", [CAP, 1, 0, -1])
def test_scalar_decisions_are_what_the_rule_gave(est, cap):
    before = cap > 0 and 0 < est <= cap     # leafexec.py at PR 30
    assert (leaf_route(est, 1, cap) == "host") == before
    assert leaf_route(est, 0, cap) == leaf_route(est, 1, cap)


@pytest.mark.parametrize("est,buckets,want", [
    (400_000, 64, "device"),        # one shard of histdev-64b-4k: 25.6 M values
    (31_250, 64, "host"),           # 2,000,000 values: at the cap
    (31_251, 64, "device"),
    (400_000, 4, "host"),           # 1.6 M values
    (0, 64, "device"),              # nothing to scan: the rule stays out
])
def test_a_histogram_sample_is_one_value_a_bucket(est, buckets, want):
    assert leaf_route(est, buckets, CAP) == want


@pytest.fixture(scope="module")
def rig():
    """The small histogram deployment, with 128 scalar counters beside it,
    the route rule forced on and the cap at 50,000: a shard's histogram leaf
    over 25 minutes is about 4,800 samples (under) and 310,000 values (over);
    its counter leaf is 4,800 values (under)."""
    from filodb_tpu.config import settings
    from filodb_tpu.core.partkey import PartKey
    stack = contextlib.ExitStack()
    stack.enter_context(histrig.environ(FILODB_TPU_FUSED_INTERPRET="1",
                                        FILODB_TPU_FORCE_HOST_ROUTE="1"))
    old_cap = settings().query.host_route_max_samples
    settings().query.host_route_max_samples = 50_000
    stack.callback(setattr, settings().query, "host_route_max_samples",
                   old_cap)
    with stack:
        r = histrig.HistRig(31)
        stack.callback(r.close)
        cfg = r.cfg
        ts = cfg["start_ms"] + np.arange(cfg["samples"], dtype=np.int64) * \
            cfg["scrape_ms"]
        keys = [PartKey.make("request_total", {
            "_ws_": "demo", "_ns_": f"App-{i % 10}",
            "instance": f"Instance-{i}"}) for i in range(histrig.SERIES)]
        vals = np.cumsum(np.random.default_rng(31).random(
            (histrig.SERIES, cfg["samples"])), axis=1)
        sh = r.srv.memstore.shards_for(cfg["dataset"])[0]
        sh.ingest_columns("prom-counter", keys,
                          np.broadcast_to(ts, vals.shape), {"count": vals},
                          offset=0)
        yield r


FAMILIES = ("leaf_hist_fused_total", "leaf_host_gather_total",
            "leaf_general_path_total", "leaf_host_routed_total")


def deltas(rig, promql):
    before = rig.counters()
    end = rig.plan.newest_s
    body = json.loads(rig.get("/api/v1/query_range", {
        "query": promql, "start": end - 1200, "end": end, "step": 60}))
    assert body["status"] == "success", body
    assert body["data"]["result"], "the query selected nothing"
    after = rig.counters()
    return tuple(after.get(f, 0.0) - before.get(f, 0.0) for f in FAMILIES)


def test_a_histogram_leaf_under_the_cap_in_samples_stays_on_the_mirror(rig):
    assert deltas(rig, "histogram_quantile(0.9, sum(rate(http_latency[5m])))"
                  ) == (histrig.SHARDS, 0, 0, 0)


def test_a_scalar_leaf_under_the_cap_gathers_on_the_host(rig):
    hist_fused, host_gather, general, host_routed = deltas(
        rig, "sum(rate(request_total[5m]))")
    assert (hist_fused, general) == (0, 0)
    assert host_gather == host_routed == 1      # the one shard that holds it


def test_an_unfusable_query_runs_the_general_path(rig):
    # a bare selector has no aggregate for the fused preflight to take
    hist_fused, host_gather, general, _ = deltas(
        rig, 'request_total{_ns_="App-3"}')
    assert (hist_fused, general) == (0, 1)
    assert host_gather == 1


def test_the_benchmarks_loader_refuses_a_program_that_routes_by_samples(
        monkeypatch):
    """`benchmark/loaders/hist_grid.py` asks `leaf_route` about a leaf of the
    deployment's own size before it generates anything: the parent commit
    (no such function, or the rule before it) ends the run there."""
    import filodb_tpu.query.leafexec as leafexec
    from filodb_tpu.config import settings
    monkeypatch.setattr(settings().query, "host_route_max_samples", CAP)
    loader = histrig.bench_module("loaders", "hist_grid")
    cfg = histrig.bench_json("configs", histrig.CONFIG)
    tp = histrig.bench_json("workloads", histrig.CELL)["traffic"]
    plan = histrig.bench_module("traffic", tp["kind"]).Plan(cfg, tp, 1)
    assert loader.leaf_samples(cfg, plan) == 1024 * 391
    loader.require_device_route(cfg, plan)
    # a rehearsal cuts `series`, not the question
    loader.require_device_route(dict(cfg, series=64), plan)
    monkeypatch.setattr(leafexec, "leaf_route",
                        lambda est, per, cap: "host" if 0 < est <= cap
                        else "device")          # the rule before PR 31
    with pytest.raises(RuntimeError, match="says 'host'"):
        loader.require_device_route(cfg, plan)
    monkeypatch.delattr(leafexec, "leaf_route")
    with pytest.raises(RuntimeError, match="no query/leafexec.leaf_route"):
        loader.require_device_route(cfg, plan)
