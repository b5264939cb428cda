"""`benchmark/traffic/quantile_open.py` and the cell that uses it: the same
seed sends the same requests, another seed the same set in another order, no
two opens of a run share a result-cache entry (the cache's rule replayed, as
`benchmark/test_selfcheck.py` does for `dashboard_open`), every window asked
for is full and in the tables, and `dashboard_open` is used, not copied."""
import json

import pytest

import histrig


def plan(seed, **over):
    cfg = histrig.bench_json("configs", histrig.CONFIG)
    tp = dict(histrig.bench_json("workloads", histrig.CELL)["traffic"],
              **over)
    return histrig.bench_module("traffic", tp["kind"]).Plan(cfg, tp, seed)


def test_same_seed_same_requests_another_seed_another_order():
    a, b, c = plan(2_147_483_659), plan(2_147_483_659), plan(12)
    assert a.requests() == b.requests() and a.warmup() == b.warmup()
    key = lambda r: json.dumps(r["params"], sort_keys=True)  # noqa: E731
    assert [key(r) for r in a.requests()] != [key(r) for r in c.requests()]
    assert sorted(map(key, a.requests())) == sorted(map(key, c.requests()))


def test_no_two_opens_share_a_cache_entry():
    """Key (promql, step, start mod step); a request is answered from an
    entry unless it reaches back before the entry's start."""
    p = plan(3)
    entries = {}
    reqs = p.warmup() + p.requests()
    assert len(reqs) == len({r["id"] for r in reqs})
    for r in reqs:
        q = r["params"]
        key = (q["query"], q["step"], q["start"] % q["step"])
        assert key not in entries or q["start"] < entries[key], r["id"]
        entries[key] = q["start"]
    assert p.capacity == len(p.requests()) >= 3000
    ends = set(p.window_ends_s().tolist())
    for r in reqs:
        q = r["params"]
        assert set(range(q["start"], q["end"] + 1, q["step"])) <= ends
        assert (q["start"] - p.range_s) * 1000 >= p.cfg["start_ms"] - \
            p.cfg["scrape_ms"]


def test_the_six_panels_are_the_issues():
    p = plan(1)
    assert p.queries[None] == [
        "histogram_quantile(0.9, sum(rate(http_latency[5m])))",
        "histogram_quantile(0.5, sum(rate(http_latency[5m])))",
        "histogram_quantile(0.99, sum(rate(http_latency[5m])))",
        "histogram_quantile(0.9, sum by (_ns_)(rate(http_latency[5m])))",
        "histogram_quantile(0.99, sum by (_ns_)(rate(http_latency[5m])))",
        "histogram_quantile(0.9, sum by (dc)(rate(http_latency[5m])))"]
    assert len(set(p.queries[None])) == 6       # six cache keys an open
    assert [len(g) for _, _, g in p.tables()] == [1, 1, 1, 10, 10, 2]
    assert p.num_base() == 10 and p.selected_series() == 4096
    first = p.requests()[:6]
    assert len({(r["params"]["start"], r["params"]["end"]) for r in first}) == 1
    assert all("table" not in r for r in first)


def test_it_is_dashboard_opens_plan_with_the_promql_wrapped():
    mod = histrig.bench_module("traffic", "quantile_open")
    dash = histrig.bench_module("traffic", "dashboard_open")
    assert mod.Plan.__mro__[1].__name__ == dash.Plan.__name__
    assert set(vars(mod.Plan)) - {"__module__", "__doc__", "__qualname__",
                                  "__firstlineno__", "__static_attributes__"} \
        == {"__init__"}
    q, d = plan(5), dash.Plan(
        histrig.bench_json("configs", histrig.CONFIG),
        histrig.bench_json("workloads", histrig.CELL)["traffic"], 5)
    strip = lambda r: {k: v for k, v in r["params"].items()  # noqa: E731
                       if k != "query"}
    assert [strip(r) for r in q.requests()] == [strip(r) for r in d.requests()]


def test_a_traffic_that_does_not_fit_is_refused():
    with pytest.raises(ValueError):
        plan(1, phases=24)          # 24 phases 5 s apart reach past one step
    assert plan(1, phases=24, phase_stride_s=2).capacity > 7000
