"""TSBS devops `double-groupby` through the served path (ISSUE 48): the
requests of `tsbscpu-gauges-40k.double-groupby` over the HTTP door against
the configuration's plain f64 reference (`benchmark/references/tsbs_cpu.py`),
on seeded data at 32 hosts x 3 metrics x the configuration's own 4,736
samples over 4 shards, the cell's own grid (twelve hours at a one-hour step,
`[1h]`: 13 windows a request, a group a host), interpret-mode kernels; which
route each leaf took, what a launch booked, and what the configuration's
files say.

Tolerance: the cell's limit, relative, on every cell of every response."""
import numpy as np
import pytest

import histrig
import ts128rig
from histrig import bench_json, bench_module

CONFIG, CELL = "tsbscpu-gauges-40k", "tsbscpu-gauges-40k.double-groupby"
METRICS = ["cpu_usage_user", "cpu_usage_system", "cpu_usage_idle"]
HOSTS = 32
SEEDS = (4800001, 2_147_483_777)


def _panels():
    return [p for p in bench_json("workloads", CELL)["traffic"]["panels"]
            if p["metric"] in METRICS]


class TsbsRig(ts128rig.Ts128Rig):
    """`ts128rig.Ts128Rig` holding this configuration at its own 4,736
    samples a series, 32 hosts of three metrics, two phases of the cell's
    own grid."""
    CONFIG, CELL = CONFIG, CELL
    SIZE = dict(series=HOSTS * len(METRICS), samples=4_736, metrics=METRICS)
    TRAFFIC = dict(phases=2, warmup_opens=1, panels=_panels())


@pytest.fixture(scope="module", autouse=True)
def interpret_kernels():
    with histrig.environ(FILODB_TPU_FUSED_INTERPRET="1"):
        yield


@pytest.fixture(scope="module", params=SEEDS)
def rig(request):
    r = TsbsRig(request.param)
    yield r
    r.close()
    from filodb_tpu.utils.events import journal
    journal.clear()         # no compile storm for a later file's verdict


def _limit():
    return bench_json("workloads", CELL)["checks"][0]["limit"]


def test_the_configuration_states_its_source_cuts_and_guarantees():
    cfg = bench_json("configs", CONFIG)
    assert (cfg["series"], cfg["samples"], cfg["shards"], cfg["chips"]) \
        == (40_000, 4_736, 4, 1)
    assert len(cfg["metrics"]) == 10 and cfg["schema"] == "gauge"
    assert cfg["series"] * cfg["samples"] == 189_440_000
    assert len(cfg["source"]) <= 200 and "tsbs" in cfg["source"]
    assert cfg["reduced"].keys() == {"samples"}
    assert len(cfg["assumed"]) >= 6 and cfg["kept"]
    assert {"answers", "checked"} <= cfg["guarantees"].keys()
    assert cfg["on_device"]
    assert set(cfg["labels"]) == {
        "_ws_", "_ns_", "hostname", "region", "datacenter", "rack", "os",
        "arch", "team", "service", "service_version", "service_environment"}
    tp = bench_json("workloads", CELL)["traffic"]
    assert (tp["kind"], tp["in_flight"], tp["span_s"], tp["step_s"],
            tp["range_s"], tp["warmup_opens"]) \
        == ("double_groupby", 6, 43_200, 3_600, 3_600, 3)
    assert [p["metric"] for p in tp["panels"]] == cfg["metrics"][:5]
    assert all((p["fn"], p["agg"], p["by"]) == ("avg_over_time", "avg",
                                                ["hostname"])
               for p in tp["panels"])
    plan = bench_module("traffic", tp["kind"]).Plan(cfg, tp, 1)
    assert (plan.n_windows, plan.opens_per_phase, plan.hosts) \
        == (13, 1, 4_000)
    assert plan.capacity == 5 * tp["phases"]
    assert max(plan.phases + plan.warm_phases) <= 549
    bench = bench_json("", "../BENCHMARK")
    entry = next(c for c in bench["configs"] if c["name"] == CONFIG)
    assert entry["reduced"] == ["samples"] and entry["source"] == cfg["source"]
    cells = [w for w in bench["workloads"] if w["config"] == CONFIG]
    assert [(w["name"], w["chips"]) for w in cells] == [(CELL, 1)]
    listed = {m["name"] for m in bench["per_layer"]
              if CELL in m.get("workloads", ())}
    assert {"fused_groups_per_leaf", "present_points_per_query",
            "band_tiles_per_query", "longrow_band_roofline",
            "general_path_leaves", "offmirror_leaves", "offroute_leaves",
            "fused_errors_in_window", "compiles_in_window",
            "cache_miss_share", "fused_dispatches_per_query",
            "fused_windows_per_launch", "working_set_misses_per_query",
            "fused_roofline", "present_ms"} <= listed
    assert not {"mirror_gather_device_ms",
                "gather_tile_visits_per_query"} & listed


@pytest.mark.parametrize("panel", range(len(METRICS)))
def test_served_double_groupby_matches_the_f64_reference(rig, panel):
    req = rig.plan.requests()[panel]
    q = req["params"]
    assert q["query"] == ("avg by (hostname)(avg_over_time("
                          f"{METRICS[panel]}[1h]))")
    assert (q["step"], q["end"] - q["start"]) == (3_600, 43_200)
    (err, why), body = rig.ask(req)
    assert why is None, why
    assert err <= _limit(), (q["query"], err)
    result = body["data"]["result"]
    assert len(result) == HOSTS
    assert all(len(r["values"]) == 13 for r in result)
    assert {r["metric"]["hostname"] for r in result} \
        == {f"host_{i}" for i in range(HOSTS)}
    assert body["stats"]["cache"]["result"] == "miss"


def test_every_leaf_of_a_double_groupby_is_one_tiled_fused_dispatch(rig):
    import time
    rig.forget_results()
    time.sleep(0.3)
    before = rig.samples()
    reqs = rig.plan.requests()[len(METRICS):]
    for req in reqs:
        (err, why), _ = rig.ask(req)
        assert why is None and err <= _limit()
    time.sleep(0.3)
    after = rig.samples()
    moved = lambda name: after.get(name, 0.0) - before.get(name, 0.0)  # noqa: E731
    n = len(reqs)
    assert moved("leaf_fused_kernel_total") == n * rig.populated
    # a group a host: the leaves' groups are the hosts, a request
    assert moved("leaf_fused_groups_total") == n * HOSTS
    assert moved("fused_enqueues_total") == n
    assert moved("fused_windows_total") == n * 13
    # every working set's band in ten tiles of 512 columns (4,736)
    assert moved("fused_band_tiles_total") == n * rig.populated * 10
    assert moved("span_leaf_band_tiled_calls_total") == n
    assert moved("http_present_points_total") == n * HOSTS * 13
    assert moved("fused_columns_read_total") == n * 4_736
    for name in ("leaf_fused_errors_total", "leaf_general_path_total",
                 "leaf_inexact_times_total", "leaf_host_gather_total",
                 "leaf_phase_fused_total", "leaf_ragged_fused_total",
                 "http_present_point_fallbacks_total"):
        assert moved(name) == 0, name
    # the working sets of a (shard, metric) are held after its first request
    assert moved("span_leaf_pad_values_calls_total") == 0


def test_the_requests_of_a_run_share_no_cache_entry_and_seeds_reorder():
    cfg = bench_json("configs", CONFIG)
    tp = bench_json("workloads", CELL)["traffic"]
    Plan = bench_module("traffic", tp["kind"]).Plan
    a, b, c = Plan(cfg, tp, 5), Plan(cfg, tp, 5), Plan(cfg, tp, 6)
    key = lambda r: (r["params"]["query"], r["params"]["start"],  # noqa: E731
                     r["params"]["end"])
    assert [key(r) for r in a.requests()] == [key(r) for r in b.requests()]
    assert [key(r) for r in a.requests()] != [key(r) for r in c.requests()]
    assert sorted(map(key, a.requests())) == sorted(map(key, c.requests()))
    # the result cache's rule: (promql, step, start mod step)
    every = a.requests() + a.warmup()
    entries = {(r["params"]["query"], r["params"]["start"] % tp["step_s"])
               for r in every}
    assert len(entries) == len(every) == a.capacity + 5 * tp["warmup_opens"]
    ends = a.window_ends_s()
    assert len(ends) == len(set(ends.tolist())) \
        == 13 * (tp["phases"] + tp["warmup_opens"])
    with pytest.raises(ValueError, match="does not fit"):
        Plan(cfg, dict(tp, phases=560), 1)


def test_the_reference_on_a_case_worked_by_hand():
    ref_mod = bench_module("references", "tsbs_cpu")
    ts = np.arange(8, dtype=np.int64) * 10_000
    wends = np.array([35_000, 70_000, 5_000_000])
    panels = [{"metric": "m", "fn": "avg_over_time", "agg": "avg",
               "by": ["hostname"]}]
    ref = ref_mod.Reference(ts, wends, 30_000, panels, 2)
    assert ref.asks("m") and not ref.asks("other")
    vals = np.array([[1., 2, 3, 4, 5, 6, 7, 8],
                     [10., 10, 10, 40, 10, 10, 10, 70]])
    ref.add("m", vals, np.array([0, 1]))
    table = ref.table(panels[0], np.array([0, 1]))
    # (5 s, 35 s] holds samples 1, 2, 3; (40 s, 70 s] holds 5, 6, 7
    np.testing.assert_array_equal(table[:, :2], [[3.0, 7.0], [20.0, 30.0]])
    assert np.isnan(table[:, 2]).all()
    np.testing.assert_array_equal(
        ref.table(panels[0], np.array([-1, 0]))[:, :2], [[20.0, 30.0]])


def test_a_program_that_holds_the_band_whole_is_turned_away(monkeypatch):
    """The loader's question (`loaders/tsbs_cpu.py`): a program whose
    kernel does not take 4,736 samples by 13 windows at 1,024 groups ends
    the run before anything is generated, as the parent commit does on a
    chip (`_run_set` refuses by name where no block fits)."""
    from filodb_tpu.ops import pallas_fused as pf
    loader = bench_module("loaders", "tsbs_cpu")
    cfg = dict(bench_json("configs", CONFIG), series=80)
    wl = bench_json("workloads", CELL)["traffic"]
    plan = bench_module("traffic", wl["kind"]).Plan(cfg, wl, 3)
    loader.require_fused_long_leaf(cfg, plan)      # this program: fused

    def refuses(*a, **k):
        raise ValueError("fused kernel shape exceeds VMEM budget at every "
                         "block size")
    monkeypatch.setattr(pf, "_run", refuses)
    with pytest.raises(RuntimeError, match="is not fused by this program"):
        loader.load(None, cfg, plan, 3, None, {}, bench_module)
    monkeypatch.undo()
    monkeypatch.setattr(pf, "kernel_mode", lambda: None)
    with pytest.raises(RuntimeError, match="may not run the fused kernel"):
        loader.require_fused_long_leaf(cfg, plan)


def test_the_generator_is_a_clamped_walk_from_the_seed():
    gen = bench_module("generators", "clamped_walk")
    a = gen.chunk(np.random.default_rng([7, 0]), np.empty((50, 400)))
    b = gen.chunk(np.random.default_rng([7, 0]), np.empty((50, 400)))
    c = gen.chunk(np.random.default_rng([7, 1]), np.empty((50, 400)))
    np.testing.assert_array_equal(a, b)
    assert not np.array_equal(a, c)
    assert a.min() >= 0.0 and a.max() <= 100.0
    steps = np.diff(a, axis=1)
    inside = (a[:, 1:] > 0) & (a[:, 1:] < 100) & (a[:, :-1] > 0) \
        & (a[:, :-1] < 100)
    assert abs(steps[inside].std() - 1.0) < 0.05
    assert a[:, 0].std() > 20          # starts spread over the range
