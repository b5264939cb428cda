"""A dashboard as Grafana opens it through the served path (ISSUE 44): the
six panels of `promperf6h-counters-82k.open` over the HTTP door against the
configuration's plain f64 reference (`benchmark/reference.py`), on seeded
data at 512 series x the configuration's own 2,304 samples over 4 shards,
the cell's own grid (six hours at a 30 s step, `[40s]`: 721 windows a
request), interpret-mode kernels; which route each leaf took, what a launch
booked, and that the configuration is its hour-long twin in all but the
length of a row and the width of a request.

Tolerance: the cell's limit, relative, on every cell of every response."""
import numpy as np
import pytest

import histrig
import ts128rig
from histrig import bench_json, bench_module

CONFIG, CELL = "promperf6h-counters-82k", "promperf6h-counters-82k.open"
TWIN = "promperf-counters-262k"
SEEDS = (4400001, 2_147_483_777)


class WideRig(ts128rig.Ts128Rig):
    """`ts128rig.Ts128Rig` holding this configuration at its own 2,304
    samples a series and the cell's own grid, two phases of it."""
    CONFIG, CELL = CONFIG, CELL
    SIZE = dict(series=512, samples=2304)
    TRAFFIC = dict(phases=2, warmup_opens=1)


@pytest.fixture(scope="module", autouse=True)
def interpret_kernels():
    with histrig.environ(FILODB_TPU_FUSED_INTERPRET="1"):
        yield


@pytest.fixture(scope="module", params=SEEDS)
def rig(request):
    r = WideRig(request.param)
    yield r
    r.close()
    from filodb_tpu.utils.events import journal
    journal.clear()         # no compile storm for a later file's verdict


def _limit():
    return bench_json("workloads", CELL)["checks"][0]["limit"]


def test_the_configuration_is_its_twin_in_all_but_rows_and_requests():
    cfg, twin = bench_json("configs", CONFIG), bench_json("configs", TWIN)
    mine = {"name", "source", "loader", "plain_reference", "series",
            "samples", "assumed", "guarantees", "on_device", "reduced"}
    assert {k for k in cfg if cfg[k] != twin.get(k)} == mine
    assert (cfg["series"], cfg["samples"], cfg["rehearse_series"]) \
        == (81_920, 2_304, 2_048)
    # to the sample what the 262,144 x 720 configurations hold
    assert cfg["series"] * cfg["samples"] == twin["series"] * twin["samples"]
    assert len(cfg["source"]) < 200 and cfg["reduced"].keys() == {"series"}
    assert "reference" not in cfg       # loaders/grid.py then takes reference.py
    wl, tw = (bench_json("workloads", c)["traffic"]
              for c in (CELL, TWIN + ".open"))
    other = {"range_s": 40, "span_s": 21_600, "step_s": 30, "phases": 15,
             "phase_stride_s": 2, "warmup_phase_s": 1}
    assert {k: v for k, v in wl.items() if tw.get(k) != v and k != "who"} \
        == other
    assert wl["panels"] == tw["panels"] and wl["in_flight"] == 6
    plan = bench_module("traffic", wl["kind"]).Plan(cfg, wl, 1)
    assert (plan.n_windows, plan.opens_per_phase, plan.capacity) \
        == (721, 46, 4_140)
    bench = bench_json("", "../BENCHMARK")
    entry = next(c for c in bench["configs"] if c["name"] == CONFIG)
    assert entry["reduced"] == ["series"] and entry["source"] == cfg["source"]
    assert [w["name"] for w in bench["workloads"] if w["config"] == CONFIG] \
        == [CELL]
    listed = {m["name"] for m in bench["per_layer"]
              if CELL in m.get("workloads", ())}
    assert {"fused_roofline", "fused_windows_per_launch",
            "gather_tile_visits_per_query", "fused_errors_in_window",
            "present_ms"} <= listed
    assert "mirror_gather_device_ms" not in listed


@pytest.mark.parametrize("panel", range(6))
def test_served_panels_match_the_f64_reference(rig, panel):
    req = rig.open(0)[panel]
    assert req["params"]["step"] == 30 and "[40s]" in req["params"]["query"]
    (err, why), body = rig.ask(req)
    assert why is None, why
    assert err <= _limit(), (req["params"]["query"], err)
    result = body["data"]["result"]
    assert len(result) == (10, 1, 2, 10, 1, 10)[panel]
    assert all(len(r["values"]) == 721 for r in result)
    assert body["stats"]["cache"]["result"] == "miss"


def test_every_leaf_of_a_wide_request_is_one_fused_dispatch(rig):
    import time
    rig.forget_results()
    time.sleep(0.3)
    before = rig.samples()
    for req in rig.open(1):
        (err, why), _ = rig.ask(req)
        assert why is None and err <= _limit()
    time.sleep(0.3)
    after = rig.samples()
    moved = lambda name: after.get(name, 0.0) - before.get(name, 0.0)  # noqa: E731
    assert moved("leaf_fused_kernel_total") == 6 * 4
    assert moved("fused_enqueues_total") == 6
    assert moved("fused_windows_total") == 6 * 721
    # four sets, two gathers, 22 of a gather's 108 (window, row) tile pairs
    assert moved("fused_gather_tile_visits_total") == 6 * 4 * 2 * 22
    for name in ("leaf_fused_errors_total", "leaf_general_path_total",
                 "leaf_inexact_times_total", "leaf_host_gather_total",
                 "leaf_phase_fused_total", "leaf_ragged_fused_total"):
        assert moved(name) == 0, name
    assert moved("span_leaf_build_plan_calls_total") <= 1


def test_a_program_that_does_not_fuse_the_wide_leaf_is_turned_away(
        monkeypatch):
    """The loader's question (`loaders/grid_wide.py`): a program whose
    kernel does not take 2,304 samples by 721 windows ends the run before
    anything is generated."""
    from filodb_tpu.ops import pallas_fused as pf
    loader = bench_module("loaders", "grid_wide")
    cfg = dict(bench_json("configs", CONFIG), series=64)
    wl = bench_json("workloads", CELL)["traffic"]
    plan = bench_module("traffic", wl["kind"]).Plan(cfg, wl, 3)
    loader.require_fused_wide_leaf(cfg, plan)      # this program: fused

    def refuses(*a, **k):
        raise RuntimeError("Mosaic failed to compile TPU kernel: "
                           "Invalid input layout")
    monkeypatch.setattr(pf, "_run", refuses)
    with pytest.raises(RuntimeError, match="is not fused by this program"):
        loader.load(None, cfg, plan, 3, None, {}, bench_module)
    monkeypatch.undo()
    monkeypatch.setattr(pf, "kernel_mode", lambda: None)
    with pytest.raises(RuntimeError, match="may not run the fused kernel"):
        loader.load(None, cfg, plan, 3, None, {}, bench_module)


def test_same_seed_same_requests(rig):
    again = ts128rig.small_plan(rig.cfg, rig.seed, CELL, **WideRig.TRAFFIC)
    assert again.requests() == rig.plan.requests()
    assert np.array_equal(again.window_ends_s(), rig.plan.window_ends_s())
