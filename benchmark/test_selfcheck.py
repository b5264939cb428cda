"""The benchmark's self-check.  Run by hand and in the rehearsal, not part of
the repo's tier-1 tests:

    python3 -m pytest benchmark/test_selfcheck.py -q -p no:cacheprovider

It holds the yardstick to hand counts (trace reduction, bytes function), the
traffic to its claims (no two opens of a run share a cache entry; the same
seed gives the same requests and data), and the comparison to the two runs
it must fail: the lower-precision control, and a run whose timed path is
broken underneath.  The last two drive `run.py` itself on the CPU at the
rehearsal size, past the look for a chip.
"""
import json
import os
import sys

import numpy as np
import pytest

os.environ["JAX_PLATFORMS"] = "cpu"
os.environ["FILODB_TPU_FUSED_INTERPRET"] = "1"
HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(HERE))
from benchmark import run  # noqa: E402

BENCH = run.load_json(os.path.join(run.ROOT, "BENCHMARK.json"))
CELLS = [w["name"] for w in BENCH["workloads"]]


def plan_of(cell, seed):
    cfg_name = next(w["config"] for w in BENCH["workloads"]
                    if w["name"] == cell)
    cfg = run.load_json(os.path.join(HERE, "configs", cfg_name + ".json"))
    wl = run.load_json(os.path.join(HERE, "workloads", cell + ".json"))
    mod = run.load_module("traffic", wl["traffic"]["kind"])
    return cfg, wl, mod.Plan(cfg, wl["traffic"], seed)


# ---------------------------------------------------------------- yardstick


def test_trace_reduction_on_the_recorded_trace():
    tr = run.load_module("", "trace")
    planes = tr.load(os.path.join(HERE, "testdata", "trace_small.json.gz"))
    want = run.load_json(os.path.join(HERE, "testdata",
                                      "trace_small.expected.json"))
    dev = tr.device_planes(planes)
    assert [p["name"] for p in dev] == want["device_planes"]
    # busy time by a count that shares no code with trace.union: every
    # stretch between two neighbouring event edges is busy if an event holds it
    evs = tr.line_events(dev[0], tr.OPS_LINE)
    edges = sorted({x for _, s, d in evs for x in (s, s + d)})
    busy = sum(b - a for a, b in zip(edges, edges[1:])
               if any(s <= a and b <= s + d for _, s, d in evs))
    assert tr.busy_seconds(planes) == pytest.approx(busy / 1e9, rel=1e-12)
    assert tr.busy_seconds(planes) == pytest.approx(want["busy_s"], rel=1e-9)
    mods = tr.matching(planes, want["line"], want["pattern"])
    assert len(mods) == want["events"]
    assert sum(ev[2] for ev in mods) / len(mods) / 1e6 == \
        pytest.approx(want["mean_ms"], rel=1e-9)
    lo, hi = tr.span_ns(planes)
    gaps = tr.idle_gaps(planes, lo, hi, lambda s, e: "gap", n=10 ** 9)
    assert sum(g for _, g in gaps) + tr.busy_seconds(planes) == \
        pytest.approx((hi - lo) / 1e9, rel=1e-9)
    assert tr.top_programs(planes, 3) == want["top_programs"]


def test_union_and_gaps_by_hand():
    tr = run.load_module("", "trace")
    planes = [{"name": "/device:TPU:0", "lines": [{"name": "XLA Ops", "events": [
        ["a", 10, 5], ["b", 12, 10], ["c", 40, 5], ["a", 100, 1]]}]},
        {"name": "/host:CPU", "lines": [{"name": "XLA Ops", "events": [
            ["x", 0, 1000]]}]}]
    assert tr.union(planes[0]["lines"][0]["events"]) == \
        [[10, 22], [40, 45], [100, 101]]
    assert tr.busy_seconds(planes) == pytest.approx(18e-9)
    assert tr.idle_gaps(planes, 0, 110, lambda s, e: f"{s}-{e}", n=2) == \
        [["45-100", 55e-9], ["22-40", 18e-9]]
    planes[0]["lines"].append({"name": "XLA Modules", "events": [
        ["jit_f(1)", 10, 12], ["jit_g(2)", 40, 5], ["jit_f(3)", 100, 1]]})
    assert tr.top_programs(planes, 1) == [["jit_f", 13e-9]]


def test_bytes_function_against_a_hand_count():
    costs = run.load_module("", "costs")
    # one shard of the 262,144-series cells: 65,536 series; the hour plus the
    # first window's 5 minutes at a 10 s scrape is 390 samples a series; 61
    # windows; 10 groups.  By hand:
    #   values 65,536 * 390 * 4 = 102,236,160
    #   base + group id 65,536 * 8 = 524,288;  out 10 * 61 * 4 = 2,440
    got = costs.fused_leaf(series=65536, span_s=3600, range_s=300, step_s=60,
                           scrape_ms=10000, groups=10)
    assert got["bytes"] == 102_236_160 + 524_288 + 2_440
    peaks = run.load_json(os.path.join(HERE, "peaks.json"))["by_device_kind"]
    secs, bound = costs.least_seconds(got, peaks["TPU v5 lite"])
    assert bound == "bytes"
    assert secs == pytest.approx(102_762_888 / 819e9)


# ------------------------------------------------------------------ traffic


@pytest.mark.parametrize("cell", CELLS)
def test_no_two_opens_share_a_cache_entry(cell):
    """The result cache's rule, replayed over every request the window could
    send: key (promql, step, start mod step); a request is answered from an
    entry unless it reaches back before the entry's start."""
    _, _, plan = plan_of(cell, 3)
    entries = {}
    reqs = plan.warmup() + plan.requests()
    assert len(reqs) == len({r["id"] for r in reqs})
    for r in reqs:
        q = r["params"]
        key = (q["query"], q["step"], q["start"] % q["step"])
        assert key not in entries or q["start"] < entries[key], r["id"]
        entries[key] = q["start"]
    assert plan.capacity == len(plan.requests()) >= 3000
    # and every window any request asks for is full and in the tables
    ends = set(plan.window_ends_s().tolist())
    cfg = plan.cfg
    for r in reqs:
        q = r["params"]
        assert set(range(q["start"], q["end"] + 1, q["step"])) <= ends
        assert (q["start"] - plan.range_s) * 1000 >= cfg["start_ms"] - \
            cfg["scrape_ms"]


@pytest.mark.parametrize("cell", CELLS)
def test_same_seed_same_requests_and_data(cell):
    cfg, _, a = plan_of(cell, 2_147_483_659)
    _, _, b = plan_of(cell, 2_147_483_659)
    _, _, c = plan_of(cell, 12)
    assert a.requests() == b.requests()
    key = lambda r: json.dumps(r["params"], sort_keys=True)  # noqa: E731
    assert [key(r) for r in a.requests()] != [key(r) for r in c.requests()]
    assert sorted(map(key, a.requests())) == sorted(map(key, c.requests()))
    gen = run.load_module("generators", cfg["generator"])
    x = gen.chunk(np.random.default_rng([2_147_483_659, 0]), np.empty((64, 720)))
    y = gen.chunk(np.random.default_rng([2_147_483_659, 0]), np.empty((64, 720)))
    z = gen.chunk(np.random.default_rng([12, 0]), np.empty((64, 720)))
    assert (x == y).all() and not (x == z).all()


def test_reference_on_a_case_worked_by_hand():
    ref = run.load_module("", "reference")
    ts = np.arange(6, dtype=np.int64) * 10_000
    vals = np.array([[1.0, 2.0, 4.0, 1.0, 3.0, 6.0]])     # reset at index 3
    corr = ref.correct_counters(vals, np.empty_like(vals))
    assert corr.tolist() == [[1.0, 2.0, 4.0, 5.0, 7.0, 10.0]]
    # window (20 s, 50 s]: samples at 30, 40, 50 s: 5, 7, 10; sampled 20 s,
    # 10 s short at the start (under 1.1 * 10 s average spacing, so
    # extrapolated in full), 0 at the end: 5 * 30 / 20 = 7.5
    inc = ref.ref_increase(ts, corr, np.array([50_000]), 30_000)
    assert inc.tolist() == [[7.5]]
    sot = ref.ref_sum_over_time(ts, np.cumsum(vals, axis=1),
                                np.array([50_000]), 30_000)
    assert sot.tolist() == [[10.0]]
    ext = ref.ref_window_extreme(ts, vals, np.array([50_000]), 30_000, "max")
    assert ext.tolist() == [[6.0]]


def test_the_window_waits_for_the_background_job(monkeypatch):
    import time

    class Door:
        def __init__(self, rows):
            self.rows = list(rows)

        def job(self, name):
            return self.rows.pop(0) if len(self.rows) > 1 else self.rows[0]
    assert run.wait_for_job(Door([None]), "flush", 1.0) < 0.1
    now = time.time()
    row = {"running": True, "lastEndUnixSeconds": now - 100,
           "intervalSeconds": 60}
    done = dict(row, running=False, lastEndUnixSeconds=now - 58.5)
    waited = run.wait_for_job(Door([row, row, done]), "flush", 1.0)
    # two polls of a running pass, then the rest of the 0.5 s until 1 s before
    # the next is due
    assert 0.45 < waited < 0.9
    assert run.wait_for_job(Door([dict(done, lastEndUnixSeconds=now - 70)]),
                            "flush", 1.0) < 0.1


# --------------------------------------------- the runs `correct` must fail


def run_cell(capsys, argv):
    rc = run.main(argv + ["--seconds", "2", "--trace", "0", "--rehearse"])
    out = capsys.readouterr().out.strip().splitlines()
    assert rc == 0
    return json.loads(out[-1]), out


@pytest.mark.parametrize("cell", CELLS)
def test_sound_run_is_correct_and_the_lower_precision_control_is_not(
        cell, capsys):
    line, out = run_cell(capsys, ["--workload", cell, "--seed", "4321"])
    assert line["correct"] is True and line["failed"] == 0, out
    assert line["device"]["platform"] == "cpu" and "rehearsal" in line["device"]
    line, out = run_cell(capsys, ["--workload", cell, "--seed", "4321",
                                  "--control", "bf16"])
    assert line["correct"] is False, out
    assert any(o.startswith("check ") and o.endswith("NOT OK") for o in out)


def test_a_broken_timed_path_is_not_correct(capsys, monkeypatch):
    """An answer altered where it is produced: every fused leaf's partial
    sums come back one part in a thousand too large."""
    from filodb_tpu.ops import pallas_fused as pf
    real = pf.fused_leaf_agg_batch

    def bent(*a, **kw):
        res = real(*a, **kw)

        def bend(parts):
            return [p * np.array([1.001] + [1.0] * (p.shape[-1] - 1))
                    for p in parts]
        return (lambda: bend(res())) if callable(res) else bend(res)
    monkeypatch.setattr(pf, "fused_leaf_agg_batch", bent)
    line, out = run_cell(capsys, ["--workload", CELLS[0], "--seed", "77"])
    assert line["correct"] is False and line["failed"] == line["attempted"], out


def test_no_accelerator_no_result(capsys):
    rc = run.main(["--workload", CELLS[0], "--seed", "1", "--seconds", "1",
                   "--trace", "0"])
    assert rc != 0
    assert capsys.readouterr().out.strip() == ""
