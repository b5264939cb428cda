"""The self-check of `tsbscpu-gauges-40k.double-groupby`.  Run by hand, not
part of tier-1 (each run waits up to a minute for the flush pass it aligns
to):

    python3 -m pytest benchmark/test_tsbscpu_selfcheck.py -q -p no:cacheprovider

It drives `run.py` itself on the CPU at the rehearsal size (32 hosts x 10
metrics x the configuration's own 4,736 samples over 4 shards,
interpret-mode kernels), past the look for a chip: a sound run is
`correct`; the lower-precision control (`--control bf16`) is not; a run
whose fused leaves return partial sums one part in a thousand too large is
not, on every request; the same seed gives the same requests and data,
another seed the same set of requests in another order; no two requests of a
run share a result-cache entry; the reference on a case worked by hand.
(Tier-1 holds the same comparison at the same row length through the door:
`tests/test_tsbscpu_served.py`.)
"""
import json
import os
import sys

import numpy as np

os.environ["JAX_PLATFORMS"] = "cpu"
os.environ["FILODB_TPU_FUSED_INTERPRET"] = "1"
HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(HERE))
from benchmark import run  # noqa: E402

CONFIG, CELL = "tsbscpu-gauges-40k", "tsbscpu-gauges-40k.double-groupby"


def run_cell(capsys, argv, rc=0):
    got = run.main(["--workload", CELL, "--seconds", "2", "--trace", "0",
                    "--rehearse"] + argv)
    cap = capsys.readouterr()
    assert got == rc and len(cap.out.strip().splitlines()) == 1
    return json.loads(cap.out), cap.err.strip().splitlines()


def _plan(seed):
    cfg = run.load_json(os.path.join(HERE, "configs", CONFIG + ".json"))
    tp = run.load_json(os.path.join(HERE, "workloads",
                                    CELL + ".json"))["traffic"]
    return cfg, tp, run.load_module("traffic", tp["kind"]).Plan(cfg, tp, seed)


def test_the_cell_is_listed_with_its_own_metrics():
    bench = json.load(open(os.path.join(run.ROOT, "BENCHMARK.json")))
    cell = next(w for w in bench["workloads"] if w["name"] == CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) \
        == (CONFIG, "double-groupby", 1)
    own = {"fused_groups_per_leaf": "counter_ratio",
           "present_points_per_query": "counter_delta",
           "band_tiles_per_query": "counter_delta",
           "longrow_band_roofline": "roofline_band"}
    for m in bench["per_layer"]:
        if m["name"] in own:
            assert m["workloads"] == [CELL]
            spec = run.load_json(os.path.join(HERE, "layer_metrics",
                                              m["name"] + ".json"))
            assert spec["reader"] == own[m["name"]]
            assert spec["layer"] == m["layer"] and spec["unit"] == m["unit"]
    entry = next(c for c in bench["configs"] if c["name"] == CONFIG)
    cfg = run.load_json(os.path.join(run.ROOT, entry["file"]))
    assert entry["reduced"] == ["samples"] == list(cfg["reduced"])
    assert entry["source"] == cfg["source"] and len(cfg["source"]) <= 200
    assert (cfg["loader"], cfg["reference"], cfg["generator"]) \
        == ("tsbs_cpu", "tsbs_cpu", "clamped_walk")


def test_same_seed_same_requests_and_data_another_seed_another_order():
    cfg, tp, a = _plan(2_147_483_659)
    _, _, b = _plan(2_147_483_659)
    _, _, c = _plan(12)
    assert a.requests() == b.requests() and a.warmup() == b.warmup()
    key = lambda r: json.dumps(r["params"], sort_keys=True)  # noqa: E731
    assert [key(r) for r in a.requests()] != [key(r) for r in c.requests()]
    assert sorted(map(key, a.requests())) == sorted(map(key, c.requests()))
    assert a.warmup() == c.warmup()
    gen = run.load_module("generators", cfg["generator"])
    u, v, w = (gen.chunk(np.random.default_rng([s, 3]), np.empty((64, 720)))
               for s in (2_147_483_659, 2_147_483_659, 12))
    assert (u == v).all() and (u != w).any()
    assert u.min() >= 0.0 and u.max() <= 100.0


def test_no_two_requests_of_a_run_share_a_result_cache_entry():
    """The cache's rule (query/resultcache.py): an entry is (promql, step,
    start mod step), and every open, the warm-up's too, has a phase of its
    own, no two a whole number of steps apart."""
    _, tp, plan = _plan(5)
    every = plan.requests() + plan.warmup()
    entries = {(r["params"]["query"], r["params"]["step"],
                r["params"]["start"] % r["params"]["step"]) for r in every}
    assert len(entries) == len(every)
    assert len(plan.requests()) == plan.capacity == 5 * tp["phases"]
    # every window of every request holds its 360 samples
    cfg = plan.cfg
    first_s = cfg["start_ms"] // 1000
    assert min(r["params"]["start"] for r in every) - tp["range_s"] >= first_s


def test_the_reference_on_a_case_worked_by_hand():
    ref_mod = run.load_module("references", "tsbs_cpu")
    ts = np.arange(8, dtype=np.int64) * 10_000
    wends = np.array([35_000, 70_000, 5_000_000])
    panel = {"metric": "m", "fn": "avg_over_time", "agg": "avg",
             "by": ["hostname"]}
    ref = ref_mod.Reference(ts, wends, 30_000, [panel], 2)
    ref.add("m", np.array([[1., 2, 3, 4, 5, 6, 7, 8],
                           [10., 10, 10, 40, 10, 10, 10, 70]]),
            np.array([0, 1]))
    table = ref.table(panel, np.array([0, 1]))
    # (5 s, 35 s] holds samples 1, 2, 3; (40 s, 70 s] holds 5, 6, 7
    assert table[:, :2].tolist() == [[3.0, 7.0], [20.0, 30.0]]
    assert np.isnan(table[:, 2]).all()


def test_a_rehearsal_runs_to_a_correct_result(capsys):
    line, out = run_cell(capsys, ["--seed", "2147483693"])
    assert line["correct"] is True and line["failed"] == 0, out
    assert line["device"]["platform"] == "cpu" and "rehearsal" in line["device"]
    assert {"query_p50_ms", "queries_per_s", "setup_s"} <= set(line["metrics"])


def test_the_lower_precision_control_is_not_correct(capsys):
    line, out = run_cell(capsys, ["--seed", "4321", "--control", "bf16"])
    assert line["correct"] is False, out
    assert line["failed"] == line["attempted"]
    assert line["checks"]["avg_rel_err"]["ok"] is False
    assert line["checks"]["requests_unanswered_or_misshapen"]["ok"] is True


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
    line, out = run_cell(capsys, ["--seed", "77"])
    assert line["correct"] is False and line["failed"] == line["attempted"], out
