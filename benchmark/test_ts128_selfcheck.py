"""The self-check of `ts128-counters-262k-32sh.open`, and a rehearsal of
`tsdev-gauges-262k.filtered` through the caches PR 35 changed.  Run by hand,
not part of tier-1 (each run waits up to a minute for the flush pass it
aligns to):

    python3 -m pytest benchmark/test_ts128_selfcheck.py -q -p no:cacheprovider

It drives `run.py` itself on the CPU at the rehearsal size (2,048 series over
32 shards, interpret-mode kernels), past the look for a chip: a sound run of
either cell is `correct`; in the 32-shard cell the lower-precision control
(`--control bf16`) is not, and a run whose fused leaves return sums one part
in a thousand too large is not; the same seed gives the same requests and
data.  `.filtered` is still a workload file that `BENCHMARK.json` does not
list (PERF.md section 2): it is rehearsed through a `BENCHMARK.json` that
lists it, as `test_selfcheck.py` does.  (Tier-1 holds the same comparison at
the same size through the door, and the share to its 128-shard deployment:
`tests/test_ts128_served.py`.)
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

CELL, FILTERED = "ts128-counters-262k-32sh.open", "tsdev-gauges-262k.filtered"


def run_cell(capsys, cell, argv):
    rc = run.main(["--workload", cell, "--seconds", "2", "--trace", "0",
                   "--rehearse"] + argv)
    cap = capsys.readouterr()
    assert rc == 0 and len(cap.out.strip().splitlines()) == 1
    return json.loads(cap.out), cap.err.strip().splitlines()


BENCH = run.load_json(os.path.join(run.ROOT, "BENCHMARK.json"))
if all(w["name"] != FILTERED for w in BENCH["workloads"]):
    BENCH["workloads"].append({
        "name": FILTERED, "config": "tsdev-gauges-262k",
        "traffic": "filtered", "chips": 1, "why": "see its workload file"})


@pytest.fixture(autouse=True)
def benchmark_json_lists_the_cell_with_a_selector(monkeypatch):
    real = run.load_json
    monkeypatch.setattr(
        run, "load_json", lambda path: BENCH
        if os.path.basename(path) == "BENCHMARK.json" else real(path))


def test_the_new_cell_is_listed_and_the_metrics_name_it():
    bench = json.load(open(os.path.join(run.ROOT, "BENCHMARK.json")))
    cells = {w["name"]: w for w in bench["workloads"]}
    assert cells[CELL]["chips"] == 1 and FILTERED not in cells
    new = {"working_set_misses_per_query", "plan_builds_per_query",
           "reduce_ms", "dispatch_serial_ms"}
    elsewhere = {"mirror_gather_device_ms", "hist_fused_leaves_per_query",
                 "hist_host_ms", "hist_fused_roofline"}
    for m in bench["per_layer"]:
        assert (CELL in m["workloads"]) == (m["name"] not in elsewhere), \
            m["name"]
        if m["name"] in new:
            assert len(m["workloads"]) == 4
            assert os.path.exists(os.path.join(
                HERE, "layer_metrics", m["name"] + ".json"))
    cfg = run.load_json(os.path.join(HERE, "configs",
                                     cells[CELL]["config"] + ".json"))
    assert (cfg["shards"], cfg["series"], cfg["labels"]["_ns_"]["mod"]) == \
        (32, 262144, 40)
    assert run.loader_of(cfg).__file__ == os.path.join(
        HERE, "loaders", "grid_on_mirror.py")
    assert cfg["deployment"]["shards"] == 4 * cfg["shards"]
    assert cfg["deployment"]["series"] == 4 * cfg["series"]
    # the traffic of the 4-shard counters cell, letter for letter
    mine = run.load_json(os.path.join(HERE, "workloads", CELL + ".json"))
    theirs = run.load_json(os.path.join(
        HERE, "workloads", "promperf-counters-262k.open.json"))
    assert mine["traffic"] == theirs["traffic"]


def test_same_seed_same_requests_and_data():
    cfg = run.load_json(os.path.join(
        HERE, "configs", "ts128-counters-262k-32sh.json"))
    tp = run.load_json(os.path.join(HERE, "workloads",
                                    CELL + ".json"))["traffic"]
    Plan = run.load_module("traffic", tp["kind"]).Plan
    a, b, c = (Plan(cfg, tp, s) for s in (2_147_483_659, 2_147_483_659, 12))
    assert a.requests() == b.requests() and a.warmup() == b.warmup()
    key = lambda r: json.dumps(r["params"], sort_keys=True)  # noqa: E731
    assert [key(r) for r in a.requests()] != [key(r) for r in c.requests()]
    assert sorted(map(key, a.requests())) == sorted(map(key, c.requests()))
    assert a.capacity == len(a.requests()) >= 3000
    assert (a.num_base(), a.selected_series()) == (40, 262144)
    gen = run.load_module("generators", cfg["generator"])
    x = gen.chunk(np.random.default_rng([2_147_483_659, 0]), np.empty((64, 720)))
    y = gen.chunk(np.random.default_rng([2_147_483_659, 0]), np.empty((64, 720)))
    z = gen.chunk(np.random.default_rng([12, 0]), np.empty((64, 720)))
    assert (x == y).all() and not (x == z).all()


@pytest.mark.parametrize("cell", [CELL, FILTERED])
def test_a_rehearsal_runs_to_a_correct_result(cell, capsys):
    line, out = run_cell(capsys, cell, ["--seed", "2147483693"])
    assert line["correct"] is True and line["failed"] == 0, out
    assert line["device"]["platform"] == "cpu" and "rehearsal" in line["device"]
    assert {"query_p50_ms", "queries_per_s", "setup_s"} <= set(line["metrics"])
    if cell == CELL:
        shards = next(o for o in out if o.startswith("loaded "))
        assert shards.count(", 0") == 2         # two of the 32 stay empty


def test_the_lower_precision_control_is_not_correct(capsys):
    line, out = run_cell(capsys, CELL, ["--seed", "4321", "--control", "bf16"])
    assert line["correct"] is False, out
    assert line["checks"]["rate_rel_err"]["ok"] is False
    assert line["checks"]["requests_unanswered_or_misshapen"]["ok"] is True
    assert any(o.startswith("check rate_rel_err") and o.endswith("NOT OK")
               for o in out)


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
    line, out = run_cell(capsys, CELL, ["--seed", "77"])
    assert line["correct"] is False and line["failed"] == line["attempted"], out
