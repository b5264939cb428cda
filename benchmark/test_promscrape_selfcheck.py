"""The self-check of `promscrape-counters-262k.open`.  Run by hand, not part
of tier-1 (each run waits up to a minute for the flush pass it aligns to):

    python3 -m pytest benchmark/test_promscrape_selfcheck.py -q -p no:cacheprovider

It drives `run.py` itself on the CPU at the rehearsal size (2,048 series over
4 shards, every series at its own scrape offset, interpret-mode kernels),
past the look for a chip: a sound run is `correct`; the lower-precision
control (`--control bf16`) is not; a program patched to read every phase as 0
(the mirror finds the grid and forgets the offsets, so every leaf runs the
unphased kernel over the base row) is not, by `rate_rel_err`; a program whose
mirror does not fuse rows at scrape offsets is turned away by the loader
before anything is generated; the same seed gives the same offsets, requests
and data.  (Tier-1 holds the same comparison at the same size through the
door: `tests/test_promscrape_served.py`.)
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

CELL, TWIN = "promscrape-counters-262k.open", "promperf-counters-262k.open"


def run_cell(capsys, argv, rc=0):
    got = run.main(["--workload", CELL, "--seconds", "2", "--trace", "0",
                    "--rehearse"] + argv)
    cap = capsys.readouterr()
    assert got == rc and len(cap.out.strip().splitlines()) == 1
    return json.loads(cap.out), cap.err.strip().splitlines()


def test_the_new_cell_is_listed_and_the_metrics_name_it():
    bench = json.load(open(os.path.join(run.ROOT, "BENCHMARK.json")))
    cells = {w["name"]: w for w in bench["workloads"]}
    assert len(cells) >= 5 and all(w["chips"] == 1 for w in cells.values())
    assert cells[CELL]["config"] == "promscrape-counters-262k"
    assert cells[CELL]["traffic"] == cells[TWIN]["traffic"] == "open"
    new = {"phase_fused_leaves_per_query", "offgrid_leaves"}
    for m in bench["per_layer"]:
        if m["name"] in new:
            assert m["workloads"] == [CELL] and m["layer"] == "Leaf routes"
            spec = run.load_json(os.path.join(HERE, "layer_metrics",
                                              m["name"] + ".json"))
            assert spec["reader"] == "counter_delta"
        else:
            # wherever the unphased twin is read the cell is read, but the
            # device time of a row gather that neither launches
            assert (CELL in m["workloads"]) == (
                TWIN in m["workloads"]
                and m["name"] != "mirror_gather_device_ms"), m["name"]
    entry = next(c for c in bench["configs"]
                 if c["name"] == cells[CELL]["config"])
    cfg = run.load_json(os.path.join(run.ROOT, entry["file"]))
    twin = run.load_json(os.path.join(HERE, "configs",
                                      "promperf-counters-262k.json"))
    assert entry["reduced"] == ["series"] == list(cfg["reduced"])
    assert entry["source"] == cfg["source"] and len(cfg["source"]) < 200
    assert cfg["guarantees"] == twin["guarantees"]
    assert (cfg["loader"], cfg["reference"]) == ("scrape_offsets",) * 2
    for key in ("schema", "metric", "generator", "series", "samples",
                "scrape_ms", "start_ms", "shards", "labels", "server"):
        assert cfg[key] == twin[key], key
    wl = run.load_json(os.path.join(HERE, "workloads", CELL + ".json"))
    assert wl["traffic"] == run.load_json(os.path.join(
        HERE, "workloads", TWIN + ".json"))["traffic"]


def test_same_seed_same_offsets_requests_and_data():
    cfg = run.load_json(os.path.join(HERE, "configs",
                                     "promscrape-counters-262k.json"))
    tp = run.load_json(os.path.join(HERE, "workloads",
                                    CELL + ".json"))["traffic"]
    Plan = run.load_module("traffic", tp["kind"]).Plan
    a, b, c = (Plan(cfg, tp, s) for s in (2_147_483_659, 2_147_483_659, 12))
    assert a.requests() == b.requests() and a.warmup() == b.warmup()
    key = lambda r: json.dumps(r["params"], sort_keys=True)  # noqa: E731
    assert [key(r) for r in a.requests()] != [key(r) for r in c.requests()]
    assert sorted(map(key, a.requests())) == sorted(map(key, c.requests()))
    offsets = run.load_module("loaders", "scrape_offsets").scrape_offsets
    x, y, z = (offsets(s, cfg["scrape_ms"], cfg["series"])
               for s in (2_147_483_659, 2_147_483_659, 12))
    assert (x == y).all() and (x != z).any()
    assert x.min() == 0 and x.max() == cfg["scrape_ms"] - 1
    # a stream of its own: the values of a seed are `grid`'s of that seed
    gen = run.load_module("generators", cfg["generator"])
    u = gen.chunk(np.random.default_rng([2_147_483_659, 0]),
                  np.empty((64, 720)))
    v = gen.chunk(np.random.default_rng([2_147_483_659, 0]),
                  np.empty((64, 720)))
    assert (u == v).all()


def test_a_rehearsal_runs_to_a_correct_result(capsys):
    line, out = run_cell(capsys, ["--seed", "2147483693"])
    assert line["correct"] is True and line["failed"] == 0, out
    assert line["device"]["platform"] == "cpu" and "rehearsal" in line["device"]
    assert {"query_p50_ms", "queries_per_s", "setup_s"} <= set(line["metrics"])


def test_the_lower_precision_control_is_not_correct(capsys):
    line, out = run_cell(capsys, ["--seed", "4321", "--control", "bf16"])
    assert line["correct"] is False, out
    assert line["checks"]["rate_rel_err"]["ok"] is False
    assert line["checks"]["requests_unanswered_or_misshapen"]["ok"] is True


def test_a_program_that_reads_every_phase_as_zero_is_not_correct(
        capsys, monkeypatch):
    from filodb_tpu.core import devicecache as dc
    real = dc._detect_phase_grid

    def blind(ts_off, counts, base_ms=0):
        base, phase, off = real(ts_off, counts, base_ms)
        return base, (None if phase is None else np.zeros_like(phase)), off
    monkeypatch.setattr(dc, "_detect_phase_grid", blind)
    line, out = run_cell(capsys, ["--seed", "77"])
    assert line["correct"] is False, out
    chk = line["checks"]["rate_rel_err"]
    assert chk["ok"] is False and chk["value"] > 50 * chk["limit"]
    assert line["checks"]["requests_unanswered_or_misshapen"]["ok"] is True


def test_a_program_that_does_not_fuse_scrape_offsets_is_turned_away(
        monkeypatch):
    from filodb_tpu.core.devicecache import DeviceMirror
    monkeypatch.setattr(DeviceMirror, "fused_eligible",
                        lambda self, *a, **kw: None)
    with pytest.raises(RuntimeError, match="is not fusable"):
        run.main(["--workload", CELL, "--seed", "5", "--seconds", "2",
                  "--trace", "0", "--rehearse"])
