"""The self-check of `promchurn-counters-262k.open`.  Run by hand, not part
of tier-1 (each run waits up to a minute for the flush pass it aligns to):

    python3 -m pytest benchmark/test_promchurn_selfcheck.py -q -p no:cacheprovider

It drives `run.py` itself on the CPU at the rehearsal size (2,048 live series
over 4 shards, eleven replacements of 1% and twelve periods of outages,
interpret-mode kernels), past the look for a chip: a sound run is `correct`;
the lower-precision control (`--control bf16`) is not; two patched programs
are not, by `rate_rel_err`: one whose mirror fills a hole with the last value
before it (`PATCHES["fill"]`), one that places a late-starting row at slot 0
(`PATCHES["slot0"]`); a program whose mirror requires equal counts is turned
away by the loader before anything is generated; the same seed gives the same
lives, outages, requests and data.  (Tier-1 holds the same comparison at the
same size through the door: `tests/test_promchurn_served.py`.)

The limit's readings on the chip came from the same two patches at the cell's
own size (README.promchurn.md says how they were run).
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

CELL, TWIN = "promchurn-counters-262k.open", "promscrape-counters-262k.open"
NEW = {"ragged_fused_leaves_per_query": [CELL], "mirror_place_s": [CELL],
       "ragged_fused_roofline": [CELL], "mirror_placed_rows": None}


def fill_holes(dc):
    """A mirror that fills a hole with the last value before it: every slot
    between a row's first and last sample holds a value."""
    real = dc._placed

    def filled(x, rows, need, slot, n_slots):
        out = real(x, rows, need, slot, n_slots)
        held = np.isfinite(out)
        at = np.maximum.accumulate(
            np.where(held, np.arange(n_slots)[None, :], -1), axis=1)
        last = n_slots - 1 - np.argmax(held[:, ::-1], axis=1)
        inside = (at >= 0) & (np.arange(n_slots)[None, :] <= last[:, None])
        return np.where(inside & ~held,
                        np.take_along_axis(out, np.maximum(at, 0), axis=1),
                        out)
    return "_placed", filled


def late_rows_at_slot_zero(dc):
    """A mirror that places a late-starting row at slot 0: every row's first
    sample in the grid's first slot, the others behind it as they came."""
    real = dc._place_on_grid

    def shifted(ts_off, counts, base_ms=0):
        got = real(ts_off, counts, base_ms)
        if isinstance(got, tuple):
            ts_row0, phase, need, slot, interval = got
            slot = np.where(slot >= 0, slot - slot[:, :1], -1)
            got = ts_row0, phase, need, slot, interval
        return got
    return "_place_on_grid", shifted


PATCHES = {"fill": fill_holes, "slot0": late_rows_at_slot_zero}


def apply_patch(name, setattr_=setattr):
    from filodb_tpu.core import devicecache as dc
    attr, fn = PATCHES[name](dc)
    setattr_(dc, attr, fn)


def run_cell(capsys, argv, rc=0):
    got = run.main(["--workload", CELL, "--seconds", "2", "--trace", "0",
                    "--rehearse"] + argv)
    cap = capsys.readouterr()
    assert got == rc and len(cap.out.strip().splitlines()) == 1
    return json.loads(cap.out), cap.err.strip().splitlines()


def test_the_new_cell_is_listed_and_the_metrics_name_it():
    bench = json.load(open(os.path.join(run.ROOT, "BENCHMARK.json")))
    cells = {w["name"]: w for w in bench["workloads"]}
    assert cells[CELL]["config"] == "promchurn-counters-262k"
    assert cells[CELL]["traffic"] == cells[TWIN]["traffic"] == "open"
    assert cells[CELL]["chips"] == 1 and len(cells[CELL]["why"]) <= 200
    seen = set()
    for m in bench["per_layer"]:
        if m["name"] in NEW:
            seen.add(m["name"])
            want = NEW[m["name"]]
            if want is None:        # read in every cell: 0 off this one
                assert m["workloads"][0] == CELL and len(m["workloads"]) >= 6
            else:
                assert m["workloads"] == want
            spec = run.load_json(os.path.join(HERE, "layer_metrics",
                                              m["name"] + ".json"))
            assert spec["layer"] == m["layer"] and spec["moves"] == m["moves"]
        elif m["name"] == "fused_roofline":
            # the DENSE leaf's bytes over a ragged launch would read wrong
            # here: `ragged_fused_roofline` is this cell's share
            assert CELL not in m["workloads"] and TWIN in m["workloads"]
        else:
            # wherever else the twin is read the cell is read
            assert (CELL in m["workloads"]) == (TWIN in m["workloads"]), \
                m["name"]
    assert seen == set(NEW)
    entry = next(c for c in bench["configs"]
                 if c["name"] == cells[CELL]["config"])
    cfg = run.load_json(os.path.join(run.ROOT, entry["file"]))
    twin = run.load_json(os.path.join(HERE, "configs",
                                      "promscrape-counters-262k.json"))
    assert entry["reduced"] == ["series"] == list(cfg["reduced"])
    assert entry["source"] == cfg["source"] and len(cfg["source"]) <= 200
    assert (cfg["loader"], cfg["reference"]) == ("churned_scrapes",) * 2
    for key in ("schema", "metric", "generator", "series", "samples",
                "scrape_ms", "start_ms", "shards", "labels", "server",
                "scrape_offsets", "column", "dataset", "chips"):
        assert cfg[key] == twin[key], key
    wl = run.load_json(os.path.join(HERE, "workloads", CELL + ".json"))
    assert wl["traffic"] == run.load_json(os.path.join(
        HERE, "workloads", TWIN + ".json"))["traffic"]


def test_same_seed_same_lives_requests_and_data():
    cfg = run.load_json(os.path.join(HERE, "configs",
                                     "promchurn-counters-262k.json"))
    tp = run.load_json(os.path.join(HERE, "workloads",
                                    CELL + ".json"))["traffic"]
    Plan = run.load_module("traffic", tp["kind"]).Plan
    a, b, c = (Plan(cfg, tp, s) for s in (2_147_483_659, 2_147_483_659, 12))
    assert a.requests() == b.requests() and a.warmup() == b.warmup()
    key = lambda r: json.dumps(r["params"], sort_keys=True)  # noqa: E731
    assert sorted(map(key, a.requests())) == sorted(map(key, c.requests()))
    lives = run.load_module("loaders", "churned_scrapes").lives
    x, y, z = (lives(s, cfg) for s in (2_147_483_659, 2_147_483_659, 12))
    for u, v in zip(x[:3] + x[3], y[:3] + y[3]):
        assert (u == v).all()
    assert (x[0] != z[0]).any() and (x[3][1] != z[3][1]).any()
    assert len(x[0]) == 290_975 and len(x[3][0]) == 12 * 1_310


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


@pytest.mark.parametrize("patch", sorted(PATCHES))
def test_a_patched_program_is_not_correct(capsys, monkeypatch, patch):
    apply_patch(patch, monkeypatch.setattr)
    line, out = run_cell(capsys, ["--seed", "77"])
    assert line["correct"] is False, out
    chk = line["checks"]["rate_rel_err"]
    assert chk["ok"] is False and chk["value"] > 5 * chk["limit"]
    assert line["checks"]["requests_unanswered_or_misshapen"]["ok"] is True


def test_a_program_that_requires_equal_counts_is_turned_away(monkeypatch):
    from filodb_tpu.core import devicecache as dc
    monkeypatch.setattr(dc, "_place_on_grid", lambda *a: 3)
    with pytest.raises(RuntimeError, match="is not fusable"):
        run.main(["--workload", CELL, "--seed", "5", "--seconds", "2",
                  "--trace", "0", "--rehearse"])
