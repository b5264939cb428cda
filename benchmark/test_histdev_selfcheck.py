"""The self-check of `histdev-64b-4k.quantiles`.  Run by hand, not part of
tier-1 (each run waits up to a minute for the flush pass it aligns to):

    python3 -m pytest benchmark/test_histdev_selfcheck.py -q -p no:cacheprovider

It drives `run.py` itself on the CPU at the rehearsal size (64 series x 64
buckets, interpret-mode kernels), past the look for a chip: a sound run is
`correct`, the lower-precision control (`--control bf16`) is not, and a run
whose fused leaves return every second bucket's sums one part in a thousand
too large is not; and it holds `costs_hist.py` and the roofline reader's
count of a dispatch to a hand count.  (Tier-1 holds the same comparison to
the same two failures at 128 series, through the door, and the reference to
hand-worked histograms: `tests/test_hist_served.py`,
`tests/test_hist_reference.py`.)
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

CELL = "histdev-64b-4k.quantiles"


def run_cell(capsys, argv):
    rc = run.main(["--workload", CELL, "--seconds", "2", "--trace", "0",
                   "--rehearse"] + argv)
    cap = capsys.readouterr()
    assert rc == 0 and len(cap.out.strip().splitlines()) == 1
    return json.loads(cap.out), cap.err.strip().splitlines()


def test_sound_run_is_correct_and_the_lower_precision_control_is_not(capsys):
    line, err = run_cell(capsys, ["--seed", "4321"])
    assert line["correct"] is True and line["failed"] == 0, err
    assert "rehearsal" in line["device"]
    line, err = run_cell(capsys, ["--seed", "4321", "--control", "bf16"])
    assert line["correct"] is False, err
    assert not line["checks"]["quantile_rel_err"]["ok"]


def test_inflated_fused_bucket_sums_are_not_correct(capsys, monkeypatch):
    """Every second (group, bucket) slot's sum 1.001 times too large, where
    the fused leaf produces it.  (One factor on ALL slots cancels in a
    quantile: rank and buckets scale alike.  The comparison is of
    quantiles and cannot see it: `tests/test_hist_served.py` shows both.)"""
    from filodb_tpu.ops import pallas_fused as pf
    real = pf.fused_leaf_agg_batch

    def bent(*a, **kw):
        finish = real(*a, **kw)

        def bend():
            out = []
            for p in finish():
                p = p.copy()
                p[1::2, :, 0] *= 1.001
                out.append(p)
            return out
        return bend
    monkeypatch.setattr(pf, "fused_leaf_agg_batch", bent)
    line, err = run_cell(capsys, ["--seed", "77"])
    assert line["correct"] is False and line["failed"] > 0, err


def test_costs_and_the_roofline_count_against_a_hand_count():
    costs = run.load_module("", "costs_hist")
    # one shard of the cell: 1,024 series x 64 buckets = 65,536 kernel rows;
    # the hour plus the first window's 5 minutes at 10 s: 390 columns; 61
    # windows; one group: 64 slots.  By hand:
    #   values 65,536 * 390 * 4 = 102,236,160
    #   base + slot id 65,536 * 8 = 524,288;  out 64 * 61 * 4 = 15,616
    #   flops 65,536 * 390 * 3 + 65,536 * 61 * (12 + 2 * 64)
    got = costs.hist_fused_leaf(series=1024, buckets=64, span_s=3600,
                                range_s=300, step_s=60, scrape_ms=10000,
                                groups=1)
    assert got["bytes"] == 102_236_160 + 524_288 + 15_616
    assert got["flops"] == 76_677_120 + 559_677_440
    peaks = run.load_json(os.path.join(HERE, "peaks.json"))["by_device_kind"]
    secs, bound = run.load_module("", "costs").least_seconds(
        got, peaks["TPU v5 lite"])
    assert bound == "bytes" and secs == pytest.approx(102_776_064 / 819e9)
    # the reader's dispatch: the cell's 4,096 series over 4 leaves, a panel's
    # groups spread over the leaves and at least one: (1 + 1 + 1 + 2.5 + 2.5
    # + 1) / 6 = 1.5 groups a dispatch
    cfg = run.load_json(os.path.join(HERE, "configs", "histdev-64b-4k.json"))
    wl = run.load_json(os.path.join(HERE, "workloads", CELL + ".json"))
    plan = run.load_module("traffic", wl["traffic"]["kind"]).Plan(
        cfg, wl["traffic"], 1)
    need = run.load_module("readers", "roofline_hist").needed(cfg, plan, 4)
    assert need == costs.hist_fused_leaf(1024.0, 64, 3600, 300, 60, 10000,
                                         1.5)
    assert need["bytes"] == 102_236_160 + 524_288 + 1.5 * 64 * 61 * 4
