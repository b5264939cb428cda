"""Native histograms through the served path (ISSUE 31): the cell's six
`histogram_quantile(q, sum [by (..)](rate(http_latency[5m])))` panels over
the HTTP door, against the benchmark's plain f64 reference
(`benchmark/references/histogram.py`, loaded by path: the one copy), on
seeded data from `benchmark/generators/latency_hist.py` at 128 series x 64
buckets x 240 samples over 4 shards, interpret-mode kernels; the two runs
the comparison must fail; and the spans a histogram query adds.

Tolerance 1e-4, relative, on every cell of every response.  The device holds
f32: bucket counts up to 2^24 are exact, a per-bucket rate and its sum over a
group's series carry about 1e-7, and the quantile's interpolation divides by
ONE bucket's share of the group's count, so the error grows by the group's
total over that bucket's count: 200-fold for a p99 of a 13-series group
(readings here: 2.3e-7 to 1.4e-5 over both seeds, p99 by `_ns_` the
largest).  Buckets stored as bfloat16 read 1.1e-2 to 1.4: a hundred times
over the tolerance on the mildest panel."""
import json
import time

import numpy as np
import pytest

import histrig

TOL = 1e-4
SEEDS = (3100001, 2_147_483_659)
PANELS = range(6)


@pytest.fixture(scope="module", autouse=True)
def interpret_kernels():
    with histrig.environ(FILODB_TPU_FUSED_INTERPRET="1"):
        yield


@pytest.fixture(scope="module", params=SEEDS)
def rig(request):
    r = histrig.HistRig(request.param)
    yield r
    r.close()


@pytest.mark.parametrize("panel", PANELS)
def test_served_quantiles_match_the_f64_reference(rig, panel):
    req = rig.open(0)[panel]
    assert req["params"]["query"].startswith("histogram_quantile(")
    (err, why), body = rig.ask(req)
    assert why is None, why
    assert err <= TOL, (req["params"]["query"], err)
    assert len(body["data"]["result"]) == (1, 1, 1, 10, 10, 2)[panel]


def test_every_leaf_was_a_fused_histogram_dispatch_from_the_mirror(rig):
    before = rig.counters()
    for req in rig.open(1):
        (err, why), _ = rig.ask(req)
        assert why is None and err <= TOL
    after = rig.counters()

    def delta(fam):
        return after.get(fam, 0.0) - before.get(fam, 0.0)
    assert delta("leaf_hist_fused_total") == 6 * histrig.SHARDS
    assert delta("leaf_fused_kernel_total") == 6 * histrig.SHARDS
    assert delta("leaf_host_gather_total") == 0
    assert delta("leaf_general_path_total") == 0


def worst(rig, n):
    errs = []
    for req in rig.open(n):
        (err, why), _ = rig.ask(req)
        assert why is None, why
        errs.append(err)
    return errs


def test_buckets_stored_as_bfloat16_fail_the_comparison():
    r = histrig.HistRig(SEEDS[0], control="bf16")
    try:
        errs = worst(r, 0)
    finally:
        r.close()
    assert min(errs) > 10 * TOL, errs


def test_inflated_fused_bucket_sums_fail_the_comparison(rig, monkeypatch):
    """Every second bucket's fused sums one part in a thousand too large.
    (All of them alike would cancel: a quantile's rank and every bucket
    scale together.  The comparison is of quantiles, so it is blind to a
    common factor on a leaf's sums, and says so here.)"""
    from filodb_tpu.ops import pallas_fused as pf
    real = pf.fused_leaf_agg_batch

    def bent(factor_of):
        def call(*a, **kw):
            finish = real(*a, **kw)

            def bend():
                out = []
                for p in finish():
                    p = p.copy()
                    p[..., 0] *= factor_of(p.shape[0])[:, None]
                    out.append(p)
                return out
            return bend
        return call
    monkeypatch.setattr(pf, "fused_leaf_agg_batch", bent(
        lambda slots: np.where(np.arange(slots) % 2, 1.001, 1.0)))
    assert max(worst(rig, 2)) > 10 * TOL
    monkeypatch.setattr(pf, "fused_leaf_agg_batch", bent(
        lambda slots: np.full(slots, 1.001)))
    assert max(worst(rig, 3)) <= TOL


# ------------------------------------------------------------------- spans


def tree(rig, trace_id):
    deadline = time.monotonic() + 10.0
    while True:
        spans = json.loads(rig.get(f"/admin/traces/{trace_id}"))["data"][
            "spans"]
        if any(e["name"] == "http.request" for e in spans):
            return spans
        assert time.monotonic() < deadline, "the root never landed"
        time.sleep(0.002)


def test_a_histogram_query_adds_four_spans_nested_where_the_work_was():
    """One shard, its first histogram query (nothing cached): each of the
    four spans once, each a child of the span that held its work before."""
    r = histrig.HistRig(SEEDS[0], shards=1)
    try:
        (err, why), body = r.ask(r.open(0)[0])
        assert why is None and err <= TOL
        spans = tree(r, body["traceID"])
    finally:
        r.close()
    by_id = {e["span_id"]: e for e in spans}
    parent = {"leaf.hist_flatten": "leaf.fused_prepare",
              "leaf.hist_finish": "leaf.present",
              "exec.hist_reduce": "exec.ReduceAggregateExec",
              "exec.hist_quantile": "exec.ReduceAggregateExec"}
    for name, held_by in parent.items():
        mine = [e for e in spans if e["name"] == name]
        assert len(mine) == 1, (name, len(mine))
        assert by_id[mine[0]["parent_id"]]["name"] == held_by, name
        assert mine[0]["dur_ns"] > 0
