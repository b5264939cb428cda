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
    common factor on a leaf's sums, and says so here.)  Bent where the
    host reads a leaf's sums back: the host path, which since ISSUE 51 a
    request takes only where the device call's epilogue declines it (the
    recognition is switched off here; the next test bends the epilogue)."""
    from filodb_tpu.ops import pallas_fused as pf
    from filodb_tpu.query import exprfuse
    monkeypatch.setattr(exprfuse, "_hist_quantiles", lambda ep, calls: [])
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


def test_inflated_merged_bucket_sums_fail_the_comparison(rig, monkeypatch):
    """The same two bends where the sums are since ISSUE 51: on the
    device, between the epilogue's merge and its quantile."""
    import jax.numpy as jnp

    from filodb_tpu.ops import pallas_fused as pf
    real = pf._merge_hist_sets

    def bent(factor_of):
        def merge(*a, **kw):
            merged, present = real(*a, **kw)
            return merged * factor_of(merged.shape[1])[None, :, None], \
                present
        return merge
    try:
        monkeypatch.setattr(pf, "_merge_hist_sets", bent(
            lambda B: jnp.where(jnp.arange(B) % 2, 1.001, 1.0)
            .astype(jnp.float32)))
        pf._run_hist_quantile.clear_cache()
        assert max(worst(rig, 4)) > 10 * TOL
        monkeypatch.setattr(pf, "_merge_hist_sets", bent(
            lambda B: jnp.full(B, 1.001, jnp.float32)))
        pf._run_hist_quantile.clear_cache()
        assert max(worst(rig, 5)) <= TOL
    finally:
        monkeypatch.undo()
        pf._run_hist_quantile.clear_cache()


# ------------------------------------------------- the epilogue (ISSUE 51)


def test_a_served_quantile_is_one_call_and_no_host_histogram_work(rig):
    """Through the HTTP door: every request of an open is answered by the
    epilogue of its one device call; the reduce merges nothing, none of
    the three host spans appears, and the open's six panels (p50 / p90 /
    p99 over three groupings) run THREE compiled programs, one a
    grouping, whatever the q."""
    from filodb_tpu.ops import pallas_fused as pf
    for req in rig.open(6):                 # every grouping compiled
        (err, why), _ = rig.ask(req)
        assert why is None and err <= TOL
    time.sleep(0.3)                         # spans book after the body
    programs = pf._run_hist_quantile._cache_size()
    plain = pf._run._cache_size()
    before = rig.counters()
    for req in rig.open(7):
        (err, why), _ = rig.ask(req)
        assert why is None and err <= TOL
    time.sleep(0.3)
    after = rig.counters()

    def delta(fam):
        return after.get(fam, 0.0) - before.get(fam, 0.0)
    assert delta("hist_device_quantiles_total") == 6
    assert delta("hist_quantile_requests_total") == 6
    assert delta("hist_device_quantile_declined_total") == 0
    assert delta("fused_enqueues_total") == 6
    assert delta("leaf_hist_fused_total") == 6 * histrig.SHARDS
    assert delta("reduce_merge_calls_total") == 0
    assert delta("span_leaf_hist_epilogue_calls_total") == 6
    for name in ("exec_hist_reduce", "exec_hist_quantile",
                 "leaf_hist_finish", "leaf_present"):
        assert delta(f"span_{name}_calls_total") == 0, name
    assert pf._run_hist_quantile._cache_size() == programs
    assert pf._run._cache_size() == plain
    # three groupings a scheme, not six panels
    assert programs <= 3 * len(SEEDS)


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
    four spans once, each a child of the span that held its work before.
    (One leaf: the engine hoists nothing and the host path runs.)"""
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
