"""Pallas fused rate+group-sum kernel vs the general XLA path.

Runs in interpret mode on CPU (the kernel itself is MXU-targeted; the
driver bench exercises it on the real chip).  The XLA path
(evaluate_range_function + agg.aggregate) is oracle-verified elsewhere
(tests/test_rangefns.py, test_query_engine.py), so agreement here chains
the conformance."""
import functools
import os

import numpy as np
import pytest

import jax.numpy as jnp

from filodb_tpu.ops import agg as agg_ops
from filodb_tpu.ops.counter import rebase_values
from filodb_tpu.ops.pallas_fused import (build_plan, can_fuse,
                                         fused_rate_groupsum, pad_inputs,
                                         present_sum)
from filodb_tpu.ops.rangefns import evaluate_range_function
from filodb_tpu.ops.timewindow import make_window_ends, to_offsets

START_STEP = 10_000


def _mk(S=120, T=160, G=5, resets=True, seed=0):
    rng = np.random.default_rng(seed)
    ts_row = np.arange(T, dtype=np.int64) * START_STEP
    raw = np.cumsum(rng.exponential(10.0, size=(S, T)), axis=1)
    if resets:
        raw[::7, T // 2:] *= 0.1          # counter resets mid-series
    gids = (np.arange(S) % G).astype(np.int32)
    return ts_row, raw, gids


def _xla(ts_row, vals32, vbase, gids, wends, range_ms, fn, G, precor):
    S, T = vals32.shape
    ts_off = to_offsets(np.tile(ts_row, (S, 1)), np.full(S, T), 0)
    r = evaluate_range_function(
        jnp.asarray(ts_off), jnp.asarray(vals32),
        jnp.asarray(wends.astype(np.int32)), range_ms, fn, shared_grid=True,
        vbase=jnp.asarray(vbase.astype(np.float32)), precorrected=precor)
    return np.asarray(agg_ops.aggregate("sum", r, jnp.asarray(gids), G))


@pytest.mark.parametrize("fn,precor", [
    ("rate", False), ("rate", True), ("increase", False),
    ("increase", True), ("delta", False)])
def test_fused_matches_xla_path(fn, precor):
    ts_row, raw, gids = _mk()
    G = 5
    range_ms = 30 * START_STEP
    wends = make_window_ends(40 * START_STEP, 150 * START_STEP,
                             6 * START_STEP)
    plan = build_plan(ts_row, wends, range_ms)
    reb, vbase = rebase_values(raw, precor and fn != "delta")
    vals32 = reb.astype(np.float32)
    vb32 = vbase.astype(np.float32)
    sums, counts = fused_rate_groupsum(
        vals32, vb32, gids, plan, G, fn_name=fn, precorrected=precor,
        interpret=True)
    got = present_sum(sums, counts)
    want = _xla(ts_row, vals32, vb32, gids, wends, range_ms, fn, G, precor)
    assert (np.isnan(got) == np.isnan(want)).all()
    np.testing.assert_allclose(got, want, rtol=2e-5, atol=1e-4,
                               equal_nan=True)


def test_fused_sparse_windows_and_edges():
    """Windows before data, with < 2 samples, and beyond the data range."""
    ts_row, raw, gids = _mk(S=40, T=50, G=3, resets=False)
    G, range_ms = 3, 2 * START_STEP          # tiny window: n varies 0..2
    wends = make_window_ends(-5 * START_STEP, 70 * START_STEP, START_STEP)
    plan = build_plan(ts_row, wends, range_ms)
    reb, vbase = rebase_values(raw, False)
    sums, counts = fused_rate_groupsum(
        reb.astype(np.float32), vbase.astype(np.float32), gids, plan, G,
        interpret=True)
    got = present_sum(sums, counts)
    want = _xla(ts_row, reb.astype(np.float32), vbase.astype(np.float32),
                gids, wends, range_ms, "rate", G, False)
    assert (np.isnan(got) == np.isnan(want)).all()
    np.testing.assert_allclose(got, want, rtol=2e-5, atol=1e-4,
                               equal_nan=True)


def test_fused_large_counter_rebase_precision():
    """Counters at 2^30: rebased f32 deltas stay exact (the round-1 f32
    cancellation bug class)."""
    S, T, G = 16, 100, 2
    ts_row = np.arange(T, dtype=np.int64) * START_STEP
    rng = np.random.default_rng(1)
    raw = 2.0**30 + np.cumsum(rng.integers(1, 100, size=(S, T)), axis=1)
    gids = (np.arange(S) % G).astype(np.int32)
    range_ms = 30 * START_STEP
    wends = make_window_ends(40 * START_STEP, 90 * START_STEP,
                             5 * START_STEP)
    plan = build_plan(ts_row, wends, range_ms)
    reb, vbase = rebase_values(raw, True)
    sums, counts = fused_rate_groupsum(
        reb.astype(np.float32), vbase.astype(np.float32), gids, plan, G,
        precorrected=True, interpret=True)
    got = present_sum(sums, counts)
    # f64 oracle on the raw values
    lo = np.searchsorted(ts_row, wends - range_ms + 1, side="left")
    hi = np.searchsorted(ts_row, wends, side="right") - 1
    per = (raw[:, hi] - raw[:, lo]) / ((ts_row[hi] - ts_row[lo]) / 1000.0)
    # extrapolation factor is near 1 for dense full windows; compare rates
    # group-summed with generous-but-small tolerance
    want = np.zeros((G, len(wends)))
    np.add.at(want, gids, per)
    np.testing.assert_allclose(got, want, rtol=5e-3)


def test_prepared_inputs_reuse():
    ts_row, raw, gids = _mk(S=64, T=80, G=4)
    G, range_ms = 4, 20 * START_STEP
    wends = make_window_ends(25 * START_STEP, 70 * START_STEP,
                             5 * START_STEP)
    plan = build_plan(ts_row, wends, range_ms)
    reb, vbase = rebase_values(raw, False)
    v32, vb32 = reb.astype(np.float32), vbase.astype(np.float32)
    prep = pad_inputs(v32, vb32, gids, plan, G)
    a, ca = fused_rate_groupsum(v32, vb32, gids, plan, G, interpret=True)
    b, cb = fused_rate_groupsum(None, None, None, plan, G, interpret=True,
                                prepared=prep)
    np.testing.assert_allclose(np.asarray(a), np.asarray(b))
    np.testing.assert_array_equal(ca, cb)


def test_can_fuse_gate():
    assert can_fuse("rate", "sum", True, True)
    assert can_fuse("increase", "sum", True, True)
    assert can_fuse("rate", "avg", True, True)        # r3: broadened aggs
    assert can_fuse("rate", "min", True, True)
    assert can_fuse("rate", "count", True, True)
    assert not can_fuse("rate", "stddev", True, True)
    assert can_fuse("sum_over_time", "sum", True, True)
    assert can_fuse("avg_over_time", "sum", True, True)
    assert can_fuse("min_over_time", "sum", True, True)  # reduce_window
    assert can_fuse("count_over_time", "max", True, True)
    assert not can_fuse("rate", "sum", False, True)   # no shared grid
    # r4: the whole fusable set takes ragged rows (valid-boundary scans
    # for the rate family, validity one-hot for last_over_time)
    assert can_fuse("rate", "sum", True, False)
    assert can_fuse("increase", "avg", True, False)
    assert can_fuse("delta", "sum", True, False)
    assert can_fuse("sum_over_time", "sum", True, False)
    assert can_fuse("min_over_time", "avg", True, False)
    assert can_fuse("last_over_time", "sum", True, False)


@pytest.mark.parametrize("fn", ["sum_over_time", "avg_over_time"])
def test_fused_over_time_single_sample_windows(fn):
    """Windows containing exactly one sample must return that sample's
    contribution, not the bare vbase (n=1 band coverage regression)."""
    S, T, G = 8, 40, 2
    ts_row = np.arange(T, dtype=np.int64) * 10_000
    rng = np.random.default_rng(5)
    raw = 100.0 + rng.random((S, T))
    gids = (np.arange(S) % G).astype(np.int32)
    range_ms = 15_000                    # < 2 scrape intervals: n is 1 or 2
    wends = make_window_ends(5_000, 380_000, 10_000)
    plan = build_plan(ts_row, wends, range_ms)
    assert (np.asarray(plan.n1)[0, :len(wends)] == 1).any(), \
        "test needs single-sample windows"
    reb, vbase = rebase_values(raw, False)
    sums, counts = fused_rate_groupsum(
        reb.astype(np.float32), vbase.astype(np.float32), gids, plan, G,
        fn_name=fn, interpret=True)
    got = present_sum(sums, counts)
    want = _xla_overtime(ts_row, reb.astype(np.float32),
                         vbase.astype(np.float32), gids, wends, range_ms,
                         fn, G)
    assert (np.isnan(got) == np.isnan(want)).all()
    np.testing.assert_allclose(got, want, rtol=2e-4, atol=1e-3,
                               equal_nan=True)


def _xla_overtime(ts_row, vals32, vbase, gids, wends, range_ms, fn, G):
    S, T = vals32.shape
    ts_off = to_offsets(np.tile(ts_row, (S, 1)), np.full(S, T), 0)
    r = evaluate_range_function(
        jnp.asarray(ts_off), jnp.asarray(vals32),
        jnp.asarray(wends.astype(np.int32)), range_ms, fn,
        shared_grid=True, vbase=jnp.asarray(vbase))
    return np.asarray(agg_ops.aggregate("sum", r, jnp.asarray(gids), G))


# ------------------------- r3 broadened eligibility (VERDICT r2 item 2)

def _general(ts_row, vals32, vbase, gids, wends, range_ms, fn, agg, G,
             precor=False):
    """General XLA path (oracle-verified elsewhere) for any (fn, agg)."""
    S, T = vals32.shape
    ts_off = to_offsets(np.tile(ts_row, (S, 1)), np.full(S, T), 0)
    r = evaluate_range_function(
        jnp.asarray(ts_off), jnp.asarray(vals32),
        jnp.asarray(wends.astype(np.int32)), range_ms, fn,
        shared_grid=True, vbase=jnp.asarray(vbase.astype(np.float32)),
        precorrected=precor)
    return np.asarray(agg_ops.aggregate(agg, r, jnp.asarray(gids), G))


@pytest.mark.parametrize("fn,agg", [
    ("rate", "avg"), ("rate", "min"), ("rate", "max"), ("rate", "count"),
    ("increase", "avg"), ("delta", "max"), ("sum_over_time", "min"),
    ("avg_over_time", "max"), ("last_over_time", "avg")])
def test_fused_leaf_agg_broadened_dense(fn, agg):
    from filodb_tpu.ops.pallas_fused import fused_leaf_agg
    ts_row, raw, gids = _mk(S=96, T=120)
    G, range_ms = 5, 30 * START_STEP
    wends = make_window_ends(40 * START_STEP, 110 * START_STEP,
                             6 * START_STEP)
    plan = build_plan(ts_row, wends, range_ms)
    precor = fn in ("rate", "increase")
    reb, vbase = rebase_values(raw, precor)
    vals32, vb32 = reb.astype(np.float32), vbase.astype(np.float32)
    prep = pad_inputs(vals32, vb32, gids, plan, G)
    comp = fused_leaf_agg(plan, prep, gids, G, fn, agg,
                          precorrected=precor, interpret=True)
    got = np.asarray(agg_ops.present(agg, jnp.asarray(comp)))
    want = _general(ts_row, vals32, vb32, gids, wends, range_ms, fn, agg,
                    G, precor)
    assert (np.isnan(got) == np.isnan(want)).all()
    np.testing.assert_allclose(got, want, rtol=5e-4, atol=1e-3,
                               equal_nan=True)


@pytest.mark.parametrize("fn,agg", [
    ("sum_over_time", "sum"), ("sum_over_time", "min"),
    ("avg_over_time", "avg"), ("avg_over_time", "sum"),
    ("count_over_time", "sum"), ("count_over_time", "count")])
def test_fused_leaf_agg_ragged_nan(fn, agg):
    """Validity-weighted kernel on a shared grid with NaN holes must match
    the general path's NaN semantics exactly."""
    from filodb_tpu.ops.pallas_fused import fused_leaf_agg
    ts_row, raw, gids = _mk(S=64, T=100, resets=False)
    rng = np.random.default_rng(11)
    holes = rng.random(raw.shape) < 0.15
    raw = raw.copy()
    raw[holes] = np.nan
    raw[7, :] = np.nan                   # one fully-absent series
    G, range_ms = 5, 30 * START_STEP
    wends = make_window_ends(40 * START_STEP, 90 * START_STEP,
                             6 * START_STEP)
    plan = build_plan(ts_row, wends, range_ms)
    vals32 = raw.astype(np.float32)
    vb32 = np.zeros(raw.shape[0], np.float32)
    prep = pad_inputs(vals32, vb32, gids, plan, G)
    comp = fused_leaf_agg(plan, prep, gids, G, fn, agg, interpret=True,
                          ragged=True)
    got = np.asarray(agg_ops.present(agg, jnp.asarray(comp)))
    want = _general(ts_row, vals32, vb32, gids, wends, range_ms, fn, agg, G)
    assert (np.isnan(got) == np.isnan(want)).all()
    np.testing.assert_allclose(got, want, rtol=5e-4, atol=1e-3,
                               equal_nan=True)


def test_fused_leaf_agg_ragged_vbase_avg():
    """Ragged avg_over_time with a non-zero vbase must not leak the base
    into absent cells (the `out * pres` guard)."""
    from filodb_tpu.ops.pallas_fused import fused_leaf_agg
    ts_row, raw, gids = _mk(S=32, T=80, resets=False)
    raw = raw + 1e8                      # large absolute values -> rebase
    raw[3, 10:70] = np.nan
    G, range_ms = 4, 30 * START_STEP
    wends = make_window_ends(40 * START_STEP, 75 * START_STEP,
                             6 * START_STEP)
    plan = build_plan(ts_row, wends, range_ms)
    reb, vbase = rebase_values(raw, False)
    vals32, vb32 = reb.astype(np.float32), vbase.astype(np.float32)
    prep = pad_inputs(vals32, vb32, gids, plan, G)
    comp = fused_leaf_agg(plan, prep, gids, G, "avg_over_time", "min",
                          interpret=True, ragged=True)
    got = np.asarray(agg_ops.present("min", jnp.asarray(comp)))
    want = _general(ts_row, vals32, vb32, gids, wends, range_ms,
                    "avg_over_time", "min", G)
    assert (np.isnan(got) == np.isnan(want)).all()
    np.testing.assert_allclose(got, want, rtol=5e-4, atol=1.0,
                               equal_nan=True)


@pytest.mark.parametrize("fn,agg,ragged", [
    ("min_over_time", "sum", False), ("min_over_time", "min", False),
    ("max_over_time", "max", False), ("max_over_time", "avg", True),
    ("min_over_time", "count", True)])
def test_fused_minmax_reduce_window(fn, agg, ragged):
    """The XLA reduce_window path vs the general masked-broadcast path."""
    from filodb_tpu.ops.pallas_fused import (fused_minmax_agg,
                                             uniform_window_geometry)
    ts_row, raw, gids = _mk(S=48, T=100, resets=False)
    if ragged:
        rng = np.random.default_rng(3)
        raw = raw.copy()
        raw[rng.random(raw.shape) < 0.2] = np.nan
    G, range_ms = 5, 30 * START_STEP
    wends = make_window_ends(40 * START_STEP, 90 * START_STEP,
                             6 * START_STEP)
    geom = uniform_window_geometry(ts_row, wends, range_ms)
    assert geom is not None
    f0, stride, width, t_needed = geom
    assert t_needed <= raw.shape[1]
    vals32 = raw.astype(np.float32)
    comp = fused_minmax_agg(jnp.asarray(vals32), None,
                            jnp.asarray(gids), f0, stride, width,
                            len(wends), fn, agg, G, ragged)
    got = np.asarray(agg_ops.present(agg, jnp.asarray(comp)))
    want = _general(ts_row, vals32, np.zeros(raw.shape[0], np.float32),
                    gids, wends, range_ms, fn, agg, G)
    assert (np.isnan(got) == np.isnan(want)).all()
    np.testing.assert_allclose(got, want, rtol=5e-5, atol=1e-3,
                               equal_nan=True)


def test_uniform_window_geometry_gate():
    from filodb_tpu.ops.pallas_fused import uniform_window_geometry
    ts_row = np.arange(100, dtype=np.int64) * 10_000
    wends = make_window_ends(300_000, 900_000, 60_000)
    geom = uniform_window_geometry(ts_row, wends, 300_000)
    assert geom is not None and geom[1] == 6 and geom[2] == 30
    # left-clipped first window -> non-uniform -> None
    wends_bad = make_window_ends(100_000, 900_000, 60_000)
    assert uniform_window_geometry(ts_row, wends_bad, 300_000) is None
    # irregular scrape grid -> None
    ts_bad = ts_row.copy()
    ts_bad[50:] += 3_000
    assert uniform_window_geometry(ts_bad, wends, 300_000) is None
    # step not a multiple of the scrape interval -> None
    wends_frac = make_window_ends(300_000, 900_000, 15_000)
    assert uniform_window_geometry(ts_row, wends_frac, 300_000) is None
    # windows past the end of the grid stay uniform: t_needed says how
    # many NaN-padded columns the caller must supply
    wends_off = make_window_ends(300_000, 1_200_000, 60_000)
    geom_off = uniform_window_geometry(ts_row, wends_off, 300_000)
    assert geom_off is not None and geom_off[3] == 121


def test_fused_minmax_right_edge_padding():
    """Windows hanging past the last sample (end=now) must match the
    general path through the NaN-padded ragged variant."""
    from filodb_tpu.ops.pallas_fused import (fused_minmax_agg,
                                             uniform_window_geometry)
    ts_row, raw, gids = _mk(S=24, T=100, resets=False)
    G, range_ms = 5, 30 * START_STEP
    wends = make_window_ends(40 * START_STEP, 108 * START_STEP,
                             6 * START_STEP)
    geom = uniform_window_geometry(ts_row, wends, range_ms)
    assert geom is not None
    f0, stride, width, t_needed = geom
    assert t_needed > raw.shape[1]
    vals32 = raw.astype(np.float32)
    padded = np.pad(vals32, ((0, 0), (0, t_needed - raw.shape[1])),
                    constant_values=np.nan)
    comp = fused_minmax_agg(jnp.asarray(padded), None, jnp.asarray(gids),
                            f0, stride, width, len(wends),
                            "max_over_time", "sum", G, ragged=True)
    got = np.asarray(agg_ops.present("sum", jnp.asarray(comp)))
    want = _general(ts_row, vals32, np.zeros(raw.shape[0], np.float32),
                    gids, wends, range_ms, "max_over_time", "sum", G)
    assert (np.isnan(got) == np.isnan(want)).all()
    np.testing.assert_allclose(got, want, rtol=5e-5, atol=1e-3,
                               equal_nan=True)


def test_fused_large_ts_offset_precision():
    """ts offsets near the 2^30 guard (f32 ulp there is 64 ms): the
    extrapolation thresholds must stay within tolerance of the f64 oracle
    (round-2 review — previously only ~2.4e6 ms offsets were exercised)."""
    import sys
    sys.path.insert(0, "tests")
    from oracle import eval_series

    S, T, G = 8, 120, 2
    base_off = (1 << 30) - 140 * START_STEP     # ~12.4 days from base
    ts_row = base_off + np.arange(T, dtype=np.int64) * START_STEP
    rng = np.random.default_rng(2)
    raw = np.cumsum(rng.exponential(10.0, size=(S, T)), axis=1)
    gids = (np.arange(S) % G).astype(np.int32)
    range_ms = 30 * START_STEP
    wends = base_off + make_window_ends(40 * START_STEP, 110 * START_STEP,
                                        6 * START_STEP)
    assert wends.max() < (1 << 30)               # inside the eval guard
    plan = build_plan(ts_row, wends, range_ms)
    reb, vbase = rebase_values(raw, True)
    sums, counts = fused_rate_groupsum(
        reb.astype(np.float32), vbase.astype(np.float32), gids, plan, G,
        fn_name="rate", precorrected=True, interpret=True)
    got = present_sum(sums, counts)
    # f64 oracle, group-summed
    want = np.zeros((G, len(wends)))
    for s in range(S):
        want[gids[s]] += eval_series(ts_row, raw[s], wends, range_ms,
                                     "rate")
    np.testing.assert_allclose(got, want, rtol=2e-3, atol=1e-4)


@pytest.mark.parametrize("fn", ["min_over_time", "max_over_time"])
def test_minmax_inf_samples_not_absent(fn):
    """+/-Inf are legal sample values: a window whose valid samples are all
    +Inf must emit +Inf from min/max_over_time, not absent (review r3)."""
    from filodb_tpu.ops.pallas_fused import (fused_minmax_agg,
                                             uniform_window_geometry)
    S, T, G = 4, 60, 2
    ts_row = np.arange(T, dtype=np.int64) * START_STEP
    raw = np.full((S, T), np.inf, np.float32)
    raw[2] = 1.5                         # one finite series
    raw[3, ::2] = np.nan                 # ragged series with inf holes
    raw[3, 1::2] = np.inf
    gids = (np.arange(S) % G).astype(np.int32)
    range_ms = 30 * START_STEP
    wends = make_window_ends(40 * START_STEP, 55 * START_STEP,
                             6 * START_STEP)
    geom = uniform_window_geometry(ts_row, wends, range_ms)
    f0, stride, width, _ = geom
    for agg in ("min", "max"):
        comp = fused_minmax_agg(jnp.asarray(raw), None, jnp.asarray(gids),
                                f0, stride, width, len(wends),
                                fn, agg, G, ragged=True)
        got = np.asarray(agg_ops.present(agg, jnp.asarray(comp)))
        want = _general(ts_row, raw, np.zeros(S, np.float32), gids, wends,
                        range_ms, fn, agg, G)
        assert (np.isnan(got) == np.isnan(want)).all(), (agg, got, want)
        np.testing.assert_allclose(got, want, equal_nan=True)
        # group 1 = {all-inf series, nan/inf series} -> +inf, never NaN
        assert np.isinf(got[1]).all(), got


# --------------------- r4: ragged rate family (VERDICT r3 item 2)

def _mk_ragged_counters(S=64, T=120, G=4, seed=11, hole_frac=0.15,
                        resets_per_series=2):
    """Production-shaped counters: NaN scrape gaps + mid-series restarts."""
    rng = np.random.default_rng(seed)
    ts_row = np.arange(T, dtype=np.int64) * START_STEP
    raw = np.cumsum(rng.exponential(10.0, size=(S, T)), axis=1)
    for s in range(S):
        for r in rng.choice(np.arange(6, T), size=resets_per_series,
                            replace=False):
            raw[s, r:] = raw[s, r:] - raw[s, r - 1] + rng.exponential(5.0)
    raw[rng.random((S, T)) < hole_frac] = np.nan
    gids = (np.arange(S) % G).astype(np.int32)
    return ts_row, raw, gids


def _oracle_group_sum(ts_row, raw, gids, wends, range_ms, fn, G):
    from oracle import eval_series
    per = np.stack([eval_series(ts_row, raw[s], wends, range_ms, fn)
                    for s in range(raw.shape[0])])
    sums = np.zeros((G, len(wends)))
    counts = np.zeros((G, len(wends)))
    for s in range(raw.shape[0]):
        m = ~np.isnan(per[s])
        sums[gids[s], m] += per[s, m]
        counts[gids[s]] += m
    return np.where(counts > 0, sums, np.nan)


@pytest.mark.parametrize("fn,precor", [
    ("rate", False), ("rate", True), ("increase", False),
    ("increase", True), ("delta", False)])
def test_fused_ragged_rate_family_vs_oracle(fn, precor):
    """Ragged counters with resets stay on the one-pass kernel: in-kernel
    fill scans find each series' valid window boundaries and the result
    matches the scalar f64 oracle (NaN slots are absent samples, skipped
    like upstream's range-vector marker filtering)."""
    ts_row, raw, gids = _mk_ragged_counters()
    G = 4
    range_ms = 30 * START_STEP
    wends = make_window_ends(40 * START_STEP, 110 * START_STEP,
                             6 * START_STEP)
    plan = build_plan(ts_row, wends, range_ms)
    reb, vbase = rebase_values(raw, precor and fn != "delta")
    sums, counts = fused_rate_groupsum(
        reb.astype(np.float32), vbase.astype(np.float32), gids, plan, G,
        fn_name=fn, precorrected=precor, interpret=True, ragged=True)
    got = present_sum(sums, counts)
    want = _oracle_group_sum(ts_row, raw, gids, wends, range_ms, fn, G)
    assert (np.isnan(got) == np.isnan(want)).all()
    np.testing.assert_allclose(got, want, rtol=5e-4, atol=1e-4,
                               equal_nan=True)


def test_general_path_ragged_rate_vs_oracle():
    """dense=False routes the general XLA path onto valid boundaries; the
    result matches the oracle exactly in f64 (including windows whose edge
    slots are NaN holes — previously poisoned to NaN)."""
    from oracle import eval_series
    ts_row, raw, gids = _mk_ragged_counters(S=24, T=90, seed=3)
    range_ms = 30 * START_STEP
    wends = make_window_ends(40 * START_STEP, 80 * START_STEP,
                             4 * START_STEP)
    ts_off = ts_row.astype(np.int32)[None, :]
    for fn in ("rate", "increase", "delta", "irate", "idelta"):
        got = np.asarray(evaluate_range_function(
            jnp.asarray(ts_off), jnp.asarray(raw),
            jnp.asarray(wends.astype(np.int32)), range_ms, fn,
            shared_grid=True, dense=False))
        want = np.stack([eval_series(ts_row, raw[s], wends, range_ms, fn)
                         for s in range(raw.shape[0])])
        assert (np.isnan(got) == np.isnan(want)).all(), fn
        np.testing.assert_allclose(got, want, rtol=1e-9, atol=1e-12,
                                   equal_nan=True, err_msg=fn)


def test_general_path_dense_flag_degenerates_on_dense_data():
    """On hole-free data the valid-boundary variant must equal the slot
    variant bit-for-bit."""
    ts_row, raw, gids = _mk(S=16, T=80, G=2, resets=True, seed=9)
    range_ms = 20 * START_STEP
    wends = make_window_ends(25 * START_STEP, 75 * START_STEP,
                             5 * START_STEP)
    ts_off = ts_row.astype(np.int32)[None, :]
    for fn in ("rate", "irate", "idelta"):
        a = np.asarray(evaluate_range_function(
            jnp.asarray(ts_off), jnp.asarray(raw),
            jnp.asarray(wends.astype(np.int32)), range_ms, fn,
            shared_grid=True, dense=True))
        b = np.asarray(evaluate_range_function(
            jnp.asarray(ts_off), jnp.asarray(raw),
            jnp.asarray(wends.astype(np.int32)), range_ms, fn,
            shared_grid=True, dense=False))
        np.testing.assert_array_equal(a, b, err_msg=fn)


def test_fused_ragged_last_over_time_slot_semantics():
    """last_over_time keeps SLOT semantics on ragged rows: a NaN in the
    newest in-window slot is a staleness marker (absent), not a hole to
    skip — matching the general path."""
    S, T, G = 16, 60, 2
    rng = np.random.default_rng(7)
    ts_row = np.arange(T, dtype=np.int64) * START_STEP
    raw = 50.0 + rng.random((S, T))
    raw[rng.random((S, T)) < 0.3] = np.nan
    raw[0, :] = np.nan                    # fully-stale series
    gids = (np.arange(S) % G).astype(np.int32)
    range_ms = 5 * START_STEP
    wends = make_window_ends(10 * START_STEP, 55 * START_STEP,
                             3 * START_STEP)
    plan = build_plan(ts_row, wends, range_ms)
    reb, vbase = rebase_values(raw, False)
    sums, counts = fused_rate_groupsum(
        reb.astype(np.float32), vbase.astype(np.float32), gids, plan, G,
        fn_name="last_over_time", interpret=True, ragged=True)
    got = present_sum(sums, counts)
    want = _xla_overtime(ts_row, reb.astype(np.float32),
                         vbase.astype(np.float32), gids, wends, range_ms,
                         "last_over_time", G)
    assert (np.isnan(got) == np.isnan(want)).all()
    np.testing.assert_allclose(got, want, rtol=2e-5, atol=1e-4,
                               equal_nan=True)


# ------------- r4: adaptive series block (on-chip scoped-vmem OOM fix)

def test_pick_block_adaptive():
    """Long ragged rate rows shrink the series block instead of being
    rejected: the first on-chip ragged compile OOM'd scoped vmem at
    bs=256, Tp=768 (Mosaic: 21.36M > 16M limit) while the old estimate
    said 13M — the calibrated model must divert THAT shape to a smaller
    block and keep the dense kernel at the full block."""
    from filodb_tpu.ops import pallas_fused as pf
    assert pf._BS == 256
    assert pf.pick_block(768, 128, 1000, "rate_family", False) == pf._BS
    bs = pf.pick_block(768, 128, 1000, "rate_family", True)
    assert bs is not None and bs < pf._BS
    assert pf.vmem_estimate(768, 128, 1000, "rate_family", True,
                            bs=bs) <= pf.VMEM_BUDGET
    # the calibrated model rejects the shape that actually OOM'd on chip
    assert pf.vmem_estimate(768, 128, 1000, "rate_family", True,
                            bs=256) > pf.VMEM_BUDGET
    # tiny shapes keep the full block (interpret-mode tests stay fast)
    assert pf.pick_block(256, 128, 8, "rate_family", True) == pf._BS
    # ISSUE 43 re-read Mosaic's report (tests/test_chip_compile.py): two
    # carriers over a window's steps take 8.77 MiB at 256 rows, so they
    # would fit; the block stays (1.7% on the chip, and other rows a
    # block regroup the f32 group sums), as promchurn-counters-262k.open's
    # ragged phased sets show
    for Gp in (16, 24):
        assert pf.pick_block(768, 128, Gp, "rate_family", True,
                             phased=True) == 128


def test_fused_ragged_rate_long_rows():
    """T=720 (dashboard shape, Tp=768): the ragged kernel runs with a
    shrunken block and still matches the f64 oracle — this is the exact
    shape whose bs=256 compile OOM'd scoped vmem on the real chip."""
    from oracle import eval_series
    S, T, G = 16, 720, 4
    rng = np.random.default_rng(9)
    ts_row = np.arange(T, dtype=np.int64) * START_STEP
    raw = np.cumsum(rng.exponential(10.0, size=(S, T)), axis=1)
    raw[rng.random((S, T)) < 0.1] = np.nan
    gids = (np.arange(S) % G).astype(np.int32)
    range_ms = 300_000
    wends = make_window_ends(600_000, int(ts_row[-1]), 60_000)
    plan = build_plan(ts_row, wends, range_ms)
    reb, vbase = rebase_values(raw, True)
    sums, counts = fused_rate_groupsum(
        reb.astype(np.float32), vbase.astype(np.float32), gids, plan, G,
        fn_name="rate", precorrected=True, interpret=True, ragged=True)
    got = present_sum(sums, counts)
    per = np.stack([eval_series(ts_row, raw[s], wends, range_ms, "rate")
                    for s in range(S)])
    want = np.zeros((G, len(wends)))
    cnt = np.zeros((G, len(wends)))
    for s in range(S):
        m = ~np.isnan(per[s])
        want[gids[s], m] += per[s, m]
        cnt[gids[s]] += m
    want = np.where(cnt > 0, want, np.nan)
    assert (np.isnan(got) == np.isnan(want)).all()
    np.testing.assert_allclose(got, want, rtol=5e-4, atol=1e-4,
                               equal_nan=True)


@pytest.mark.parametrize("mode", ["episplit"])
def test_split_precision_matches_highest_interpret(monkeypatch, mode):
    """The group epilogue's three-pass decomposition
    (ops/pallas_fused._dot_split3) must produce the same results as an
    all-HIGHEST epilogue — in interpret mode, so a future edit that
    breaks its operand-order convention (or _split3 itself) fails here
    instead of only as wrong numbers on the chip.  The reference is the
    same kernel with the epilogue's matmul swapped for _dot_hi; jit
    caches don't key on a module attribute, so they are cleared around
    the swap."""
    import jax
    from filodb_tpu.ops import pallas_fused as pf
    ts_row, raw, gids = _mk(S=48, T=96, G=4)
    G, range_ms = 4, 30 * START_STEP
    wends = make_window_ends(40 * START_STEP, 90 * START_STEP,
                             6 * START_STEP)
    plan = build_plan(ts_row, wends, range_ms)
    reb, vbase = rebase_values(raw, True)
    vals32 = reb.astype(np.float32)
    vb32 = vbase.astype(np.float32)
    ragged_vals = vals32.copy()
    ragged_vals[np.random.default_rng(5).random(vals32.shape) < 0.2] = np.nan

    def run_all():
        out = []
        for vals, ragged in ((vals32, False), (ragged_vals, True)):
            sums, counts = fused_rate_groupsum(
                vals, vb32, gids, plan, G, fn_name="rate",
                precorrected=True, interpret=True, ragged=ragged)
            out.append(present_sum(sums, counts))
        return out

    monkeypatch.setattr(pf, "_dot_split3", pf._dot_hi)
    jax.clear_caches()
    try:
        base = run_all()
        monkeypatch.undo()
        jax.clear_caches()
        split = run_all()
    finally:
        monkeypatch.undo()
        jax.clear_caches()
    for b, s in zip(base, split):
        assert (np.isnan(b) == np.isnan(s)).all()
        np.testing.assert_allclose(s, b, rtol=1e-5, atol=1e-6,
                                   equal_nan=True)


# ------------- one kernel strategy, chosen by nothing outside the kernel

_JAXPR_PROBE = """
import numpy as np, jax
from filodb_tpu.ops import pallas_fused as pf
S, T, G, step = 40, 96, 4, 10_000
ts_row = np.arange(T, dtype=np.int64) * step
plan = pf.build_plan(ts_row, np.arange(40, 91, 6, dtype=np.int64) * step,
                     30 * step)
gids = (np.arange(S) % G).astype(np.int32)
for fn in ("rate", "sum_over_time"):
    print(jax.make_jaxpr(lambda v: pf.fused_rate_groupsum(
        v, np.zeros(S, np.float32), gids, plan, G, fn, precorrected=True,
        interpret=True)[0])(np.zeros((S, T), np.float32)))
"""


@functools.lru_cache(maxsize=None)
def _fused_jaxprs(name=None, value=None):
    """The jaxprs of one small interpret-mode `rate` and `sum_over_time`
    dispatch (padding, kernel operands and kernel body included), traced
    in a fresh process whose environment holds `name=value` before the
    import."""
    import subprocess
    import sys
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env = {k: v for k, v in os.environ.items()
           if not k.startswith("FILODB_FUSED_")}
    env.update(PYTHONPATH=repo, JAX_PLATFORMS="cpu")
    if name:
        env[name] = value
    p = subprocess.run([sys.executable, "-c", _JAXPR_PROBE], cwd=repo,
                       env=env, capture_output=True, text=True, timeout=300)
    assert p.returncode == 0, p.stderr[-2000:]
    return p.stdout


@pytest.mark.parametrize("name,value", [
    ("FILODB_FUSED_GATHER", "0"),
    ("FILODB_FUSED_PRECISION", "split"),
    ("FILODB_FUSED_BS", "64"),
])
def test_no_kernel_strategy_comes_from_the_environment(name, value):
    """The selection strategy, the matmul precision and the series block
    were once read from these variables at import; the program a
    dispatch traces must be the same whether or not they are set."""
    base = _fused_jaxprs()
    assert "pallas_call" in base
    assert _fused_jaxprs(name, value) == base


# ------------- one fused dispatch = one jit call (ISSUE 28, ROADMAP A1)

def _host_selection_matrices(ts_row, wends, range_ms):
    """o1, o2, l1, l2 as the host built them before ISSUE 28
    (`build_plan.sel`): the reference the in-jit construction must equal
    bit for bit."""
    from filodb_tpu.ops.pallas_fused import _LANE, _pad_to, window_counts
    ts_row = np.asarray(ts_row, np.int64)
    wend = np.asarray(wends, np.int64)
    first = np.searchsorted(ts_row, wend - int(range_ms) + 1, side="left")
    last = np.searchsorted(ts_row, wend, side="right") - 1
    valid = window_counts(ts_row, wend, range_ms) >= 1
    W, T = len(wend), len(ts_row)
    Wp, Tp = _pad_to(max(W, 1), _LANE), _pad_to(max(T, 1), _LANE)

    def sel(idx, leq):
        m = np.zeros((Tp, Wp), np.float32)
        t = np.arange(Tp)[:, None]
        iw = np.where(valid, np.clip(idx, 0, T - 1), -1)[None, :]
        m[:, :W] = ((t <= iw) if leq else (t == iw)).astype(np.float32)
        return m

    return sel(first, False), sel(last, False), sel(first, True), \
        sel(last, True)


@pytest.mark.parametrize("T,wends,range_ms", [
    (160, np.arange(40, 151, 6) * START_STEP, 30 * START_STEP),
    # windows before the data, between samples (empty) and on the grid
    (130, np.concatenate([[-50 * START_STEP, 3, START_STEP + 1],
                          np.arange(2, 120, 9) * START_STEP + 5]),
     START_STEP // 2),
    # windows hanging past the grid's right edge, the last ones empty
    (100, np.arange(90, 140, 4) * START_STEP, 7 * START_STEP),
    # two window tiles (W > 128) over three time tiles
    (300, np.arange(1, 300, 2) * START_STEP, 12 * START_STEP),
], ids=["mid", "empty-and-padded", "past-right-edge", "two-window-tiles"])
def test_kernel_operands_equal_host_selection_matrices(T, wends, range_ms):
    """The matrices `_run` builds on the device from idx1 / idx2 / n1 are
    the 0/1 f32 arrays the host used to upload, bit for bit; the gather
    kinds build none; `n` and `tsrow` resolve as the host resolved them."""
    import jax
    from filodb_tpu.ops import pallas_fused as pf
    ts_row = np.arange(T, dtype=np.int64) * START_STEP
    plan = build_plan(ts_row, wends, range_ms)
    assert plan.rows.shape == (8, plan.t1.shape[1])
    assert plan.rows.nbytes + plan.tsrow.nbytes < 16 << 10
    for i, f in enumerate(("t1", "t2", "n", "n1", "wstart_x", "wend_x",
                           "idx1", "idx2")):
        assert np.shares_memory(getattr(plan, f), plan.rows)
        np.testing.assert_array_equal(getattr(plan, f)[0], plan.rows[i])
    want = _host_selection_matrices(ts_row, wends, range_ms)
    assert (plan.n1[0] == 0).any()                   # padded windows
    build = jax.jit(pf.kernel_operands, static_argnums=(2, 3))
    ops = build(plan.rows, plan.tsrow, plan.Tp, "sum_over_time")
    for got, ref in zip(ops[:4], want):
        got = np.asarray(got)
        assert got.dtype == np.float32 and got.shape == ref.shape
        assert got.tobytes() == ref.tobytes()
    np.testing.assert_array_equal(np.asarray(ops[6]), plan.n1)
    np.testing.assert_array_equal(np.asarray(ops[9]), plan.tsrow)
    for got, f in zip(ops[4:6] + ops[7:9] + ops[10:],
                      ("t1", "t2", "wstart_x", "wend_x", "idx1", "idx2")):
        np.testing.assert_array_equal(np.asarray(got), getattr(plan, f))
    ops = build(plan.rows, None, plan.Tp, "rate_family")
    assert all(o.shape == (8, 128) and not np.asarray(o).any()
               for o in ops[:4])
    np.testing.assert_array_equal(np.asarray(ops[6]), plan.n)
    assert ops[9].shape == plan.tsrow.shape and not np.asarray(ops[9]).any()


def _enqueue_counts():
    from filodb_tpu.utils.metrics import registry
    return (registry.counter("fused_enqueues").value,
            registry.counter("fused_enqueue_uploads").value)


def _batch_case(fn, aggs, ragged=False, S=96, T=120, hist_buckets=0):
    """(plan, values, panels, check): `aggs` panels over one working set;
    check(comps) holds every panel to the general XLA path."""
    from filodb_tpu.ops.pallas_fused import pad_groups, pad_values
    if ragged and fn in ("rate", "increase", "delta"):
        ts_row, raw, _ = _mk_ragged_counters(S=S, T=T)
    else:
        ts_row, raw, _ = _mk(S=S, T=T, resets=not ragged)
        if ragged:
            raw = raw.copy()
            raw[np.random.default_rng(3).random(raw.shape) < 0.15] = np.nan
    range_ms = 30 * START_STEP
    wends = make_window_ends(40 * START_STEP, (T - 10) * START_STEP,
                             6 * START_STEP)
    plan = build_plan(ts_row, wends, range_ms)
    precor = fn in ("rate", "increase")
    if ragged:
        vals32 = raw.astype(np.float32)
        vb32 = np.zeros(S, np.float32)
    else:
        reb, vbase = rebase_values(raw, precor)
        vals32, vb32 = reb.astype(np.float32), vbase.astype(np.float32)
    values = pad_values(vals32, vb32, plan)
    Gs = [5, 1, 12, 7]
    panels, gid_list = [], []
    for i, agg in enumerate(aggs):
        G = Gs[i % len(Gs)]
        gids = (np.arange(S) % G).astype(np.int32)
        if hist_buckets:
            # histogram leaf: one kernel slot per (group, bucket)
            gids = gids * hist_buckets + np.arange(S) % hist_buckets
            G *= hist_buckets
        gid_list.append((gids.astype(np.int32), G))
        panels.append((pad_groups(gids, S, G), G, agg))

    def check(comps):
        assert len(comps) == len(aggs)
        for comp, agg, (gids, G) in zip(comps, aggs, gid_list):
            got = np.asarray(agg_ops.present(agg, jnp.asarray(comp)))
            if ragged and fn in ("rate", "increase", "delta"):
                assert agg == "sum"
                want = _oracle_group_sum(ts_row, raw, gids, wends, range_ms,
                                         fn, G)
            else:
                want = _general(ts_row, vals32, vb32, gids, wends, range_ms,
                                fn, agg, G, precor)
            assert (np.isnan(got) == np.isnan(want)).all()
            np.testing.assert_allclose(got, want, rtol=5e-4, atol=1e-3,
                                       equal_nan=True)

    return plan, values, panels, dict(fn_name=fn, precorrected=precor and
                                      not ragged, interpret=True,
                                      ragged=ragged, num_series=S), check


@pytest.mark.parametrize("fn,aggs,ragged,uploads", [
    ("rate", ["sum"], False, 1),
    ("rate", ["sum", "avg", "sum"], False, 2),
    ("sum_over_time", ["sum"], False, 1),
    ("sum_over_time", ["sum", "avg", "sum"], False, 2),
    ("rate", ["sum"], True, 2),            # the ragged rate family reads tsrow
    ("rate", ["min"], False, 1),           # per-series mode + segment jit
], ids=["rate-1p", "rate-3p", "sum_ot-1p", "sum_ot-3p", "rate-ragged-1p",
        "rate-min-1p"])
def test_enqueue_is_explicit_uploads_and_one_call(fn, aggs, ragged, uploads):
    """One lazy fused_leaf_agg_batch call makes no implicit host-to-device
    transfer (5 before ISSUE 28), and puts at most the plan's rows (+ the
    offsets of a merged batch, + tsrow where the kernel reads it): booked
    on fused_enqueue_uploads_total beside fused_enqueues_total.  A fresh
    plan uploads them (13 puts before ISSUE 28); a repeated one holds its
    own on the device and brings only the call's offsets (ISSUE 41)."""
    import jax
    from filodb_tpu.ops.pallas_fused import fused_leaf_agg_batch
    plan, values, panels, kw, check = _batch_case(fn, aggs, ragged)
    merged = sum(a in ("sum", "avg") for a in aggs) > 1
    for want in (uploads, int(merged)):     # fresh plan, then the same one
        e0, u0 = _enqueue_counts()
        with jax.transfer_guard_host_to_device("disallow"):
            finisher = fused_leaf_agg_batch(plan, values, panels, lazy=True,
                                            **kw)
        e1, u1 = _enqueue_counts()
        assert e1 - e0 == 1
        assert u1 - u0 == want <= 2
        check(finisher())


@pytest.mark.parametrize("fn,aggs,ragged,hist", [
    ("rate", ["sum", "avg", "count", "sum"], False, 0),
    ("sum_over_time", ["sum", "avg", "sum"], False, 0),
    ("sum_over_time", ["sum", "avg", "count"], True, 0),
    ("rate", ["sum", "sum", "sum"], True, 0),
    ("rate", ["sum", "sum"], False, 4),
    ("rate", ["min", "max", "sum", "max"], False, 0),
    ("avg_over_time", ["max", "avg", "min"], True, 0),
], ids=["dense-rate", "dense-sum_ot", "ragged-sum_ot", "ragged-rate",
        "histogram-slots", "minmax-rate", "minmax-ragged-avg_ot"])
def test_fused_batch_merged_in_jit_matches_general(fn, aggs, ragged, hist):
    """Several panels merged INSIDE the one jit call (gid columns as a
    tuple operand, offsets traced) give every panel the general path's
    answer: dense, ragged, histogram (group, bucket) slots, and min/max
    panels riding the per-series run."""
    from filodb_tpu.ops.pallas_fused import fused_leaf_agg_batch
    plan, values, panels, kw, check = _batch_case(fn, aggs, ragged,
                                                  hist_buckets=hist)
    check(fused_leaf_agg_batch(plan, values, panels, **kw))


def test_merge_gid_cols_identity_and_traced_offsets():
    """One gid operand passes through untouched; several merge to the
    matrix the eager host merge built; and a batch with other group
    counts under the same padded total compiles nothing new."""
    from filodb_tpu.ops import pallas_fused as pf
    S = 70
    cols = [pf.pad_groups((np.arange(S) % g).astype(np.int32), S, g).gids_p
            for g in (5, 1, 12)]
    assert pf.merge_gid_cols((cols[0],), None) is cols[0]
    offs = np.array([0, 5, 6], np.int32)
    got = np.asarray(pf.merge_gid_cols(tuple(cols), jnp.asarray(offs)))
    want = np.stack([np.where(np.asarray(c)[:, 0] >= 0,
                              np.asarray(c)[:, 0] + o, -1)
                     for c, o in zip(cols, offs)], axis=1)
    np.testing.assert_array_equal(got, want)
    assert got.dtype == np.int32 and (got[S:] == -1).all()

    plan, values, panels, kw, _ = _batch_case("rate", ["sum"] * 3)
    pf.fused_leaf_agg_batch(plan, values, panels, **kw)     # 5 + 1 + 12
    compiled = pf._run._cache_size()
    other = [(pf.pad_groups((np.arange(96) % g).astype(np.int32), 96, g),
              g, "sum") for g in (6, 2, 11)]                # same Gp = 24
    pf.fused_leaf_agg_batch(plan, values, other, **kw)
    assert pf._run._cache_size() == compiled


# ------------------------------------------- one call for several working sets
# (ISSUE 36: a request's shard leaves share a plan and a device, so their
# group-mode runs are ONE `_run` call and ONE readback)

def _sets_case(fn, ragged, specs):
    """[(values, panels)] over ONE plan object, one working set a spec
    (S, aggs, hist_buckets): mixed row rungs, group counts and panel
    counts, as a request's shards bring them."""
    plan, kw, sets = None, None, []
    for S, aggs, hist in specs:
        p, values, panels, kw, _ = _batch_case(fn, aggs, ragged, S=S,
                                               hist_buckets=hist)
        plan = plan or p            # same grid: the rows are equal
        assert p.rows.tobytes() == plan.rows.tobytes()
        sets.append((values, panels))
    return plan, sets, kw


def _dispatch_sets(plan, sets, kw, order=None):
    """The sets through ONE FusedDispatch, as fusedbatch runs them: every
    set added, one enqueue, then each set's finisher."""
    from filodb_tpu.ops import pallas_fused as pf
    flavor = {k: kw[k] for k in ("precorrected", "interpret", "ragged")}
    disp = pf.FusedDispatch(plan, kw["fn_name"], **flavor)
    order = list(order or range(len(sets)))
    fins = {k: pf.fused_leaf_agg_batch(plan, *sets[k], lazy=True,
                                       dispatch=disp, **kw) for k in order}
    disp.enqueue()
    return [fins[k]() for k in range(len(sets))], disp


_SETS = {
    "dense-rate-mixed-rungs": ("rate", False, [
        (96, ["sum"], 0), (300, ["sum", "avg"], 0), (600, ["sum"], 0),
        (96, ["avg", "sum", "sum", "sum"], 0)]),
    "dense-sum_ot-with-host-counts": ("sum_over_time", False, [
        (96, ["sum", "count"], 0), (300, ["avg"], 0)]),
    "ragged-sum_ot": ("sum_over_time", True, [
        (96, ["sum", "avg", "count"], 0), (300, ["count"], 0),
        (96, ["sum"], 0)]),
    "ragged-rate": ("rate", True, [(64, ["sum"], 0),
                                   (300, ["sum", "sum"], 0)]),
    "histogram-slots-mixed-gp": ("rate", False, [
        (96, ["sum"], 4), (300, ["sum", "sum"], 16), (96, ["sum"], 0)]),
    "minmax-ride-beside": ("rate", False, [
        (96, ["min", "sum"], 0), (300, ["max", "avg", "sum"], 0)]),
    "one-set-alone": ("rate", False, [(96, ["sum", "avg"], 0)]),
}


@pytest.mark.parametrize("case", sorted(_SETS))
def test_sets_in_one_call_are_bit_identical_to_a_call_each(case):
    """Every panel's partial out of the merged call equals, bit for bit,
    what its working set's own call returns; the merged call books ONE
    enqueue for its group-mode sets (a min/max set adds its per-series
    run, as it did alone) and the sets it carried."""
    from filodb_tpu.ops import pallas_fused as pf
    from filodb_tpu.utils.metrics import registry
    fn, ragged, specs = _SETS[case]
    plan, sets, kw = _sets_case(fn, ragged, specs)
    want = [pf.fused_leaf_agg_batch(plan, v, p, **kw) for v, p in sets]
    e0, _ = _enqueue_counts()
    s0 = registry.counter("fused_enqueue_sets").value
    got, disp = _dispatch_sets(plan, sets, kw)
    per_series = sum(any(op in ("min", "max") for _, _, op in p)
                     for _, p in sets)
    assert _enqueue_counts()[0] - e0 == 1 + per_series
    assert registry.counter("fused_enqueue_sets").value - s0 \
        == len(disp) + per_series
    assert len(disp) == len(sets)
    for w_set, g_set, (_, panels) in zip(want, got, sets):
        assert len(w_set) == len(g_set) == len(panels)
        for w, g in zip(w_set, g_set):
            assert g.dtype == w.dtype == np.float64 and g.shape == w.shape
            assert g.tobytes() == w.tobytes()


def test_leaf_order_adds_no_entry_to_the_trace_cache():
    """The sets of a call are ordered by shape before the jit call: the
    same working sets prepared in another order are the same program, and
    every set still gets its own rows back."""
    from filodb_tpu.ops import pallas_fused as pf
    plan, sets, kw = _sets_case("rate", False, [
        (300, ["sum"], 0), (96, ["sum", "avg"], 0), (600, ["sum"], 0),
        (96, ["sum"], 4), (300, ["avg"], 0)])
    first, _ = _dispatch_sets(plan, sets, kw)
    compiled = pf._run._cache_size()
    for order in ([4, 3, 2, 1, 0], [2, 0, 4, 1, 3]):
        again, _ = _dispatch_sets(plan, sets, kw, order=order)
        assert pf._run._cache_size() == compiled, order
        for a_set, f_set in zip(again, first):
            for a, f in zip(a_set, f_set):
                assert a.tobytes() == f.tobytes()


def test_a_dispatch_is_not_shared_across_plans_or_flavors():
    from filodb_tpu.ops import pallas_fused as pf
    plan, sets, kw = _sets_case("rate", False, [(96, ["sum"], 0)])
    other = pf.FusedDispatch(plan, "increase", kw["precorrected"],
                             kw["interpret"], kw["ragged"])
    with pytest.raises(ValueError, match="shared across"):
        pf.fused_leaf_agg_batch(plan, *sets[0], dispatch=other, **kw)
    empty = pf.FusedDispatch(plan, "rate", kw["precorrected"],
                             kw["interpret"], kw["ragged"])
    e0, _ = _enqueue_counts()
    empty.enqueue()                          # nothing added: no call
    assert _enqueue_counts()[0] == e0 and len(empty) == 0


@pytest.mark.parametrize("fn,ragged", [("rate", False), ("sum_over_time", True),
                                       ("rate", True)],
                         ids=["dense", "ragged-sum_ot", "ragged-rate"])
def test_the_one_presentation_is_the_per_panel_formula(fn, ragged):
    """The whole-array presentation (f32 sums x present mask and the
    counts, widened into one f64 block) gives every panel what the
    per-panel finisher computed from a raw `_run` call: f64 sums masked
    by `counts > 0`, stacked beside the f64 counts."""
    from filodb_tpu.ops import pallas_fused as pf
    plan, sets, kw = _sets_case(fn, ragged, [(96, ["sum", "avg"], 0),
                                             (300, ["sum"], 4)])
    got, _ = _dispatch_sets(plan, sets, kw)
    flags = pf._flavor(plan, kw["fn_name"], kw["precorrected"], True, ragged)
    wvalid = plan.wvalid1 if flags.kind in pf.OVER_TIME_FNS else plan.wvalid
    for (values, panels), g_set in zip(sets, got):
        for (groups, G, _), g in zip(panels, g_set):
            res = pf.run_kernel(
                values.vals_p, values.vbase_p, groups.gids_p, plan.rows,
                plan.tsrow if ragged and flags.kind == "rate_family" else None,
                num_groups=pf.pad_group_count(G), **flags._asdict())
            if ragged:
                sums, counts = (np.asarray(r, np.float64)[:G, :plan.W]
                                for r in res)
            else:
                sums = np.asarray(res, np.float64)[:G, :plan.W]
                counts = groups.gsize[:, None].astype(np.float64) \
                    * wvalid[None, :].astype(np.float64)
            want = np.stack([sums * (counts > 0), counts], axis=-1)
            assert g.dtype == want.dtype and g.shape == want.shape
            assert g.tobytes() == want.tobytes()


# ------- ISSUE 43: the ragged rate family's fills reach a window's width

REACH_SPAN, REACH_T = 30, 120          # [5m] of a 10 s grid; Tp 128


def _reach_rows(kind, span=REACH_SPAN, T=REACH_T, S=8, seed=3):
    """[S, T] monotone counters (precorrected: no reset), each row holed
    as `kind` says, at a place of its own."""
    rng = np.random.default_rng(seed)
    raw = 1e5 + np.cumsum(rng.integers(1, 50, (S, T)).astype(float), axis=1)
    for s in range(S):
        a = int(rng.integers(span, T - span))
        if kind.startswith("hole-"):
            raw[s, a:a + int(kind[5:])] = np.nan
        elif kind == "born-late":       # after the first window has closed
            raw[s, :a + 5] = np.nan
        elif kind == "ended-early":     # before the last window opens
            raw[s, a - 5:] = np.nan
        elif kind == "one-sample":
            raw[s, :a], raw[s, a + 1:] = np.nan, np.nan
        elif kind == "none":
            raw[s] = np.nan
        elif kind == "random":
            raw[s, rng.random(T) < 0.1 * (s + 1)] = np.nan
    return raw


def _reach_plan(range_ms=REACH_SPAN * START_STEP, T=REACH_T, stride=3):
    ts_row = np.arange(T, dtype=np.int64) * START_STEP
    # ends off the slots, from before the row's first window to past its last
    wends = np.arange(2 * START_STEP + 4_000, (T + 6) * START_STEP,
                      stride * START_STEP)
    return ts_row, wends, build_plan(ts_row, wends, range_ms)


def _reach_phase(S, phased, seed=5):
    if not phased:
        return None
    phase = np.random.default_rng(seed).integers(1, START_STEP, S)
    phase[0], phase[1] = 0, START_STEP - 1
    return phase


def _ragged_launch(plan, raw, phase, fn="rate", steps=None, gids=None):
    """One ragged rate-family launch (a group a row unless `gids` says
    otherwise) at `steps` doubling steps, None being the plan's own:
    -> (sums, presence) [G, W] f32, as the device returned them."""
    from filodb_tpu.ops import pallas_fused as pf
    S = raw.shape[0]
    gids = np.arange(S) if gids is None else gids
    G = int(gids.max()) + 1
    vbase = np.where(np.isnan(raw), np.inf, raw).min(axis=1)
    vbase = np.where(np.isfinite(vbase), vbase, 0.0)
    prepared = pad_inputs((raw - vbase[:, None]).astype(np.float32),
                          vbase.astype(np.float32), gids.astype(np.int32),
                          plan, G, phase=phase)
    flags = pf._flavor(plan, fn, True, True, True, phase is not None)
    if steps is not None:
        flags = flags._replace(steps=steps)
    res, _ = pf._enqueue_run(
        plan, None, (pf._kernel_set(prepared, (prepared.gids_p,)),), None,
        (pf.pad_group_count(G),), **flags._asdict())
    return tuple(np.asarray(r)[:G, :plan.W] for r in res)


def _full_reach(plan):
    return (plan.Tp - 1).bit_length()


_PHASED = pytest.mark.parametrize("phased", [False, True],
                                  ids=["shared-row", "phase-grid"])


@_PHASED
@pytest.mark.parametrize("kind", [
    "hole-1", "hole-6", f"hole-{REACH_SPAN - 1}", "born-late", "ended-early",
    "one-sample", "none", "random"])
def test_fills_as_far_as_a_window_equal_fills_across_the_row(kind, phased):
    """(a) A launch whose fills take the plan's steps (5: a `[5m]` window
    is 30 slots, 31 on a phase grid) returns what the same launch returns
    at the reach of the whole row (7 at 128 columns), bit for bit."""
    from filodb_tpu.ops import pallas_fused as pf
    _, _, plan = _reach_plan()
    raw = _reach_rows(kind)
    phase = _reach_phase(len(raw), phased)
    assert pf.scan_steps(plan, "rate_family", True, phased) == 5
    assert _full_reach(plan) == 7
    got = _ragged_launch(plan, raw, phase)
    want = _ragged_launch(plan, raw, phase, steps=_full_reach(plan))
    for g, w in zip(got, want):
        assert np.array_equal(g, w)
        assert not np.isnan(g).any()
    if kind in ("one-sample", "none"):
        assert not got[1].any()         # a rate takes two samples
    else:
        assert got[1].any()


@_PHASED
@pytest.mark.parametrize("fn", ["rate", "delta"])
def test_a_hole_longer_than_the_window_leaves_it_absent(fn, phased):
    """(b) A hole of 36 slots under windows of 30: the windows inside it
    are absent, every other is the oracle's and the full reach's."""
    import oracle
    ts_row, wends, plan = _reach_plan(stride=1)
    raw = _reach_rows(f"hole-{REACH_SPAN + 6}")
    phase = _reach_phase(len(raw), phased)
    got = _ragged_launch(plan, raw, phase, fn)
    want = _ragged_launch(plan, raw, phase, fn, steps=_full_reach(plan))
    ph = np.zeros(len(raw), int) if phase is None else phase
    ref = np.stack([oracle.eval_series(ts_row + ph[s], raw[s], wends,
                                       REACH_SPAN * START_STEP, fn)
                    for s in range(len(raw))])
    for g, w in zip(got, want):
        assert np.array_equal(g, w)
    sums, pres = got
    np.testing.assert_array_equal(pres, ~np.isnan(ref))
    for s in range(len(raw)):
        hole = np.flatnonzero(np.isnan(raw[s]))
        first = np.searchsorted(ts_row + ph[s], wends - REACH_SPAN
                                * START_STEP + 1, side="left")
        last = np.searchsorted(ts_row + ph[s], wends, side="right") - 1
        inside = (first >= hole[0]) & (last <= hole[-1]) & (last >= first)
        assert inside.sum() >= 5 and not pres[s, inside].any()
        # ... and its neighbours on either side are there
        w0, w1 = np.flatnonzero(inside)[[0, -1]]
        assert pres[s, w0 - 3] and pres[s, w1 + 3]
    np.testing.assert_allclose(np.where(pres > 0, sums, np.nan), ref,
                               rtol=2e-5, atol=1e-6, equal_nan=True)


@_PHASED
@pytest.mark.parametrize("fn", ["rate", "increase", "delta"])
def test_an_all_nan_row_adds_nothing_and_no_output_is_nan(fn, phased):
    """(c) Rows without a sample (a mesh pack's pad rows, a target that
    never answered) ride in a group beside live rows and add 0 to its sum
    and 0 to its presence: the groups read what they read with those rows
    in a group of their own, and no cell of either output is NaN."""
    _, _, plan = _reach_plan()
    raw = _reach_rows("random", S=12)
    raw[[2, 5, 6, 11]] = np.nan
    phase = _reach_phase(len(raw), phased)
    among = np.arange(len(raw)) % 2
    apart = np.where(np.isnan(raw).all(axis=1), 2, among)
    got = _ragged_launch(plan, raw, phase, fn, gids=among)
    want = _ragged_launch(plan, raw, phase, fn, gids=apart)
    for g, w in zip(got, want):
        assert not np.isnan(g).any() and not np.isnan(w).any()
        assert np.array_equal(g, w[:2])
        assert not w[2].any()
    assert 0 < got[1].max() <= (among == 0).sum() - 2


@pytest.mark.parametrize("T,range_ms,phased,want", [
    (720, 300_000, False, 5),       # [5m] of a 10 s grid: 30 slots
    (720, 300_000, True, 5),        # ... 31 with the early slot
    (720, 320_000, False, 5),       # 32 slots: 2**5 - 1 still crosses
    (720, 320_000, True, 6),        # 33 do not
    (720, 3_600_000, False, 9),     # [1h]: 360
    (720, 3_600_000, True, 9),
    (720, 7_000_000, False, 10),    # 700 slots, past 512
    (720, 9_000_000, True, 10),     # a range past the row: the row's reach
    (120, 9_000_000, False, 7),
    (120, 10_000, True, 1),         # one slot a window, two with the early
    (120, 10_000, False, 0),
], ids=lambda v: str(v))
def test_scan_steps_follow_the_plans_widest_window(T, range_ms, phased, want):
    """(d) The least j with 2**j - 1 >= span - 1, capped at what crosses
    the row; the launch's signature names it."""
    from filodb_tpu.ops import pallas_fused as pf
    ts_row = np.arange(T, dtype=np.int64) * START_STEP
    wends = np.arange(4_000, (T + 6) * START_STEP, 6 * START_STEP)
    plan = build_plan(ts_row, wends, range_ms)
    assert plan.span == pf.window_counts(ts_row, wends, range_ms).max()
    flags = pf._flavor(plan, "rate", True, True, True, phased)
    assert flags.steps == want == pf.scan_steps(plan, "rate_family", True,
                                                phased)
    sets = ((np.zeros((256, plan.Tp)),),)
    sig = pf._run_shape_sig(sets, plan, (8,), flags.kind, True, phased,
                            flags.steps)
    assert (f":{want}steps" in sig) == (want > 0)


@pytest.mark.parametrize("fn,ragged,phased", [
    ("rate", False, False), ("rate", False, True), ("delta", False, False),
    ("sum_over_time", False, False), ("sum_over_time", True, True),
    ("avg_over_time", True, False), ("count_over_time", True, True),
    ("last_over_time", True, False), ("last_over_time", False, True)])
def test_no_other_flavor_gets_a_compile_key_of_the_windows(fn, ragged,
                                                           phased):
    """(d) Only the ragged rate family reads `steps`: every other flavor
    passes 0 whatever the plan's windows, so a new range compiles nothing
    there, and its signature does not name the steps."""
    from filodb_tpu.ops import pallas_fused as pf
    sigs = set()
    for range_ms in (300_000, 3_600_000):
        _, _, plan = _reach_plan(range_ms)
        flags = pf._flavor(plan, fn, True, True, ragged, phased)
        assert flags.steps == 0
        sigs.add(pf._run_shape_sig(((np.zeros((256, plan.Tp)),),), plan,
                                   (8,), flags.kind, ragged, phased,
                                   flags.steps))
    assert len(sigs) == 1 and "steps" not in sigs.pop()


@_PHASED
def test_a_launch_books_its_scan_steps(phased):
    """`fused_ragged_scan_steps_total` moves by the launch's steps, once a
    launch, beside `fused_enqueues_total`; a dense launch moves it by 0."""
    from filodb_tpu.utils.metrics import registry

    def booked():
        return registry.counter("fused_ragged_scan_steps").value

    for range_ms, want in ((300_000, 5), (900_000, 7)):
        _, _, plan = _reach_plan(range_ms)
        raw = _reach_rows("hole-6")
        s0, e0 = booked(), _enqueue_counts()[0]
        _ragged_launch(plan, raw, _reach_phase(len(raw), phased))
        assert booked() - s0 == want
        assert _enqueue_counts()[0] - e0 == 1
    ts_row, raw, gids = _mk(S=16, T=REACH_T, resets=False)
    s0 = booked()
    reb, vbase = rebase_values(raw, True)
    fused_rate_groupsum(reb.astype(np.float32), vbase.astype(np.float32),
                        gids, plan, 5, interpret=True, precorrected=True)
    assert booked() == s0


@pytest.mark.parametrize("kind", ["hole-1", "hole-6", "random", "none"])
def test_a_caller_without_the_plans_steps_fills_across_the_row(kind):
    """`run_kernel` without `steps` (a caller that composes the kernel
    and has no plan at hand: __graft_entry__'s dry run before it passed
    them) fills across the whole row and returns what the plan's steps
    return, bit for bit; never a fill of no steps, which reads a hole at
    a window's edge as a sample."""
    from filodb_tpu.ops import pallas_fused as pf
    _, _, plan = _reach_plan()
    raw = _reach_rows(kind)
    S = len(raw)
    vbase = np.where(np.isnan(raw), np.inf, raw).min(axis=1)
    vbase = np.where(np.isfinite(vbase), vbase, 0.0)
    prepared = pad_inputs((raw - vbase[:, None]).astype(np.float32),
                          vbase.astype(np.float32),
                          np.arange(S, dtype=np.int32), plan, S)
    kw = dict(num_groups=pf.pad_group_count(S), is_counter=True,
              is_rate=True, with_drops=False, interpret=True, ragged=True)
    got = pf.run_kernel(prepared.vals_p, prepared.vbase_p, prepared.gids_p,
                        plan.rows, plan.tsrow, **kw)
    want = _ragged_launch(plan, raw, None)
    for g, w in zip(got, want):
        assert np.array_equal(np.asarray(g)[:S, :plan.W], w)
    # ... and so does the mesh executor's per-device call, with and without
    from filodb_tpu.parallel.mesh import _device_fused_call
    for steps in ({}, {"steps": pf.scan_steps(plan, "rate_family", True)}):
        dev = _device_fused_call(
            (raw - vbase[:, None]).astype(np.float32)[None],
            np.arange(S, dtype=np.int32)[None, :, None],
            vbase.astype(np.float32)[None], plan.rows, plan.tsrow, G=S, S=S,
            T=raw.shape[1], Tp=plan.Tp, is_counter=True, is_rate=True,
            interpret=True, ragged=True, **steps)
        for g, w in zip(dev, want):
            assert np.array_equal(np.asarray(g)[:, :plan.W], w)
    if kind != "none":
        # ... where no fill at all answers otherwise
        none = pf.run_kernel(prepared.vals_p, prepared.vbase_p,
                             prepared.gids_p, plan.rows, plan.tsrow,
                             steps=0, **kw)
        assert not np.array_equal(np.asarray(none[0])[:S, :plan.W], want[0])


def test_the_jitted_entries_take_no_default_for_the_steps():
    """`_run` (and `_run_set`, `_kernel` under it) name `steps` or fail:
    a default would be a reach that some caller's windows outgrow."""
    from filodb_tpu.ops import pallas_fused as pf
    _, _, plan = _reach_plan()
    flags = pf._flavor(plan, "rate", True, True, True)._asdict()
    del flags["steps"]
    vals = jnp.zeros((256, plan.Tp), jnp.float32)
    st = (vals, vals[:, :1], (jnp.zeros((256, 1), jnp.int32),))
    with pytest.raises(TypeError, match="steps"):
        pf._run((st,), None, plan.rows, plan.tsrow, num_groups=(8,), **flags)


@pytest.mark.parametrize("phased", [False, True])
@pytest.mark.parametrize("Tp,Wp", [(768, 128), (768, 1024), (1536, 512),
                                   (2048, 1024)])
def test_the_vmem_estimate_holds_the_ragged_rate_familys_band(Tp, Wp, phased):
    """The one [Tp, Wp] f32 band this flavor reads lies in VMEM twice
    (the pipeline's two buffers): the estimate holds both beside what the
    dense twin's holds, so a long range of many windows diverts
    (`pick_block` None) and does not fail at lowering; the cell's shape
    keeps its 128 rows."""
    from filodb_tpu.ops import pallas_fused as pf
    for bs in (32, 128):
        ragged = pf.vmem_estimate(Tp, Wp, 128, "rate_family", True, bs=bs,
                                  phased=phased)
        dense = pf.vmem_estimate(Tp, Wp, 128, "rate_family", False, bs=bs,
                                 phased=phased)
        # (the dense twin's own terms were refitted in ISSUE 44: two
        # [bs, Tp] temporaries more, fewer [bs, Wp] ones)
        assert ragged > dense and ragged >= 8 * Tp * Wp + 21 * bs * Tp * 4
    bs = pf.pick_block(Tp, Wp, 128, "rate_family", True, phased=phased)
    if (Tp, Wp) == (768, 128):
        assert bs == 128
    if 8 * Tp * Wp >= pf.VMEM_BUDGET:
        assert bs is None
