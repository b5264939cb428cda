"""Rows on a phase grid (ISSUE 37): series that Prometheus scraped lie on ONE
scrape grid, each behind it by its target's offset inside the interval.

(a) the benchmark's plain reference for such series
    (`benchmark/references/scrape_offsets.py`) against a per-sample loop on a
    hand-sized case, and bit-equal to `benchmark/reference.py` at phase 0;
(b) the fused kernel's phased variant (interpret mode) against
    `tests/oracle.py`, dense and NaN-holed, with phases that include 0,
    `stride - 1` and each window's slack and slack + 1 (the `<=` / `<` edges),
    and windows at the row's first and last slots;
(d) the device mirror: detection, an incremental refresh that keeps the grid,
    and what loses it;
(e) every phase zero: the operands, the signature and the jit call are what
    they were before the variant existed.
((c), the served path, is `test_promscrape_served.py`.)
"""
import numpy as np
import pytest

import histrig
import oracle
from filodb_tpu.core.blockstore import DenseSeriesStore
from filodb_tpu.core.devicecache import DeviceMirror, _detect_phase_grid
from filodb_tpu.core.schemas import DEFAULT_SCHEMAS
from filodb_tpu.ops import pallas_fused as pf
from filodb_tpu.ops.timewindow import PAD_TS

STEP, RANGE = 10_000, 45_000
START = 1_600_000_000_000


# ------------------------------------------------------------ (a) reference

def _ref_case(seed, S=12, T=40):
    rng = np.random.default_rng(seed)
    ts_row = START + np.arange(T, dtype=np.int64) * STEP
    vals = np.cumsum(rng.integers(0, 40, (S, T)).astype(np.float64), axis=1)
    vals[2, 17:] -= vals[2, 17] - 3.0          # a counter reset
    phase = rng.integers(0, STEP, S)
    phase[0], phase[1] = 0, STEP - 1
    wends = np.array([ts_row[0] - 1, ts_row[0], ts_row[1] + 9_999,
                      ts_row[5] + 4_000, ts_row[9], ts_row[20] + 5_000,
                      ts_row[-1], ts_row[-1] + 7_000, ts_row[-1] + 60_000])
    return ts_row, vals, phase, wends


@pytest.mark.parametrize("fn,agg", [("rate", "sum"), ("increase", "sum"),
                                    ("rate", "avg")])
def test_the_reference_is_a_per_sample_loop_on_a_hand_sized_case(fn, agg):
    mod = histrig.bench_module("references", "scrape_offsets")
    ts_row, vals, phase, wends = _ref_case(5)
    base_ids = np.arange(len(vals)) % 3
    panel = {"fn": fn, "agg": agg, "by": ["g"]}
    ref = mod.Reference(ts_row, wends, RANGE, [panel], 3)
    ref.add(vals[:7], base_ids[:7], phase[:7])     # in two blocks
    ref.add(vals[7:], base_ids[7:], phase[7:])
    got = ref.table(panel, np.array([0, 1, 0]))    # base groups 0 and 2 fold
    want = np.zeros((2, len(wends)))
    cnt = np.zeros((2, len(wends)))
    for s in range(len(vals)):
        ts = ts_row + phase[s]
        corr = np.array(oracle.correct_counter(list(vals[s])))
        for w, we in enumerate(wends):
            m = (ts > we - RANGE) & (ts <= we)     # sample by sample
            if m.sum() < 2:
                continue
            t, v = ts[m], corr[m]
            x = oracle.extrapolated_rate(we - RANGE, we, int(m.sum()), t[0],
                                         v[0], t[-1], v[-1], True,
                                         fn == "rate")
            g = (0, 1, 0)[base_ids[s]]
            want[g, w] += x
            cnt[g, w] += 1
    if agg == "avg":
        want = want / np.where(cnt > 0, cnt, 1)
    want[cnt == 0] = np.nan
    assert np.isnan(want).any() and not np.isnan(want).all()
    np.testing.assert_allclose(got, want, rtol=1e-12, equal_nan=True)


@pytest.mark.parametrize("panel", [
    {"fn": "rate", "agg": "sum", "by": ["_ns_"]},
    {"fn": "increase", "agg": "sum", "by": []},
    {"fn": "rate", "agg": "avg", "by": ["_ns_", "dc"]}],
    ids=lambda p: f"{p['agg']}-{p['fn']}-{len(p['by'])}")
def test_at_phase_zero_the_reference_is_reference_py_bit_for_bit(panel):
    mine = histrig.bench_module("references", "scrape_offsets")
    theirs = histrig.bench_module("", "reference")
    ts_row, vals, _, wends = _ref_case(11, S=300)
    base_ids = np.arange(300) % 20
    a = mine.Reference(ts_row, wends, RANGE, [panel], 20)
    b = theirs.Reference(ts_row, wends, RANGE, [panel], 20)
    for lo in range(0, 300, 128):
        a.add(vals[lo:lo + 128], base_ids[lo:lo + 128],
              np.zeros(len(vals[lo:lo + 128]), np.int64))
        b.add(vals[lo:lo + 128], base_ids[lo:lo + 128])
    fold = np.arange(20) % (1 if not panel["by"] else 10)
    x, y = a.table(panel, fold), b.table(panel, fold)
    assert np.isnan(y).any() and not np.isnan(y).all()
    assert x.tobytes() == y.tobytes()


# --------------------------------------------------------------- (b) kernel

def _kernel_case(fn, holes, seed=0, S=24, T=40):
    rng = np.random.default_rng(seed)
    ts_row = 5_000 + np.arange(T) * STEP
    # windows before, at and past the row's first and last slots
    wends = np.array([ts_row[0] - 3_000, ts_row[0], ts_row[0] + 1,
                      ts_row[1] + 4_000, ts_row[4], ts_row[10] + 9_999,
                      ts_row[20] + 5_000, ts_row[-1] - 1, ts_row[-1],
                      ts_row[-1] + 4_000, ts_row[-1] + 9_999,
                      ts_row[-1] + 30_000, ts_row[-1] + 50_000])
    plan = pf.build_plan(ts_row, wends, RANGE)
    phase = rng.integers(0, STEP, S)
    phase[0], phase[1] = 0, STEP - 1
    # phases AT a window's slack (the slot stays) and one past it (it moves)
    slack = np.concatenate([plan.prows[pf._PS1, :len(wends)],
                            plan.prows[pf._PS2, :len(wends)]])
    edges = [int(x) for x in np.concatenate([slack, slack + 1])
             if 0 <= x < STEP]
    assert len(edges) >= 8
    phase[2:2 + len(edges[:S - 2])] = edges[:S - 2]
    if fn in ("rate", "increase"):
        vals = np.cumsum(rng.integers(0, 50, (S, T)).astype(float),
                         axis=1) + 1e6
        vals[3, 20:] -= vals[3, 20] - 5         # a reset
    else:
        vals = rng.normal(100, 10, (S, T))
    if holes:
        vals[rng.random((S, T)) < 0.15] = np.nan
        vals[5, :] = np.nan
    return ts_row, wends, plan, phase, vals


FNS = ("rate", "increase", "sum_over_time", "avg_over_time",
       "count_over_time", "last_over_time")


@pytest.mark.parametrize("holes", [False, True], ids=["dense", "nan-holed"])
@pytest.mark.parametrize("fn", FNS)
def test_the_phased_kernel_is_the_oracle_on_each_rows_own_timestamps(
        fn, holes):
    ts_row, wends, plan, phase, vals = _kernel_case(fn, holes)
    S, G = len(vals), 3
    gids = np.arange(S) % G
    corr = np.array([oracle.correct_counter(list(r)) for r in vals]) \
        if fn in ("rate", "increase") else vals
    vbase = np.where(np.isnan(corr), np.inf, corr).min(axis=1)
    vbase = np.where(np.isfinite(vbase), vbase, 0.0)
    sums, counts = pf.fused_rate_groupsum(
        (corr - vbase[:, None]).astype(np.float32),
        vbase.astype(np.float32), gids, plan, G, fn, precorrected=True,
        interpret=True, ragged=holes, phase=phase)
    want = np.zeros((G, len(wends)))
    cnt = np.zeros((G, len(wends)))
    for s in range(S):
        o = oracle.eval_series(ts_row + phase[s], vals[s], wends, RANGE, fn)
        want[gids[s]] += np.nan_to_num(o)
        cnt[gids[s]] += ~np.isnan(o)
    want[cnt == 0] = np.nan
    assert 0 < np.isnan(want).sum() < want.size
    np.testing.assert_array_equal(counts, cnt)
    np.testing.assert_allclose(pf.present_sum(sums, counts), want,
                               rtol=2e-5, atol=1e-6, equal_nan=True)


@pytest.mark.parametrize("holes", [False, True], ids=["dense", "nan-holed"])
def test_the_phased_kernel_corrects_resets_itself_on_raw_counters(holes):
    """`precorrected=False`: the in-kernel drop correction (a host-gathered
    block's) under per-row slots."""
    ts_row, wends, plan, phase, vals = _kernel_case("rate", holes, seed=3)
    vals = vals - 1e6 + 10                       # raw, small enough for f32
    vals[3, 20:] = np.cumsum(np.ones(vals.shape[1] - 20)) + 2   # the reset
    S, G = len(vals), 3
    gids = np.arange(S) % G
    sums, counts = pf.fused_rate_groupsum(
        vals.astype(np.float32), np.zeros(S, np.float32), gids, plan, G,
        "increase", precorrected=False, interpret=True, ragged=holes,
        phase=phase)
    want = np.zeros((G, len(wends)))
    cnt = np.zeros((G, len(wends)))
    for s in range(S):
        o = oracle.eval_series(ts_row + phase[s], vals[s], wends, RANGE,
                               "increase")
        want[gids[s]] += np.nan_to_num(o)
        cnt[gids[s]] += ~np.isnan(o)
    want[cnt == 0] = np.nan
    np.testing.assert_array_equal(counts, cnt)
    np.testing.assert_allclose(pf.present_sum(sums, counts), want,
                               rtol=2e-5, atol=1e-4, equal_nan=True)


def test_a_row_takes_the_earlier_slot_exactly_past_the_windows_slack():
    """first = idx1 - (phase > ws - ts_row[idx1 - 1]), last = idx2 -
    (phase > we - ts_row[idx2]), against a search on the row itself."""
    ts_row = 5_000 + np.arange(30) * STEP
    wends = np.arange(ts_row[0] - 20_000, ts_row[-1] + 70_000, 3_333)
    p = pf.build_plan(ts_row, wends, RANGE).prows[:, :len(wends)]
    for phase in (0, 1, 1_667, 3_333, 4_999, 5_000, 5_001, STEP - 1):
        row = ts_row + phase
        first = np.searchsorted(row, wends - RANGE + 1, side="left")
        last = np.searchsorted(row, wends, side="right") - 1
        n = last - first + 1
        e1, e2 = phase > p[pf._PS1], phase > p[pf._PS2]
        np.testing.assert_array_equal(p[pf._N1] + e1 - e2, n)
        has = n >= 1
        np.testing.assert_array_equal((p[pf._PI1] - e1)[has], first[has])
        np.testing.assert_array_equal((p[pf._PI2] - e2)[has], last[has])
        np.testing.assert_array_equal(
            (np.where(e1, p[pf._PT1M], p[pf._PT1]) + phase)[has],
            row[first[has]])
        np.testing.assert_array_equal(
            (np.where(e2, p[pf._PT2M], p[pf._PT2]) + phase)[has],
            row[last[has]])


@pytest.mark.parametrize("fn", ["rate", "delta"])
@pytest.mark.parametrize("holes", ["every-third", "pairs", "random", "head",
                                   "tail", "all-but-two"])
def test_the_valid_count_is_a_rows_own_on_either_side_of_the_slack(
        holes, fn):
    """ISSUE 43: the ragged rate family counts a row's valid samples a
    window by one product with the base row's band and the two gathered
    corrections, where it took a running count at the row's own slots.
    The rows of the test above, holed: phases at, before and past each
    window's slack, windows whose base band is empty included; presence
    is `count >= 2` by a count on the row itself, the values the
    oracle's (which divide by the count), and both are what fills across
    the whole row give."""
    ts_row = 5_000 + np.arange(30) * STEP
    wends = np.arange(ts_row[0] - 20_000, ts_row[-1] + 70_000, 3_333)
    plan = pf.build_plan(ts_row, wends, RANGE)
    slack = np.concatenate([plan.prows[pf._PS1, :len(wends)],
                            plan.prows[pf._PS2, :len(wends)]])
    phase = np.unique([0, 1, 1_667, 3_333, 4_999, 5_000, 5_001, STEP - 1]
                      + [int(x) for x in np.concatenate([slack, slack + 1])
                         if 0 <= x < STEP])
    S, T = len(phase), len(ts_row)
    rng = np.random.default_rng(43)
    vals = 1e4 + np.cumsum(rng.integers(1, 30, (S, T)).astype(float), axis=1)
    m = {"every-third": np.arange(T)[None, :] % 3 == np.arange(S)[:, None] % 3,
         "pairs": (np.arange(T)[None, :] + np.arange(S)[:, None]) % 5 < 2,
         "random": rng.random((S, T)) < 0.4,
         "head": np.arange(T)[None, :] < 3 + np.arange(S)[:, None] % 9,
         "tail": np.arange(T)[None, :] > 18 + np.arange(S)[:, None] % 9,
         "all-but-two": ~np.isin(
             np.arange(T)[None, :] - np.arange(S)[:, None] % 20, (4, 6)),
         }[holes]
    vals[np.broadcast_to(m, vals.shape)] = np.nan
    vbase = np.where(np.isnan(vals), np.inf, vals).min(axis=1)
    args = ((vals - vbase[:, None]).astype(np.float32),
            vbase.astype(np.float32), np.arange(S), plan, S, fn)
    sums, counts = pf.fused_rate_groupsum(
        *args, precorrected=True, interpret=True, ragged=True, phase=phase)
    held = np.zeros((S, len(wends)), int)
    want = np.full((S, len(wends)), np.nan)
    for s in range(S):
        row = ts_row + phase[s]
        first = np.searchsorted(row, wends - RANGE + 1, side="left")
        last = np.searchsorted(row, wends, side="right") - 1
        ok = ~np.isnan(vals[s])
        held[s] = [ok[a:b + 1].sum() for a, b in zip(first, last)]
        want[s] = oracle.eval_series(row, vals[s], wends, RANGE, fn)
    base_empty = plan.rows[pf._N1, :len(wends)] == 0
    assert base_empty.any() and (held[:, ~base_empty] >= 2).any()
    assert (held[:, base_empty] <= 1).all()
    np.testing.assert_array_equal(counts, held >= 2)
    np.testing.assert_allclose(pf.present_sum(sums, counts), want,
                               rtol=2e-5, atol=1e-6, equal_nan=True)
    # ... and at the reach of the whole row
    prepared = pf.pad_inputs(*args[:5], phase=phase)
    flags = pf._flavor(plan, fn, True, True, True, True)
    assert flags.steps == 3 < (plan.Tp - 1).bit_length()
    full, _ = pf._enqueue_run(
        plan, None, (pf._kernel_set(prepared, (prepared.gids_p,)),), None,
        (pf.pad_group_count(S),),
        **flags._replace(steps=(plan.Tp - 1).bit_length())._asdict())
    np.testing.assert_array_equal(np.asarray(full[0])[:S, :len(wends)],
                                  np.asarray(sums))
    np.testing.assert_array_equal(np.asarray(full[1])[:S, :len(wends)],
                                  counts)


# --------------------------------------------------------------- (d) mirror

def _store(offsets, n=8, late=None, counts=None):
    store = DenseSeriesStore(DEFAULT_SCHEMAS["gauge"])
    rows = np.array([store.new_row() for _ in offsets], dtype=np.int64)
    for r, off in enumerate(offsets):
        k = n if counts is None else counts[r]
        ts = START + off + np.arange(k, dtype=np.int64) * STEP
        if late is not None and late[0] == r:
            ts[late[1]] += late[2]
        store.append_grid(rows[r:r + 1], ts[None, :],
                          {"value": np.arange(k, dtype=float)[None, :] + r})
    return store, rows


def _append(store, rows, offsets, k0, k=1, late=None):
    ts = START + np.asarray(offsets)[:, None] \
        + (k0 + np.arange(k, dtype=np.int64))[None, :] * STEP
    if late is not None:
        ts[late[0], late[1]] += late[2]
    store.append_grid(rows, ts, {"value": np.ones(ts.shape) * k0})


OFFSETS = [4_000, 0, STEP - 1, 1, 4_000]


@pytest.mark.parametrize("case,offsets,kw,on_grid,offgrid", [
    ("scrape offsets", OFFSETS, {}, True, 0),
    ("one timestamp row", [0] * 5, {}, True, 0),
    ("the earliest row is not row 0", [7, 9_000, 3, 3, 8], {}, True, 0),
    ("a late sample", OFFSETS, {"late": (2, 5, 7)}, False, 1),
    # rows that hold other counts of samples, or lie a whole interval
    # apart, are placed on the grid's slots (ISSUE 42: test_slot_placement)
    ("a hole: one row a sample short", OFFSETS,
     {"counts": [8, 8, 7, 8, 8]}, "placed", 0),
    ("a row a whole interval behind", [0, 5, STEP, 9], {}, "placed", 0),
    # ... and one that starts in the next interval since the epoch, but
    # less than an interval behind the earliest: a phase off THAT row
    ("no whole interval holds every first sample", [6_000, 15_000, 7_000],
     {}, True, 0),
], ids=lambda v: v.replace(" ", "-") if isinstance(v, str) else None)
def test_the_mirror_finds_the_phase_grid_or_counts_the_rows_off_it(
        case, offsets, kw, on_grid, offgrid):
    from filodb_tpu.utils.metrics import registry
    store, rows = _store(offsets, **kw)
    mirror = DeviceMirror(shard_num=977)
    assert mirror.ensure_fresh(store)
    snap = mirror.snapshot()
    got = mirror.fused_eligible("value", snap)
    assert (got is not None) == (on_grid is True), case
    assert registry.gauge("device_mirror_offgrid_rows",
                          shard="977").value == offgrid
    if on_grid == "placed":
        # fusable by the ragged variants alone: the empty slots are NaN
        assert snap.interval == STEP and snap.placed_rows
        assert mirror.fused_eligible("value", snap,
                                     allow_ragged=True) is not None
        assert registry.gauge("device_mirror_placed_rows",
                              shard="977").value == snap.placed_rows
        return
    assert snap.interval == 0 and registry.gauge(
        "device_mirror_placed_rows", shard="977").value == 0
    if not on_grid:
        assert snap.phase is None and not snap.uniform_grid
        assert mirror.gather_cached(rows, snap).deferred("phase") is None
        return
    # the base row is the earliest row's moved back to a whole interval
    # since the epoch (START is one) where the phases allow, so that every
    # shard of a deployment finds the same; in offsets from the mirror's
    # base, the earliest sample
    lo = min(offsets)
    zero = 0 if max(offsets) < STEP else lo
    assert snap.base_ms == START + lo
    np.testing.assert_array_equal(snap.phase, np.asarray(offsets) - zero)
    np.testing.assert_array_equal(got[:8], zero - lo + np.arange(8) * STEP)
    assert snap.phase_rows == sum(o != zero for o in offsets)
    assert registry.gauge("device_mirror_phase_rows",
                          shard="977").value == snap.phase_rows
    # one shared timestamp row is the grid whose phases are all zero
    assert snap.uniform_grid == (snap.phase_rows == 0)
    gather = mirror.gather_cached(rows[[2, 0]], snap)
    held = gather.deferred("phase")
    if snap.phase_rows == 0:
        assert held is None and snap.phase_dev is None
        return
    np.testing.assert_array_equal(held.host(), snap.phase[[2, 0]])
    col = np.asarray(held.resolve(256))             # zero rows behind
    assert col.shape == (256, 1) and col.dtype == np.float32
    np.testing.assert_array_equal(col[:2, 0], snap.phase[[2, 0]])
    assert not col[2:].any()


@pytest.mark.parametrize("case,late,whole,keeps", [
    ("every row appends its own phase + k x stride", None, True, True),
    ("one row appends late", (3, 0, 2), True, False),
    ("one row does not append", None, False, False),
], ids=lambda v: v.replace(" ", "-") if isinstance(v, str) else None)
def test_an_incremental_refresh_keeps_the_grid_only_on_the_grid(
        case, late, whole, keeps):
    from filodb_tpu.utils.metrics import registry
    store, rows = _store(OFFSETS, n=128)
    mirror = DeviceMirror(shard_num=978)
    assert mirror.ensure_fresh(store)
    old = mirror.snapshot()
    inc = registry.counter("device_mirror_incremental").value
    sel = slice(None) if whole else slice(0, 4)
    _append(store, rows[sel], OFFSETS[sel], 128, k=2, late=late)
    assert mirror.ensure_fresh(store)
    snap = mirror.snapshot()
    assert registry.counter("device_mirror_incremental").value == inc + 1
    assert (snap.ts_row0 is not None) == keeps, case
    if keeps:
        np.testing.assert_array_equal(
            snap.ts_row0[:130], np.arange(130) * STEP)
        assert snap.phase is old.phase and snap.phase_dev is old.phase_dev
        assert snap.phase_rows == old.phase_rows == 4
        want = START + np.asarray(OFFSETS)[:, None] \
            + np.arange(130)[None, :] * STEP - snap.base_ms
        np.testing.assert_array_equal(
            np.asarray(snap.ts_off)[:5, :130], want)
    else:
        assert snap.phase is None and snap.phase_dev is None
        assert registry.gauge("device_mirror_offgrid_rows",
                              shard="978").value == 1
        assert mirror.fused_eligible("value", snap) is None


def test_detection_compares_the_counted_region_only():
    ts = np.full((3, 6), PAD_TS, np.int32)
    ts[:, :4] = np.array([5, 0, 9])[:, None] + np.arange(4)[None, :] * 10
    base, phase, off = _detect_phase_grid(ts, np.array([4, 4, 4]))
    np.testing.assert_array_equal(base, [0, 10, 20, 30, PAD_TS, PAD_TS])
    np.testing.assert_array_equal(phase, [5, 0, 9])
    assert off == 0
    # the same rows 3 ms after a whole interval since the epoch: the base
    # row moves back onto it while every phase stays under the gap
    near = ts.copy()
    near[2, :4] -= 3                                # first samples 5, 0, 6
    base, phase, _ = _detect_phase_grid(near, np.array([4, 4, 4]), 1003)
    np.testing.assert_array_equal(base, [-3, 7, 17, 27, PAD_TS, PAD_TS])
    np.testing.assert_array_equal(phase, [8, 3, 9])
    # ... and stays the earliest row's where one would not (9 + 3)
    base, phase, _ = _detect_phase_grid(ts, np.array([4, 4, 4]), 1003)
    np.testing.assert_array_equal(base[:4], [0, 10, 20, 30])
    np.testing.assert_array_equal(phase, [5, 0, 9])
    # a phase of a whole gap is another slot, not a phase
    ts[2, :4] += 1
    assert _detect_phase_grid(ts, np.array([4, 4, 4])) == (None, None, 1)


# ------------------------------------------------------- (e) all phases zero

def test_with_every_phase_zero_the_call_is_the_unphased_one(monkeypatch):
    ts_row = np.arange(40) * STEP
    wends = ts_row[-1] - np.arange(5)[::-1] * 60_000
    plan = pf.build_plan(ts_row, wends, 300_000)
    vals = np.cumsum(np.ones((20, 40), np.float32), axis=1)
    gids = np.arange(20) % 4
    calls = []
    real = pf._run

    def spy(sets, offsets, rows, tsrow, **kw):
        calls.append((sets, rows, kw))
        return real(sets, offsets, rows, tsrow, **kw)
    monkeypatch.setattr(pf, "_run", spy)
    sigs = []
    real_sig = pf._run_shape_sig
    monkeypatch.setattr(pf, "_run_shape_sig", lambda *a: sigs.append(
        real_sig(*a)) or sigs[-1])
    outs = [pf.fused_rate_groupsum(vals, np.zeros(20, np.float32), gids,
                                   plan, 4, "rate", precorrected=True,
                                   interpret=True, phase=ph)
            for ph in (None, np.zeros(20, np.int64))]
    assert pf.pad_values(vals, np.zeros(20), plan,
                         phase=np.zeros(20, np.int64)).phase_p is None
    for sets, rows, kw in calls:
        assert len(sets) == 1 and len(sets[0]) == 3     # no phase column
        assert rows.shape == (8, 128) and kw["phased"] is False
        np.testing.assert_array_equal(np.asarray(rows), plan.rows)
    # the parent's signature string, letter for letter
    assert sigs == ["S256xT128xW128xG8:rate_family"] * 2
    np.testing.assert_array_equal(np.asarray(outs[0][0]),
                                  np.asarray(outs[1][0]))
    np.testing.assert_array_equal(outs[0][1], outs[1][1])
    # ... and one phase that is not zero takes the other variant
    calls.clear()
    sums, counts = pf.fused_rate_groupsum(
        vals, np.zeros(20, np.float32), gids, plan, 4, "rate",
        precorrected=True, interpret=True, phase=np.arange(20) % 2)
    (sets, rows, kw), = calls
    assert len(sets[0]) == 4 and sets[0][3].shape == (256, 1)
    assert rows.shape == (16, 128) and kw["phased"] is True
    assert sigs[-1] == "S256xT128xW128xG8:rate_family:phased"
    np.testing.assert_array_equal(np.asarray(rows), plan.prows)
    np.testing.assert_array_equal(plan.prows[:8], plan.rows)
    np.testing.assert_array_equal(counts, np.full((4, 5), 5.0))
