"""Rows that hold other counts of samples than one another, placed on the
slots of their scrape grid (ISSUE 42): a fleet that churns has rows that
start late, rows that end early and rows that missed scrapes in every shard.

(a) the mirror's placement against a per-sample loop: a late start, an early
    end, interior runs, a reset across a hole, a target replaced twice, an
    empty row; and what stays off the grid, counted: a sample that fits no
    slot, a second interval, a store too sparse for a grid;
(b) an incremental refresh after placement equals a full build: an append to
    a holed row, a new row mid-stream, a scrape that every row but one made;
    a late append declines and the full build counts the row;
(c) what reads a placed snapshot: the general path and `max_over_time` equal
    the oracle, and a function that takes a NaN for a sample (the staleness
    rule of `last_over_time`, `count_over_time`'s slots) declines the mirror
    and still equals it;
(d) the benchmark's side: the plain reference against a per-sample loop and
    bit-equal to `scrape_offsets.py` where nothing is absent, how lives and
    outages follow from the seed, the loader's question to the program,
    `costs_ragged` against a hand count.
((e), the served path, is `test_promchurn_served.py`.)
"""
import numpy as np
import pytest

import histrig
import oracle
from filodb_tpu.core import devicecache
from filodb_tpu.core.blockstore import DenseSeriesStore
from filodb_tpu.core.devicecache import DeviceMirror
from filodb_tpu.core.schemas import DEFAULT_SCHEMAS
from filodb_tpu.ops.timewindow import PAD_TS
from filodb_tpu.utils.metrics import registry

STEP, RANGE = 10_000, 45_000
START = 1_600_000_000_000
T = 24

# name -> (scrape offset, the scrapes that exist, where the counter resets)
ROWS = {
    "every scrape": (0, np.arange(T), None),
    "a late start": (3, np.arange(5, T), None),
    "an early end": (5_000, np.arange(0, 17), None),
    "interior runs": (STEP - 1, np.r_[0:4, 6:11, 14:T], None),
    "a reset across a hole": (7, np.r_[0:8, 11:T], 10),
    "replaced: the first life": (40, np.arange(0, 6), None),
    "replaced: the second life": (9_000, np.arange(6, 12), 8),
    "replaced: the third life": (123, np.arange(12, T), None),
    "one sample": (77, np.arange(20, 21), None),
    "no sample": (0, np.arange(0), None),
}


def _values(name):
    off, held, reset = ROWS[name]
    full = np.cumsum(np.arange(1.0, T + 1) * (3 + len(name) % 5)) + 1e7
    if reset is not None:
        full[reset:] -= full[reset] - 2.0
    return START + off + held * STEP, full[held]


def _store(names=tuple(ROWS), schema="prom-counter", col="count", late=None):
    store = DenseSeriesStore(DEFAULT_SCHEMAS[schema])
    for name in names:
        row = np.array([store.new_row()])
        ts, vals = _values(name)
        if late is not None and late[0] == name:
            ts = ts.copy()
            ts[late[1]] += late[2]
        if ts.size:
            store.append_grid(row, ts[None, :], {col: vals[None, :]})
    return store


@pytest.fixture(scope="module")
def placed():
    mirror = DeviceMirror(shard_num=4201)
    rows0 = registry.counter("device_mirror_rows_placed").value
    assert mirror.ensure_fresh(_store())
    return mirror, mirror.snapshot(), \
        registry.counter("device_mirror_rows_placed").value - rows0


# ------------------------------------------------------------- (a) placement

@pytest.mark.parametrize("name", list(ROWS), ids=lambda n: n.replace(" ", "-"))
def test_a_rows_samples_lie_in_the_slots_of_their_scrapes(placed, name):
    mirror, snap, _ = placed
    r = list(ROWS).index(name)
    off, held, _ = ROWS[name]
    ts, vals = _values(name)
    assert snap.interval == STEP and snap.t_used == T
    assert snap.counts[r] == held.size
    assert snap.phase[r] == (off if held.size else 0)
    # the grid: slot k at START + k x STEP, in offsets from the mirror's base
    np.testing.assert_array_equal(
        snap.ts_row0, START - snap.base_ms + np.arange(T) * STEP)
    got = np.asarray(snap.cols["count"])[r]
    got_ts = np.asarray(snap.ts_off)[r]
    vbase = float(np.asarray(snap.vbases["count"])[r])
    want = np.full(T, np.nan)
    corr = oracle.correct_counter(list(vals))       # sample by sample
    for k, t in enumerate(ts):
        slot = (t - off - START) // STEP
        assert (t - off - START) % STEP == 0
        want[slot] = corr[k] - corr[0]
    np.testing.assert_allclose(got, want, rtol=0, atol=1e-6, equal_nan=True)
    assert vbase == (vals[0] if held.size else 0.0)
    # every slot's own time, filled or not
    np.testing.assert_array_equal(
        got_ts, snap.ts_row0 + (off if held.size else 0))
    assert bool(snap.vbase_valid["count"][r]) == bool(held.size)
    if held.size:
        # the tail state is the LAST EXISTING sample's, wherever it lies
        assert snap.tail_last_raw["count"][r] == vals[-1]
        assert snap.tail_cum_drop["count"][r] == pytest.approx(
            corr[-1] - vals[-1])


def test_a_placed_snapshot_is_fusable_by_the_ragged_variants_alone(placed):
    mirror, snap, counted = placed
    n = len(ROWS)
    assert snap.placed_rows == counted == n - 1     # all but "every scrape"
    assert registry.gauge("device_mirror_placed_rows",
                          shard="4201").value == n - 1
    assert registry.gauge("device_mirror_offgrid_rows",
                          shard="4201").value == 0
    assert not snap.col_finite["count"] and not mirror.col_dense("count")
    assert mirror.fused_eligible("count", snap) is None
    np.testing.assert_array_equal(
        mirror.fused_eligible("count", snap, allow_ragged=True), snap.ts_row0)
    assert snap.phase_rows == np.count_nonzero(snap.phase) == 8
    col = np.asarray(mirror.gather_cached(np.arange(n), snap)
                     .deferred("phase").resolve(256))
    np.testing.assert_array_equal(col[:n, 0], snap.phase)
    # the rows past the store's: no slot time, no value
    assert (np.asarray(snap.ts_off)[n:] == PAD_TS).all()
    assert np.isnan(np.asarray(snap.cols["count"])[n:]).all()
    assert registry.counter("span_mirror_place_slots_calls").value >= 2


def test_equal_counts_cost_what_they_cost_before(monkeypatch):
    """One shared row, and one phase grid with equal counts, are tried
    first and never reach the placement."""
    monkeypatch.setattr(devicecache, "_place_on_grid",
                        lambda *a: pytest.fail("placement ran"))
    for offs in ([0, 0, 0], [5, 0, 9_999]):
        store = DenseSeriesStore(DEFAULT_SCHEMAS["gauge"])
        for off in offs:
            store.append_grid(
                np.array([store.new_row()]),
                (START + off + np.arange(8) * STEP)[None, :],
                {"value": np.arange(8.0)[None, :]})
        mirror = DeviceMirror(shard_num=4202)
        assert mirror.ensure_fresh(store)
        snap = mirror.snapshot()
        assert snap.interval == 0 and snap.placed_rows == 0
        assert mirror.fused_eligible("value", snap) is not None
    assert registry.gauge("device_mirror_placed_rows",
                          shard="4202").value == 0


@pytest.mark.parametrize("case,names,late,offgrid", [
    ("a scrape 7 ms late", ("every scrape", "a late start", "interior runs"),
     ("interior runs", 6, 7), 1),
    ("a scrape a ms early", ("every scrape", "an early end"),
     ("an early end", 16, -1), 1),
], ids=lambda v: v.replace(" ", "-") if isinstance(v, str) else None)
def test_a_sample_that_fits_no_slot_leaves_the_store_off_the_grid(
        case, names, late, offgrid):
    store = _store(names, late=late)
    placed0 = registry.counter("device_mirror_rows_placed").value
    mirror = DeviceMirror(shard_num=4203)
    assert mirror.ensure_fresh(store)
    snap = mirror.snapshot()
    assert snap.interval == 0 and snap.ts_row0 is None and snap.phase is None
    assert mirror.fused_eligible("count", snap, allow_ragged=True) is None
    assert registry.gauge("device_mirror_offgrid_rows",
                          shard="4203").value == offgrid
    assert registry.gauge("device_mirror_placed_rows",
                          shard="4203").value == 0
    assert registry.counter("device_mirror_rows_placed").value == placed0
    # the store's own layout: a row's k-th sample in column k
    r = names.index(late[0])
    ts, _ = _values(late[0])
    want = ts - snap.base_ms
    want[late[1]] += late[2]
    np.testing.assert_array_equal(
        np.asarray(snap.ts_off)[r, :ts.size], want)
    assert (np.asarray(snap.ts_off)[r, ts.size:] == PAD_TS).all()


def test_a_second_interval_and_a_sparse_store_stay_off_the_grid():
    def build(rows):
        store = DenseSeriesStore(DEFAULT_SCHEMAS["gauge"])
        for ts in rows:
            store.append_grid(np.array([store.new_row()]), ts[None, :],
                              {"value": np.ones((1, ts.size))})
        mirror = DeviceMirror(shard_num=4204)
        assert mirror.ensure_fresh(store)
        return mirror.snapshot()
    ten = START + np.arange(12) * STEP
    # most rows agree on 10 s; one target is scraped every 15 s
    snap = build([ten, ten + 5, ten[2:], START + np.arange(8) * 15_000])
    assert snap.interval == 0 and snap.ts_row0 is None
    assert registry.gauge("device_mirror_offgrid_rows",
                          shard="4204").value == 1
    # a multiple of the interval fits: every third slot
    snap = build([ten, ten + 5, ten[2:], START + np.arange(4) * 30_000])
    assert snap.interval == STEP and snap.placed_rows == 2
    # one row a day later: a grid would be holes, the store's layout stays
    snap = build([ten, ten[:9], ten + 8_640 * STEP])
    assert snap.interval == 0 and snap.ts_row0 is None
    assert registry.gauge("device_mirror_offgrid_rows",
                          shard="4204").value >= 1


# ------------------------------------------------- (b) incremental refreshes

def _append(store, names, k0, k, skip=(), late=None):
    """Scrapes k0 .. k0+k-1 of `names`, each row at its own offset."""
    for r, name in enumerate(names):
        if name in skip:
            continue
        ts = START + ROWS[name][0] + (k0 + np.arange(k)) * STEP
        if late == name:
            ts = ts + 4
        store.append_grid(np.array([r]), ts[None, :],
                          {"count": (5e7 + ts[None, :] % 977).astype(float)})


def _same_as_a_full_build(store, mirror):
    snap = mirror.snapshot()
    fresh = DeviceMirror()
    assert fresh._refresh(store)
    want = fresh.snapshot()
    n = store.num_series
    for f in ("interval", "t_used", "placed_rows", "phase_rows", "base_ms"):
        assert getattr(snap, f) == getattr(want, f), f
    for f in ("ts_row0", "phase", "counts"):
        np.testing.assert_array_equal(getattr(snap, f), getattr(want, f), f)
    # the fact of each row: a refresh never calls a row whole that a full
    # build does not (one that was holed stays so until the next build)
    assert snap.whole.shape == want.whole.shape == (n,)
    assert not (snap.whole & ~want.whole).any()
    np.testing.assert_array_equal(np.asarray(snap.ts_off)[:n],
                                  np.asarray(want.ts_off)[:n])
    np.testing.assert_array_equal(np.asarray(snap.phase_dev),
                                  np.asarray(want.phase_dev))
    # absolute values: the bases of a row that was empty may differ
    for s_ in (snap, want):
        assert set(s_.cols) == {"count"}
    a, b = (np.asarray(s_.cols["count"])[:n]
            + np.asarray(s_.vbases["count"])[:n, None] for s_ in (snap, want))
    np.testing.assert_allclose(a, b, rtol=1e-12, equal_nan=True)
    np.testing.assert_array_equal(snap.tail_last_raw["count"],
                                  want.tail_last_raw["count"])
    np.testing.assert_allclose(snap.tail_cum_drop["count"],
                               want.tail_cum_drop["count"])


NAMES = ("every scrape", "a late start", "interior runs",
         "a reset across a hole", "no sample")


@pytest.mark.parametrize("case", [
    "every row appends", "a holed row and no other", "one row skips a scrape",
    "a new row mid-stream", "an empty row gets its first sample"])
def test_an_incremental_refresh_places_by_slot_and_equals_a_full_build(case):
    store = _store(NAMES)
    mirror = DeviceMirror(shard_num=4205)
    assert mirror.ensure_fresh(store)
    inc = registry.counter("device_mirror_incremental").value
    full = registry.counter("device_mirror_refreshes").value
    live = NAMES[:4]
    if case == "every row appends":
        _append(store, live, T, 2)
    elif case == "a holed row and no other":
        _append(store, live, T, 3, skip=set(live) - {"interior runs"})
    elif case == "one row skips a scrape":
        _append(store, live, T, 1, skip={"a late start"})
        _append(store, live, T + 1, 2)
    elif case == "a new row mid-stream":
        _append(store, live, T, 1)
        row = np.array([store.new_row()])
        store.append_grid(row, (START + 4_321 + (T + 1 + np.arange(2))
                                * STEP)[None, :],
                          {"count": np.array([[9e8, 9e8 + 5]])})
        _append(store, live, T + 1, 2)
    else:
        _append(store, NAMES, T, 2, skip={"every scrape"})
    assert mirror.ensure_fresh(store)
    assert registry.counter("device_mirror_incremental").value == inc + 1
    assert registry.counter("device_mirror_refreshes").value == full
    snap = mirror.snapshot()
    assert snap.interval == STEP and snap.t_used > T
    # never at counts[s]: a holed row's new sample lies in its scrape's slot
    r = NAMES.index("interior runs")
    np.testing.assert_array_equal(
        np.asarray(snap.ts_off)[r, :snap.t_used],
        snap.ts_row0 + ROWS["interior runs"][0])
    _same_as_a_full_build(store, mirror)
    assert mirror.fused_eligible("count", snap, allow_ragged=True) is not None


def test_a_late_append_declines_and_the_full_build_counts_the_row():
    store = _store(NAMES)
    mirror = DeviceMirror(shard_num=4206)
    assert mirror.ensure_fresh(store)
    inc = registry.counter("device_mirror_incremental").value
    full = registry.counter("device_mirror_refreshes").value
    _append(store, NAMES[:4], T, 1, late="interior runs")
    assert mirror.ensure_fresh(store)
    assert registry.counter("device_mirror_incremental").value == inc
    assert registry.counter("device_mirror_refreshes").value == full + 1
    snap = mirror.snapshot()
    assert snap.interval == 0 and snap.ts_row0 is None
    assert registry.gauge("device_mirror_offgrid_rows",
                          shard="4206").value == 1
    assert registry.gauge("device_mirror_placed_rows",
                          shard="4206").value == 0


# ------------------------------------------ (c) what reads a placed snapshot

S_Q = START // 1000
ARGS = (S_Q + 60, 30, S_Q + (T - 1) * 10 + 20)
GAUGES = ("every scrape", "a late start", "an early end", "interior runs",
          "replaced: the second life", "one sample")


def _engine(schema, col, metric, names, offsets=True):
    from filodb_tpu.core.memstore import TimeSeriesMemStore
    from filodb_tpu.core.partkey import PartKey
    from filodb_tpu.parallel.shardmapper import ShardEvent, ShardMapper
    from filodb_tpu.query.engine import QueryEngine
    ms = TimeSeriesMemStore()
    sh = ms.setup("prometheus", 0)
    mapper = ShardMapper(1)
    mapper.update_from_event(
        ShardEvent("IngestionStarted", "prometheus", 0, "local"))
    series = {}
    for i, name in enumerate(names):
        ts, vals = _values(name)
        if not offsets:
            ts = ts - ROWS[name][0]
        key = PartKey.make(metric, {"_ws_": "demo", "_ns_": f"App-{i % 2}",
                                    "instance": f"I{i}"})
        assert sh.ingest_columns(schema, [key], ts[None, :],
                                 {col: vals[None, :]}) == ts.size
        series[i] = (ts, vals)
    return QueryEngine("prometheus", ms, mapper), sh, series


def _oracle(series, fn, agg, wends):
    """agg by (_ns_) of fn over the samples that exist, series by series."""
    out = {}
    for i, (ts, vals) in series.items():
        per = oracle.eval_series(ts, vals, wends, RANGE, fn)
        out.setdefault(f"App-{i % 2}", []).append(per)
    fold = {"sum": np.nansum, "max": np.nanmax}[agg]
    # the case is one only if some series is absent from some window
    assert any(np.isnan(r).any() and not np.isnan(r).all()
               for rows in out.values() for r in rows)
    res = {}
    for ns, rows in out.items():
        rows = np.array(rows)
        none = np.isnan(rows).all(axis=0)
        with np.errstate(all="ignore"):
            res[ns] = np.where(none, np.nan,
                               fold(np.where(none, 0.0, rows), axis=0))
    return res


def _answer(res):
    assert res.error is None, res.error
    return {k.labels_dict["_ns_"]: np.asarray(v) for k, _, v in res.series()}


@pytest.mark.parametrize("fn,agg,schema,offsets,route", [
    ("rate", "sum", "prom-counter", True, "leaf_general_path"),
    ("increase", "sum", "prom-counter", True, "leaf_general_path"),
    ("sum_over_time", "sum", "gauge", True, "leaf_general_path"),
    ("max_over_time", "max", "gauge", True, "leaf_general_path"),
    ("max_over_time", "max", "gauge", False, "leaf_fused_minmax"),
    ("min_over_time", "sum", "gauge", False, "leaf_fused_minmax"),
    # a NaN is a sample to these: they read the store's own rows
    ("count_over_time", "sum", "gauge", True, "leaf_host_gather"),
    ("last_over_time", "sum", "gauge", True, "leaf_host_gather"),
], ids=lambda v: str(v))
def test_whatever_reads_a_placed_mirror_equals_the_oracle(
        fn, agg, schema, offsets, route):
    col, metric = {"prom-counter": ("count", "request_total"),
                   "gauge": ("value", "heap_usage")}[schema]
    engine, sh, series = _engine(schema, col, metric, GAUGES, offsets)
    took = registry.counter(route)
    before = took.value
    q = f'{agg}({fn}({metric}{{_ws_="demo"}}[45s])) by (_ns_)'
    got = _answer(engine.query_range(q, *ARGS))
    mirror = sh.stores[schema].device_mirror
    snap = mirror.snapshot()
    assert snap.interval == STEP and snap.placed_rows == len(GAUGES) - 1
    assert (snap.phase_rows > 0) == offsets
    assert took.value - before == 1, route
    wends = np.arange(ARGS[0], ARGS[2] + 1, ARGS[1]) * 1000
    want = _oracle(series, fn, agg, wends)
    assert set(got) == set(want)
    for ns in want:
        np.testing.assert_allclose(got[ns], want[ns], rtol=2e-5,
                                   equal_nan=True, err_msg=f"{fn} {ns}")


# ------------------------------------------------- (d) the benchmark's side

def _churn_cfg(series=512, samples=240, **over):
    cfg = dict(histrig.bench_json("configs", "promchurn-counters-262k"),
               series=series, samples=samples)
    cfg.update(over)
    return cfg


def _ref_case(seed=11, S=14):
    rng = np.random.default_rng(seed)
    ts_row = START + np.arange(40, dtype=np.int64) * STEP
    vals = np.cumsum(rng.integers(0, 40, (S, 40)).astype(np.float64), axis=1)
    vals[2, 17:] -= vals[2, 17] - 3.0
    vals[5, 21:] -= vals[5, 21] - 1.0           # a reset inside a hole
    phase = rng.integers(0, STEP, S)
    phase[0], phase[1] = 0, STEP - 1
    exists = np.ones((S, 40), bool)
    exists[3, :12] = exists[4, 25:] = exists[5, 19:23] = False
    exists[6, 5:7] = exists[6, 30:36] = False
    exists[7, :] = False
    exists[8, 1:] = False
    wends = np.array([ts_row[0] - 1, ts_row[1] + 9_999, ts_row[5] + 4_000,
                      ts_row[13], ts_row[20] + 5_000, ts_row[22],
                      ts_row[26], ts_row[33], ts_row[-1], ts_row[-1] + 7_000,
                      ts_row[-1] + 60_000])
    return ts_row, vals, phase, exists, wends


@pytest.mark.parametrize("fn,agg", [("rate", "sum"), ("increase", "sum"),
                                    ("rate", "avg")])
def test_the_reference_is_a_per_sample_loop_over_the_samples_that_exist(
        fn, agg):
    mod = histrig.bench_module("references", "churned_scrapes")
    ts_row, vals, phase, exists, wends = _ref_case()
    base_ids = np.arange(len(vals)) % 3
    panel = {"fn": fn, "agg": agg, "by": ["g"]}
    ref = mod.Reference(ts_row, wends, RANGE, [panel], 3)
    ref.add(vals[:6], base_ids[:6], phase[:6], exists[:6])
    ref.add(vals[6:], base_ids[6:], phase[6:], exists[6:])
    got = ref.table(panel, np.array([0, 1, 0]))
    want, cnt = np.zeros((2, len(wends))), np.zeros((2, len(wends)))
    for s in range(len(vals)):
        ts = (ts_row + phase[s])[exists[s]]
        corr = np.array(oracle.correct_counter(list(vals[s][exists[s]])))
        for w, we in enumerate(wends):
            m = (ts > we - RANGE) & (ts <= we)
            if m.sum() < 2:
                continue
            t, v = ts[m], corr[m]
            g = (0, 1, 0)[base_ids[s]]
            want[g, w] += oracle.extrapolated_rate(
                we - RANGE, we, int(m.sum()), t[0], v[0], t[-1], v[-1],
                True, fn == "rate")
            cnt[g, w] += 1
    if agg == "avg":
        want = want / np.where(cnt > 0, cnt, 1)
    want[cnt == 0] = np.nan
    assert np.isnan(want).any() and not np.isnan(want).all()
    np.testing.assert_allclose(got, want, rtol=1e-12, equal_nan=True)


@pytest.mark.parametrize("panel", [
    {"fn": "rate", "agg": "sum", "by": ["g"]},
    {"fn": "increase", "agg": "avg", "by": ["g"]}], ids=["rate", "increase"])
def test_with_every_sample_present_the_reference_is_scrape_offsets(panel):
    ts_row, vals, phase, exists, wends = _ref_case(3)
    base_ids = np.arange(len(vals)) % 4
    mine = histrig.bench_module("references", "churned_scrapes").Reference(
        ts_row, wends, RANGE, [panel], 4)
    twin = histrig.bench_module("references", "scrape_offsets").Reference(
        ts_row, wends, RANGE, [panel], 4)
    mine.add(vals, base_ids, phase, np.ones_like(exists))
    twin.add(vals, base_ids, phase)
    fold = np.array([0, 1, 1, -1])
    np.testing.assert_array_equal(mine.table(panel, fold),
                                  twin.table(panel, fold))


def test_lives_and_outages_follow_from_the_seed_alone():
    mod = histrig.bench_module("loaders", "churned_scrapes")
    cfg = _churn_cfg(series=2000, samples=240)
    a, b, c = (mod.lives(s, cfg) for s in (7, 7, 8))
    for x, y in zip(a[:3] + a[3], b[:3] + b[3]):
        np.testing.assert_array_equal(x, y)
    assert any((x != y).any() for x, y in zip(a[:3], c[:3]))
    target, first, end, (series, start, length) = a
    P = cfg["churn"]["period_samples"]
    N = 2000 + 3 * 20                   # 1% of 2,000, three times
    assert len(target) == len(first) == len(end) == N
    assert (first[:2000] == 0).all() and (target[:2000] == np.arange(2000)).all()
    for j in (1, 2, 3):
        new = slice(2000 + (j - 1) * 20, 2000 + j * 20)
        assert (first[new] == j * P).all()
        assert len(set(target[new])) == 20          # without replacement
        assert (np.bincount(end, minlength=241)[j * P]) == 20
    # 2,000 targets live at every instant
    pos = np.arange(240)
    alive = ((pos[None, :] >= first[:, None]) & (pos[None, :] < end[:, None]))
    assert (alive.sum(axis=0) == 2000).all()
    # every outage lies inside one period and inside its series' life
    assert len(series) == 4 * 10 and length.min() >= 2 and length.max() <= 6
    assert (start // P == (start + length - 1) // P).all()
    assert (first[series] <= start).all() and (start + length <= end[series]).all()
    mask = mod.existing(first, end, (series, start, length), 0, N, 240)
    assert mask.sum() == alive.sum() - length.sum()
    row, a0, b0 = mod.runs(mask)
    assert (np.diff(row) >= 0).all() and (b0 > a0).all()
    assert (b0 - a0).sum() == mask.sum()
    # the offsets of the first `series` rows are the twin's of that seed
    offs = histrig.bench_module("loaders", "scrape_offsets").scrape_offsets
    np.testing.assert_array_equal(offs(7, STEP, N)[:2000], offs(7, STEP, 2000))


def test_the_loaders_question(monkeypatch):
    mod = histrig.bench_module("loaders", "churned_scrapes")
    cfg = _churn_cfg()
    mod.require_slot_placement(cfg)                 # this program: yes
    # a program whose mirror requires equal counts: no, in seconds
    monkeypatch.setattr(devicecache, "_place_on_grid", lambda *a: 3)
    with pytest.raises(RuntimeError, match="not fusable"):
        mod.require_slot_placement(cfg)


def test_costs_ragged_is_a_hand_count():
    cost = histrig.bench_module("", "costs_ragged").ragged_phased_launch(
        series=1000, span_s=3600, range_s=300, step_s=60, scrape_ms=10_000,
        groups=10)
    cols, windows = 390, 61             # (3600 + 300) / 10; 3600 / 60 + 1
    assert cost["bytes"] == 1000 * cols * 4 + 1000 * 12 \
        + 2 * 10 * windows * 4 == 1_576_880
    assert cost["flops"] == 1000 * cols * 4 \
        + 1000 * windows * (12 + 4 * 10) == 4_732_000
    dense = histrig.bench_module("", "costs").fused_leaf(
        series=1000, span_s=3600, range_s=300, step_s=60, scrape_ms=10_000,
        groups=10)
    # the phases and the second output, beside what a dense leaf needs
    assert cost["bytes"] - dense["bytes"] == 1000 * 4 + 10 * windows * 4
