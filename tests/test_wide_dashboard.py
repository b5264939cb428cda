"""A dashboard as Grafana opens it (ISSUE 44): requests of more than 128
windows over rows of up to 2,304 samples, through the leaf's normal route
(`QueryEngine.query_range` -> `leafexec` -> `FusedDispatch`, interpret-mode
kernels), every kind whose boundaries `_gather_cols` selects, against an f64
oracle written here.  Before PR 44 none of these lowered on a chip past one
tile of 128 windows and no test asked more than 110.

What each case holds: the answers (relative 2e-5, the cells' limit), that the
leaf was a fused dispatch (`leaf_fused_kernel_total` +1) with
`leaf_fused_errors_total` and `leaf_general_path_total` +0; or, where the
kernel's block fits VMEM at no size (`pick_block` None: the ragged rate
family's band, twice, past Wp 256 at Tp 2,304), that it was DECLINED by that
guard (`leaf_general_path_total` +1, errors still 0) and answered all the
same."""
import numpy as np
import pytest

from filodb_tpu.core.records import RecordBatch
from filodb_tpu.ingest.generator import counter_batch, gauge_batch
from filodb_tpu.ops import pallas_fused as pf
from filodb_tpu.utils.metrics import registry

from test_query_engine import _mk_engine

START_MS = 1_600_000_000_000
SCRAPE_MS, SERIES = 10_000, 12
TOL = 2e-5
SHAPES = [(T, W) for T in (768, 2304) for W in (129, 300, 721)]


@pytest.fixture(scope="module", autouse=True)
def interpret_kernels():
    import histrig
    with histrig.environ(FILODB_TPU_FUSED_INTERPRET="1"):
        yield


# ---- the oracle: f64, one series at a time on its own timestamps

def _corrected(v):
    """Counter resets walked out of a row of samples (NaN: no sample)."""
    out, add, prev = np.full(len(v), np.nan), 0.0, None
    for i, x in enumerate(v):
        if x == x:
            if prev is not None and x < prev:
                add += prev
            prev = x
            out[i] = x + add
    return out


def _oracle_series(ts, v, wends, range_ms, fn):
    """One series' value a window (NaN: absent), Prometheus' rules: samples
    in (wend - range, wend]; extrapolatedRate for rate / increase / delta."""
    live = v == v
    ts, v = ts[live], (_corrected(v) if fn in ("rate", "increase") else v)[live]
    lo = np.searchsorted(ts, wends - range_ms + 1, side="left")
    hi = np.searchsorted(ts, wends, side="right") - 1
    n = hi - lo + 1
    out = np.full(len(wends), np.nan)
    if fn == "last_over_time":
        ok = n >= 1
        out[ok] = v[hi[ok]]
        return out
    ok = n >= 2
    lo, hi, n, we = lo[ok], hi[ok], n[ok], wends[ok].astype(np.float64)
    t1, t2, v1, v2 = ts[lo] * 1.0, ts[hi] * 1.0, v[lo], v[hi]
    start = (t1 - (we - range_ms)) / 1000.0
    end = (we - t2) / 1000.0
    sampled = (t2 - t1) / 1000.0
    avg = sampled / (n - 1)
    delta = v2 - v1
    if fn in ("rate", "increase"):
        with np.errstate(divide="ignore", invalid="ignore"):
            zero = sampled * (v1 / delta)
        start = np.where((delta > 0) & (v1 >= 0) & (zero < start), zero, start)
    ext = sampled + np.where(start < avg * 1.1, start, avg / 2) \
        + np.where(end < avg * 1.1, end, avg / 2)
    out[ok] = delta * (ext / sampled)
    if fn == "rate":
        out[ok] /= range_ms / 1000.0
    return out


def _oracle(store, wends, range_ms, fn):
    """sum by (_ns_): {ns: [W]} over the store's rows (NaN: no series there)."""
    out = {}
    for ns, ts, v in store:
        row = _oracle_series(ts, v, wends, range_ms, fn)
        have = out.get(ns)
        out[ns] = row if have is None else np.where(
            have == have, have + np.where(row == row, row, 0.0), row)
    return out


# ---- the stores: one shard, 12 series, T samples

class Store:
    """An engine over one shard and the rows it holds, for the oracle."""

    def __init__(self, batch, metric):
        self.engine, self.metric = _mk_engine([batch]), metric
        S = len(batch.part_keys)
        T = len(batch.timestamps) // S
        col = next(iter(batch.columns.values()))
        self.rows = [(pk.label("_ns_"), batch.timestamps[i * T:(i + 1) * T],
                      np.asarray(col[i * T:(i + 1) * T], np.float64))
                     for i, pk in enumerate(batch.part_keys)]
        self.newest_ms = int(batch.timestamps.max())

    def __iter__(self):
        return iter(self.rows)


def _with(batch, **cols):
    return RecordBatch(batch.schema, batch.part_keys, batch.part_idx,
                       cols.pop("timestamps", batch.timestamps),
                       cols or batch.columns, batch.bucket_les)


def _whole(batch, col):
    """Samples as whole numbers (what a counter of requests is): exact in
    f32, so what is compared is the kernel's arithmetic and not the storage
    of a 40 s difference of values near 20,000."""
    return _with(batch, **{col: np.floor(batch.columns[col])})


def _build(data, T, scrape_ms=SCRAPE_MS):
    seed = T + len(data)
    if data == "gauges":
        return Store(_whole(gauge_batch(SERIES, T, start_ms=START_MS,
                                        seed=seed), "value"), "heap_usage")
    batch = _whole(counter_batch(SERIES, T, start_ms=START_MS, seed=seed,
                                 step_ms=scrape_ms), "count")
    if data == "holed":
        # scrapes that failed: NaN on one shared row (the ragged variants)
        vals = batch.columns["count"].copy()
        vals[np.random.default_rng(seed).random(vals.shape) < 0.08] = np.nan
        batch = _with(batch, count=vals)
    elif data == "offsets":
        # a scrape offset a target (the phased variant), whole ms
        phase = np.random.default_rng(seed).integers(1, scrape_ms, SERIES)
        batch = _with(batch, timestamps=batch.timestamps
                      + phase[batch.part_idx])
    return Store(batch, "request_total")


_STORES = {}


@pytest.fixture(scope="module")
def store_of():
    def get(data, T, scrape_ms=SCRAPE_MS):
        if (data, T, scrape_ms) not in _STORES:
            _STORES[data, T, scrape_ms] = _build(data, T, scrape_ms)
        return _STORES[data, T, scrape_ms]
    yield get
    _STORES.clear()


def _families():
    return tuple(registry.counter(n).value for n in (
        "leaf_fused_kernel", "leaf_fused_errors", "leaf_general_path",
        "leaf_phase_fused", "leaf_ragged_fused"))


def _ask(store, fn, W, step_s, range_s, end_back_s=7):
    """(answers {ns: [W]}, what the five leaf counters moved by)."""
    end = store.newest_ms // 1000 - end_back_s
    start = end - (W - 1) * step_s
    sel = f'{store.metric}{{_ws_="demo"}}'
    promql = f"sum by (_ns_)({sel})" if fn == "last_over_time" \
        else f"sum by (_ns_)({fn}({sel}[{range_s}s]))"
    before = _families()
    res = store.engine.query_range(promql, start, step_s, end)
    assert res.error is None, res.error
    moved = tuple(a - b for a, b in zip(_families(), before))
    got = {k.labels_dict["_ns_"]: np.asarray(v, np.float64)
           for k, _, v in res.series()}
    wends = (start + np.arange(W) * step_s) * 1000
    return got, wends, moved


def _hold(got, want):
    assert set(got) == {ns for ns, row in want.items()
                        if (row == row).any()}
    worst = 0.0
    for ns, row in got.items():
        ref = want[ns]
        assert ((row == row) == (ref == ref)).all(), ns
        ok = ref == ref
        scale = np.maximum(np.abs(ref[ok]), 1e-9)
        worst = max(worst, float((np.abs(row[ok] - ref[ok]) / scale).max()))
    assert worst <= TOL, worst


KINDS = [("rate", "counters"), ("increase", "counters"), ("delta", "gauges"),
         ("last_over_time", "gauges"), ("rate", "holed"), ("rate", "offsets")]


@pytest.mark.parametrize("T,W", SHAPES, ids=[f"T{t}-W{w}" for t, w in SHAPES])
@pytest.mark.parametrize("fn,data", KINDS,
                         ids=[f"{f}-{d}" for f, d in KINDS])
def test_a_wide_request_is_one_fused_leaf_and_right(store_of, fn, data, T, W):
    # rows at scrape offsets hold 2,304 slots inside f32's exact 4.66 h only
    # at a 5 s scrape (at 10 s the mirror declines the grid: the test below)
    scrape_s = 5 if data == "offsets" and T == 2304 else SCRAPE_MS // 1000
    store = store_of(data, T, scrape_s * 1000)
    # a panel's resolution: the step that spreads W windows over the row,
    # a window of four scrapes (Grafana's $__rate_interval)
    step_s = max((T - 8) * scrape_s // W // scrape_s * scrape_s, scrape_s)
    # instant selectors look back the default 5 minutes
    range_s = 300 if fn == "last_over_time" else 4 * scrape_s
    got, wends, moved = _ask(store, fn, W, step_s, range_s)
    fused, errors, general, phased, ragged = moved
    kind = fn if fn == "last_over_time" else "rate_family"
    fits = pf.pick_block(pf._pad_to(T + (data == "offsets"), 128),
                         pf._pad_to(W, 128), 16, kind, data == "holed",
                         phased=data == "offsets") is not None
    assert fits == (not (data == "holed" and T == 2304 and W > 256))
    assert errors == 0
    assert (fused, general) == ((1, 0) if fits else (0, 1))
    assert phased == (1 if data == "offsets" else 0)
    assert ragged == (1 if data == "holed" and fits else 0)
    _hold(got, _oracle(store, wends, range_s * 1000, fn))


def test_slot_times_past_f32s_exact_range_are_answered_exactly(store_of):
    """2,304 slots at 10 s reach 23,030,000 ms, past 2^24 (16,777,216): the
    shared row's times and whole-second window edges are multiples of 1,000
    ms, exact in f32 to 2^27, which the plan checks (`FusedPlan.exact`);
    windows that end in the row's last half hour read slots past 2^24 and
    come out right."""
    store = store_of("counters", 2304)
    got, wends, moved = _ask(store, "rate", 129, 10, 40, end_back_s=3)
    assert (wends - START_MS).min() > 1 << 24
    assert moved[:3] == (1, 0, 0)
    _hold(got, _oracle(store, wends, 40_000, "rate"))


def test_a_phase_grid_past_f32s_exact_range_is_declined_by_name(store_of):
    """2,304 slots at 10 s with a scrape offset a target: a row's time is
    the base row's plus its phase, odd milliseconds past 2^24.  The dense
    phase grid checks what the placed one checks (`_slot_times_exact`):
    the mirror declines the grid (`device_mirror_inexact_grids_total`, every
    row off the grid), the leaf goes the general path (`leaf_offgrid_total`)
    and the answers are the oracle's.  At 1,600 slots the same rows fuse."""
    from filodb_tpu.core.devicecache import _detect_phase_grid
    booked = registry.counter("device_mirror_inexact_grids")
    before = booked.value, registry.counter("leaf_offgrid").value
    store = store_of("offsets", 2304)
    got, wends, moved = _ask(store, "rate", 300, 60, 40)
    assert moved == (0, 0, 1, 0, 0)
    assert booked.value > before[0]
    assert registry.counter("leaf_offgrid").value == before[1] + 1
    _hold(got, _oracle(store, wends, 40_000, "rate"))
    # the detector alone, at either side of the limit: 1,677 slots and a
    # phase reach 16,780,000 ms
    phase = np.arange(1, 5)[:, None] * 1_111
    for slots, fits in ((1_600, True), (1_676, True), (1_677, False),
                        (2_304, False)):
        off = (np.arange(slots) * SCRAPE_MS)[None, :] + phase
        row0, ph, offgrid = _detect_phase_grid(
            off.astype(np.int32), np.full(4, slots))
        assert (row0 is not None, offgrid) == (fits, 0 if fits else 4)
    # ... and one shared row of any length has no phase to add
    off = np.broadcast_to(np.arange(2_304) * SCRAPE_MS, (4, 2_304))
    assert _detect_phase_grid(off.astype(np.int32), np.full(4, 2_304))[2] == 0


def test_a_plan_whose_times_f32_cannot_hold_is_declined_by_name():
    """A shared row at odd milliseconds past 4.66 h: the kernel's f32 times
    would be a millisecond off.  `build_plan` says so, the leaf declines
    (`leaf_inexact_times_total`) and the general path answers."""
    T = 2304
    batch = _whole(counter_batch(SERIES, T, start_ms=START_MS, seed=5),
                   "count")
    # every scrape 10,001 ms after the one before: the row's offsets from
    # its first sample are odd past slot 1,677
    drift = np.tile(np.arange(T, dtype=np.int64), SERIES)
    store = Store(_with(batch, timestamps=batch.timestamps + drift),
                  "request_total")
    ts = store.rows[0][1] - store.rows[0][1][0]
    plan = pf.build_plan(ts, ts[-1] - 3_000 - np.arange(300)[::-1] * 60_000,
                         40_000)
    assert not plan.exact
    assert pf.build_plan(ts[:1600], ts[1599] - np.arange(30)[::-1] * 60_000,
                         40_000).exact
    before = registry.counter("leaf_inexact_times").value
    got, wends, moved = _ask(store, "rate", 300, 60, 40)
    assert registry.counter("leaf_inexact_times").value == before + 1
    assert moved[:3] == (0, 0, 1)
    _hold(got, _oracle(store, wends, 40_000, "rate"))


# ---- the gather alone: tile ranges, and narrowed equal to wide

def _launch(plan, vals, gids, fn, wide, phase=None, ragged=False):
    """`fused_rate_groupsum` with the gathers' tile ranges as the plan gives
    them, or (`wide`) opened to every tile of the row."""
    real = pf._tile_ranges

    def every_tile(xp, rows, Tp, phased, c0=0):
        n = rows.shape[1] // 128
        return xp.stack([xp.zeros(n), xp.full(n, Tp // 128 - 1)]
                        ).astype(xp.int32)
    if wide:
        pf._tile_ranges = every_tile
        pf._run.clear_cache()
        pf._run_set.clear_cache()
    try:
        sums, counts = pf.fused_rate_groupsum(
            vals, np.zeros(len(vals), np.float32), gids, plan, 4, fn,
            precorrected=True, interpret=True, phase=phase, ragged=ragged)
        return np.asarray(sums), np.asarray(counts)
    finally:
        if wide:
            pf._tile_ranges = real
            pf._run.clear_cache()
            pf._run_set.clear_cache()


@pytest.mark.parametrize("fn,phased,ragged", [
    ("rate", False, False), ("delta", True, False),
    ("last_over_time", False, False), ("increase", False, True),
    ("rate", True, True), ("sum_over_time", True, True)])
def test_the_narrowed_gather_is_the_gather_over_every_tile_bit_for_bit(
        fn, phased, ragged):
    """The same launch with each window tile visiting its own row tiles and
    with every tile visited: sums and counts equal to the bit, on a grid
    whose first window tile straddles a row-tile edge (its windows' slots
    run from row tile 0 into tile 2), whose last tile holds padded windows,
    and with empty windows before the data."""
    T, W = 700, 300
    rng = np.random.default_rng(11)
    ts = np.arange(T, dtype=np.int64) * SCRAPE_MS
    wends = np.concatenate([[-90_000, -30_000],
                            5_000 + np.arange(W - 2) * 23_000])
    plan = pf.build_plan(ts, wends, 40_000)
    tiles = pf._tile_ranges(np, plan.prows if phased else plan.rows, plan.Tp,
                            phased)
    assert tiles[0].tolist() == [0, 2, 4] and tiles[1].tolist() == [2, 4, 5]
    assert plan.tile_visits[phased] == 8 < 3 * 6
    vals = np.cumsum(rng.random((40, T)), axis=1).astype(np.float32)
    if ragged:
        vals[rng.random(vals.shape) < 0.1] = np.nan
    gids = (np.arange(40) % 4).astype(np.int32)
    phase = rng.integers(1, SCRAPE_MS, 40) if phased else None
    narrow = _launch(plan, vals, gids, fn, False, phase, ragged)
    wide = _launch(plan, vals, gids, fn, True, phase, ragged)
    for a, b in zip(narrow, wide):
        assert a.tobytes() == b.tobytes()
    assert np.isfinite(narrow[0]).all() and narrow[1][:, 2:].any()


def test_tile_ranges_on_the_host_are_the_kernels_on_the_device():
    """`build_plan` counts the visits from `_tile_ranges(np, ...)`; the
    kernel's scalar operand is `_tile_ranges(jnp, ...)` inside `_run`'s
    trace: one function, equal on both, for one shared row and a phase
    grid; a tile of nothing but empty windows visits nothing."""
    import jax.numpy as jnp
    ts = np.arange(2304, dtype=np.int64) * SCRAPE_MS
    wends = ts[-1] - 2_000 - np.arange(721)[::-1] * 30_000
    plan = pf.build_plan(ts, wends, 40_000)
    for phased, rows in ((False, plan.rows), (True, plan.prows)):
        host = pf._tile_ranges(np, rows, plan.Tp, phased)
        dev = np.asarray(pf._tile_ranges(jnp, jnp.asarray(rows), plan.Tp,
                                         phased))
        assert host.dtype == dev.dtype == np.int32
        np.testing.assert_array_equal(host, dev)
        assert host.shape == (2, 6) and (host[1] - host[0] <= 4).all()
    assert plan.tile_visits == (22, 22)     # of 6 x 18 = 108
    hour = pf.build_plan(ts[:720], ts[719] - 5_000
                         - np.arange(61)[::-1] * 60_000, 300_000)
    # one window tile over six row tiles: few enough pairs to visit them
    # all unrolled (`gather_loops`), whatever the ranges say (tiles 2 to 5)
    assert not pf.gather_loops(hour.Tp, 128) and pf.gather_loops(2304, 768)
    # (the kernel computes over the 512 columns the windows reach, from
    # column 256: four tiles a gather, where the row has six)
    assert (hour.Tq, hour.c0) == (512, (256, 256))
    assert hour.tile_visits == (4, 4)
    assert pf._tile_ranges(np, hour.rows, hour.Tp, False).tolist() \
        == [[2], [5]]
    assert pf._tile_ranges(np, hour.rows, hour.Tq, False, 256).tolist() \
        == [[0], [3]]
    empty = pf.build_plan(ts[:720], -np.arange(1, 200)[::-1] * 60_000, 40_000)
    assert pf._tile_ranges(np, empty.rows, empty.Tp, False).tolist() \
        == [[6, 6], [-1, -1]]
    # (windows that reach one slot get a row block of one tile: two pairs,
    # few enough to visit unrolled, where the row's six tiles were looped
    # over and none visited)
    assert empty.Tq == 128 and not pf.gather_loops(empty.Tq, 256)
    assert empty.tile_visits[0] == 2


def test_a_launch_books_its_windows_and_the_tiles_its_gathers_visit():
    """`fused_windows_total` +W and `fused_gather_tile_visits_total` +sets x
    gathers x the plan's visits, where `fused_enqueues_total` is booked."""
    ts = np.arange(768, dtype=np.int64) * SCRAPE_MS
    plan = pf.build_plan(ts, ts[-1] - np.arange(300)[::-1] * 20_000, 40_000)
    vals = np.cumsum(np.ones((40, 768), np.float32), axis=1)
    gids = np.zeros(40, np.int32)
    names = ("fused_enqueues", "fused_windows", "fused_gather_tile_visits")
    for fn, ragged, gathers in (("rate", False, 2), ("last_over_time", False, 1),
                                ("rate", True, 4), ("sum_over_time", False, 0)):
        before = [registry.counter(n).value for n in names]
        pf.fused_rate_groupsum(vals, np.zeros(40, np.float32), gids, plan, 1,
                               fn, precorrected=True, interpret=True,
                               ragged=ragged)
        moved = [registry.counter(n).value - b
                 for n, b in zip(names, before)]
        assert moved == [1, 300, gathers * plan.tile_visits[0]], fn
        assert pf.gathers("rate_family" if fn == "rate" else fn, ragged,
                          False) == gathers


def test_a_wide_matrix_is_presented_point_for_point():
    """`to_prom_matrix` takes a row's points out of NumPy in one call and
    formats a row without an infinity without asking each point: the
    payload is what one `_fmt` a point gave, for whole rows, rows with
    absent windows, infinities, and a row that is absent throughout."""
    from filodb_tpu.query.engine import QueryEngine, _fmt
    from filodb_tpu.query.rangevector import (QueryResult, RangeVectorKey,
                                              ResultBlock)
    rng = np.random.default_rng(7)
    vals = rng.standard_normal((5, 721)) * 10.0 ** rng.integers(-9, 9, (5, 1))
    vals[1, rng.random(721) < 0.3] = np.nan
    vals[2, ::50] = np.inf
    vals[2, 7::90] = -np.inf
    vals[2, 3::40] = np.nan
    vals[3] = np.nan
    vals[4, 0] = 0.1
    wends = (1_600_000_007 + np.arange(721) * 30) * 1000
    keys = [RangeVectorKey.make({"_ns_": f"App-{i}"}) for i in range(5)]
    got = QueryEngine.to_prom_matrix(
        QueryResult([ResultBlock(keys, wends, vals)]))["data"]["result"]
    assert [r["metric"]["_ns_"] for r in got] \
        == ["App-0", "App-1", "App-2", "App-4"]
    for r, i in zip(got, (0, 1, 2, 4)):
        want = [[int(t) / 1000.0, _fmt(v)] for t, v in zip(wends, vals[i])
                if v == v]
        assert r["values"] == want
    assert got[3]["values"][0] == [1_600_000_007.0, "0.10000000000000001"]
    assert ["+Inf", "-Inf"] == [got[2]["values"][0][1],
                                [v for _, v in got[2]["values"]
                                 if v.startswith("-I")][0]]
