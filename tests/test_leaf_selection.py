"""A leaf's selection is looked up once, not per open (ISSUE 32): the lookup
memo is keyed by (filters, limit) and stamped with (index.mutations,
keys_epoch), not by the range; the facts a leaf reads off its rows (counts,
extents, the scan estimate's inputs, the paging verdict's two scalars) are
stamped with the store's generation.  A request whose range moved one step
makes no call of the index, of `_estimate_scan` or of the paging check's
array half; whatever the answer was derived from changing refills it."""
import json
import os
import threading
import urllib.request

import numpy as np
import pytest

import filodb_tpu.query.leafexec as leafexec
from filodb_tpu.config import FilodbSettings
from filodb_tpu.core.blockstore import DenseSeriesStore
from filodb_tpu.core.index import Equals, PartKeyIndex
from filodb_tpu.core.memstore import TimeSeriesMemStore
from filodb_tpu.core.partkey import PartKey
from filodb_tpu.core.schemas import DEFAULT_SCHEMAS
from filodb_tpu.core.shard import SelectionFacts, TimeSeriesShard
from filodb_tpu.ingest.generator import gauge_batch
from filodb_tpu.persist import LocalDiskColumnStore, LocalDiskMetaStore
from filodb_tpu.parallel.shardmapper import (ShardEvent, ShardMapper,
                                             SpreadProvider)
from filodb_tpu.query.engine import QueryEngine
from filodb_tpu.query.leafexec import _estimate_scan, leaf_route
from filodb_tpu.query.rangevector import PlannerParams
from filodb_tpu.standalone import DatasetConfig, FiloServer
from filodb_tpu.utils.metrics import registry

START = 1_600_000_000_000
STEP = 10_000


def fills():
    return {dict(tags).get("cause"): val
            for name, tags, val in registry.snapshot_samples()
            if name == "leaf_selection_fills_total"}


def fills_since(before):
    now = fills()
    return {c: now[c] - before.get(c, 0.0) for c in now
            if now[c] != before.get(c, 0.0)}


# ---------------------------------------------------------------- (a) ranges

LATE = START + 100 * STEP           # the second wave's first sample
ENDED = START + 40 * STEP           # where three of the first wave end


@pytest.fixture(scope="module")
def lives():
    """40 gauges: 20 alive from START, 20 more from LATE; three of the first
    wave ended at ENDED, ENDED + 1 step, ENDED + 2 steps."""
    ms = TimeSeriesMemStore()
    shard = ms.setup("prometheus", 0)
    shard.ingest(gauge_batch(20, 50, start_ms=START), offset=1)
    shard.ingest(gauge_batch(40, 50, start_ms=LATE), offset=2)
    for k, pid in enumerate((3, 7, 11)):
        shard.index.update_end_time(pid, ENDED + k * STEP)
    return shard


RANGES = [
    ("inside every life", LATE + STEP, LATE + 20 * STEP),
    ("all of time", 0, 1 << 62),
    ("cuts the late starts", START, LATE - STEP),
    ("ends on the first late start", START, LATE),
    ("cuts the ended ones", ENDED + STEP + 1, LATE + STEP),
    ("cuts both", ENDED + 3 * STEP, LATE - 1),
    ("before anything", 0, START - 1),
    ("one instant, on an end", ENDED, ENDED),
]


@pytest.mark.parametrize("limit", [None, 5, 1000])
@pytest.mark.parametrize("filt", [(), (Equals("_ns_", "App-3"),)],
                         ids=["all", "App-3"])
@pytest.mark.parametrize("what,start,end", RANGES,
                         ids=[r[0].replace(" ", "-") for r in RANGES])
def test_any_range_gets_the_index_own_answer(lives, what, start, end, filt,
                                             limit):
    """One filled entry answers every range element for element as
    `part_ids_from_filters` does (a subsequence of a stably sorted
    sequence is the stably sorted subsequence)."""
    lives.lookup_partitions(filt, 0, 1 << 62, limit)       # the entry
    before = fills()
    got = lives.lookup_partitions(filt, start, end, limit)
    assert not fills_since(before), "the range is no part of the key"
    want = lives.index.part_ids_from_filters(filt, start, end, limit)
    assert got.part_ids.tolist() == want.tolist()
    assert not got.part_ids.flags.writeable
    if want.size:
        assert got.pids_by_schema["gauge"].tolist() == want.tolist()
        assert got.first_schema == "gauge"
    else:
        assert got.first_schema is None and not got.pids_by_schema


def test_ranges_that_hold_every_life_share_one_object(lives):
    filt = [Equals("_ns_", "App-5")]      # none of the three ended ones
    a = lives.lookup_partitions(filt, LATE + STEP, LATE + 2 * STEP)
    b = lives.lookup_partitions(filt, LATE + 2 * STEP, LATE + 9 * STEP)
    assert a is b and a.shared
    assert a.selection("gauge") is b.selection("gauge")
    cut = lives.lookup_partitions(filt, START, LATE - 1)
    assert cut is not a and not cut.shared


def test_ranges_that_leave_the_same_series_out_share_one_object(lives):
    """A range that cuts lives is answered by the series it leaves out, and
    those are named by two ranks in the entry's sorted lives (two binary
    searches; ISSUE 50): ranges one step apart that pass no birth and no
    death get ONE object, and a hit lays no mask over the lives (they are
    swapped for an object that raises on any read)."""
    filt = ()
    lives.lookup_partitions(filt, 0, 1 << 62)
    ent = lives._lookup_cache[((), None)]
    assert ent.whole.part_ids.size == 40
    ent.parts.clear()               # the tests before this one laid masks
    a = lives.lookup_partitions(filt, ENDED + 3 * STEP, LATE - 5 * STEP)
    assert ent.starts.tolist() == sorted(ent.start.tolist())
    assert ent.ends.tolist() == sorted(ent.end.tolist())
    before = fills()

    class Tripwire:
        def _read(self, *a, **k):
            raise AssertionError("a hit read the lives")
        __le__ = __ge__ = __getitem__ = __array__ = __len__ = _read

    start, end = ent.start, ent.end
    ent.start = ent.end = Tripwire()
    try:
        for k in range(1, 4):
            b = lives.lookup_partitions(filt, ENDED + (3 + k) * STEP,
                                        LATE - (5 - k) * STEP)
            assert b is a and b.within is ent.whole and not b.shared
        # ... and a range that passes a death or a birth is a miss
        with pytest.raises(AssertionError, match="read the lives"):
            lives.lookup_partitions(filt, ENDED + STEP, LATE - 5 * STEP)
        with pytest.raises(AssertionError, match="read the lives"):
            lives.lookup_partitions(filt, ENDED + 3 * STEP, LATE)
    finally:
        ent.start, ent.end = start, end
    assert not fills_since(before)
    # random ranges: element for element the index's own answer
    rng = np.random.default_rng(50)
    for _ in range(200):
        s, e = np.sort(rng.integers(START - 5 * STEP, LATE + 60 * STEP, 2))
        got = lives.lookup_partitions(filt, int(s), int(e))
        want = lives.index.part_ids_from_filters(filt, int(s), int(e))
        assert got.part_ids.tolist() == want.tolist(), (s, e)


# -------------------------------------------------------------- (b) estimate

def store_of(counts, first=START, step=STEP):
    """A gauge store whose row i holds counts[i] samples from first[i]."""
    counts = np.asarray(counts)
    first = np.broadcast_to(np.asarray(first), counts.shape)
    store = DenseSeriesStore(DEFAULT_SCHEMAS["gauge"])
    rows = np.array([store.new_row() for _ in counts], dtype=np.int64)
    for r, (n, t0) in enumerate(zip(counts.tolist(), first.tolist())):
        if n:
            ts = (t0 + np.arange(n, dtype=np.int64) * step)[None, :]
            store.append_grid(rows[r:r + 1], ts, {"value": ts * 1.0})
    return store, rows


STORES = {
    "uniform": lambda: store_of([720] * 96),
    "ragged counts": lambda: store_of([720, 1, 300, 0, 719, 2] * 16),
    "ragged starts": lambda: store_of(
        [100] * 64, START + np.arange(64) * 7 * STEP),
    # one scrape grid, each row behind it by its target's offset
    "scrape offsets": lambda: store_of(
        [720] * 96, START + (np.arange(96) * 7919) % STEP),
    "empty rows": lambda: store_of([0] * 32),
    "no rows": lambda: store_of([]),
    "one sample a row": lambda: store_of([1] * 40),
}
SPANS = [(START, START + 719 * STEP), (START + 300 * STEP, START + 660 * STEP),
         (0, START - 1), (START + 10 ** 9, START + 2 * 10 ** 9),
         (START + 5 * STEP + 3, START + 5 * STEP + 4), (0, 1 << 62),
         (START + 719 * STEP, START + 719 * STEP)]


def estimate_scan_before(store, rows, start_ms, end_ms):
    """`_estimate_scan` as it stood before ISSUE 32, line for line: the
    reference of what the facts answer."""
    cnt = store.counts[rows].astype(np.int64)
    if store.ts.shape[1] == 0 or not cnt.any():
        return 0
    first = store.ts[rows, 0]
    last = store.ts[rows, np.maximum(cnt - 1, 0)]
    lo = np.maximum(first, start_ms)
    hi = np.minimum(last, end_ms)
    span = np.maximum(last - first, 1).astype(np.float64)
    frac = np.clip((hi - lo).astype(np.float64) / span, 0.0, 1.0)
    est = np.where((cnt > 0) & (hi >= lo), np.maximum(cnt * frac, 1.0), 0.0)
    return int(est.sum())


@pytest.mark.parametrize("span", SPANS, ids=[str(i) for i in range(len(SPANS))])
@pytest.mark.parametrize("shape", list(STORES))
def test_the_estimate_from_facts_is_estimate_scans(shape, span):
    store, rows = STORES[shape]()
    facts = SelectionFacts(store, rows)
    want = estimate_scan_before(store, rows, *span)
    assert _estimate_scan(store, rows, *span) == want
    got = facts.estimate(*span)
    if facts.uniform is None:
        assert got == want          # the same formula over the same arrays
    else:
        # scalar form: one row's estimate times the rows, where
        # _estimate_scan sums S equal floats: the integer within 1
        assert abs(got - want) <= 1
    # rows of one count and one extent answer from four scalars wherever
    # the span clips them alike (ISSUE 37: rows that differ by a phase)
    assert (facts.uniform is not None) == (
        shape in ("uniform", "empty rows", "one sample a row",
                  "ragged starts", "scrape offsets"))
    # the two decisions made from it, away from the last unit
    for cap in (0, 1, want // 2, 2 * want + 2, 2_000_000):
        assert leaf_route(got, 1, cap) == leaf_route(want, 1, cap)
        assert leaf_route(got, 64, cap) == leaf_route(want, 64, cap)
    for limit in (want // 2, 2 * want + 2, 50_000_000):
        assert (got > limit) == (want > limit)
    assert facts.samples == int(store.counts[rows].sum())


def test_facts_paging_verdict_is_the_array_paths(tmp_path):
    """`may_need_paging` on the two scalars says what the array half of
    `ensure_paged_pids` computes row by row, on a store with recovered
    (page-only) rows, live rows, empty rows and paged floors."""
    store, rows = store_of([50, 50, 0, 50, 10, 0])
    store.page_only[[0, 4]] = True        # recovered rows: never appended to
    store.set_paged(0, floor=START - 100 * STEP, ceil=START + 80 * STEP)
    store.set_paged(3, floor=START - 5 * STEP)
    facts = SelectionFacts(store, rows)
    cnt, first, last = store.row_extents(rows)
    for start in (0, START - 101 * STEP, START - 100 * STEP, START - 1,
                  START, START + 60 * STEP):
        for end in (START, START + 9 * STEP, START + 10 * STEP,
                    START + 80 * STEP, START + 81 * STEP, 1 << 62):
            covered = np.minimum(store.paged_floor[rows],
                                 np.where(cnt > 0, first, 1 << 62))
            need = start < covered
            need |= (store.page_only[rows] & (cnt > 0)
                     & (end > np.maximum(store.paged_ceil[rows],
                                         np.where(cnt > 0, last, 0))))
            assert facts.may_need_paging(start, end) == bool(need.any()), \
                (start, end)


# ---------------------------------------------------------- (c) invalidation

def engine_over(ms, shards=1):
    mapper = ShardMapper(shards)
    for s in range(shards):
        mapper.update_from_event(
            ShardEvent("IngestionStarted", "prometheus", s, "local"))
    return QueryEngine("prometheus", ms, mapper,
                       SpreadProvider(default_spread=0))


def count_at(eng, t_ms, promql="count(heap_usage)", scan_limit=None):
    params = None if scan_limit is None \
        else PlannerParams(scan_limit=scan_limit)
    res = eng.query_range(promql, t_ms // 1000, 60, t_ms // 1000, params)
    assert res.error is None, res.error
    if not res.blocks or not res.num_series:
        return None, res
    return float(np.asarray(res.blocks[0].values)[0][-1]), res


@pytest.fixture
def live():
    ms = TimeSeriesMemStore()
    shard = ms.setup("prometheus", 0)
    shard.ingest(gauge_batch(20, 60, start_ms=START), offset=1)
    return shard, engine_over(ms)


def test_an_appended_sample_is_in_the_next_answer(live):
    shard, eng = live
    newest = START + 59 * STEP
    q = "sum(count_over_time(heap_usage[1m]))"
    v0, _ = count_at(eng, newest, q)
    assert v0 == 20 * 6
    before = fills()
    later = gauge_batch(20, 3, start_ms=newest + STEP)
    shard.ingest(later, offset=2)
    v1, res = count_at(eng, newest + 3 * STEP, q)
    assert v1 == 20 * 6, "the window moved onto the appended samples"
    assert res.stats.samples_scanned == 20 * 63
    # the samples moved store.generation: the facts were read again, the
    # postings were not
    assert fills_since(before) == {"generation": 1.0}


def test_a_new_series_appears(live):
    shard, eng = live
    at = START + 59 * STEP
    assert count_at(eng, at)[0] == 20
    before = fills()
    shard.ingest(gauge_batch(25, 60, start_ms=START), offset=2)
    assert count_at(eng, at)[0] == 25
    assert fills_since(before) == {"index": 1.0}


def test_an_ended_series_leaves(live):
    shard, eng = live
    at = START + 59 * STEP
    assert count_at(eng, at)[0] == 20
    before = fills()
    # ended before the query's lookback reaches: the index drops it from
    # this range, and the entry was refilled because its end time moved
    shard.index.update_end_time(4, START + 10 * STEP)
    assert count_at(eng, at)[0] == 19
    assert fills_since(before) == {"index": 1.0, "range": 1.0}
    # ... and evicted: the key epoch moves too
    before = fills()
    assert shard.evict_ended_partitions(START + 20 * STEP) == 1
    assert count_at(eng, at)[0] == 19
    assert set(fills_since(before)) <= {"index", "epoch"}
    assert fills_since(before)


def test_a_series_that_ended_was_evicted_and_came_back_has_its_history():
    """Eviction takes an ended series out of memory and out of the index;
    its flushed chunks stay in the column store under its key.  When the
    key is scraped again ingest creates a new row, whose history lies on
    disk: a query that starts below the row's first sample pages it in
    (the floor of a row that ingest creates is unknown, not 0)."""
    from filodb_tpu.core.store import InMemoryColumnStore, InMemoryMetaStore
    ms = TimeSeriesMemStore(column_store=InMemoryColumnStore(),
                            meta_store=InMemoryMetaStore())
    shard = ms.setup("prometheus", 0)
    eng = engine_over(ms)
    shard.ingest(gauge_batch(4, 60, start_ms=START), offset=1)
    shard.flush_all_groups()
    q = "sum(count_over_time(heap_usage[5m]))"
    at = START + 59 * STEP
    assert count_at(eng, at, q)[0] == 4 * 30
    back = START + 200 * STEP
    for pid in range(4):
        shard.index.update_end_time(pid, at)
    assert shard.evict_ended_partitions(back) == 4
    assert count_at(eng, at, q)[0] is None
    shard.ingest(gauge_batch(4, 10, start_ms=back), offset=2)
    store = shard.stores["gauge"]
    rows = shard.rows_for(shard.lookup_partitions([], 0, 1 << 62).part_ids)
    assert (store.paged_floor[rows] == np.iinfo(np.int64).max).all()
    # (a query that ends before the new life begins is not the row's: its
    # index entry starts at `back`)
    assert count_at(eng, at, q)[0] is None
    res = eng.query_range(q, at // 1000, 60, (back + 9 * STEP) // 1000)
    assert res.error is None, res.error
    assert float(np.asarray(res.blocks[0].values)[0][0]) == 4 * 30
    assert res.stats.samples_paged == 4 * 31     # from the range's start
    # ... and a series that has no history (born inside the range) is
    # asked about once, as far below again as the query reached, and not by
    # every query that starts a step earlier
    shard.ingest(gauge_batch(6, 10, start_ms=back), offset=3)
    fresh = np.setdiff1d(shard.rows_for(shard.lookup_partitions(
        [], 0, 1 << 62).part_ids), rows)
    assert fresh.size == 2
    start = back - 30 * STEP
    assert shard.ensure_paged_pids(
        "gauge", shard.lookup_partitions([], 0, 1 << 62).part_ids,
        start, back + 9 * STEP) == 0
    # (30 steps were asked about, the query is 39 long)
    assert (store.paged_floor[fresh] == start - 39 * STEP).all()
    gen = store.generation
    lk = shard.lookup_partitions([], 0, 1 << 62)
    _, facts = shard.selection_facts(lk, "gauge")
    assert not facts.may_need_paging(start - 39 * STEP, back + 9 * STEP)
    assert facts.may_need_paging(start - 40 * STEP, back + 9 * STEP)
    assert store.generation == gen


def cold_shard(tmp_path, series=4, samples=60):
    """A shard flushed to disk and recovered into a fresh memstore: every
    row is page-only and empty until a query pages it in."""
    cs = LocalDiskColumnStore(str(tmp_path))
    ms = TimeSeriesMemStore(column_store=cs,
                            meta_store=LocalDiskMetaStore(str(tmp_path)))
    shard = ms.setup("prometheus", 0)
    shard.ingest(gauge_batch(series, samples, start_ms=START), offset=1)
    shard.flush_all_groups()
    cs.close()
    ms2 = TimeSeriesMemStore(
        column_store=LocalDiskColumnStore(str(tmp_path)),
        meta_store=LocalDiskMetaStore(str(tmp_path)))
    sh2 = ms2.setup("prometheus", 0)
    assert sh2.recover_index() == series
    return sh2, engine_over(ms2)


def test_paging_that_pages_is_followed_by_a_fresh_estimate(tmp_path,
                                                           monkeypatch):
    shard, eng = cold_shard(tmp_path)
    estimates = []
    real = SelectionFacts.estimate
    monkeypatch.setattr(
        SelectionFacts, "estimate",
        lambda self, s, e: estimates.append(real(self, s, e)) or
        estimates[-1])
    at = START + 59 * STEP
    # a leaf whose rows hold no more samples in all than the scan cap
    # estimates nothing (ISSUE 50): nothing resident, then the 84 samples
    # an earlier instant's lookback pages in
    v, res = count_at(eng, START + 20 * STEP, scan_limit=150)
    assert v == 4 and res.stats.samples_paged == 84 and not estimates
    # the newest instant pages the other 156: 240 resident pass the cap of
    # 150, so the leaf estimates, from facts read AFTER the paging moved
    # the store's generation (the range's share of the rows, 122; the
    # stale facts' rows end before the range and would say 4)
    v, res = count_at(eng, at, scan_limit=150)
    assert v == 4 and res.stats.samples_paged == 156
    assert estimates == [122]
    # the same leaf again: resident now, one estimate, nothing paged
    del estimates[:]
    v, res = count_at(eng, at, scan_limit=150)
    assert v == 4 and res.stats.samples_paged == 0
    assert estimates == [122]
    # ... under the default cap no leaf estimates at all, and a cap the
    # estimate passes refuses the query as before
    del estimates[:]
    v, res = count_at(eng, at)
    assert v == 4 and not estimates
    with pytest.raises(AssertionError, match="over the scan limit 100"):
        count_at(eng, at, scan_limit=100)


def test_eviction_forces_a_fill_and_a_repage(tmp_path):
    shard, eng = cold_shard(tmp_path)
    at = START + 59 * STEP
    q = "sum(count_over_time(heap_usage[10m]))"
    v, res = count_at(eng, at, q)
    assert v == 4 * 60 and res.stats.samples_paged == 4 * 60
    v, res = count_at(eng, at, q)
    assert res.stats.samples_paged == 0
    store = shard.stores["gauge"]
    before, shift0 = fills(), store.shift_version
    store.evict_oldest(30)                  # the first 30 are disk-only again
    assert store.shift_version == shift0 + 1
    v, res = count_at(eng, at, q)
    assert v == 4 * 60, "the evicted samples were paged back"
    assert res.stats.samples_paged == 4 * 30
    # once for the eviction, once more after the paging it caused
    assert fills_since(before) == {"generation": 2.0}


def test_bookkeeping_only_paging_moves_the_generation(tmp_path):
    """A page-in that finds nothing on disk still writes paged_floor:
    the write lies inside store.mutation(), so facts read before it are
    stale by their stamp."""
    shard, _ = cold_shard(tmp_path)
    lookup = shard.lookup_partitions([], 0, 1 << 62)
    sel, facts = shard.selection_facts(lookup, "gauge")
    store = shard.stores["gauge"]
    assert facts.may_need_paging(START - 10 ** 7, START - 10 ** 6)
    gen = store.generation
    # a range before any chunk: nothing to page, the floor moves
    assert shard.ensure_paged_pids("gauge", sel.pids, START - 10 ** 7,
                                   START - 10 ** 6, facts=facts) == 0
    assert store.generation > gen and store.generation % 2 == 0
    sel, again = shard.selection_facts(lookup, "gauge")
    assert again is not facts
    assert not again.may_need_paging(START - 10 ** 7, START - 10 ** 6)


def test_readers_under_ingest_never_keep_a_stale_selection():
    """More readers than cores on one key, a short switch interval, a
    writer that appends samples (the generation moves) and adds series
    (index.mutations moves): no reader errors, facts always fit their
    rows, and what is served once the writer stops is the store's own."""
    import sys
    import time
    ms = TimeSeriesMemStore()
    shard = ms.setup("prometheus", 0)
    shard.ingest(gauge_batch(64, 20, start_ms=START), offset=1)
    filt = [Equals("_ws_", "demo")]
    stop, errors = threading.Event(), []

    def reader():
        try:
            while not stop.is_set():
                lookup = shard.lookup_partitions(filt, START, START + 10 ** 7)
                sel, facts = shard.selection_facts(lookup, "gauge")
                assert facts.counts.size == sel.rows.size == \
                    lookup.part_ids.size
                assert facts.generation % 2 == 0
                assert facts.estimate(START, START + 10 ** 7) >= sel.rows.size
        except Exception as e:  # noqa: BLE001 — surfaced below
            errors.append(e)
    old = sys.getswitchinterval()
    sys.setswitchinterval(1e-5)
    try:
        threads = [threading.Thread(target=reader) for _ in range(12)]
        for t in threads:
            t.start()
        deadline = time.monotonic() + 20
        for k in range(1, 25):
            shard.ingest(gauge_batch(64 + 8 * (k // 4), 2,
                                     start_ms=START + (20 + 2 * k) * STEP),
                         offset=1 + k)
            assert time.monotonic() < deadline
        stop.set()
        for t in threads:
            t.join(20)
            assert not t.is_alive()
    finally:
        stop.set()
        sys.setswitchinterval(old)
    assert not errors, errors[:3]
    lookup = shard.lookup_partitions(filt, START, START + 10 ** 7)
    sel, facts = shard.selection_facts(lookup, "gauge")
    store = shard.stores["gauge"]
    assert lookup.part_ids.size == 64 + 8 * 6
    assert facts.generation == store.generation
    assert facts.counts.tolist() == store.counts[sel.rows].tolist()
    assert len(shard._lookup_cache) == 1


# ------------------------------------------------- (d), (e): the served path

SERIES, SAMPLES, SHARDS = 512, 360, 4
END_S = START // 1000 + SAMPLES * 10 - 100


class Rig:
    """A FiloServer on port 0, four shards of counters, interpret-mode
    kernels; every request moves its range one step (a result-cache miss,
    as the benchmark's dashboard_open sends them)."""

    def __init__(self):
        self.srv = FiloServer([DatasetConfig("prometheus", SHARDS)],
                              config=FilodbSettings(),
                              http_host="127.0.0.1", http_port=0)
        self.srv.start()
        ts = START + np.arange(SAMPLES, dtype=np.int64) * STEP
        keys = [PartKey.make("request_total", {
            "_ws_": "demo", "_ns_": f"App-{i % 10}",
            "instance": f"Instance-{i}", "dc": f"DC{i % 2}"})
            for i in range(SERIES)]
        mapper = self.srv.mappers["prometheus"]
        spread = self.srv.spreads["prometheus"]
        shard_of = np.array([mapper.ingestion_shard(
            pk.shard_key_hash(), pk.partition_hash(),
            spread.spread_for(pk.shard_key())) for pk in keys])
        vals = np.cumsum(np.random.default_rng(32).random((SERIES, SAMPLES)),
                         axis=1)
        self.shards = self.srv.memstore.shards_for("prometheus")
        for sh in self.shards:
            idx = np.flatnonzero(shard_of == sh.shard_num)
            assert idx.size, "every shard must hold series"
            sh.ingest_columns("prom-counter", [keys[i] for i in idx],
                              np.broadcast_to(ts, (idx.size, SAMPLES)),
                              {"count": vals[idx]}, offset=0)
            # sealed chunks in the resident tier: the paging check no
            # longer returns before it looks at the rows
            sh.flush_all_groups()
            assert sh.resident.num_chunks > 0
        self.base = f"http://127.0.0.1:{self.srv.http.port}"
        self.asked = 0
        self.query()                # builds the mirrors, compiles

    def get(self, path):
        with urllib.request.urlopen(self.base + path, timeout=120) as r:
            return r.read()

    def query(self, promql="sum%20by%20(_ns_)(rate(request_total[5m]))",
              steps_back=None):
        if steps_back is None:
            self.asked += 1
            steps_back = self.asked
        end = END_S - 60 * steps_back
        body = json.loads(self.get(
            f"/api/v1/query_range?query={promql}&start={end - 1800}"
            f"&end={end}&step=60"))
        assert body["status"] == "success", body
        return body

    def spans(self, trace_id):
        import time
        deadline = time.monotonic() + 10.0
        while True:
            data = json.loads(self.get(f"/admin/traces/{trace_id}"))["data"]
            if any(e["name"] == "http.request" for e in data["spans"]):
                return data["spans"]
            assert time.monotonic() < deadline, "the root never landed"
            time.sleep(0.002)

    def metrics(self):
        out = {}
        for line in self.get("/metrics").decode().splitlines():
            name, _, val = line.rpartition(" ")
            if name.startswith("leaf_selection_"):
                out[name] = float(val)
        return out


@pytest.fixture(scope="module")
def rig():
    old = os.environ.get("FILODB_TPU_FUSED_INTERPRET")
    os.environ["FILODB_TPU_FUSED_INTERPRET"] = "1"
    r = Rig()
    try:
        yield r
    finally:
        r.srv.shutdown()
        if old is None:
            del os.environ["FILODB_TPU_FUSED_INTERPRET"]
        else:
            os.environ["FILODB_TPU_FUSED_INTERPRET"] = old


@pytest.fixture
def calls(monkeypatch):
    """Counts of what a hit must not call: the index, `_estimate_scan`,
    the paging check's array half, and a fresh read of the facts."""
    n = {"index": 0, "estimate_scan": 0, "page_arrays": 0, "facts": 0}

    def counting(key, fn):
        def wrapped(*a, **kw):
            n[key] += 1
            return fn(*a, **kw)
        return wrapped
    monkeypatch.setattr(PartKeyIndex, "part_ids_from_filters", counting(
        "index", PartKeyIndex.part_ids_from_filters))
    monkeypatch.setattr(leafexec, "_estimate_scan", counting(
        "estimate_scan", leafexec._estimate_scan))
    monkeypatch.setattr(TimeSeriesShard, "_page_in_needed", counting(
        "page_arrays", TimeSeriesShard._page_in_needed))
    monkeypatch.setattr(SelectionFacts, "__init__", counting(
        "facts", SelectionFacts.__init__))
    return n


def test_a_range_one_step_on_calls_none_of_them(rig, calls):
    first = rig.query()
    assert calls == {"index": 0, "estimate_scan": 0, "page_arrays": 0,
                     "facts": 0}, "the warm-up filled every shard's entry"
    second = rig.query()
    assert calls == {"index": 0, "estimate_scan": 0, "page_arrays": 0,
                     "facts": 0}
    assert first["data"]["result"] and second["data"]["result"]
    # the control: the same leaf on an emptied memo calls the index and
    # reads the facts once a shard
    for sh in rig.shards:
        sh._lookup_cache.clear()
    rig.query()
    assert calls["index"] == SHARDS and calls["facts"] == SHARDS
    assert calls["estimate_scan"] == 0 and calls["page_arrays"] == 0


def test_a_range_before_the_data_runs_the_array_half(rig, calls):
    """The verdict is a verdict: a range that reaches below the first
    sample says "maybe", and today's array path answers."""
    end = START // 1000 + 600
    body = json.loads(rig.get(
        "/api/v1/query_range?query=sum(rate(request_total[5m]))"
        f"&start={end - 1800}&end={end}&step=60"))
    assert body["status"] == "success", body
    assert calls["page_arrays"] == SHARDS and calls["index"] == 0


PANELS = ["sum%20by%20(_ns_)(rate(request_total[5m]))",
          "sum(rate(request_total[5m]))",
          "sum%20by%20(dc)(rate(request_total[5m]))",
          "sum%20by%20(_ns_)(increase(request_total[5m]))",
          "sum(increase(request_total[5m]))",
          "sum%20by%20(_ns_,dc)(rate(request_total[5m]))"]


def test_six_panels_of_one_open_race_one_key_and_agree(rig):
    """Six threads, one selector a shard, an emptied memo: racing fills
    are allowed, every answer is the sequential one, one entry stays."""
    def forget():
        rig.srv.api.frontends["prometheus"].cache.clear()
        for sh in rig.shards:
            sh._lookup_cache.clear()
    forget()
    want = [rig.query(p, steps_back=3)["data"]["result"] for p in PANELS]
    forget()
    got, errors = [None] * 6, []
    gate = threading.Barrier(6)

    def ask(i):
        try:
            gate.wait(10)
            got[i] = rig.query(PANELS[i], steps_back=3)["data"]["result"]
        except Exception as e:  # noqa: BLE001 — surfaced below
            errors.append(e)
    threads = [threading.Thread(target=ask, args=(i,)) for i in range(6)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(120)
        assert not t.is_alive()
    assert not errors, errors
    assert got == want
    for sh in rig.shards:
        assert len(sh._lookup_cache) == 1


def test_one_lookup_and_no_estimate_a_leaf_and_the_counters_say_hit(rig):
    rig.query()
    before = rig.metrics()
    body = rig.query()
    names = [e["name"] for e in rig.spans(body["traceID"])]
    # (no estimate: the rows hold fewer samples in all than the scan cap,
    # and a leaf that reads the mirror is routed by no size; ISSUE 50)
    for span, n in (("leaf.index_lookup", SHARDS),
                    ("leaf.scan_estimate", 0),
                    ("leaf.page_check", SHARDS),
                    ("leaf.counts_copy", SHARDS)):
        assert names.count(span) == n, (span, names.count(span))
    after = rig.metrics()
    assert after["leaf_selection_hits_total"] \
        - before["leaf_selection_hits_total"] == SHARDS
    causes = {k: v for k, v in after.items() if "fills" in k}
    assert causes and causes == {k: before.get(k) for k in causes}
    assert 'leaf_selection_fills_total{cause="new"}' in causes
