"""A ragged working set stored whole rows first (ISSUE 50): the rows that
fill every slot of their scrape grid stand first, each part of the set on a
rung of the row ladder of its own, and ONE launch runs the dense body over
the first part and the ragged body over the rest.

(a) the kernel: a set of whole, late-starting, early-ending and holed rows
    answered by the split launch equals the unsplit ragged launch and the
    per-sample f64 oracle, every flavor the placed functions take; the
    layout (`pf.whole_first`) moves by rungs, so one more holed series
    compiles nothing; a set of one kind has no layout; a per-series run
    refuses one;
(b) the mirror: the fact of each row (`_MirrorSnapshot.whole`) at a full
    build and after incremental refreshes (a whole row that misses a scrape,
    one that appends a NaN), and the take in the layout;
(c) the served path (`test_promchurn_served.ChurnRig`): both counters move,
    a part of a set maps its group ids through the layout, and a request
    that finds its working sets reads nothing of the layout or the fact and
    uploads nothing.
"""
import numpy as np
import pytest

import histrig
import oracle
import test_promchurn_served as churn
import test_slot_placement as placement
from filodb_tpu.core.devicecache import DeviceMirror
from filodb_tpu.ops import pallas_fused as pf
from filodb_tpu.utils.metrics import registry

STEP, RANGE = 10_000, 300_000
TOL = churn.TOL                 # the cell's limit, `rate_rel_err`


# --------------------------------------------------------------- (a) kernel

def _rows(S, T, rng):
    """Counters on one scrape grid, NaN where a row holds no sample: seven
    rows of ten whole, the others late-starting, early-ending or holed."""
    vals = 1e6 + np.cumsum(rng.integers(1, 40, (S, T)).astype(float), axis=1)
    kind = np.arange(S) % 10
    for s in np.flatnonzero(kind >= 7):
        if kind[s] == 7:
            vals[s, :rng.integers(1, T - 40)] = np.nan       # a late start
        elif kind[s] == 8:
            vals[s, rng.integers(T - 45, T - 1):] = np.nan   # an early end
        else:
            vals[s, rng.integers(T - 60, T, 6)] = np.nan     # missed scrapes
    return vals, kind < 7


def _case(phased, S=300, T=500, seed=50):
    rng = np.random.default_rng([seed, phased])
    ts_row = np.arange(T, dtype=np.int64) * STEP
    wends = ts_row[-1] - np.arange(21, dtype=np.int64)[::-1] * 60_000
    plan = pf.build_plan(ts_row, wends, RANGE)
    assert plan.Tq == 256 < plan.Tp == 512      # a trimmed plan's block
    vals, whole = _rows(S, T, rng)
    phase = rng.integers(0, STEP, S) if phased else None
    gids = (np.arange(S) % 7).astype(np.int32)
    return ts_row, wends, plan, vals, whole, phase, gids


def _padded(plan, vals, phase, layout=None):
    """-> (PaddedValues, at): the rows rebased as the mirror rebases them,
    in the set's order, or in `layout` (`pf.whole_first`'s answer) as a take
    out of the mirror lays them."""
    vbase = np.where(np.isnan(vals), np.inf, vals).min(axis=1)
    rebased = (vals - vbase[:, None]).astype(np.float32)
    if layout is None:
        return pf.pad_values(rebased, vbase.astype(np.float32), plan,
                             phase=phase), None
    index, at, Sw = layout

    def laid(x):
        pad = (index < 0).reshape((-1,) + (1,) * (x.ndim - 1))
        return np.where(pad, 0, x[np.maximum(index, 0)])
    return pf.pad_values(laid(rebased), laid(vbase.astype(np.float32)), plan,
                         phase=None if phase is None else laid(phase),
                         split=(at, Sw)), at


def _launch(plan, values, gids, fn, op, at=None, G=7):
    groups = pf.pad_groups(gids, len(gids), G, at=at,
                           rows=values.vals_p.shape[0])
    return pf.fused_leaf_agg_batch(plan, values, [(groups, G, op)], fn,
                                   precorrected=True, interpret=True,
                                   ragged=True)[0]


def _oracle(ts_row, wends, vals, phase, gids, fn, op, G=7):
    S = len(vals)
    per = np.stack([oracle.eval_series(
        ts_row + (0 if phase is None else int(phase[s])), vals[s], wends,
        RANGE, fn) for s in range(S)])
    ok = ~np.isnan(per)
    sums, counts = np.zeros((G, len(wends))), np.zeros((G, len(wends)))
    np.add.at(sums, gids, np.where(ok, per, 0.0))
    np.add.at(counts, gids, ok)
    return sums, counts


@pytest.mark.parametrize("phased", [False, True], ids=["one-row", "phased"])
@pytest.mark.parametrize("op", ["sum", "avg", "count"])
@pytest.mark.parametrize("fn", ["rate", "increase", "sum_over_time",
                                "avg_over_time"])
def test_the_split_launch_is_the_ragged_launch_and_the_oracle(fn, op, phased):
    ts_row, wends, plan, vals, whole, phase, gids = _case(phased)
    layout = pf.whole_first(whole)
    assert layout is not None and layout[2] == 256 \
        and len(layout[0]) == 256 + 256
    ragged, _ = _padded(plan, vals, phase)
    split, at = _padded(plan, vals, phase, layout)
    assert ragged.split == 0 and ragged.at is None and split.split == 256
    sets0 = registry.counter("fused_set_rows").value
    whole0 = registry.counter("fused_whole_rows").value
    got = _launch(plan, split, gids, fn, op, at)
    assert registry.counter("fused_set_rows").value - sets0 == 512
    assert registry.counter("fused_whole_rows").value - whole0 == 256
    want = _launch(plan, ragged, gids, fn, op)
    assert registry.counter("fused_whole_rows").value - whole0 == 256
    W = len(wends)
    assert got.shape == want.shape == (7, W, 1 if op == "count" else 2)
    # the same f32 arithmetic a row: only the order of a group's sum differs
    np.testing.assert_array_equal(got[..., -1], want[..., -1])
    np.testing.assert_allclose(got[..., 0], want[..., 0], rtol=1e-6, atol=0)
    sums, counts = _oracle(ts_row, wends, vals, phase, gids, fn, op)
    np.testing.assert_array_equal(got[..., -1], counts)
    if op != "count":
        np.testing.assert_allclose(got[..., 0], sums, rtol=TOL, atol=0)


def test_a_set_of_one_kind_has_no_layout_and_runs_one_body():
    assert pf.whole_first(np.ones(40, bool)) is None
    assert pf.whole_first(np.zeros(40, bool)) is None
    assert pf.whole_first(np.zeros(0, bool)) is None
    # ... and a launch over sets none of which is split passes no `splits`:
    # the call it was
    _, _, plan, vals, _, phase, gids = _case(True, S=40)
    values, _ = _padded(plan, vals, phase)
    assert pf._splits_of((values, values)) is None
    assert pf._splits_of((values, values._replace(split=256))) == (0, 256)
    assert pf._set_parts(((values.vals_p,),), None, True) \
        == [(0, 0, 256, True)]
    assert pf._set_parts(((np.zeros((768, 8)),),), (512,), True) \
        == [(0, 0, 512, False), (0, 512, 256, True)]


def test_the_split_row_moves_by_rungs_and_one_more_series_compiles_nothing():
    ts_row, wends, plan, vals, whole, phase, gids = _case(True)
    fn, op = "rate", "sum"
    split, at = _padded(plan, vals, phase, pf.whole_first(whole))
    _launch(plan, split, gids, fn, op, at)
    before = pf.jit_cache_stats()
    # a whole series that now counts as holed (the ragged body takes either
    # kind), and one holed series more
    fewer = whole.copy()
    fewer[np.flatnonzero(whole)[0]] = False
    for grown in (fewer, np.r_[whole, False]):
        S = len(grown)
        more = np.vstack([vals, vals[-1:]])[:S] if S > len(vals) else vals
        ph = np.r_[phase, 77][:S]
        layout = pf.whole_first(grown)
        assert layout[2] == 256 and len(layout[0]) == 512
        values, at = _padded(plan, more, ph, layout)
        got = _launch(plan, values, np.r_[gids, 3][:S], fn, op, at)
        assert np.isfinite(got[..., 0]).all()
    assert pf.jit_cache_stats() == before
    # the rungs are the row ladder's, a part at a time
    index, at, Sw = pf.whole_first(np.arange(9000) % 4 > 0)
    assert (Sw, len(index)) == (pf.pad_series_count(6750),
                                pf.pad_series_count(6750)
                                + pf.pad_series_count(2250))
    assert Sw % pf._BS == 0 and len(index) % pf._BS == 0
    # either part in the set's order, the padding nobody's
    assert (np.diff(at[np.arange(9000) % 4 > 0]) == 1).all()
    assert (np.diff(at[np.arange(9000) % 4 == 0]) == 1).all()
    assert (index[at] == np.arange(9000)).all() and (index < 0).sum() \
        == len(index) - 9000


def test_a_per_series_run_refuses_a_set_stored_whole_rows_first():
    _, _, plan, vals, whole, phase, gids = _case(True)
    split, at = _padded(plan, vals, phase, pf.whole_first(whole))
    groups = pf.pad_groups(gids, len(gids), 7, at=at,
                           rows=split.vals_p.shape[0])
    with pytest.raises(ValueError, match="per-series"):
        pf.fused_leaf_agg_batch(plan, split, [(groups, 7, "max")], "rate",
                                precorrected=True, interpret=True,
                                ragged=True)


def test_a_group_column_follows_the_layout_and_a_part_leaves_rows_out():
    whole = np.arange(600) % 3 > 0
    index, at, Sw = pf.whole_first(whole)
    gids = (np.arange(600) % 11).astype(np.int32)
    col = np.asarray(pf.pad_groups(gids, 600, 11, at=at,
                                   rows=len(index)).gids_p)[:, 0]
    np.testing.assert_array_equal(col, np.where(index >= 0,
                                                gids[np.maximum(index, 0)],
                                                -1))
    # a part of the set (a range that leaves the newest series out): its
    # series where they stand among the set's, through the layout
    member = np.flatnonzero(np.arange(600) % 7 > 0)
    part = pf.pad_groups(gids[member], len(member), 11, at=at[member],
                         rows=len(index))
    col = np.asarray(part.gids_p)[:, 0]
    kept = np.zeros(600, bool)
    kept[member] = True
    np.testing.assert_array_equal(
        col, np.where((index >= 0) & kept[np.maximum(index, 0)],
                      gids[np.maximum(index, 0)], -1))
    np.testing.assert_array_equal(part.gsize,
                                  np.bincount(gids[member], minlength=11))


# --------------------------------------------------------------- (b) mirror

NAMES = placement.NAMES         # one whole row, three short, one empty


def _built(names=NAMES, shard=5001):
    store = placement._store(names)
    mirror = DeviceMirror(shard_num=shard)
    assert mirror.ensure_fresh(store)
    return store, mirror


def test_a_placed_build_keeps_the_fact_of_each_row():
    store, mirror = _built(tuple(placement.ROWS))
    snap = mirror.snapshot()
    assert snap.interval == STEP and snap.whole.dtype == bool
    np.testing.assert_array_equal(
        snap.whole, [n == "every scrape" for n in placement.ROWS])
    assert snap.placed_rows == (~snap.whole).sum()
    # equal counts are no placed snapshot and keep no fact
    twin = DeviceMirror()
    assert twin.ensure_fresh(placement._store(("every scrape",) * 3))
    assert twin.snapshot().interval == 0 and twin.snapshot().whole is None
    # a NaN SAMPLE in a row that fills every slot: not whole
    store = placement._store(("every scrape", "every scrape", "a late start"))
    store.cols["count"][1, 5] = np.nan
    holed = DeviceMirror()
    assert holed.ensure_fresh(store)
    np.testing.assert_array_equal(holed.snapshot().whole,
                                  [True, False, False])


@pytest.mark.parametrize("case", ["every row appends",
                                  "the whole row misses a scrape",
                                  "the whole row appends a NaN",
                                  "a new row mid-stream"])
def test_an_incremental_refresh_keeps_the_fact_right(case):
    store, mirror = _built(shard=5002)
    np.testing.assert_array_equal(mirror.snapshot().whole,
                                  [True, False, False, False, False])
    inc = registry.counter("device_mirror_incremental").value
    live, T = NAMES[:4], placement.T
    if case == "every row appends":
        placement._append(store, live, T, 2)
        want = [True, False, False, False, False]
    elif case == "the whole row misses a scrape":
        placement._append(store, live, T, 1, skip={"every scrape"})
        placement._append(store, live, T + 1, 2)
        want = [False] * 5
    elif case == "the whole row appends a NaN":
        placement._append(store, live, T, 1)
        store.cols["count"][0, store.counts[0] - 1] = np.nan
        want = [False] * 5
    else:
        placement._append(store, live, T, 1)
        row = np.array([store.new_row()])
        store.append_grid(row, (placement.START + 4_321
                                + (T + 1 + np.arange(2)) * STEP)[None, :],
                          {"count": np.array([[9e8, 9e8 + 5]])})
        placement._append(store, live, T + 1, 2)
        want = [True, False, False, False, False, False]
    assert mirror.ensure_fresh(store)
    assert registry.counter("device_mirror_incremental").value == inc + 1
    snap = mirror.snapshot()
    np.testing.assert_array_equal(snap.whole, want)
    fresh = DeviceMirror()
    assert fresh._refresh(store)
    np.testing.assert_array_equal(fresh.snapshot().whole, want)
    assert snap.placed_rows == fresh.snapshot().placed_rows


def test_a_take_in_the_layout_is_the_rows_whole_rows_first():
    store, mirror = _built(("every scrape", "a late start", "every scrape",
                            "interior runs", "every scrape"), shard=5003)
    snap = mirror.snapshot()
    rows = np.array([4, 3, 2, 1, 0])
    gather = mirror.gather_cached(rows, snap)
    at, Sw = gather.whole_first()
    assert Sw == pf._BS and list(at) == [0, Sw, 1, Sw + 1, 2]
    vals = np.asarray(gather.deferred("values", "count").resolve(
        whole_first=True))
    phase = np.asarray(gather.deferred("phase").resolve(whole_first=True))
    assert vals.shape == (2 * pf._BS, snap.t_used) and phase.shape \
        == (2 * pf._BS, 1)
    dev, ph = np.asarray(snap.cols["count"]), np.asarray(snap.phase_dev)
    for i, r in enumerate(rows):
        np.testing.assert_array_equal(vals[at[i]], dev[r])
        assert phase[at[i], 0] == ph[r, 0]
    pad = np.setdiff1d(np.arange(2 * pf._BS), at)
    assert not vals[pad].any() and not phase[pad].any()
    assert np.isfinite(vals[:3]).all() and np.isnan(vals[[Sw, Sw + 1]]).any()
    # the plain take beside it, as before
    plain = np.asarray(gather.deferred("values", "count").resolve(256))
    np.testing.assert_array_equal(plain[:5], dev[rows])
    # rows of one kind have no layout, and the take is the plain one
    for rows in (np.array([0, 2, 4]), np.array([1, 3])):
        g = mirror.gather_cached(rows, snap)
        assert g.whole_first() is None
        got = np.asarray(g.deferred("values", "count").resolve(
            256, whole_first=True))
        np.testing.assert_array_equal(got[:len(rows)], dev[rows])
    assert gather.deferred("values", "count").placed
    assert mirror.gather_cached(rows, snap).deferred("vbase",
                                                     "count").placed


# --------------------------------------------------------------- (c) served

@pytest.fixture(scope="module", autouse=True)
def interpret_kernels():
    with histrig.environ(FILODB_TPU_FUSED_INTERPRET="1"):
        yield


@pytest.fixture(scope="module")
def rig():
    r = churn.ChurnRig(churn.SEEDS[0])
    yield r
    r.close()
    churn._forget_compiles()


def _cached_sets(rig):
    """The padded working sets of the rig's mirrors: key -> PaddedValues."""
    from filodb_tpu.query.execbase import (_FUSED_CACHE_LOCK,
                                           _FUSED_VALS_CACHE)
    serials = {sh.stores[rig.cfg["schema"]].device_mirror.serial
               for sh in rig.srv.memstore.shards_for(rig.cfg["dataset"])}
    with _FUSED_CACHE_LOCK:
        return {k: v for k, v in _FUSED_VALS_CACHE.items()
                if k[0] in serials}


def test_every_launch_books_its_rows_and_those_the_dense_body_runs(rig):
    rig.delta_over(rig.open(0))
    rig.forget_results()
    delta = rig.delta_over(rig.open(1))
    sets = _cached_sets(rig)
    assert len(sets) == 4 and all(v.split and v.at is not None
                                  for v in sets.values())
    rows = sum(v.vals_p.shape[0] for v in sets.values())
    whole = sum(v.split for v in sets.values())
    assert delta("fused_enqueues_total") == 6
    assert delta("fused_set_rows_total") == 6 * rows
    assert delta("fused_whole_rows_total") == 6 * whole
    assert 0.5 < whole / rows < 1.0
    assert delta("leaf_ragged_fused_total") == 6 * 4
    for fam in ("leaf_fused_errors_total", "leaf_general_path_total",
                "span_leaf_pad_values_calls_total"):
        assert delta(fam) == 0, fam
    # the fact the layout came from: the mirror's own, row by row
    for sh in rig.srv.memstore.shards_for(rig.cfg["dataset"]):
        store = sh.stores[rig.cfg["schema"]]
        snap = store.device_mirror.snapshot()
        np.testing.assert_array_equal(
            snap.whole, store.counts[:store.num_series] == snap.t_used)
        assert 0 < snap.placed_rows == (~snap.whole).sum()


def test_a_part_of_a_set_maps_its_groups_through_the_layout(rig):
    """A range that ends before the newest series were born reads the set of
    ALL the selector's series, stored whole rows first, and the rows it
    leaves out stand in no group wherever the layout put them."""
    from filodb_tpu.query.execbase import (_FUSED_CACHE_LOCK,
                                           _FUSED_GROUP_CACHE)
    rig.delta_over(rig.open(0))
    rig.forget_results()
    delta = rig.delta_over(rig.open(22))        # answers: the reference's
    assert delta('leaf_selection_fills_total{cause="range"}') <= 4
    assert delta("span_leaf_pad_values_calls_total") == 0
    assert delta("fused_whole_rows_total") > 0
    sets = _cached_sets(rig)
    with _FUSED_CACHE_LOCK:
        parts = {k: v for k, v in _FUSED_GROUP_CACHE.items()
                 if k[:4] in sets and len(k) == 7}
    assert len(parts) >= 4 * 4                  # four groupings a shard
    for key, (groups, gkeys) in parts.items():
        values = sets[key[:4]]
        col = np.asarray(groups.gids_p)[:, 0]
        assert col.shape[0] == values.vals_p.shape[0]
        held = np.zeros(col.shape[0], bool)
        held[values.at] = True
        assert (col[~held] == -1).all()         # the two parts' padding
        left_out = int((col[held] == -1).sum())
        assert 0 < left_out < 30                # the newest 20 of 2,108
        assert groups.gsize.sum() == held.sum() - left_out
        # ... and they are holed rows: born after the grid's first slot
        assert (np.flatnonzero(held & (col == -1)) >= values.split).all()


class _Tripwire:
    """Stands where an array of a set's rows stood: any read raises."""

    def _read(self, *a, **k):
        raise AssertionError("a request read an array over a set's rows")
    __getitem__ = __array__ = __len__ = __iter__ = __bool__ = _read
    __le__ = __ge__ = __lt__ = __gt__ = _read
    any = all = sum = astype = nonzero = _read


def test_a_request_that_finds_its_sets_reads_no_array_over_their_rows(rig):
    """The host's work, counted: with the caches warm a request's leaves
    read nothing of a set's layout (`PaddedValues.at`), of the mirror's
    fact of each row (`_MirrorSnapshot.whole`), of the lives a lookup's
    entry holds or of its selections' counts and extents: all are swapped
    for an object that raises on any read, and the opens still answer,
    upload nothing and pad nothing."""
    from filodb_tpu.query.execbase import (_FUSED_CACHE_LOCK,
                                           _FUSED_VALS_CACHE)
    rig.delta_over(rig.open(0))
    rig.delta_over(rig.open(22))
    sets = _cached_sets(rig)
    mirrors = [sh.stores[rig.cfg["schema"]].device_mirror
               for sh in rig.srv.memstore.shards_for(rig.cfg["dataset"])]
    facts = [m.snapshot().whole for m in mirrors]
    trip = _Tripwire()
    # ... nor the lives a lookup's entry holds, nor the counts and extents
    # of a selection's facts (the parent laid a mask over the lives, four
    # passes a leaf, and estimated a scan it then routed by nothing)
    held = []
    for sh in rig.srv.memstore.shards_for(rig.cfg["dataset"]):
        for ent in sh._lookup_cache.values():
            held += [(ent, f, getattr(ent, f)) for f in ("start", "end")]
            for res in [ent.whole] + list(ent.parts.values()):
                f_ = res.selection(rig.cfg["schema"]).facts
                if f_ is not None:
                    held += [(f_, f, getattr(f_, f))
                             for f in ("counts", "first", "last")]
    assert len(held) >= 4 * (2 + 3 * 2)     # whole and a part a shard
    try:
        with _FUSED_CACHE_LOCK:
            for k, v in sets.items():
                _FUSED_VALS_CACHE[k] = v._replace(at=trip)
        for m in mirrors:
            object.__setattr__(m.snapshot(), "whole", trip)
        for obj, field, _ in held:
            setattr(obj, field, trip)
        for n in (0, 22):
            rig.forget_results()
            delta = rig.delta_over(rig.open(n))
            assert delta("fused_enqueues_total") == 6
            assert delta("fused_whole_rows_total") > 0
            for fam in ("fused_enqueue_uploads_total",
                        "span_leaf_pad_values_calls_total",
                        "span_leaf_pad_groups_calls_total",
                        "span_leaf_group_ids_calls_total",
                        "span_leaf_build_plan_calls_total",
                        "mirror_gather_takes_total",
                        "leaf_fused_errors_total",
                        "leaf_general_path_total"):
                assert delta(fam) == 0, (n, fam)
    finally:
        with _FUSED_CACHE_LOCK:
            for k, v in sets.items():
                if k in _FUSED_VALS_CACHE:
                    _FUSED_VALS_CACHE[k] = v
        for m, fact in zip(mirrors, facts):
            object.__setattr__(m.snapshot(), "whole", fact)
        for obj, field, was in held:
            setattr(obj, field, was)
    # the tripwire trips: a new grouping lays its column out by the layout
    with pytest.raises(AssertionError, match="set's rows"):
        np.asarray(trip)
